"""The harness takes additions as data: a directory holding one new
configuration file, one traffic file, one metric with a reader of its
own and the matching entries is run by name, with no edit to any file
the benchmark already has."""
import json

from ._util import REPO, last_line, run_cell

READER = '''"""Reader ``count_sent``: requests the generator sent in the window."""


def read(evidence, timed_only: bool = True):
    return float(sum(1 for s in evidence.samples
                     if s["sent"] is not None
                     and (s["timed"] or not timed_only)))
'''


def test_a_new_cell_metric_and_reader_run_with_no_edit(tmp_path):
    with open(REPO / "BENCHMARK.json") as fh:
        bench = json.load(fh)
    with open(REPO / "chipbench" / "configs" /
              "mistral-7b-l16-serve.json") as fh:
        config = json.load(fh)
    with open(REPO / "chipbench" / "traffic" / "chat-steady.json") as fh:
        mix = json.load(fh)
    extra = tmp_path / "extra"
    for sub in ("configs", "traffic", "metrics", "readers"):
        (extra / sub).mkdir(parents=True)
    config["num_hidden_layers"] = 8
    (extra / "configs" / "other-l8.json").write_text(json.dumps(config))
    mix["rehearse"]["arrivals"]["rate_per_s"] = 6.0
    (extra / "traffic" / "other-mix.json").write_text(json.dumps(mix))
    (extra / "metrics" / "extra.sent.json").write_text(json.dumps(
        {"reader": "count_sent", "args": {"timed_only": True}}))
    (extra / "readers" / "count_sent.py").write_text(READER)
    cell = {"name": "other-cell", "config": "other-l8",
            "traffic": "other-mix", "chips": 1, "why": "a test"}
    added = {
        "command": bench["command"], "paths": ["extra"],
        "run_seconds": bench["run_seconds"],
        "configs": [{"name": "other-l8", "source": config["source"],
                     "file": "extra/configs/other-l8.json",
                     "reduced": ["num_hidden_layers"], "why": "a test"}],
        "workloads": [cell],
        # the metrics the benchmark already has are found in its own
        # directory; only the new one lives in the added directory
        "end_to_end": [m for m in bench["end_to_end"]
                       if m["name"] in ("tpot_p90_ms", "setup_s")],
        "per_layer": [
            {"name": "extra.sent", "unit": "count", "better": "higher",
             "source": "program_counter", "layer": "load generator",
             "moves": "tpot_p90_ms"},
            {"name": "loadgen.late_p90_ms", "unit": "ms", "better": "lower",
             "source": "host_clock", "layer": "load generator",
             "moves": "tpot_p90_ms"}]}
    for m in added["end_to_end"]:
        m.pop("workloads", None)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(added))

    proc = run_cell("--benchmark-json", str(tmp_path / "BENCHMARK.json"),
                    "--workload", "other-cell", "--seed", "4", "--seconds",
                    "3", "--trace", "1", "--rehearse")
    line = last_line(proc)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"extra.sent", "loadgen.late_p90_ms"}
    assert line["metrics"]["extra.sent"] == {
        "value": float(line["attempted"]), "unit": "count"}
