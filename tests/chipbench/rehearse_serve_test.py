"""The serving cells end to end under ``--rehearse``: toy widths on the
CPU, through the same driver, load generator, readers and checks as on
the chip. No number of a rehearsal is a device metric."""
import json

import pytest

from ._util import REPO, json_lines, last_line, run_cell


def cells(driver):
    with open(REPO / "BENCHMARK.json") as fh:
        bench = json.load(fh)
    out = []
    for w in bench["workloads"]:
        with open(REPO / "chipbench" / "traffic" /
                  f"{w['traffic']}.json") as fh:
            if json.load(fh)["driver"] == driver:
                out.append(w["name"])
    return out


@pytest.mark.parametrize("cell", cells("serve"))
@pytest.mark.parametrize("trace", [0, 1])
def test_serving_cell_rehearses(cell, trace):
    proc = run_cell("--workload", cell, "--seed", str(2**31 + 11),
                    "--seconds", "3", "--trace", str(trace), "--rehearse")
    line = last_line(proc)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    with open(REPO / "BENCHMARK.json") as fh:
        bench = json.load(fh)
    group = "per_layer" if trace else "end_to_end"
    allowed = {m["name"] for m in bench[group]
               if "workloads" not in m or cell in m["workloads"]}
    assert set(line["metrics"]) <= allowed
    if trace:
        assert {"busy_s", "window_s"} <= set(line["device"])
        # nothing read from a device trace may appear from a CPU run
        traced = {m["name"] for m in bench["per_layer"]
                  if m["source"] == "device_trace"}
        assert not traced & set(line["metrics"])
    else:
        assert set(line["metrics"]) == allowed
        assert all(m["value"] > 0 for m in line["metrics"].values())
    assert "compiles inside the window: 0" in proc.stdout


def test_without_rehearse_the_cpu_is_refused():
    proc = run_cell("--workload", cells("serve")[0], "--seed", "1",
                    "--seconds", "1", "--trace", "0", timeout=120)
    assert proc.returncode != 0
    assert not json_lines(proc.stdout)
    assert "TPU" in proc.stderr
