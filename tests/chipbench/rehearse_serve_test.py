"""The serving cells end to end under ``--rehearse``: toy widths on the
CPU, through the same driver, load generator, readers and checks as on
the chip. No number of a rehearsal is a device metric."""
import json

import pytest

from ._util import REPO, json_lines, last_line, run_broken, run_cell


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


@pytest.mark.parametrize("cell", cells("serve"))
def test_a_token_altered_where_it_is_produced_is_not_correct(cell):
    """The rest of a run over a timed path broken underneath: the
    engine's programs emit the neighbour of every token; the request
    checks pass, the comparison of the served tokens with the plain
    reference does not, and the line says which number and its limit."""
    proc = run_broken("token_altered", "--workload", cell, "--seed",
                      str(2**31 + 12), "--seconds", "3", "--trace", "0")
    line = last_line(proc)
    assert line["correct"] is False and line["failed"] == 0
    checks = line["checks"]
    assert list(line)[-1] == "checks"
    assert checks["failed_requests"]["ok"] and checks["step_max_dlogit"]["ok"]
    worst = checks["token_worst_below_best"]
    assert not worst["ok"] and worst["value"] > 10 * worst["limit"]
    tail = proc.stderr.strip().splitlines()[-len(checks) - 1:]
    assert tail[-1] == "correct: False"
    assert any(t.startswith("check token_worst_below_best:")
               and t.endswith("NOT OK") for t in tail)


def test_every_number_compared_stands_beside_its_limit():
    proc = run_cell("--workload", cells("serve")[0], "--seed",
                    str(2**31 + 13), "--seconds", "3", "--trace", "0",
                    "--rehearse")
    line = last_line(proc)
    assert list(line)[-1] == "checks" and line["correct"] is True
    assert set(line["checks"]) == {"step_max_dlogit",
                                   "token_worst_below_best",
                                   "failed_requests"}
    for c in line["checks"].values():
        assert c["ok"] is True and c["value"] <= c["limit"]
    tail = proc.stderr.strip().splitlines()[-4:]
    assert [t.split(":")[0] for t in tail] == [
        "check step_max_dlogit", "check token_worst_below_best",
        "check failed_requests", "correct"]
    # the comparisons run after the window: none of them is set-up
    out = proc.stdout
    assert out.index("server started") < out.index("paged step vs plain")


def test_without_rehearse_the_cpu_is_refused():
    proc = run_cell("--workload", cells("serve")[0], "--seed", "1",
                    "--seconds", "1", "--trace", "0", timeout=120)
    assert proc.returncode != 0
    assert not json_lines(proc.stdout)
    assert "TPU" in proc.stderr
