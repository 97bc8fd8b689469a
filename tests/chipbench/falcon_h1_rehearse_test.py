"""The cell ``falcon-h1-chat-saturated`` end to end under ``--rehearse``:
toy widths on the CPU through the same driver, load generator, readers
and comparisons as on the chip, and the same run over a timed path whose
every token is altered. No number of a rehearsal is a device metric."""
import json

import pytest

from ._util import REPO, last_line, run_broken, run_cell

CELL = "falcon-h1-chat-saturated"
CHECKS = {"step_max_dlogit", "step_rms_dlogit", "token_worst_below_best",
          "failed_requests"}


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses(trace):
    proc = run_cell("--workload", CELL, "--seed", str(2**31 + 34),
                    "--seconds", "3", "--trace", str(trace), "--rehearse")
    line = last_line(proc)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    assert set(line["checks"]) == CHECKS
    with open(REPO / "BENCHMARK.json") as fh:
        bench = json.load(fh)
    if trace:
        # what the program counts appears; nothing read from a device
        # trace may appear from a CPU run
        counters = {m["name"] for m in bench["per_layer"]
                    if CELL in m.get("workloads", ())
                    and m["source"] == "program_counter"}
        assert counters == set(line["metrics"])
        assert line["metrics"]["compile.in_window.h1"]["value"] == 0
        assert line["metrics"]["loop.stall_s.overload"]["value"] == 0
    else:
        assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    # the step comparison crossed a chunk boundary
    assert "'chunks': 2" in proc.stdout
    assert "compiles inside the window: 0" in proc.stdout
    out = proc.stdout
    assert out.index("server started") < out.index("paged step vs plain")


def test_a_token_altered_where_it_is_produced_is_not_correct():
    """The engine's programs emit the neighbour of every token: the
    request checks and the step comparison (which keeps the true head)
    pass, the comparison of the served tokens with the plain reference
    does not, and the line says which number and its limit."""
    proc = run_broken("token_altered", "--workload", CELL, "--seed",
                      str(2**31 + 35), "--seconds", "3", "--trace", "0")
    line = last_line(proc)
    assert line["correct"] is False and line["failed"] == 0
    checks = line["checks"]
    assert list(line)[-1] == "checks" and set(checks) == CHECKS
    assert checks["failed_requests"]["ok"] and checks["step_max_dlogit"]["ok"]
    assert checks["step_rms_dlogit"]["ok"]
    worst = checks["token_worst_below_best"]
    assert not worst["ok"] and worst["value"] > 10 * worst["limit"]
    tail = proc.stderr.strip().splitlines()[-len(checks) - 1:]
    assert tail[-1] == "correct: False"
    assert any(t.startswith("check token_worst_below_best:")
               and t.endswith("NOT OK") for t in tail)
