"""Reader ``trace_host_gaps``: the attribution of idle gaps to host spans
on gaps and spans written by hand, to the nanosecond; the reader on
evidence without a device plane and on the small trace recorded on the
chip (which holds no ``elephas.`` span); and the metrics this reader and
the program's new counters feed, in the CPU rehearsal."""
import json
import shutil
from types import SimpleNamespace

import pytest

from chipbench import trace_reduce
from chipbench.readers import trace_host_gaps as hg

from ._util import REPO, last_line, run_cell

STEP = "elephas.loop.step"
ADMIT = f"{STEP}/elephas.loop.admit"
REQUEST = f"{ADMIT}/elephas.loop.admit.request"


def hand_spans():
    """One engine-loop thread: lock wait, a step that admits one request
    (claim, prefill) and decodes, then the server's hand-off."""
    return [("elephas.server.lock_wait", 0, 10),
            (STEP, 10, 100),
            ("elephas.loop.admit", 12, 50),
            ("elephas.loop.admit.request", 15, 48),
            ("elephas.loop.admit.claim", 16, 19),
            ("elephas.loop.prefill", 20, 45),
            ("elephas.loop.decode.dispatch", 55, 70),
            ("elephas.loop.decode.wait", 70, 95),
            ("elephas.server.deliver", 100, 110)]


def test_every_nanosecond_goes_to_the_innermost_span():
    gaps = [(5, 18), (40, 60), (105, 130)]
    charged = hg.attribute(gaps, hand_spans())
    assert charged == {
        "elephas.server.lock_wait": [5, 1],            # 5..10
        STEP: [7, 1],                                  # 10..12, 50..55
        ADMIT: [5, 1],                                 # 12..15, 48..50
        REQUEST: [4, 1],                               # 15..16, 45..48
        f"{REQUEST}/elephas.loop.admit.claim": [2, 1],             # 16..18
        f"{REQUEST}/elephas.loop.prefill": [5, 1],                 # 40..45
        f"{STEP}/elephas.loop.decode.dispatch": [5, 1],            # 55..60
        "elephas.server.deliver": [5, 1],              # 105..110
        hg.UNATTRIBUTED: [20, 1]}                      # 110..130
    assert sum(ns for ns, _ in charged.values()) == sum(
        e - s for s, e in gaps)


def test_a_gap_under_no_span_and_spans_under_no_gap():
    spans = [(STEP, 100, 200), ("elephas.loop.emit", 150, 160)]
    assert hg.attribute([(0, 50)], spans) == {hg.UNATTRIBUTED: [50, 1]}
    assert hg.attribute([], spans) == {}
    assert hg.attribute([(0, 50), (60, 90)], []) == {
        hg.UNATTRIBUTED: [80, 2]}
    # a gap that covers a whole span and more on both sides
    assert hg.attribute([(90, 210)], spans) == {
        STEP: [90, 1], f"{STEP}/elephas.loop.emit": [10, 1],
        hg.UNATTRIBUTED: [20, 1]}


def test_spans_of_one_name_count_once_each():
    spans = [(STEP, 0, 10), (STEP, 20, 30), (STEP, 40, 50)]
    charged = hg.attribute([(5, 25), (45, 46)], spans)
    assert charged == {STEP: [11, 3], hg.UNATTRIBUTED: [10, 1]}


def hand_evidence(spans, monkeypatch):
    """A window 0..200 with the device busy 20..40 and 60..195; two
    executions of the decode step."""
    ops = [("fusion.1", 20, 40), ("fusion.2", 60, 195)]
    modules = [("jit__step_paged(1)", 20, 40),
               ("jit__step_paged(1)", 60, 195)]
    trace = trace_reduce.Trace(
        {0: {"ops": ops, "modules": modules}},
        [(trace_reduce.BEGIN_MARK, -1, 0), (trace_reduce.END_MARK, 200, 201)])
    monkeypatch.setattr(hg, "loop_spans", lambda path: spans)
    return SimpleNamespace(trace=trace, trace_dir="unused")


def test_metrics_by_hand(monkeypatch, capsys):
    ev = hand_evidence(hand_spans(), monkeypatch)
    # idle: 0..20, 40..60, 195..200 = 45 ns
    server = hg.read(ev, match=r"^elephas\.server\.", per="decode_step")
    engine = hg.read(ev, match=r"^elephas\.loop\.(?!.*admit\.request)",
                     per="decode_step")
    admission = hg.read(ev, match=r"elephas\.loop\.admit\.request",
                        per="admission")
    share = hg.read(ev, mode="unattributed_share")
    # server: lock_wait 0..10; engine: 10..15, 48..60; admission:
    # 15..20, 40..48; unattributed: 195..200
    assert server == pytest.approx(10 / 1e6 / 2)
    assert engine == pytest.approx(17 / 1e6 / 2)
    assert admission == pytest.approx(13 / 1e6 / 1)
    assert share == pytest.approx(100.0 * 5 / 45)
    # the parts add up to the idle time the idle share is made of
    assert (server + engine) * 2 + admission * 1 == pytest.approx(
        (45 - 5) / 1e6)
    assert ev.trace.idle_share() == pytest.approx(45 / 200)
    # the table is logged once, however many metrics read it
    out = capsys.readouterr().out
    assert out.count("[host_gaps") == 1
    assert "elephas.server.lock_wait 0.000000s x1" in out
    with pytest.raises(ValueError):
        hg.read(ev, match="x", per="fortnight")


def test_no_device_plane_reads_nothing():
    ev = SimpleNamespace(trace=None, trace_dir=None)
    assert hg.read(ev, match="elephas", per="decode_step") is None
    assert hg.read(ev, mode="unattributed_share") is None


def test_a_program_without_the_spans_is_all_unattributed(tmp_path):
    """The recorded chip trace predates the spans: no engine-loop line,
    so the per-span metrics read nothing and all idle time is
    unattributed (what the parent commit reads)."""
    run_dir = tmp_path / "plugins" / "profile" / "run"
    run_dir.mkdir(parents=True)
    shutil.copy(REPO / "chipbench" / "testdata" / "tiny-v5e.xplane.pb",
                run_dir / "tiny.xplane.pb")
    assert hg.loop_spans(str(run_dir / "tiny.xplane.pb")) == []
    trace = trace_reduce.load(str(run_dir / "tiny.xplane.pb"))
    ev = SimpleNamespace(trace=trace, trace_dir=str(tmp_path))
    assert hg.read(ev, mode="unattributed_share") == pytest.approx(100.0)
    assert hg.read(ev, match=r"^elephas\.server\.",
                   per="decode_step") is None
    assert hg.idle_gaps(trace) and sum(
        e - s for s, e in hg.idle_gaps(trace)) == pytest.approx(
            trace.idle_share() * trace.window_s * 1e9, rel=1e-9)


def added_metrics(cell):
    with open(REPO / "BENCHMARK.json") as fh:
        per_layer = json.load(fh)["per_layer"]
    return {m["name"]: m for m in per_layer
            if cell in m["workloads"]
            and m["name"].split(".")[0] in ("gap", "loop")
            or m["name"] == "compile.in_window.engine"
            and cell in m["workloads"]}


@pytest.mark.parametrize("cell", ["mistral7b-chat-steady",
                                  "mistral7b-chat-overload"])
def test_the_added_metrics_in_the_rehearsal(cell):
    """On the CPU the device-trace metrics are absent and the program's
    counters are read: no compile and no stalled second in the window."""
    added = added_metrics(cell)
    assert len(added) == (6 if cell.endswith("steady") else 5)
    proc = run_cell("--workload", cell, "--seed", str(2**31 + 25),
                    "--seconds", "3", "--trace", "1", "--rehearse")
    line = last_line(proc)
    assert line["correct"] is True and line["failed"] == 0
    got = line["metrics"]
    for name, entry in added.items():
        if entry["source"] == "device_trace":
            assert name not in got
        else:
            assert got[name] == {"value": 0.0, "unit": entry["unit"]}, (
                name, got.get(name), proc.stdout[-2000:])
