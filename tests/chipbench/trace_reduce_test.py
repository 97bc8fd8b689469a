"""The reduction from a trace to numbers: on events written by hand,
where every number can be worked out, and on a small trace recorded on
the chip (``chipbench/testdata``)."""
import pytest

from chipbench import trace_reduce as tr

from ._util import REPO

MS = 1_000_000


def hand_trace():
    """Two devices, window 0..100 ms (the marks). Device 0: program A
    runs 10..40 (ops: fusion 10..30, all-reduce 25..40), program B runs
    60..80 (one op, copy 60..80). Device 1: the same shifted by 0."""
    ops = [("fusion.1", 10 * MS, 30 * MS), ("all-reduce.2", 25 * MS, 40 * MS),
           ("copy.3", 60 * MS, 80 * MS)]
    modules = [("jit_a(123)", 10 * MS, 40 * MS), ("jit_b(9)", 60 * MS, 80 * MS)]
    host = [(tr.BEGIN_MARK, -1 * MS, 0), (tr.END_MARK, 100 * MS, 101 * MS),
            ("chipbench.epoch", 41 * MS, 59 * MS)]
    devices = {0: {"ops": list(ops), "modules": list(modules)},
               1: {"ops": list(ops), "modules": list(modules)}}
    return tr.Trace(devices, host)


def test_union_merges_overlaps_and_touching_intervals():
    assert tr.union_ns([(5, 7), (0, 3), (2, 4), (7, 9)]) == [[0, 4], [5, 9]]


def test_busy_idle_and_window_by_hand():
    trace = hand_trace()
    assert trace.window == (0, 100 * MS)
    assert trace.window_s == pytest.approx(0.1)
    # busy: 10..40 and 60..80 = 50 ms of 100
    assert trace.busy_s() == pytest.approx(0.05)
    assert trace.idle_share() == pytest.approx(0.5)


def test_time_by_program_by_hand():
    programs = hand_trace().program_time()
    assert programs["jit_a"] == {"count": 1.0, "seconds": pytest.approx(0.03)}
    assert programs["jit_b"] == {"count": 1.0, "seconds": pytest.approx(0.02)}


def test_collectives_and_their_exposed_part_by_hand():
    coll = hand_trace().collectives()
    # all-reduce 25..40 = 15 ms; 30..40 has nothing beside it = 10 ms
    assert coll["seconds"] == pytest.approx(0.015)
    assert coll["exposed_seconds"] == pytest.approx(0.010)


def test_breakdown_by_hand():
    out = hand_trace().breakdown()
    assert out["device_ops"] == [["fusion.1", pytest.approx(0.02)],
                                 ["copy.3", pytest.approx(0.02)],
                                 ["all-reduce.2", pytest.approx(0.015)]]
    gaps = dict(out["idle_gaps"])
    # 0..10 before a; 40..60 before b, under the harness's epoch span;
    # 80..100 before the end of the window
    assert gaps == {"before jit_a": pytest.approx(0.01),
                    "before chipbench.epoch > jit_b": pytest.approx(0.02),
                    "before end of window": pytest.approx(0.02)}
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10


def nested_trace():
    """One device, window 0..100 ms. A ``conditional`` runs 10..30 and
    holds a fusion 12..20 and a copy 20..28 (events of the same line); a
    fusion of the same kind runs alone 40..45. The program's spans: a
    step 0..60 holding an admission 31..39 holding ``row_init`` 33..38,
    and a dispatch 46..60 on a second thread's list order."""
    ops = [("%conditional.1 = bf16[32,128]{1,0} conditional(%p)",
            10 * MS, 30 * MS),
           ("%fusion.7 = bf16[32,128]{1,0} fusion(%a), kind=kLoop",
            12 * MS, 20 * MS),
           ("%copy.3 = bf16[64]{0} copy(%b)", 20 * MS, 28 * MS),
           ("%fusion.9 = bf16[32,128]{1,0} fusion(%a), kind=kLoop",
            40 * MS, 45 * MS)]
    modules = [("jit_step(1)", 10 * MS, 30 * MS),
               ("jit_row(2)", 40 * MS, 45 * MS)]
    host = [(tr.BEGIN_MARK, -1 * MS, 0), (tr.END_MARK, 100 * MS, 101 * MS),
            ("elephas.loop.decode.dispatch", 46 * MS, 60 * MS),
            ("elephas.loop.prefill.row_init", 33 * MS, 38 * MS),
            ("elephas.loop.step", 0, 60 * MS),
            ("elephas.loop.admit.request", 31 * MS, 39 * MS)]
    return tr.Trace({0: {"ops": ops, "modules": modules}}, host)


def test_a_conditional_and_the_operations_inside_it_count_once():
    trace = nested_trace()
    ops = dict(trace.top_ops())
    # the conditional's own time is 20 - 8 - 8 = 4 ms; the two fusions
    # add up under one kind
    assert ops == {"conditional bf16[32,128]": pytest.approx(0.004),
                   "fusion bf16[32,128]": pytest.approx(0.013),
                   "copy bf16[64]": pytest.approx(0.008)}
    assert sum(ops.values()) == pytest.approx(trace.busy_s())


@pytest.mark.parametrize("ops, want", [
    ([("a", 0, 10)], [["a", 10]]),
    ([("a", 0, 10), ("b", 2, 4), ("c", 4, 9)], [["a", 3], ["b", 2],
                                                 ["c", 5]]),
    # two levels: c inside b inside a
    ([("a", 0, 10), ("b", 1, 9), ("c", 2, 3)], [["a", 2], ["b", 7],
                                                 ["c", 1]]),
    # clipped to the window 5..20: a keeps 5..10, b is outside
    ([("a", 0, 10), ("b", 1, 4), ("d", 12, 30)], [["a", 5], ["d", 8]])])
def test_own_time_of_nested_operations(ops, want):
    lo, hi = (5, 20) if len(ops) == 3 and ops[2][0] == "d" else (0, 100)
    assert tr.own_ns(ops, lo, hi) == want


def test_idle_gaps_are_named_by_the_innermost_program_span():
    gaps = dict(nested_trace().idle_gaps())
    assert gaps == {
        # 0..10: under the step span alone
        "before elephas.loop.step > jit_step": pytest.approx(0.010),
        # 30..40: its middle (35) lies in row_init, inside the admission
        "before elephas.loop.prefill.row_init > jit_row":
            pytest.approx(0.010),
        # 45..100: its middle (72.5) is under no span
        "before end of window": pytest.approx(0.055)}


def test_a_trace_with_no_marks_spans_its_device_events():
    trace = hand_trace()
    bare = tr.Trace(trace.devices, [])
    assert bare.window == (10 * MS, 80 * MS)
    assert bare.idle_share() == pytest.approx(20 / 70)


def test_an_empty_trace_reads_nothing():
    empty = tr.Trace({}, [])
    assert empty.window is None and empty.idle_share() is None
    assert empty.busy_s() == 0.0 and empty.program_time() == {}
    assert empty.breakdown() == {"device_ops": [], "idle_gaps": []}


# ------------------------------------------------ a trace from the chip
RECORDED = REPO / "chipbench" / "testdata" / "tiny-v5e.xplane.pb"


@pytest.fixture(scope="module")
def recorded():
    """Recorded on one v5e chip (PR 24): three executions each of
    ``jit_alpha`` (tanh(x @ x), 1024 x 1024 bf16) and ``jit_beta`` (a
    reduction), between the harness's marks, with a host span around
    each call. The numbers below are read off the raw events with
    ``jax.profiler.ProfileData`` by hand."""
    return tr.load(str(RECORDED))


def test_recorded_trace_planes_and_window(recorded):
    assert sorted(recorded.devices) == [0]
    dev = recorded.devices[0]
    assert (len(dev["modules"]), len(dev["ops"]), len(dev["async"])) \
        == (6, 12, 3)
    # the window runs between the two marks on the host's clock, and
    # every device event lies inside it: one clock
    begin = next(e for n, _, e in recorded.host if n == tr.BEGIN_MARK)
    end = next(s for n, s, _ in recorded.host if n == tr.END_MARK)
    assert recorded.window == (begin, end) == (40597935, 95188739)
    assert all(begin <= s and e <= end for _, s, e in dev["ops"])


def test_recorded_trace_busy_idle_and_programs(recorded):
    # 12 operations, none overlapping: busy is the sum of their durations
    dev = recorded.devices[0]
    by_hand = sum(e - s for _, s, e in dev["ops"])
    assert by_hand == 61974
    assert recorded.busy_s() == pytest.approx(61974e-9)
    assert recorded.idle_share() == pytest.approx(1 - 61974 / 54590804)
    programs = recorded.program_time()
    assert programs["jit_alpha"]["count"] == 3
    assert programs["jit_alpha"]["seconds"] == pytest.approx(47357e-9)
    assert programs["jit_beta"] == {"count": 3.0,
                                    "seconds": pytest.approx(14646e-9)}
    assert recorded.collectives() == {"seconds": 0.0,
                                      "exposed_seconds": 0.0}


def test_recorded_trace_breakdown(recorded):
    out = recorded.breakdown()
    assert out["device_ops"][0] == [
        "fusion:convolution_tanh bf16[1024,1024]", pytest.approx(37785e-9)]
    assert [name for name, _ in out["device_ops"]] == [
        "fusion:convolution_tanh bf16[1024,1024]",
        "fusion:add_reduce bf16[]", "copy-done bf16[1024,1024]",
        "copy-start bf16[1024,1024]"]
    causes = {name for name, _ in out["idle_gaps"]}
    assert {"before jit_alpha", "before jit_beta",
            "before end of window"} <= causes
    assert sum(s for _, s in out["idle_gaps"]) == pytest.approx(
        recorded.window_s - recorded.busy_s())


@pytest.mark.parametrize("text, kind", [
    ("%copy.278 = bf16[32,8,256,16,128]{4,3,2,1,0:T(8,128)(2,1)} "
     "copy(bf16[32,8,256,16,128]{4,3,1,2,0:T(8,128)(2,1)} %bitcast.42)",
     "copy bf16[32,8,256,16,128]"),
    ("%fusion.12 = bf16[8192,8,16,128]{3,2,1,0} fusion(a), kind=kLoop",
     "fusion bf16[8192,8,16,128]"),
    ("%all-reduce-start.5 = (f32[4096,4096]{1,0}, f32[4096,4096]{1,0}) "
     "all-reduce-start(%x)", "all-reduce-start f32[4096,4096]"),
    ("not HLO text", "not HLO text")])
def test_operations_are_named_by_kind_and_shape(text, kind):
    assert tr.op_kind(text) == kind
    assert bool(tr.COLLECTIVE.match(text)) == text.startswith("%all-")
