"""Fleet SLO plane: TTFT/inter-token latency decomposition, the
engine-loop continuous profiler, and burn-rate alerting.

Covers the whole chain: per-request TTFT/inter-token histograms on the
engines (observed off host state — a flight-recorder eviction never
costs a sample), the disaggregated submit-stamp passthrough that puts
prefill-tier time inside TTFT, loop-utilization phase accounting with
jit compiles tracked separately, the SLO tracker's fast/slow burn-rate
state machine, per-replica ``GET /slo`` lifted by the membership
prober onto the router's fleet aggregation with worst-replica
attribution, plus the satellites: event-sink rotation, histogram
exemplars, and ``/metrics`` self-observation.
"""
import json
import os
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elephas_tpu.models.transformer import TransformerConfig, init_params
from elephas_tpu.obs import (EventLog, LoopProfiler, MetricsRegistry,
                             SLOObjective, SLOTracker, clear_events,
                             recent_events)
from elephas_tpu.obs.context import new_root, use_context
from elephas_tpu.obs.events import FlightRecorder
from elephas_tpu.obs.profiler import PHASES, SPANS
from elephas_tpu.serving_engine import DecodeEngine


def _tiny_config(max_seq_len=32):
    return TransformerConfig(vocab_size=97, num_layers=2, num_heads=2,
                             d_model=16, d_ff=32,
                             max_seq_len=max_seq_len,
                             dtype=jnp.float32)


@pytest.fixture(scope="module")
def tiny():
    c = _tiny_config()
    return c, init_params(c, jax.random.PRNGKey(0))


def _drain(eng):
    while eng.pending:
        eng.step()


def _get(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read())


def _post(url, body, timeout=30):
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


# ------------------------------------------------- latency decomposition

def test_ttft_and_inter_token_histograms(tiny):
    c, params = tiny
    eng = DecodeEngine(params, c, max_slots=2)
    n, new = 3, 6
    rids = [eng.submit(list(range(1, 5)), new) for _ in range(n)]
    _drain(eng)
    for r in rids:
        assert len(eng.result(r)) == new
    reg = eng.registry
    ttft = reg.get("serving_ttft_seconds").labels()
    itl = reg.get("serving_inter_token_seconds").labels()
    # one TTFT sample per request; one inter-token gap per token after
    # the first
    assert ttft.count == n
    assert itl.count == n * (new - 1)
    stats = eng.stats
    assert stats["ttft_p50_s"] > 0
    assert stats["inter_token_p50_s"] >= 0
    # the terminal flight-recorder event carries the per-request value
    trace = eng.request_trace(rids[0])
    term = [e for e in trace["events"] if e["event"] == "finished"]
    assert term and term[0]["ttft_s"] > 0


def test_ttft_survives_flight_recorder_eviction(tiny):
    """The eviction edge: a request whose timeline fell off the bounded
    trace ring (the 257th concurrent rid evicts the 1st) must still
    stamp correct TTFT/inter-token samples — counters never depend on
    the diagnostic ring."""
    c, params = tiny
    eng = DecodeEngine(params, c, max_slots=4)
    n = eng.recorder.max_requests + 1          # 257 concurrent rids
    rids = [eng.submit([1, 2, 3], 2, admit=False) for _ in range(n)]
    # the first rid's timeline was evicted when the 257th started,
    # while it was still queued
    assert eng.request_trace(rids[0]) is None
    assert eng.request_trace(rids[-1]) is not None
    _drain(eng)
    assert all(len(eng.result(r)) == 2 for r in rids)
    ttft = eng.registry.get("serving_ttft_seconds").labels()
    itl = eng.registry.get("serving_inter_token_seconds").labels()
    assert ttft.count == n                     # every request sampled
    assert itl.count == n                      # 2 tokens -> 1 gap each


def test_submitted_at_passthrough_puts_prefill_tier_inside_ttft(tiny):
    """The disaggregated wiring: submit_prefilled(submitted_at=...)
    measures TTFT from the FRONT END's submit stamp, while queue-wait
    keeps measuring the decode stage only."""
    c, params = tiny
    exporter = DecodeEngine(params, c, max_slots=1)
    prompt = list(range(1, 9))
    out = exporter.export_prefill(prompt)
    eng = DecodeEngine(params, c, max_slots=1)
    lag = 5.0                                  # synthetic upstream time
    rid = eng.submit_prefilled(prompt, 3, out["kv_blocks"],
                               out["first_token"],
                               submitted_at=time.monotonic() - lag)
    _drain(eng)
    assert len(eng.result(rid)) == 3
    ttft = eng.registry.get("serving_ttft_seconds").labels()
    assert ttft.count == 1
    assert ttft.sum >= lag                     # upstream time included
    # the decode-stage queue wait did NOT absorb the upstream lag
    wait = eng.registry.get("serving_queue_wait_seconds").labels(
        tier="colocated")
    assert wait.sum < lag / 2


# ------------------------------------------------------- loop profiler

def test_loop_profiler_phases_and_jit_tracking(tiny):
    c, params = tiny
    eng = DecodeEngine(params, c, max_slots=2)
    assert eng.profiler is not None            # on by default
    rids = [eng.submit(list(range(1, 6)), 8) for _ in range(3)]
    _drain(eng)
    eng.profiler.tick()                        # close the last iteration
    for r in rids:
        assert eng.result(r) is not None
    util = eng.profiler.utilization()
    assert util["decode"] > 0 and util["prefill"] > 0
    assert 0 <= sum(v for k, v in util.items()) <= 1.0 + 1e-6
    # the first step/prefill compiles went through the JAX monitoring
    # listener into the dedicated jit series
    assert eng.registry.get("serving_jit_compiles_total").value > 0
    assert eng.registry.get("serving_jit_compile_seconds").sum > 0
    snap = eng.stats["loop"]
    assert snap["iterations"] > 0 and snap["jit_compiles"] > 0
    # gauges render per phase
    text = eng.registry.render()
    assert 'serving_loop_utilization{phase="decode"}' in text


def test_loop_profiler_exclusive_nesting_and_off_switch(tiny):
    reg = MetricsRegistry()
    clk = [0.0]
    prof = LoopProfiler(reg, window_s=100.0, track_jit=False,
                        clock=lambda: clk[0])
    prof.tick()
    with prof.section("admit"):
        clk[0] += 1.0
        with prof.section("prefill"):
            clk[0] += 3.0
        clk[0] += 2.0                          # a compile's wall time,
        prof.record_compile(2.0)               # excluded from admit
        clk[0] += 1.0
    clk[0] += 4.0                              # unclaimed -> idle
    prof.tick()
    util = prof.utilization()
    wall = 11.0                                # 1+3+2+1+4 clock total
    assert util["admit"] == pytest.approx(2.0 / wall)
    assert util["prefill"] == pytest.approx(3.0 / wall)
    assert util["jit"] == pytest.approx(2.0 / wall)
    assert util["idle"] == pytest.approx(4.0 / wall)
    # profiler=False: no gauges, no sections, stats carries no block
    c, params = tiny
    eng = DecodeEngine(params, c, max_slots=1, profiler=False)
    eng.run([[1, 2, 3]], 2)
    assert eng.profiler is None
    assert eng.registry.get("serving_loop_utilization") is None
    assert "loop" not in eng.stats


def _fake_profiler(window_s=100.0):
    reg = MetricsRegistry()
    clk = [0.0]
    prof = LoopProfiler(reg, window_s=window_s, track_jit=False,
                        clock=lambda: clk[0])
    return reg, clk, prof


def _phase_seconds(reg, name="serving_loop_phase_seconds_total"):
    return {key[0]: child.value
            for key, child in reg.get(name).series().items()}


def test_phase_seconds_counters_are_the_sections_exclusive_times():
    reg, clk, prof = _fake_profiler()
    assert set(_phase_seconds(reg)) == set(PHASES)      # all from the start
    assert all(v == 0 for v in _phase_seconds(reg).values())
    prof.tick()
    before = _phase_seconds(reg)
    with prof.section("elephas.server.lock_wait"):
        clk[0] += 0.25
    prof.tick()                                # iteration 1: 0.25 s
    with prof.section("elephas.loop.step"):    # parent only
        clk[0] += 0.01                         # its own: no phase
        with prof.section("elephas.loop.admit"):
            clk[0] += 0.02
            with prof.section("elephas.loop.admit.request"):
                clk[0] += 0.03                 # parent only: to admit
                with prof.section("elephas.loop.admit.claim"):
                    clk[0] += 0.04
                with prof.section("elephas.loop.prefill"):
                    clk[0] += 0.05
                    with prof.section("elephas.loop.prefill.chunks"):
                        clk[0] += 0.06         # parent only: to prefill
        with prof.section("elephas.loop.decode.dispatch"):
            clk[0] += 0.07
        with prof.section("elephas.loop.decode.wait"):
            clk[0] += 0.08
        with prof.section("elephas.loop.emit"):
            clk[0] += 0.09
    with prof.section("elephas.server.deliver"):
        clk[0] += 0.10
    with prof.section("elephas.server.housekeeping"):
        clk[0] += 0.11
    with prof.section("elephas.server.yield"):
        clk[0] += 0.12
    clk[0] += 0.13                             # no section: idle
    prof.tick()                                # iteration 2
    delta = {ph: v - before[ph]
             for ph, v in _phase_seconds(reg).items()}
    want = {"lock_wait": 0.25, "admit": 0.02 + 0.03 + 0.04,
            "prefill": 0.05 + 0.06, "decode_dispatch": 0.07,
            "decode": 0.08, "emit": 0.09, "deliver": 0.10 + 0.11,
            "yield": 0.12, "idle": 0.01 + 0.13}
    for ph in PHASES:
        assert delta[ph] == pytest.approx(want.get(ph, 0.0)), ph
    # the phases and idle sum to the wall
    assert sum(delta.values()) == pytest.approx(clk[0])
    assert reg.get("serving_loop_iterations_total").value == 2
    # the gauges read the same accumulation
    util = prof.utilization()
    assert util["decode"] == pytest.approx(0.08 / clk[0])
    assert util["decode_dispatch"] == pytest.approx(0.07 / clk[0])
    assert sum(util.values()) == pytest.approx(1.0)
    # a bare phase name still is a section (span elephas.loop.<phase>)
    with prof.section("decode"):
        clk[0] += 1.0
    prof.tick()
    assert _phase_seconds(reg)["decode"] == pytest.approx(1.08)


def test_slow_iteration_lands_once_under_its_dominant_phase():
    reg, clk, prof = _fake_profiler()
    clear_events()
    prof.tick()
    with prof.section("elephas.loop.decode.wait"):
        clk[0] += 0.9                          # 0.9 s: not slow
    prof.tick()
    with prof.section("elephas.loop.emit"):
        clk[0] += 0.3
    with prof.section("elephas.server.lock_wait"):
        clk[0] += 0.8
    clk[0] += 0.1
    prof.tick()                                # 1.2 s, most in lock_wait
    with prof.section("elephas.loop.emit"):
        clk[0] += 0.2
    prof.tick()
    slow = _phase_seconds(reg, "serving_loop_slow_iterations_total")
    slow_s = _phase_seconds(reg,
                            "serving_loop_slow_iteration_seconds_total")
    assert set(slow) == set(slow_s) == set(PHASES)
    assert slow.pop("lock_wait") == 1
    assert slow_s.pop("lock_wait") == pytest.approx(1.2)
    assert not any(slow.values()) and not any(slow_s.values())
    events = [e for e in recent_events()
              if e["event"] == "engine.slow_iteration"]
    assert len(events) == 1
    assert events[0]["phase"] == "lock_wait"
    assert events[0]["wall_s"] == pytest.approx(1.2)
    assert events[0]["phases"] == {"emit": pytest.approx(0.3),
                                   "lock_wait": pytest.approx(0.8),
                                   "idle": pytest.approx(0.1)}
    # an iteration nothing claimed is slow under idle
    clk[0] += 5.0
    prof.tick()
    assert _phase_seconds(
        reg, "serving_loop_slow_iteration_seconds_total")[
            "idle"] == pytest.approx(5.0)


def test_compiles_and_collections_leave_the_section_they_interrupt():
    reg, clk, prof = _fake_profiler()
    prof.tick()
    with prof.section("elephas.loop.prefill"):
        clk[0] += 1.0
        with prof.section("elephas.loop.prefill.chunks"):
            clk[0] += 2.0                      # a compile's wall time
            prof.record_compile(2.0)
            clk[0] += 0.5                      # a collection's
            prof.record_gc(0.5)
            clk[0] += 0.25
    prof.tick()
    got = _phase_seconds(reg)
    assert got["jit"] == pytest.approx(2.0)
    assert got["gc"] == pytest.approx(0.5)
    assert got["prefill"] == pytest.approx(1.25)
    assert got["idle"] == pytest.approx(0.0)
    # the collector's own hook: a real collection inside a section, on
    # the thread the profiler is bound to, goes to the gc phase
    import gc

    reg2 = MetricsRegistry()
    real = LoopProfiler(reg2, track_jit=False)
    real.tick()
    junk = [[i] for i in range(20000)]
    for item in junk:
        item.append(junk)                      # cycles to find
    with real.section("elephas.loop.emit"):
        del junk, item
        gc.collect()
    real.tick()
    got = _phase_seconds(reg2)
    assert got["gc"] > 0
    assert got["emit"] >= 0
    # a collection may strike anywhere, also while tick() folds the
    # closed iteration: with the collector on a hair trigger the loop
    # must go on (the hook claims into the new iteration's split)
    threshold = gc.get_threshold()
    gc.set_threshold(1)
    try:
        for _ in range(500):
            real.tick()
            with real.section("elephas.loop.emit"):
                junk = [[] for _ in range(8)]
    finally:
        gc.set_threshold(*threshold)
    assert reg2.get("serving_loop_iterations_total").value >= 500


def _host_lines(trace_dir):
    """``[[(name, start, end, stats)]]``: the ``elephas.`` events of
    each host thread line of the newest trace under ``trace_dir``."""
    import glob

    from jax.profiler import ProfileData

    path = max(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                      "*", "*.xplane.pb")),
               key=os.path.getmtime)
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = [(ev.name, int(ev.start_ns),
                       int(ev.start_ns) + int(ev.duration_ns),
                       dict(ev.stats))
                      for ev in line.events
                      if ev.name.startswith("elephas.")]
            if events:
                lines.append(events)
    return lines


def test_loop_spans_land_in_the_profilers_trace(tiny, tmp_path):
    """The sections are host spans of ``jax.profiler``'s own trace: one
    thread line holds the engine loop, the step's parts inside
    ``elephas.loop.step`` and the server's outside it, and an
    admission carries its request id."""
    from elephas_tpu import ServingServer

    c, params = tiny
    eng = DecodeEngine(params, c, max_slots=2, paged=(16, 8),
                       prefill_chunk=8)
    eng.warmup(prompt_lengths=[12])
    srv = ServingServer(eng).start()
    url = f"http://127.0.0.1:{srv.port}/v1/generate"
    try:
        _post(url, {"prompt": list(range(1, 13)), "max_new_tokens": 2})
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            out = _post(url, {"prompt": list(range(2, 14)),
                              "max_new_tokens": 5})
        finally:
            jax.profiler.stop_trace()
    finally:
        srv.stop()
    assert out["status"] == "done" and len(out["tokens"]) == 5
    lines = [ln for ln in _host_lines(str(tmp_path))
             if any(name == "elephas.loop.step" for name, *_ in ln)]
    assert len(lines) == 1                     # one engine-loop thread
    events = lines[0]
    assert {name for name, *_ in events} <= set(SPANS)
    steps = [(s, e) for name, s, e, _ in events
             if name == "elephas.loop.step"]
    assert len(steps) >= 4

    def inside_a_step(start, end):
        return any(s <= start and end <= e for s, e in steps)

    by_name = {}
    for name, start, end, stats in events:
        by_name.setdefault(name, []).append((start, end, stats))
    for name in ("elephas.loop.decode.dispatch", "elephas.loop.decode.wait",
                 "elephas.loop.emit", "elephas.loop.admit",
                 "elephas.loop.admit.request", "elephas.loop.admit.claim",
                 "elephas.loop.prefill", "elephas.loop.prefill.row_init",
                 "elephas.loop.prefill.chunks",
                 "elephas.loop.prefill.install",
                 "elephas.loop.prefill.first_token"):
        assert by_name.get(name), name
        assert all(inside_a_step(s, e) for s, e, _ in by_name[name]), name
    for name in ("elephas.server.lock_wait", "elephas.server.deliver",
                 "elephas.server.housekeeping", "elephas.server.yield"):
        assert by_name.get(name), name
        assert not any(inside_a_step(s, e) for s, e, _ in by_name[name])
    (r_start, r_end, stats), = by_name["elephas.loop.admit.request"]
    assert stats == {"rid": 1, "prompt_tokens": 12, "prefix_tokens": 0}
    (p_start, p_end, _), = by_name["elephas.loop.prefill"]
    assert r_start <= p_start and p_end <= r_end
    for name in ("row_init", "chunks", "install", "first_token"):
        (s, e, _), = by_name[f"elephas.loop.prefill.{name}"]
        assert p_start <= s and e <= p_end, name
    # no session: a section writes nothing and the loop goes on
    assert eng.registry.get("serving_loop_iterations_total").value > 0


# ----------------------------------------------------- SLO / burn rates

def _fake_clock():
    clk = [0.0]
    return clk, (lambda: clk[0])


def test_slo_tracker_fires_and_recovers_with_events():
    clear_events()
    reg = MetricsRegistry()
    good = reg.counter("serving_requests_finished_total", "g")
    shed = reg.counter("serving_requests_shed_total", "s")
    clk, clock = _fake_clock()
    tr = SLOTracker([SLOObjective.availability(target=0.9)], reg,
                    fast_window_s=10, slow_window_s=50,
                    burn_threshold=2.0, clock=clock, name="r1")
    good.inc(10)
    snap = tr.evaluate()
    assert snap["objectives"]["availability"]["state"] == "ok"
    clk[0] += 5
    shed.inc(10)                               # 50% bad, budget 10%
    snap = tr.evaluate()
    obj = snap["objectives"]["availability"]
    assert obj["state"] == "firing" and obj["burn_fast"] >= 2.0
    assert tr.firing() == ["availability"]
    # steady firing does NOT re-emit
    clk[0] += 1
    tr.evaluate()
    fired = [e for e in recent_events("slo.burn_rate_exceeded")
             if e["source"] == "r1"]
    assert len(fired) == 1
    assert fired[0]["trace_id"] is not None    # under trace context
    assert fired[0]["objective"] == "availability"
    # clean traffic flushes the fast window -> recovery, once
    clk[0] += 20
    good.inc(200)
    tr.evaluate()
    clk[0] += 11
    good.inc(200)
    snap = tr.evaluate()
    assert snap["objectives"]["availability"]["state"] == "ok"
    recovered = [e for e in recent_events("slo.recovered")
                 if e["source"] == "r1"]
    assert len(recovered) == 1
    # the derivation is also scraped
    text = reg.render()
    assert 'slo_burn_rate{objective="availability",window="fast"}' in text
    assert reg.get("slo_alerts_total").labels(
        objective="availability").value == 1


def test_histogram_count_le_rounds_bound_up():
    from elephas_tpu.obs.metrics import Histogram

    h = Histogram(buckets=(0.05, 0.1, 0.25))
    for v in (0.04, 0.07, 0.2, 0.9):
        h.observe(v)
    assert h.count_le(0.05) == (1, 4)
    # off-boundary bound rounds UP to the covering bucket — rounding
    # down would silently tighten a latency objective
    assert h.count_le(0.08) == (2, 4)
    assert h.count_le(0.1) == (2, 4)
    # above the top finite bucket: all finite buckets, never +Inf
    assert h.count_le(0.5) == (3, 4)


def test_slo_latency_objective_reads_histogram_buckets():
    reg = MetricsRegistry()
    h = reg.histogram("serving_ttft_seconds", "t")
    clk, clock = _fake_clock()
    tr = SLOTracker(
        [SLOObjective.latency("ttft_p95", "serving_ttft_seconds",
                              bound_s=0.05, target=0.5)],
        reg, fast_window_s=10, slow_window_s=20, burn_threshold=1.5,
        clock=clock, name="x")
    for _ in range(10):
        h.observe(0.01)
    tr.evaluate()
    clk[0] += 5
    for _ in range(10):
        h.observe(0.4)                         # all over the bound
    snap = tr.evaluate()
    obj = snap["objectives"]["ttft_p95"]
    assert obj["state"] == "firing"
    assert obj["bound_s"] == 0.05 and obj["kind"] == "latency"


def test_canary_slo_gate_regresses_on_firing_alert():
    from elephas_tpu.weightsync.canary import CanaryController

    class FakeSub:
        def __init__(self):
            self.auto = True
            self.registry = MetricsRegistry()
            self.engine = type("E", (), {"registry": self.registry})()

    class FakeTracker:
        def evaluate(self):
            return {}

        def firing(self):
            return ["ttft_p95"]

    sub = FakeSub()
    ctl = CanaryController([sub], bake_s=0.0, min_requests=0,
                           registry=sub.registry, slo=FakeTracker())
    verdict, detail = ctl._bake([ctl._read(sub.engine)], version=1)
    assert verdict == "regressed"
    assert detail["reason"] == "slo_burn_rate"
    assert detail["slo_firing"] == ["ttft_p95"]


def test_autoscaler_treats_firing_slo_as_up_pressure():
    from elephas_tpu.fleet.autoscaler import FleetAutoscaler, TierPolicy

    class FakeTier:
        name = "decode"
        policy = TierPolicy(min_replicas=1, max_replicas=4, up_after=2,
                            down_after=3)

        def __init__(self):
            self.n = 1
            self.scaled = []

        def count(self):
            return self.n

        def draining(self):
            return 0

        def signals(self):
            # zero backlog, zero sheds — only the SLO plane says help
            return {"queue_depth": 0, "queued_tokens": 0,
                    "in_flight": 0, "requests_shed": 0,
                    "requests_finished": 10, "depth": 0.0,
                    "wait_p99_s": 0.0, "slo_firing": 1}

        def scale_up(self):
            self.n += 1
            self.scaled.append("up")
            return f"replica-{self.n}"

        def scale_down(self):
            return None

    tier = FakeTier()
    auto = FleetAutoscaler([tier], registry=MetricsRegistry())
    assert auto.poll_once() == {"decode": None}      # hysteresis
    assert auto.poll_once() == {"decode": "up"}      # up_after=2
    assert tier.scaled == ["up"]
    events = [e for e in recent_events("fleet.scaled_up")]
    assert any("slo_burn" in e.get("reason", "") for e in events)


# ----------------------------------------------------------- satellites

def test_event_log_sink_rotation(tmp_path):
    path = str(tmp_path / "events.jsonl")
    log = EventLog(sink_path=path, sink_max_bytes=400)
    for i in range(80):
        log.emit("tick", i=i)
    log.close()
    assert os.path.getsize(path) <= 400
    assert os.path.getsize(path + ".1") <= 400
    # the newest event survived in the live file, the rollover holds
    # the generation before it — nothing silently vanished mid-stream
    live = [json.loads(x) for x in open(path).read().splitlines()]
    rolled = [json.loads(x)
              for x in open(path + ".1").read().splitlines()]
    assert live[-1]["i"] == 79
    assert rolled[-1]["i"] == live[0]["i"] - 1


def test_histogram_exemplars_render_and_snapshot():
    reg = MetricsRegistry()
    h = reg.histogram("ttft_seconds", "t", exemplars=True)
    with use_context(new_root()) as ctx:
        h.observe(0.04)
    h.observe(0.07)                            # no context: no exemplar
    snap = h.labels()._snapshot()
    ex = snap["exemplars"]
    assert list(ex.values())[0]["trace_id"] == ctx.trace_id
    # rendering is opt-in: classic exposition stays 0.0.4-clean
    assert "# {trace_id=" not in reg.render()
    text = reg.render(exemplars=True)
    assert f'# {{trace_id="{ctx.trace_id}"}}' in text


def test_metrics_scrape_self_observation(tiny):
    from elephas_tpu.serving_http import ServingServer

    c, params = tiny
    eng = DecodeEngine(params, c, max_slots=1)
    server = ServingServer(eng, port=0)
    with server:
        base = f"http://127.0.0.1:{server.port}"
        urllib.request.urlopen(base + "/metrics", timeout=10).read()
        text = urllib.request.urlopen(base + "/metrics",
                                      timeout=10).read().decode()
    # the FIRST scrape's cost is visible on the second (one late by
    # construction)
    assert 'obs_scrape_duration_seconds_bucket{site="serving"' in text
    assert 'obs_scrape_size_bytes_bucket{site="serving"' in text


# --------------------------------------------------- fleet /slo end-to-end

class _SlowStep:
    """Engine proxy injecting a latency regression: each step() stalls
    before dispatch while ``delay_s`` is set (the autoscaler bench's
    wrapper pattern), inflating admission — and therefore TTFT — on
    one replica only."""

    def __init__(self, engine):
        self.engine = engine
        self.delay_s = 0.0

    def step(self):
        if self.delay_s:
            time.sleep(self.delay_s)
        return self.engine.step()

    def __getattr__(self, name):
        return getattr(self.engine, name)


def _mk_replica(params, c, name):
    from elephas_tpu.serving_http import ServingServer

    eng = DecodeEngine(params, c, max_slots=2)
    eng.warmup(prompt_lengths=[4])
    slow = _SlowStep(eng)
    tracker = SLOTracker(
        [SLOObjective.latency("ttft_p95", "serving_ttft_seconds",
                              bound_s=0.05, target=0.5)],
        eng.registry, fast_window_s=0.6, slow_window_s=1.2,
        burn_threshold=1.5, eval_interval_s=0.05, name=name)
    server = ServingServer(slow, port=0).start()
    server.slo = tracker
    return eng, slow, tracker, server


@pytest.mark.slow
def test_router_slo_aggregation_fires_and_recovers_end_to_end(tiny):
    """The acceptance scenario: an injected latency regression on ONE
    replica drives its TTFT-p95 burn rate over threshold, fires exactly
    one trace-stamped ``slo.burn_rate_exceeded``, shows up on the
    router's ``GET /slo`` with worst-replica attribution, and recovers
    after the fault clears."""
    from elephas_tpu.fleet.router import FleetRouter

    clear_events()
    c, params = tiny
    a = _mk_replica(params, c, "replica-a")
    b = _mk_replica(params, c, "replica-b")
    router = FleetRouter(
        [f"http://127.0.0.1:{a[3].port}",
         f"http://127.0.0.1:{b[3].port}"],
        policy="round_robin", probe_interval=0.1, hedge=False).start()
    url_b = f"http://127.0.0.1:{b[3].port}"
    base = f"http://127.0.0.1:{router.port}"
    try:
        def traffic(n=6):
            for _ in range(n):
                _post(base + "/v1/generate",
                      {"prompt": [1, 2, 3, 4], "max_new_tokens": 2})

        traffic()                              # healthy baseline
        # regress replica B only: 80ms per step ≫ the 50ms TTFT bound,
        # while a 2-token request still finishes in ~0.25s — several
        # bad samples per fast window, so the min-evidence gate has
        # data to fire on
        b[1].delay_s = 0.08
        deadline = time.monotonic() + 20
        summary = None
        while time.monotonic() < deadline:
            traffic(4)
            summary = _get(base + "/slo")
            obj = summary["objectives"].get("ttft_p95")
            if obj and obj["state"] == "firing":
                break
            time.sleep(0.1)
        obj = summary["objectives"]["ttft_p95"]
        assert obj["state"] == "firing", summary
        assert obj["firing_replicas"] == [url_b]
        assert obj["worst_replica"] == url_b
        # exactly one alert, trace-stamped, from replica B
        fired = [e for e in recent_events("slo.burn_rate_exceeded")
                 if e["source"] == "replica-b"]
        assert len(fired) == 1 and fired[0]["trace_id"] is not None
        assert not [e for e in recent_events("slo.burn_rate_exceeded")
                    if e["source"] == "replica-a"]
        # per-replica surfaces agree with the aggregation
        assert _get(url_b + "/slo")["firing"] == ["ttft_p95"]
        assert _get(url_b + "/stats")["slo"]["firing"] == ["ttft_p95"]
        # fault clears -> fresh fast traffic flushes the window
        b[1].delay_s = 0.0
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            traffic(4)
            summary = _get(base + "/slo")
            if summary["objectives"]["ttft_p95"]["state"] == "ok":
                break
            time.sleep(0.1)
        assert summary["objectives"]["ttft_p95"]["state"] == "ok", summary
        recovered = [e for e in recent_events("slo.recovered")
                     if e["source"] == "replica-b"]
        assert len(recovered) == 1
    finally:
        router.stop()
        a[3].stop()
        b[3].stop()
