"""Crash-safe serving: the engine-loop watchdog (stall detection ->
ready flip -> prober eviction -> recovery, and the crash-only abort
bound), per-request seeds (position-deterministic sampling that
survives resume), mid-generation resume (``resume_from`` forced-prefix
admission; the router's journaled stream resume with exactly-once
delivery), and supervised replica restart with crash-loop quarantine.

Headline chaos acceptance: SIGKILL-equivalent death of the replica
serving a live stream, with the client's stream completing token-
identical to a never-killed greedy oracle — zero duplicated, zero
missing token indices."""
import json
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from elephas_tpu.fleet import (FleetRouter, ReplicaPool,
                               ReplicaSupervisor, RestartPolicy)
from elephas_tpu.models.transformer import TransformerConfig, init_params
from elephas_tpu.obs import EngineWatchdog, MetricsRegistry
from elephas_tpu.obs.events import clear_events, recent_events
from elephas_tpu.fleet.membership import ReplicaMembership
from elephas_tpu.serving_engine import DecodeEngine
from elephas_tpu.serving_http import ServingServer
from elephas_tpu.utils.faults import FaultPlan, clear_plan, install_plan

from ._greedy import greedy_reference as _ref


@pytest.fixture(scope="module")
def model():
    config = TransformerConfig(vocab_size=300, num_layers=2, num_heads=4,
                               d_model=32, d_ff=64, max_seq_len=48,
                               dtype=jnp.float32)
    params = init_params(config, jax.random.PRNGKey(0))
    return params, config


@pytest.fixture(autouse=True)
def _clean_slate():
    clear_plan()
    clear_events()
    yield
    clear_plan()


def _post(port, path, payload):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as resp:
        return json.loads(resp.read())


def _get(port, path):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=120) as resp:
        return json.loads(resp.read())


def _get_status(port, path):
    """(code, payload) for GETs that may legitimately answer non-200."""
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=120) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as err:
        return err.code, json.loads(err.read())


def _drain_engine(engine, rids, timeout=60.0):
    deadline = time.monotonic() + timeout
    while engine.pending and time.monotonic() < deadline:
        engine.step()
    return {rid: engine.result(rid) for rid in rids}


class _SlowStepEngine(DecodeEngine):
    """Paces decode so a chaos kill can land mid-stream
    deterministically."""

    def step(self):
        out = super().step()
        time.sleep(0.05)
        return out


# ================================================== watchdog (unit)
def test_watchdog_detects_stall_and_recovers():
    """Deterministic clock: beat -> healthy; beat age past the bound ->
    exactly one 'stalled' transition + engine.stalled + on_stall; the
    next beat recovers with the measured stall length."""
    t = [0.0]
    stalls, recovers = [], []
    reg = MetricsRegistry()
    wd = EngineWatchdog(stall_after_s=1.0, registry=reg,
                        on_stall=stalls.append,
                        on_recover=recovers.append,
                        clock=lambda: t[0])
    assert wd.check_once(now=5.0) is None     # no beat yet: no judgment
    t[0] = 0.0
    wd.beat()
    assert wd.check_once(now=0.5) is None
    assert wd.check_once(now=1.5) == "stalled"
    assert wd.stalled and len(stalls) == 1
    # already-stalled passes do not re-fire the transition
    assert wd.check_once(now=1.8) is None
    assert len(stalls) == 1
    evts = recent_events(event="engine.stalled")
    assert evts and evts[-1]["stall_after_s"] == 1.0
    assert evts[-1]["beat_age_s"] == pytest.approx(1.5)
    t[0] = 2.5
    wd.beat()
    assert not wd.stalled and len(recovers) == 1
    evts = recent_events(event="engine.recovered")
    # stall measured from the LAST beat (t=0) to the recovering one
    assert evts and evts[-1]["stalled_for_s"] == pytest.approx(2.5)
    status = wd.status()
    assert status["stalled"] is False
    assert status["stall_after_s"] == 1.0


def test_watchdog_aborts_past_hard_bound():
    """Crash-only discipline: past abort_after_s the injected abort_fn
    runs exactly once, after engine.stall_aborted is emitted."""
    t = [0.0]
    aborts = []
    wd = EngineWatchdog(stall_after_s=1.0, abort_after_s=3.0,
                        clock=lambda: t[0],
                        abort_fn=lambda: aborts.append(1))
    wd.beat()
    assert wd.check_once(now=1.5) == "stalled"
    assert not aborts                       # soft bound only so far
    assert wd.check_once(now=3.5) == "aborted"
    assert aborts == [1]
    wd.check_once(now=4.0)                  # never aborts twice
    assert aborts == [1]
    evts = recent_events(event="engine.stall_aborted")
    assert evts and evts[-1]["abort_after_s"] == 3.0


def test_watchdog_validation():
    with pytest.raises(ValueError, match="stall_after_s"):
        EngineWatchdog(stall_after_s=0.0)
    with pytest.raises(ValueError, match="must exceed"):
        EngineWatchdog(stall_after_s=5.0, abort_after_s=5.0)


# ===================================== watchdog (server integration)
def test_stuck_step_sheds_traffic_and_recovers(model):
    """The tentpole integration: an injected stuck step (FaultPlan
    delay on serving.step) -> engine.stalled, /ready answers 503
    {"status": "stalled"}, the membership prober evicts the replica as
    UNREADY (draining semantics — reachable, keeps its work); when the
    step completes, engine.recovered, /ready flips back, the replica
    rejoins the ring, and the stuck request still finishes correctly."""
    params, config = model
    engine = DecodeEngine(params, config, max_slots=2)
    srv = ServingServer(engine, watchdog_stall_s=0.3)
    srv.start()
    url = f"http://127.0.0.1:{srv.port}"
    mem = ReplicaMembership([url], probe_interval=0.1, evict_after=1,
                            join_after=1).start()
    try:
        deadline = time.time() + 10
        while time.time() < deadline and mem.ring_size() != 1:
            time.sleep(0.05)
        assert mem.ring_size() == 1
        stats = _get(srv.port, "/stats")
        assert stats["watchdog"]["stalled"] is False
        assert stats["watchdog"]["stall_after_s"] == 0.3

        install_plan(FaultPlan([{"site": "serving.step",
                                 "action": "delay", "delay": 2.0,
                                 "times": 1}]))
        prompt = [1, 2, 3]
        rid = _post(srv.port, "/v1/submit",
                    {"prompt": prompt, "max_new_tokens": 3})["id"]

        # the stall is detected while the step sleeps: /ready flips
        deadline = time.time() + 10
        code = payload = None
        while time.time() < deadline:
            code, payload = _get_status(srv.port, "/ready")
            if code == 503 and payload.get("status") == "stalled":
                break
            time.sleep(0.05)
        assert (code, payload) == (503, {"status": "stalled"}), payload
        evts = recent_events(event="engine.stalled")
        assert evts and evts[-1]["stall_after_s"] == 0.3
        # the prober sees the 503 and evicts as UNREADY — the replica
        # answered, so it drains instead of being declared dead
        deadline = time.time() + 10
        while time.time() < deadline and mem.ring_size() != 0:
            time.sleep(0.05)
        assert mem.ring_size() == 0
        evts = recent_events(event="fleet.replica_evicted")
        assert any(e["replica"] == url and e["reason"] == "unready"
                   for e in evts), evts

        # the delayed step completes: recovery, rejoin, correct output
        deadline = time.time() + 15
        while time.time() < deadline:
            if (not recent_events(event="engine.recovered")
                    or mem.ring_size() != 1):
                time.sleep(0.05)
                continue
            break
        assert recent_events(event="engine.recovered")
        assert mem.ring_size() == 1
        assert _get(srv.port, "/ready") == {"status": "ready"}
        deadline = time.time() + 15
        out = None
        while time.time() < deadline:
            out = _get(srv.port, f"/v1/result?id={rid}")
            if out.get("status") != "pending":
                break
            time.sleep(0.05)
        assert out["tokens"] == _ref(params, config, prompt, 3)
        stats = _get(srv.port, "/stats")
        assert stats["watchdog"]["stalled"] is False
    finally:
        mem.stop()
        srv.stop()


# ========================================= per-request seeds
def test_seeded_sampling_is_deterministic_and_resumable(model):
    """Same seed -> identical tokens across engines and batch
    compositions; the seeded sample keys off (seed, absolute position)
    alone, so a resume re-samples the identical continuation."""
    params, config = model
    prompt = [7, 11, 13]
    n = 8
    eng = DecodeEngine(params, config, max_slots=2)
    r1 = eng.submit(prompt, n, temperature=0.9, seed=123)
    out1 = _drain_engine(eng, [r1])[r1]
    # fresh engine, same seed: identical sequence
    eng2 = DecodeEngine(params, config, max_slots=2)
    r2 = eng2.submit(prompt, n, temperature=0.9, seed=123)
    r3 = eng2.submit([5, 6], 4, temperature=0.9, seed=7)  # co-batched
    outs = _drain_engine(eng2, [r2, r3])
    assert outs[r2] == out1
    # a different seed genuinely changes the draw
    eng3 = DecodeEngine(params, config, max_slots=2)
    r4 = eng3.submit(prompt, n, temperature=0.9, seed=124)
    assert _drain_engine(eng3, [r4])[r4] != out1
    # seeded resume: first 5 tokens forced, continuation identical
    eng4 = DecodeEngine(params, config, max_slots=2)
    r5 = eng4.submit(prompt + out1[:5], n - 5, temperature=0.9,
                     seed=123, resume_from=5)
    assert _drain_engine(eng4, [r5])[r5] == out1


def test_seed_rides_the_admitted_event_and_http(model):
    """The admitted flight-recorder event carries the seed, and the
    HTTP surface plumbs it end to end."""
    params, config = model
    engine = DecodeEngine(params, config, max_slots=2)
    rid = engine.submit([1, 2, 3], 4, temperature=0.8, seed=99)
    _drain_engine(engine, [rid])
    trace = engine.recorder.trace(rid)
    admitted = [e for e in trace["events"] if e["event"] == "admitted"]
    assert admitted and admitted[0]["seed"] == 99
    with ServingServer(DecodeEngine(params, config, max_slots=2)) as srv:
        a = _post(srv.port, "/v1/generate",
                  {"prompt": [1, 2, 3], "max_new_tokens": 5,
                   "temperature": 0.9, "seed": 42})
        b = _post(srv.port, "/v1/generate",
                  {"prompt": [1, 2, 3], "max_new_tokens": 5,
                   "temperature": 0.9, "seed": 42})
        assert a["tokens"] == b["tokens"]


def test_seed_and_resume_validation(model):
    params, config = model
    engine = DecodeEngine(params, config, max_slots=2)
    with pytest.raises(ValueError, match="seed"):
        engine.submit([1, 2, 3], 4, seed=-1)
    with pytest.raises(ValueError, match="seed"):
        engine.submit([1, 2, 3], 4, seed=2 ** 31)
    with pytest.raises(ValueError, match="resume_from"):
        engine.submit([1, 2, 3], 4, resume_from=3)   # no real prompt left
    with pytest.raises(ValueError, match="resume_from"):
        engine.submit([1, 2, 3], 4, resume_from=-1)


# ========================================= mid-generation resume (engine)
def test_resume_from_forced_prefix_matches_uninterrupted(model):
    """resume_from=N: the last N prompt tokens are already-emitted
    output — result() returns prefix + continuation, max_new_tokens
    buys N fewer NEW tokens, and greedy output is token-identical to
    the never-interrupted decode."""
    params, config = model
    prompt = [3, 1, 4, 1, 5]
    n = 10
    oracle = _ref(params, config, prompt, n)
    engine = DecodeEngine(params, config, max_slots=2)
    cut = 4
    rid = engine.submit(prompt + oracle[:cut], n - cut,
                        resume_from=cut)
    out = _drain_engine(engine, [rid])[rid]
    assert out == oracle
    trace = engine.recorder.trace(rid)
    assert any(e["event"] == "resumed" for e in trace["events"])


# =================================== headline chaos: kill mid-stream
@pytest.mark.parametrize("mode", ["prefix", "recompute"])
def test_stream_survives_replica_kill_token_identical(model, mode):
    """THE acceptance scenario: 3 replicas, a live greedy stream,
    SIGKILL-equivalent death of the replica serving it. The stream
    completes with zero duplicated and zero missing token indices,
    token-identical to a never-killed oracle — in prefix mode via
    forced-prefix re-admission on a sibling, in recompute mode via the
    router's index dedupe. fleet.stream_interrupted (the PR 6 gap) and
    fleet.stream_resumed are both emitted and counted."""
    params, config = model
    prompt = [2, 7, 1, 8]
    n = 16
    oracle = _ref(params, config, prompt, n)
    pool = ReplicaPool(
        lambda: _SlowStepEngine(params, config, max_slots=2), n=3).start()
    try:
        with FleetRouter(pool.urls, probe_interval=0.2, evict_after=2,
                         stream_resume=mode) as router:
            req = urllib.request.Request(
                f"http://127.0.0.1:{router.port}/v1/generate",
                data=json.dumps({"prompt": prompt, "max_new_tokens": n,
                                 "stream": True}).encode(),
                headers={"Content-Type": "application/json"})
            streamed, terminal, killed = [], None, False
            with urllib.request.urlopen(req, timeout=120) as resp:
                for raw in resp:
                    line = json.loads(raw)
                    if "status" in line:
                        terminal = line
                        continue
                    streamed.extend(line["tokens"])
                    if not killed and len(streamed) >= 4:
                        stats = _get(router.port, "/stats")
                        victims = [u for u, info in
                                   stats["replicas"].items()
                                   if info["in_flight"] > 0]
                        assert victims, stats["replicas"]
                        pool.kill(pool.urls.index(victims[0]))
                        killed = True
            assert killed, "stream finished before the kill landed"
            assert terminal == {"status": "done"}
            # exactly-once AND complete: the full oracle, no dupes,
            # no gaps, no reordering
            assert streamed == oracle
            stats = _get(router.port, "/stats")
            assert stats["streams_interrupted"] == 1
            assert stats["streams_resumed"] == 1
            assert stats["streams_journaled"] == 0   # journal popped
            evts = recent_events(event="fleet.stream_interrupted")
            assert evts and evts[-1]["tokens_streamed"] >= 4
            evts = recent_events(event="fleet.stream_resumed")
            assert evts and evts[-1]["mode"] == mode
            if mode == "prefix":
                # the sibling was told what was already emitted
                assert evts[-1]["resume_from"] >= 4
            # every stream released its in-flight hold
            assert all(info["in_flight"] == 0
                       for info in stats["replicas"].values())
    finally:
        pool.stop()


# ======================================= supervised replica restart
def test_supervisor_restarts_dead_replica_then_quarantines(model):
    """First death: the supervisor respawns the replica after backoff
    and swaps the router's candidate set old URL -> new URL (ring back
    to full strength). Repeated deaths inside the crash-loop window:
    quarantine — fleet.replica_crashlooping, no further restarts — and
    the fleet keeps serving on the survivors with zero failed client
    requests."""
    params, config = model
    pool = ReplicaPool(
        lambda: DecodeEngine(params, config, max_slots=2), n=3).start()
    with FleetRouter(pool.urls, probe_interval=0.15,
                     evict_after=2) as router:
        sup = ReplicaSupervisor(
            pool, router,
            policy=RestartPolicy(backoff_base_s=0.2,
                                 crashloop_window_s=60.0,
                                 crashloop_threshold=3)).start()
        old = pool.urls[1]
        pool.kill(1)
        # a client request trips the dead replica -> mark_down fires
        # the supervisor via the eviction feed; meanwhile every
        # request keeps succeeding
        for _ in range(8):
            out = _post(router.port, "/v1/generate",
                        {"prompt": [1, 2, 3, 4], "max_new_tokens": 3})
            assert out["tokens"] == _ref(params, config, [1, 2, 3, 4], 3)
        deadline = time.time() + 20
        while time.time() < deadline:
            if pool.alive(1) and router.stats()["ring_size"] == 3:
                break
            time.sleep(0.1)
        assert pool.alive(1), "replica 1 never restarted"
        new = pool.urls[1]
        assert new != old
        stats = router.stats()
        assert stats["ring_size"] == 3
        assert old not in stats["replicas"] and new in stats["replicas"]
        evts = recent_events(event="fleet.replica_restarted")
        assert any(e["replica"] == new and e["replaced"] == old
                   for e in evts), evts
        assert sup.pending_restarts() == 0

        # two more deaths inside the window -> threshold 3 -> quarantine
        for k in range(2):
            pool.kill(1)
            deadline = time.time() + 20
            while time.time() < deadline:
                if k == 1 and 1 in sup.quarantined():
                    break
                if k == 0 and pool.alive(1):
                    break
                try:   # poke the router so mark_down fires promptly
                    _post(router.port, "/v1/generate",
                          {"prompt": [5, 6, 7], "max_new_tokens": 2})
                except Exception:  # noqa: BLE001 — transient 5xx is
                    pass           # the prober's business, not ours
                time.sleep(0.1)
        assert sup.quarantined() == [1], sup.status()
        assert not pool.alive(1)        # left dead: crash-only
        evts = recent_events(event="fleet.replica_crashlooping")
        assert evts and evts[-1]["deaths_in_window"] == 3
        assert evts[-1]["action"] == "quarantined"
        # the fleet serves on, zero failed client requests
        for _ in range(6):
            out = _post(router.port, "/v1/generate",
                        {"prompt": [9, 8, 7], "max_new_tokens": 3})
            assert out["tokens"] == _ref(params, config, [9, 8, 7], 3)
        assert router.stats()["ring_size"] == 2
        sup.stop()
    pool.stop()
