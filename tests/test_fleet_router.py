"""Replicated serving fleet: hash-ring stability, cache-aware routing
beating round-robin on prefix-cache hits, kill -> probe eviction ->
re-route with zero failed client requests, pool-saturated 429 with a
backoff hint, trace-id propagation through the proxy, and graceful
drain of one replica while siblings serve."""
import json
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elephas_tpu.fleet import FleetRouter, HashRing, ReplicaPool
from elephas_tpu.models.transformer import TransformerConfig, init_params
from elephas_tpu.obs.events import recent_events
from elephas_tpu.serving_engine import DecodeEngine
from elephas_tpu.utils.faults import FaultPlan, clear_plan, install_plan

from ._greedy import greedy_reference as _ref


@pytest.fixture(scope="module")
def model():
    config = TransformerConfig(vocab_size=300, num_layers=2, num_heads=4,
                               d_model=32, d_ff=64, max_seq_len=48,
                               dtype=jnp.float32)
    params = init_params(config, jax.random.PRNGKey(0))
    return params, config


def _post(port, path, payload, headers=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **(headers or {})})
    with urllib.request.urlopen(req, timeout=120) as resp:
        return json.loads(resp.read())


def _get(port, path):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=120) as resp:
        return json.loads(resp.read())


def _http_error(fn):
    with pytest.raises(urllib.error.HTTPError) as exc:
        fn()
    return exc.value.code, json.loads(exc.value.read())


# ------------------------------------------------------------- hash ring
def test_hash_ring_stability_under_join_and_evict():
    """A membership change moves only ~1/N of the key space (the whole
    point of consistent over modulo hashing), removal is the exact
    inverse of addition, and ownership stays reasonably balanced."""
    ring = HashRing(["r0", "r1", "r2"])
    keys = [f"key-{i}".encode() for i in range(2000)]
    before = {k: ring.lookup(k) for k in keys}

    counts = {n: 0 for n in ring.nodes}
    for owner in before.values():
        counts[owner] += 1
    # 64 vnodes keep each node's share near 1/3 — no node may own
    # almost nothing or almost everything
    for node, n in counts.items():
        assert 0.1 < n / len(keys) < 0.6, (node, counts)

    ring.add("r3")
    after_join = {k: ring.lookup(k) for k in keys}
    moved = sum(before[k] != after_join[k] for k in keys) / len(keys)
    # ideal is 1/4; far under 1/2, and every moved key moved TO r3
    assert 0.05 < moved < 0.45, moved
    assert all(after_join[k] == "r3" for k in keys
               if before[k] != after_join[k])

    ring.remove("r3")
    assert {k: ring.lookup(k) for k in keys} == before

    ring.remove("r1")
    after_evict = {k: ring.lookup(k) for k in keys}
    moved = sum(before[k] != after_evict[k] for k in keys) / len(keys)
    assert 0.05 < moved < 0.6, moved
    # only r1's keys moved; everyone else's placement is undisturbed
    assert all(before[k] == "r1" for k in keys
               if after_evict[k] != before[k])


# ------------------------------------------------- cache-aware routing
def test_consistent_hash_beats_round_robin_on_prefix_hits(model):
    """The acceptance property: over a 3-replica pool with lazy
    per-replica prefix registration, consistent-hash routing
    concentrates each prompt-prefix group on one replica (one cold
    miss per group fleet-wide), while round-robin pays the miss on
    every replica a group touches — a strictly higher aggregate
    prefix-cache hit rate for the hash policy."""
    params, config = model
    rng = np.random.default_rng(7)
    groups = [[int(t) for t in rng.integers(0, 300, 6)] for _ in range(5)]
    prompts = [groups[i % len(groups)]
               + [int(t) for t in rng.integers(0, 300, 3)]
               for i in range(30)]

    def run(policy):
        pool = ReplicaPool(
            lambda: DecodeEngine(params, config, max_slots=2), n=3,
            auto_prefix_tokens=6).start()
        try:
            with FleetRouter(pool.urls, policy=policy, prefix_tokens=6,
                             probe_interval=0.5,
                             spill_threshold=None) as router:
                for p in prompts:
                    out = _post(router.port, "/v1/generate",
                                {"prompt": p, "max_new_tokens": 3})
                    assert out["tokens"] == _ref(params, config, p, 3)
                # a cold registration IS a prefix-cache miss: that
                # head's KV state was not resident on the replica the
                # request landed on (see _AutoPrefixEngine.misses)
                misses = sum(e.misses for e in pool.engines)
                reused = sum(
                    int(_get(srv.port, "/stats")
                        .get("prefix_tokens_reused", 0))
                    for srv in pool.servers)
                stats = _get(router.port, "/stats")
            return misses, reused, stats
        finally:
            pool.stop()

    rr_miss, rr_reused, _ = run("round_robin")
    ch_miss, ch_reused, ch_stats = run("prefix_hash")
    n = len(prompts)
    # hash: each prefix group pays ONE cold miss fleet-wide; round-robin
    # pays one per (group, replica) pair it touches
    assert ch_miss == len(groups), (ch_miss, len(groups))
    assert rr_miss > len(groups), rr_miss
    ch_rate, rr_rate = 1 - ch_miss / n, 1 - rr_miss / n
    assert ch_rate > rr_rate, (ch_rate, rr_rate)
    assert reused_sanity_ok(ch_reused, rr_reused)
    # same-prefix requests landed on one replica: every routed request
    # was a "hash" placement (spill disabled above)
    for info in ch_stats["replicas"].values():
        assert set(info["routes"]) <= {"hash"}


def reused_sanity_ok(ch_reused: int, rr_reused: int) -> bool:
    """Both policies DO reuse registered prefixes once warm — the
    difference the miss counts capture is how often each replica had
    to warm up from cold."""
    return ch_reused > 0 and rr_reused > 0


# -------------------------------------------- kill -> evict -> re-route
def _wait_for(cond, what, timeout=60.0):
    """Poll ``cond`` until it returns something true (returned); the
    tests below wait on what they assert, not on the clock: six test
    workers on one box stretch every interval."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        out = cond()
        if out:
            return out
        time.sleep(0.02)
    raise AssertionError(f"timed out after {timeout}s waiting for {what}")


def test_replica_kill_evicts_and_reroutes_with_no_failed_requests(model):
    """Killing one replica mid-load, with a decode step of its engine
    in flight: the router evicts it (connect errors and/or the /ready
    probe) and every client request still succeeds with the solo
    decode's tokens — re-routing costs recompute, never a failed
    response. A dead replica refuses connections at once, so the probe
    timeout can be generous: a live replica that answers late on a
    loaded box must not be taken for a dead one."""
    params, config = model
    rng = np.random.default_rng(11)
    pool = ReplicaPool(
        lambda: DecodeEngine(params, config, max_slots=2), n=3).start()
    try:
        with FleetRouter(pool.urls, probe_interval=0.2, evict_after=2,
                         probe_timeout=30.0) as router:
            def stats():
                return _get(router.port, "/stats")

            _wait_for(lambda: stats()["ring_size"] == 3,
                      "the three replicas to join the ring")
            prompts = [[int(t) for t in rng.integers(0, 300, 5)]
                       for _ in range(4)]
            refs = [_ref(params, config, p, 4) for p in prompts]
            failures, done = [], threading.Event()
            served = [0, 0, 0]

            def load(worker):
                i = 0
                while not done.is_set():
                    p = prompts[(worker + i) % len(prompts)]
                    try:
                        out = _post(router.port, "/v1/generate",
                                    {"prompt": p, "max_new_tokens": 4})
                        if out["tokens"] != refs[(worker + i)
                                                 % len(prompts)]:
                            failures.append(("wrong tokens", out))
                    except Exception as exc:  # noqa: BLE001
                        failures.append((type(exc).__name__, str(exc)))
                    i += 1
                    served[worker] += 1

            def all_served(more):
                mark = list(served)
                _wait_for(lambda: all(n >= m + more
                                      for n, m in zip(served, mark)),
                          f"{more} more answers for every client")

            threads = [threading.Thread(target=load, args=(w,))
                       for w in range(3)]
            for t in threads:
                t.start()
            all_served(2)              # load established
            # the victim: a replica whose engine has a step in the air
            victim = _wait_for(
                lambda: next((i + 1 for i, eng in enumerate(pool.engines)
                              if eng._ahead is not None), None),
                "a replica with a decode step in flight") - 1
            pool.kill(victim)
            killed_url = pool.urls[victim]
            snap = _wait_for(
                lambda: (s := stats())["replicas_evicted"] >= 1
                and killed_url not in s["ring_nodes"] and s,
                "the killed replica's eviction")
            all_served(2)              # more traffic after the eviction
            done.set()
            for t in threads:
                t.join(timeout=60)
            assert not failures, failures[:5]
            assert snap["ring_size"] == 2
            assert not snap["replicas"][killed_url]["ready"]
            evts = recent_events(event="fleet.replica_evicted")
            assert any(e["replica"] == killed_url and e["reason"] == "dead"
                       for e in evts)
    finally:
        pool.stop()


def test_submit_rerouted_to_sibling_after_replica_death(model):
    """A submitted-but-unfetched request whose replica dies is
    resubmitted to a sibling from the router's stored body — the poll
    eventually answers done, never an error."""
    params, config = model
    rng = np.random.default_rng(13)
    pool = ReplicaPool(
        lambda: DecodeEngine(params, config, max_slots=2), n=2).start()
    try:
        with FleetRouter(pool.urls, probe_interval=0.2,
                         evict_after=2) as router:
            prompt = [int(t) for t in rng.integers(0, 300, 5)]
            # find which replica got the submit, then kill exactly it
            fid = _post(router.port, "/v1/submit",
                        {"prompt": prompt, "max_new_tokens": 4})["id"]
            with router._records_lock:
                victim_url = router._records[fid]["url"]
            victim = router._urls.index(victim_url)
            pool.kill(victim)
            deadline = time.time() + 30
            while time.time() < deadline:
                out = _get(router.port, f"/v1/result?id={fid}")
                if out["status"] == "done":
                    break
                time.sleep(0.05)
            assert out["status"] == "done"
            assert out["tokens"] == _ref(params, config, prompt, 4)
            assert _get(router.port, "/stats")["requests_rerouted"] >= 1
    finally:
        pool.stop()


# -------------------------------------------------- pool-saturated 429
def test_pool_saturated_answers_429_with_retry_hint(model):
    """When EVERY ready replica sheds (QueueFullError -> 429), the
    router's edge admission answers 429 with the largest
    ``retry_after_ms`` observed instead of queueing or erroring."""
    params, config = model
    pool = ReplicaPool(
        lambda: DecodeEngine(params, config, max_slots=1, max_queue=1),
        n=2).start()
    try:
        with FleetRouter(pool.urls, probe_interval=0.5) as router:
            # slow steps keep slots occupied for a multi-second window
            install_plan(FaultPlan([{"site": "serving.step",
                                     "action": "delay", "delay": 0.05,
                                     "times": None}]))
            rng = np.random.default_rng(17)
            fids, shed = [], None
            for i in range(12):
                p = [int(t) for t in rng.integers(0, 300, 5)]
                try:
                    fids.append(_post(router.port, "/v1/submit",
                                      {"prompt": p,
                                       "max_new_tokens": 40})["id"])
                except urllib.error.HTTPError as err:
                    shed = (err.code, json.loads(err.read()))
                    break
            assert shed is not None, "pool never saturated"
            code, body = shed
            assert code == 429
            assert body["retry_after_ms"] >= 50
            assert "capacity" in body["error"]
            assert len(fids) >= 2       # the pool DID absorb real work
            for fid in fids:            # free the slots for teardown
                _post(router.port, "/v1/cancel", {"id": fid})
    finally:
        clear_plan()
        pool.stop()


# ------------------------------------------------------- trace routing
def test_trace_id_end_to_end_through_the_proxy(model):
    """A client traceparent survives router -> replica: the router's
    response echoes the trace id, and the replica's flight-recorder
    timeline (fetched through the router by FLEET id) is stamped with
    the same id."""
    params, config = model
    rng = np.random.default_rng(19)
    trace_id = "cafe" * 8
    parent = f"00-{trace_id}-{'ab' * 8}-01"
    pool = ReplicaPool(
        lambda: DecodeEngine(params, config, max_slots=2), n=3).start()
    try:
        with FleetRouter(pool.urls, probe_interval=0.5) as router:
            prompt = [int(t) for t in rng.integers(0, 300, 5)]
            req = urllib.request.Request(
                f"http://127.0.0.1:{router.port}/v1/submit",
                data=json.dumps({"prompt": prompt,
                                 "max_new_tokens": 3}).encode(),
                headers={"Content-Type": "application/json",
                         "traceparent": parent})
            with urllib.request.urlopen(req, timeout=120) as resp:
                assert resp.headers["X-Trace-Id"] == trace_id
                fid = json.loads(resp.read())["id"]
            deadline = time.time() + 30
            while time.time() < deadline:
                out = _get(router.port, f"/v1/result?id={fid}")
                if out["status"] == "done":
                    break
                time.sleep(0.02)
            assert out["status"] == "done"
            trace = _get(router.port, f"/v1/requests/{fid}/trace")
            assert trace["trace_id"] == trace_id
            assert any(e["event"] == "finished" for e in trace["events"])
            # a fleet id nobody issued is a clean 404
            code, body = _http_error(
                lambda: _get(router.port, "/v1/requests/9999/trace"))
            assert code == 404 and body["status"] == "unknown"
    finally:
        pool.stop()


# ---------------------------------------------------------- streaming
def test_streaming_generate_proxies_through_router(model):
    """stream:true through the router: ndjson lines forward as the
    replica emits them, the concatenation is the solo greedy decode,
    and the stream's in-flight hold on the spill signal is released
    when it ends."""
    params, config = model
    rng = np.random.default_rng(29)
    prompt = [int(t) for t in rng.integers(0, 300, 5)]
    pool = ReplicaPool(
        lambda: DecodeEngine(params, config, max_slots=2), n=3).start()
    try:
        with FleetRouter(pool.urls, probe_interval=0.5) as router:
            req = urllib.request.Request(
                f"http://127.0.0.1:{router.port}/v1/generate",
                data=json.dumps({"prompt": prompt, "max_new_tokens": 8,
                                 "stream": True}).encode(),
                headers={"Content-Type": "application/json"})
            lines = []
            with urllib.request.urlopen(req, timeout=120) as resp:
                assert resp.headers["Content-Type"] == \
                    "application/x-ndjson"
                assert resp.headers["X-Trace-Id"]
                for raw in resp:
                    lines.append(json.loads(raw))
            assert lines[-1] == {"status": "done"}
            streamed = [t for ln in lines[:-1] for t in ln["tokens"]]
            assert streamed == _ref(params, config, prompt, 8)
            # the stream's in-flight count was released at close
            stats = _get(router.port, "/stats")
            assert all(info["in_flight"] == 0
                       for info in stats["replicas"].values())
    finally:
        pool.stop()


# ------------------------------------------------------- graceful drain
def test_graceful_drain_shifts_traffic_to_siblings(model):
    """begin_drain() on one replica: the prober evicts it (reason
    'unready' — it is alive and finishing its work), new requests all
    land on siblings, and no client request fails."""
    params, config = model
    rng = np.random.default_rng(23)
    pool = ReplicaPool(
        lambda: DecodeEngine(params, config, max_slots=2), n=3).start()
    try:
        with FleetRouter(pool.urls, probe_interval=0.15,
                         evict_after=2) as router:
            drained_url = pool.urls[0]
            pool.drain(0)
            # requests keep succeeding THROUGH the membership change
            for i in range(10):
                p = [int(t) for t in rng.integers(0, 300, 5)]
                out = _post(router.port, "/v1/generate",
                            {"prompt": p, "max_new_tokens": 3})
                assert out["tokens"] == _ref(params, config, p, 3)
            deadline = time.time() + 5
            while time.time() < deadline:
                stats = _get(router.port, "/stats")
                if stats["ring_size"] == 2:
                    break
                time.sleep(0.05)
            assert stats["ring_size"] == 2
            assert drained_url not in stats["ring_nodes"]
            info = stats["replicas"][drained_url]
            assert not info["ready"] and info["reachable"]
            evts = recent_events(event="fleet.replica_evicted")
            assert any(e["replica"] == drained_url
                       and e["reason"] == "unready" for e in evts)
            # post-eviction traffic routes around the drained replica
            before = stats["replicas"][drained_url]["routes"]
            for i in range(6):
                p = [int(t) for t in rng.integers(0, 300, 5)]
                _post(router.port, "/v1/generate",
                      {"prompt": p, "max_new_tokens": 3})
            after = _get(router.port,
                         "/stats")["replicas"][drained_url]["routes"]
            assert after == before
            # the router stays ready on the surviving pair
            assert _get(router.port, "/ready")["replicas_ready"] == 2
    finally:
        pool.stop()
