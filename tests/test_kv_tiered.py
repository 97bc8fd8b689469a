"""Tiered KV spill + resumable cross-request sessions: eviction
demotes parked blocks device -> host RAM -> (Q8) object storage
instead of discarding, admission chain walks fall through the tiers
and promote back, and a session-tagged request's trailing KV persists
at retirement so the conversation's next request admits as a chain hit
on ANY replica sharing the session store — all asserted
token-identical against the spill-off / solo ``generate`` oracles,
with the lossy-payload content-addressing rule pinned."""
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elephas_tpu.kvtier import (HostTier, SessionStore, SpilledBlock,
                                StorageTier, TieredSpill, decode_payload,
                                encode_payload)
from elephas_tpu.models.block_cache import chain_keys
from elephas_tpu.models.transformer import TransformerConfig, init_params
from elephas_tpu.serving_engine import DecodeEngine
from elephas_tpu.utils.storage import LocalMirrorStore, register_store

from ._greedy import greedy_reference as _ref


@pytest.fixture(scope="module")
def model():
    config = TransformerConfig(vocab_size=97, num_layers=2, num_heads=4,
                               d_model=32, d_ff=64, max_seq_len=64,
                               dtype=jnp.float32)
    params = init_params(config, jax.random.PRNGKey(0))
    return params, config


@pytest.fixture()
def mirror(tmp_path):
    store = LocalMirrorStore(tmp_path)
    register_store("mirror", store)
    yield store
    register_store("mirror", None)


def _drain(eng):
    while eng.pending:
        eng.step()


def _events(eng, rid):
    return (eng.request_trace(rid) or {"events": []})["events"]


# ------------------------------------------------------ payload codec
def test_payload_codec_exact_and_q8_lossy_marking():
    """The wire format: ``compress="none"`` round-trips bit-exact with
    ``lossy=False``; ``"q8"`` round-trips close-but-marked-lossy at
    well under half the bytes. The lossy bit travels WITH the payload —
    it is what keeps a dequantized copy from ever re-registering as
    the exact content its tokens address."""
    rng = np.random.default_rng(3)
    payload = {f"layer_{i}": (rng.standard_normal((8, 32, 16),
                                                  dtype=np.float32),
                              rng.standard_normal((8, 32, 16),
                                                  dtype=np.float32))
               for i in range(2)}
    exact = encode_payload(payload, 8, compress="none")
    got, tokens, lossy = decode_payload(exact)
    assert tokens == 8 and not lossy
    for name, (k, v) in payload.items():
        np.testing.assert_array_equal(got[name][0], k)
        np.testing.assert_array_equal(got[name][1], v)
    q8 = encode_payload(payload, 8, compress="q8")
    got, tokens, lossy = decode_payload(q8)
    assert tokens == 8 and lossy
    for name, (k, v) in payload.items():
        np.testing.assert_allclose(got[name][0], k, atol=0.05)
        assert not np.array_equal(got[name][0], k)   # genuinely lossy
    assert len(q8) < 0.5 * len(exact)
    # SpilledBlock accounts its own f32 footprint
    blk = SpilledBlock(b"k", payload, 8, lossy=False)
    assert blk.nbytes == sum(k.nbytes + v.nbytes
                             for k, v in payload.values())


def test_host_overflow_cascades_to_storage_keyed_by_original_tokens(
        mirror):
    """Tier mechanics without an engine: host LRU overflow lands in
    the storage tier Q8-compressed, stored under the ORIGINAL chain
    key (content address of the exact tokens) but marked lossy;
    ``lookup`` falls through host -> storage and reports the source
    tier; ``consumed`` drops only the host copy (storage is the
    durability layer)."""
    rng = np.random.default_rng(5)
    spill = TieredSpill(host_capacity_blocks=2,
                        storage_url="mirror://spill-unit")
    keys = [bytes([i]) * 4 for i in range(3)]
    for key in keys:
        payload = {"layer_0": (rng.standard_normal((4, 8, 8),
                                                   dtype=np.float32),
                               rng.standard_normal((4, 8, 8),
                                                   dtype=np.float32))}
        spill.demote(key, payload, 8)
    # keys[0] aged out of the 2-block host tier into storage, ON DISK
    # under its original content address
    assert mirror.exists(f"mirror://spill-unit/{keys[0].hex()}.npz")
    blk, tier = spill.lookup(keys[0])
    assert tier == "storage" and blk.lossy and blk.key == keys[0]
    blk, tier = spill.lookup(keys[2])
    assert tier == "host" and not blk.lossy
    assert spill.lookup(b"absent") is None
    # consumed: host copy gone, storage copy stays
    spill.consumed(keys[2])
    assert spill.lookup(keys[2]) is None
    spill.consumed(keys[0])
    assert spill.lookup(keys[0])[1] == "storage"
    st = spill.stats()
    assert st["host"]["demotions"] == 3
    assert st["host"]["blocks"] == 1
    assert st["storage"]["blocks"] == 1 and st["storage"]["demotions"] == 1
    assert 0 < st["storage"]["bytes"] < st["host"]["demoted_bytes"]


def test_tiered_spill_thread_safety():
    """Demotion runs on the engine loop while admission walks read
    from submitter threads: hammer both sides plus ``consumed`` and
    require coherent counts, no exceptions."""
    spill = TieredSpill(host_capacity_blocks=8)
    payload = {"l": (np.zeros((2, 4, 4), np.float32),
                     np.zeros((2, 4, 4), np.float32))}
    errors = []

    def writer():
        try:
            for i in range(200):
                spill.demote(bytes([i % 16]), payload, 4)
        except Exception as exc:           # pragma: no cover
            errors.append(exc)

    def reader():
        try:
            for i in range(200):
                found = spill.lookup(bytes([i % 16]))
                if found is not None and i % 3 == 0:
                    spill.consumed(found[0].key)
        except Exception as exc:           # pragma: no cover
            errors.append(exc)

    threads = [threading.Thread(target=f)
               for f in (writer, writer, reader, reader)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert len(spill.host) <= 8
    assert spill.stats()["host"]["demotions"] == 400


# ------------------------------------------- engine demote/promote
def test_eviction_demotes_and_promotion_is_token_identical(model):
    """The tentpole property: under pool pressure parked blocks demote
    to host RAM instead of being discarded, a returning prompt's chain
    walk promotes them back, and the outputs are token-identical to
    the spill-OFF engine AND the solo oracle — with zero refcount
    leaks and the movement visible in /stats + the flight recorder."""
    params, config = model
    rng = np.random.default_rng(5)
    cold = [np.asarray(rng.integers(0, 97, 24)) for _ in range(3)]
    fresh = np.asarray(rng.integers(0, 97, 33))
    traffic = cold + [fresh, cold[0]]

    on = DecodeEngine(params, config, max_slots=1, paged=(13, 8))
    on.enable_kv_spill(host_capacity_blocks=64)
    off = DecodeEngine(params, config, max_slots=1, paged=(13, 8))
    outs = []
    for p, n in [(p, 8) for p in cold] + [(fresh, 6), (cold[0], 8)]:
        rid = on.submit(p, n)
        _drain(on)
        got = on.result(rid)
        outs.append((rid, got))
        orid = off.submit(p, n)
        _drain(off)
        assert got == off.result(orid)                  # spill invisible
    assert outs[-1][1] == _ref(params, config, cold[0], 8)
    st = on.stats["kv_tiers"]
    assert st["host"]["demotions"] >= 2                 # evictions caught
    assert st["promotions"]["host"] >= 1                # and came back
    assert st["host"]["gets"] >= 1
    # the promoted re-admission is an ordinary chain hit on its slot
    last = outs[-1][0]
    promote = next(ev for ev in _events(on, last)
                   if ev["event"] == "kv_promote")
    assert promote["tiers"] == {"host": promote["blocks"]}
    hit = next(ev for ev in _events(on, last)
               if ev["event"] == "kv_cache_hit")
    assert hit["promoted"] >= 1 and hit["blocks"] >= hit["promoted"]
    demote = next(ev for ev in _events(on, last)
                  if ev["event"] == "kv_demote")
    assert demote["blocks"] >= 1                        # one accumulated
    # zero leaks: everything reclaimable, every refcount released
    assert on.stats["blocks_free"] == on.stats["blocks_total"]
    assert all(e.refcount == 0 for e in on._kv_cache._entries.values())
    # spill-off engine surfaces no tier block at all
    assert "kv_tiers" not in off.stats


def test_lossy_storage_block_never_reregisters_chain(model, mirror):
    """The content-addressing fix, pinned: a Q8 storage block keys by
    its ORIGINAL tokens but carries ``lossy=True``. The default engine
    stops its tier walk at the lossy block (recompute, exact output);
    with ``lossy_promote=True`` the block installs but TAINTS the slot
    — its freshly computed blocks never re-register under chain keys,
    never park, never persist to a session."""
    params, config = model
    rng = np.random.default_rng(17)
    cold = [np.asarray(rng.integers(0, 97, 24)) for _ in range(3)]
    fresh = np.asarray(rng.integers(0, 97, 33))

    def pressure(eng):
        for p in cold:
            rid = eng.submit(p, 8)
            _drain(eng)
        rid = eng.submit(fresh, 6)
        _drain(eng)

    # host tier of ONE block: the rest of the evicted chain cascades
    # to Q8 storage, so cold[0]'s leading blocks come back lossy
    strict = DecodeEngine(params, config, max_slots=1, paged=(13, 8))
    strict.enable_kv_spill(host_capacity_blocks=1,
                           storage_url="mirror://spill-strict")
    pressure(strict)
    rid = strict.submit(cold[0], 8)
    _drain(strict)
    assert strict.result(rid) == _ref(params, config, cold[0], 8)
    assert strict.stats["kv_tiers"].get("promotions", {}) == {}
    assert not any(ev["event"] == "kv_promote"
                   for ev in _events(strict, rid))

    opt = DecodeEngine(params, config, max_slots=1, paged=(13, 8))
    opt.enable_kv_spill(host_capacity_blocks=1,
                        storage_url="mirror://spill-opt",
                        lossy_promote=True)
    pressure(opt)
    rid = opt.submit(cold[0], 8)
    _drain(opt)
    out = opt.result(rid)
    assert len(out) == 8                    # served, approximate KV
    promote = next(ev for ev in _events(opt, rid)
                   if ev["event"] == "kv_promote")
    assert promote["tiers"].get("storage", 0) >= 1
    # the tainted slot registered NOTHING: the prompt's chain is not
    # walkable on device, and no lossy payload was parked or persisted
    walk = chain_keys(cold[0][:16], 8, 0)
    assert opt._kv_cache.match_chain(walk) == []
    assert opt.stats["blocks_free"] == opt.stats["blocks_total"]


# ------------------------------------------------- resumable sessions
def test_session_resume_on_different_replica_token_identical(model):
    """The cross-request session: replica A retires a session-tagged
    request and persists its trailing chain; the conversation's next
    turn lands on replica B (same shared store) and admits as a chain
    hit — token-identical to a never-resumed engine, with the
    hit/miss counters and timeline events telling the story."""
    params, config = model
    rng = np.random.default_rng(11)
    store = SessionStore()
    a = DecodeEngine(params, config, max_slots=1, paged=(16, 8),
                     session_store=store)
    b = DecodeEngine(params, config, max_slots=1, paged=(16, 8),
                     session_store=store)
    turn1 = np.asarray(rng.integers(0, 97, 21))
    rid1 = a.submit(turn1, 6, session="conv-1")
    _drain(a)
    out1 = a.result(rid1)
    assert out1 == _ref(params, config, turn1, 6)
    assert any(ev["event"] == "session_saved"
               for ev in _events(a, rid1))
    assert store.stats()["blocks"] == 3     # (21 + 6) tokens -> 3 full
    # turn 2 = turn1 ++ reply ++ new user tokens, on the OTHER replica
    turn2 = np.concatenate([turn1, np.asarray(out1, np.int32),
                            rng.integers(0, 97, 5).astype(np.int32)])
    rid2 = b.submit(turn2, 6, session="conv-1")
    _drain(b)
    plain = DecodeEngine(params, config, max_slots=1, paged=(16, 8))
    prid = plain.submit(turn2, 6)
    _drain(plain)
    assert b.result(rid2) == plain.result(prid) == _ref(
        params, config, turn2, 6)
    promote = next(ev for ev in _events(b, rid2)
                   if ev["event"] == "kv_promote")
    assert promote["tiers"] == {"session": 3}
    # hit/miss accounting: A's first turn had no chain to find (miss),
    # B's resume found it (hit) — per-engine deltas, shared registry
    assert a.stats["kv_tiers"]["session"]["misses"] == 1
    assert a.stats["kv_tiers"]["session"]["hits"] == 0
    assert b.stats["kv_tiers"]["session"]["hits"] == 1
    assert b.stats["kv_tiers"]["session"]["misses"] == 0
    # idempotent persistence: B re-persisted ONLY the blocks A's turn
    # had not already content-addressed
    assert store.stats()["blocks"] == 4     # turn2's 32 KV tokens
    assert b.stats["blocks_free"] == b.stats["blocks_total"]


def test_session_store_object_backend_roundtrip(model, mirror):
    """A storage-backed session store (``url=``) persists through the
    object store and resumes from a COLD replica process — the
    crash-safe variant of the host-backed topology."""
    params, config = model
    rng = np.random.default_rng(23)
    turn1 = np.asarray(rng.integers(0, 97, 21))
    a = DecodeEngine(params, config, max_slots=1, paged=(16, 8),
                     session_store={"url": "mirror://sessions"})
    rid = a.submit(turn1, 6, session="conv-9")
    _drain(a)
    out1 = a.result(rid)
    # a brand-new engine + store object, same URL: state is in the
    # object store, not the process
    b = DecodeEngine(params, config, max_slots=1, paged=(16, 8),
                     session_store={"url": "mirror://sessions"})
    turn2 = np.concatenate([turn1, np.asarray(out1, np.int32),
                            rng.integers(0, 97, 5).astype(np.int32)])
    rid2 = b.submit(turn2, 6, session="conv-9")
    _drain(b)
    assert b.result(rid2) == _ref(params, config, turn2, 6)
    assert any(ev["event"] == "kv_promote"
               for ev in _events(b, rid2))


def test_hot_swap_invalidates_every_tier(model):
    """Weight hot-swap x tiers: chains key on ``weights_version``, so
    spilled and session blocks from v0 can never serve v1 traffic —
    the host tier's RAM is returned eagerly at the swap, the same
    prompt promotes nothing, and the session's next turn misses by
    construction and recomputes under the new params."""
    params, config = model
    params2 = init_params(config, jax.random.PRNGKey(7))
    rng = np.random.default_rng(29)
    store = SessionStore()
    eng = DecodeEngine(params, config, max_slots=1, paged=(13, 8),
                       session_store=store)
    eng.enable_kv_spill(host_capacity_blocks=64)
    cold = [np.asarray(rng.integers(0, 97, 24)) for _ in range(3)]
    for p in cold:
        rid = eng.submit(p, 8, session="conv-2")
        _drain(eng)
    fresh = np.asarray(rng.integers(0, 97, 33))
    rid = eng.submit(fresh, 6)
    _drain(eng)
    assert eng.stats["kv_tiers"]["host"]["demotions"] >= 1
    assert eng.stats["kv_tiers"]["host"]["blocks"] >= 1
    eng.stage_params(params2, version=1)
    # the swap's atomic point empties the host tier outright — the RAM
    # comes back NOW, not at LRU age-out
    assert eng.apply_staged_params() == 1
    assert eng.stats["kv_tiers"]["host"]["blocks"] == 0
    rid = eng.submit(cold[0], 8, session="conv-2")
    _drain(eng)
    got = eng.result(rid)
    assert got == _ref(params2, config, cold[0], 8)
    assert got != _ref(params, config, cold[0], 8)
    # nothing promoted: v0 chain keys simply do not exist under v1
    # (post-swap allocation pressure may re-demote stale v0 DEVICE
    # blocks — they are unreachable by construction and age out)
    assert not any(ev["event"] == "kv_promote"
                   for ev in _events(eng, rid))
    assert eng.stats["kv_tiers"]["session"]["misses"] >= 1
    # v1 sessions persist under v1 keys and resume fine post-swap
    turn2 = np.concatenate([cold[0], np.asarray(got, np.int32)])
    rid2 = eng.submit(turn2, 4, session="conv-2")
    _drain(eng)
    assert eng.result(rid2) == _ref(params2, config, turn2, 4)


# ----------------------------------------------------- QoS interplay
def test_preemption_parks_to_tiers_without_pinning_hbm(model):
    """QoS preemption x spill: a preempted low-priority decode parks
    its blocks UNPINNED (reclaimable, not HBM-resident by fiat); when
    the high-priority admission's allocation needs them they demote to
    host instead of being discarded, and the victim still resumes
    token-identical (its chain promotes back)."""
    from elephas_tpu.serving_qos import TenantQoS

    params, config = model
    rng = np.random.default_rng(31)
    qos = TenantQoS(tenants={"batch": {"priority": "low"},
                             "live": {"priority": "high"}})
    eng = DecodeEngine(params, config, max_slots=1, paged=(9, 8),
                       qos=qos)
    eng.enable_kv_spill(host_capacity_blocks=64)
    pa = np.asarray(rng.integers(0, 97, 12))
    ra = eng.submit(pa, 12, tenant="batch")
    for _ in range(6):
        eng.step()
    # a high-priority arrival whose allocation exceeds the raw free
    # list: the victim's parked blocks must be RECLAIMED (demoted),
    # never pinned in the pool
    pb = np.asarray(rng.integers(0, 97, 52))
    rb = eng.submit(pb, 4, tenant="live")
    _drain(eng)
    assert eng.result(ra) == _ref(params, config, pa, 12)
    assert eng.result(rb) == _ref(params, config, pb, 4)
    assert eng.stats["preemptions"] == 1
    assert eng.stats["kv_cache"]["pinned_blocks"] == 0
    assert eng.stats["kv_tiers"]["host"]["demotions"] >= 1
    events = [ev["event"] for ev in _events(eng, ra)]
    assert "preempted" in events and "resumed" in events
    assert eng.stats["blocks_free"] == eng.stats["blocks_total"]


def test_queued_same_head_after_promotion_no_double_install(model):
    """The concurrent-claim race, pinned deterministically: two
    same-head requests with the head spilled to host. The first
    admission promotes AND re-registers the chain; the queued second
    must then claim those freshly registered device blocks (its stale
    promo memo is invalidated by the changed hit count) rather than
    double-installing the host copies over them."""
    params, config = model
    rng = np.random.default_rng(37)
    eng = DecodeEngine(params, config, max_slots=1, paged=(13, 8))
    eng.enable_kv_spill(host_capacity_blocks=64)
    cold = [np.asarray(rng.integers(0, 97, 24)) for _ in range(3)]
    for p in cold:
        rid = eng.submit(p, 8)
        _drain(eng)
    fresh = np.asarray(rng.integers(0, 97, 33))
    rid = eng.submit(fresh, 6)
    _drain(eng)
    # two same-head continuations: #2 queues behind #1 (one slot)
    p1 = np.concatenate([cold[0][:16], rng.integers(0, 97, 5)])
    p2 = np.concatenate([cold[0][:16], rng.integers(0, 97, 7)])
    r1 = eng.submit(p1, 6)
    r2 = eng.submit(p2, 6)
    _drain(eng)
    assert eng.result(r1) == _ref(params, config, p1, 6)
    assert eng.result(r2) == _ref(params, config, p2, 6)
    # the second rode device blocks: at most one admission promoted
    promos = [ev for r in (r1, r2) for ev in _events(eng, r)
              if ev["event"] == "kv_promote"]
    assert len(promos) <= 1
    hit2 = next(ev for ev in _events(eng, r2)
                if ev["event"] == "kv_cache_hit")
    assert hit2["promoted"] == 0 and hit2["blocks"] >= 1
    assert eng.stats["blocks_free"] == eng.stats["blocks_total"]
    assert all(e.refcount == 0 for e in eng._kv_cache._entries.values())


# ------------------------------------------------------ observability
def test_metrics_stats_http_and_fleet_surfaces(model):
    """The observability satellite end to end: the spill/session
    counter families and tier gauges render on the registry and agree
    with /stats' ``kv_tiers``; the HTTP server forwards the request
    ``session`` field; a fleet membership probe lands ``kv_tiers`` on
    the replica snapshot and sums session hits into the decode tier
    signals."""
    import json
    import urllib.request

    from elephas_tpu.fleet.membership import ReplicaMembership
    from elephas_tpu.serving_http import ServingServer

    params, config = model
    rng = np.random.default_rng(41)
    eng = DecodeEngine(params, config, max_slots=1, paged=(13, 8),
                       session_store=SessionStore())
    eng.enable_kv_spill(host_capacity_blocks=64)
    srv = ServingServer(eng)
    srv.start()
    try:
        url = f"http://127.0.0.1:{srv.port}"

        def post(body):
            req = urllib.request.Request(
                url + "/v1/generate", data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json"})
            return json.loads(
                urllib.request.urlopen(req, timeout=30).read())

        turn1 = [int(t) for t in rng.integers(0, 97, 21)]
        out1 = post({"prompt": turn1, "max_new_tokens": 6,
                     "session": "conv-http"})
        turn2 = turn1 + [int(t) for t in out1["tokens"]] + [3, 1, 4]
        post({"prompt": turn2, "max_new_tokens": 4,
              "session": "conv-http"})
        # force demotions so the host-tier series are non-trivial
        for _ in range(3):
            post({"prompt": [int(t) for t in rng.integers(0, 97, 33)],
                  "max_new_tokens": 4})
        text = eng.registry.render()
        for fam in ("serving_kv_spill_demotions_total",
                    "serving_kv_spill_promotions_total",
                    "serving_kv_spill_bytes_total",
                    "serving_kv_session_hits_total",
                    "serving_kv_session_misses_total",
                    "serving_kv_tier_blocks",
                    "serving_kv_tier_bytes"):
            assert fam in text, fam
        kt = eng.stats["kv_tiers"]
        m = re.search(r'^serving_kv_spill_demotions_total\{tier="host"\}'
                      r' (\S+)$', text, re.MULTILINE)
        assert m and float(m.group(1)) == kt["host"]["demotions"]
        m = re.search(r'^serving_kv_session_hits_total (\S+)$', text,
                      re.MULTILINE)
        assert m and float(m.group(1)) == kt["session"]["hits"] == 1
        m = re.search(r'^serving_kv_tier_blocks\{tier="session"\} (\S+)$',
                      text, re.MULTILINE)
        assert m and float(m.group(1)) == kt["session"]["blocks"]
        # fleet probe: the /stats block lands on the snapshot and the
        # summed session counters land on the decode tier signals
        mem = ReplicaMembership([url], probe_interval=30.0,
                                join_after=1)
        mem.probe_once()
        snap = mem.snapshot()[url]
        assert snap["kv_tiers"]["session"]["hits"] == 1
        tiers = mem.tier_signals()
        kv = tiers["decode"]["kv_tiers"]
        assert kv["replicas"] == 1 and kv["session_hits"] == 1
        assert kv["host_blocks"] == kt["host"]["blocks"]
    finally:
        srv.stop()


def test_http_session_rejected_on_engines_without_support(model):
    """The capability-probe contract: an explicit ``session`` on an
    engine whose submit has no session parameter fails loudly (400),
    never silently dropped."""
    import json
    import urllib.error
    import urllib.request

    from elephas_tpu.serving_http import ServingServer

    params, config = model

    class _NoSession:
        def __init__(self, eng):
            self._eng = eng
            self.registry = eng.registry

        def submit(self, prompt, max_new_tokens, admit=True):
            return self._eng.submit(prompt, max_new_tokens, admit=admit)

        def __getattr__(self, name):
            return getattr(self._eng, name)

    eng = DecodeEngine(params, config, max_slots=1)
    srv = ServingServer(_NoSession(eng))
    srv.start()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/v1/generate",
            data=json.dumps({"prompt": [1, 2, 3], "max_new_tokens": 2,
                             "session": "s"}).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(req, timeout=30)
        assert err.value.code == 400
        assert b"session" in err.value.read()
    finally:
        srv.stop()
