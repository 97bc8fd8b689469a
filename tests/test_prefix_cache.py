"""Automatic block-level KV prefix caching: content-addressed chain
keys, refcounted pooled blocks with LRU reclaim, host-mode caching on
contiguous engines and prefill workers, weight-swap version keying,
register_prefix as the pinning layer, and the admission accounting —
all asserted token-identical against the solo ``generate`` oracle."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elephas_tpu.models.block_cache import BlockCache, chain_keys
from elephas_tpu.models.transformer import TransformerConfig, init_params
from elephas_tpu.serving_engine import DecodeEngine

from ._greedy import greedy_reference as _ref


@pytest.fixture(scope="module")
def model():
    config = TransformerConfig(vocab_size=97, num_layers=2, num_heads=4,
                               d_model=32, d_ff=64, max_seq_len=64,
                               dtype=jnp.float32)
    params = init_params(config, jax.random.PRNGKey(0))
    return params, config


# ---------------------------------------------------------- chain keys
def test_chain_keys_content_addressed():
    """Keys are a pure function of (version, token contents): shared
    heads share leading keys, any divergence — content or version —
    changes every key from the divergence on, and partial tail blocks
    never key."""
    toks = np.arange(20, dtype=np.int32)
    keys = chain_keys(toks, 8, 0)
    assert len(keys) == 2                     # 20 // 8, tail excluded
    assert keys == chain_keys(toks.copy(), 8, 0)
    # shared head, divergent second block: first key shared, second not
    other = toks.copy()
    other[9] += 1
    keys2 = chain_keys(other, 8, 0)
    assert keys2[0] == keys[0] and keys2[1] != keys[1]
    # the chain embeds the WHOLE prefix: same second-block tokens under
    # a different first block give a different second key
    shifted = toks.copy()
    shifted[0] += 1
    assert chain_keys(shifted, 8, 0)[1] != keys[1]
    # weights_version seeds the chain root
    assert chain_keys(toks, 8, 1)[0] != keys[0]


def test_block_cache_refcount_lru_pin_eviction():
    c = BlockCache()
    k = [bytes([i]) for i in range(4)]
    e0 = c.insert(k[0], 100, 8, acquire=True)
    e1 = c.insert(k[1], 101, 16, acquire=True)
    assert c.reclaimable_count() == 0
    assert c.match_chain(k[:2]) == [e0, e1]
    # walk stops at the first gap
    assert c.match_chain([k[0], k[2], k[1]]) == [e0]
    # shared: two slots referencing, released one at a time
    c.acquire(e0)
    c.release(e0)
    assert c.reclaimable_count() == 0         # still referenced
    c.release(e0)
    c.release(e1)
    assert c.reclaimable_count() == 2
    # LRU order: e0 released first -> evicted first
    freed = []
    c2 = BlockCache(on_evict=lambda e: freed.append(e.payload))
    a = c2.insert(k[0], 1, 8, acquire=True)
    b = c2.insert(k[1], 2, 8, acquire=True)
    c2.release(a)
    c2.release(b)
    assert c2.evict_lru() is a and freed == [1]
    assert c2.match_chain([k[0]]) == []       # gone from the map
    # pinned: never parks, never evicts; unpin re-parks
    p = c2.insert(k[2], 3, 8, acquire=True)
    c2.pin(p)
    c2.release(p)
    assert c2.reclaimable_count() == 1        # only b... b was evicted?
    # b remains parked; p pinned and excluded
    assert c2.is_parked(b) and not c2.is_parked(p)
    c2.unpin(p)
    assert c2.is_parked(p)
    # host-mode capacity evicts past the bound, pinned exempt
    c3 = BlockCache(capacity=2)
    pin = c3.insert(k[0], "pinned", 8)
    c3.pin(pin)
    c3.insert(k[1], "x", 8)
    c3.insert(k[2], "y", 8)
    c3.insert(k[3], "z", 8)
    assert len(c3) == 3 and c3.get(k[0]) is not None
    assert c3.evictions == 1


# ------------------------------------------------- paged engine caching
def test_paged_shared_prefix_hits_token_identical(model):
    """The tentpole property: same traffic, cache on vs off, outputs
    token-identical; with the cache on, every same-head admission
    after the first reuses the head's full blocks (pointer install)
    and records a ``kv_cache_hit`` timeline event."""
    params, config = model
    rng = np.random.default_rng(5)
    head = list(rng.integers(0, 97, 19))      # 2 full blocks + tail 3
    prompts = [np.asarray(head + list(rng.integers(0, 97, 4)))
               for _ in range(5)]

    on = DecodeEngine(params, config, max_slots=2, paged=(32, 8))
    off = DecodeEngine(params, config, max_slots=2, paged=(32, 8),
                       prefix_cache=False)
    rids = [on.submit(p, 6) for p in prompts]
    while on.pending:
        on.step()
    got = [on.result(r) for r in rids]
    assert got == off.run(prompts, max_new_tokens=6)
    for g, p in zip(got, prompts):
        assert g == _ref(params, config, p, 6)
    st = on.stats
    assert st["kv_cache"]["hits"] == 4        # every admission after #1
    assert st["kv_cache"]["misses"] == 1
    assert st["prefix_tokens_reused"] >= 4 * 16
    assert st["blocks_free"] == st["blocks_total"]   # all reclaimable
    assert st["kv_cache"]["reclaimable_blocks"] == st["kv_cache"][
        "cached_blocks"]
    # the flight recorder shows the hit with its block/token counts
    hits = [ev for r in rids
            for ev in (on.request_trace(r) or {"events": []})["events"]
            if ev["event"] == "kv_cache_hit"]
    assert len(hits) == 4
    assert all(ev["blocks"] == 2 and ev["tokens_reused"] == 16
               for ev in hits)
    # off-engine: no cache surfaces at all
    assert "kv_cache" not in off.stats


def test_concurrent_same_head_requests_share_blocks(model):
    """Two same-head requests IN FLIGHT TOGETHER point their tables at
    the same physical blocks (refcount 2); retirement parks the entries
    instead of freeing the blocks, leaking nothing."""
    params, config = model
    rng = np.random.default_rng(9)
    head = list(rng.integers(0, 97, 16))
    p1 = np.asarray(head + list(rng.integers(0, 97, 3)))
    p2 = np.asarray(head + list(rng.integers(0, 97, 5)))
    eng = DecodeEngine(params, config, max_slots=2, paged=(32, 8))
    r1 = eng.submit(p1, 8)
    r2 = eng.submit(p2, 8)
    shared = [e for lst in eng._slot_cached for e in lst]
    assert {e.refcount for e in shared} == {2}       # both tables point
    assert len({id(e) for e in shared}) == 2         # 2 head blocks
    # the two slots' leading table entries are the SAME block ids
    assert list(eng._tables[0][:2]) == list(eng._tables[1][:2])
    while eng.pending:
        eng.step()
    assert eng.result(r1) == _ref(params, config, p1, 8)
    assert eng.result(r2) == _ref(params, config, p2, 8)
    assert all(e.refcount == 0 for e in shared)
    assert eng.stats["blocks_free"] == eng.stats["blocks_total"]


def test_full_pool_reclaims_cold_blocks_instead_of_waiting(model):
    """The acceptance eviction property: a pool whose free list is
    EMPTY (every block parked in the cache) admits new requests by
    reclaiming cold cached blocks LRU-first — never wedging the queue,
    never shedding an admissible request."""
    params, config = model
    rng = np.random.default_rng(13)
    eng = DecodeEngine(params, config, max_slots=1, paged=(13, 8))
    # three cold 24-token prompts: 3 full blocks each -> 9 of the 12
    # allocatable blocks parked in the cache once retired
    cold = [np.asarray(rng.integers(0, 97, 24)) for _ in range(3)]
    for p in cold:
        rid = eng.submit(p, 8)
        while eng.pending:
            eng.step()
        assert eng.result(rid) == _ref(params, config, p, 8)
    st = eng.stats
    assert st["kv_cache"]["cached_blocks"] == 9
    assert len(eng._free_block_ids) == 3      # raw free list: 3 blocks
    assert st["blocks_free"] == 12            # ... but ALL reclaimable
    # a brand-new request needing FIVE blocks — more than the raw free
    # list holds — still admits immediately by reclaiming cold blocks
    fresh = np.asarray(rng.integers(0, 97, 33))
    rid = eng.submit(fresh, 6)
    while eng.pending:
        eng.step()
    assert eng.result(rid) == _ref(params, config, fresh, 6)
    assert eng.stats["kv_cache"]["evictions"] >= 2
    # LRU: the OLDEST cold prompt's chain broke first
    assert len(eng._kv_cache.match_chain(
        chain_keys(cold[0][:24], 8, 0))) < 3


def test_weight_swap_version_keyed_invalidation(model):
    """The hot-swap x cache interaction: blocks cached under version 0
    are NEVER served after a swap (the chain keys on weights_version,
    so the same prompt misses by construction and recomputes under the
    new params — output == the new-params oracle), and the old-version
    blocks park and reclaim under pressure instead of leaking
    refcounts."""
    params, config = model
    params2 = init_params(config, jax.random.PRNGKey(7))
    rng = np.random.default_rng(21)
    head = list(rng.integers(0, 97, 16))
    p1 = np.asarray(head + list(rng.integers(0, 97, 4)))
    p2 = np.asarray(head + list(rng.integers(0, 97, 4)))
    eng = DecodeEngine(params, config, max_slots=1, paged=(16, 8))
    # warm the cache under v0 and prove it hits
    assert eng.run([p1, p2], max_new_tokens=6) == [
        _ref(params, config, p1, 6), _ref(params, config, p2, 6)]
    assert eng.stats["kv_cache"]["hits"] == 1
    v0_keys = chain_keys(p1[:16], 8, 0)
    assert len(eng._kv_cache.match_chain(v0_keys)) == 2
    # hot-swap mid-traffic (stage from "any thread", applied at the
    # admission atomic point) — the SAME head must now miss and the
    # output must equal the NEW params' oracle, not v0's
    eng.stage_params(params2, version=1)
    rid = eng.submit(p1, 6)
    while eng.pending:
        eng.step()
    got = eng.result(rid)
    assert got == _ref(params2, config, p1, 6)
    assert got != _ref(params, config, p1, 6)  # the swap is observable
    st = eng.stats
    assert st["weights_version"] == 1
    assert st["kv_cache"]["hits"] == 1        # unchanged: v0 never hit
    assert st["kv_cache"]["misses"] == 2
    # v1 chains now cache; v0 entries linger parked (no refcount leak)
    assert len(eng._kv_cache.match_chain(chain_keys(p1[:16], 8, 1))) == 2
    assert all(e.refcount == 0 for e in eng._kv_cache._entries.values())
    # ... and age out of the LRU under pool pressure rather than
    # surviving forever: big fresh prompts force reclaim of v0 blocks
    for _ in range(3):
        big = np.asarray(rng.integers(0, 97, 40))
        rid = eng.submit(big, 8)
        while eng.pending:
            eng.step()
        assert eng.result(rid) == _ref(params2, config, big, 8)
    assert eng.stats["kv_cache"]["evictions"] > 0
    assert len(eng._kv_cache.match_chain(v0_keys)) == 0


def test_register_prefix_pins_against_pressure(model):
    """register_prefix = the pinning layer: its full blocks carry a
    refcount floor (never evicted) while unpinned traffic churns the
    LRU around them; clear_prefixes lifts the floor and the blocks
    become ordinary reclaimable entries."""
    params, config = model
    rng = np.random.default_rng(31)
    prefix = list(rng.integers(0, 97, 16))    # 2 pinned blocks
    eng = DecodeEngine(params, config, max_slots=1, paged=(12, 8))
    eng.register_prefix(prefix)
    st = eng.stats
    assert st["kv_cache"]["pinned_blocks"] == 2
    assert st["kv_cache"]["cached_blocks"] == 2
    # a matching request hits the pinned chain with zero head prefill
    p = np.asarray(prefix + list(rng.integers(0, 97, 4)))
    rid = eng.submit(p, 6)
    while eng.pending:
        eng.step()
    assert eng.result(rid) == _ref(params, config, p, 6)
    assert eng.stats["kv_cache"]["hits"] == 1
    # churn: distinct prompts large enough to force eviction pressure
    for _ in range(4):
        q = np.asarray(rng.integers(0, 97, 30))
        rid = eng.submit(q, 6)
        while eng.pending:
            eng.step()
        assert eng.result(rid) == _ref(params, config, q, 6)
    st = eng.stats
    assert st["kv_cache"]["evictions"] > 0
    assert st["kv_cache"]["pinned_blocks"] == 2      # floor held
    assert eng.stats["kv_cache"]["hits"] >= 1
    eng.clear_prefixes()
    assert eng.stats["kv_cache"]["pinned_blocks"] == 0
    assert eng.stats["kv_cache"]["reclaimable_blocks"] == eng.stats[
        "kv_cache"]["cached_blocks"]


def test_paged_registered_subblock_tail_still_wins(model):
    """Longest registered match wins over the block chain: a pinned
    20-token row (2 full blocks + a 4-token tail) serves a matching
    admission WHOLE — counted as the pinning layer's reuse, neither a
    cache hit nor a miss — while a prompt sharing only the full blocks
    takes the cache-hit path."""
    params, config = model
    rng = np.random.default_rng(81)
    prefix = list(rng.integers(0, 97, 20))
    eng = DecodeEngine(params, config, max_slots=1, paged=(16, 8))
    eng.register_prefix(prefix)
    p = np.asarray(prefix + list(rng.integers(0, 97, 4)))
    rid = eng.submit(p, 6)
    while eng.pending:
        eng.step()
    assert eng.result(rid) == _ref(params, config, p, 6)
    st = eng.stats
    assert st["prefix_hits"] == 1             # the 20-token row served
    assert st["prefix_tokens_reused"] == 20
    assert st["kv_cache"]["hits"] == 0
    assert st["kv_cache"]["misses"] == 0      # registered reuse != miss
    # same 2 full blocks, different continuation: no row match, the
    # pinned chain serves via the ordinary cache walk
    q = np.asarray(prefix[:16] + list(rng.integers(0, 97, 6)))
    rid = eng.submit(q, 6)
    while eng.pending:
        eng.step()
    assert eng.result(rid) == _ref(params, config, q, 6)
    assert eng.stats["kv_cache"]["hits"] == 1
    assert eng.stats["prefix_hits"] == 1      # unchanged


def test_check_admissible_accounts_pinned_blocks(model):
    """Pinned blocks permanently shrink allocatable capacity — a
    non-matching request that could only fit by evicting them 400s at
    submit instead of wedging the FIFO head forever; a request RIDING
    the pinned prefix still fits (its table points at the pins)."""
    params, config = model
    rng = np.random.default_rng(41)
    prefix = list(rng.integers(0, 97, 32))    # 4 pinned of 9 allocatable
    eng = DecodeEngine(params, config, max_slots=1, paged=(10, 8))
    eng.register_prefix(prefix)
    # 9 - 4 pinned = 5 allocatable; a foreign 41+7 request needs 6
    with pytest.raises(ValueError, match="never be admitted"):
        eng.submit(np.asarray(rng.integers(0, 97, 41)), 7)
    # the SAME size starting with the pinned prefix needs only 2 new
    # blocks (4 ride the pins) — admissible, and completes
    p = np.asarray(prefix + list(rng.integers(0, 97, 9)))
    rid = eng.submit(p, 7)
    while eng.pending:
        eng.step()
    assert eng.result(rid) == _ref(params, config, p, 7)


def test_pinned_credit_never_wedges_on_unaligned_registered_prefix(model):
    """The check_admissible/_admit consistency contract: a riding
    request admitted on the strength of its leading pinned run must
    ALWAYS ride it at admission time — the longest-registered-match
    override (the non-block-aligned row covers 2 tokens more than the
    chain) yields when pins make a full private allocation permanently
    impossible, instead of wedging the FIFO head forever."""
    params, config = model
    rng = np.random.default_rng(91)
    prefix = list(rng.integers(0, 97, 26))   # 6 pinned blocks + 2 tail
    eng = DecodeEngine(params, config, max_slots=1, paged=(11, 4))
    eng.register_prefix(prefix)
    assert eng.stats["kv_cache"]["pinned_blocks"] == 6
    # 28 + 8 = 36 tokens -> 9 blocks: only admissible via the pinned
    # run (10 allocatable - 6 pinned = 4 private)
    p = np.asarray(prefix + list(rng.integers(0, 97, 2)))
    rid = eng.submit(p, 8)
    for _ in range(60):
        if not eng.pending:
            break
        eng.step()
    assert eng.result(rid) == _ref(params, config, p, 8)


# ----------------------------------------- host mode: contiguous/export
def test_host_mode_export_prefill_cache(model):
    """The prefill-tier cache: a contiguous export engine's second
    same-head export skips the head's prefill compute (cached_tokens)
    and ships an equivalent frame: the cached head's positions are
    bit-identical copies, the recomputed remainder agrees to float
    rounding (a different XLA program), and the sampled first token —
    what decode parity rides on — is identical."""
    params, config = model
    rng = np.random.default_rng(51)
    head = list(rng.integers(0, 97, 16))
    p1 = head + list(rng.integers(0, 97, 4))
    p2 = head + list(rng.integers(0, 97, 4))
    eng = DecodeEngine(params, config, max_slots=1, prefix_cache=True,
                       prefix_cache_block_size=8)
    out1 = eng.export_prefill(p1, block_size=8)
    assert out1["cached_tokens"] == 0
    out2 = eng.export_prefill(p2, block_size=8)
    assert out2["cached_tokens"] == 16
    # oracle: an uncached engine's export of the same prompt
    plain = DecodeEngine(params, config, max_slots=1,
                         prefix_cache=False)
    ref2 = plain.export_prefill(p2, block_size=8)
    assert out2["first_token"] == ref2["first_token"]
    for a, b in zip(out2["kv_blocks"], ref2["kv_blocks"]):
        # blocks 0-1 (the cached head) are bit-identical copies; the
        # remainder block recomputes under a different fusion
        np.testing.assert_array_equal(a[:2], b[:2])
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)
    assert eng.stats["kv_cache"]["hits"] == 1
    # and the identical FULL prompt re-export hits its whole walkable
    # chain (the final aligned block recomputes by design: the
    # remainder extend produces the first-token logits)
    out3 = eng.export_prefill(p2, block_size=8)
    assert out3["cached_tokens"] == 16
    assert out3["first_token"] == ref2["first_token"]


def test_prefill_worker_enables_tier_local_cache(model):
    """PrefillWorker turns the cache on at its wire block size by
    default (and leaves it off when asked)."""
    from elephas_tpu.disagg import PrefillWorker

    params, config = model
    eng = DecodeEngine(params, config, max_slots=1)
    w = PrefillWorker(eng, block_size=8)
    assert eng._kv_cache is not None and eng._kv_cache_bs == 8
    eng2 = DecodeEngine(params, config, max_slots=1)
    PrefillWorker(eng2, block_size=8, prefix_cache=False)
    assert eng2._kv_cache is None
    del w


def test_fleet_shim_reads_engine_cache(model):
    """The _AutoPrefixEngine compat shim: same ctor surface, misses now
    read straight off the engine's block cache."""
    from elephas_tpu.fleet.pool import _AutoPrefixEngine

    params, config = model
    rng = np.random.default_rng(61)
    head = list(rng.integers(0, 97, 6))
    eng = _AutoPrefixEngine(DecodeEngine(params, config, max_slots=2),
                            prefix_tokens=6, capacity=32)
    prompts = [np.asarray(head + list(rng.integers(0, 97, 3)))
               for _ in range(4)]
    rids = [eng.submit(p, 3) for p in prompts]
    while eng.pending:
        eng.step()
    for rid, p in zip(rids, prompts):
        assert eng.result(rid) == _ref(params, config, p, 3)
    assert eng.misses == 1                    # one cold head
    assert eng.registered_prefixes >= 1
    assert eng.stats["kv_cache"]["hits"] == 3


# ------------------------------------------------------- observability
def test_metrics_and_stats_surfaces(model):
    """The new serving_kv_cache_* series render on the registry and
    agree with /stats' kv_cache dict."""
    params, config = model
    rng = np.random.default_rng(71)
    head = list(rng.integers(0, 97, 8))
    eng = DecodeEngine(params, config, max_slots=1, paged=(16, 8))
    prompts = [np.asarray(head + list(rng.integers(0, 97, 3)))
               for _ in range(3)]
    eng.run(prompts, max_new_tokens=3)
    text = eng.registry.render()
    for fam in ("serving_kv_cache_hits_total",
                "serving_kv_cache_misses_total",
                "serving_kv_cache_evictions_total",
                "serving_kv_cache_blocks",
                "serving_kv_cache_reclaimable_blocks"):
        assert fam in text, fam
    ks = eng.stats["kv_cache"]
    m = re.search(r"^serving_kv_cache_hits_total (\S+)$", text,
                  re.MULTILINE)
    assert m and float(m.group(1)) == ks["hits"]
    snap = eng.registry.snapshot()
    assert "serving_kv_cache_blocks" in snap
    assert ks["hits"] == 2 and ks["misses"] == 1


def test_speculative_mode_composes_with_prefix_cache(model):
    """Speculative mode caches the TARGET model's KV like any other
    engine (draft KV is recomputed at admission, never cached) — the
    former constructor rejection is gone; the full hit/parity story is
    pinned in tests/test_speculative_serving.py."""
    params, config = model
    eng = DecodeEngine(params, config, draft_params=params,
                       draft_config=config, prefix_cache=True,
                       prefix_cache_block_size=8)
    assert eng._kv_cache is not None and eng.draft_config is not None
