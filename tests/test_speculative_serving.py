"""Speculative decoding as a first-class serving mode: paged
draft/verify parity, prefix-cache compatibility, draft freshness via
the live weight plane, and disaggregated speculative decode workers.

The invariant every test leans on: speculative sampling is EXACT with
respect to the target model (greedy f32 here, so token-identical) —
the draft moves only the acceptance rate. That is what makes the
greedy A/B against the solo ``generate`` oracle the acceptance test
for every composition below, and what makes a stale draft a
performance event instead of a correctness event.
"""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elephas_tpu.models.transformer import TransformerConfig, init_params
from elephas_tpu.serving_engine import DecodeEngine

from ._greedy import greedy_reference as _ref


def _config(**overrides):
    # f32: the parity oracle compares tokens across different compiled
    # programs (spec round vs generate's fused scan) — the same
    # cross-program argmax-near-tie caveat every engine parity test
    # documents
    base = dict(vocab_size=64, num_layers=2, num_heads=4, d_model=32,
                d_ff=64, max_seq_len=64, dtype=jnp.float32)
    base.update(overrides)
    return TransformerConfig(**base)


def _draft_config(**overrides):
    base = dict(vocab_size=64, num_layers=1, num_heads=2, d_model=16,
                d_ff=32, max_seq_len=64, dtype=jnp.float32)
    base.update(overrides)
    return TransformerConfig(**base)


@pytest.fixture(scope="module")
def model():
    config = _config()
    params = init_params(config, jax.random.PRNGKey(0))
    dcfg = _draft_config()
    draft = init_params(dcfg, jax.random.PRNGKey(9))
    return params, config, draft, dcfg


def _drain(eng, rids):
    while eng.pending:
        eng.step()
    return [eng.result(r) for r in rids]


# ------------------------------------------------------- paged parity
def test_paged_speculative_matches_generate_concurrent_slots(model):
    """The tentpole parity: paged speculative stepping with MORE
    requests than slots — staggered admissions, mixed lengths, slot
    reuse — on a tight pool where slots' block allocations interleave.
    Every output must equal its solo greedy decode: a verify round's
    rejected-position writes land only in the writing slot's own
    blocks (tables are disjoint; the gamma slack is budgeted per
    slot), so no neighbor slot's KV is ever perturbed."""
    params, config, draft, dcfg = model
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 64, int(n))
               for n in rng.integers(3, 14, size=8)]
    eng = DecodeEngine(params, config, max_slots=3, draft_params=draft,
                       draft_config=dcfg, gamma=3, paged=(32, 8))
    rids = [eng.submit(p, 11) for p in prompts]
    outs = _drain(eng, rids)
    for p, o in zip(prompts, outs):
        assert o == _ref(params, config, p, 11)
    st = eng.stats
    assert st["speculative_rounds"] > 0
    assert 0.0 <= st["draft_acceptance"] <= 1.0


def test_paged_speculative_incremental_submission_reuses_blocks(model):
    """Requests submitted mid-decode (the online pattern) onto slots
    whose verify slack is live, plus slot/block reuse after
    retirement, stay token-identical."""
    params, config, draft, dcfg = model
    rng = np.random.default_rng(2)
    p1, p2, p3 = (rng.integers(0, 64, n) for n in (5, 9, 4))
    eng = DecodeEngine(params, config, max_slots=2, draft_params=draft,
                       draft_config=dcfg, gamma=4, paged=(24, 8))
    r1 = eng.submit(p1, 9)
    r2 = eng.submit(p2, 9)
    eng.step()
    r3 = eng.submit(p3, 9)       # queued: both slots busy
    outs = _drain(eng, [r1, r2, r3])
    for p, o in zip((p1, p2, p3), outs):
        assert o == _ref(params, config, p, 9)
    # all blocks returned (cache entries may stay parked = reclaimable)
    assert eng.stats["blocks_free"] == eng.stats["blocks_total"]


def test_paged_slack_budgeted_in_admission(model):
    """check_admissible budgets the gamma verify slack into the paged
    block arithmetic: a request that fits without slack but not with
    it 400s at submit instead of corrupting the tail block at the
    first verify past its allocation."""
    params, config, draft, dcfg = model
    # pool of 5 allocatable blocks of 8 = 40 positions
    eng = DecodeEngine(params, config, max_slots=1, max_len=48,
                       draft_params=draft, draft_config=dcfg, gamma=4,
                       paged=(6, 8))
    # 33 + 7 = 40 fits 5 blocks; + gamma 4 needs a 6th
    with pytest.raises(ValueError, match="blocks"):
        eng.submit(np.zeros(33, np.int32), 7)
    # max_len bound carries the slack term too (named in the message)
    with pytest.raises(ValueError, match="gamma"):
        eng.submit(np.zeros(40, np.int32), 8)
    # the same prompt with slack room admits fine
    rid = eng.submit(np.zeros(30, np.int32), 6)
    assert _drain(eng, [rid])[0] == _ref(params, config,
                                         np.zeros(30, np.int32), 6)


# ------------------------------------------------------- prefix cache
def test_speculative_prefix_cache_hit_and_ab_parity(model):
    """Prefix cache x speculative: the TARGET's full prompt blocks are
    cached/shared exactly as in plain mode — a same-head request hits
    (hit counters + recorder event), outputs are token-identical with
    the cache on vs off, and the hit chain's shared blocks survive the
    hitting request's verify writes (they only cover positions below
    the prompt head)."""
    params, config, draft, dcfg = model
    rng = np.random.default_rng(3)
    head = list(rng.integers(0, 64, 16))          # two full 8-blocks
    prompts = [np.asarray(head + list(rng.integers(0, 64, 3)))
               for _ in range(4)]
    outs = {}
    for cache_on in (False, True):
        eng = DecodeEngine(params, config, max_slots=2,
                           draft_params=draft, draft_config=dcfg,
                           gamma=3, paged=(40, 8),
                           prefix_cache=cache_on)
        rids = [eng.submit(p, 8) for p in prompts]
        outs[cache_on] = _drain(eng, rids)
        if cache_on:
            ks = eng.stats["kv_cache"]
            assert ks["hits"] >= 1, ks
            hit_events = [e for t in eng.recorder.recent(limit=8)
                          for e in t["events"]
                          if e["event"] == "kv_cache_hit"]
            assert hit_events and hit_events[0]["tokens_reused"] >= 8
    assert outs[True] == outs[False]
    for p, o in zip(prompts, outs[True]):
        assert o == _ref(params, config, p, 8)


def test_speculative_host_mode_cache_contiguous(model):
    """The host-array cache variant (contiguous engine) composes too:
    the former enable_prefix_cache rejection is gone and parity
    holds through a cache hit."""
    params, config, draft, dcfg = model
    rng = np.random.default_rng(4)
    head = list(rng.integers(0, 64, 12))
    prompts = [np.asarray(head + [int(t)]) for t in rng.integers(0, 64, 3)]
    eng = DecodeEngine(params, config, max_slots=2, draft_params=draft,
                       draft_config=dcfg, gamma=3, prefix_cache=True,
                       prefix_cache_block_size=4)
    rids = [eng.submit(p, 7) for p in prompts]
    outs = _drain(eng, rids)
    for p, o in zip(prompts, outs):
        assert o == _ref(params, config, p, 7)
    assert eng.stats["kv_cache"]["hits"] >= 1


def test_speculative_register_prefix_still_pins(model):
    """register_prefix keeps working in speculative paged mode: the
    pinned TARGET blocks serve matches and the registered draft row
    serves the draft's head."""
    params, config, draft, dcfg = model
    rng = np.random.default_rng(5)
    prefix = rng.integers(0, 64, 10)
    eng = DecodeEngine(params, config, max_slots=2, draft_params=draft,
                       draft_config=dcfg, gamma=3, paged=(40, 8))
    eng.register_prefix(prefix)
    p = np.asarray(list(prefix) + [3, 1])
    rid = eng.submit(p, 8)
    assert _drain(eng, [rid])[0] == _ref(params, config, p, 8)
    assert eng.stats["prefix_hits"] >= 1


# --------------------------------------------- draft freshness / plane
def test_stale_draft_degrades_acceptance_only(model):
    """The draft-freshness contract: a deliberately-wrong (stale)
    draft tanks the acceptance rate but every output stays
    token-identical to the target oracle; staging fresh draft params
    through the draft channel restores acceptance without touching
    outputs. The 'fresh' draft here is the TARGET itself (acceptance
    ~1.0 greedy), the 'stale' one random garbage (acceptance ~0)."""
    params, config, _, _ = model
    stale_draft = init_params(config, jax.random.PRNGKey(123))
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, 64, int(n)) for n in (6, 9, 5)]
    eng = DecodeEngine(params, config, max_slots=2,
                       draft_params=stale_draft, draft_config=config,
                       gamma=3, paged=(40, 8))

    def per_request_acceptance(rids):
        # the per-request stamps on the terminal events — exactly the
        # observability this PR adds (pooled counters would mix passes:
        # a high-acceptance pass proposes FEWER tokens, so the ratio
        # of sums underweights it)
        accs = []
        for r in rids:
            term = [e for e in eng.request_trace(r)["events"]
                    if e["event"] == "finished"][0]
            accs.append(term["draft_accepted"]
                        / max(term["draft_proposed"], 1))
        return sum(accs) / len(accs)

    rids = [eng.submit(p, 10) for p in prompts]
    outs = _drain(eng, rids)
    for p, o in zip(prompts, outs):
        assert o == _ref(params, config, p, 10)
    stale_acc = per_request_acceptance(rids)
    # draft channel: stage the target's own params as the fresh draft
    # from a foreign thread, like a WeightSubscriber would
    t = threading.Thread(
        target=lambda: eng.stage_draft_params(params, version=7))
    t.start()
    t.join()
    rids = [eng.submit(p, 10) for p in prompts]
    outs2 = _drain(eng, rids)
    assert outs2 == outs                 # same prompts, same outputs
    st = eng.stats
    assert st["draft_weights_version"] == 7
    fresh_acc = per_request_acceptance(rids)
    assert stale_acc < 0.3 < fresh_acc, (stale_acc, fresh_acc)
    assert fresh_acc > stale_acc + 0.3, (stale_acc, fresh_acc)


def test_target_hot_swap_with_stale_draft_token_identical(model):
    """A live TARGET hot-swap under a draft that was distilled for the
    OLD target: output must equal the NEW target's oracle (the verify
    pass is exact w.r.t. whatever target is serving), with the stale
    draft costing acceptance only. Also pins chain-key hygiene: the
    cache is keyed by the TARGET version, so post-swap admissions
    cannot hit v0 blocks."""
    params, config, draft, dcfg = model
    params_v1 = init_params(config, jax.random.PRNGKey(77))
    rng = np.random.default_rng(7)
    head = list(rng.integers(0, 64, 16))
    p = np.asarray(head + [2, 5])
    eng = DecodeEngine(params, config, max_slots=2, draft_params=draft,
                       draft_config=dcfg, gamma=3, paged=(40, 8))
    r = eng.submit(p, 8)
    assert _drain(eng, [r])[0] == _ref(params, config, p, 8)
    eng.stage_params(params_v1, version=1)
    r = eng.submit(p, 8)                 # swap applies at admission
    out = _drain(eng, [r])[0]
    assert out == _ref(params_v1, config, p, 8)
    assert eng.weights_version == 1


def test_weight_subscriber_draft_channel(model):
    """WeightSubscriber(channel='draft') polls/pulls like the target
    channel but stages through stage_draft_params and watches
    draft_weights_version — driven here by a fake parameter-plane
    client for determinism."""
    from elephas_tpu.weightsync import WeightSubscriber

    params, config, draft, dcfg = model
    fresh = init_params(dcfg, jax.random.PRNGKey(42))
    leaves = [np.asarray(w) for w in jax.tree_util.tree_leaves(fresh)]

    class FakeClient:
        def __init__(self):
            self.version = 3

        def get_version(self):
            return self.version

        def get_parameters_versioned(self):
            return self.version, leaves

        def close(self):
            pass

    eng = DecodeEngine(params, config, max_slots=1, draft_params=draft,
                       draft_config=dcfg, gamma=2)
    with pytest.raises(ValueError, match="draft"):
        WeightSubscriber(DecodeEngine(params, config), FakeClient(),
                         channel="draft")
    sub = WeightSubscriber(eng, FakeClient(), channel="draft",
                           auto=True)
    # no start(): drive the poll synchronously (no baseline, so the
    # first poll pulls and stages)
    assert sub.poll_once() is True
    eng.apply_staged_params()
    assert eng.draft_weights_version == 3
    assert eng.weights_version == 0      # target channel untouched
    got = jax.tree_util.tree_leaves(eng.draft_params)
    np.testing.assert_array_equal(np.asarray(got[0]), leaves[0])
    # outputs under the swapped draft still match the target oracle
    rng = np.random.default_rng(8)
    p = rng.integers(0, 64, 6)
    r = eng.submit(p, 8)
    assert _drain(eng, [r])[0] == _ref(params, config, p, 8)


# ------------------------------------------------------ disaggregation
def test_submit_prefilled_into_speculative_engine(model):
    """The disagg handshake without the wire: a TARGET-only engine
    exports the prefill, a speculative decode engine installs it and
    recomputes the draft KV at admission — output token-identical to
    the oracle, first token included."""
    params, config, draft, dcfg = model
    rng = np.random.default_rng(9)
    p = rng.integers(0, 64, 9)
    prefiller = DecodeEngine(params, config, max_slots=1)
    out = prefiller.export_prefill(p, block_size=8)
    eng = DecodeEngine(params, config, max_slots=2, draft_params=draft,
                       draft_config=dcfg, gamma=3, paged=(24, 8),
                       tier="decode")
    rid = eng.submit_prefilled(p, 9, out["kv_blocks"],
                               out["first_token"],
                               weights_version=out["weights_version"])
    assert _drain(eng, [rid])[0] == _ref(params, config, p, 9)


def test_speculative_prefill_export_rejected_with_alternative(model):
    """The genuinely-unsupported path keeps raising — and the message
    names the supported deployment (target-only prefill tier,
    speculative decode workers)."""
    params, config, draft, dcfg = model
    eng = DecodeEngine(params, config, max_slots=1, draft_params=draft,
                       draft_config=dcfg, gamma=2)
    with pytest.raises(ValueError, match="target-only"):
        eng.export_prefill(np.zeros(4, np.int32), block_size=4)


@pytest.mark.slow
def test_disagg_engine_speculative_decode_worker(model):
    """End to end over the real wire: DisaggEngine fronting a
    speculative paged decode engine fed by a target-only
    PrefillWorker. Outputs token-identical to the oracle; /stats
    carries the decode engine's acceptance rate."""
    from elephas_tpu.disagg import DisaggEngine, PrefillWorker

    params, config, draft, dcfg = model
    rng = np.random.default_rng(10)
    prompts = [rng.integers(0, 64, int(n)) for n in (7, 5, 10, 6)]
    prefill_eng = DecodeEngine(params, config, max_slots=1)
    worker = PrefillWorker(prefill_eng, quant=False, block_size=8,
                           name="spec-prefill").start()
    decode_eng = DecodeEngine(params, config, max_slots=2,
                              draft_params=draft, draft_config=dcfg,
                              gamma=3, paged=(40, 8), tier="decode")
    disagg = DisaggEngine(decode_eng, [worker])
    try:
        rids = [disagg.submit(p, 9) for p in prompts]
        deadline = time.monotonic() + 60
        outs = {}
        while len(outs) < len(rids) and time.monotonic() < deadline:
            if disagg.pending:
                disagg.step()
            else:
                time.sleep(0.005)
            for r in rids:
                if r not in outs:
                    got = disagg.result(r)
                    if got is not None:
                        outs[r] = got
        for p, r in zip(prompts, rids):
            assert outs[r] == _ref(params, config, p, 9)
        st = disagg.stats
        assert "draft_acceptance" in st and st["speculative_rounds"] > 0
        # per-request sampling overrides 400 at THIS front end's submit
        with pytest.raises(ValueError, match="speculative"):
            disagg.submit(prompts[0], 4, temperature=0.5)
    finally:
        disagg.stop()
        worker.stop()


# ------------------------------------------------------- observability
def test_finished_event_carries_acceptance(model):
    """Per-request acceptance observability: the flight recorder's
    terminal event stamps draft_accepted/draft_proposed, and the
    registry exposes the engine-level gauge + rounds counter."""
    params, config, draft, dcfg = model
    rng = np.random.default_rng(11)
    p = rng.integers(0, 64, 6)
    eng = DecodeEngine(params, config, max_slots=1, draft_params=draft,
                       draft_config=dcfg, gamma=3, paged=(24, 8))
    rid = eng.submit(p, 10)
    _drain(eng, [rid])
    tr = eng.request_trace(rid)
    term = [e for e in tr["events"] if e["event"] == "finished"]
    assert term and term[0]["draft_proposed"] > 0
    assert 0 <= term[0]["draft_accepted"] <= term[0]["draft_proposed"]
    rendered = eng.registry.render()
    assert "serving_speculative_rounds_total" in rendered
    assert "serving_speculative_acceptance" in rendered
    assert "request_tokens_per_s_p50" in eng.stats


def test_fleet_probe_surfaces_acceptance(model):
    """The fleet half of the observability satellite: a membership
    probe of a speculative replica's /stats lands draft_acceptance +
    request_tokens_per_s_p50 on the replica snapshot and the decode
    tier signals (what the router's /stats serves)."""
    from elephas_tpu.fleet.membership import ReplicaMembership
    from elephas_tpu.serving_http import ServingServer

    params, config, draft, dcfg = model
    eng = DecodeEngine(params, config, max_slots=2, draft_params=draft,
                       draft_config=dcfg, gamma=3, paged=(24, 8))
    srv = ServingServer(eng)
    srv.start()
    try:
        import json
        import urllib.request

        url = f"http://127.0.0.1:{srv.port}"
        body = json.dumps({"prompt": [1, 2, 3, 4],
                           "max_new_tokens": 6}).encode()
        req = urllib.request.Request(
            url + "/v1/generate", data=body,
            headers={"Content-Type": "application/json"})
        urllib.request.urlopen(req, timeout=30).read()
        mem = ReplicaMembership([url], probe_interval=30.0,
                                join_after=1)
        mem.probe_once()
        snap = mem.snapshot()[url]
        assert "draft_acceptance" in snap, snap
        assert "request_tokens_per_s_p50" in snap, snap
        tiers = mem.tier_signals()
        assert "draft_acceptance_min" in tiers["decode"], tiers
    finally:
        srv.stop()


# ------------------------------------------------------------ qos edge
def test_speculative_preemption_resume_token_identical(model):
    """QoS preemption now reaches speculative paged engines (paged +
    cache is the park/resume substrate): a preempted speculative
    decode resumes token-identical, with its parked blocks reclaimed
    through the ordinary chain walk."""
    params, config, draft, dcfg = model
    rng = np.random.default_rng(12)
    low_p = rng.integers(0, 64, 8)
    hi_p = rng.integers(0, 64, 6)
    qos = {"tenants": {"low": {"priority": "low"},
                       "hi": {"priority": "high"}},
           "preempt": True}
    eng = DecodeEngine(params, config, max_slots=1, draft_params=draft,
                       draft_config=dcfg, gamma=3, paged=(40, 8),
                       qos=qos)
    r_low = eng.submit(low_p, 12, tenant="low")
    eng.step()                            # low is mid-decode
    r_hi = eng.submit(hi_p, 6, tenant="hi", admit=False)
    outs = _drain(eng, [r_low, r_hi])
    assert outs[0] == _ref(params, config, low_p, 12)
    assert outs[1] == _ref(params, config, hi_p, 6)
    assert eng.stats["preemptions"] >= 1
