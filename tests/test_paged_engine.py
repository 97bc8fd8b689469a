"""Paged KV cache: the block-pool engine must emit exactly the
contiguous engine's tokens (per-request ≡ solo greedy decode) while its
memory scales with tokens in flight — oversubscribed pools queue
admissions and recycle blocks on retirement, and the scratch-sink
invariant keeps inactive slots from ever corrupting live requests."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elephas_tpu.models.transformer import TransformerConfig, init_params
from elephas_tpu.serving_engine import DecodeEngine

from ._greedy import greedy_reference as _ref


def _config(**overrides):
    base = dict(vocab_size=64, num_layers=2, num_heads=4, d_model=32,
                d_ff=64, max_seq_len=48, dtype=jnp.float32)
    base.update(overrides)
    return TransformerConfig(**base)


@pytest.fixture(scope="module")
def model():
    config = _config()
    params = init_params(config, jax.random.PRNGKey(0))
    return params, config


def test_paged_parity_mixed_lengths(model):
    """Ample pool: outputs must be identical to the contiguous engine
    across mixed prompt lengths and staggered admission."""
    params, config = model
    rng = np.random.default_rng(40)
    prompts = [rng.integers(0, 64, int(n))
               for n in rng.integers(3, 12, size=6)]
    plain = DecodeEngine(params, config, max_slots=2)
    paged = DecodeEngine(params, config, max_slots=2, paged=(32, 8))
    expected = plain.run(prompts, max_new_tokens=9)
    got = paged.run(prompts, max_new_tokens=9)
    assert got == expected
    for p, o in zip(prompts, expected):
        assert o == _ref(params, config, p, 9)
    # every block returned to the pool after the drain
    assert paged.stats["blocks_free"] == paged.stats["blocks_total"]


def test_paged_oversubscription_queues_and_completes(model):
    """A pool holding FEWER positions than max_slots*max_len (the whole
    point): admission waits for blocks when the pool runs dry, every
    request still completes with its exact solo decode."""
    params, config = model
    rng = np.random.default_rng(41)
    # 4 slots x 48 max_len = 192 contiguous positions; pool = 9
    # allocatable blocks x 8 = 72 positions
    prompts = [rng.integers(0, 64, int(n))
               for n in rng.integers(3, 10, size=8)]
    eng = DecodeEngine(params, config, max_slots=4, paged=(10, 8))
    saw_dry_pool = False
    rids = [eng.submit(p, 12) for p in prompts]
    while eng.pending:
        eng.step()
        if eng.stats["blocks_free"] == 0:
            saw_dry_pool = True
    for rid, p in zip(rids, prompts):
        assert eng.result(rid) == _ref(params, config, p, 12)
    assert eng.stats["blocks_free"] == 9


def test_paged_request_larger_than_pool_rejected(model):
    params, config = model
    eng = DecodeEngine(params, config, max_slots=2, paged=(3, 8))
    with pytest.raises(ValueError, match="never be admitted"):
        eng.submit(np.zeros(20, np.int32), 20)


def test_paged_composes_with_prefix_chunked(model):
    """paged x prefix caching x chunked prefill — the full serving
    stack in one engine, still token-exact."""
    params, config = model
    rng = np.random.default_rng(42)
    prefix = list(rng.integers(0, 64, 6))
    prompts = [np.asarray(prefix + list(rng.integers(0, 64, int(n))))
               for n in (2, 5, 8)]
    prompts.append(rng.integers(0, 64, 4))
    eng = DecodeEngine(params, config, max_slots=2, paged=(24, 8),
                       prefill_chunk=5)
    eng.register_prefix(prefix)
    outs = eng.run(prompts, max_new_tokens=8)
    for p, o in zip(prompts, outs):
        assert o == _ref(params, config, p, 8)
    assert eng.stats["prefix_hits"] == 3
    assert eng.stats["blocks_free"] == eng.stats["blocks_total"]


_VARIANTS = {
    "window": {"attention_window": 6},
    "alibi": {"positional": "alibi"},
    "rope": {"positional": "rope"},
    "sinusoidal": {"positional": "sinusoidal"},
    "gqa": {"num_kv_heads": 2},
}


@pytest.mark.parametrize("variant", sorted(_VARIANTS))
def test_paged_window_and_alibi_variants(variant):
    """Masking variants flow through the paged gather identically, at
    RAGGED per-row positions: four prompts of mixed lengths through two
    slots, so rows sit at different positions and join mid-flight."""
    config = _config(**_VARIANTS[variant])
    params = init_params(config, jax.random.PRNGKey(1))
    rng = np.random.default_rng(43)
    prompts = [rng.integers(0, 64, n) for n in (3, 9, 14, 6)]
    eng = DecodeEngine(params, config, max_slots=2, paged=(24, 8))
    outs = eng.run(prompts, max_new_tokens=8)
    for p, o in zip(prompts, outs):
        assert o == _ref(params, config, p, 8)


def test_paged_eos_returns_blocks_early(model):
    params, config = model
    rng = np.random.default_rng(44)
    prompt = rng.integers(0, 64, 6)
    full = _ref(params, config, prompt, 12)
    # the eos at its FIRST occurrence: under this machine's numerics the
    # token at a fixed index can also appear earlier in the decode, and
    # the engine (correctly) stops at the first hit — same seed-flake
    # hardening as test_serving_engine/test_ssm_engine's eos tests
    eos = full[4]
    want = full[:full.index(eos)]
    eng = DecodeEngine(params, config, max_slots=1, paged=(16, 8),
                       eos_id=eos)
    rid = eng.submit(prompt, 12)
    while eng.pending:
        eng.step()
    assert eng.result(rid) == want
    assert eng.stats["blocks_free"] == eng.stats["blocks_total"]


def test_paged_rejects_incompatible_modes(model):
    params, config = model
    # speculative mode COMPOSES with paged KV since the paged
    # draft/verify unification (tests/test_speculative_serving.py pins
    # the parity); the genuinely incompatible modes still reject
    eng = DecodeEngine(params, config, paged=(8, 8), draft_params=params,
                       draft_config=config)
    assert eng.paged is not None and eng.draft_config is not None
    qcfg = dataclasses.replace(config, kv_cache_quant=True)
    with pytest.raises(ValueError, match="kv_cache_quant"):
        DecodeEngine(params, qcfg, paged=(8, 8))
    with pytest.raises(ValueError, match="num_blocks"):
        DecodeEngine(params, config, paged=(1, 8))


def test_paged_max_len_not_block_multiple(model):
    """max_len that does not divide block_size: the final partial block
    pads at install and decode parity still holds."""
    params, config = model
    rng = np.random.default_rng(45)
    prompt = rng.integers(0, 64, 17)      # prompt reaches the tail block
    eng = DecodeEngine(params, config, max_slots=2, max_len=20,
                       paged=(16, 8))
    [out] = eng.run([prompt], max_new_tokens=3)
    assert out == _ref(params, config, prompt, 3)


def test_preemption_with_a_step_in_flight_drops_its_surplus_token(model):
    """A low-priority decode is preempted while its next token is in
    flight: the token is dropped (the resume samples it again), the
    parked blocks hold what the synchronous loop's would, and all three
    requests decode as they do alone."""
    from elephas_tpu.serving_qos import TenantQoS

    params, config = model
    rng = np.random.default_rng(61)
    pa, pb, pc = (rng.integers(0, 64, n) for n in (10, 6, 5))
    qos = TenantQoS(tenants={"batch": {"priority": "low"},
                             "live": {"priority": "high"}})
    eng = DecodeEngine(params, config, max_slots=2, paged=(24, 4),
                       qos=qos)
    ra = eng.submit(pa, 14, tenant="batch")
    rb = eng.submit(pb, 14, tenant="live")
    for _ in range(6):
        eng.step()
    assert eng._ahead is not None and eng._ahead.rows.all()
    rc = eng.submit(pc, 4, tenant="live")     # no free slot: ra parks
    while eng.pending:
        eng.step()
    assert eng.stats["preemptions"] == 1
    assert eng.stats["surplus_rows"] == 1
    for rid, p, n in ((ra, pa, 14), (rb, pb, 14), (rc, pc, 4)):
        assert eng.result(rid) == _ref(params, config, p, n)
    events = eng.request_trace(ra)["events"]
    assert next(ev for ev in events
                if ev["event"] == "preempted")["parked_blocks"] >= 1
