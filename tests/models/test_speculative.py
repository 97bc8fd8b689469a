"""Speculative decoding: the greedy path must reproduce the target
model's own greedy decode token-for-token regardless of draft quality
(draft rejection only costs speed, never correctness), and
``decode_block`` — the verify primitive — must be bit-consistent with
sequential ``decode_step`` calls for scalar and per-row positions."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elephas_tpu.models.speculative import speculative_generate
from elephas_tpu.models.transformer import (TransformerConfig, generate,
                                            init_params)

from ._transformer_util import DECODE_BLOCK, DECODE_STEP, PREFILL


def _config(**overrides):
    # f32 compute: greedy-parity oracles compare tokens across different
    # compiled programs (the speculative while_loop vs generate's scan);
    # bf16 rounding differs ~5e-4 between compilation granularities,
    # which can flip argmax near-ties of a random flat model.
    base = dict(vocab_size=128, num_layers=2, num_heads=4, d_model=32,
                d_ff=64, max_seq_len=64, dtype=jnp.float32)
    base.update(overrides)
    return TransformerConfig(**base)


VARIANTS = {
    "base": {},
    "gqa": {"num_kv_heads": 2},
    "window": {"attention_window": 5},
    "alibi": {"positional": "alibi"},
    "sinusoidal": {"positional": "sinusoidal"},
    "kvq": {"kv_cache_quant": True},
    "moe": {"num_experts": 2, "expert_top_k": 1},
}


def _cache_diff(a, b):
    return max(float(jnp.abs(a[k][kk].astype(jnp.float32)
                             - b[k][kk].astype(jnp.float32)).max())
               for k in a for kk in a[k])


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_decode_block_matches_stepwise(variant):
    """One decode_block over S tokens == S sequential decode_steps:
    same logits, same cache contents."""
    config = _config(**VARIANTS[variant])
    params = init_params(config, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (3, 6), 0,
                                config.vocab_size)
    _, cache = PREFILL(params, prompt, config, 32)
    toks = jax.random.randint(jax.random.PRNGKey(2), (3, 4), 0,
                              config.vocab_size)

    block_logits, block_cache = DECODE_BLOCK(params, cache, toks, 6, config)
    step_cache, step_logits = cache, []
    for j in range(4):
        lg, step_cache = DECODE_STEP(params, step_cache, toks[:, j], 6 + j,
                                     config)
        step_logits.append(lg)
    np.testing.assert_allclose(np.asarray(block_logits),
                               np.asarray(jnp.stack(step_logits, 1)),
                               atol=2e-5)
    assert _cache_diff(block_cache, step_cache) <= 1e-5


@pytest.mark.parametrize("variant", ["base", "gqa", "alibi", "kvq"])
def test_vector_positions_match_scalar(variant):
    """decode_step/decode_block with a per-row position vector of equal
    entries == the scalar-position path (the vector path is what
    speculative decoding's per-row acceptance rides on)."""
    config = _config(**VARIANTS[variant])
    params = init_params(config, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (3, 6), 0,
                                config.vocab_size)
    _, cache = PREFILL(params, prompt, config, 32)
    toks = jax.random.randint(jax.random.PRNGKey(2), (3, 4), 0,
                              config.vocab_size)
    vec = jnp.full((3,), 6, jnp.int32)

    ls, cs = DECODE_STEP(params, cache, toks[:, 0], 6, config)
    lv, cv = DECODE_STEP(params, cache, toks[:, 0], vec, config)
    np.testing.assert_allclose(np.asarray(ls), np.asarray(lv), atol=1e-6)
    assert _cache_diff(cs, cv) == 0.0

    bs, cbs = DECODE_BLOCK(params, cache, toks, 6, config)
    bv, cbv = DECODE_BLOCK(params, cache, toks, vec, config)
    np.testing.assert_allclose(np.asarray(bs), np.asarray(bv), atol=1e-6)
    assert _cache_diff(cbs, cbv) == 0.0


def test_vector_positions_genuinely_ragged():
    """Rows at genuinely different cache positions decode as if each row
    ran alone (vector-pos correctness beyond the degenerate equal case)."""
    config = _config()
    params = init_params(config, jax.random.PRNGKey(0))
    full = jax.random.randint(jax.random.PRNGKey(1), (2, 10), 0,
                              config.vocab_size)
    lens = [7, 4]
    # per-row caches built independently at each row's own length
    row_logits = []
    for b, ln in enumerate(lens):
        _, c1 = PREFILL(params, full[b:b + 1, :ln], config, 32)
        lg, _ = DECODE_STEP(params, c1, full[b:b + 1, ln], ln, config)
        row_logits.append(lg)
    # one batched cache: prefill the longer row, then a vector-pos step
    _, cache = PREFILL(params, full[:, :7], config, 32)
    # row 1's cache holds garbage past position 3, which the per-row
    # length mask must hide
    toks = jnp.stack([full[0, 7], full[1, 4]])
    lg, _ = DECODE_STEP(params, cache, toks,
                        jnp.asarray(lens, jnp.int32), config)
    np.testing.assert_allclose(np.asarray(lg[0]), np.asarray(row_logits[0][0]),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(lg[1]), np.asarray(row_logits[1][0]),
                               atol=2e-5)


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_speculative_greedy_matches_generate(variant):
    """Greedy speculative decode == the target's own greedy generate,
    token-for-token, with an arbitrary (even random/unrelated) draft."""
    config = _config(**VARIANTS[variant])
    draft_config = _config(num_layers=1, num_heads=2, d_model=16, d_ff=32,
                           **{k: v for k, v in VARIANTS[variant].items()
                              if k not in ("num_kv_heads",)})
    params = init_params(config, jax.random.PRNGKey(0))
    draft_params = init_params(draft_config, jax.random.PRNGKey(7))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (3, 5), 0,
                                config.vocab_size)

    ref = generate(params, prompt, 14, config)
    spec = speculative_generate(params, draft_params, prompt, 14, config,
                                draft_config, gamma=3)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(spec))


@pytest.mark.parametrize("gamma", [1, 4, 8])
def test_speculative_gamma_sweep(gamma):
    config = _config()
    draft_config = _config(num_layers=1, d_model=16, d_ff=32, num_heads=2)
    params = init_params(config, jax.random.PRNGKey(0))
    draft_params = init_params(draft_config, jax.random.PRNGKey(7))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0,
                                config.vocab_size)
    ref = generate(params, prompt, 11, config)
    spec = speculative_generate(params, draft_params, prompt, 11, config,
                                draft_config, gamma=gamma)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(spec))


def test_self_draft_accepts_everything():
    """Draft == target: every proposal is accepted, so the loop finishes
    in ceil(max_new / (gamma+1)) rounds with acceptance 1.0 — the
    round-count bound that gives speculative decoding its speedup."""
    config = _config()
    params = init_params(config, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 5), 0,
                                config.vocab_size)
    ref = generate(params, prompt, 12, config)
    spec, stats = speculative_generate(params, params, prompt, 12, config,
                                       config, gamma=3, return_stats=True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(spec))
    assert stats["draft_acceptance"] == 1.0
    assert stats["rounds"] == 3  # ceil((12-1)/4): n0 from prefill, then 4/round


def test_speculative_sampling_runs_and_is_in_range():
    """Sampling mode: correct shapes, in-vocab tokens, and with draft ==
    target every acceptance test passes (p_t/p_d == 1)."""
    config = _config()
    params = init_params(config, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 5), 0,
                                config.vocab_size)
    toks, stats = speculative_generate(
        params, params, prompt, 9, config, config, gamma=2,
        temperature=0.7, key=jax.random.PRNGKey(3), return_stats=True)
    assert toks.shape == (2, 9)
    assert int(toks.min()) >= 0 and int(toks.max()) < config.vocab_size
    assert stats["draft_acceptance"] == 1.0


def test_speculative_validation():
    config = _config()
    draft_small_vocab = _config(vocab_size=64)
    params = init_params(config, jax.random.PRNGKey(0))
    prompt = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(ValueError, match="vocab"):
        speculative_generate(params, params, prompt, 4, config,
                             draft_small_vocab)
    with pytest.raises(ValueError, match="gamma"):
        speculative_generate(params, params, prompt, 4, config, config,
                             gamma=0)
    with pytest.raises(ValueError, match="max_seq_len"):
        speculative_generate(params, params, prompt, 80, config, config)
    with pytest.raises(ValueError, match="PRNG"):
        speculative_generate(params, params, prompt, 4, config, config,
                             temperature=0.5)


def test_negative_temperature_is_greedy():
    """temperature <= 0 decodes greedily, matching generate()'s
    convention (never sampling an inverted distribution)."""
    config = _config()
    params = init_params(config, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 5), 0,
                                config.vocab_size)
    ref = generate(params, prompt, 8, config)
    spec = speculative_generate(params, params, prompt, 8, config, config,
                                gamma=2, temperature=-1.0)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(spec))


def test_model_surface():
    """TransformerModel.speculative_generate wraps the functional API."""
    from elephas_tpu.models.transformer_model import TransformerModel

    config = _config()
    draft_config = _config(num_layers=1, d_model=16, d_ff=32, num_heads=2)
    model = TransformerModel(config)
    model.build(seed=0)
    draft = TransformerModel(draft_config)
    draft.build(seed=7)
    prompt = np.random.default_rng(0).integers(0, config.vocab_size, (2, 5))
    ref = model.generate(prompt, 8)
    spec = model.speculative_generate(draft, prompt, 8, gamma=3)
    np.testing.assert_array_equal(ref, spec)


# paged mode excludes kvq (no int8 pool) and moe (validate_paged_config)
PAGED_VARIANTS = {k: v for k, v in VARIANTS.items()
                  if k not in ("kvq", "moe")}


@pytest.mark.parametrize("variant", sorted(PAGED_VARIANTS))
def test_decode_block_paged_matches_decode_block(variant):
    """The paged verify primitive == the contiguous one on every
    paged-compatible config variant (GQA grouping, window mask, ALiBi
    and sinusoidal position math), at RAGGED per-row positions: same
    logits, and the written pool positions gather back to the same
    cache contents. The engine-level speculative tests drive only the
    default variant, so this is where the variant branches are pinned."""
    from elephas_tpu.models.paged_decode import (decode_block_paged,
                                                 gather_blocks_to_row,
                                                 init_paged_pool,
                                                 install_row_paged)

    config = _config(**PAGED_VARIANTS[variant])
    params = init_params(config, jax.random.PRNGKey(0))
    bs, max_len, s = 8, 32, 4
    lens = [3, 6, 9]                               # ragged rows
    nb = max_len // bs
    pool = init_paged_pool(config, 1 + len(lens) * nb, bs)
    # the rows' prompts, right-padded, in one prefill compiled once: the
    # model is causal, so a row's cache below position n is its own
    # n-token prefill's, and the block at n writes n on and reads no
    # position after its own
    prompts = np.zeros((len(lens), max(lens)), np.int32)
    for r, n in enumerate(lens):
        prompts[r, :n] = jax.random.randint(jax.random.PRNGKey(10 + r),
                                            (1, n), 0, config.vocab_size)
    _, caches = PREFILL(params, jnp.asarray(prompts), config, max_len)
    tables, row_caches = [], []
    for r, n in enumerate(lens):
        row = jax.tree_util.tree_map(lambda a: a[r:r + 1], caches)
        row_caches.append(row)
        ids = [1 + r * nb + j for j in range(nb)]
        pool = install_row_paged(pool, row, ids, nb)
        tables.append(ids)
    toks = jax.random.randint(jax.random.PRNGKey(2), (len(lens), s), 0,
                              config.vocab_size)

    paged_logits, pool = jax.jit(decode_block_paged, static_argnums=5)(
        params, pool, jnp.asarray(tables), toks,
        jnp.asarray(lens, jnp.int32), config)

    for r, n in enumerate(lens):
        ref_logits, ref_cache = DECODE_BLOCK(params, row_caches[r],
                                             toks[r:r + 1], n, config)
        np.testing.assert_allclose(np.asarray(paged_logits[r]),
                                   np.asarray(ref_logits[0]), atol=2e-5)
        got = gather_blocks_to_row(pool, jnp.asarray(tables[r]), max_len)
        for name in ref_cache:
            for kk in ("k", "v"):
                np.testing.assert_allclose(
                    np.asarray(got[name][kk][0, :, :n + s]),
                    np.asarray(ref_cache[name][kk][0, :, :n + s]),
                    atol=1e-5)
