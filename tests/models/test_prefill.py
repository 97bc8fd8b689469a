"""Batched prefill: one forward pass fills the decode cache; must be
bit-consistent with the token-by-token decode path across config
variants, and generate's fast path must produce identical greedy output
to the unified ragged scan."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elephas_tpu.models.transformer import (TransformerConfig, generate,
                                            init_params)

from ._transformer_util import DECODE_STEP, FORWARD, PREFILL


def _config(**overrides):
    base = dict(vocab_size=128, num_layers=2, num_heads=4, d_model=32,
                d_ff=64, max_seq_len=48)
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


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_prefill_then_decode_matches_stepwise(variant):
    """prefill_cache(prompt) + decode_step continuation == teacher-
    forcing every token through decode_step (cache contents and logits
    agree)."""
    config = _config(**VARIANTS[variant])
    params = init_params(config, jax.random.PRNGKey(0))
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, 12),
                                           0, config.vocab_size))
    prompt_len, total = 8, 12

    # stepwise reference
    from elephas_tpu.models.transformer import init_kv_cache

    cache_ref = init_kv_cache(config, 2, max_len=total)
    for t in range(total):
        logits_ref, cache_ref = DECODE_STEP(
            params, cache_ref, jnp.asarray(tokens[:, t]), t, config)

    # prefill + stepwise continuation
    logits_pf, cache_pf = PREFILL(params, jnp.asarray(
        tokens[:, :prompt_len]), config, max_len=total)
    # prefill's last-position logits == stepwise logits at that position
    cache_chk = init_kv_cache(config, 2, max_len=total)
    for t in range(prompt_len):
        step_logits, cache_chk = DECODE_STEP(
            params, cache_chk, jnp.asarray(tokens[:, t]), t, config)
    np.testing.assert_allclose(np.asarray(logits_pf),
                               np.asarray(step_logits), atol=2e-3,
                               rtol=2e-3)
    for t in range(prompt_len, total):
        logits_pf, cache_pf = DECODE_STEP(
            params, cache_pf, jnp.asarray(tokens[:, t]), t, config)
    np.testing.assert_allclose(np.asarray(logits_pf),
                               np.asarray(logits_ref), atol=2e-3,
                               rtol=2e-3)
    # and both agree with the batched forward at the last position —
    # except under kv_cache_quant, where decode attends over the int8
    # cache while forward uses full-precision k/v (int8-level gap by
    # design; the prefill-vs-stepwise consistency above is the contract)
    if not config.kv_cache_quant:
        fwd = np.asarray(FORWARD(params, jnp.asarray(tokens),
                                 config))[:, -1]
        np.testing.assert_allclose(np.asarray(logits_pf), fwd, atol=2e-3,
                                   rtol=2e-3)


def test_fast_path_greedy_equals_ragged_scan():
    """Uniform prompts: the prefill fast path and the unified ragged
    scan (forced via prompt_lengths) emit identical greedy tokens."""
    config = _config()
    params = init_params(config, jax.random.PRNGKey(0))
    prompt = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (3, 7),
                                           0, config.vocab_size))
    fast = np.asarray(generate(params, prompt, 10, config))
    slow = np.asarray(generate(params, prompt, 10, config,
                               prompt_lengths=np.full(3, 7)))
    np.testing.assert_array_equal(fast, slow)


def test_fast_path_single_new_token():
    config = _config()
    params = init_params(config, jax.random.PRNGKey(0))
    prompt = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, 5),
                                           0, config.vocab_size))
    out = np.asarray(generate(params, prompt, 1, config))
    assert out.shape == (2, 1)
    slow = np.asarray(generate(params, prompt, 1, config,
                               prompt_lengths=np.full(2, 5)))
    np.testing.assert_array_equal(out, slow)


def test_fast_path_repetition_penalty_semantics():
    """Rep penalty through the fast path matches the ragged scan: the
    prompt marks the seen buffer, then each emitted token does.

    The two paths are different XLA programs, so their f32 logits
    differ in the last ulps and an argmax near-tie can flip between
    them on a given machine, cascading for the rest of that row (the
    PR 2/PR 7 machine-numerics class). Comparison is therefore token-
    exact UP TO a provable near-tie: at a row's first divergence the
    penalized next-token logits are recomputed from ``forward`` plus
    the documented CTRL rule, and the top-2 gap must sit below the
    cross-program noise — a genuine seen-buffer bug (prompt unmarked,
    emissions unmarked) perturbs penalized logits by a factor of p on
    O(0.1+) values and still fails decisively."""
    from elephas_tpu.models.transformer import forward

    config = _config()
    p = 1.4
    params = init_params(config, jax.random.PRNGKey(0))
    prompt = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, 6),
                                           0, config.vocab_size))
    fast = np.asarray(generate(params, prompt, 8, config,
                               repetition_penalty=p))
    slow = np.asarray(generate(params, prompt, 8, config,
                               repetition_penalty=p,
                               prompt_lengths=np.full(2, 6)))

    def penalized_next_logits(prefix):
        # reference semantics, recomputed independently: every prefix
        # token (prompt or emitted) is "seen"; CTRL shrinks seen
        # tokens' logits toward less-likely on either side of zero
        logits = np.asarray(
            forward(params, np.asarray([prefix], np.int32), config)
            [0, -1], np.float32).copy()
        seen = sorted(set(int(t) for t in prefix))
        for tok in seen:
            logits[tok] = (logits[tok] / p if logits[tok] > 0
                           else logits[tok] * p)
        return logits

    for b in range(fast.shape[0]):
        for t in range(fast.shape[1]):
            if int(fast[b, t]) == int(slow[b, t]):
                continue
            prefix = ([int(x) for x in prompt[b]]
                      + [int(x) for x in fast[b, :t]])
            logits = penalized_next_logits(prefix)
            # BOTH divergent tokens must be the near-tied pair: a
            # seen-buffer bug emitting an unrelated token fails even
            # at a step where some other pair happens to tie
            top = float(logits.max())
            gap_fast = top - float(logits[int(fast[b, t])])
            gap_slow = top - float(logits[int(slow[b, t])])
            assert max(gap_fast, gap_slow) < 1e-3, (
                f"row {b} diverges at step {t} ({fast[b, t]} vs "
                f"{slow[b, t]}) and the tokens are NOT a near-tied "
                f"pair (penalized gaps to max: {gap_fast:.6f} / "
                f"{gap_slow:.6f}) — a real semantics mismatch")
            break   # post-tie tokens legitimately diverge


@pytest.mark.parametrize("variant", ["base", "gqa", "window", "kvq"])
def test_chunked_prefill_matches_whole(variant):
    """prefill_cache_chunked == prefill_cache (logits + cache), incl.
    a chunk size that does not divide the prompt length."""
    from elephas_tpu.models.transformer import (prefill_cache,
                                                prefill_cache_chunked)

    config = _config(**VARIANTS[variant])
    params = init_params(config, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 11), 0,
                                config.vocab_size)
    # op by op on purpose: in bfloat16 the two paths round alike only
    # where each operation rounds on its own, and the tolerance holds
    # them to that
    ref_logits, ref_cache = prefill_cache(params, prompt, config, 24)
    for chunk in (4, 11, 16):
        lg, cache = prefill_cache_chunked(params, prompt, config, 24,
                                          chunk=chunk)
        np.testing.assert_allclose(np.asarray(lg), np.asarray(ref_logits),
                                   atol=2e-5)
        for k in ref_cache:
            for kk in ref_cache[k]:
                np.testing.assert_allclose(
                    np.asarray(cache[k][kk], dtype=np.float32),
                    np.asarray(ref_cache[k][kk], dtype=np.float32),
                    atol=2e-5)
