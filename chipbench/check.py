"""The comparisons that decide ``correct``, all outside the timed window.

The methods are ``chip_smoke.py``'s (PR 21), pointed at the independent
plain reference in ``chipbench/reference/`` instead of the program's own
``forward``. Logits are compared, not tokens: with seeded weights the
largest logit changes on rounding.
"""
import numpy as np

__all__ = ["paged_step_vs_reference", "logit_margins", "losses_ok"]


def paged_step_vs_reference(params, config, ref_logits, rows: int,
                            cached: int, block_size: int, seed: int):
    """One ``prefill_cache`` + ``install_row_paged`` +
    ``decode_step_paged`` step (``rows`` rows, ``cached`` positions each)
    against the reference's logits at the same position: holds the whole
    cache path (prefill, block install, table lookup, attention over the
    pool) to a number. Returns max |logit - reference logit|."""
    import jax
    import jax.numpy as jnp

    from elephas_tpu.models.paged_decode import (decode_step_paged,
                                                 init_paged_pool,
                                                 install_row_paged)
    from elephas_tpu.models.transformer import prefill_cache

    need = cached // block_size + 1
    max_len = need * block_size
    prompts = jax.random.randint(jax.random.PRNGKey(seed), (rows, cached),
                                 1, config.vocab_size)
    logits, cache = jax.jit(
        lambda p, t: prefill_cache(p, t, config, max_len))(params, prompts)
    pool = init_paged_pool(config, 1 + rows * need, block_size)
    tables = np.zeros((rows, need), np.int32)
    for r in range(rows):
        tables[r] = 1 + r * need + np.arange(need)
        row = jax.tree_util.tree_map(lambda a: a[r:r + 1], cache)
        pool = install_row_paged(pool, row, tables[r], need)
    last = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    step = jax.jit(lambda p, pl, tb, tk, ps: decode_step_paged(
        p, pl, tb, tk, ps, config)[0])
    got = np.asarray(step(params, pool, jnp.asarray(tables), last,
                          jnp.full((rows,), cached, jnp.int32)), np.float32)
    want = ref_logits(np.concatenate(
        [np.asarray(prompts), np.asarray(last)[:, None]], axis=1))[:, -1]
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        return float("inf")
    return float(np.abs(got - want).max())


def logit_margins(ref_logits, prompts, outputs, pad_to: int) -> list:
    """Teacher-force prompt + output through the reference, one request
    at a time in rows of the fixed width ``pad_to`` (one compiled shape;
    causal, so the right padding touches nothing before it). For every
    request the worst distance of an emitted token's logit below that
    position's maximum: 0 means the float32 model picks the same
    token."""
    worst = []
    for prompt, output in zip(prompts, outputs):
        row = np.zeros((1, pad_to), np.int32)
        seq = list(prompt) + list(output)
        row[0, :len(seq)] = seq
        logits = ref_logits(row)[0]
        margin = 0.0
        for j, tok in enumerate(output):
            at = logits[len(prompt) + j - 1]      # predicts len(prompt)+j
            margin = max(margin, float(at.max() - at[tok]))
        worst.append(margin)
    return worst


def losses_ok(losses) -> bool:
    """Training made progress: every loss finite, the last below the
    first."""
    losses = np.asarray(losses, np.float64)
    return bool(losses.size >= 2 and np.isfinite(losses).all()
                and losses[-1] < losses[0])
