"""The comparisons of a family whose rows keep recurrent state beside
their cached positions (``falcon_h1``), and the plain reference run so
that it fits beside 10 GB of weights.

``check.py``'s step has no slot to install into and prefills its prompt
in one piece. Here the cache path is held to the reference where this
family's mechanism can go wrong: a prompt LONGER than ``prefill_chunk``
goes through the engine's own chunk program (``decode_block`` with the
engine's ladder of widths, the head for the last position only), so the
state crosses a chunk boundary; the finished row is installed into a
slot that held ANOTHER row's state, which a decode step had advanced
since; then one paged step, against the reference's logits at that
position.
"""
import numpy as np

__all__ = ["Reference", "paged_step_vs_reference"]

#: vocabulary columns the reference's head computes at a time
HEAD_BLOCK = 32768


class Reference:
    """The family's plain reference over the program's parameters, the
    layers one at a time and the head in blocks of the vocabulary, so
    that only one layer's float32 copy is alive at once. ``lower`` (a
    control) maps one layer's or the head's parameter subtree to another
    precision inside the jitted piece; ``state_round`` is the
    reference's own."""

    def __init__(self, reference, family, params, config, sizes,
                 lower=None, state_round=None):
        import jax

        self.params, self.layers = params, config.num_layers
        self.vocab = config.vocab_size
        lower = lower or (lambda tree: tree)
        self._embed = jax.jit(lambda table, t: reference.embed(
            lower({"embed": table}), t, sizes))
        self._block = jax.jit(lambda layer, x: reference.block(
            family.to_reference_layer(lower(layer), config), x, sizes,
            state_round))
        # (no piece closes over ``self``: a cycle would keep the
        # parameters on the device until the collector runs)
        self.block_cols = cols = min(HEAD_BLOCK, self.vocab)
        self._head = jax.jit(lambda final, matrix, x, first: reference.head(
            {"final_norm": final, "head": lower({"head": matrix})["head"]},
            x, sizes, first, cols))

    def hidden(self, tokens):
        """Token ids (1, T) -> the last block's output, on the device."""
        p = self.params
        x = self._embed(p["embed"]["tokens"], np.asarray(tokens))
        for i in range(self.layers):
            x = self._block(p[f"layer_{i}"], x)
        return x

    def head(self, x) -> np.ndarray:
        """(..., D) -> float32 logits over the whole vocabulary, block
        by block (the last block is taken from the vocabulary's end and
        its overlap dropped)."""
        p, cols, out = self.params, self.block_cols, []
        for first in range(0, self.vocab, cols):
            start = min(first, self.vocab - cols)
            part = np.asarray(self._head(p["final_ln"]["gamma"], p["head"],
                                         x, start))
            out.append(part[..., first - start:])
        return np.concatenate(out, axis=-1)

    def logits(self, rows) -> np.ndarray:
        """(n, T) -> (n, T, V), one row at a time."""
        return np.stack([self.head(self.hidden(np.asarray(row)[None])[0])
                         for row in np.asarray(rows)])

    def last_logits(self, rows) -> np.ndarray:
        """(n, T) -> (n, V): the last position's logits alone."""
        return np.stack([
            self.head(self.hidden(np.asarray(row)[None])[0, -1])
            for row in np.asarray(rows)])


def paged_step_vs_reference(params, config, last_logits, rows: int,
                            cached: int, engine_sizes: dict, seed: int):
    """``rows`` seeded prompts of ``cached`` tokens (more than a chunk),
    each prefilled chunk by chunk as the engine does it, installed into
    a slot whose state another row and a decode step left behind, one
    ``decode_step_paged``, against ``last_logits(prompt + token)``.
    Returns ``{"max_abs_dlogit", "rms_dlogit", "chunks"}``."""
    import jax
    import jax.numpy as jnp

    from elephas_tpu.models.paged_decode import (decode_step_paged,
                                                 held_ladder,
                                                 init_paged_pool,
                                                 install_row_paged)
    from elephas_tpu.models.transformer import (decode_block,
                                                init_kv_cache,
                                                prefill_ladder)

    block_size = int(engine_sizes["paged"][1])
    chunk = int(engine_sizes["prefill_chunk"])
    max_len = int(engine_sizes["max_len"])
    if not chunk < cached < max_len - 1:
        raise ValueError(f"the step check needs prefill_chunk {chunk} < "
                         f"paged_cached {cached} < max_len {max_len} - 1")
    need = cached // block_size + 1
    prompts = np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), (rows, cached), 1, config.vocab_size))
    widths = prefill_ladder(chunk, max_len)
    extend = jax.jit(lambda p, row, blk, pos: decode_block(
        p, row, blk, pos, config, attend_widths=widths, last_only=True),
        donate_argnums=(1,))
    filled, first = [], []
    for r in range(rows):
        row = init_kv_cache(config, 1, max_len)
        for start in range(0, cached, chunk):
            logits, row = extend(params, row,
                                 jnp.asarray(prompts[r:r + 1,
                                                     start:start + chunk]),
                                 jnp.int32(start))
        filled.append(row)
        first.append(int(np.argmax(np.asarray(logits[0, -1]))))
    first = np.asarray(first, np.int32)
    pool = init_paged_pool(config, 1 + rows * need, block_size, slots=rows)
    tables = np.zeros((rows, need), np.int32)
    for r in range(rows):
        tables[r] = 1 + r * need + np.arange(need)
    mb = -(-max_len // block_size)
    step = jax.jit(lambda p, pl, tb, tk, ps: decode_step_paged(
        p, pl, tb, tk, ps, config,
        held_blocks=held_ladder(config, rows, mb))[:2], donate_argnums=(1,))
    at = jnp.full((rows,), cached, jnp.int32)
    # every slot first holds ANOTHER row (the next one's), and a step
    # advances what it left there; then each row moves into its own slot
    for shift in ((1, 0) if rows > 1 else (0,)):
        for r in range(rows):
            slot = (r + shift) % rows
            pool = install_row_paged(pool, filled[r], tables[slot], need,
                                     slot=slot)
        got, pool = step(params, pool, jnp.asarray(tables),
                         jnp.asarray(np.roll(first, shift)), at)
    got = np.asarray(got, np.float32)
    want = last_logits(np.concatenate([prompts, first[:, None]], axis=1))
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        return {"max_abs_dlogit": float("inf"),
                "rms_dlogit": float("inf"), "chunks": -(-cached // chunk)}
    diff = got.astype(np.float64) - want
    return {"max_abs_dlogit": float(np.abs(diff).max()),
            "rms_dlogit": float(np.sqrt(np.mean(diff * diff))),
            "chunks": -(-cached // chunk)}
