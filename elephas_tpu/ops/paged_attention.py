"""Paged decode attention as a Pallas TPU kernel.

The serving engine's paged decode step
(:func:`~elephas_tpu.models.paged_decode.decode_step_paged`) reads the
KV cache by materializing a gathered copy of the blocks its rows hold
— one extra pass over the held K/V per layer per step — and then runs
a masked softmax over it. This module fuses the gather INTO the
attention loop, over each row's whole table: the kernel's
``BlockSpec`` index map reads the block table (scalar-prefetched into
SMEM) and DMAs each block of k/v straight from its pool slot into
VMEM, accumulating flash-style online softmax across the row's blocks.
The (B, MB*bs, D) gathered view is never materialized.

Grid ``(batch, max_blocks)`` with the block axis innermost
(sequential): one program attends one row's query heads against one
pool block. GQA runs as an unrolled loop over kv heads inside the
kernel — each kv head's ``groups`` query rows share its k/v tile.
Blocks entirely past the row's position (or entirely outside its
sliding window) are skipped before any compute. ALiBi biases are baked
in as compile-time constants (slopes are a pure function of the head
count). All accumulation is f32 regardless of pool dtype.

This kernel covers the S=1 decode step — the tokens/s hot path, where
the gather pass is pure overhead. The S>1 verify pass of speculative
decoding keeps the gather path (its cost amortizes over gamma+1
positions and its mask is 2-D).

Numerics: online softmax is algebraically identical to the gather
path's full-row softmax but associates the reduction differently, so
logits agree to float rounding (parity-tested across the attention
variant matrix), not bit-for-bit.

On non-TPU backends the kernel runs via the Pallas interpreter
(``interpret=True``) — correct but slow, which is why the ENGINE falls
back to the gather path off-TPU and only the parity tests drive the
interpreter directly.
"""
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_attention import NEG_INF, _use_interpret

__all__ = ["paged_decode_attention", "pallas_supported"]


def pallas_supported() -> bool:
    """True when the compiled (non-interpreted) kernel can run here —
    the engine's ``kernel="pallas"`` fallback check."""
    return jax.default_backend() == "tpu"


def _paged_kernel(tables_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, bs: int, kvh: int,
                  groups: int, scale: float, window: Optional[int],
                  slopes: Optional[tuple]):
    b = pl.program_id(0)
    j = pl.program_id(1)
    nb = pl.num_programs(1)
    pos = pos_ref[b, 0]

    @pl.when(j == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # skip blocks wholly past the causal frontier — and, with a sliding
    # window, wholly before it. Table entries past the row's allocation
    # are the scratch sink (id 0): their positions sit past ``pos`` so
    # this same predicate skips them without reading them.
    live = j * bs <= pos
    if window is not None:
        live = live & (j * bs + bs - 1 > pos - window)

    @pl.when(live)
    def _():
        kpos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        valid = kpos <= pos
        if window is not None:
            valid = valid & (kpos > pos - window)
        if slopes is not None:
            dist = (pos - kpos).astype(jnp.float32)        # (1, bs)
        for n in range(kvh):
            lo = n * groups
            qh = q_ref[0, lo:lo + groups, :]               # (G, D)
            s = jax.lax.dot_general(
                qh, k_ref[0, n], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (G, bs)
            if slopes is not None:
                # slopes are python floats (compile-time constants):
                # scalar multiplies, no captured-array constant
                s = s - jnp.concatenate(
                    [dist * slopes[lo + g] for g in range(groups)],
                    axis=0)
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_ref[lo:lo + groups, 0]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            p = jnp.where(valid, jnp.exp(s - m_new[:, None]), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_ref[lo:lo + groups, 0] = (l_ref[lo:lo + groups, 0] * corr
                                        + jnp.sum(p, axis=-1))
            acc_ref[lo:lo + groups, :] = (
                acc_ref[lo:lo + groups, :] * corr[:, None]
                + jax.lax.dot_general(
                    p.astype(v_ref.dtype), v_ref[0, n],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))
            m_ref[lo:lo + groups, 0] = m_new

    @pl.when(j == nb - 1)
    def _():
        l = l_ref[:, 0]
        o_ref[0] = (acc_ref[:]
                    / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def paged_decode_attention(q: jnp.ndarray, k_pool: jnp.ndarray,
                           v_pool: jnp.ndarray, tables: jnp.ndarray,
                           pos: jnp.ndarray,
                           window: Optional[int] = None,
                           alibi_slopes=None,
                           interpret: Optional[bool] = None
                           ) -> jnp.ndarray:
    """Single-position paged attention straight off the block pool.

    :param q: ``(B, num_heads, head_dim)`` queries (positional encoding
        already applied — the kernel sees post-RoPE values, exactly what
        the gather path's einsum sees).
    :param k_pool: ``(num_blocks, kv_heads, block_size, head_dim)``
        pool tensor AFTER this step's k scatter (the current position's
        key is already in its owning block).
    :param v_pool: same shape, values.
    :param tables: ``(B, max_blocks)`` int block ids per row.
    :param pos: ``(B,)`` int current position per row; keys at
        ``kpos <= pos`` (within ``window`` if set) are attended.
    :param window: optional sliding-window width (attend
        ``kpos > pos - window``).
    :param alibi_slopes: optional per-query-head slope array ``(H,)``;
        adds the ``-slope * (pos - kpos)`` ALiBi bias. Must be
        CONCRETE (slopes are a function of the head count, not of
        data) — they are baked into the kernel as constants.
    :param interpret: force/forbid the Pallas interpreter; default
        auto (compiled on TPU, interpreter elsewhere).
    :returns: ``(B, num_heads, head_dim)`` attention output in
        ``q.dtype``.
    """
    b, h, d = q.shape
    _, kvh, bs, _ = k_pool.shape
    if h % kvh:
        raise ValueError(f"kv heads {kvh} must divide query heads {h}")
    mb = tables.shape[1]
    slopes = None
    if alibi_slopes is not None:
        sl = np.asarray(alibi_slopes, np.float32).reshape(-1)
        if sl.shape[0] != h:
            raise ValueError(f"{sl.shape[0]} ALiBi slopes for {h} heads")
        slopes = tuple(float(s) for s in sl)
    kernel = functools.partial(
        _paged_kernel, bs=bs, kvh=kvh, groups=h // kvh,
        scale=1.0 / math.sqrt(d),
        window=int(window) if window is not None else None,
        slopes=slopes)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, mb),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda bi, j, tbl, ps: (bi, 0, 0)),
            # the fused gather: the index map reads the row's table and
            # streams that pool block HBM -> VMEM, no gathered copy
            pl.BlockSpec((1, kvh, bs, d),
                         lambda bi, j, tbl, ps: (tbl[bi, j], 0, 0, 0)),
            pl.BlockSpec((1, kvh, bs, d),
                         lambda bi, j, tbl, ps: (tbl[bi, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, d),
                               lambda bi, j, tbl, ps: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, d), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_use_interpret(interpret),
    )(jnp.asarray(tables, jnp.int32),
      jnp.asarray(pos, jnp.int32).reshape(b, 1), q, k_pool, v_pool)
