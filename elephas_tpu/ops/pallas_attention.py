"""Flash attention as Pallas TPU kernels (forward + backward).

The reference framework has no attention at all (SURVEY.md §5 — its largest
model is an MLP), so this module is pure TPU-native upside: the flagship
transformer's hot op written against the MXU/VMEM directly instead of
through XLA's generic fusion.

Design (flash-attention v2 recurrence):

- Forward grid ``(batch*heads, q_blocks, kv_blocks)`` — the kv axis is the
  innermost (sequential) grid dimension, so the online-softmax accumulators
  live in VMEM scratch across kv steps while ``BlockSpec`` index maps
  stream q/k/v tiles HBM -> VMEM. Never materializes the ``(seq, seq)``
  score matrix.
- Backward is two kernels sharing the saved per-row logsumexp: ``dq`` over
  ``(bh, q_blocks, kv_blocks)`` and ``dk/dv`` over ``(bh, kv_blocks,
  q_blocks)``; ``delta = rowsum(dO * O)`` is precomputed with plain jnp.
- All accumulation is f32 regardless of input dtype (bf16 inputs hit the
  MXU; softmax statistics stay f32 for stability).
- Ragged sequence lengths are handled by padding to block multiples and
  masking both key and query validity inside the kernels.

On non-TPU backends the same kernels run via the Pallas interpreter
(``interpret=True``), which is how the CPU test suite exercises them.
"""
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

__all__ = ["flash_attention", "flash_attention_sharded"]


def _use_interpret(interpret: Optional[bool]) -> bool:
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


# --------------------------------------------------------------------- fwd
def _fwd_kernel(qo_ref, ko_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref,
                *, sq: int, sk: int, block_q: int, block_k: int,
                causal: bool, scale: float, window=None):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)
    # global-position offsets (SMEM scalars): 0 for plain attention;
    # under ring/sequence parallelism they place this device's q shard
    # and the current hop's k/v shard on the global sequence axis, so
    # causal/band masking and block skipping see global positions
    q_off = qo_ref[0, 0]
    k_off = ko_ref[0, 0]

    @pl.when(kj == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # skip blocks strictly above the causal diagonal — and, with a
    # sliding window, blocks entirely below it
    diag_reached = ((not causal)
                    or (k_off + kj * block_k
                        <= q_off + qi * block_q + block_q - 1))
    if window is not None:
        in_band = (k_off + kj * block_k + block_k - 1
                   > q_off + qi * block_q - window)
        diag_reached = diag_reached & in_band

    @pl.when(diag_reached)
    def _():
        # native-dtype operands into the MXU (bf16 multiply, f32 accumulate
        # via preferred_element_type) — casting to f32 first would force a
        # 4x-slower f32 MXU pass
        s = jax.lax.dot_general(q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale

        q_loc = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_loc = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        # padding bounds are local to the shard; causal/band are global
        valid = k_loc < sk
        if causal:
            valid = valid & (k_off + k_loc <= q_off + q_loc)
        if window is not None:
            valid = valid & (k_off + k_loc > q_off + q_loc - window)
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        # fully-masked rows (query padding): keep p exactly zero
        p = jnp.where(valid, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[:, 0] = l_ref[:, 0] * corr + jnp.sum(p, axis=-1)
        acc_ref[:] = acc_ref[:] * corr[:, None] + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:, 0] = m_new

    @pl.when(kj == nk - 1)
    def _():
        l = l_ref[:, 0]
        o_ref[0] = (acc_ref[:] / jnp.maximum(l, 1e-30)[:, None]).astype(
            o_ref.dtype)
        # lse is (block_q, 1): trailing dims (block_q, 1) satisfy the TPU
        # (8, 128)-or-full-dim tile rule, which a (1, block_q) block doesn't
        # fully-masked rows keep lse = NEG_INF-ish so a cross-hop merge
        # weights them to zero
        lse_ref[0] = (m_ref[:, 0] + jnp.log(jnp.maximum(l, 1e-30)))[:, None]


def _as_offset(x):
    """Scalar offset -> (1, 1) int32 array for the SMEM block spec."""
    return jnp.asarray(x, jnp.int32).reshape(1, 1)


#: whole-array SMEM placement for the (1, 1) int32 offset scalars
_SMEM_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


def _fwd(q, k, v, causal, block_q, block_k, interpret, window=None,
         q_offset=0, k_offset=0):
    b, h, sq, d = q.shape
    kvh = k.shape[1]
    grp = h // kvh
    sk = k.shape[2]
    scale = 1.0 / math.sqrt(d)
    sq_p, sk_p = _round_up(sq, block_q), _round_up(sk, block_k)

    qr = jnp.pad(q, ((0, 0), (0, 0), (0, sq_p - sq), (0, 0))).reshape(
        b * h, sq_p, d)
    kr = jnp.pad(k, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0))).reshape(
        b * kvh, sk_p, d)
    vr = jnp.pad(v, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0))).reshape(
        b * kvh, sk_p, d)

    def kv_row(bh):
        # GQA: query row bh = bi*h + hi reads kv row bi*kvh + hi//grp
        return (bh // h) * kvh + (bh % h) // grp

    grid = (b * h, sq_p // block_q, sk_p // block_k)
    kernel = functools.partial(_fwd_kernel, sq=sq, sk=sk, block_q=block_q,
                               block_k=block_k, causal=causal, scale=scale,
                               window=window)
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            _SMEM_SPEC,
            _SMEM_SPEC,
            pl.BlockSpec((1, block_q, d), lambda bh, qi, kj: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, qi, kj: (kv_row(bh), kj, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, qi, kj: (kv_row(bh), kj, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, kj: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, qi, kj: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq_p, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, sq_p, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_use_interpret(interpret),
    )(_as_offset(q_offset), _as_offset(k_offset), qr, kr, vr)
    return (o[:, :sq].reshape(b, h, sq, d),
            lse[:, :sq, 0].reshape(b, h, sq))


# --------------------------------------------------------------------- bwd
def _dq_kernel(qo_ref, ko_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
               delta_ref, dq_ref,
               dq_acc, *, sq: int, sk: int, block_q: int, block_k: int,
               causal: bool, scale: float, window=None):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)
    q_off = qo_ref[0, 0]
    k_off = ko_ref[0, 0]

    @pl.when(kj == 0)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    diag_reached = ((not causal)
                    or (k_off + kj * block_k
                        <= q_off + qi * block_q + block_q - 1))
    if window is not None:
        diag_reached = diag_reached & (k_off + kj * block_k + block_k - 1
                                       > q_off + qi * block_q - window)

    @pl.when(diag_reached)
    def _():
        s = jax.lax.dot_general(q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        q_loc = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_loc = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        valid = k_loc < sk
        if causal:
            valid = valid & (k_off + k_loc <= q_off + q_loc)
        if window is not None:
            valid = valid & (k_off + k_loc > q_off + q_loc - window)
        p = jnp.where(valid, jnp.exp(s - lse_ref[0]), 0.0)
        dp = jax.lax.dot_general(do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0]) * scale
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kj == nk - 1)
    def _():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _dkv_kernel(qo_ref, ko_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, sq: int, sk: int,
                block_q: int, block_k: int, causal: bool, scale: float,
                nq_blocks: int, window=None):
    kj = pl.program_id(1)
    t = pl.program_id(2)
    # the trailing grid axis enumerates (group member, q block): every
    # query head sharing this kv head accumulates into the same dk/dv
    qi = t % nq_blocks
    total = pl.num_programs(2)
    q_off = qo_ref[0, 0]
    k_off = ko_ref[0, 0]

    @pl.when(t == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    diag_reached = ((not causal)
                    or (k_off + kj * block_k
                        <= q_off + qi * block_q + block_q - 1))
    if window is not None:
        diag_reached = diag_reached & (k_off + kj * block_k + block_k - 1
                                       > q_off + qi * block_q - window)

    @pl.when(diag_reached)
    def _():
        s = jax.lax.dot_general(q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        q_loc = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_loc = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        # mask BOTH query padding (q_loc >= sq would use garbage lse) and
        # key validity/causality
        valid = (k_loc < sk) & (q_loc < sq)
        if causal:
            valid = valid & (k_off + k_loc <= q_off + q_loc)
        if window is not None:
            valid = valid & (k_off + k_loc > q_off + q_loc - window)
        p = jnp.where(valid, jnp.exp(s - lse_ref[0]), 0.0)
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do_ref.dtype), do_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0]) * scale
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q_ref.dtype), q_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(t == total - 1)
    def _():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd(causal, block_q, block_k, interpret, window, residuals, g):
    q, k, v, o, lse = residuals
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    return _bwd_calls(q, k, v, g, lse, delta, causal, block_q, block_k,
                      interpret, window)


def _bwd_calls(q, k, v, g, lse, delta, causal, block_q, block_k, interpret,
               window, q_offset=0, k_offset=0):
    """dq/dk/dv kernel dispatch given precomputed lse and delta.

    ``lse``/``delta`` may be GLOBAL row statistics (ring attention:
    softmax over the whole sequence factorizes as exp(s - lse_global), so
    a per-shard backward with global statistics yields exact gradients).
    """
    b, h, sq, d = q.shape
    kvh = k.shape[1]
    grp = h // kvh
    sk = k.shape[2]
    scale = 1.0 / math.sqrt(d)
    sq_p, sk_p = _round_up(sq, block_q), _round_up(sk, block_k)

    def prep(x, s_pad):
        rows = x.shape[0] * x.shape[1]
        return jnp.pad(x, ((0, 0), (0, 0), (0, s_pad - x.shape[2]),
                           (0, 0))).reshape(rows, s_pad, x.shape[3])

    qr, dor = prep(q, sq_p), prep(g, sq_p)
    kr, vr = prep(k, sk_p), prep(v, sk_p)
    # rows as (bh, seq, 1): trailing block dims (block_q, 1) fit TPU tiling
    lser = jnp.pad(lse, ((0, 0), (0, 0), (0, sq_p - sq))).reshape(
        b * h, sq_p, 1)
    deltar = jnp.pad(delta, ((0, 0), (0, 0), (0, sq_p - sq))).reshape(
        b * h, sq_p, 1)

    interp = _use_interpret(interpret)
    common = dict(sq=sq, sk=sk, block_q=block_q, block_k=block_k,
                  causal=causal, scale=scale, window=window)

    def kv_row(bh):
        return (bh // h) * kvh + (bh % h) // grp

    q_spec = pl.BlockSpec((1, block_q, d), lambda bh, qi, kj: (bh, qi, 0))
    k_spec = pl.BlockSpec((1, block_k, d),
                          lambda bh, qi, kj: (kv_row(bh), kj, 0))
    row_spec = pl.BlockSpec((1, block_q, 1), lambda bh, qi, kj: (bh, qi, 0))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **common),
        grid=(b * h, sq_p // block_q, sk_p // block_k),
        in_specs=[_SMEM_SPEC, _SMEM_SPEC,
                  q_spec, k_spec, k_spec, q_spec, row_spec, row_spec],
        out_specs=[q_spec],
        out_shape=[jax.ShapeDtypeStruct((b * h, sq_p, d), q.dtype)],
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interp,
    )(_as_offset(q_offset), _as_offset(k_offset),
      qr, kr, vr, dor, lser, deltar)[0]

    # kv-major grid over the NARROW kv rows; the trailing axis walks
    # (group member, q block) so all grp query heads sharing a kv head
    # accumulate into its dk/dv block
    nq = sq_p // block_q

    def q_row(bkv, t):
        return (bkv // kvh) * h + (bkv % kvh) * grp + t // nq

    q_spec_t = pl.BlockSpec((1, block_q, d),
                            lambda bkv, kj, t: (q_row(bkv, t), t % nq, 0))
    k_spec_t = pl.BlockSpec((1, block_k, d),
                            lambda bkv, kj, t: (bkv, kj, 0))
    row_spec_t = pl.BlockSpec((1, block_q, 1),
                              lambda bkv, kj, t: (q_row(bkv, t), t % nq, 0))

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, nq_blocks=nq, **common),
        grid=(b * kvh, sk_p // block_k, grp * nq),
        in_specs=[_SMEM_SPEC, _SMEM_SPEC,
                  q_spec_t, k_spec_t, k_spec_t, q_spec_t, row_spec_t,
                  row_spec_t],
        out_specs=[k_spec_t, k_spec_t],
        out_shape=[jax.ShapeDtypeStruct((b * kvh, sk_p, d), k.dtype),
                   jax.ShapeDtypeStruct((b * kvh, sk_p, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interp,
    )(_as_offset(q_offset), _as_offset(k_offset),
      qr, kr, vr, dor, lser, deltar)

    return (dq[:, :sq].reshape(b, h, sq, d),
            dk[:, :sk].reshape(b, kvh, sk, d),
            dv[:, :sk].reshape(b, kvh, sk, d))


# ------------------------------------------------------------- ring hops
def _clamp_blocks(block_q, block_k, sq, sk):
    # round to 32 rows — a multiple of every dtype's min sublane tile
    return (min(block_q, _round_up(sq, 32)), min(block_k, _round_up(sk, 32)))


def flash_hop_forward(q, k, v, q_offset, k_offset, causal: bool = True,
                      window: Optional[int] = None, block_q: int = 256,
                      block_k: int = 512, interpret: Optional[bool] = None):
    """One ring-attention hop through the flash kernel: block attention of
    the local q shard against one circulating k/v shard, masked on GLOBAL
    positions (``q_offset``/``k_offset`` are traced per-device scalars).

    Returns ``(o, lse)`` — per-hop normalized output and logsumexp row
    statistics, merged across hops by the caller. NOT differentiable;
    ring attention's custom VJP calls :func:`flash_hop_backward`.
    """
    block_q, block_k = _clamp_blocks(block_q, block_k, q.shape[2],
                                     k.shape[2])
    return _fwd(q, k, v, causal, block_q, block_k, interpret, window,
                q_offset=q_offset, k_offset=k_offset)


def flash_hop_backward(q, k, v, g, lse, delta, q_offset, k_offset,
                       causal: bool = True, window: Optional[int] = None,
                       block_q: int = 256, block_k: int = 512,
                       interpret: Optional[bool] = None):
    """Per-hop backward with GLOBAL row statistics: softmax over the full
    ring factorizes as ``exp(s - lse_global)``, so dq/dk/dv for this hop's
    shard pair are exact given the global ``lse`` and
    ``delta = rowsum(dO * O_global)``."""
    block_q, block_k = _clamp_blocks(block_q, block_k, q.shape[2],
                                     k.shape[2])
    return _bwd_calls(q, k, v, g, lse, delta, causal, block_q, block_k,
                      interpret, window, q_offset=q_offset,
                      k_offset=k_offset)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, block_q, block_k, interpret, window):
    o, _ = _fwd(q, k, v, causal, block_q, block_k, interpret, window)
    return o


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret, window):
    o, lse = _fwd(q, k, v, causal, block_q, block_k, interpret, window)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, block_q, block_k, interpret, window, residuals, g):
    return _bwd(causal, block_q, block_k, interpret, window, residuals, g)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = False, block_q: int = 256,
                    block_k: int = 512,
                    interpret: Optional[bool] = None,
                    window: Optional[int] = None) -> jnp.ndarray:
    """Flash attention over ``(batch, heads, seq, head_dim)`` tensors.

    Differentiable (custom VJP with Pallas backward kernels). ``interpret``
    defaults to auto: compiled on TPU, interpreter elsewhere. Block sizes
    should stay multiples of the f32 min tile (8, 128) on real hardware;
    sequence lengths need not be multiples of the block size.
    """
    if q.ndim != 4:
        raise ValueError(f"expected (batch, heads, seq, head_dim), got "
                         f"{q.shape}")
    if q.shape[1] % k.shape[1]:
        raise ValueError(
            f"kv heads {k.shape[1]} must divide query heads {q.shape[1]} "
            "(GQA)")
    # clamp blocks for short sequences, rounding to 32 rows — a multiple of
    # every dtype's min sublane tile (8 f32 / 16 bf16 / 32 int8)
    if window is not None and window < 1:
        raise ValueError("window must be >= 1")
    block_q = min(block_q, _round_up(q.shape[2], 32))
    block_k = min(block_k, _round_up(k.shape[2], 32))
    return _flash(q, k, v, causal, block_q, block_k, interpret,
                  int(window) if window is not None else None)


def flash_attention_sharded(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                            mesh, causal: bool = False,
                            batch_axis: Optional[str] = None,
                            head_axis: Optional[str] = None,
                            block_q: int = 256, block_k: int = 512,
                            interpret: Optional[bool] = None,
                            window: Optional[int] = None) -> jnp.ndarray:
    """Flash attention under a device mesh.

    The Mosaic kernel has no SPMD partitioning rule, so a bare
    :func:`flash_attention` inside a GSPMD-jitted program either fails to
    partition or replicates. Attention is independent per (batch, head), so
    dp/tp sharding needs no communication at all: ``shard_map`` pins the
    batch axis to ``batch_axis`` (data parallel) and the head axis to
    ``head_axis`` (Megatron tensor parallel — the same axis the qkv/out
    projections shard over), and each device runs the kernel on its local
    ``(b/dp, h/tp, seq, d)`` block. Sequence parallelism is NOT handled
    here — that is :func:`~elephas_tpu.ops.ring_attention.ring_attention_sharded`.
    """
    from functools import partial as _partial

    from jax.sharding import PartitionSpec as _P

    spec = _P(batch_axis, head_axis, None, None)
    fn = jax.shard_map(
        _partial(flash_attention, causal=causal, block_q=block_q,
                 block_k=block_k, interpret=interpret, window=window),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)
