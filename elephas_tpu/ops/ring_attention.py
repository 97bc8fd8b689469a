"""Ring attention: sequence/context parallelism over a mesh axis.

Long sequences are sharded across devices along a ``seq`` mesh axis; each
device holds a query shard and streams key/value shards around the ring
with ``lax.ppermute`` (compiled to ICI neighbor exchanges on TPU), folding
each incoming block into a flash-attention online-softmax accumulator. HBM
and VMEM footprint per device is O(seq/P), enabling context lengths that
cannot fit on one chip — the "long-context first-class" requirement the
TPU framework adds over the reference (SURVEY.md §5 lists it absent there).

Communication overlaps with compute: at ring step i every device computes
scores against the shard it currently holds while the next shard is in
flight — the classic ring-attention schedule.

Use inside ``shard_map`` with ``q, k, v`` already sharded on the sequence
axis; see :func:`ring_attention_sharded` for the wrapped entry point.
"""
import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

NEG_INF = -1e30


def ring_num_hops(axis_size: int, shard_len: int,
                  window: Optional[int]) -> int:
    """Ring hops a causal sliding-window band actually needs.

    Hop ``i`` visits the kv block ``i`` shards behind the query shard;
    the farthest-back block any query in a shard of length ``s`` can see
    with a band ``k > q - window`` is ``floor((window - 2)/s) + 1`` hops
    away — identical for every device, so the bound is static and the
    out-of-band hops (and their ppermutes) are simply never executed.
    """
    if window is None:
        return axis_size
    if window <= 1:
        return 1  # each query sees only itself: the diagonal block
    return min(axis_size, 2 + (window - 2) // shard_len)


def ring_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   axis_name: str, causal: bool = False,
                   window: Optional[int] = None) -> jnp.ndarray:
    """Attention over a ring; call inside ``shard_map``.

    :param q: local query shard ``(batch, heads, seq_local, head_dim)``
    :param k, v: local key/value shards ``(batch, kv_heads, seq_local,
        head_dim)`` — GQA-aware: with ``kv_heads < heads`` the ring
        circulates the NARROW k/v buffers (ICI traffic shrinks by the
        group factor) and each query group attends to its shared head
    :param axis_name: mesh axis carrying the sequence shards
    :param causal: apply a causal mask over *global* positions
    :param window: sliding-window band over global positions — each
        query attends to at most the last ``window`` keys (itself
        included). Requires ``causal``; hops entirely outside the band
        are skipped statically (see :func:`ring_num_hops`), so a narrow
        window on a long ring pays O(window) compute and ICI traffic,
        not O(seq).
    """
    if window is not None and not causal:
        raise ValueError("window requires causal=True (sliding-window "
                         "attention is a causal band)")
    axis_size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    b, h, sq, d = q.shape
    kvh = k.shape[1]
    if h % kvh:
        raise ValueError(f"kv heads {kvh} must divide query heads {h}")
    g = h // kvh
    qg = q.reshape(b, kvh, g, sq, d)
    scale = 1.0 / math.sqrt(d)
    q_pos = my_idx * sq + jnp.arange(sq)[:, None]
    n_hops = ring_num_hops(axis_size, sq, window)

    def step(i, carry):
        o, l, m, k_cur, v_cur = carry
        kv_idx = (my_idx - i) % axis_size
        s = jnp.einsum("bngqd,bnkd->bngqk", qg, k_cur) * scale
        if causal:
            k_pos = kv_idx * k_cur.shape[2] + jnp.arange(k_cur.shape[2])[None, :]
            keep = k_pos <= q_pos
            if window is not None:
                keep = keep & (k_pos > q_pos - window)
            s = jnp.where(keep, s, NEG_INF)
        # hop 0 is the diagonal block, so every query row sees at least
        # its own position first: m is finite from the first hop on and
        # fully-masked later blocks contribute exp(NEG_INF - m) = 0
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        correction = jnp.exp(m - m_new)
        l_new = l * correction + jnp.sum(p, axis=-1)
        o_new = (o * correction[..., None]
                 + jnp.einsum("bngqk,bnkd->bngqd", p, v_cur))
        # rotate k/v shards one hop around the ring (ICI neighbor exchange)
        perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
        k_next = lax.ppermute(k_cur, axis_name, perm)
        v_next = lax.ppermute(v_cur, axis_name, perm)
        return o_new, l_new, m_new, k_next, v_next

    o0 = jnp.zeros_like(qg)
    l0 = jnp.zeros((b, kvh, g, sq), dtype=q.dtype)
    m0 = jnp.full((b, kvh, g, sq), NEG_INF, dtype=q.dtype)
    o, l, m, _, _ = lax.fori_loop(0, n_hops, step, (o0, l0, m0, k, v))
    o = o / jnp.maximum(l, 1e-20)[..., None]
    return o.reshape(b, h, sq, d)


# -------------------------------------------------------- zigzag layout
def _zigzag_perms(axis_size: int):
    """The two chunk permutations between contiguous and zigzag layouts.

    Global sequence = ``2P`` chunks. Contiguous: device ``d`` holds
    chunks ``(2d, 2d+1)``. Zigzag: device ``d`` holds ``(d, 2P-1-d)`` —
    one early and one late chunk, so every device owns the same amount
    of causal work. Each layout change moves exactly one chunk per
    device per permutation: two ppermutes total.
    """
    P = axis_size
    perm1 = [(d, 2 * d if 2 * d < P else 2 * P - 1 - 2 * d)
             for d in range(P)]
    perm2 = [(d, 2 * d + 1 if 2 * d + 1 < P else 2 * P - 2 - 2 * d)
             for d in range(P)]
    return perm1, perm2


def _zigzag_scatter(x, axis_name: str, seq_dim: int):
    """Contiguous shard -> zigzag shard (low ‖ high chunk), in-shard_map.

    Device parity decides which received buffer is the low (early)
    chunk: the even-indexed global chunk lands via perm1 on even
    devices and via perm2 on odd ones.
    """
    P = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    perm1, perm2 = _zigzag_perms(P)
    c1, c2 = jnp.split(x, 2, axis=seq_dim)
    r1 = lax.ppermute(c1, axis_name, perm1)
    r2 = lax.ppermute(c2, axis_name, perm2)
    even = (my % 2) == 0
    low = jnp.where(even, r1, r2)
    high = jnp.where(even, r2, r1)
    return jnp.concatenate([low, high], axis=seq_dim)


def _zigzag_gather(x, axis_name: str, seq_dim: int):
    """Zigzag shard -> contiguous shard (inverse of _zigzag_scatter)."""
    P = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    perm1, perm2 = _zigzag_perms(P)
    inv1 = [(dst, src) for src, dst in perm1]
    inv2 = [(dst, src) for src, dst in perm2]
    low, high = jnp.split(x, 2, axis=seq_dim)
    # device d holds global chunks (d, 2P-1-d); the even-indexed one is
    # `low` on even devices, `high` on odd devices
    even = (my % 2) == 0
    even_chunk = jnp.where(even, low, high)
    odd_chunk = jnp.where(even, high, low)
    r1 = lax.ppermute(even_chunk, axis_name, inv1)
    r2 = lax.ppermute(odd_chunk, axis_name, inv2)
    return jnp.concatenate([r1, r2], axis=seq_dim)


# ----------------------------------------------------------- flash ring
@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _ring_flash(q, k, v, axis_name, causal, window, block_q, block_k,
                interpret):
    o, _ = _ring_flash_fwd(q, k, v, axis_name, causal, window, block_q,
                           block_k, interpret)
    return o


def _ring_flash_fwd(q, k, v, axis_name, causal, window, block_q, block_k,
                    interpret):
    from .pallas_attention import flash_hop_forward

    axis_size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    b, h, sq, d = q.shape
    sk = k.shape[2]
    n_hops = ring_num_hops(axis_size, sq, window) if causal else axis_size
    q_off = my_idx * sq
    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]

    def hop(i, carry):
        o, lse, k_cur, v_cur = carry
        kv_idx = (my_idx - i) % axis_size
        # each hop runs the flash kernel on the local block pair with
        # global-position masking; per-hop (o, lse) merge by logsumexp
        # weights — the hop-level analog of the kernel's kv-block online
        # softmax
        o_h, lse_h = flash_hop_forward(q, k_cur, v_cur, q_off,
                                       kv_idx * sk, causal, window,
                                       block_q, block_k, interpret)
        lse_new = jnp.logaddexp(lse, lse_h)
        o = (o * jnp.exp(lse - lse_new)[..., None]
             + o_h.astype(jnp.float32) * jnp.exp(lse_h - lse_new)[..., None])
        k_next = lax.ppermute(k_cur, axis_name, perm)
        v_next = lax.ppermute(v_cur, axis_name, perm)
        return o, lse_new, k_next, v_next

    o0 = jnp.zeros((b, h, sq, d), jnp.float32)
    lse0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    o, lse, _, _ = lax.fori_loop(0, n_hops, hop, (o0, lse0, k, v))
    return o.astype(q.dtype), lse


def _ring_flash_fwd_vjp(q, k, v, axis_name, causal, window, block_q,
                        block_k, interpret):
    o, lse = _ring_flash_fwd(q, k, v, axis_name, causal, window, block_q,
                             block_k, interpret)
    return o, (q, k, v, o, lse)


def _ring_flash_bwd(axis_name, causal, window, block_q, block_k, interpret,
                    residuals, g):
    from .pallas_attention import flash_hop_backward

    q, k, v, o, lse = residuals
    axis_size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    sq, sk = q.shape[2], k.shape[2]
    n_hops = ring_num_hops(axis_size, sq, window) if causal else axis_size
    q_off = my_idx * sq
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]

    def hop(i, carry):
        dq, k_cur, v_cur, dk, dv = carry
        kv_idx = (my_idx - i) % axis_size
        dq_h, dk_h, dv_h = flash_hop_backward(
            q, k_cur, v_cur, g, lse, delta, q_off, kv_idx * sk, causal,
            window, block_q, block_k, interpret)
        dq = dq + dq_h.astype(jnp.float32)
        # dk/dv accumulators travel WITH their k/v shard around the ring
        dk = dk + dk_h.astype(jnp.float32)
        dv = dv + dv_h.astype(jnp.float32)
        k_next = lax.ppermute(k_cur, axis_name, perm)
        v_next = lax.ppermute(v_cur, axis_name, perm)
        dk = lax.ppermute(dk, axis_name, perm)
        dv = lax.ppermute(dv, axis_name, perm)
        return dq, k_next, v_next, dk, dv

    dq0 = jnp.zeros(q.shape, jnp.float32)
    dk0 = jnp.zeros(k.shape, jnp.float32)
    dv0 = jnp.zeros(v.shape, jnp.float32)
    dq, _, _, dk, dv = lax.fori_loop(0, n_hops, hop,
                                     (dq0, k, v, dk0, dv0))
    if n_hops % axis_size:
        # the travelling dk/dv accumulators are n_hops positions past
        # their home shard — one permute sends every block home
        home = [(j, (j - n_hops) % axis_size) for j in range(axis_size)]
        dk = lax.ppermute(dk, axis_name, home)
        dv = lax.ppermute(dv, axis_name, home)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_ring_flash.defvjp(_ring_flash_fwd_vjp, _ring_flash_bwd)


# ------------------------------------------------- zigzag-balanced ring
def _chunk_offsets(z, axis_size, chunk_len):
    """Global row offsets of zigzag device ``z``'s (low, high) chunks."""
    return z * chunk_len, (2 * axis_size - 1 - z) * chunk_len


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _zigzag_ring_flash(q, k, v, axis_name, block_q, block_k, interpret):
    o, _, _ = _zigzag_fwd(q, k, v, axis_name, block_q, block_k, interpret)
    return o


def _zigzag_fwd(q, k, v, axis_name, block_q, block_k, interpret):
    """Balanced causal ring: every device owns one early + one late
    chunk, so per-hop work (after the kernel's dynamic block skip) is
    uniform across the ring — ~2x better wall clock than the contiguous
    layout, whose last device computes every hop while the first sits
    in fully-masked blocks."""
    from .pallas_attention import flash_hop_forward

    P = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    b, h, s, d = q.shape
    if s % 2:
        raise ValueError("zigzag ring needs an even local shard length")
    hl = s // 2
    qz = _zigzag_scatter(q, axis_name, seq_dim=2)
    kz = _zigzag_scatter(k, axis_name, seq_dim=2)
    vz = _zigzag_scatter(v, axis_name, seq_dim=2)
    ql, qh = qz[:, :, :hl], qz[:, :, hl:]
    q_off_l, q_off_h = _chunk_offsets(my, P, hl)
    perm = [(j, (j + 1) % P) for j in range(P)]

    def hop(i, carry):
        o_l, lse_l, o_h, lse_h, k_cur, v_cur = carry
        z = (my - i) % P
        k_off_l, k_off_h = _chunk_offsets(z, P, hl)
        kl, kh = k_cur[:, :, :hl], k_cur[:, :, hl:]
        vl, vh = v_cur[:, :, :hl], v_cur[:, :, hl:]

        def fold(o, lse, qc, q_off, kc, vc, k_off):
            o_p, lse_p = flash_hop_forward(qc, kc, vc, q_off, k_off, True,
                                           None, block_q, block_k,
                                           interpret)
            lse_new = jnp.logaddexp(lse, lse_p)
            o = (o * jnp.exp(lse - lse_new)[..., None]
                 + o_p.astype(jnp.float32)
                 * jnp.exp(lse_p - lse_new)[..., None])
            return o, lse_new

        # NO (q_low, k_high) fold: low q chunks are indices 0..P-1, high
        # k chunks are P..2P-1 — always entirely in the future, fully
        # masked for every device at every hop
        o_l, lse_l = fold(o_l, lse_l, ql, q_off_l, kl, vl, k_off_l)
        o_h, lse_h = fold(o_h, lse_h, qh, q_off_h, kl, vl, k_off_l)
        o_h, lse_h = fold(o_h, lse_h, qh, q_off_h, kh, vh, k_off_h)
        k_next = lax.ppermute(k_cur, axis_name, perm)
        v_next = lax.ppermute(v_cur, axis_name, perm)
        return o_l, lse_l, o_h, lse_h, k_next, v_next

    z0 = lambda: (jnp.zeros((b, h, hl, d), jnp.float32),
                  jnp.full((b, h, hl), NEG_INF, jnp.float32))
    o_l, lse_l = z0()
    o_h, lse_h = z0()
    o_l, lse_l, o_h, lse_h, _, _ = lax.fori_loop(
        0, P, hop, (o_l, lse_l, o_h, lse_h, kz, vz))
    oz = jnp.concatenate([o_l, o_h], axis=2)
    lsez = jnp.concatenate([lse_l, lse_h], axis=2)
    o = _zigzag_gather(oz.astype(q.dtype), axis_name, seq_dim=2)
    return o, (qz, kz, vz, oz, lsez), None


def _zigzag_fwd_vjp(q, k, v, axis_name, block_q, block_k, interpret):
    o, residuals, _ = _zigzag_fwd(q, k, v, axis_name, block_q, block_k,
                                  interpret)
    return o, residuals


def _zigzag_bwd(axis_name, block_q, block_k, interpret, residuals, g):
    from .pallas_attention import flash_hop_backward

    qz, kz, vz, oz, lsez = residuals
    P = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    s = qz.shape[2]
    hl = s // 2
    # cotangent + global row statistics, in zigzag layout (the transpose
    # of the output gather is the input scatter: both are permutations)
    gz = _zigzag_scatter(g, axis_name, seq_dim=2)
    delta = jnp.sum(gz.astype(jnp.float32) * oz, axis=-1)
    ql, qh = qz[:, :, :hl], qz[:, :, hl:]
    gl, gh = gz[:, :, :hl], gz[:, :, hl:]
    lse_l, lse_h = lsez[:, :, :hl], lsez[:, :, hl:]
    d_l, d_h = delta[:, :, :hl], delta[:, :, hl:]
    q_off_l, q_off_h = _chunk_offsets(my, P, hl)
    perm = [(j, (j + 1) % P) for j in range(P)]

    def hop(i, carry):
        dq, k_cur, v_cur, dk, dv = carry
        z = (my - i) % P
        k_off_l, k_off_h = _chunk_offsets(z, P, hl)
        # mirrors the forward's three folds — the (q_low, k_high) pair is
        # always fully masked and contributes zero gradient
        for q_half, (qc, gc, lse_c, del_c, q_off), k_slices in (
                ((slice(0, hl)), (ql, gl, lse_l, d_l, q_off_l),
                 ((slice(0, hl), k_off_l),)),
                ((slice(hl, s)), (qh, gh, lse_h, d_h, q_off_h),
                 ((slice(0, hl), k_off_l), (slice(hl, s), k_off_h)))):
            for sl, k_off in k_slices:
                dq_p, dk_p, dv_p = flash_hop_backward(
                    qc, k_cur[:, :, sl], v_cur[:, :, sl], gc, lse_c,
                    del_c, q_off, k_off, True, None, block_q, block_k,
                    interpret)
                dq = dq.at[:, :, q_half].add(dq_p.astype(jnp.float32))
                dk = dk.at[:, :, sl].add(dk_p.astype(jnp.float32))
                dv = dv.at[:, :, sl].add(dv_p.astype(jnp.float32))
        k_next = lax.ppermute(k_cur, axis_name, perm)
        v_next = lax.ppermute(v_cur, axis_name, perm)
        dk = lax.ppermute(dk, axis_name, perm)
        dv = lax.ppermute(dv, axis_name, perm)
        return dq, k_next, v_next, dk, dv

    dq0 = jnp.zeros(qz.shape, jnp.float32)
    dk0 = jnp.zeros(kz.shape, jnp.float32)
    dv0 = jnp.zeros(vz.shape, jnp.float32)
    dq, _, _, dk, dv = lax.fori_loop(0, P, hop, (dq0, kz, vz, dk0, dv0))
    # P rotations returned the travelling dk/dv accumulators home; undo
    # the zigzag layout for all three grads (gather = scatter transpose)
    dq = _zigzag_gather(dq, axis_name, seq_dim=2)
    dk = _zigzag_gather(dk, axis_name, seq_dim=2)
    dv = _zigzag_gather(dv, axis_name, seq_dim=2)
    return (dq.astype(qz.dtype), dk.astype(kz.dtype), dv.astype(vz.dtype))


_zigzag_ring_flash.defvjp(_zigzag_fwd_vjp, _zigzag_bwd)


def ring_flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                         axis_name: str, causal: bool = False,
                         window: Optional[int] = None, block_q: int = 256,
                         block_k: int = 512,
                         interpret: Optional[bool] = None,
                         zigzag: Optional[bool] = None) -> jnp.ndarray:
    """Ring attention whose per-hop local block runs the Pallas flash
    kernel (VMEM-tiled, never materializing the local ``(sq, sk)`` score
    matrix) instead of the einsum path — the long-context composition of
    sequence parallelism and flash attention. Same semantics and calling
    convention as :func:`ring_attention`; differentiable via the
    global-lse factorization (each hop's backward uses the full ring's
    row statistics, which is exact).

    ``zigzag`` (default: auto — on for full-causal rings) runs the
    balanced schedule: each device owns one early and one late sequence
    chunk, so causal work is uniform across the ring instead of the
    last device computing every hop (~2x wall clock at large ring
    sizes). Windowed rings keep the contiguous layout — the static
    out-of-band hop skip is the better schedule for a narrow band.
    """
    if window is not None and not causal:
        raise ValueError("window requires causal=True")
    if q.shape[1] % k.shape[1]:
        raise ValueError(f"kv heads {k.shape[1]} must divide query heads "
                         f"{q.shape[1]}")
    if zigzag is None:
        zigzag = (causal and window is None and q.shape[2] % 2 == 0
                  and q.shape[2] == k.shape[2])
    if zigzag:
        if not causal or window is not None:
            raise ValueError("zigzag schedule is full-causal only")
        if q.shape[2] != k.shape[2] or q.shape[2] % 2:
            raise ValueError("zigzag needs equal, even q/k shard lengths")
        return _zigzag_ring_flash(q, k, v, axis_name, block_q, block_k,
                                  interpret)
    return _ring_flash(q, k, v, axis_name, causal,
                       int(window) if window is not None else None,
                       block_q, block_k, interpret)


def ring_attention_sharded(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           mesh: Mesh, seq_axis: str = "seq",
                           causal: bool = False,
                           batch_axis: Optional[str] = None,
                           window: Optional[int] = None,
                           impl: str = "einsum",
                           interpret: Optional[bool] = None) -> jnp.ndarray:
    """shard_map wrapper: global ``(batch, heads, seq, head_dim)`` arrays in,
    sequence sharded over ``seq_axis`` (and optionally batch over
    ``batch_axis``), global attention out.

    ``impl='flash'`` runs each hop's local block through the Pallas flash
    kernel (:func:`ring_flash_attention`) — the TPU path; ``'einsum'`` is
    the XLA reference formulation."""
    batch_spec = batch_axis if batch_axis else None
    spec = PartitionSpec(batch_spec, None, seq_axis, None)

    if impl == "flash":
        local = partial(ring_flash_attention, axis_name=seq_axis,
                        causal=causal, window=window, interpret=interpret)
    elif impl == "einsum":
        local = partial(ring_attention, axis_name=seq_axis, causal=causal,
                        window=window)
    else:
        raise ValueError(f"impl must be 'einsum' or 'flash', got {impl!r}")
    fn = jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    return fn(q, k, v)
