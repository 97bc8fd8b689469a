"""Paged KV cache: block-pool decode for memory-oversubscribed serving.

The engine's default cache gives every slot a contiguous
``max_len``-row strip — simple and fastest, but memory is reserved for
the worst case: ``max_slots × max_len`` positions whether requests use
them or not. Paged mode (vLLM's PagedAttention memory model) allocates
cache in fixed ``block_size``-position blocks from one shared pool;
each slot holds a small block table. Capacity then scales with TOKENS
IN FLIGHT, not worst-case sequence length — short requests and early
eos retirements return their blocks immediately, so a pool far smaller
than ``max_slots × max_len`` serves the same traffic (admission simply
queues when the pool is momentarily empty).

The decode step (:func:`decode_step_paged`) attends over a FLAT list
of the blocks its rows hold: per layer one leading-axis gather of
``held_blocks`` pool blocks, contracted in the pool's own ``(block,
head, pos, D)`` layout and normalised per row across its blocks. Its
cost follows the SUM of what the rows hold (bucketed to one of a few
static widths, picked on the device), not ``batch × table width``: a
row of 40 positions costs 3 blocks, whatever ``max_len`` is. The
gathered copy is still one extra pass over the held K/V versus
streaming the held blocks in place (no such kernel exists yet), and a
contiguous strip read in place is still the cheapest of all at full
occupancy.

Math mirrors :func:`~elephas_tpu.models.transformer.decode_block`
(S=1) exactly — same norms, RoPE convention, GQA grouping,
window/ALiBi masks — pinned by parity tests against the contiguous
engine. Safety invariant: block id 0 is a reserved scratch sink that
is never allocated; freed slots' tables are reset to 0, so an inactive
slot's garbage decode (the engine's static-batch idiom) can never
write into a block owned by a live request.

The pool is also the storage layer for AUTOMATIC prefix caching
(:mod:`~elephas_tpu.models.block_cache`): full prompt blocks are
content-addressed and shared across requests by table pointers —
:func:`gather_blocks_to_row` turns a cached chain back into a row head
for remainder prefill, and :func:`install_row_paged`'s ``start``
offset writes only the private remainder around shared blocks.

:func:`decode_block_paged` is the multi-position mirror of
:func:`decode_step_paged` — the target-verify pass of PAGED
speculative decoding: one forward scores ``S`` positions per row,
scattering their k/v into each row's own block table. Shared
prefix-cache blocks stay read-only under it for the same reason they
do under plain decode: every verify write lands at a position at or
past the prompt length, past every shared full block.

**What a block holds** is asked of the model
(``TransformerConfig.cache_leaves``): ``k`` and ``v`` per KV head for
``attention_kind="mha"``; for ``"mla"`` ONE leaf ``latent`` of shape
``(num_blocks, 1, block_size, latent_width)`` -- the normalised latent
and the rotated rope key every head shares, 576 values at DeepSeek-V2's
sizes (1,152 bytes a position and layer in bf16), zero-padded to 640 so
that the pool keeps a row-major layout on the TPU
(:func:`~elephas_tpu.models.mla.latent_width`). Install,
gather, export and import map over those leaves; nothing here names
``k`` or ``v`` outside the ``mha`` attention itself. The ``mla`` decode
step is the ABSORBED form (:mod:`~elephas_tpu.models.mla`): the key half
of ``W_kvb`` is folded into the query, the 128 heads score against the
held latents directly, and the value half is applied to the weighted
sum of latents -- K and V are never expanded. Its flat list is laid out
in tiles of :data:`LATENT_TILE_BLOCKS` blocks, each tile owned by one
row, so that the per-tile partial sums (heads x kv_lora_rank values)
stay small against the latents read.

Expert layers (``mlp_kinds``, either expert variant) run in the paged
step like any MLP; with ``with_stats`` the step also returns the
routing counts of its swiglu expert layers.

Not supported in paged mode (constructor raises): ``kv_cache_quant``
(compose the int8 cache with the contiguous engine instead). The
speculative verify block (:func:`decode_block_paged`) refuses
``attention_kind="mla"``.
"""
import math
from functools import partial
from typing import Dict, List, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from . import mla as _mla
from . import mamba2 as _mamba
from .transformer import (NEG_INF, TransformerConfig, _alibi_slopes,
                          _apply_rope, _attn_out, _mlp_sublayer, _norm,
                          _qkv, _sinusoidal_table, _times, _with_mixer,
                          head_logits)

__all__ = ["init_paged_pool", "decode_step_paged", "decode_block_paged",
           "held_block_count", "held_tile", "held_ladder",
           "LATENT_TILE_BLOCKS",
           "install_row_paged", "gather_blocks_to_row",
           "validate_paged_config", "require_per_head_cache",
           "require_stateless_cache",
           "export_kv_blocks",
           "import_kv_blocks", "export_pool_blocks",
           "install_pool_blocks"]


#: widths in :func:`held_ladder`: the widest, halved five times
LADDER_RUNGS = 6

#: blocks in one tile of the ``mla`` step's flat list; a row's held
#: blocks are padded to whole tiles (at most ``LATENT_TILE_BLOCKS - 1``
#: masked blocks a row)
LATENT_TILE_BLOCKS = 8


def validate_paged_config(config: TransformerConfig):
    if config.kv_cache_quant:
        raise ValueError("paged KV mode does not compose with "
                         "kv_cache_quant; use the contiguous engine for "
                         "the int8 cache")


def require_per_head_cache(config: TransformerConfig, what: str):
    """Refuse ``what`` for a latent cache: the KV tiers, the
    disaggregated wire and the speculative verify block move or score
    per-head k/v and have no ``mla`` form yet."""
    if config.attention_kind == "mla":
        raise ValueError(f"{what} has no latent-cache form yet: it is "
                         "not available with attention_kind='mla'")


def require_stateless_cache(config: TransformerConfig, what: str):
    """Refuse ``what`` for a config whose rows keep recurrent state
    (``config.ssm``): whatever skips, moves or rolls back cached
    POSITIONS (prefix reuse, preemption, the KV tiers, the disaggregated
    wire, the speculative verify block) would need the state as it
    stood at that position, and nobody keeps such a snapshot yet."""
    if config.ssm is not None:
        raise ValueError(
            f"{what} needs a snapshot of the recurrent state at a block "
            "boundary, which is not kept yet: it is not available with a "
            "state-space mixer (config.ssm)")


def held_tile(config: TransformerConfig) -> int:
    """Blocks a row's share of the decode step's flat list is padded to
    a multiple of: 1 for per-head k/v, :data:`LATENT_TILE_BLOCKS` for
    the latent pool."""
    return LATENT_TILE_BLOCKS if config.attention_kind == "mla" else 1


def held_ladder(config: TransformerConfig, rows: int,
                max_blocks: int) -> Tuple[int, ...]:
    """Widths for :func:`decode_step_paged`'s ``held_blocks``, derived
    from the shapes: ``rows`` x table width (in whole tiles: enough for
    any rows) halved ``LADDER_RUNGS - 1`` times, none narrower than a
    tile a row (every row holds at least its scratch block)."""
    tile = held_tile(config)
    top = rows * (-(-max_blocks // tile) * tile)
    return tuple(sorted({max(top >> k, rows * tile)
                         for k in range(LADDER_RUNGS)}))


def init_paged_pool(config: TransformerConfig, num_blocks: int,
                    block_size: int, slots: int = 0) -> Dict:
    """Shared block pool: per layer one array per cache leaf
    (``config.cache_leaves()``) of shape ``(num_blocks, heads,
    block_size, width)`` -- ``k``/``v`` with ``(kv_heads, head_dim)``, or
    the one ``latent`` leaf of ``attention_kind="mla"``. Block 0 is the
    reserved scratch sink (allocators must hand out ids >= 1).

    A config whose rows keep state (``config.state_leaves()``) gets,
    under ``pool["state"]``, per layer one ``(slots, ...)`` array a
    state leaf beside the blocks: two kinds of cache in one tree, the
    positions by block table, the state by slot. ``slots`` is the batch
    width of the step that will run over the pool."""
    validate_paged_config(config)
    c = config
    pool = {f"layer_{i}": {
        leaf: jnp.zeros((num_blocks, heads, block_size, width), c.dtype)
        for leaf, (heads, width) in c.cache_leaves().items()}
        for i in range(c.num_layers)}
    if c.ssm is not None:
        if slots < 1:
            raise ValueError("a pool for a config with per-slot state "
                             "needs slots >= 1")
        pool["state"] = _mamba.zero_state(c, int(slots))
    return pool


def _block_size(pool: Dict) -> int:
    return jax.tree_util.tree_leaves(pool["layer_0"])[0].shape[2]


def _blocks(tree: Dict) -> Dict:
    """The per-position part of a pool or of a row cache: everything
    but the per-slot ``state``."""
    return {name: sub for name, sub in tree.items() if name != "state"}


def install_row_paged(pool: Dict, row_cache: Dict, block_ids,
                      nblocks: int, start: int = 0, slot=None) -> Dict:
    """Scatter a contiguous batch-1 prefill row into pool blocks:
    positions ``[start*block_size, nblocks*block_size)`` of
    ``row_cache`` land in ``block_ids[start:nblocks]``. ``start > 0``
    is the prefix-cache-hit install: the first ``start`` table entries
    point at SHARED cached blocks that already hold those positions —
    writing them again would be wasted HBM traffic over blocks other
    slots are reading. One jit specialization per ``(start, nblocks)``
    pair (both bounded by the per-slot table width).

    A row that carries state (``row_cache["state"]``) also needs
    ``slot``: its state overwrites that slot's, whatever the slot held
    before (the row that retired there, or what a step made of it
    since)."""
    if "state" in pool and slot is None:
        raise ValueError("a pool with per-slot state installs a row "
                         "into a slot: give slot=")
    # host values: the call transfers them, no program converts. A copy
    # of the ids, because a transfer reads its host array when it runs
    # and the caller's is a row of a table it goes on writing
    return _install_jit(pool, row_cache, np.array(block_ids, np.int32),
                        nblocks, start,
                        np.int32(slot) if "state" in pool else None)


def _install(pool, row_cache, block_ids, nblocks: int, start: int = 0,
             slot=None):
    n_write = nblocks - start
    bs = _block_size(pool)
    ids = block_ids[start:nblocks]

    def to_blocks(big, row):                     # (1, H, L, D) -> blocks
        h, length, d = row.shape[1:]
        take = min(nblocks * bs, length)
        chunk = row[0, :, start * bs:take]
        if take < nblocks * bs:
            # max_len need not divide block_size: the final block's
            # tail holds zero padding that no position ever reads
            # (every valid position is < max_len)
            chunk = jnp.pad(chunk, ((0, 0), (0, nblocks * bs - take),
                                    (0, 0)))
        return big.at[ids].set(jnp.swapaxes(
            chunk.reshape(h, n_write, bs, d), 0, 1))

    new = jax.tree_util.tree_map(to_blocks, _blocks(pool),
                                 _blocks(row_cache))
    if slot is not None:
        new["state"] = jax.tree_util.tree_map(
            lambda big, row: jax.lax.dynamic_update_index_in_dim(
                big, row[0].astype(big.dtype), slot, 0),
            pool["state"], row_cache["state"])
    return new


_install_jit = jax.jit(_install, static_argnums=(3, 4),
                       donate_argnums=(0,))


def gather_blocks_to_row(pool: Dict, block_ids, max_len: int) -> Dict:
    """The inverse of :func:`install_row_paged`: read ``block_ids``'
    pool blocks back into a contiguous batch-1 row cache (``(1,
    kv_heads, max_len, head_dim)`` per layer k/v, zero past
    ``len(block_ids) * block_size``). This is how a prefix-cache HIT
    feeds the remainder prefill: the cached blocks become the row's
    head and :func:`~elephas_tpu.models.transformer.decode_block`
    extends past them — no recompute of the cached positions, one
    O(prefix) device gather instead. One jit specialization per block
    count (bounded by the per-slot table width)."""
    return _gather_jit(pool, np.asarray(block_ids, np.int32), int(max_len))


@partial(jax.jit, static_argnums=(2,))
def _gather_jit(pool, block_ids, max_len: int):
    n = block_ids.shape[0]
    bs = _block_size(pool)

    def to_row(p):                              # blocks -> (1, H, L, D)
        sel = p[block_ids]                      # (n, H, bs, D)
        h, d = sel.shape[1], sel.shape[3]
        flat = jnp.swapaxes(sel, 0, 1).reshape(h, n * bs, d)
        return jnp.pad(flat, ((0, 0), (0, max_len - n * bs),
                              (0, 0)))[None]

    return jax.tree_util.tree_map(to_row, pool)


# --------------------------------------------------------------------------
# Off-engine block transfer — disaggregated prefill/decode.
#
# A prefill worker computes a contiguous batch-1 row cache and ships it
# to a decode worker in fixed ``block_size``-position blocks: the paged
# pool's native currency, and a bounded shape family (at most
# ``ceil(max_len / block_size)`` distinct block counts) so the decode
# side's install jit cannot churn one compile per prompt length. The
# exports are HOST numpy arrays — they exist to cross a socket
# (:mod:`elephas_tpu.disagg.wire`), not to stay on device.
# --------------------------------------------------------------------------

def _layer_names(row_cache: Dict) -> List[str]:
    """``layer_0..layer_{n-1}`` in index order — the canonical wire
    order, independent of dict insertion order."""
    return sorted(row_cache, key=lambda n: int(n.split("_", 1)[1]))


def export_kv_blocks(row_cache: Dict, length: int,
                     block_size: int) -> List[np.ndarray]:
    """Extract a batch-1 row cache's first ``length`` positions as
    block-unit host arrays: a flat list, layer after layer and within a
    layer leaf after leaf in sorted leaf order (``[k_0, v_0, k_1, v_1,
    ...]``; ``[latent_0, latent_1, ...]`` for ``mla``), of shape
    ``(nblocks, heads, block_size, width)`` each, ``nblocks =
    ceil(length / block_size)``. The final block's tail is zero padding
    (no position past ``length`` is ever read after install — the same
    contract as :func:`install_row_paged`'s padding)."""
    length = int(length)
    bs = int(block_size)
    if length < 1 or bs < 1:
        raise ValueError("length and block_size must be >= 1")
    nb = -(-length // bs)
    out: List[np.ndarray] = []
    for name in _layer_names(row_cache):
        lc = row_cache[name]
        for part in sorted(lc):
            row = np.asarray(lc[part])[0]          # (H, L, D)
            h, cached, d = row.shape
            if cached < length:
                raise ValueError(f"row cache holds {cached} positions, "
                                 f"cannot export {length}")
            chunk = np.zeros((h, nb * bs, d), row.dtype)
            chunk[:, :length] = row[:, :length]
            out.append(np.ascontiguousarray(
                chunk.reshape(h, nb, bs, d).swapaxes(0, 1)))
    return out


def import_kv_blocks(arrays: Sequence[np.ndarray], length: int,
                     max_len: int,
                     leaves: Sequence[str] = ("k", "v")) -> Dict:
    """Reassemble :func:`export_kv_blocks` output into a contiguous
    batch-1 row cache dict (``{"layer_i": {leaf: (1, heads, max_len,
    width)}}``) padded with zeros past ``length`` — ready for the decode
    engine's slot install (contiguous ``_install_fn`` or
    :func:`install_row_paged`). ``leaves`` names the cache's leaves
    (``sorted(config.cache_leaves())``; the default is the ``mha``
    cache's)."""
    leaves = sorted(leaves)
    if not arrays or len(arrays) % len(leaves):
        raise ValueError(f"KV block export must hold {tuple(leaves)} per "
                         f"layer, got {len(arrays)} arrays")
    length, max_len = int(length), int(max_len)
    if length > max_len:
        raise ValueError(f"length {length} exceeds max_len {max_len}")
    row: Dict = {}
    for i in range(len(arrays) // len(leaves)):
        parts = {}
        for j, part in enumerate(leaves):
            blocks = np.asarray(arrays[i * len(leaves) + j])
            if blocks.ndim != 4:
                raise ValueError("KV block tensors must be (nblocks, "
                                 f"heads, block_size, head_dim), got "
                                 f"shape {blocks.shape}")
            nb, h, bs, d = blocks.shape
            if nb * bs < length:
                raise ValueError(f"{nb} blocks of {bs} positions cannot "
                                 f"cover length {length}")
            flat = blocks.swapaxes(0, 1).reshape(h, nb * bs, d)
            full = np.zeros((1, h, max_len, d), blocks.dtype)
            full[0, :, :length] = flat[:, :length]
            parts[part] = full
        row[f"layer_{i}"] = parts
    return row


def export_pool_blocks(pool: Dict, block_ids: Sequence[int]) -> List[Dict]:
    """Read pool blocks out to host payload dicts: one ``{layer: tuple of
    the layer's leaves in sorted leaf order}`` dict per id (``(k, v)``
    for the ``mha`` pool, ``(latent,)`` for ``mla``; each array
    ``(heads, block_size, width)`` — the block cache's host payload
    format). One device->host gather per layer tensor regardless of
    block count. The KV spill tier's demotion read
    (:mod:`elephas_tpu.kvtier`) and the session store's persistence
    read."""
    ids = [int(b) for b in block_ids]
    if not ids:
        return []
    idx = jnp.asarray(ids)
    per_layer = {name: tuple(np.asarray(lc[leaf][idx])
                             for leaf in sorted(lc))
                 for name, lc in pool.items()}
    return [{name: tuple(np.ascontiguousarray(a[i]) for a in parts)
             for name, parts in per_layer.items()}
            for i in range(len(ids))]


def install_pool_blocks(pool: Dict, payloads: Sequence[Dict],
                        block_ids: Sequence[int]) -> Dict:
    """Inverse of :func:`export_pool_blocks`: scatter host payload
    dicts into ``block_ids``' pool blocks (the spill tier's PROMOTION
    write — the same one host->device copy per block the host-mode
    cache trades on every hit). Payloads are cast to the pool dtype.
    One jit specialization per block count."""
    if len(payloads) != len(block_ids):
        raise ValueError(f"{len(payloads)} payloads for "
                         f"{len(block_ids)} block ids")
    if not payloads:
        return pool
    stacked = {name: {
        leaf: jnp.asarray(np.stack([np.asarray(p[name][j], np.float32)
                                    for p in payloads]), lc[leaf].dtype)
        for j, leaf in enumerate(sorted(lc))}
        for name, lc in pool.items()}
    return _install_blocks_jit(pool, stacked,
                               jnp.asarray([int(b) for b in block_ids]))


@partial(jax.jit, donate_argnums=(0,))
def _install_blocks_jit(pool, blocks, block_ids):
    return jax.tree_util.tree_map(
        lambda big, new: big.at[block_ids].set(new), pool, blocks)


def _held_range(pos, block_size: int, max_blocks: int, window, xp):
    """Table entries a row at ``pos`` attends over, as (first, count):
    every block up to the one holding ``pos``, from the first the
    ``window`` touches. ``xp`` is ``numpy`` on the host and
    ``jax.numpy`` in the traced step, so both count by one formula."""
    last = xp.minimum(pos // block_size, max_blocks - 1)
    if window is None:
        return xp.zeros_like(last), last + 1
    first = xp.minimum(xp.maximum(pos - window + 1, 0) // block_size,
                       last)
    return first, last - first + 1


def held_block_count(pos, block_size: int, max_blocks: int,
                     window=None, tile: int = 1) -> int:
    """Blocks the rows at host positions ``pos`` hold in all: the least
    ``held_blocks`` :func:`decode_step_paged` may be given for them. An
    inactive row (``pos`` 0) holds its one scratch block. With ``tile``
    (:func:`held_tile`) every row's count is rounded up to whole tiles,
    which is what the latent pool's step lays out."""
    count = _held_range(np.asarray(pos), block_size, max_blocks,
                        window, np)[1]
    return int((-(-count // tile) * tile).sum())


def _ladder(held_blocks, whole: int) -> List[int]:
    """``decode_step_paged``'s ``held_blocks`` as ascending widths;
    ``whole`` (every table entry of every row) when it is None."""
    if held_blocks is None:
        return [whole]
    if isinstance(held_blocks, (tuple, list)):
        return sorted(set(held_blocks))
    return [int(held_blocks)]


def _held_slots(tables, pos, bs: int, window, width: int, alibi: bool):
    """Lay the rows' held blocks out as one flat list of ``width``
    slots, row after row, from ``tables`` and ``pos`` alone. Returns the
    number of live slots and the slot-major arrays ``row`` ``(W,)`` (the
    slot's owner), ``owns`` ``(W, B)`` (its one-hot, all false on
    padding slots), ``flat_blk`` ``(W,)`` (the pool block; padding reads
    scratch block 0), ``mask`` ``(W, bs)`` (positions the owner attends
    to; all false on padding) and, under ``alibi``, ``dist`` ``(W, bs)``
    (query position minus key position). Live slots come first, so the
    first ``w`` slots are the whole list for any ``w`` that covers
    them."""
    b, mb = tables.shape
    first, count = _held_range(pos, bs, mb, window, jnp)
    ends = jnp.cumsum(count)
    slot = jnp.arange(width)
    row = jnp.minimum(jnp.searchsorted(ends, slot, side="right",
                                       method="compare_all"), b - 1)
    live = slot < ends[-1]
    j = jnp.where(live, first[row] + slot - (ends - count)[row], 0)
    flat_blk = jnp.where(live, tables[row, j], 0)
    kpos = j[:, None] * bs + jnp.arange(bs)[None, :]
    rpos = pos[row][:, None]
    mask = live[:, None] & (kpos <= rpos)
    if window is not None:
        mask = mask & (kpos > rpos - window)
    owns = (row[:, None] == jnp.arange(b)[None, :]) & live[:, None]
    dist = (rpos - kpos).astype(jnp.float32) if alibi else None
    return ends[-1], (row, owns, flat_blk, mask, dist)


def _held_attention(q, pk, pv, slots, slopes, scale):
    """Attention of each row's query ``q`` ``(B, KV, G, D)`` over the
    flat list of held blocks ``slots`` (:func:`_held_slots`): one
    leading-axis gather each from the pools ``pk``/``pv``, contracted in
    their own ``(block, head, pos, D)`` layout. Scores and the softmax's
    sums are float32; the softmax is normalised per row across its
    slots through the one-hot ``owns`` (a masked max, a masked sum and
    one matmul) — a scatter-based segment sum would serialise on the
    TPU. Returns ``(B, KV, G, D)``."""
    row, owns, flat_blk, mask, dist = slots
    kb, vb = pk[flat_blk], pv[flat_blk]            # (W, KV, bs, D)
    s = jnp.einsum("wngd,wnpd->wngp", q[row], kb,
                   preferred_element_type=jnp.float32) * scale
    if dist is not None:
        s = s - slopes.reshape(1, *q.shape[1:3], 1) * dist[:, None, None]
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    own = owns[:, :, None, None]                   # (W, B, 1, 1)
    top = jnp.max(jnp.where(own, s.max(-1)[:, None], NEG_INF), axis=0)
    p = jnp.exp(s - top[row][..., None])           # masked -> exactly 0
    denom = jnp.sum(jnp.where(own, p.sum(-1)[:, None], 0.0), axis=0)
    o = jnp.einsum("wngp,wnpd->wngd", p.astype(vb.dtype), vb,
                   preferred_element_type=jnp.float32)
    # (a plain bf16 x bf16 -> f32 matmul is not implemented on the CPU
    # backend, so the per-row sum leaves the matmul in the data dtype)
    o = jnp.einsum("wb,wngd->bngd", owns.astype(vb.dtype),
                   o.astype(vb.dtype))
    return (o.astype(jnp.float32) / denom[..., None]).astype(vb.dtype)


def _held_tiles(tables, pos, bs: int, tile: int, width: int):
    """The latent pool's flat list: ``width`` tiles of ``tile`` blocks,
    each owned by ONE row (a row at ``pos`` holds ``ceil((pos // bs + 1)
    / tile)`` of them, row after row, live tiles first). Returns the
    number of live tiles and the tile-major arrays ``row`` ``(T,)``,
    ``owns`` ``(T, B)`` (all false on padding tiles), ``blk`` ``(T,
    tile)`` (pool blocks; entries past what the row holds read scratch
    block 0) and ``mask`` ``(T, tile * bs)`` (positions the owner
    attends to)."""
    b, mb = tables.shape
    _, count = _held_range(pos, bs, mb, None, jnp)
    tiles = -(-count // tile)
    ends = jnp.cumsum(tiles)
    slot = jnp.arange(width)
    row = jnp.minimum(jnp.searchsorted(ends, slot, side="right",
                                       method="compare_all"), b - 1)
    live = slot < ends[-1]
    j = ((slot - (ends - tiles)[row]) * tile)[:, None] + jnp.arange(tile)
    held = live[:, None] & (j < count[row][:, None])
    blk = jnp.where(held, tables[row[:, None], jnp.minimum(j, mb - 1)], 0)
    kpos = (j[:, :, None] * bs + jnp.arange(bs)).reshape(width, tile * bs)
    mask = live[:, None] & (kpos <= pos[row][:, None])
    owns = (row[:, None] == jnp.arange(b)[None, :]) & live[:, None]
    return ends[-1], (row, owns, blk, mask)


def _latent_attention(q, pool, tiles, scale, rank: int):
    """The absorbed attention of each row's folded query ``q`` ``(B, H,
    latent_width)`` over the held tiles (:func:`_held_tiles`) of the
    latent pool ``(blocks, 1, bs, latent_width)``: one gather of the
    tiles' blocks, scores of all heads against every gathered latent,
    a softmax normalised per row across its tiles through ``owns``, and
    the weighted sum of the latents' first ``rank`` values. Scores and
    sums are float32. Returns ``(B, H, rank)``."""
    row, owns, blk, mask = tiles
    t = blk.shape[0]
    lat = pool[blk][:, :, 0].reshape(t, -1, pool.shape[-1])   # (T, P, W)
    s = jnp.einsum("thw,tpw->thp", q[row], lat,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask[:, None, :], s, NEG_INF)
    own = owns[:, :, None]                         # (T, B, 1)
    top = jnp.max(jnp.where(own, s.max(-1)[:, None], NEG_INF), axis=0)
    p = jnp.exp(s - top[row][..., None])           # masked -> exactly 0
    denom = jnp.sum(jnp.where(own, p.sum(-1)[:, None], 0.0), axis=0)
    u = jnp.einsum("thp,tpr->thr", p.astype(lat.dtype), lat[..., :rank],
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("tb,thr->bhr", owns.astype(lat.dtype),
                   u.astype(lat.dtype))
    return (u.astype(jnp.float32) / denom[..., None]).astype(lat.dtype)


def decode_step_paged(params: Dict, pool: Dict, tables: jnp.ndarray,
                      tokens: jnp.ndarray, pos,
                      config: TransformerConfig,
                      held_blocks: Union[None, int, Sequence[int]] = None,
                      with_stats: bool = False):
    """One autoregressive step over the block pool: token ids ``(B,)``
    at per-row positions ``pos`` ``(B,)``; ``tables`` is ``(B,
    max_blocks)`` of block ids. Returns (logits ``(B, vocab)``, updated
    pool). The paged mirror of
    :func:`~elephas_tpu.models.transformer.decode_step`.

    Attention runs over the flat list of blocks the rows hold: a
    row at ``pos`` holds ``pos // block_size + 1`` blocks (under
    ``attention_window`` only those the window touches), the list is
    laid out row after row from ``tables`` and ``pos`` alone, padded to
    a static width with masked reads of scratch block 0, gathered once
    per layer and normalised per row. ``held_blocks`` gives the width:
    an int must be at least the sum of the rows' blocks (the caller's
    to guarantee — a traced program cannot check it); a sequence of
    widths compiles a branch for each and the step picks, on the
    device, the narrowest that covers what its rows hold (the widest
    has to cover any input it will meet); ``None`` means ``B ×
    max_blocks``, enough for any input.

    ``attention_kind="mla"`` runs the absorbed step over the latent pool
    (module docstring) with the same ladder; its widths count blocks and
    must be multiples of :func:`held_tile`, and the rows' need is
    :func:`held_block_count` with that ``tile``.

    With ``with_stats`` a third value is returned, None for a model
    without swiglu expert layers, else ``{"counts", "picks"}``: the
    int32 routing counts summed over those layers
    (:data:`~elephas_tpu.models.grouped_experts.STATS`: picks made by
    live rows -- those at ``pos`` > 0, which an engine's idle slots are
    not --, picks on held experts, held experts touched, expert layers
    run) and every such layer's picks ``(layers, B, top_k)``."""
    c = config
    b = tokens.shape[0]
    bs = _block_size(pool)
    mla = c.attention_kind == "mla"
    pos = jnp.asarray(pos)
    blk = jnp.take_along_axis(tables, (pos // bs)[:, None],
                              axis=1)[:, 0]        # (B,) owning block
    off = pos % bs

    x = _times(params["embed"]["tokens"][tokens], c, "embedding")  # (B, D)
    if c.positional == "learned":
        x = x + params["embed"]["pos"][pos]
    elif c.positional == "sinusoidal":
        x = x + _sinusoidal_table(pos, c.d_model)
    x = x.astype(c.dtype)[:, None]                 # (B, 1, D)

    scale = 1.0 / math.sqrt(c.head_dim)
    rp = pos[:, None, None]                        # (B, 1, 1) rope angles
    groups = c.num_heads // c.kv_heads
    hidx = jnp.arange(c.kv_heads)
    if mla:
        tile = LATENT_TILE_BLOCKS
        widths = _ladder(held_blocks,
                         b * (-(-tables.shape[1] // tile) * tile))
        if any(w % tile for w in widths):
            raise ValueError(f"held_blocks {widths} must be multiples of "
                             f"the latent tile, {tile} blocks")
        held, tiles = _held_tiles(tables, pos, bs, tile,
                                  widths[-1] // tile)
        sigma = _mla.softmax_scale(c)

        def attend_over(width):
            cut = jax.tree_util.tree_map(lambda a: a[:width // tile],
                                         tiles)
            return lambda q, lat: _latent_attention(q, lat, cut, sigma,
                                                    c.kv_lora_rank)

        branches = [attend_over(w) for w in widths]
        pick = jnp.searchsorted(jnp.asarray(widths), held * tile,
                                side="left")
    else:
        widths = _ladder(held_blocks, b * tables.shape[1])
        alibi = c.positional == "alibi"
        held, slots = _held_slots(tables, pos, bs, c.attention_window,
                                  widths[-1], alibi)
        slopes = _alibi_slopes(c.num_heads) if alibi else None

        def attend_over(width):
            # the first `width` slots are the whole list when they
            # cover what the rows hold
            cut = jax.tree_util.tree_map(lambda a: a[:width], slots)
            return lambda q, pk, pv: _held_attention(q, pk, pv, cut,
                                                     slopes, scale)

        branches = [attend_over(w) for w in widths]
        # the narrowest width that covers the rows, picked on the
        # device from what the step is given anyway
        pick = jnp.searchsorted(jnp.asarray(widths), held, side="left")
    new_pool: Dict = {}
    new_state: Dict = {}
    if c.ssm is not None and jax.tree_util.tree_leaves(
            pool["state"])[0].shape[0] != b:
        raise ValueError("the pool's per-slot state has another number of "
                         f"slots than the step has rows ({b}): row r is "
                         "slot r")
    live = pos > 0
    stats = []
    for i in range(c.num_layers):
        layer = params[f"layer_{i}"]
        h = _norm(x, layer["ln1"], c).astype(c.dtype)
        if mla:
            attn = layer["attn"]
            q_nope, q_rope = _mla.project_query(attn, h, pos[:, None], c)
            new = _mla.project_latent(attn, h, pos[:, None], c)
            # this position's latent into each row's owning block
            lat = pool[f"layer_{i}"]["latent"].at[blk, 0, off].set(
                new[:, 0])
            new_pool[f"layer_{i}"] = {"latent": lat}
            q_lat = _mla.absorb_query(attn, q_nope, q_rope, c)
            with jax.named_scope("elephas.mla.attend"):
                u = jax.lax.switch(pick, branches, q_lat[:, 0], lat)
            x = x + _mla.unabsorb_output(attn, u[:, None], c)
            x, st = _mlp_sublayer(layer, x, c, i, live=live[:, None])
            if st is not None:
                stats.append(st)
            continue
        q, k_new, v_new = _qkv(layer, h, c)
        if c.positional == "rope":
            q = _apply_rope(q, rp, c)
            k_new = _apply_rope(k_new, rp, c)

        lc = pool[f"layer_{i}"]
        # scatter this position's k/v into each row's owning block:
        # target (block, head, offset) per (b, h)
        widx = (blk[:, None], hidx[None, :], off[:, None])
        pk = lc["k"].at[widx].set(k_new[:, :, 0])
        pv = lc["v"].at[widx].set(v_new[:, :, 0])
        new_pool[f"layer_{i}"] = {"k": pk, "v": pv}

        qg = q.reshape(b, c.kv_heads, groups, c.head_dim)
        # (a single width is no branch: lax.switch calls it)
        o = jax.lax.switch(pick, branches, qg, pk, pv).reshape(
            b, c.num_heads, 1, c.head_dim)
        # (a config with a mixer: row r's state is slot r's, read and
        # written once, for every row of the batch -- a row that is not
        # live leaves a state that its slot's next install overwrites)
        x = x + _with_mixer(_attn_out(layer, o, c), layer, h,
                            pool.get("state"), new_state, i, c)
        x, st = _mlp_sublayer(layer, x, c, i, live=live[:, None])
        if st is not None:
            stats.append(st)
    if new_state:
        new_pool["state"] = new_state
    logits = head_logits(params["embed"], params["final_ln"], x[:, 0],
                         head=params.get("head"), norm=c.norm,
                         rms_norm_eps=c.rms_norm_eps,
                         multipliers=c.multipliers)
    if with_stats:
        return logits, new_pool, None if not stats else {
            "counts": sum(st["counts"] for st in stats),
            "picks": jnp.stack([st["picks"][:, 0] for st in stats])}
    return logits, new_pool


def decode_block_paged(params: Dict, pool: Dict, tables: jnp.ndarray,
                       tokens: jnp.ndarray, pos0,
                       config: TransformerConfig) -> Tuple[jnp.ndarray,
                                                           Dict]:
    """Multi-token cached decode over the block pool: process ``(B, S)``
    tokens sitting at per-row positions ``pos0 .. pos0+S-1``, scattering
    each position's k/v into the owning block of that row's table, and
    return (logits ``(B, S, vocab)``, updated pool).

    The paged mirror of
    :func:`~elephas_tpu.models.transformer.decode_block` (vector-``pos0``
    form) and the ``S > 1`` generalization of :func:`decode_step_paged` —
    the verify pass of paged speculative decoding. Math matches
    ``decode_block`` exactly (norms, RoPE convention, GQA grouping,
    window/ALiBi masks); within the block each query attends causally to
    cache positions ``<= pos0 + j`` (all S positions' k/v are written
    before attention, so intra-block attention sees the new keys).
    Writes are confined to the row's own table — a row's rejected
    (stale) tail positions are masked until later rounds overwrite them
    and can never corrupt another row's blocks."""
    c = config
    require_per_head_cache(c, "decode_block_paged (the speculative "
                              "verify pass)")
    require_stateless_cache(c, "decode_block_paged (the speculative "
                               "verify pass, which rolls rows back)")
    if c.multipliers is not None:
        raise ValueError("decode_block_paged has no form with "
                         "multipliers yet")
    b, s = tokens.shape
    bs = _block_size(pool)
    mb = tables.shape[1]
    length = mb * bs                               # gathered view length
    pos0 = jnp.asarray(pos0)
    blockpos = pos0[:, None] + jnp.arange(s)[None, :]        # (B, S)
    blk = jnp.take_along_axis(tables, blockpos // bs, axis=1)  # (B, S)
    off = blockpos % bs

    x = params["embed"]["tokens"][tokens]          # (B, S, D)
    if c.positional == "learned":
        x = x + params["embed"]["pos"][blockpos]
    elif c.positional == "sinusoidal":
        x = x + _sinusoidal_table(blockpos, c.d_model)
    x = x.astype(c.dtype)

    kpos = jnp.arange(length)
    mask = kpos[None, None, :] <= blockpos[:, :, None]       # (B, S, L)
    if c.attention_window is not None:
        mask = mask & (kpos[None, None, :]
                       > blockpos[:, :, None] - c.attention_window)
    scale = 1.0 / math.sqrt(c.head_dim)
    rp = blockpos[:, None, :]                      # (B, 1, S) rope angles
    groups = c.num_heads // c.kv_heads
    hidx = jnp.arange(c.kv_heads)
    # scatter target per (b, s): (block, head, offset) — broadcast to
    # (B, S, H). Distinct rows own disjoint tables; within a row the S
    # positions are distinct (block, offset) pairs; only inactive rows
    # (tables all zero) collide, and they collide on the scratch sink
    widx = (blk[:, :, None], hidx[None, None, :], off[:, :, None])
    new_pool: Dict = {}
    for i in range(c.num_layers):
        layer = params[f"layer_{i}"]
        h = _norm(x, layer["ln1"], c).astype(c.dtype)
        q = jnp.einsum("bsd,dhk->bhsk", h,
                       layer["attn"]["wq"].astype(c.dtype))
        k_new = jnp.einsum("bsd,dhk->bhsk", h,
                           layer["attn"]["wk"].astype(c.dtype))
        v_new = jnp.einsum("bsd,dhk->bhsk", h,
                           layer["attn"]["wv"].astype(c.dtype))
        if c.positional == "rope":
            q = _apply_rope(q, rp, c)
            k_new = _apply_rope(k_new, rp, c)

        lc = pool[f"layer_{i}"]
        # (B, H, S, D) -> (B, S, H, D) to line up with the (B, S, H)
        # scatter index
        pk = lc["k"].at[widx].set(jnp.swapaxes(k_new, 1, 2))
        pv = lc["v"].at[widx].set(jnp.swapaxes(v_new, 1, 2))
        new_pool[f"layer_{i}"] = {"k": pk, "v": pv}

        ck = jnp.swapaxes(pk[tables], 1, 2).reshape(
            b, c.kv_heads, length, c.head_dim)
        cv = jnp.swapaxes(pv[tables], 1, 2).reshape(
            b, c.kv_heads, length, c.head_dim)

        qg = q.reshape(b, c.kv_heads, groups, s, c.head_dim)
        scores = jnp.einsum("bngsk,bntk->bngst", qg, ck) * scale
        if c.positional == "alibi":
            dist = (blockpos[:, :, None] - kpos[None, None, :]).astype(
                jnp.float32)                       # (B, S, L)
            ab = (-_alibi_slopes(c.num_heads)[None, :, None, None]
                  * dist[:, None]).reshape(b, c.kv_heads, groups, s,
                                           length)
            scores = scores + ab
        scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
        weights = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("bngst,bntk->bngsk", weights, cv)
        o = o.reshape(b, c.num_heads, s, c.head_dim)
        x = x + jnp.einsum("bhsk,hkd->bsd", o,
                           layer["attn"]["wo"].astype(c.dtype))
        x, _ = _mlp_sublayer(layer, x, c, i)
    logits = head_logits(params["embed"], params["final_ln"], x,
                         head=params.get("head"), norm=c.norm,
                         rms_norm_eps=c.rms_norm_eps)
    return logits, new_pool
