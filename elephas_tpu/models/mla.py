"""Multi-head latent attention (MLA, DeepSeek-V2 §2.1) with YaRN RoPE.

Keys and values are not cached per head. Each position caches ONE vector
of ``kv_lora_rank + qk_rope_head_dim`` values (zero-padded to a multiple
of 128, :func:`latent_width`): the RMS-normalised latent ``c_kv`` and
the rotated rope key ``k_rope`` that all heads share. Two
forms of the same attention:

- **expanded** (:func:`attend_expanded`; prefill, chunked extend, the
  training forward): ``[k_nope_h ; v_h] = c_kv W_kvb`` is expanded for
  every cached position and attention runs per head as usual;
- **absorbed** (:func:`absorb_query` / :func:`unabsorb_output`; the
  paged decode step): ``W_kvb``'s key half is folded into the query
  (``q~_h = q_nope_h W_kvb^K,h^T``) and its value half is applied after
  the weighted sum of latents, so a step reads one latent a position
  and never expands K or V.

Parameters of one layer (``layer["attn"]``)::

    wq_a (D, q_rank)   q_norm {gamma (q_rank,)}
    wq_b (q_rank, H, nope + rope)
    wkv_a (D, kv_rank + rope)   kv_norm {gamma (kv_rank,)}
    wkv_b (kv_rank, H, nope + v)
    wo (H, v, D)

The rope dims use the half-split ``rotate_half`` convention of
:func:`~elephas_tpu.models.transformer._apply_rope` (the published
checkpoints interleave the pairs; with seeded weights the two differ by
a fixed permutation of ``wq_b``'s and ``wkv_a``'s rope columns).

Nothing here imports :mod:`~elephas_tpu.models.transformer`: that module
imports this one.
"""
import math
from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.attention import NEG_INF

__all__ = ["YarnScaling", "latent_width", "init_attn", "attn_specs",
           "softmax_scale", "rope", "project_query", "project_latent",
           "attend_expanded", "absorb_query", "unabsorb_output",
           "attn_full"]


class YarnScaling(NamedTuple):
    """YaRN RoPE scaling (Peng et al. 2023), hashable so that it can sit
    in a frozen config."""

    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


#: the cached vector is padded to a multiple of this many values
LATENT_ALIGN = 128


def latent_width(c) -> int:
    """Values a cache entry takes per position and layer: the latent and
    the rope key, zero-padded to a multiple of :data:`LATENT_ALIGN`
    (576 -> 640 at DeepSeek-V2's sizes). The TPU runtime's own layout
    for an array whose minor dimension is not a multiple of 128 puts the
    blocks axis innermost, and every layer of every decode step then
    copies the whole pool into a row-major layout and back (measured on
    a v5e: 10 copies of 151 MB, 5.2 ms of a 26 ms step). The padding
    columns are zeros and score as zeros."""
    used = c.kv_lora_rank + c.qk_rope_head_dim
    return -(-used // LATENT_ALIGN) * LATENT_ALIGN


def softmax_scale(c) -> float:
    """``(nope + rope)^-1/2``, times YaRN's ``mscale(factor,
    mscale_all_dim)^2`` when the rope is scaled."""
    scale = 1.0 / math.sqrt(c.qk_nope_head_dim + c.qk_rope_head_dim)
    y = c.rope_scaling
    if y is not None and y.mscale_all_dim:
        scale *= _yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def _rope_tables(c) -> Tuple[np.ndarray, float]:
    """Inverse frequencies of the rope dims (float32, half of them) and
    the factor cos and sin are scaled by. Plain RoPE without
    ``rope_scaling``; with it, YaRN: interpolated frequencies (divided
    by ``factor``) below the dim of ``beta_slow`` rotations over the
    original context, the original ones above the dim of ``beta_fast``,
    a linear ramp between."""
    dim = c.qk_rope_head_dim
    exponent = np.arange(0, dim, 2, dtype=np.float64) / dim
    extra = 1.0 / (c.rope_theta ** exponent)
    y = c.rope_scaling
    if y is None:
        return extra.astype(np.float32), 1.0

    def correction_dim(rotations):
        return (dim * math.log(y.original_max_position
                               / (rotations * 2 * math.pi))
                / (2 * math.log(c.rope_theta)))

    low = max(math.floor(correction_dim(y.beta_fast)), 0)
    high = min(math.ceil(correction_dim(y.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    inv_freq = extra / y.factor * ramp + extra * (1.0 - ramp)
    amplitude = (_yarn_mscale(y.factor, y.mscale)
                 / _yarn_mscale(y.factor, y.mscale_all_dim))
    return inv_freq.astype(np.float32), amplitude


def rope(x, positions, c):
    """Rotate the last axis of ``x`` (``qk_rope_head_dim`` wide) by the
    angles of ``positions``, which broadcasts against ``x``'s leading
    axes. Angles in float32, the rotation in ``x``'s dtype."""
    inv_freq, amplitude = _rope_tables(c)
    half = inv_freq.shape[0]
    angles = positions.astype(jnp.float32)[..., None] * jnp.asarray(inv_freq)
    cos = (jnp.cos(angles) * amplitude).astype(x.dtype)
    sin = (jnp.sin(angles) * amplitude).astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1)


def _rms(x, gamma, eps):
    ms = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(ms + eps).astype(x.dtype)) * gamma.astype(
        x.dtype)


def init_attn(c, keys, dense) -> Dict:
    """One layer's attention parameters; ``dense(key, shape, fan_in)`` is
    the caller's initialiser, ``keys`` at least five PRNG keys."""
    qk = c.qk_nope_head_dim + c.qk_rope_head_dim
    return {
        "wq_a": dense(keys[0], (c.d_model, c.q_lora_rank), c.d_model),
        "q_norm": {"gamma": jnp.ones((c.q_lora_rank,), c.param_dtype)},
        "wq_b": dense(keys[1], (c.q_lora_rank, c.num_heads, qk),
                      c.q_lora_rank),
        "wkv_a": dense(keys[2], (c.d_model,
                                 c.kv_lora_rank + c.qk_rope_head_dim),
                       c.d_model),
        "kv_norm": {"gamma": jnp.ones((c.kv_lora_rank,), c.param_dtype)},
        "wkv_b": dense(keys[3], (c.kv_lora_rank, c.num_heads,
                                 c.qk_nope_head_dim + c.v_head_dim),
                       c.kv_lora_rank),
        "wo": dense(keys[4], (c.num_heads, c.v_head_dim, c.d_model),
                    c.num_heads * c.v_head_dim),
    }


def attn_specs(P, h_ax) -> Dict:
    """PartitionSpecs mirroring :func:`init_attn`: the per-head
    up-projections and the output projection shard their head axis, the
    low-rank down-projections and their norms replicate."""
    return {"wq_a": P(None, None), "q_norm": {"gamma": P(None)},
            "wq_b": P(None, h_ax, None), "wkv_a": P(None, None),
            "kv_norm": {"gamma": P(None)}, "wkv_b": P(None, h_ax, None),
            "wo": P(h_ax, None, None)}


def project_query(attn: Dict, h, positions, c):
    """``h`` (B, S, D) -> ``q_nope`` (B, S, H, nope), rotated ``q_rope``
    (B, S, H, rope). ``positions``: (S,) or (B, S)."""
    with jax.named_scope("elephas.mla.project"):
        cq = _rms(h @ attn["wq_a"].astype(c.dtype), attn["q_norm"]["gamma"],
                  c.rms_norm_eps)
        q = jnp.einsum("bsr,rhk->bshk", cq, attn["wq_b"].astype(c.dtype))
        q_nope = q[..., :c.qk_nope_head_dim]
        q_rope = rope(q[..., c.qk_nope_head_dim:], positions[..., None], c)
    return q_nope, q_rope


def project_latent(attn: Dict, h, positions, c):
    """``h`` (B, S, D) -> what the cache holds for these positions,
    (B, S, :func:`latent_width`): the normalised latent, the rotated
    rope key, zeros."""
    with jax.named_scope("elephas.mla.project"):
        kv = h @ attn["wkv_a"].astype(c.dtype)
        ckv = _rms(kv[..., :c.kv_lora_rank], attn["kv_norm"]["gamma"],
                   c.rms_norm_eps)
        k_rope = rope(kv[..., c.kv_lora_rank:], positions, c)
        pad = latent_width(c) - kv.shape[-1]
        return jnp.concatenate(
            [ckv, k_rope, jnp.zeros(kv.shape[:-1] + (pad,), kv.dtype)],
            axis=-1)


def attend_expanded(attn: Dict, q_nope, q_rope, latent, mask, c):
    """The expanded form: ``latent`` (B, L, :func:`latent_width`) is expanded
    to per-head keys and values, queries (B, S, H, .) attend under
    ``mask`` (B|1, S, L), and the result is projected back to (B, S, D).
    Scores and the softmax are float32."""
    with jax.named_scope("elephas.mla.attend"):
        ckv = latent[..., :c.kv_lora_rank]
        k_rope = latent[..., c.kv_lora_rank:
                        c.kv_lora_rank + c.qk_rope_head_dim]
        kvb = jnp.einsum("blr,rhk->blhk", ckv, attn["wkv_b"].astype(c.dtype))
        k_nope = kvb[..., :c.qk_nope_head_dim]
        v = kvb[..., c.qk_nope_head_dim:]
        scores = (jnp.einsum("bshk,blhk->bhsl", q_nope, k_nope,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bshk,blk->bhsl", q_rope, k_rope,
                               preferred_element_type=jnp.float32))
        scores = jnp.where(mask[:, None], scores * softmax_scale(c), NEG_INF)
        weights = jax.nn.softmax(scores, axis=-1).astype(c.dtype)
        o = jnp.einsum("bhsl,blhk->bshk", weights, v)
    with jax.named_scope("elephas.mla.project"):
        return jnp.einsum("bshk,hkd->bsd", o, attn["wo"].astype(c.dtype))


def absorb_query(attn: Dict, q_nope, q_rope, c):
    """Fold ``W_kvb``'s key half into the query: (B, S, H, nope) and
    (B, S, H, rope) -> (B, S, H, :func:`latent_width`), which scores
    against the cached vector directly (zeros against its padding)."""
    with jax.named_scope("elephas.mla.project"):
        wk = attn["wkv_b"][..., :c.qk_nope_head_dim].astype(c.dtype)
        q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, wk)
        pad = latent_width(c) - c.kv_lora_rank - c.qk_rope_head_dim
        return jnp.concatenate(
            [q_lat, q_rope, jnp.zeros(q_rope.shape[:-1] + (pad,),
                                      q_rope.dtype)], axis=-1)


def unabsorb_output(attn: Dict, u, c):
    """The weighted sums of latents ``u`` (B, S, H, kv_rank) -> (B, S, D)
    through ``W_kvb``'s value half and the output projection."""
    with jax.named_scope("elephas.mla.project"):
        wv = attn["wkv_b"][..., c.qk_nope_head_dim:].astype(c.dtype)
        o = jnp.einsum("bshr,rhk->bshk", u, wv)
        return jnp.einsum("bshk,hkd->bsd", o, attn["wo"].astype(c.dtype))


def attn_full(attn: Dict, h, c):
    """Causal self-attention over a whole sequence ``h`` (B, T, D), no
    cache: the training and scoring forward."""
    t = h.shape[1]
    positions = jnp.arange(t)
    q_nope, q_rope = project_query(attn, h, positions, c)
    latent = project_latent(attn, h, positions, c)
    mask = (positions[None, :] <= positions[:, None])[None]
    return attend_expanded(attn, q_nope, q_rope, latent, mask, c)
