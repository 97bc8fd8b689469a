"""Plain reference for the dense decoder family (Mistral-7B-v0.1 shape).

Written from the published description (Jiang et al. 2023, "Mistral 7B";
the Hugging Face ``MistralForCausalLM`` equations): pre-norm blocks of
RMSNorm -> grouped-query attention with rotary embeddings (half-split
``rotate_half`` convention) under a causal sliding-window mask ->
RMSNorm -> SiLU-gated feed-forward, a final RMSNorm and an untied head.
Straight ``jax.numpy`` in float32 under "highest" matmul precision: no
cache, no kernel, no batching tricks, nothing imported from the program.

Weights come in this file's own layout (the family file maps the
program's tree onto it)::

    {"embed": (V, D), "head": (D, V), "final_norm": (D,),
     "layers": [{"attn_norm": (D,), "wq": (D, H*hd), "wk": (D, KV*hd),
                 "wv": (D, KV*hd), "wo": (H*hd, D), "mlp_norm": (D,),
                 "w_gate": (D, F), "b_gate": (F,), "w_up": (D, F),
                 "w_down": (F, D), "b_down": (D,)}]}

Departures from the published model, both inherited from the program so
that the same seeded weights can be compared: the gate and down
projections carry a bias (``b_gate``, ``b_down``; the program initialises
them to zero, Mistral has none), and weights are seeded, not trained.

``cfg`` is the configuration file's own dict (Hugging Face key names).
"""
import jax
import jax.numpy as jnp

__all__ = ["forward", "loss"]

F32 = jnp.float32


def _rms_norm(x, gamma, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gamma.astype(F32)


def _rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def _rope(x, positions, theta):
    """x: (B, T, heads, hd); positions: (T,)."""
    hd = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    angles = positions.astype(F32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)      # (T, hd)
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    return x * cos + _rotate_half(x) * sin


def forward(weights, tokens, cfg):
    """Token ids (B, T) -> float32 logits (B, T, V)."""
    heads = int(cfg["num_attention_heads"])
    kv_heads = int(cfg["num_key_value_heads"])
    hd = int(cfg["hidden_size"]) // heads
    eps = float(cfg["rms_norm_eps"])
    theta = float(cfg["rope_theta"])
    window = cfg.get("sliding_window")
    batch, length = tokens.shape
    positions = jnp.arange(length)
    causal = positions[None, :] <= positions[:, None]        # key <= query
    if window is not None:
        causal &= positions[None, :] > positions[:, None] - int(window)
    with jax.default_matmul_precision("highest"):
        x = weights["embed"].astype(F32)[tokens]
        for layer in weights["layers"]:
            h = _rms_norm(x, layer["attn_norm"], eps)
            q = (h @ layer["wq"].astype(F32)).reshape(
                batch, length, heads, hd)
            k = (h @ layer["wk"].astype(F32)).reshape(
                batch, length, kv_heads, hd)
            v = (h @ layer["wv"].astype(F32)).reshape(
                batch, length, kv_heads, hd)
            q, k = _rope(q, positions, theta), _rope(k, positions, theta)
            k = jnp.repeat(k, heads // kv_heads, axis=2)
            v = jnp.repeat(v, heads // kv_heads, axis=2)
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
                jnp.asarray(hd, F32))
            scores = jnp.where(causal[None, None], scores, -jnp.inf)
            probs = jax.nn.softmax(scores, axis=-1)
            attn = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(
                batch, length, heads * hd)
            x = x + attn @ layer["wo"].astype(F32)
            h = _rms_norm(x, layer["mlp_norm"], eps)
            gate = jax.nn.silu(h @ layer["w_gate"].astype(F32)
                               + layer["b_gate"].astype(F32))
            up = h @ layer["w_up"].astype(F32)
            x = x + (gate * up) @ layer["w_down"].astype(F32) \
                + layer["b_down"].astype(F32)
        x = _rms_norm(x, weights["final_norm"], eps)
        return x @ weights["head"].astype(F32)


def loss(weights, tokens, cfg):
    """Mean next-token cross-entropy over every predicting position."""
    logits = forward(weights, tokens, cfg)[:, :-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -jnp.mean(picked)
