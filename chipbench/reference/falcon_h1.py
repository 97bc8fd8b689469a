"""Plain reference for the family ``falcon_h1`` (Falcon-H1 shape).

Written from the published description (Falcon-H1 technical report, TII
2025, and the equations of the Hugging Face ``FalconH1ForCausalLM``; the
mixer is Mamba-2, Dao and Gu 2024, "Transformers are SSMs"). Every block
normalises its input once and gives it to TWO mixers whose outputs are
added: a Mamba-2 (SSD) mixer and grouped-query attention. With ``h`` the
block's input and the keys of ``config.json`` in backticks::

    x0      = embed[tokens] * embedding_multiplier
    u       = rmsnorm(h; input_layernorm, rms_norm_eps)
    # mixer: d_ssm = mamba_d_ssm = mamba_n_heads x mamba_d_head,
    #        G = mamba_n_groups, N = mamba_d_state
    p       = (u * ssm_in_multiplier) @ W_in           # no bias
    p       = p * mup, mup = ssm_multipliers[0..4] over the segments
              [z d_ssm | x d_ssm | B G*N | C G*N | dt heads]
    z, xBC, dt = split(p)
    xBC     = silu(causal_depthwise_conv1d(xBC; kernel mamba_d_conv, bias))
    x, B, C = split(xBC);  head i uses group i // (heads / G)
    dt      = softplus(dt + dt_bias);  A = -exp(A_log);  a_t = exp(dt_t * A)
    S_t     = a_t * S_{t-1} + dt_t * x_t (outer) B_t      # (head_dim, N) a head
    y_t     = S_t @ C_t + D * x_t
    y       = group_rmsnorm(y * silu(z); G groups, weight, rms_norm_eps)
    m       = (y @ W_out) * ssm_out_multiplier
    # attention, on the same u
    q, k, v = (u * attention_in_multiplier) @ Wq, Wk, Wv
    k       = k * key_multiplier;  RoPE(q, k; rope_theta, whole head, rotate-half)
    a       = softmax(q k^T / sqrt(head_dim), causal) v @ Wo * attention_out_multiplier
    h       = h + m + a
    f       = rmsnorm(h; pre_ff_layernorm)
    h       = h + (silu((f @ W_gate) * mlp_multipliers[0]) * (f @ W_up)) @ W_down
                  * mlp_multipliers[1]
    logits  = (rmsnorm(h_last; final_layernorm) @ head) * lm_head_multiplier

Straight ``jax.numpy`` in float32 under "highest" matmul precision. The
recurrence is a token-by-token ``lax.scan``: no chunking, no cache, no
batching, and the convolution is four shifted copies of the zero-padded
sequence. Nothing is imported from the program. :func:`embed`,
:func:`block` and :func:`head` are the three pieces of :func:`forward`,
so that a caller with 10 GB of weights on a 16 GB chip can run the layers
one at a time and the head in blocks of the vocabulary.

Weights come in this file's own layout (the family file maps the
program's tree onto it)::

    {"embed": (V, D), "head": (D, V), "final_norm": (D,),
     "layers": [{"attn_norm": (D,), "wq": (D, H*hd), "wk": (D, KV*hd),
                 "wv": (D, KV*hd), "wo": (H*hd, D),
                 "w_in": (D, 2*d_ssm + 2*G*N + heads),
                 "conv_w": (d_conv, d_ssm + 2*G*N), "conv_b": (d_ssm + 2*G*N,),
                 "A_log": (heads,), "dt_bias": (heads,), "D": (heads,),
                 "ssm_norm": (d_ssm,), "w_out": (d_ssm, D),
                 "mlp_norm": (D,), "w_gate": (D, F), "b_gate": (F,),
                 "w_up": (D, F), "w_down": (F, D), "b_down": (D,)}]}

``conv_w[k]`` multiplies the input ``d_conv - 1 - k`` tokens back (the
last row the current token), which is PyTorch's ``Conv1d`` weight
``(channels, 1, d_conv)`` transposed.

Where this reading differs from the published code, and why:

- ``mamba_rms_norm`` is true in the 34B config and the norm is applied;
  ``mamba_norm_before_gate`` is false, so the gate comes first.
- The gate and down projections carry the program's biases ``b_gate``
  and ``b_down`` (zero; Falcon-H1 has none, ``mlp_bias`` false). The
  bias is added before the multiplier, as the program does; at zero the
  order does not show.
- ``D`` is a scalar a head (``(heads,)``), as in the published class.
- Weights are seeded, not trained.

``state_round``, where given, is applied to the state after every token
(a control: the state kept in a lower precision).
"""
import jax
import jax.numpy as jnp

__all__ = ["forward", "embed", "block", "head"]

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


def _mixer(layer, u, cfg, state_round=None):
    """The Mamba-2 mixer over the normed input ``u`` (B, T, D)."""
    d_ssm = int(cfg["mamba_d_ssm"])
    heads, hd = int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"])
    groups, n = int(cfg["mamba_n_groups"]), int(cfg["mamba_d_state"])
    taps = int(cfg["mamba_d_conv"])
    eps = float(cfg["rms_norm_eps"])
    batch, length, _ = u.shape
    gn = groups * n
    mup = jnp.concatenate([
        jnp.full((width,), float(scale), F32) for width, scale in zip(
            (d_ssm, d_ssm, gn, gn, heads), cfg["ssm_multipliers"])])
    p = ((u * float(cfg["ssm_in_multiplier"]))
         @ layer["w_in"].astype(F32)) * mup
    z, xbc, dt = (p[..., :d_ssm], p[..., d_ssm:2 * d_ssm + 2 * gn],
                  p[..., 2 * d_ssm + 2 * gn:])
    # causal depthwise convolution: taps - 1 zeros in front
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = layer["conv_b"].astype(F32) + sum(
        padded[:, k:k + length] * layer["conv_w"].astype(F32)[k]
        for k in range(taps))
    xbc = jax.nn.silu(conv)
    x = xbc[..., :d_ssm].reshape(batch, length, heads, hd)
    b_in = xbc[..., d_ssm:d_ssm + gn].reshape(batch, length, groups, n)
    c_out = xbc[..., d_ssm + gn:].reshape(batch, length, groups, n)
    # head i reads group i // (heads / groups)
    b_in = jnp.repeat(b_in, heads // groups, axis=2)          # (B, T, H, N)
    c_out = jnp.repeat(c_out, heads // groups, axis=2)
    dt = jax.nn.softplus(dt + layer["dt_bias"].astype(F32))   # (B, T, H)
    a = jnp.exp(dt * -jnp.exp(layer["A_log"].astype(F32)))
    skip = layer["D"].astype(F32)

    def token(state, now):
        x_t, b_t, c_t, dt_t, a_t = now
        state = (a_t[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        if state_round is not None:
            state = state_round(state)
        y_t = jnp.einsum("bhpn,bhn->bhp", state, c_t) \
            + skip[:, None] * x_t
        return state, y_t

    _, y = jax.lax.scan(
        token, jnp.zeros((batch, heads, hd, n), F32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (x, b_in, c_out, dt, a)))
    y = jnp.moveaxis(y, 0, 1).reshape(batch, length, d_ssm)
    y = y * jax.nn.silu(z)
    y = y.reshape(batch, length, groups, d_ssm // groups)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
    y = y.reshape(batch, length, d_ssm) * layer["ssm_norm"].astype(F32)
    return (y @ layer["w_out"].astype(F32)) * float(
        cfg["ssm_out_multiplier"])


def _attention(layer, u, cfg):
    heads = int(cfg["num_attention_heads"])
    kv_heads = int(cfg["num_key_value_heads"])
    hd = int(cfg["head_dim"])
    batch, length, _ = u.shape
    positions = jnp.arange(length)
    causal = positions[None, :] <= positions[:, None]        # key <= query
    u = u * float(cfg["attention_in_multiplier"])
    q = (u @ layer["wq"].astype(F32)).reshape(batch, length, heads, hd)
    k = (u @ layer["wk"].astype(F32)).reshape(batch, length, kv_heads, hd)
    v = (u @ layer["wv"].astype(F32)).reshape(batch, length, kv_heads, hd)
    k = k * float(cfg["key_multiplier"])
    theta = float(cfg["rope_theta"])
    q, k = _rope(q, positions, theta), _rope(k, positions, theta)
    k = jnp.repeat(k, heads // kv_heads, axis=2)
    v = jnp.repeat(v, heads // kv_heads, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
        jnp.asarray(hd, F32))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    attn = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(
        batch, length, heads * hd)
    return (attn @ layer["wo"].astype(F32)) * float(
        cfg["attention_out_multiplier"])


def embed(weights, tokens, cfg):
    """Token ids (B, T) -> the first block's input (B, T, D)."""
    return weights["embed"][tokens].astype(F32) * float(
        cfg["embedding_multiplier"])


def block(layer, x, cfg, state_round=None):
    """One block: (B, T, D) -> (B, T, D)."""
    eps = float(cfg["rms_norm_eps"])
    gate_mult, down_mult = (float(m) for m in cfg["mlp_multipliers"])
    with jax.default_matmul_precision("highest"):
        u = _rms_norm(x, layer["attn_norm"], eps)
        x = x + _mixer(layer, u, cfg, state_round) \
            + _attention(layer, u, cfg)
        f = _rms_norm(x, layer["mlp_norm"], eps)
        gate = jax.nn.silu((f @ layer["w_gate"].astype(F32)
                            + layer["b_gate"].astype(F32)) * gate_mult)
        up = f @ layer["w_up"].astype(F32)
        return x + ((gate * up) @ layer["w_down"].astype(F32)
                    + layer["b_down"].astype(F32)) * down_mult


def head(weights, x, cfg, first=0, count=None):
    """The last block's output (..., D) -> float32 logits over the
    vocabulary columns ``[first, first + count)`` (all, by default;
    ``first`` may be traced)."""
    matrix = weights["head"]
    if count is not None:
        matrix = jax.lax.dynamic_slice_in_dim(matrix, first, count, axis=1)
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(x, weights["final_norm"], float(cfg["rms_norm_eps"]))
        return (x @ matrix.astype(F32)) * float(cfg["lm_head_multiplier"])


def forward(weights, tokens, cfg, state_round=None):
    """Token ids (B, T) -> float32 logits (B, T, V)."""
    x = embed(weights, tokens, cfg)
    for layer in weights["layers"]:
        x = block(layer, x, cfg, state_round)
    return head(weights, x, cfg)
