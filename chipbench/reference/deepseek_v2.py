"""Plain reference for the family ``deepseek_v2`` (DeepSeek-V2 shape).

Written from the published description (DeepSeek-AI 2024, "DeepSeek-V2",
arXiv:2405.04434, sections 2.1 and 2.2, and the equations of the Hugging
Face ``DeepseekV2ForCausalLM``): pre-norm blocks of RMSNorm -> multi-head
latent attention (low-rank query ``W_qa`` / ``W_qb``, joint low-rank
key/value ``W_kva`` / ``W_kvb``, a rope key shared by all heads, YaRN
frequencies, softmax scale ``(nope + rope)^-1/2 * mscale^2``) -> RMSNorm
-> a dense SwiGLU in the leading ``first_k_dense_replace`` layers, else
the expert layer: softmax router over all ``router_experts`` in float32,
``group_limited_greedy`` choice (``topk_group`` best of ``n_group``
groups by their best expert, then the ``num_experts_per_tok`` largest
probabilities among them, not renormalised), routed sum times
``routed_scaling_factor``, plus the shared experts as one SwiGLU of width
``n_shared_experts * moe_intermediate_size``. A final RMSNorm and an
untied head.

Straight ``jax.numpy`` in float32 under "highest" matmul precision, in
the PREFILL form of the attention: K and V are expanded for every
position, there is no cache and nothing is absorbed. The experts are a
Python loop with a 0/1 mask: no sort, no grouping, one expert's weights
cast to float32 at a time. Attention runs over ``HEAD_GROUP`` heads at a
time (``lax.map``) so that the score matrix of a 1,536-token row stays
under 0.2 GB. Nothing is imported from the program.

**The chip's share.** ``cfg["n_routed_experts"]`` experts are held here,
the range ``[held_first, held_first + n_routed_experts)`` of the
router's ``cfg["router_experts"]``; the routed sum runs over the picks
that fall on them and what the absent experts would add is left out, as
in the program. ``vocab_size`` is the slice held here.

Weights come in this file's own layout (the family file maps the
program's tree onto it)::

    {"embed": (V, D), "head": (D, V), "final_norm": (D,),
     "layers": [{"attn_norm": (D,), "wq_a": (D, Rq), "q_norm": (Rq,),
                 "wq_b": (Rq, H*(nope+rope)), "wkv_a": (D, Rkv+rope),
                 "kv_norm": (Rkv,), "wkv_b": (Rkv, H*(nope+v)),
                 "wo": (H*v, D), "mlp_norm": (D,),
                 # dense layer:
                 "w_gate": (D, F), "w_up": (D, F), "w_down": (F, D)
                 # or expert layer:
                 "router": (D, E_all), "e_gate": (E, D, Fe),
                 "e_up": (E, D, Fe), "e_down": (E, Fe, D),
                 "s_gate": (D, Fs), "s_up": (D, Fs), "s_down": (Fs, D)}]}

Departures from the published model, all inherited from the program so
that the same seeded weights can be compared: the rope dims are paired
half-split (``rotate_half``) where the checkpoints interleave them (a
fixed permutation of the rope columns of ``wq_b`` and ``wkv_a``, which
seeded weights do not see); the dense layer's SwiGLU carries the
program's biases ``b_gate`` and ``b_down`` when given (zero); weights
are seeded, not trained.

``last_picks`` (expert layers, B, k), where given, are the experts whose
outputs are summed at each row's LAST position in place of the
reference's own choice there, weighted by the reference's own float32
probabilities: a comparison hands in the picks of the program it holds
to account, so that a near-tie the program broke the other way does not
show as an expert's whole output in the logits. The picks and margins
returned stay the reference's own choice at every position.

``cfg`` is the configuration file's own dict (Hugging Face key names).
``precision`` is ``"float32"`` or, for showing that the comparison's
limits catch a lower precision, ``"float8"``: every matmul operand is
rounded through ``float8_e4m3fn`` first; ``"float8_experts"`` rounds the
routed experts' operands only.
"""
import math

import jax
import jax.numpy as jnp

__all__ = ["forward", "forward_with_routing", "expert_layer", "route"]

F32 = jnp.float32
#: heads attended at a time (memory, not mathematics)
HEAD_GROUP = 16


def _cast(x, precision):
    x = x.astype(F32)
    if precision == "float8":
        return x.astype(jnp.float8_e4m3fn).astype(F32)
    return x


def _mm(a, b, precision):
    return _cast(a, precision) @ _cast(b, precision)


def _rms_norm(x, gamma, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gamma.astype(F32)


def _yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _rope_angles(positions, cfg):
    """(T, rope) angles, duplicated over the two halves, and the
    amplitude cos and sin carry (YaRN; 1 without ``rope_scaling``)."""
    dim = int(cfg["qk_rope_head_dim"])
    base = float(cfg["rope_theta"])
    freq = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=F32) / dim))
    amplitude = 1.0
    scaling = cfg.get("rope_scaling")
    if scaling:
        factor = float(scaling["factor"])
        original = float(scaling["original_max_position_embeddings"])

        def dim_of(rotations):
            return dim * math.log(original / (rotations * 2 * math.pi)) \
                / (2 * math.log(base))

        low = max(math.floor(dim_of(float(scaling["beta_fast"]))), 0)
        high = min(math.ceil(dim_of(float(scaling["beta_slow"]))), dim - 1)
        high = high + 0.001 if low == high else high
        ramp = jnp.clip((jnp.arange(dim // 2, dtype=F32) - low)
                        / (high - low), 0.0, 1.0)
        freq = freq / factor * ramp + freq * (1.0 - ramp)
        amplitude = (_yarn_mscale(factor, float(scaling["mscale"]))
                     / _yarn_mscale(factor,
                                    float(scaling["mscale_all_dim"])))
    angles = positions.astype(F32)[:, None] * freq[None, :]
    return jnp.concatenate([angles, angles], axis=-1), amplitude


def _rotate(x, angles, amplitude):
    """x: (..., T, rope) with T on the second-to-last axis."""
    half = x.shape[-1] // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return (x * jnp.cos(angles) + rotated * jnp.sin(angles)) * amplitude


def _softmax_scale(cfg):
    scale = (int(cfg["qk_nope_head_dim"])
             + int(cfg["qk_rope_head_dim"])) ** -0.5
    scaling = cfg.get("rope_scaling")
    if scaling and scaling.get("mscale_all_dim"):
        scale *= _yarn_mscale(float(scaling["factor"]),
                              float(scaling["mscale_all_dim"])) ** 2
    return scale


def _attention(layer, h, cfg, precision):
    """MLA in its prefill form. h: (B, T, D) -> (B, T, D)."""
    batch, length, _ = h.shape
    heads = int(cfg["num_attention_heads"])
    nope, rope = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
    vdim, rank = int(cfg["v_head_dim"]), int(cfg["kv_lora_rank"])
    eps = float(cfg["rms_norm_eps"])
    positions = jnp.arange(length)
    angles, amplitude = _rope_angles(positions, cfg)
    cq = _rms_norm(_mm(h, layer["wq_a"], precision), layer["q_norm"], eps)
    q = _mm(cq, layer["wq_b"], precision).reshape(
        batch, length, heads, nope + rope).transpose(0, 2, 1, 3)
    q_nope, q_rope = q[..., :nope], _rotate(q[..., nope:], angles, amplitude)
    kv = _mm(h, layer["wkv_a"], precision)
    ckv = _rms_norm(kv[..., :rank], layer["kv_norm"], eps)
    k_rope = _rotate(kv[..., rank:], angles, amplitude)        # (B, T, rope)
    kvb = _mm(ckv, layer["wkv_b"], precision).reshape(
        batch, length, heads, nope + vdim).transpose(0, 2, 1, 3)
    k_nope, v = kvb[..., :nope], kvb[..., nope:]
    causal = positions[None, :] <= positions[:, None]
    scale = _softmax_scale(cfg)
    group = math.gcd(heads, HEAD_GROUP)

    def attend(part):
        qn, qr, kn, vv = part                   # (B, group, T, .)
        scores = (jnp.einsum("bhqd,bhkd->bhqk", _cast(qn, precision),
                             _cast(kn, precision))
                  + jnp.einsum("bhqd,bkd->bhqk", _cast(qr, precision),
                               _cast(k_rope, precision))) * scale
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", _cast(probs, precision),
                          _cast(vv, precision))

    def split(x):                               # (B, H, T, d) -> groups
        return x.reshape(batch, heads // group, group, length,
                         x.shape[-1]).transpose(1, 0, 2, 3, 4)

    out = jax.lax.map(attend, (split(q_nope), split(q_rope),
                               split(k_nope), split(v)))
    out = out.transpose(1, 0, 2, 3, 4).reshape(batch, heads, length, vdim)
    out = out.transpose(0, 2, 1, 3).reshape(batch, length, heads * vdim)
    return _mm(out, layer["wo"], precision)


def route(h, router, cfg):
    """The router on normalised activations h (..., D), float32
    whatever ``precision``: returns ``(weights, picks, margin, probs)``:
    the ``num_experts_per_tok`` kept probabilities times
    ``routed_scaling_factor``, their expert ids over all
    ``router_experts``, how decided the choice was -- the smaller of
    the relative gaps between the last kept and the first dropped
    group's score and between the last kept and the first dropped
    expert's probability (a bf16 program may choose differently where
    this is small, and only there) -- and every expert's probability."""
    experts = int(cfg["router_experts"])
    groups, keep = int(cfg["n_group"]), int(cfg["topk_group"])
    top_k = int(cfg["num_experts_per_tok"])
    if cfg.get("topk_method") != "group_limited_greedy" or \
            cfg.get("scoring_func", "softmax") != "softmax" or \
            cfg.get("norm_topk_prob"):
        raise ValueError("the reference knows the softmax, "
                         "group_limited_greedy, unnormalised router only")
    probs = jax.nn.softmax(h.astype(F32) @ router.astype(F32), axis=-1)
    per_group = probs.reshape(*probs.shape[:-1], groups, experts // groups)
    group_score = per_group.max(axis=-1)
    ranked = jnp.sort(group_score, axis=-1)[..., ::-1]
    group_margin = ((ranked[..., keep - 1] - ranked[..., keep])
                    / ranked[..., keep - 1] if keep < groups
                    else jnp.ones(probs.shape[:-1], F32))
    kept = group_score >= ranked[..., keep - 1:keep]
    eligible = jnp.where(kept[..., None], per_group, 0.0).reshape(
        probs.shape)
    order = jnp.argsort(-eligible, axis=-1)
    picks = order[..., :top_k]
    sorted_p = jnp.take_along_axis(eligible, order[..., :top_k + 1], axis=-1)
    pick_margin = ((sorted_p[..., top_k - 1] - sorted_p[..., top_k])
                   / sorted_p[..., top_k - 1])
    weights = sorted_p[..., :top_k] * float(cfg["routed_scaling_factor"])
    return weights, picks, jnp.minimum(group_margin, pick_margin), probs


def _swiglu(h, gate, up, down, precision):
    return _mm(jax.nn.silu(_mm(h, gate, precision)) * _mm(h, up, precision),
               down, precision)


def expert_layer(layer, h, cfg, precision="float32", shared=True,
                 last_picks=None):
    """The expert layer on normalised activations h (B, T, D): this
    chip's routed part plus (``shared``) the shared experts. Returns
    ``(out, picks, margin)``, the router's own choice; with
    ``last_picks`` (B, k) the experts summed at the last position are
    those."""
    first = int(cfg.get("held_first", 0))
    held = int(cfg["n_routed_experts"])
    weights, picks, margin, probs = route(h, layer["router"], cfg)
    used, used_weights = picks, weights
    if last_picks is not None:
        used = picks.at[:, -1].set(last_picks)
        used_weights = weights.at[:, -1].set(
            jnp.take_along_axis(probs[:, -1], last_picks, axis=-1)
            * float(cfg["routed_scaling_factor"]))
    out = jnp.zeros(h.shape, F32)
    routed = "float8" if precision == "float8_experts" else precision
    for e in range(held):                       # one expert at a time
        gate_e = jnp.sum(jnp.where(used == first + e, used_weights, 0.0),
                         axis=-1)               # 0 where not picked
        out = out + gate_e[..., None] * _swiglu(
            h, layer["e_gate"][e], layer["e_up"][e], layer["e_down"][e],
            routed)
    if shared and "s_gate" in layer:
        out = out + _swiglu(h, layer["s_gate"], layer["s_up"],
                            layer["s_down"], precision)
    return out, picks, margin


def forward_with_routing(weights, tokens, cfg, precision="float32",
                         last_picks=None):
    """Token ids (B, T) -> (float32 logits (B, T, V), picks (L_e, B, T,
    k), margins (L_e, B, T)) over the ``L_e`` expert layers;
    ``last_picks``: (L_e, B, k), see the top of the file."""
    eps = float(cfg["rms_norm_eps"])
    picks, margins = [], []
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens].astype(F32)
        for layer in weights["layers"]:
            h = _rms_norm(x, layer["attn_norm"], eps)
            x = x + _attention(layer, h, cfg, precision)
            h = _rms_norm(x, layer["mlp_norm"], eps)
            if "router" in layer:
                out, picked, margin = expert_layer(
                    layer, h, cfg, precision,
                    last_picks=None if last_picks is None
                    else last_picks[len(picks)])
                picks.append(picked)
                margins.append(margin)
                x = x + out
            else:
                gate = _mm(h, layer["w_gate"], precision)
                if "b_gate" in layer:
                    gate = gate + layer["b_gate"].astype(F32)
                x = x + _mm(jax.nn.silu(gate)
                            * _mm(h, layer["w_up"], precision),
                            layer["w_down"], precision)
                if "b_down" in layer:
                    x = x + layer["b_down"].astype(F32)
        x = _rms_norm(x, weights["final_norm"], eps)
        logits = _mm(x, weights["head"], precision)
    return logits, jnp.stack(picks), jnp.stack(margins)


def forward(weights, tokens, cfg, precision="float32"):
    """Token ids (B, T) -> float32 logits (B, T, V)."""
    return forward_with_routing(weights, tokens, cfg, precision)[0]
