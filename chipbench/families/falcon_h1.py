"""Family ``falcon_h1``: a configuration file (Hugging Face key names,
Falcon-H1 shape) -> the program's ``TransformerConfig`` (a Mamba-2 mixer
beside grouped-query attention in every block, the model's fixed
multipliers), seeded parameters made on the device in one jitted call,
and the same weights in the plain reference's layout.

**Seeding.** The program's ``init_params`` gives every matrix a standard
deviation of ``1 / sqrt(fan_in)`` and the mixer Mamba-2's published
initialisation (``A_log = log(uniform(1, 16))``, ``dt_bias`` the inverse
softplus of a log-uniform 0.001-0.1, a uniform convolution: decays
between 0.85 and 1.0 a token, none degenerate). On top of that
:func:`make_params`

- divides every matrix by the fixed multiplier that FOLLOWS it (the key
  projection by ``key_multiplier``, the head by ``lm_head_multiplier``,
  each segment of the mixer's projection by ``ssm_in_multiplier`` times
  its ``ssm_multipliers`` entry, ...). The published multipliers belong
  to TRAINED weights, whose scale they complement; with unit-scale seeded
  weights a ``key_multiplier`` of 0.011 would make every attention a
  plain mean and an ``mlp_multipliers[1]`` of 0.011 every feed-forward a
  rounding error, and no comparison with the reference would see either
  layer. The multipliers themselves are applied as published;
- draws the norms' weights, the mixer's ``D`` and its convolution bias
  around their usual values instead of exactly 1 and 0, so that leaving
  one out changes the result.
"""
import jax
import jax.numpy as jnp

#: ``chipbench/reference/<REFERENCE>.py`` is this family's plain reference
REFERENCE = "falcon_h1"

#: sizes of the CPU rehearsal (``--rehearse``) and of the CPU tests: every
#: branch stays on -- 2 blocks, a mixer of 4 heads in 2 groups with a
#: state of 16, grouped-query attention 4 / 2 with heads narrower than
#: hidden / heads, an untied head, every multiplier away from 1. Never
#: used on the chip.
REHEARSE_SIZES = {
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "vocab_size": 512, "mamba_d_ssm": 64, "mamba_n_heads": 4,
    "mamba_d_head": 16, "mamba_n_groups": 2, "mamba_d_state": 16,
    "mamba_d_conv": 4, "mamba_chunk_size": 8,
    "attention_in_multiplier": 0.9, "attention_out_multiplier": 1.2,
    "embedding_multiplier": 1.5, "key_multiplier": 0.8,
    "lm_head_multiplier": 0.7, "mlp_multipliers": [1.25, 0.6],
    "ssm_in_multiplier": 1.1, "ssm_multipliers": [0.9, 1.1, 1.2, 0.8, 1.3],
    "ssm_out_multiplier": 0.75}

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def model_sizes(cfg: dict, rehearse: bool) -> dict:
    """The configuration's sizes as run (the toy ones in a rehearsal)."""
    return dict(cfg, **REHEARSE_SIZES) if rehearse else cfg


def program_config(cfg: dict, max_seq_len: int, param_dtype: str,
                   **overrides):
    """The program's config for these sizes. ``overrides`` are engine or
    test settings (``dtype``), never widths."""
    from elephas_tpu.models.transformer import (Mamba2Mixer, Multipliers,
                                                TransformerConfig)

    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError("falcon_h1 is SiLU-gated")
    if not cfg.get("mamba_rms_norm") or cfg.get("mamba_norm_before_gate") \
            or cfg.get("mamba_proj_bias") or not cfg.get("mamba_conv_bias"):
        raise ValueError("falcon_h1's mixer gates, then norms in groups, "
                         "with a convolution bias and no projection bias")
    if cfg.get("attention_bias") or cfg.get("mlp_bias") \
            or cfg.get("projectors_bias") or cfg.get("rope_scaling") \
            or cfg.get("attn_layer_indices"):
        raise ValueError("falcon_h1 has attention in every block, no "
                         "biases and plain RoPE")
    heads, d_head = int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"])
    if int(cfg["mamba_d_ssm"]) != heads * d_head:
        raise ValueError("mamba_d_ssm != mamba_n_heads x mamba_d_head")
    gate, down = cfg["mlp_multipliers"]
    settings = dict(dtype=jnp.bfloat16)
    settings.update(overrides)
    return TransformerConfig(
        vocab_size=int(cfg["vocab_size"]),
        num_layers=int(cfg["num_hidden_layers"]),
        num_heads=int(cfg["num_attention_heads"]),
        num_kv_heads=int(cfg["num_key_value_heads"]),
        attention_head_dim=int(cfg["head_dim"]),
        d_model=int(cfg["hidden_size"]),
        d_ff=int(cfg["intermediate_size"]),
        max_seq_len=int(max_seq_len), param_dtype=_DTYPES[param_dtype],
        positional="rope", rope_theta=float(cfg["rope_theta"]),
        norm="rmsnorm", rms_norm_eps=float(cfg["rms_norm_eps"]),
        mlp_variant="swiglu",
        tied_embedding=bool(cfg["tie_word_embeddings"]),
        ssm=Mamba2Mixer(
            d_ssm=int(cfg["mamba_d_ssm"]), heads=heads, head_dim=d_head,
            groups=int(cfg["mamba_n_groups"]),
            d_state=int(cfg["mamba_d_state"]),
            d_conv=int(cfg["mamba_d_conv"]),
            chunk=int(cfg["mamba_chunk_size"]),
            state_dtype=_DTYPES[cfg.get("assumed", {}).get(
                "ssm_state_dtype", "float32")]),
        multipliers=Multipliers(
            embedding=float(cfg["embedding_multiplier"]),
            lm_head=float(cfg["lm_head_multiplier"]),
            attention_in=float(cfg["attention_in_multiplier"]),
            attention_out=float(cfg["attention_out_multiplier"]),
            key=float(cfg["key_multiplier"]),
            ssm_in=float(cfg["ssm_in_multiplier"]),
            ssm=tuple(float(m) for m in cfg["ssm_multipliers"]),
            ssm_out=float(cfg["ssm_out_multiplier"]),
            mlp_gate=float(gate), mlp_down=float(down)),
        **settings)


def _seeded(params: dict, config, key) -> dict:
    """The module docstring's two steps over ``init_params``' tree."""
    mult, mixer = config.multipliers, config.ssm
    dtype = config.param_dtype
    segments = jnp.concatenate([
        jnp.full((width,), 1.0 / (mult.ssm_in * scale), jnp.float32)
        for width, scale in zip(mixer.segments, mult.ssm)])

    def over(leaf, factor):
        return (leaf.astype(jnp.float32) / factor).astype(dtype)

    def around(k, leaf, centre, spread=0.1):
        return (centre + spread * jax.random.normal(
            k, leaf.shape, jnp.float32)).astype(dtype)

    out = dict(params)
    out["head"] = over(params["head"], mult.lm_head)
    out["final_ln"] = dict(params["final_ln"], gamma=around(
        jax.random.fold_in(key, 999), params["final_ln"]["gamma"], 1.0))
    for i in range(config.num_layers):
        p = params[f"layer_{i}"]
        k = jax.random.split(jax.random.fold_in(key, i), 5)
        a, s, m = p["attn"], p["ssm"], p["mlp"]
        out[f"layer_{i}"] = dict(
            p,
            ln1=dict(p["ln1"], gamma=around(k[0], p["ln1"]["gamma"], 1.0)),
            ln2=dict(p["ln2"], gamma=around(k[1], p["ln2"]["gamma"], 1.0)),
            attn=dict(a, wq=over(a["wq"], mult.attention_in),
                      wk=over(a["wk"], mult.attention_in * mult.key),
                      wv=over(a["wv"], mult.attention_in),
                      wo=over(a["wo"], mult.attention_out)),
            ssm=dict(s, w_in=(s["w_in"].astype(jnp.float32)
                              * segments).astype(dtype),
                     w_out=over(s["w_out"], mult.ssm_out),
                     norm=around(k[2], s["norm"], 1.0),
                     D=around(k[3], s["D"], 1.0, 0.3),
                     conv_b=around(k[4], s["conv_b"], 0.0)),
            mlp=dict(m, w1=over(m["w1"], mult.mlp_gate),
                     w2=over(m["w2"], mult.mlp_down)))
    return out


def make_params(config, seed: int, out_shardings=None):
    """The program's own ``init_params``, reseeded as the module
    docstring says, as ONE jitted call on the device, in the dtype the
    weights are used in."""
    from elephas_tpu.models.transformer import init_params

    def init(key):
        return _seeded(init_params(config, key), config,
                       jax.random.fold_in(key, 7))

    return jax.jit(init, out_shardings=out_shardings)(
        jax.random.PRNGKey(int(seed)))


def to_reference_layer(p: dict, config) -> dict:
    """One ``layer_i`` of the program's tree in the reference's layout."""
    d, h, kv, hd = (config.d_model, config.num_heads, config.kv_heads,
                    config.head_dim)
    s = p["ssm"]
    return {
        "attn_norm": p["ln1"]["gamma"],
        "wq": p["attn"]["wq"].reshape(d, h * hd),
        "wk": p["attn"]["wk"].reshape(d, kv * hd),
        "wv": p["attn"]["wv"].reshape(d, kv * hd),
        "wo": p["attn"]["wo"].reshape(h * hd, d),
        "w_in": s["w_in"], "conv_w": s["conv_w"], "conv_b": s["conv_b"],
        "A_log": s["A_log"], "dt_bias": s["dt_bias"], "D": s["D"],
        "ssm_norm": s["norm"], "w_out": s["w_out"],
        "mlp_norm": p["ln2"]["gamma"],
        "w_gate": p["mlp"]["w1"], "b_gate": p["mlp"]["b1"],
        "w_up": p["mlp"]["w3"],
        "w_down": p["mlp"]["w2"], "b_down": p["mlp"]["b2"]}


def to_reference(params: dict, config) -> dict:
    """The program's parameter tree in the plain reference's layout
    (reshapes only; call it inside the jitted reference so nothing is
    copied). The program's RMSNorm ``beta`` leaves are unused by both."""
    return {"embed": params["embed"]["tokens"], "head": params["head"],
            "final_norm": params["final_ln"]["gamma"],
            "layers": [to_reference_layer(params[f"layer_{i}"], config)
                       for i in range(config.num_layers)]}
