"""Family ``dense_decoder``: a configuration file (Hugging Face key names,
Mistral-7B-v0.1 shape) -> the program's ``TransformerConfig``, seeded
parameters made on the device in one jitted call, and the same weights in
the plain reference's layout. A ``model_config`` PR for another family
adds a file beside this one; nothing here is edited.
"""
import jax
import jax.numpy as jnp

#: ``chipbench/reference/<REFERENCE>.py`` is this family's plain reference
REFERENCE = "dense_decoder"

#: sizes of the CPU rehearsal (``--rehearse``): every Mistral-shaped branch
#: stays on (GQA, RoPE, RMSNorm, SiLU gate, a window, untied head), widths
#: are toys. Never used on the chip.
REHEARSE_SIZES = {"hidden_size": 64, "intermediate_size": 128,
                  "num_hidden_layers": 2, "num_attention_heads": 4,
                  "num_key_value_heads": 2, "vocab_size": 512,
                  "sliding_window": 256}

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def model_sizes(cfg: dict, rehearse: bool) -> dict:
    """The configuration's sizes as run (the toy ones in a rehearsal)."""
    return dict(cfg, **REHEARSE_SIZES) if rehearse else cfg


def program_config(cfg: dict, max_seq_len: int, param_dtype: str,
                   **overrides):
    """The program's config for these sizes. ``overrides`` are trainer or
    engine settings from the configuration file (``remat``,
    ``attention_impl``), never widths."""
    from elephas_tpu.models.transformer import TransformerConfig

    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError("dense_decoder is SiLU-gated")
    if abs(float(cfg["rms_norm_eps"]) - 1e-5) > 1e-12:
        raise ValueError("the program's RMSNorm epsilon is fixed at 1e-5")
    return TransformerConfig(
        vocab_size=int(cfg["vocab_size"]),
        num_layers=int(cfg["num_hidden_layers"]),
        num_heads=int(cfg["num_attention_heads"]),
        num_kv_heads=int(cfg["num_key_value_heads"]),
        d_model=int(cfg["hidden_size"]),
        d_ff=int(cfg["intermediate_size"]),
        max_seq_len=int(max_seq_len),
        dtype=jnp.bfloat16, param_dtype=_DTYPES[param_dtype],
        positional="rope", rope_theta=float(cfg["rope_theta"]),
        norm="rmsnorm", mlp_variant="swiglu",
        tied_embedding=bool(cfg["tie_word_embeddings"]),
        attention_window=cfg.get("sliding_window"), **overrides)


def make_params(config, seed: int, out_shardings=None):
    """The program's own ``init_params`` as ONE jitted call on the device,
    in the dtype the weights are used in (and already sharded, when
    ``out_shardings`` is given): no host copy, no leaf-by-leaf dispatch."""
    from elephas_tpu.models.transformer import init_params

    init = jax.jit(lambda key: init_params(config, key),
                   out_shardings=out_shardings)
    return init(jax.random.PRNGKey(int(seed)))


def to_reference(params: dict, config) -> dict:
    """The program's parameter tree in the plain reference's layout
    (reshapes only; call it inside the jitted reference so nothing is
    copied). The program's RMSNorm ``beta`` leaves are unused by both."""
    d, h, kv, hd = (config.d_model, config.num_heads, config.kv_heads,
                    config.head_dim)
    layers = []
    for i in range(config.num_layers):
        p = params[f"layer_{i}"]
        layers.append({
            "attn_norm": p["ln1"]["gamma"],
            "wq": p["attn"]["wq"].reshape(d, h * hd),
            "wk": p["attn"]["wk"].reshape(d, kv * hd),
            "wv": p["attn"]["wv"].reshape(d, kv * hd),
            "wo": p["attn"]["wo"].reshape(h * hd, d),
            "mlp_norm": p["ln2"]["gamma"],
            "w_gate": p["mlp"]["w1"], "b_gate": p["mlp"]["b1"],
            "w_up": p["mlp"]["w3"],
            "w_down": p["mlp"]["w2"], "b_down": p["mlp"]["b2"]})
    return {"embed": params["embed"]["tokens"], "head": params["head"],
            "final_norm": params["final_ln"]["gamma"], "layers": layers}
