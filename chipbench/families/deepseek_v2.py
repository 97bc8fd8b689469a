"""Family ``deepseek_v2``: a configuration file (Hugging Face key names,
DeepSeek-V2 shape) -> the program's ``TransformerConfig`` (latent
attention, leading dense layers, group-routed SwiGLU experts of which
this chip holds a range, shared experts), seeded parameters made on the
device in one jitted call, and the same weights in the plain reference's
layout.

Beside the Hugging Face keys the file carries the chip's share:
``n_routed_experts`` is the number of experts HELD here, ``held_first``
the first of them, ``router_experts`` the published count the router
still scores; ``vocab_size`` is the slice held here.
"""
import jax
import jax.numpy as jnp

#: ``chipbench/reference/<REFERENCE>.py`` is this family's plain reference
REFERENCE = "deepseek_v2"

#: sizes of the CPU rehearsal (``--rehearse``) and of the CPU tests: every
#: branch stays on -- one dense layer and two expert layers, 8 experts in
#: 4 groups of which 2 groups are kept and 1 group is held, a shared
#: expert, both LoRA ranks, YaRN. Never used on the chip.
REHEARSE_SIZES = {
    "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "num_attention_heads": 4,
    "num_key_value_heads": 4, "q_lora_rank": 48, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "router_experts": 8, "n_routed_experts": 2, "held_first": 2,
    "n_group": 4, "topk_group": 2, "num_experts_per_tok": 2,
    "n_shared_experts": 1, "vocab_size": 512,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 64,
                     "type": "yarn"}}

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def model_sizes(cfg: dict, rehearse: bool) -> dict:
    """The configuration's sizes as run (the toy ones in a rehearsal)."""
    return dict(cfg, **REHEARSE_SIZES) if rehearse else cfg


def program_config(cfg: dict, max_seq_len: int, param_dtype: str,
                   **overrides):
    """The program's config for these sizes. ``overrides`` are engine or
    test settings (``dtype``), never widths."""
    from elephas_tpu.models.transformer import (TransformerConfig,
                                                YarnScaling)

    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError("deepseek_v2 is SiLU-gated")
    if cfg.get("topk_method") != "group_limited_greedy" or \
            cfg.get("scoring_func", "softmax") != "softmax" or \
            cfg.get("norm_topk_prob") or int(cfg.get("moe_layer_freq", 1)) != 1:
        raise ValueError("deepseek_v2 routes by softmax scores, "
                         "group_limited_greedy, unnormalised, in every "
                         "layer after the leading dense ones")
    layers = int(cfg["num_hidden_layers"])
    dense = int(cfg["first_k_dense_replace"])
    scaling = cfg.get("rope_scaling")
    settings = dict(dtype=jnp.bfloat16)
    settings.update(overrides)
    return TransformerConfig(
        vocab_size=int(cfg["vocab_size"]), num_layers=layers,
        num_heads=int(cfg["num_attention_heads"]),
        d_model=int(cfg["hidden_size"]),
        d_ff=int(cfg["intermediate_size"]),
        max_seq_len=int(max_seq_len), param_dtype=_DTYPES[param_dtype],
        positional="rope", rope_theta=float(cfg["rope_theta"]),
        norm="rmsnorm", rms_norm_eps=float(cfg["rms_norm_eps"]),
        mlp_variant="swiglu",
        tied_embedding=bool(cfg["tie_word_embeddings"]),
        attention_kind="mla", q_lora_rank=int(cfg["q_lora_rank"]),
        kv_lora_rank=int(cfg["kv_lora_rank"]),
        qk_nope_head_dim=int(cfg["qk_nope_head_dim"]),
        qk_rope_head_dim=int(cfg["qk_rope_head_dim"]),
        v_head_dim=int(cfg["v_head_dim"]),
        rope_scaling=None if not scaling else YarnScaling(
            factor=float(scaling["factor"]),
            original_max_position=int(
                scaling["original_max_position_embeddings"]),
            beta_fast=float(scaling["beta_fast"]),
            beta_slow=float(scaling["beta_slow"]),
            mscale=float(scaling["mscale"]),
            mscale_all_dim=float(scaling["mscale_all_dim"])),
        mlp_kinds=("dense",) * dense + ("experts",) * (layers - dense),
        num_experts=int(cfg["router_experts"]),
        expert_top_k=int(cfg["num_experts_per_tok"]),
        expert_variant="swiglu",
        expert_d_ff=int(cfg["moe_intermediate_size"]),
        shared_d_ff=(int(cfg["n_shared_experts"])
                     * int(cfg["moe_intermediate_size"])),
        moe_n_groups=int(cfg["n_group"]),
        moe_topk_groups=int(cfg["topk_group"]),
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        held_experts=(int(cfg.get("held_first", 0)),
                      int(cfg["n_routed_experts"])),
        **settings)


def make_params(config, seed: int, out_shardings=None):
    """The program's own ``init_params`` as ONE jitted call on the device,
    in the dtype the weights are used in."""
    from elephas_tpu.models.transformer import init_params

    init = jax.jit(lambda key: init_params(config, key),
                   out_shardings=out_shardings)
    return init(jax.random.PRNGKey(int(seed)))


def to_reference(params: dict, config) -> dict:
    """The program's parameter tree in the plain reference's layout
    (reshapes only; call it inside the jitted reference so nothing is
    copied). The program's RMSNorm ``beta`` leaves are unused by both."""
    layers = []
    for i in range(config.num_layers):
        p = params[f"layer_{i}"]
        a = p["attn"]
        layer = {
            "attn_norm": p["ln1"]["gamma"], "mlp_norm": p["ln2"]["gamma"],
            "wq_a": a["wq_a"], "q_norm": a["q_norm"]["gamma"],
            "wq_b": a["wq_b"].reshape(a["wq_b"].shape[0], -1),
            "wkv_a": a["wkv_a"], "kv_norm": a["kv_norm"]["gamma"],
            "wkv_b": a["wkv_b"].reshape(a["wkv_b"].shape[0], -1),
            "wo": a["wo"].reshape(-1, a["wo"].shape[-1])}
        if "moe" in p:
            m = p["moe"]
            layer.update(router=m["gate"], e_gate=m["w1"], e_up=m["w3"],
                         e_down=m["w2"])
            if "shared" in m:
                layer.update(s_gate=m["shared"]["w1"],
                             s_up=m["shared"]["w3"],
                             s_down=m["shared"]["w2"])
        else:
            m = p["mlp"]
            layer.update(w_gate=m["w1"], b_gate=m["b1"], w_up=m["w3"],
                         w_down=m["w2"], b_down=m["b2"])
        layers.append(layer)
    return {"embed": params["embed"]["tokens"], "head": params["head"],
            "final_norm": params["final_ln"]["gamma"], "layers": layers}
