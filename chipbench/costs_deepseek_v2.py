"""Operations and bytes of the family ``deepseek_v2``, from shapes.

Sizes come in as the configuration file's own dict (Hugging Face key
names; ``n_routed_experts`` is the number of experts held on this chip,
``vocab_size`` the slice held here). Nothing here is measured: a share
needs a device time from the trace.
"""
__all__ = ["attention_params", "expert_params", "shared_params",
           "router_params", "dense_mlp_params", "expert_layers",
           "total_params", "latent_bytes_per_position",
           "resident_matrix_bytes", "experts_bytes", "decode_step_bytes",
           "attend_cost", "serve_token_flops"]

_DTYPE_BYTES = {"bfloat16": 2, "float32": 4}


def attention_params(cfg: dict) -> int:
    """Matrices of one layer's latent attention: ``W_qa``, ``W_qb``,
    ``W_kva``, ``W_kvb``, ``W_o`` (norm scales left out)."""
    d, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    q_rank, kv_rank = int(cfg["q_lora_rank"]), int(cfg["kv_lora_rank"])
    nope, rope = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
    v = int(cfg["v_head_dim"])
    return (d * q_rank + q_rank * heads * (nope + rope)
            + d * (kv_rank + rope) + kv_rank * heads * (nope + v)
            + heads * v * d)


def expert_params(cfg: dict) -> int:
    """One routed expert: three matrices of width
    ``moe_intermediate_size``."""
    return 3 * int(cfg["hidden_size"]) * int(cfg["moe_intermediate_size"])


def shared_params(cfg: dict) -> int:
    return int(cfg["n_shared_experts"]) * expert_params(cfg)


def router_params(cfg: dict) -> int:
    return int(cfg["hidden_size"]) * int(cfg["router_experts"])


def dense_mlp_params(cfg: dict) -> int:
    return 3 * int(cfg["hidden_size"]) * int(cfg["intermediate_size"])


def expert_layers(cfg: dict) -> int:
    return int(cfg["num_hidden_layers"]) - int(cfg["first_k_dense_replace"])


def total_params(cfg: dict) -> int:
    """Every matrix held here: the layers with the held experts, the
    embedding and the untied head."""
    layers = int(cfg["num_hidden_layers"])
    per_expert_layer = (shared_params(cfg) + router_params(cfg)
                        + int(cfg["n_routed_experts"]) * expert_params(cfg))
    return (layers * attention_params(cfg)
            + int(cfg["first_k_dense_replace"]) * dense_mlp_params(cfg)
            + expert_layers(cfg) * per_expert_layer
            + 2 * int(cfg["hidden_size"]) * int(cfg["vocab_size"]))


def latent_bytes_per_position(cfg: dict, dtype: str = "bfloat16",
                              layers: int = None) -> int:
    """Bytes one cached position takes: the latent and the rope key
    (all layers unless ``layers`` is given)."""
    n = int(cfg["num_hidden_layers"]) if layers is None else layers
    return ((int(cfg["kv_lora_rank"]) + int(cfg["qk_rope_head_dim"]))
            * _DTYPE_BYTES[dtype] * n)


def resident_matrix_bytes(cfg: dict, dtype: str) -> int:
    """Bytes of the matrices EVERY decode step reads whatever the
    routing: attention, the dense layers' MLP, router, shared experts,
    the head (the embedding contributes one row a token)."""
    layers = int(cfg["num_hidden_layers"])
    n = (layers * attention_params(cfg)
         + int(cfg["first_k_dense_replace"]) * dense_mlp_params(cfg)
         + expert_layers(cfg) * (shared_params(cfg) + router_params(cfg))
         + int(cfg["hidden_size"]) * int(cfg["vocab_size"]))
    return n * _DTYPE_BYTES[dtype]


def experts_bytes(cfg: dict, dtype: str, experts_touched: float) -> float:
    """Bytes of ``experts_touched`` routed experts' weights (summed over
    the expert layers): what the grouped matmuls of a step must read."""
    return experts_touched * expert_params(cfg) * _DTYPE_BYTES[dtype]


def decode_step_bytes(cfg: dict, param_dtype: str, positions_held: float,
                      experts_touched: float,
                      cache_dtype: str = "bfloat16") -> float:
    """The least one decode step must read: the resident matrices, the
    weights of the experts its rows touched (all expert layers added
    up) and the latent of every position its rows hold. An expert no
    live row picked, the padding of the block list and a second pass
    over anything are the program's doing and are not counted."""
    return (resident_matrix_bytes(cfg, param_dtype)
            + experts_bytes(cfg, param_dtype, experts_touched)
            + positions_held * latent_bytes_per_position(cfg, cache_dtype))


def attend_cost(cfg: dict, positions_held: float,
                cache_dtype: str = "bfloat16") -> dict:
    """FLOPs and bytes of one step's absorbed attention over
    ``positions_held`` positions, all layers: per position and layer
    every head scores against the latent and the rope key (``2 * heads *
    (kv_rank + rope)``) and adds the latent into its sum (``2 * heads *
    kv_rank``), and the latent is read once."""
    heads = int(cfg["num_attention_heads"])
    rank, rope = int(cfg["kv_lora_rank"]), int(cfg["qk_rope_head_dim"])
    layers = int(cfg["num_hidden_layers"])
    return {"flops": 2.0 * heads * (2 * rank + rope) * positions_held
            * layers,
            "bytes": float(positions_held)
            * latent_bytes_per_position(cfg, cache_dtype)}


def serve_token_flops(cfg: dict, held_pick_share: float = None) -> dict:
    """Matrix FLOPs of serving one token on this chip: ``body`` (latent
    attention's matrices, the dense layers' MLP, and per expert layer
    the router, the shared experts and the picks that fall on HELD
    experts: ``num_experts_per_tok`` x ``held_pick_share``, the
    program's counters' share, or held / scored experts without them)
    and ``head``. The absorbed attention's own products are left out:
    a share made from this is a floor."""
    if held_pick_share is None:
        held_pick_share = (int(cfg["n_routed_experts"])
                           / int(cfg["router_experts"]))
    per_expert_layer = (shared_params(cfg) + router_params(cfg)
                        + int(cfg["num_experts_per_tok"]) * held_pick_share
                        * expert_params(cfg))
    body = (int(cfg["num_hidden_layers"]) * attention_params(cfg)
            + int(cfg["first_k_dense_replace"]) * dense_mlp_params(cfg)
            + expert_layers(cfg) * per_expert_layer)
    return {"body": 2.0 * body,
            "head": 2.0 * int(cfg["hidden_size"]) * int(cfg["vocab_size"])}
