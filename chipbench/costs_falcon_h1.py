"""Operations and bytes of the family ``falcon_h1``, from shapes.

Sizes come in as the configuration file's own dict (Hugging Face key
names). Nothing here is measured: a share needs a device time from the
trace. Parameters are counted as published (no bias in any projection;
the program's zero ``b1`` / ``b2`` and the norms' unused ``beta`` are not
counted).
"""
__all__ = ["attention_params", "mixer_params", "mlp_params",
           "layer_params", "vocab_params", "total_params",
           "kv_bytes_per_position", "state_bytes_per_row",
           "step_weight_bytes", "decode_step_bytes", "update_cost",
           "scan_cost", "serve_token_flops"]

_DTYPE_BYTES = {"bfloat16": 2, "float32": 4}


def _mixer_dims(cfg: dict):
    heads, d_head = int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"])
    groups, n = int(cfg["mamba_n_groups"]), int(cfg["mamba_d_state"])
    d_ssm = int(cfg["mamba_d_ssm"])
    return d_ssm, heads, d_head, groups, n, d_ssm + 2 * groups * n


def attention_params(cfg: dict) -> int:
    """q, k, v and o of one block: ``head_dim`` is the config's own key,
    not ``hidden_size / num_attention_heads``."""
    d, hd = int(cfg["hidden_size"]), int(cfg["head_dim"])
    heads, kv = (int(cfg["num_attention_heads"]),
                 int(cfg["num_key_value_heads"]))
    return d * (heads + 2 * kv) * hd + heads * hd * d


def mixer_params(cfg: dict) -> int:
    """One block's Mamba-2 mixer: the input projection ``[z | x | B | C |
    dt]``, the output projection, the depthwise convolution with its
    bias, the gated norm's weight, and ``A_log``, ``dt_bias`` and ``D``
    a head."""
    d = int(cfg["hidden_size"])
    d_ssm, heads, _, _, _, conv_dim = _mixer_dims(cfg)
    return (d * (d_ssm + conv_dim + heads) + d_ssm * d
            + conv_dim * (int(cfg["mamba_d_conv"]) + 1) + d_ssm + 3 * heads)


def mlp_params(cfg: dict) -> int:
    return 3 * int(cfg["hidden_size"]) * int(cfg["intermediate_size"])


def layer_params(cfg: dict) -> int:
    """One block, with its two RMSNorm weights."""
    return (attention_params(cfg) + mixer_params(cfg) + mlp_params(cfg)
            + 2 * int(cfg["hidden_size"]))


def vocab_params(cfg: dict) -> int:
    """The embedding, and as much again the untied head."""
    return int(cfg["vocab_size"]) * int(cfg["hidden_size"])


def total_params(cfg: dict) -> int:
    return (int(cfg["num_hidden_layers"]) * layer_params(cfg)
            + 2 * vocab_params(cfg) + int(cfg["hidden_size"]))


def kv_bytes_per_position(cfg: dict, dtype: str = "bfloat16",
                          layers: int = None) -> int:
    """Bytes of K and V one cached position takes (all layers unless
    ``layers`` is given)."""
    n = int(cfg["num_hidden_layers"]) if layers is None else layers
    return (2 * int(cfg["num_key_value_heads"]) * int(cfg["head_dim"])
            * _DTYPE_BYTES[dtype] * n)


def state_bytes_per_row(cfg: dict, layers: int = None,
                        state_dtype: str = "float32",
                        conv_dtype: str = "bfloat16") -> int:
    """Bytes of what one ROW keeps, whatever its length: the state of
    every mixer head and the convolution's carried inputs (all layers
    unless ``layers`` is given)."""
    _, heads, d_head, _, n, conv_dim = _mixer_dims(cfg)
    count = int(cfg["num_hidden_layers"]) if layers is None else layers
    return count * (heads * d_head * n * _DTYPE_BYTES[state_dtype]
                    + (int(cfg["mamba_d_conv"]) - 1) * conv_dim
                    * _DTYPE_BYTES[conv_dtype])


def step_weight_bytes(cfg: dict, dtype: str) -> int:
    """Bytes of the weights every decode step reads: the blocks and the
    head (the embedding contributes one row a token)."""
    return (int(cfg["num_hidden_layers"]) * layer_params(cfg)
            + vocab_params(cfg)) * _DTYPE_BYTES[dtype]


def decode_step_bytes(cfg: dict, param_dtype: str, positions_held: float,
                      row_updates: float,
                      cache_dtype: str = "bfloat16") -> float:
    """The least one decode step must move: the weights once, the K and
    V of every position its rows hold, and the state of every row it
    updates read once and written once (``row_updates`` counts rows x
    layers). What a step moves beyond that -- the state of a slot no
    live row sits in, a second pass over anything -- is the program's
    doing and is not counted."""
    return (step_weight_bytes(cfg, param_dtype)
            + positions_held * kv_bytes_per_position(cfg, cache_dtype)
            + row_updates * 2 * state_bytes_per_row(cfg, layers=1))


def update_cost(cfg: dict, row_updates: float) -> dict:
    """FLOPs and bytes of ``row_updates`` one-token state updates (rows
    x layers): per state element a decay, a multiply-add of the input
    and a multiply-add into ``y`` (5 FLOPs), and the state read once and
    written once."""
    _, heads, d_head, _, n, _ = _mixer_dims(cfg)
    return {"flops": 5.0 * heads * d_head * n * row_updates,
            "bytes": 2.0 * state_bytes_per_row(cfg, layers=1)
            * row_updates}


def scan_cost(cfg: dict, tokens: float, chunks: float,
              dtype: str = "bfloat16") -> dict:
    """FLOPs and bytes of the chunk scan over ``tokens`` tokens x layers
    in ``chunks`` prompt chunks x layers, in blocks of
    ``mamba_chunk_size`` = L. Per token: ``C B^T`` against a block (2 L
    N a group), the masked product with the block's inputs (2 L P a
    head), the token's part of the block's state and its read of the
    carried state (2 P N each, a head). Bytes: a token's ``x``, ``B``,
    ``C`` and ``dt`` read and its ``y`` written, and per chunk the state
    read once and written once."""
    d_ssm, heads, d_head, groups, n, conv_dim = _mixer_dims(cfg)
    block = int(cfg["mamba_chunk_size"])
    per_token = (2.0 * block * n * groups + 2.0 * block * d_head * heads
                 + 4.0 * d_head * n * heads)
    size = _DTYPE_BYTES[dtype]
    token_bytes = conv_dim * size + heads * 4 + d_ssm * size
    state = heads * d_head * n * 4
    return {"flops": per_token * tokens,
            "bytes": token_bytes * tokens + 2.0 * state * chunks}


def serve_token_flops(cfg: dict, held_pick_share: float = None) -> dict:
    """Matrix FLOPs of serving one token: ``body`` (every block's
    matrices, the mixer's two projections among them) and ``head``.
    Attention's own two products and the scan's are left out (under 3%
    at these contexts): a share made from this is a floor.
    ``held_pick_share`` is the routed families' and is not used."""
    d = int(cfg["hidden_size"])
    d_ssm, heads, _, _, _, conv_dim = _mixer_dims(cfg)
    matrices = (attention_params(cfg) + mlp_params(cfg)
                + d * (d_ssm + conv_dim + heads) + d_ssm * d)
    return {"body": 2.0 * int(cfg["num_hidden_layers"]) * matrices,
            "head": 2.0 * vocab_params(cfg)}
