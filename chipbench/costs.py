"""Operations and bytes from shapes, and the table of peaks.

The arithmetic is ``bench.py``'s (FLOPs per token of a dense decoder),
extended to gated feed-forwards, grouped-query attention and an untied
head, plus the bytes one decode step has to read. Sizes come in as the
configuration file's own dict (Hugging Face key names). Nothing here is
measured: a rate or a share needs a device time from the trace.
"""
import json
import os

__all__ = ["peaks", "layer_params", "matmul_params", "kv_bytes_per_token",
           "train_flops_per_token", "decode_step_bytes", "weight_bytes",
           "serve_token_flops"]

_DTYPE_BYTES = {"bfloat16": 2, "float32": 4}


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip, by ``device_kind``. A device that is
    not in the table is an error, not a default."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as fh:
        table = json.load(fh)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


def _dims(cfg: dict):
    d = int(cfg["hidden_size"])
    heads = int(cfg["num_attention_heads"])
    kv = int(cfg["num_key_value_heads"])
    return d, heads, kv, d // heads, int(cfg["intermediate_size"])


def layer_params(cfg: dict) -> int:
    """Matrix parameters of one block: q, k, v, o and the three
    feed-forward matrices (norm scales and biases left out: 0.006%)."""
    d, heads, kv, hd, ff = _dims(cfg)
    return d * heads * hd + 2 * d * kv * hd + heads * hd * d + 3 * d * ff


def matmul_params(cfg: dict) -> int:
    """Parameters a token multiplies with: every block and the untied
    head; the embedding is a row lookup."""
    return (int(cfg["num_hidden_layers"]) * layer_params(cfg)
            + int(cfg["hidden_size"]) * int(cfg["vocab_size"]))


def kv_bytes_per_token(cfg: dict, dtype: str = "bfloat16",
                       layers: int = None) -> int:
    """Bytes of K and V one cached position takes (all layers unless
    ``layers`` is given)."""
    _, _, kv, hd, _ = _dims(cfg)
    n = int(cfg["num_hidden_layers"]) if layers is None else layers
    return 2 * kv * hd * _DTYPE_BYTES[dtype] * n


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Model FLOPs per trained token, forward and backward, no
    recomputation counted: 6 x matmul parameters, plus attention's two
    matmuls (QK^T and PV) at 12 x layers x d_model x the causal mean of
    positions attended (``bench.py``'s convention counts the full
    ``seq``; here the causal half, windowed)."""
    d = int(cfg["hidden_size"])
    window = cfg.get("sliding_window") or seq
    span = min(seq, int(window))
    # mean keys attended per query under a causal window of `span`
    mean_keys = (span * (span + 1) / 2 + (seq - span) * span) / seq
    attention = 12.0 * int(cfg["num_hidden_layers"]) * d * mean_keys
    return 6.0 * matmul_params(cfg) + attention


def weight_bytes(cfg: dict, dtype: str) -> int:
    """Bytes of the matrices one decode step reads: every block and the
    head (the embedding contributes one row per token)."""
    return matmul_params(cfg) * _DTYPE_BYTES[dtype]


def decode_step_bytes(cfg: dict, param_dtype: str, kv_tokens_held: float,
                      cache_dtype: str = "bfloat16") -> float:
    """The least one decode step must read from memory: the weights once
    and the K and V of every position the batch's rows hold. What a step
    reads beyond that (a gather over the whole table, say) is the
    program's doing and is not counted."""
    return weight_bytes(cfg, param_dtype) + kv_tokens_held * \
        kv_bytes_per_token(cfg, cache_dtype)


def serve_token_flops(cfg: dict, held_pick_share: float = None) -> dict:
    """Matrix FLOPs of serving one token: ``body`` (every block, which a
    prompt token and a served token both pay) and ``head`` (the untied
    head, which a served token pays and a prompt pays once, for its
    first token). Attention's own two products are left out (2-3% at
    these contexts), so a share made from this is a floor."""
    head = int(cfg["hidden_size"]) * int(cfg["vocab_size"])
    return {"body": 2.0 * (matmul_params(cfg) - head), "head": 2.0 * head}
