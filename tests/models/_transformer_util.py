"""Configurations the ``test_transformer*.py`` files share."""
import jax.numpy as jnp

from elephas_tpu.models.transformer import TransformerConfig


def _config():
    return TransformerConfig(vocab_size=64, num_layers=2, num_heads=4,
                             d_model=32, d_ff=64, max_seq_len=32,
                             dtype=jnp.float32)


def _moe_config(**kw):
    import dataclasses

    kw.setdefault("num_experts", 4)
    kw.setdefault("expert_top_k", 2)
    return dataclasses.replace(_config(), **kw)


def _rope_config(**kw):
    import dataclasses

    kw.setdefault("positional", "rope")
    return dataclasses.replace(_config(), **kw)


def _gqa_config(num_kv_heads):
    import dataclasses

    return dataclasses.replace(_config(), num_kv_heads=num_kv_heads)
