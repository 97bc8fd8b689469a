"""Configurations and compiled entry points the model tests share."""
import jax
import jax.numpy as jnp
import numpy as np

from elephas_tpu.models.paged_decode import decode_step_paged
from elephas_tpu.models.transformer import (TransformerConfig, decode_block,
                                            decode_step, forward, lm_loss,
                                            prefill_cache)

# The model's functions compiled whole, once a shape and config. Called
# op by op, one pass of even a two-layer toy model is a hundred small
# compiles, and an oracle loop pays them again at every new length.
_AXES = ("mesh", "seq_axis", "batch_axis", "model_axis")
FORWARD = jax.jit(forward, static_argnums=2, static_argnames=_AXES)
LM_LOSS = jax.jit(lm_loss, static_argnums=2, static_argnames=_AXES)
LOSS_AND_GRAD = jax.jit(jax.value_and_grad(lm_loss), static_argnums=2,
                        static_argnames=_AXES)
DECODE_STEP = jax.jit(decode_step, static_argnums=4)
DECODE_BLOCK = jax.jit(decode_block, static_argnums=4,
                       static_argnames=("attend_widths", "last_only"))
PREFILL = jax.jit(prefill_cache, static_argnums=(2, 3))
DECODE_PAGED = jax.jit(decode_step_paged, static_argnums=5,
                       static_argnames=("held_blocks", "with_stats"))


def assert_greedy_continuation(params, prompt, out, config):
    """``out`` is what greedy decoding after ``prompt`` produces: the
    model is causal, so one forward pass over ``prompt + out`` holds at
    each position the logits that step-by-step argmax feedback saw
    there, and their argmax must be the token that follows."""
    seq = np.concatenate([np.asarray(prompt), np.asarray(out)], axis=1)
    logits = np.asarray(FORWARD(params, jnp.asarray(seq[:, :-1]), config))
    np.testing.assert_array_equal(
        np.asarray(out), logits[:, prompt.shape[1] - 1:].argmax(-1))


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
