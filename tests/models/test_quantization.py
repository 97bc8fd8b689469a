"""Weight-only int8 quantization: error bounds, forward/loss closeness,
teacher-forced decode consistency, generate/TextGenerator integration,
MoE coverage, round-trip."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from elephas_tpu.models.quantization import (QTensor, dequantize_lm_params,
                                             quantize_lm_params,
                                             quantize_weight)
from elephas_tpu.models.transformer import (TransformerConfig, generate,
                                            init_kv_cache, init_params)

from ._transformer_util import DECODE_STEP, FORWARD, LM_LOSS


def _config(**overrides):
    base = dict(vocab_size=128, num_layers=2, num_heads=4, d_model=64,
                d_ff=128, max_seq_len=48)
    base.update(overrides)
    return TransformerConfig(**base)


def test_quantize_weight_error_bound():
    w = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (64, 32)))
    q = quantize_weight(w, (0,))
    assert q.data.dtype == jnp.int8
    deq = np.asarray(q.astype(jnp.float32))
    # symmetric int8: per-channel error <= scale/2 + fp rounding
    bound = np.asarray(q.scale)[0] * 0.5 + 1e-6
    assert (np.abs(deq - w) <= bound[None, :]).all()


def test_qtensor_transpose_and_shape():
    w = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (8, 4)))
    q = quantize_weight(w, (0,))
    assert q.shape == (8, 4) and q.ndim == 2
    np.testing.assert_allclose(np.asarray(q.T.astype(jnp.float32)),
                               np.asarray(q.astype(jnp.float32)).T)


def test_quantized_forward_and_loss_close():
    config = _config()
    params = init_params(config, jax.random.PRNGKey(0))
    qparams = quantize_lm_params(params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0,
                                config.vocab_size)
    ref = np.asarray(FORWARD(params, tokens, config))
    got = np.asarray(FORWARD(qparams, tokens, config))
    # int8 per-channel keeps logits within a few percent of fp scale
    assert np.abs(got - ref).max() < 0.05 * np.abs(ref).max() + 0.05
    l_ref = float(LM_LOSS(params, tokens, config))
    l_q = float(LM_LOSS(qparams, tokens, config))
    assert abs(l_q - l_ref) < 0.05 * l_ref


def test_quantized_decode_matches_quantized_forward():
    """Teacher-forced decode through the quantized params reproduces the
    quantized forward logits. fp-level (not bitwise) tolerance: the
    dequant multiply is f32 and XLA's excess-precision rules may fuse it
    into the two programs' matmuls differently."""
    config = _config()
    params = quantize_lm_params(init_params(config, jax.random.PRNGKey(0)))
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, 10),
                                           0, config.vocab_size))
    full = np.asarray(FORWARD(params, jnp.asarray(tokens), config))
    cache = init_kv_cache(config, 2, max_len=10)
    for t in range(10):
        logits, cache = DECODE_STEP(params, cache,
                                    jnp.asarray(tokens[:, t]), t, config)
        np.testing.assert_allclose(np.asarray(logits), full[:, t],
                                   atol=2e-3, rtol=2e-3)


def test_quantized_generate_and_text_generator():
    from elephas_tpu.serving import TextGenerator

    config = _config(vocab_size=256)
    params = init_params(config, jax.random.PRNGKey(0))
    qparams = quantize_lm_params(params)
    prompt = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, 6),
                                           0, 256))
    out = np.asarray(generate(qparams, prompt, 8, config))
    assert out.shape == (2, 8)

    gen = TextGenerator(qparams, config)
    texts = gen(["hello", "tpu"], max_new_tokens=6)
    assert len(texts) == 2


def test_quantize_moe_and_untied_head():
    config = _config(num_experts=2, expert_top_k=1, moe_shared_expert=True,
                     tied_embedding=False)
    params = init_params(config, jax.random.PRNGKey(0))
    qparams = quantize_lm_params(params)
    assert isinstance(qparams["layer_0"]["moe"]["w1"], QTensor)
    assert isinstance(qparams["layer_0"]["moe"]["shared"]["w1"], QTensor)
    assert isinstance(qparams["head"], QTensor)
    # gates stay fp (routing-critical)
    assert not isinstance(qparams["layer_0"]["moe"]["gate"], QTensor)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                config.vocab_size)
    ref = np.asarray(FORWARD(params, tokens, config))
    got = np.asarray(FORWARD(qparams, tokens, config))
    assert np.abs(got - ref).max() < 0.05 * np.abs(ref).max() + 0.05


def test_quantized_untied_head_chunked_loss():
    """The chunked-vocab loss transposes the head (QTensor.T) — the
    quantized untied-head path must run and stay close to fp."""
    config = _config(tied_embedding=False, loss_vocab_chunk=32)
    params = init_params(config, jax.random.PRNGKey(0))
    qparams = quantize_lm_params(params)
    assert isinstance(qparams["head"], QTensor)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                config.vocab_size)
    l_ref = float(LM_LOSS(params, tokens, config))
    l_q = float(LM_LOSS(qparams, tokens, config))
    assert abs(l_q - l_ref) < 0.05 * l_ref
    # chunked and dense quantized losses agree with each other too
    dense_cfg = dataclasses.replace(config, loss_vocab_chunk=None)
    l_dense = float(LM_LOSS(qparams, tokens, dense_cfg))
    np.testing.assert_allclose(l_q, l_dense, atol=1e-5, rtol=1e-5)


def test_int8_kv_cache_decode_close_to_fp():
    """Decode with the int8 KV cache tracks the fp-cache logits within
    int8 tolerance, and the cache actually stores int8."""
    config = _config()
    qcfg = dataclasses.replace(config, kv_cache_quant=True)
    params = init_params(config, jax.random.PRNGKey(0))
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, 12),
                                           0, config.vocab_size))
    cache_fp = init_kv_cache(config, 2, max_len=12)
    cache_q = init_kv_cache(qcfg, 2, max_len=12)
    assert cache_q["layer_0"]["k"].dtype == jnp.int8
    max_rel = 0.0
    for t in range(12):
        l_fp, cache_fp = DECODE_STEP(params, cache_fp,
                                     jnp.asarray(tokens[:, t]), t, config)
        l_q, cache_q = DECODE_STEP(params, cache_q,
                                   jnp.asarray(tokens[:, t]), t, qcfg)
        diff = np.abs(np.asarray(l_q) - np.asarray(l_fp)).max()
        max_rel = max(max_rel, diff / (np.abs(np.asarray(l_fp)).max()
                                       + 1e-6))
    assert max_rel < 0.05, max_rel


def test_full_int8_serving_stack():
    """Weight-only int8 + int8 KV cache together through generate,
    beam_search and TextGenerator."""
    from elephas_tpu.models.transformer import beam_search
    from elephas_tpu.serving import TextGenerator

    config = _config(vocab_size=256, kv_cache_quant=True)
    qparams = quantize_lm_params(init_params(config, jax.random.PRNGKey(0)))
    prompt = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, 6),
                                           0, 256))
    out = np.asarray(generate(qparams, prompt, 8, config))
    assert out.shape == (2, 8)
    seqs, scores = beam_search(qparams, prompt, 6, config, num_beams=2)
    assert np.asarray(seqs).shape == (2, 2, 6)
    texts = TextGenerator(qparams, config)(["ab", "cd"], max_new_tokens=5)
    assert len(texts) == 2


def test_dequantize_round_trip():
    config = _config()
    params = init_params(config, jax.random.PRNGKey(0))
    qparams = quantize_lm_params(params)
    deq = dequantize_lm_params(qparams)
    w = np.asarray(params["layer_0"]["attn"]["wq"], np.float32)
    dq = np.asarray(deq["layer_0"]["attn"]["wq"])
    assert dq.dtype == np.float32
    scale = np.asarray(qparams["layer_0"]["attn"]["wq"].scale)
    assert (np.abs(dq - w) <= scale * 0.5 + 1e-6).all()
