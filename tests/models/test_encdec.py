"""Encoder-decoder (seq2seq) tests: shapes, copy-task training, cached
greedy decode parity, padding isolation, sharded parity."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from elephas_tpu.models.encdec import (EncDecConfig, decode_logits, encode,
                                       greedy_decode, init_params,
                                       make_train_step, param_specs,
                                       seq2seq_loss, shard_params)


def _config(**kw):
    base = dict(vocab_size=32, num_encoder_layers=2, num_decoder_layers=2,
                num_heads=4, d_model=32, d_ff=64, max_seq_len=32,
                dtype=jnp.float32)
    base.update(kw)
    return EncDecConfig(**base)


# each compiled whole once a shape: op by op, a pass is a hundred
# small compiles
ENCODE = jax.jit(encode, static_argnums=2)
DECODE_LOGITS = jax.jit(decode_logits, static_argnums=4)
LOSS = jax.jit(seq2seq_loss, static_argnums=3)
LOSS_GRAD = jax.jit(jax.grad(seq2seq_loss), static_argnums=3)


def _assert_teacher_forced(params, memory, src, out, config):
    """``out`` is what greedy decoding with argmax feedback produces:
    the decoder is causal, so one teacher-forced pass over ``[bos] +
    out[:, :-1]`` holds at each position the logits the step at that
    position saw, and their argmax must be ``out`` there, up to a row's
    first eos; every later position holds eos."""
    bos = np.full((out.shape[0], 1), config.bos_token_id, out.dtype)
    tgt_in = np.concatenate([bos, out[:, :-1]], axis=1)
    logits = np.asarray(DECODE_LOGITS(params, memory, src,
                                      jnp.asarray(tgt_in), config))
    eos = out == config.eos_token_id
    after_eos = np.cumsum(eos, axis=1) - eos > 0
    want = np.where(after_eos, config.eos_token_id, logits.argmax(-1))
    np.testing.assert_array_equal(out, want)


def _copy_data(n=64, t=8, seed=0, config=None):
    c = config or _config()
    rng = np.random.default_rng(seed)
    src = rng.integers(3, c.vocab_size, size=(n, t)).astype("int32")
    tgt = np.concatenate(
        [src, np.full((n, 1), c.eos_token_id)], axis=1).astype("int32")
    return jnp.asarray(src), jnp.asarray(tgt)


def test_shapes_and_loss():
    config = _config()
    params = init_params(config, jax.random.PRNGKey(0))
    src, tgt = _copy_data(4)
    memory = ENCODE(params, src, config)
    assert memory.shape == (4, 8, 32)
    logits = DECODE_LOGITS(params, memory, src, tgt[:, :-1], config)
    assert logits.shape == (4, 8, 32)
    loss = float(LOSS(params, src, tgt, config))
    assert np.isfinite(loss) and abs(loss - np.log(32)) < 1.0


def test_copy_task_trains_and_greedy_decodes():
    """The classic seq2seq sanity: learn to copy the source through the
    cross-attention bottleneck, then greedy-decode it back."""
    config = _config()
    params = init_params(config, jax.random.PRNGKey(0))
    src, tgt = _copy_data(256, 6)
    tx = optax.adam(3e-3)
    opt = tx.init(params)
    step = make_train_step(config, tx)
    first = None
    for _ in range(120):
        params, opt, loss = step(params, opt, src, tgt)
        first = first if first is not None else float(loss)
    assert float(loss) < 0.3 * first, (first, float(loss))

    out = np.asarray(greedy_decode(params, src[:16], 7, config))
    acc = float((out[:, :6] == np.asarray(src[:16])).mean())
    assert acc > 0.8, acc


def test_greedy_decode_matches_teacher_forced_argmax():
    """The cached decode path must replay the teacher-forced logits."""
    config = _config()
    params = init_params(config, jax.random.PRNGKey(0))
    src, _ = _copy_data(3, 6)
    max_len = 5
    out = np.asarray(greedy_decode(params, src, max_len, config))
    _assert_teacher_forced(params, ENCODE(params, src, config), src, out,
                           config)


def test_encoder_padding_isolation_and_loss_mask():
    config = _config()
    params = init_params(config, jax.random.PRNGKey(0))
    src, tgt = _copy_data(2, 8)
    src = np.asarray(src).copy()
    src[:, 5:] = config.pad_token_id
    m1 = np.asarray(ENCODE(params, jnp.asarray(src), config))
    m_short = np.asarray(ENCODE(params, jnp.asarray(src[:, :5]), config))
    np.testing.assert_allclose(m1[:, :5], m_short, atol=1e-4)

    # the loss equals a manual masked CE over the teacher-forced logits
    tgt_a = np.asarray(tgt).copy()
    tgt_a[:, 6:] = config.pad_token_id
    memory = ENCODE(params, jnp.asarray(src), config)
    bos = np.full((2, 1), config.bos_token_id, dtype="int32")
    tgt_in = np.concatenate([bos, tgt_a[:, :-1]], axis=1)
    logits = np.asarray(DECODE_LOGITS(params, memory, jnp.asarray(src),
                                      jnp.asarray(tgt_in), config))
    logp = jax.nn.log_softmax(jnp.asarray(logits), axis=-1)
    picked = np.take_along_axis(np.asarray(logp), tgt_a[..., None],
                                axis=-1)[..., 0]
    w = (tgt_a != config.pad_token_id)
    manual = -(picked * w).sum() / w.sum()
    got = float(LOSS(params, jnp.asarray(src), jnp.asarray(tgt_a), config))
    np.testing.assert_allclose(got, manual, atol=1e-5, rtol=1e-5)


def test_sharded_forward_matches_unsharded():
    config = _config()
    params = init_params(config, jax.random.PRNGKey(0))
    src, tgt = _copy_data(8)
    expected = float(LOSS(params, src, tgt, config))
    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))
    sp = shard_params(params, config, mesh)
    sd = jax.device_put(src, NamedSharding(mesh, P("data", None)))
    td = jax.device_put(tgt, NamedSharding(mesh, P("data", None)))
    got = float(jax.jit(lambda p, s, t: seq2seq_loss(p, s, t, config))(
        sp, sd, td))
    np.testing.assert_allclose(got, expected, atol=2e-4, rtol=2e-4)
    jax.tree_util.tree_map(lambda p, s: None, params, param_specs(config))


def test_dropout_and_validation():
    config = _config(dropout_rate=0.1)
    params = init_params(config, jax.random.PRNGKey(0))
    src, tgt = _copy_data(8)
    tx = optax.adam(1e-3)
    opt = tx.init(params)
    step = make_train_step(config, tx)
    params, opt, loss = step(params, opt, src, tgt, jax.random.PRNGKey(1))
    assert np.isfinite(float(loss))
    with pytest.raises(ValueError):
        _config(num_heads=5)


def test_review_fixes_bounds_specs_and_dropout_arity():
    config = _config()
    params = init_params(config, jax.random.PRNGKey(0))
    src, tgt = _copy_data(2, 6)
    # max_len bound validated (silent dec_pos clamping before)
    with pytest.raises(ValueError):
        greedy_decode(params, src, config.max_seq_len + 1, config)
    # non-divisible heads replicate instead of crashing device_put
    mesh = Mesh(np.array(jax.devices()).reshape(1, 8), ("data", "model"))
    specs = param_specs(config, mesh=mesh)  # 4 heads on an 8-way axis
    assert specs["enc_0"]["attn"]["wq"] == P(None, None, None)
    sp = shard_params(params, config, mesh)  # crashed before
    got = float(jax.jit(lambda p, s, t: seq2seq_loss(p, s, t, config))(
        sp, src, tgt))
    np.testing.assert_allclose(got, float(LOSS(params, src, tgt, config)),
                               atol=2e-4, rtol=2e-4)
    # dropout configs REQUIRE the key
    dcfg = _config(dropout_rate=0.1)
    dp = init_params(dcfg, jax.random.PRNGKey(0))
    import optax as _optax
    tx = _optax.adam(1e-3)
    step = make_train_step(dcfg, tx)
    with pytest.raises(TypeError):
        step(dp, tx.init(dp), src, tgt)


def test_relative_position_bias():
    """T5-style buckets: bias participates (outputs differ from the
    no-bias config with identical other params), cached decode stays
    consistent with teacher forcing, and the copy task still trains."""
    config = _config(relative_position_buckets=8,
                     relative_position_max_distance=16)
    params = init_params(config, jax.random.PRNGKey(0))
    assert params["rel_bias"]["enc"].shape == (8, 4)
    src, tgt = _copy_data(4, 6)

    base_cfg = _config()
    base_params = {k: v for k, v in params.items() if k != "rel_bias"}
    memory = ENCODE(params, src, config)
    memory_base = ENCODE(base_params, src, base_cfg)
    assert np.abs(np.asarray(memory) - np.asarray(memory_base)).max() > 1e-6

    # cached greedy decode == teacher-forced argmax with bias active
    out = np.asarray(greedy_decode(params, src, 5, config))
    _assert_teacher_forced(params, memory, src, out, config)

    # bias receives gradient; specs cover it
    g = LOSS_GRAD(params, src, tgt, config)
    assert np.abs(np.asarray(g["rel_bias"]["dec"])).sum() > 0
    jax.tree_util.tree_map(lambda p, s: None, params, param_specs(config))


def test_sampled_decoding():
    config = _config()
    params = init_params(config, jax.random.PRNGKey(0))
    src, _ = _copy_data(4, 6)
    g = np.asarray(greedy_decode(params, src, 5, config))
    s1 = np.asarray(greedy_decode(params, src, 5, config, temperature=1.0,
                                  key=jax.random.PRNGKey(1)))
    s2 = np.asarray(greedy_decode(params, src, 5, config, temperature=1.0,
                                  key=jax.random.PRNGKey(1)))
    s3 = np.asarray(greedy_decode(params, src, 5, config, temperature=1.0,
                                  key=jax.random.PRNGKey(2)))
    np.testing.assert_array_equal(s1, s2)  # same key deterministic
    assert not np.array_equal(s1, s3) or not np.array_equal(s1, g)
    with pytest.raises(ValueError):
        greedy_decode(params, src, 5, config, temperature=1.0)
