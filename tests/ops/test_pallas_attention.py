"""Pallas flash-attention parity tests (interpreter mode on the CPU mesh).

Oracle = the plain XLA softmax attention in ``elephas_tpu.ops.attention``,
for both outputs and gradients, over causal/non-causal and ragged
(non-block-multiple) sequence lengths.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elephas_tpu.ops.attention import attention
from elephas_tpu.ops.pallas_attention import flash_attention

from ._grads import out_and_grads


def _qkv(key, b=2, h=2, sq=32, sk=32, d=16, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, h, sq, d), dtype)
    k = jax.random.normal(kk, (b, h, sk, d), dtype)
    v = jax.random.normal(kv, (b, h, sk, d), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("sq,sk,block", [(32, 32, 16), (40, 40, 16),
                                         (17, 29, 8)])
def test_forward_matches_reference(causal, sq, sk, block):
    if causal and sq != sk:
        pytest.skip("causal requires square attention")
    q, k, v = _qkv(jax.random.PRNGKey(0), sq=sq, sk=sk)
    got = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, block_q=block, block_k=block,
        interpret=True))(q, k, v)
    want = attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("sq,block", [(32, 16), (27, 8)])
def test_gradients_match_reference(causal, sq, block):
    q, k, v = _qkv(jax.random.PRNGKey(1), sq=sq, sk=sq)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, block_q=block,
                            block_k=block, interpret=True)
        return jnp.sum(jnp.sin(o))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(attention(q, k, v, causal=causal)))

    g_flash = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for a, b, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5,
                                   rtol=5e-4, err_msg=f"d{name}")


def test_bfloat16_inputs():
    q, k, v = _qkv(jax.random.PRNGKey(2), dtype=jnp.bfloat16)
    got = flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                          interpret=True)
    want = attention(q.astype(jnp.float32), k.astype(jnp.float32),
                     v.astype(jnp.float32), causal=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, dtype=np.float32),
                               np.asarray(want), atol=3e-2)


def test_sharded_flash_matches_reference_on_dp_tp_mesh():
    """shard_map-wrapped kernel on a 2x2 data x model mesh: batch shards
    over data, heads over model; outputs and grads must match the
    unsharded XLA oracle."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from elephas_tpu.ops.pallas_attention import flash_attention_sharded

    q, k, v = _qkv(jax.random.PRNGKey(4), b=4, h=4, sq=32, sk=32, d=16)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("data", "model"))
    spec = NamedSharding(mesh, P("data", "model", None, None))
    q_d, k_d, v_d = (jax.device_put(a, spec) for a in (q, k, v))

    def sharded(q, k, v):
        return flash_attention_sharded(q, k, v, mesh, causal=True,
                                       batch_axis="data", head_axis="model",
                                       block_q=16, block_k=16,
                                       interpret=True)

    got = jax.jit(sharded)(q_d, k_d, v_d)
    want = attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)

    def loss_sharded(q, k, v):
        return jnp.sum(jnp.sin(sharded(q, k, v)))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(attention(q, k, v, causal=True)))

    g_got = jax.jit(jax.grad(loss_sharded, argnums=(0, 1, 2)))(q_d, k_d, v_d)
    g_want = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for a, b, name in zip(g_got, g_want, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5,
                                   rtol=5e-4, err_msg=f"d{name}")


def test_jit_and_vmap_compose():
    q, k, v = _qkv(jax.random.PRNGKey(3), b=1, h=2, sq=16, sk=16, d=8)

    @jax.jit
    def f(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=8, block_k=8,
                               interpret=True)

    np.testing.assert_allclose(np.asarray(f(q, k, v)),
                               np.asarray(attention(q, k, v, causal=True)),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("kvh,causal", [(2, True), (1, True), (2, False)])
def test_flash_gqa_matches_expanded_reference(kvh, causal):
    """GQA in-kernel (narrow k/v rows, grouped dkv accumulation) matches
    head-broadcast attention for values AND gradients."""
    b, h, t, d = 2, 4, 48, 16
    kq, kk, kv_, kg = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(kq, (b, h, t, d), jnp.float32)
    k = jax.random.normal(kk, (b, kvh, t, d), jnp.float32)
    v = jax.random.normal(kv_, (b, kvh, t, d), jnp.float32)
    gout = jax.random.normal(kg, (b, h, t, d), jnp.float32)

    def ref_fn(q, k, v):
        kf = jnp.repeat(k, h // kvh, axis=1)
        vf = jnp.repeat(v, h // kvh, axis=1)
        return attention(q, kf, vf, causal=causal)

    def flash_fn(q, k, v):
        return flash_attention(q, k, v, causal=causal, block_q=16,
                               block_k=16, interpret=True)

    ref, g_ref = out_and_grads(ref_fn, q, k, v, gout)
    got, g_fl = out_and_grads(flash_fn, q, k, v, gout)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    for a, b_ in zip(g_fl, g_ref):
        assert a.shape == b_.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=3e-4, rtol=3e-4)


def test_flash_gqa_validates_head_divisibility():
    q = jnp.zeros((1, 4, 8, 8))
    k = v = jnp.zeros((1, 3, 8, 8))
    with pytest.raises(ValueError):
        flash_attention(q, k, v, interpret=True)


@pytest.mark.parametrize("window,kvh", [(3, 4), (8, 4), (64, 4), (5, 2)])
def test_flash_sliding_window_matches_band_reference(window, kvh):
    """Windowed flash (band-skipped blocks) == band-masked reference, for
    values and gradients — incl. windows smaller than a block, crossing
    block boundaries, and covering the sequence; composed with GQA."""
    b, h, t, d = 2, 4, 40, 16
    kq, kk, kv_, kg = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(kq, (b, h, t, d), jnp.float32)
    k = jax.random.normal(kk, (b, kvh, t, d), jnp.float32)
    v = jax.random.normal(kv_, (b, kvh, t, d), jnp.float32)
    gout = jax.random.normal(kg, (b, h, t, d), jnp.float32)

    q_pos = jnp.arange(t)[:, None]
    k_pos = jnp.arange(t)[None, :]
    band = ((k_pos <= q_pos) & (k_pos > q_pos - window))[None, None]

    def ref_fn(q, k, v):
        kf = jnp.repeat(k, h // kvh, axis=1)
        vf = jnp.repeat(v, h // kvh, axis=1)
        return attention(q, kf, vf, causal=False, mask=band)

    def flash_fn(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window,
                               block_q=16, block_k=16, interpret=True)

    ref, g_ref = out_and_grads(ref_fn, q, k, v, gout)
    got, g_fl = out_and_grads(flash_fn, q, k, v, gout)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    for a, b_ in zip(g_fl, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=3e-4, rtol=3e-4)
