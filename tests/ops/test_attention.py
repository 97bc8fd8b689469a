"""Attention op tests: blockwise and ring attention must match the plain
softmax-attention reference exactly (within fp tolerance)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec

from elephas_tpu.ops import (attention, blockwise_attention, ring_attention,
                             ring_attention_sharded)


def _qkv(b=2, h=4, s=32, d=16, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return [jax.random.normal(k, (b, h, s, d), jnp.float32) for k in keys]


@pytest.mark.parametrize("causal", [False, True])
def test_blockwise_matches_full(causal):
    q, k, v = _qkv()
    full = attention(q, k, v, causal=causal)
    blocked = blockwise_attention(q, k, v, block_size=8, causal=causal)
    np.testing.assert_allclose(np.asarray(full), np.asarray(blocked),
                               atol=1e-4)


def test_blockwise_uneven_blocks():
    q, k, v = _qkv(s=40)
    full = attention(q, k, v, causal=True)
    blocked = blockwise_attention(q, k, v, block_size=16, causal=True)
    np.testing.assert_allclose(np.asarray(full), np.asarray(blocked),
                               atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("ring_size", [2, 4, 8])
def test_ring_matches_full(causal, ring_size):
    q, k, v = _qkv()
    mesh = Mesh(np.array(jax.devices()[:ring_size]), ("seq",))
    full = attention(q, k, v, causal=causal)
    ring = ring_attention_sharded(q, k, v, mesh=mesh, seq_axis="seq",
                                  causal=causal)
    np.testing.assert_allclose(np.asarray(full), np.asarray(ring), atol=1e-4)


def test_ring_with_batch_axis():
    q, k, v = _qkv(b=4)
    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "seq"))
    full = attention(q, k, v, causal=True)
    ring = ring_attention_sharded(q, k, v, mesh=mesh, seq_axis="seq",
                                  causal=True, batch_axis="data")
    np.testing.assert_allclose(np.asarray(full), np.asarray(ring), atol=1e-4)


def _band_mask(s, window):
    q_pos = jnp.arange(s)[:, None]
    k_pos = jnp.arange(s)[None, :]
    return ((k_pos <= q_pos) & (k_pos > q_pos - window))[None, None]


@pytest.mark.parametrize("window", [1, 3, 8, 9, 31, 32, 100])
@pytest.mark.parametrize("ring_size", [4, 8])
def test_windowed_ring_matches_band_reference(window, ring_size):
    """Sliding-window x sequence-parallel composes: the ring applies the
    band over global positions and matches the XLA band-mask path."""
    q, k, v = _qkv()
    mesh = Mesh(np.array(jax.devices()[:ring_size]), ("seq",))
    expected = attention(q, k, v, mask=_band_mask(q.shape[2], window))
    ring = ring_attention_sharded(q, k, v, mesh=mesh, seq_axis="seq",
                                  causal=True, window=window)
    np.testing.assert_allclose(np.asarray(expected), np.asarray(ring),
                               atol=1e-4)


def test_windowed_ring_skips_out_of_band_hops():
    """The static hop count drops with the window: a narrow band on a
    long ring pays O(window) hops, not O(seq)."""
    from elephas_tpu.ops.ring_attention import ring_num_hops

    # shard_len 8, 8 shards (seq 64)
    assert ring_num_hops(8, 8, None) == 8      # full causal: every hop
    assert ring_num_hops(8, 8, 1) == 1         # self only: diagonal hop
    assert ring_num_hops(8, 8, 8) == 2         # band spills one shard back
    assert ring_num_hops(8, 8, 9) == 2
    assert ring_num_hops(8, 8, 10) == 3        # q=s_start needs k 9 back
    assert ring_num_hops(8, 8, 64) == 8        # window >= seq: all hops
    assert ring_num_hops(8, 8, 1000) == 8      # clamped at ring size
    # exactness: hop bound must not under-count — brute-force check that
    # every (q, k) pair inside the band lies within the visited hops
    for s in (4, 8):
        for p in (2, 4, 8):
            for w in range(1, s * p + 2):
                hops = ring_num_hops(p, s, w)
                need = 0
                for qpos in range(s * p):
                    for kpos in range(max(0, qpos - w + 1), qpos + 1):
                        need = max(need, qpos // s - kpos // s)
                assert hops >= need + 1, (s, p, w)


def test_windowed_ring_requires_causal():
    q, k, v = _qkv(s=8)
    mesh = Mesh(np.array(jax.devices()[:2]), ("seq",))
    with pytest.raises(ValueError):
        ring_attention_sharded(q, k, v, mesh=mesh, seq_axis="seq",
                               causal=False, window=4)


def test_windowed_ring_gqa():
    b, h, kvh, t, d = 2, 4, 2, 32, 8
    kq, kk, kv_ = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (b, h, t, d))
    k = jax.random.normal(kk, (b, kvh, t, d))
    v = jax.random.normal(kv_, (b, kvh, t, d))
    k_full = jnp.repeat(k, h // kvh, axis=1)
    v_full = jnp.repeat(v, h // kvh, axis=1)
    expected = attention(q, k_full, v_full, mask=_band_mask(t, 5))
    mesh = Mesh(np.array(jax.devices()[:4]), ("seq",))
    got = ring_attention_sharded(q, k, v, mesh=mesh, seq_axis="seq",
                                 causal=True, window=5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("window", [None, 5])
@pytest.mark.parametrize("ring_size", [2, 4])
def test_ring_flash_matches_einsum_ring(window, ring_size):
    """Flash-kernel hops (interpret mode on CPU) match the einsum ring
    and the full-attention reference, with and without a window."""
    q, k, v = _qkv()
    mesh = Mesh(np.array(jax.devices()[:ring_size]), ("seq",))
    ref = ring_attention_sharded(q, k, v, mesh=mesh, seq_axis="seq",
                                 causal=True, window=window)
    got = ring_attention_sharded(q, k, v, mesh=mesh, seq_axis="seq",
                                 causal=True, window=window, impl="flash")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-4)


def test_ring_flash_gradients_match_einsum_ring():
    """The global-lse per-hop backward is exact: grads through the flash
    ring equal grads through the (autodiffed) einsum ring."""
    q, k, v = _qkv(s=16, d=8)
    mesh = Mesh(np.array(jax.devices()[:4]), ("seq",))
    cot = jax.random.normal(jax.random.PRNGKey(7), q.shape, jnp.float32)

    def loss(impl):
        def f(q, k, v):
            out = ring_attention_sharded(q, k, v, mesh=mesh,
                                         seq_axis="seq", causal=True,
                                         window=7, impl=impl)
            return jnp.sum(out * cot)
        return f

    ref_grads = jax.grad(loss("einsum"), argnums=(0, 1, 2))(q, k, v)
    got_grads = jax.grad(loss("flash"), argnums=(0, 1, 2))(q, k, v)
    for rg, gg in zip(ref_grads, got_grads):
        np.testing.assert_allclose(np.asarray(gg), np.asarray(rg),
                                   atol=3e-4, rtol=3e-4)


def test_ring_flash_gqa_forward_and_grad():
    b, h, kvh, t, d = 2, 4, 2, 16, 8
    kq, kk, kv_ = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (b, h, t, d))
    k = jax.random.normal(kk, (b, kvh, t, d))
    v = jax.random.normal(kv_, (b, kvh, t, d))
    mesh = Mesh(np.array(jax.devices()[:4]), ("seq",))

    ref = ring_attention_sharded(q, k, v, mesh=mesh, seq_axis="seq",
                                 causal=True)
    got = ring_attention_sharded(q, k, v, mesh=mesh, seq_axis="seq",
                                 causal=True, impl="flash")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-4)

    cot = jax.random.normal(jax.random.PRNGKey(7), q.shape, jnp.float32)

    def loss(impl):
        def f(q, k, v):
            out = ring_attention_sharded(q, k, v, mesh=mesh,
                                         seq_axis="seq", causal=True,
                                         impl=impl)
            return jnp.sum(out * cot)
        return f

    ref_grads = jax.grad(loss("einsum"), argnums=(0, 1, 2))(q, k, v)
    got_grads = jax.grad(loss("flash"), argnums=(0, 1, 2))(q, k, v)
    for rg, gg in zip(ref_grads, got_grads):
        np.testing.assert_allclose(np.asarray(gg), np.asarray(rg),
                                   atol=3e-4, rtol=3e-4)


@pytest.mark.parametrize("ring_size", [2, 4, 8])
def test_zigzag_ring_flash_matches_full(ring_size):
    """The balanced zigzag schedule (auto for full-causal flash rings)
    matches the plain attention reference exactly."""
    from functools import partial

    from elephas_tpu.ops.ring_attention import ring_flash_attention

    q, k, v = _qkv()
    mesh = Mesh(np.array(jax.devices()[:ring_size]), ("seq",))
    ref = attention(q, k, v, causal=True)
    spec = PartitionSpec(None, None, "seq", None)
    for zigzag in (True, None):  # explicit and auto both take the path
        fn = jax.shard_map(
            partial(ring_flash_attention, axis_name="seq", causal=True,
                    zigzag=zigzag),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False)
        got = fn(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-4)


def test_zigzag_ring_flash_gradients_match_plain():
    from functools import partial

    from elephas_tpu.ops.ring_attention import ring_flash_attention

    q, k, v = _qkv(s=16, d=8)
    mesh = Mesh(np.array(jax.devices()[:4]), ("seq",))
    spec = PartitionSpec(None, None, "seq", None)
    cot = jax.random.normal(jax.random.PRNGKey(7), q.shape, jnp.float32)

    def loss(zigzag):
        fn = jax.shard_map(
            partial(ring_flash_attention, axis_name="seq", causal=True,
                    zigzag=zigzag),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False)
        return lambda q, k, v: jnp.sum(fn(q, k, v) * cot)

    ref_grads = jax.grad(loss(False), argnums=(0, 1, 2))(q, k, v)
    got_grads = jax.grad(loss(True), argnums=(0, 1, 2))(q, k, v)
    for rg, gg in zip(ref_grads, got_grads):
        np.testing.assert_allclose(np.asarray(gg), np.asarray(rg),
                                   atol=3e-4, rtol=3e-4)


def test_zigzag_ring_flash_gqa():
    from functools import partial

    from elephas_tpu.ops.ring_attention import ring_flash_attention

    b, h, kvh, t, d = 2, 4, 2, 32, 8
    kq, kk, kv_ = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (b, h, t, d))
    k = jax.random.normal(kk, (b, kvh, t, d))
    v = jax.random.normal(kv_, (b, kvh, t, d))
    k_full = jnp.repeat(k, h // kvh, axis=1)
    v_full = jnp.repeat(v, h // kvh, axis=1)
    expected = attention(q, k_full, v_full, causal=True)
    mesh = Mesh(np.array(jax.devices()[:4]), ("seq",))
    spec = PartitionSpec(None, None, "seq", None)
    fn = jax.shard_map(
        partial(ring_flash_attention, axis_name="seq", causal=True,
                zigzag=True),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    np.testing.assert_allclose(np.asarray(fn(q, k, v)),
                               np.asarray(expected), atol=2e-5, rtol=2e-5)


def test_ring_flash_bf16():
    """bf16 inputs (the chip dtype): flash ring matches the f32 einsum
    ring within bf16 tolerance and returns bf16."""
    q, k, v = _qkv(s=32, d=16)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    mesh = Mesh(np.array(jax.devices()[:4]), ("seq",))
    ref = ring_attention_sharded(q, k, v, mesh=mesh, seq_axis="seq",
                                 causal=True)
    got = ring_attention_sharded(qb, kb, vb, mesh=mesh, seq_axis="seq",
                                 causal=True, impl="flash")
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref), atol=0.03, rtol=0.03)


def test_ring_attention_gqa_matches_full_attention():
    """GQA ring (kv-width buffers on the wire) matches grouped full
    attention computed by head-broadcast."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from elephas_tpu.ops.attention import attention
    from elephas_tpu.ops.ring_attention import ring_attention_sharded

    b, h, kvh, t, d = 2, 4, 2, 16, 8
    kq, kk, kv_ = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (b, h, t, d))
    k = jax.random.normal(kk, (b, kvh, t, d))
    v = jax.random.normal(kv_, (b, kvh, t, d))

    k_full = jnp.repeat(k, h // kvh, axis=1)
    v_full = jnp.repeat(v, h // kvh, axis=1)
    expected = np.asarray(attention(q, k_full, v_full, causal=True))

    mesh = Mesh(np.array(jax.devices()[:4]), ("seq",))
    got = np.asarray(ring_attention_sharded(q, k, v, mesh=mesh,
                                            seq_axis="seq", causal=True))
    np.testing.assert_allclose(got, expected, atol=2e-5, rtol=2e-5)
