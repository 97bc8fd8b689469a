"""Vision Transformer tests: shapes, training, sharded parity."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from elephas_tpu.models.vit import (ViTConfig, forward, init_params,
                                    make_train_step, param_specs,
                                    shard_params, vit_loss)

# compiled whole, once a shape and config: op by op, a pass is a
# hundred small compiles
FORWARD = jax.jit(forward, static_argnums=2)
LOSS = jax.jit(vit_loss, static_argnums=3)


def _config(**kw):
    base = dict(image_size=16, patch_size=4, channels=3, num_classes=10,
                num_layers=2, num_heads=4, d_model=32, d_ff=64,
                dtype=jnp.float32)
    base.update(kw)
    return ViTConfig(**base)


def _images(n=32, config=None, seed=0):
    """Separable task: class k images have a bright k-th 4x4 cell."""
    c = config or _config()
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, c.num_classes, n)
    x = rng.normal(0.0, 0.3, (n, c.image_size, c.image_size, c.channels))
    for i, k in enumerate(labels):
        r, col = divmod(int(k), c.image_size // c.patch_size)
        x[i, r * 4:(r + 1) * 4, col * 4:(col + 1) * 4, :] += 2.0
    return x.astype("float32"), labels.astype("int32")


def test_vit_forward_shapes_and_loss():
    config = _config()
    params = init_params(config, jax.random.PRNGKey(0))
    x, y = _images(8, config)
    logits = FORWARD(params, jnp.asarray(x), config)
    assert logits.shape == (8, 10)
    loss = float(LOSS(params, jnp.asarray(x), jnp.asarray(y), config))
    assert np.isfinite(loss)
    assert abs(loss - np.log(10)) < 0.5  # untrained ~ uniform


@pytest.mark.parametrize("pool", ["cls", "mean"])
def test_vit_trains(pool):
    config = _config(pool=pool)
    params = init_params(config, jax.random.PRNGKey(0))
    x, y = _images(64, config)
    tx = optax.adam(1e-3)
    opt = tx.init(params)
    step = make_train_step(config, tx)
    first = None
    for _ in range(20):
        params, opt, loss = step(params, opt, jnp.asarray(x), jnp.asarray(y))
        if first is None:
            first = float(loss)
    assert float(loss) < first
    preds = np.asarray(FORWARD(params, jnp.asarray(x), config)).argmax(1)
    assert (preds == y).mean() > 0.5


def test_vit_sharded_matches_unsharded():
    config = _config()
    params = init_params(config, jax.random.PRNGKey(0))
    x, y = _images(8, config)
    expected = np.asarray(FORWARD(params, jnp.asarray(x), config))

    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))
    sharded_params = shard_params(params, config, mesh)
    xs = jax.device_put(jnp.asarray(x),
                        NamedSharding(mesh, P("data", None, None, None)))
    got = np.asarray(jax.jit(
        lambda p, im: forward(p, im, config))(sharded_params, xs))
    np.testing.assert_allclose(expected, got, atol=2e-3)


def test_vit_sharded_train_step_decreases_loss():
    config = _config()
    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))
    params = shard_params(init_params(config, jax.random.PRNGKey(0)),
                          config, mesh)
    x, y = _images(32, config)
    tx = optax.adam(1e-3)
    opt = jax.jit(tx.init)(params)
    xs = jax.device_put(jnp.asarray(x),
                        NamedSharding(mesh, P("data", None, None, None)))
    ys = jax.device_put(jnp.asarray(y), NamedSharding(mesh, P("data")))
    step = make_train_step(config, tx, mesh=mesh)
    params, opt, l1 = step(params, opt, xs, ys)
    params, opt, l2 = step(params, opt, xs, ys)
    assert np.isfinite(float(l2)) and float(l2) < float(l1)


def test_vit_config_validation_and_gqa():
    with pytest.raises(ValueError):
        _config(patch_size=5)
    with pytest.raises(ValueError):
        _config(pool="max")
    with pytest.raises(ValueError):
        _config(num_kv_heads=3)
    config = _config(num_kv_heads=2)
    params = init_params(config, jax.random.PRNGKey(0))
    assert params["layer_0"]["attn"]["wk"].shape == (32, 2, 8)
    x, _ = _images(4, config)
    assert FORWARD(params, jnp.asarray(x), config).shape == (4, 10)
    # specs structure matches params
    jax.tree_util.tree_map(lambda p, s: None, params, param_specs(config))


def test_vit_dropout_active_in_training_only():
    config = _config(dropout_rate=0.2)
    params = init_params(config, jax.random.PRNGKey(0))
    x, y = _images(8, config)
    a = np.asarray(FORWARD(params, jnp.asarray(x), config))
    b = np.asarray(FORWARD(params, jnp.asarray(x), config))
    np.testing.assert_array_equal(a, b)  # inference deterministic
    d = np.asarray(FORWARD(params, jnp.asarray(x), config,
                           dropout_key=jax.random.PRNGKey(1)))
    assert np.abs(d - a).max() > 1e-6
    tx = optax.adam(1e-3)
    opt = tx.init(params)
    step = make_train_step(config, tx)
    params, opt, loss = step(params, opt, jnp.asarray(x), jnp.asarray(y),
                             jax.random.PRNGKey(2))
    assert np.isfinite(float(loss))


def test_vit_stochastic_depth():
    # inference identical regardless of rate; training path differs and
    # still trains; first block never drops (rate scales from 0)
    config = _config(drop_path_rate=0.5, num_layers=3)
    base = _config(num_layers=3)
    params = init_params(base, jax.random.PRNGKey(0))
    x, y = _images(16, base)
    np.testing.assert_array_equal(
        np.asarray(FORWARD(params, jnp.asarray(x), config)),
        np.asarray(FORWARD(params, jnp.asarray(x), base)))
    d = np.asarray(FORWARD(params, jnp.asarray(x), config,
                           dropout_key=jax.random.PRNGKey(1)))
    assert np.abs(d - np.asarray(FORWARD(params, jnp.asarray(x),
                                         base))).max() > 1e-6
    tx = optax.adam(1e-3)
    opt = tx.init(params)
    step = make_train_step(config, tx)
    params, opt, loss = step(params, opt, jnp.asarray(x), jnp.asarray(y),
                             jax.random.PRNGKey(2))
    assert np.isfinite(float(loss))
    with pytest.raises(ValueError):
        _config(drop_path_rate=1.0)


def test_vit_remat_with_drop_path_and_dropout():
    config = _config(remat=True, drop_path_rate=0.3, dropout_rate=0.1,
                     num_layers=3)
    params = init_params(config, jax.random.PRNGKey(0))
    x, y = _images(16, config)
    # remat must not change inference values
    base = _config(num_layers=3)
    np.testing.assert_allclose(
        np.asarray(FORWARD(params, jnp.asarray(x), config)),
        np.asarray(FORWARD(params, jnp.asarray(x), base)), atol=1e-6)
    tx = optax.adam(1e-3)
    opt = tx.init(params)
    step = make_train_step(config, tx)
    params, opt, loss = step(params, opt, jnp.asarray(x), jnp.asarray(y),
                             jax.random.PRNGKey(1))
    assert np.isfinite(float(loss))
