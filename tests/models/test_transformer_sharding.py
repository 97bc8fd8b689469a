"""Transformer flagship tests: RoPE, GQA / MQA, FSDP, ZeRO and
dp/tp/sp-sharded parity with the unsharded computation."""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from elephas_tpu.models.transformer import (forward, init_params, lm_loss,
                                            make_train_step, param_specs,
                                            shard_params)

from ._transformer_util import _config, _rope_config, _gqa_config


def test_sharded_forward_matches_unsharded():
    config = _config()
    params = init_params(config, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                config.vocab_size)
    expected = np.asarray(forward(params, tokens, config))

    mesh = Mesh(np.array(jax.devices()).reshape(2, 2, 2),
                ("data", "model", "seq"))
    params_sharded = shard_params(params, config, mesh)
    tokens_sharded = jax.device_put(tokens, NamedSharding(mesh, P("data", "seq")))

    sharded = np.asarray(jax.jit(
        lambda p, t: forward(p, t, config, mesh=mesh, seq_axis="seq",
                             batch_axis="data"))(params_sharded, tokens_sharded))
    np.testing.assert_allclose(expected, sharded, atol=2e-3)


def test_sharded_train_step_runs():
    config = _config()
    mesh = Mesh(np.array(jax.devices()).reshape(2, 2, 2),
                ("data", "model", "seq"))
    params = shard_params(init_params(config, jax.random.PRNGKey(0)),
                          config, mesh)
    tx = optax.adam(1e-3)
    opt_state = jax.jit(tx.init)(params)
    tokens = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                           config.vocab_size),
        NamedSharding(mesh, P("data", "seq")))
    step = make_train_step(config, tx, mesh=mesh, seq_axis="seq")
    params, opt_state, loss1 = step(params, opt_state, tokens)
    params, opt_state, loss2 = step(params, opt_state, tokens)
    assert np.isfinite(float(loss2))
    assert float(loss2) < float(loss1)


def test_param_specs_structure_matches_params():
    config = _config()
    params = init_params(config, jax.random.PRNGKey(0))
    specs = param_specs(config)
    jax.tree_util.tree_map(lambda p, s: None, params, specs)  # same structure


def test_flash_under_dp_tp_mesh_matches_unsharded():
    """The flagship configuration: dp/tp mesh (no sequence axis) must hit
    the Pallas kernel via shard_map and agree with the unsharded XLA path
    in both values and gradients."""
    import dataclasses

    config = dataclasses.replace(_config(), attention_impl="flash")
    xla_config = dataclasses.replace(config, attention_impl="xla")
    params = init_params(config, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                config.vocab_size)
    expected = np.asarray(forward(params, tokens, xla_config))

    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))
    params_d = shard_params(params, config, mesh)
    tokens_d = jax.device_put(tokens, NamedSharding(mesh, P("data", None)))

    got = np.asarray(jax.jit(
        lambda p, t: forward(p, t, config, mesh=mesh, batch_axis="data",
                             model_axis="model"))(params_d, tokens_d))
    np.testing.assert_allclose(got, expected, atol=1e-4, rtol=1e-4)

    g_ref = jax.grad(lm_loss)(params, tokens, xla_config)
    g_mesh = jax.jit(jax.grad(
        lambda p, t: lm_loss(p, t, config, mesh=mesh, batch_axis="data",
                             model_axis="model")))(params_d, tokens_d)
    for a, b in zip(jax.tree_util.tree_leaves(g_mesh),
                    jax.tree_util.tree_leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4,
                                   rtol=2e-3)


def test_attention_impl_selection_rules():
    """The safety rules of the kernel gate, tested directly with injected
    backend/device-count (real-TPU combinations are not reachable on the
    CPU suite)."""
    import dataclasses

    from elephas_tpu.models.transformer import select_attention_impl

    cfg = _config()  # attention_impl='auto', 4 heads
    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))

    # auto + TPU + single device, no mesh -> bare kernel
    assert select_attention_impl(cfg, None, None, None, None, 4,
                                 backend="tpu", n_devices=1) == "flash"
    # auto + TPU + MULTIPLE visible devices, no mesh -> stay off the
    # kernel (no SPMD rule; inputs may be GSPMD-sharded)
    assert select_attention_impl(cfg, None, None, None, None, 4,
                                 backend="tpu", n_devices=8) == "xla"
    # auto + CPU -> xla
    assert select_attention_impl(cfg, None, None, None, None, 4,
                                 backend="cpu", n_devices=1) == "xla"
    # forced flash without a mesh: caller's responsibility, any count
    flash_cfg = dataclasses.replace(cfg, attention_impl="flash")
    assert select_attention_impl(flash_cfg, None, None, None, None, 4,
                                 backend="cpu", n_devices=8) == "flash"
    # mesh + seq axis -> ring; forced flash runs the kernel in the hops
    assert select_attention_impl(flash_cfg, mesh, "seq", "data", "model",
                                 4) == "ring_flash"
    assert select_attention_impl(cfg, mesh, "seq", "data", "model", 4,
                                 backend="cpu") == "ring"
    assert select_attention_impl(cfg, mesh, "seq", "data", "model", 4,
                                 backend="tpu") == "ring_flash"
    # mesh + auto on TPU -> shard_map'd kernel when dims divide
    assert select_attention_impl(cfg, mesh, None, "data", "model", 4,
                                 backend="tpu") == "flash_sharded"
    # mesh + auto on TPU with non-divisible batch -> xla fallback
    assert select_attention_impl(cfg, mesh, None, "data", "model", 3,
                                 backend="tpu") == "xla"
    # mesh + non-divisible heads (4 heads over model=2 divides; use a
    # 3-head config) -> xla fallback
    cfg3 = dataclasses.replace(cfg, num_heads=3)
    assert select_attention_impl(cfg3, mesh, None, "data", "model", 4,
                                 backend="tpu") == "xla"
    # mesh + forced xla -> xla even on TPU
    xla_cfg = dataclasses.replace(cfg, attention_impl="xla")
    assert select_attention_impl(xla_cfg, mesh, None, "data", "model", 4,
                                 backend="tpu") == "xla"


def test_remat_under_mesh_trains():
    import dataclasses

    config = dataclasses.replace(_config(), remat=True)
    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))
    params = shard_params(init_params(config, jax.random.PRNGKey(0)),
                          config, mesh)
    tx = optax.adam(1e-3)
    opt_state = jax.jit(tx.init)(params)
    tokens = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                           config.vocab_size),
        NamedSharding(mesh, P("data", None)))
    step = make_train_step(config, tx, mesh=mesh)
    params, opt_state, l1 = step(params, opt_state, tokens)
    params, opt_state, l2 = step(params, opt_state, tokens)
    assert np.isfinite(float(l2)) and float(l2) < float(l1)


@pytest.mark.xfail(
    strict=False,
    reason="environment-bound (PR 7 closing measurement: fails "
           "identically on the untouched seed here): this jaxlib's XLA "
           "CPU runtime rejects the zero-optimizer train step's donated "
           "buffers under the virtual 8-device mesh with 'INTERNAL: "
           "Expected aliased input ... and output ... to have the same "
           "size' — the donated replicated input aliases a shard-sized "
           "ZeRO output, which newer runtimes silently un-donate (the "
           "'donated buffers were not usable' warning path) and this one "
           "hard-errors on. Not an assertion knife-edge; passes on "
           "matching-jaxlib dev boxes, so non-strict.")
def test_zero_optimizer_sharding_saves_memory_and_matches():
    """ZeRO-1: with zero_optimizer=True the Adam moments shard over the
    data axis (memory / dp instead of replicated) and training matches
    the replicated-optimizer run."""
    from elephas_tpu.models.transformer import zero_opt_specs

    config = _config()
    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))
    tx = optax.adam(1e-3)

    params = shard_params(init_params(config, jax.random.PRNGKey(0)),
                          config, mesh)
    tokens = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                           config.vocab_size),
        NamedSharding(mesh, P("data", None)))

    # replicated-optimizer reference (independent buffers: the train
    # steps donate their inputs)
    ref_params = jax.tree_util.tree_map(jnp.copy, params)
    ref_opt = jax.jit(tx.init)(ref_params)
    ref_step = make_train_step(config, tx, mesh=mesh)
    ref_params, ref_opt, ref_loss = ref_step(ref_params, ref_opt, tokens)

    z_opt = jax.jit(tx.init)(params)
    z_step = make_train_step(config, tx, mesh=mesh, zero_optimizer=True)
    params, z_opt, z_loss = z_step(params, z_opt, tokens)

    np.testing.assert_allclose(float(z_loss), float(ref_loss), rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(ref_params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5,
                                   rtol=1e-5)

    # the moments really are data-sharded: at least the big leaves carry
    # the data axis in their sharding spec
    data_sharded = [
        leaf for leaf in jax.tree_util.tree_leaves(z_opt)
        if hasattr(leaf, "sharding")
        and isinstance(leaf.sharding, NamedSharding)
        and any("data" == ax for entry in leaf.sharding.spec
                for ax in ((entry,) if isinstance(entry, str)
                           else (entry or ())))]
    assert len(data_sharded) > 0

    # spec structure sanity: embed moment spec gains the data axis on the
    # vocab dim while keeping the tensor-parallel axis
    specs = zero_opt_specs(tx, params, config, mesh)
    mu_embed_spec = specs[0].mu["embed"]["tokens"]
    assert "model" in mu_embed_spec and "data" in mu_embed_spec


def test_rope_forward_trains_and_has_no_pos_table():
    config = _rope_config()
    params = init_params(config, jax.random.PRNGKey(0))
    assert "pos" not in params["embed"]
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                config.vocab_size)
    logits = forward(params, tokens, config)
    assert logits.shape == (4, 16, config.vocab_size)
    tx = optax.adam(1e-2)
    opt_state = tx.init(params)
    step = make_train_step(config, tx)
    first = None
    for _ in range(8):
        params, opt_state, loss = step(params, opt_state, tokens)
        if first is None:
            first = float(loss)
    assert np.isfinite(float(loss)) and float(loss) < first


def test_rope_is_position_sensitive_and_relative():
    """Same token at different positions must produce different logits
    (position is encoded), and rope must depend on q/k positions."""
    config = _rope_config()
    params = init_params(config, jax.random.PRNGKey(0))
    tok = np.full((1, 8), 7, dtype=np.int64)
    tok[0, 3] = 11
    shifted = np.roll(tok, 2, axis=1)
    a = np.asarray(forward(params, jnp.asarray(tok), config))
    b = np.asarray(forward(params, jnp.asarray(shifted), config))
    assert not np.allclose(a, b, atol=1e-4)


def test_rope_sharded_forward_matches_unsharded():
    """dp/tp/sp mesh (ring attention) with rope must equal the unsharded
    computation — the rotation happens on the global sequence before the
    ring shard_map."""
    config = _rope_config()
    params = init_params(config, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                config.vocab_size)
    expected = np.asarray(forward(params, tokens, config))
    mesh = Mesh(np.array(jax.devices()).reshape(2, 2, 2),
                ("data", "model", "seq"))
    params_d = shard_params(params, config, mesh)
    tokens_d = jax.device_put(tokens,
                              NamedSharding(mesh, P("data", "seq")))
    got = np.asarray(jax.jit(
        lambda p, t: forward(p, t, config, mesh=mesh, seq_axis="seq",
                             batch_axis="data"))(params_d, tokens_d))
    np.testing.assert_allclose(got, expected, atol=2e-3)


def test_rope_requires_even_head_dim():
    import dataclasses
    import pytest

    with pytest.raises(ValueError, match="even head_dim"):
        dataclasses.replace(_config(), positional="rope", num_heads=32,
                            d_model=32)  # head_dim 1


def test_gqa_validation_and_param_shapes():
    import pytest

    for bad in (3, 0, 8):  # 3 doesn't divide 4; 0 invalid; 8 > num_heads
        with pytest.raises(ValueError):
            _gqa_config(bad)
    config = _gqa_config(2)
    assert config.kv_heads == 2 and config.num_heads == 4
    params = init_params(config, jax.random.PRNGKey(0))
    attn = params["layer_0"]["attn"]
    assert attn["wq"].shape == (32, 4, 8)
    assert attn["wk"].shape == (32, 2, 8)
    assert attn["wv"].shape == (32, 2, 8)
    # default (None) stays full multi-head
    assert _config().kv_heads == _config().num_heads


def test_gqa_forward_trains():
    config = _gqa_config(2)
    params = init_params(config, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                config.vocab_size)
    logits = forward(params, tokens, config)
    assert logits.shape == (4, 16, config.vocab_size)
    tx = optax.adam(1e-2)
    opt_state = tx.init(params)
    step = make_train_step(config, tx)
    first = None
    for _ in range(8):
        params, opt_state, loss = step(params, opt_state, tokens)
        if first is None:
            first = float(loss)
    assert np.isfinite(float(loss)) and float(loss) < first


def test_gqa_sharded_matches_unsharded():
    """GQA under a dp/tp mesh (kv heads sharded over the model axis)
    matches the single-device forward."""
    config = _gqa_config(2)
    params = init_params(config, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                config.vocab_size)
    expected = np.asarray(forward(params, tokens, config))

    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))
    params_sharded = shard_params(params, config, mesh)
    tokens_sharded = jax.device_put(tokens,
                                    NamedSharding(mesh, P("data", None)))
    sharded = np.asarray(jax.jit(
        lambda p, t: forward(p, t, config, mesh=mesh, batch_axis="data",
                             model_axis="model"))(params_sharded,
                                                  tokens_sharded))
    np.testing.assert_allclose(expected, sharded, atol=2e-3)


# ------------------------------------------------------------------ FSDP
def test_fsdp_specs_shard_every_large_param():
    from elephas_tpu.models.transformer import fsdp_param_specs

    config = _config()
    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))
    specs = fsdp_param_specs(config, mesh)
    flat, _ = jax.tree_util.tree_flatten(
        specs, is_leaf=lambda x: isinstance(x, P))
    shapes, _ = jax.tree_util.tree_flatten(
        jax.eval_shape(lambda k: init_params(config, k), jax.random.PRNGKey(0)))
    for spec, leaf in zip(flat, shapes):
        entries = list(spec) + [None] * (len(leaf.shape) - len(spec))
        if any(s is None and d % 4 == 0 and d >= 4
               for s, d in zip(entries, leaf.shape)):
            assert "data" in spec, (spec, leaf.shape)


def test_fsdp_training_matches_unsharded_and_shrinks_memory():
    """The FSDP step must compute the same optimization trajectory as the
    plain single-device step while holding only 1/dp of each large param
    (and Adam moment) per device."""
    config = _config()
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0,
                                config.vocab_size)
    tx = optax.adam(1e-2)

    ref_params = init_params(config, jax.random.PRNGKey(0))
    ref_opt = tx.init(ref_params)
    ref_step = make_train_step(config, tx)

    mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
    params = shard_params(init_params(config, jax.random.PRNGKey(0)),
                          config, mesh, fsdp_axis="data")
    opt_state = jax.jit(tx.init)(params)
    tok_sharded = jax.device_put(tokens,
                                 NamedSharding(mesh, P("data", None)))
    step = make_train_step(config, tx, mesh=mesh, fsdp=True)

    # per-device bytes: embedding (64x32 f32) shards 8-way over the vocab
    emb = params["embed"]["tokens"]
    assert emb.addressable_shards[0].data.shape == (8, 32)

    for i in range(4):
        ref_params, ref_opt, ref_loss = ref_step(ref_params, ref_opt, tokens)
        params, opt_state, loss = step(params, opt_state, tok_sharded)
        np.testing.assert_allclose(float(loss), float(ref_loss),
                                   atol=2e-4, rtol=2e-4)
        # params stay fully sharded across steps (donation keeps layout)
        assert params["embed"]["tokens"].addressable_shards[0].data.shape \
            == (8, 32)
        # the step pins ZeRO-3 shardings on the optimizer moments too
        moments = [l for l in jax.tree_util.tree_leaves(opt_state)
                   if hasattr(l, "size") and l.size > 8]
        assert moments and all(
            l.addressable_shards[0].data.size < l.size for l in moments)

    flat_ref = jax.tree_util.tree_leaves(ref_params)
    flat = jax.tree_util.tree_leaves(params)
    for a, b in zip(flat, flat_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-3, rtol=2e-3)


def test_fsdp_with_tensor_parallel_axis_trains():
    config = _config()
    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))
    params = shard_params(init_params(config, jax.random.PRNGKey(0)),
                          config, mesh, fsdp_axis="data")
    tx = optax.adam(1e-3)
    opt_state = jax.jit(tx.init)(params)
    tokens = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                           config.vocab_size),
        NamedSharding(mesh, P("data", None)))
    step = make_train_step(config, tx, mesh=mesh, fsdp=True)
    params, opt_state, loss1 = step(params, opt_state, tokens)
    params, opt_state, loss2 = step(params, opt_state, tokens)
    assert np.isfinite(float(loss2)) and float(loss2) < float(loss1)


def test_fsdp_rejects_zero_optimizer_and_missing_mesh():
    import pytest

    config = _config()
    with pytest.raises(ValueError):
        make_train_step(config, optax.adam(1e-3), fsdp=True)
    mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
    with pytest.raises(ValueError):
        make_train_step(config, optax.adam(1e-3), mesh=mesh, fsdp=True,
                        zero_optimizer=True)


def test_mqa_under_tensor_parallel_mesh_replicates_kv_and_matches():
    """kv_heads=1 cannot shard over tp=2: param_specs must replicate
    wk/wv under that mesh instead of crashing, and the sharded forward
    still matches the unsharded one."""
    config = _gqa_config(1)
    params = init_params(config, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                config.vocab_size)
    expected = np.asarray(forward(params, tokens, config))

    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))
    specs = param_specs(config, mesh=mesh)
    assert specs["layer_0"]["attn"]["wk"] == P(None, None, None)
    assert specs["layer_0"]["attn"]["wq"] == P(None, "model", None)
    params_sharded = shard_params(params, config, mesh)  # crashed before
    tokens_sharded = jax.device_put(tokens,
                                    NamedSharding(mesh, P("data", None)))
    sharded = np.asarray(jax.jit(
        lambda p, t: forward(p, t, config, mesh=mesh, batch_axis="data",
                             model_axis="model"))(params_sharded,
                                                  tokens_sharded))
    np.testing.assert_allclose(expected, sharded, atol=2e-3)


def test_gqa_ring_sharded_forward_matches_unsharded():
    """GQA + sequence parallelism: the ring path takes kv-width buffers
    and the sharded forward matches the single-device one."""
    config = _gqa_config(2)
    params = init_params(config, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                config.vocab_size)
    expected = np.asarray(forward(params, tokens, config))
    mesh = Mesh(np.array(jax.devices()).reshape(2, 2, 2),
                ("data", "model", "seq"))
    sp = shard_params(params, config, mesh)
    td = jax.device_put(tokens, NamedSharding(mesh, P("data", "seq")))
    got = np.asarray(jax.jit(
        lambda p, t: forward(p, t, config, mesh=mesh, seq_axis="seq",
                             batch_axis="data"))(sp, td))
    np.testing.assert_allclose(expected, got, atol=2e-3)


def test_gqa_flash_impl_matches_xla_forward_and_grads():
    """The GQA flash path (narrow k/v into the kernel) matches the xla
    path for the full model, values and grads."""
    import dataclasses

    config = dataclasses.replace(_gqa_config(2), attention_impl="flash")
    xla_cfg = dataclasses.replace(_gqa_config(2), attention_impl="xla")
    params = init_params(config, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64)
    ref = forward(params, tokens, xla_cfg)
    got = forward(params, tokens, config)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)
    g_ref = jax.grad(lm_loss)(params, tokens, xla_cfg)
    g_fl = jax.grad(lm_loss)(params, tokens, config)
    for a, b in zip(jax.tree_util.tree_leaves(g_fl),
                    jax.tree_util.tree_leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-3)


def test_gqa_flash_under_dp_tp_mesh_matches_unsharded():
    import dataclasses

    config = dataclasses.replace(_gqa_config(2), attention_impl="flash")
    params = init_params(config, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 64)
    expected = np.asarray(forward(params, tokens,
                                  dataclasses.replace(config,
                                                      attention_impl="xla")))
    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))
    sp = shard_params(params, config, mesh)
    td = jax.device_put(tokens, NamedSharding(mesh, P("data", None)))
    got = np.asarray(jax.jit(
        lambda p, t: forward(p, t, config, mesh=mesh, batch_axis="data",
                             model_axis="model"))(sp, td))
    np.testing.assert_allclose(expected, got, atol=2e-3)


def test_param_specs_replicate_on_non_divisible_model_axis():
    """4 heads on an 8-way model axis must replicate (not crash
    device_put) — uniformly across the sharded dims."""
    config = _config()  # 4 heads, d_ff 64, vocab 64
    mesh = Mesh(np.array(jax.devices()).reshape(1, 8), ("data", "model"))
    specs = param_specs(config, mesh=mesh)
    assert specs["layer_0"]["attn"]["wq"] == P(None, None, None)
    assert specs["layer_0"]["mlp"]["w1"] == P(None, "model")  # 64 % 8 == 0
    params = shard_params(init_params(config, jax.random.PRNGKey(0)),
                          config, mesh)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 64)
    expected = float(lm_loss(init_params(config, jax.random.PRNGKey(0)),
                             tokens, config))
    got = float(jax.jit(lambda p, t: lm_loss(p, t, config))(params, tokens))
    np.testing.assert_allclose(got, expected, atol=2e-4, rtol=2e-4)


def test_window_under_seq_mesh_runs_windowed_ring_and_matches():
    import dataclasses

    config = dataclasses.replace(_config(), attention_window=4)
    # the test helper injects backend="tpu": windowed seq-mesh configs
    # run the flash ring there (einsum ring on other backends)
    assert select_attention_impl_for_test(config) == "ring_flash"
    params = init_params(config, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 64)
    expected = np.asarray(forward(params, tokens, config))
    mesh = Mesh(np.array(jax.devices()).reshape(2, 2, 2),
                ("data", "model", "seq"))
    sp = shard_params(params, config, mesh)
    td = jax.device_put(tokens, NamedSharding(mesh, P("data", "seq")))
    got = np.asarray(jax.jit(
        lambda p, t: forward(p, t, config, mesh=mesh, seq_axis="seq",
                             batch_axis="data"))(sp, td))
    np.testing.assert_allclose(expected, got, atol=2e-3)


def select_attention_impl_for_test(config):
    from elephas_tpu.models.transformer import select_attention_impl
    from jax.sharding import Mesh as _Mesh

    mesh = _Mesh(np.array(jax.devices()).reshape(2, 2, 2),
                 ("data", "model", "seq"))
    return select_attention_impl(config, mesh, "seq", "data", "model", 4,
                                 backend="tpu", n_devices=8)
