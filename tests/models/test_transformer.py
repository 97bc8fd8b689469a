"""Transformer flagship tests: forward shapes, the training step,
flash attention, mixture-of-experts layers and rematerialisation."""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from elephas_tpu.models.transformer import (TransformerConfig, _moe_block,
                                            forward, init_params, lm_loss,
                                            make_train_step, param_specs,
                                            shard_params)

from ._transformer_util import (DECODE_STEP, FORWARD, LM_LOSS,
                                LOSS_AND_GRAD, _config, _moe_config)

MOE_BLOCK = jax.jit(_moe_block, static_argnums=2,
                    static_argnames="dispatch")


def test_forward_shapes_and_loss():
    config = _config()
    params = init_params(config, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                config.vocab_size)
    logits = FORWARD(params, tokens, config)
    assert logits.shape == (2, 16, 64)
    loss = float(LM_LOSS(params, tokens, config))
    assert np.isfinite(loss)
    # untrained LM loss should be near log(vocab)
    assert abs(loss - np.log(config.vocab_size)) < 1.0


def test_training_decreases_loss():
    config = _config()
    params = init_params(config, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                config.vocab_size)
    tx = optax.adam(1e-2)
    opt_state = tx.init(params)
    step = make_train_step(config, tx)
    first = None
    for _ in range(10):
        params, opt_state, loss = step(params, opt_state, tokens)
        if first is None:
            first = float(loss)
    assert float(loss) < first


def test_flash_attention_impl_matches_xla():
    import dataclasses

    config = _config()
    params = init_params(config, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                config.vocab_size)
    flash_config = dataclasses.replace(config, attention_impl="flash")
    # force the XLA reference: on a TPU backend 'auto' would also resolve
    # to flash, making the comparison vacuous
    xla_config = dataclasses.replace(config, attention_impl="xla")
    ref = FORWARD(params, tokens, xla_config)
    got = FORWARD(params, tokens, flash_config)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-4,
                               rtol=1e-4)
    _, g_ref = LOSS_AND_GRAD(params, tokens, xla_config)
    _, g_flash = LOSS_AND_GRAD(params, tokens, flash_config)
    flat_ref, _ = jax.tree_util.tree_flatten(g_ref)
    flat_flash, _ = jax.tree_util.tree_flatten(g_flash)
    for a, b in zip(flat_flash, flat_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4,
                                   rtol=1e-3)


def test_moe_forward_and_training():
    config = _moe_config()
    params = init_params(config, jax.random.PRNGKey(0))
    assert "moe" in params["layer_0"] and "mlp" not in params["layer_0"]
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                config.vocab_size)
    logits = FORWARD(params, tokens, config)
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


def test_moe_top1_routes_to_single_expert():
    """With top_k=1 the block output must equal the argmax expert's MLP
    scaled by its raw softmax probability (Switch-style gating)."""
    config = _moe_config(num_experts=3, expert_top_k=1,
                         num_layers=1)
    params = init_params(config, jax.random.PRNGKey(0))
    moe = params["layer_0"]["moe"]
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 5, config.d_model),
                          jnp.float32)
    out, aux = MOE_BLOCK(h, moe, config)
    out = np.asarray(out)
    assert np.isfinite(float(aux)) and float(aux) >= 1.0  # >= uniform bound
    probs = np.asarray(jax.nn.softmax(h @ moe["gate"], axis=-1))
    chosen = probs.argmax(-1)
    for b in range(2):
        for t in range(5):
            e = chosen[b, t]
            ref = jax.nn.gelu(h[b, t] @ moe["w1"][e] + moe["b1"][e])
            ref = (ref @ moe["w2"][e] + moe["b2"][e]) * probs[b, t, e]
            np.testing.assert_allclose(out[b, t], np.asarray(ref), atol=1e-5)


def test_moe_router_receives_gradient():
    """The gate must train even with top_k=1 (Switch scaling keeps the
    router gradient alive). aux_weight=0 isolates the scaling path — the
    aux loss would otherwise feed the gate a gradient by itself and mask
    a regression to hard routing."""
    config = _moe_config(num_experts=4, expert_top_k=1, num_layers=1,
                         moe_aux_weight=0.0)
    params = init_params(config, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                config.vocab_size)
    _, grads = LOSS_AND_GRAD(params, tokens, config)
    gate_grad = np.asarray(grads["layer_0"]["moe"]["gate"])
    assert np.abs(gate_grad).max() > 0.0


def test_moe_sharded_matches_unsharded():
    """Expert parallelism: experts sharded over the model axis must give
    the same result as the unsharded computation."""
    config = _moe_config()
    params = init_params(config, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                config.vocab_size)
    expected = np.asarray(FORWARD(params, tokens, config))

    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))
    params_sharded = shard_params(params, config, mesh)
    tokens_sharded = jax.device_put(
        tokens, NamedSharding(mesh, P("data", None)))
    sharded = np.asarray(jax.jit(lambda p, t: forward(p, t, config))(
        params_sharded, tokens_sharded))
    np.testing.assert_allclose(expected, sharded, atol=2e-3)


def test_moe_routed_matches_dense_when_nothing_drops():
    """With capacity_factor = E/k the capacity equals the token count, so
    no assignment can drop and routed dispatch must agree with dense
    dispatch exactly (same router, same experts, different data path)."""
    config = _moe_config(num_experts=4, expert_top_k=2, num_layers=1,
                         moe_capacity_factor=2.0)  # C = N: lossless
    params = init_params(config, jax.random.PRNGKey(0))
    moe = params["layer_0"]["moe"]
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 8, config.d_model),
                          jnp.float32)
    dense, aux_d = MOE_BLOCK(h, moe, config, dispatch="dense")
    routed, aux_r = MOE_BLOCK(h, moe, config, dispatch="routed")
    np.testing.assert_allclose(np.asarray(routed), np.asarray(dense),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(float(aux_r), float(aux_d), rtol=1e-6)


def test_moe_routed_flops_scale_with_top_k_not_experts():
    """The point of routed dispatch: expert-MLP FLOPs stay ~constant as
    num_experts grows (dense doubles when E doubles)."""
    def flops(num_experts, dispatch):
        config = _moe_config(num_experts=num_experts, expert_top_k=2,
                             num_layers=1, moe_capacity_factor=1.0)
        params = init_params(config, jax.random.PRNGKey(0))
        moe = params["layer_0"]["moe"]
        h = jnp.zeros((4, 32, config.d_model), jnp.float32)
        lowered = jax.jit(
            lambda hh, mm: _moe_block(hh, mm, config, dispatch=dispatch)
        ).lower(h, moe)
        return lowered.cost_analysis()["flops"]

    dense8, dense16 = flops(8, "dense"), flops(16, "dense")
    routed8, routed16 = flops(8, "routed"), flops(16, "routed")
    assert dense16 > 1.7 * dense8          # dense pays num_experts x
    assert routed16 < 1.3 * routed8        # routed pays top_k x
    assert routed8 < 0.5 * dense8          # and wins outright at E=8


def test_moe_routed_drops_over_capacity_tokens():
    """Assignments beyond an expert's capacity contribute nothing: with a
    gate forced to a single expert and capacity < N, exactly the first
    `capacity` tokens (token-major priority) produce output."""
    config = _moe_config(num_experts=4, expert_top_k=1, num_layers=1,
                         moe_capacity_factor=1.0)  # C = N/E = 2
    params = init_params(config, jax.random.PRNGKey(0))
    moe = dict(params["layer_0"]["moe"])
    # rig the router: a zero gate gives every token identical logits, and
    # top_k tie-breaks to expert 0 — all 8 tokens chase one expert
    moe["gate"] = jnp.zeros_like(moe["gate"])
    h = jax.random.normal(jax.random.PRNGKey(2), (1, 8, config.d_model),
                          jnp.float32)
    out, _ = MOE_BLOCK(h, moe, config, dispatch="routed")
    out = np.asarray(out)
    capacity = 2  # ceil(1.0 * 1 * 8 / 4)
    assert np.abs(out[0, :capacity]).max() > 0
    np.testing.assert_allclose(out[0, capacity:], 0.0, atol=1e-7)


def test_moe_routed_trains_and_router_gets_gradient():
    config = _moe_config(num_experts=8, expert_top_k=2,
                         moe_dispatch="routed", moe_aux_weight=0.0)
    params = init_params(config, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                config.vocab_size)
    _, grads = LOSS_AND_GRAD(params, tokens, config)
    assert np.abs(np.asarray(grads["layer_0"]["moe"]["gate"])).max() > 0
    tx = optax.adam(1e-2)
    opt_state = tx.init(params)
    step = make_train_step(config, tx)
    first = None
    for _ in range(8):
        params, opt_state, loss = step(params, opt_state, tokens)
        if first is None:
            first = float(loss)
    assert np.isfinite(float(loss)) and float(loss) < first


def test_moe_dispatch_auto_selection():
    from elephas_tpu.models.transformer import select_moe_dispatch

    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))
    small = _moe_config(num_experts=4)
    big = _moe_config(num_experts=8)
    assert select_moe_dispatch(small) == "dense"
    assert select_moe_dispatch(big) == "routed"
    # expert-sharded mesh routes too (shard_map EP program) when the
    # experts divide the axis
    assert select_moe_dispatch(big, mesh, "model") == "routed"
    # dp-only usage of the same mesh routes
    assert select_moe_dispatch(big, mesh, None) == "routed"
    forced = _moe_config(num_experts=2, moe_dispatch="routed")
    assert select_moe_dispatch(forced, mesh, "model") == "routed"


def test_moe_routed_ep_matches_unsharded_routed():
    """Expert-parallel routed dispatch (shard_map + psum over the model
    axis) must equal the single-device routed computation when capacity
    is lossless, and train with live router gradients."""
    import dataclasses

    config = _moe_config(num_experts=8, expert_top_k=2,
                         moe_dispatch="routed",
                         moe_capacity_factor=4.0)  # C = N: lossless
    params = init_params(config, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                config.vocab_size)
    expected = np.asarray(FORWARD(params, tokens, config))

    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))
    params_d = shard_params(params, config, mesh)
    tokens_d = jax.device_put(tokens, NamedSharding(mesh, P("data", None)))
    got = np.asarray(jax.jit(
        lambda p, t: forward(p, t, config, mesh=mesh, batch_axis="data",
                             model_axis="model"))(params_d, tokens_d))
    np.testing.assert_allclose(got, expected, atol=2e-3)

    # gradients flow through the shard_map program (router included)
    g = jax.jit(jax.grad(
        lambda p, t: lm_loss(p, t, config, mesh=mesh, batch_axis="data",
                             model_axis="model")))(params_d, tokens_d)
    gate_grad = np.asarray(g["layer_0"]["moe"]["gate"])
    assert np.isfinite(gate_grad).all() and np.abs(gate_grad).max() > 0


def test_moe_routed_ep_train_step_decreases_loss():
    config = _moe_config(num_experts=8, expert_top_k=2,
                         moe_dispatch="routed")
    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))
    params = shard_params(init_params(config, jax.random.PRNGKey(0)),
                          config, mesh)
    tx = optax.adam(1e-2)
    opt_state = jax.jit(tx.init)(params)
    tokens = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                           config.vocab_size),
        NamedSharding(mesh, P("data", None)))
    step = make_train_step(config, tx, mesh=mesh)
    first = None
    for _ in range(6):
        params, opt_state, loss = step(params, opt_state, tokens)
        if first is None:
            first = float(loss)
    assert np.isfinite(float(loss)) and float(loss) < first


def test_moe_dispatch_auto_under_ep_mesh_routes_when_divisible():
    from elephas_tpu.models.transformer import select_moe_dispatch

    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))
    assert select_moe_dispatch(_moe_config(num_experts=8), mesh,
                               "model") == "routed"
    # 6 experts over a 4-way model axis don't divide: dense einsum
    assert select_moe_dispatch(_moe_config(num_experts=6, expert_top_k=2),
                               mesh, "model") == "dense"


def test_forced_routed_with_non_divisible_model_axis_stays_routed():
    """An explicit moe_dispatch='routed' is honored (GSPMD routed path)
    even when the experts don't divide the model axis or a seq axis is in
    play — the shard_map EP program only engages when its divisibility
    precondition holds."""
    config = _moe_config(num_experts=2, expert_top_k=1,
                         moe_dispatch="routed", moe_capacity_factor=2.0)
    params = init_params(config, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                config.vocab_size)
    expected = np.asarray(FORWARD(params, tokens, config))
    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))
    # E=2 can't shard over a 4-way axis: params stay replicated
    got = np.asarray(jax.jit(
        lambda p, t: forward(p, t, config, mesh=mesh, batch_axis="data",
                             model_axis="model"))(params, tokens))
    np.testing.assert_allclose(got, expected, atol=2e-3)


def test_remat_matches_baseline_values_and_grads():
    import dataclasses

    config = _config()
    remat_config = dataclasses.replace(config, remat=True)
    params = init_params(config, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                config.vocab_size)
    np.testing.assert_allclose(
        np.asarray(FORWARD(params, tokens, remat_config)),
        np.asarray(FORWARD(params, tokens, config)), atol=1e-6)
    _, g = LOSS_AND_GRAD(params, tokens, config)
    _, g_r = LOSS_AND_GRAD(params, tokens, remat_config)
    for a, b in zip(jax.tree_util.tree_leaves(g_r),
                    jax.tree_util.tree_leaves(g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5,
                                   rtol=1e-5)


def test_mlp_variant_and_norm_validation():
    import pytest

    with pytest.raises(ValueError):
        TransformerConfig(mlp_variant="relu")
    with pytest.raises(ValueError):
        TransformerConfig(norm="batchnorm")
    # gelu default unchanged: no w3 in params
    params = init_params(_config(), jax.random.PRNGKey(0))
    assert "w3" not in params["layer_0"]["mlp"]


def test_remat_dots_policy_matches_values_and_grads():
    import dataclasses

    base = _config()
    params = init_params(base, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, 64)
    ref, g_ref = LOSS_AND_GRAD(params, tokens, base)
    for policy in ("full", "dots"):
        cfg = dataclasses.replace(base, remat=True, remat_policy=policy)
        loss, g = LOSS_AND_GRAD(params, tokens, cfg)
        np.testing.assert_allclose(float(loss), float(ref), atol=1e-5,
                                   rtol=1e-5)
        for a, b in zip(jax.tree_util.tree_leaves(g),
                        jax.tree_util.tree_leaves(g_ref)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5, rtol=1e-4)
    import pytest
    with pytest.raises(ValueError):
        dataclasses.replace(base, remat_policy="everything")


def test_moe_shared_expert():
    """DeepSeek-style shared expert: adds an always-on dense path to the
    MoE combine, consistent across dense/routed dispatch and decode."""
    import dataclasses

    from elephas_tpu.models.transformer import init_kv_cache

    config = _moe_config(num_experts=4, expert_top_k=2)
    shared_cfg = dataclasses.replace(config, moe_shared_expert=True)
    params = init_params(shared_cfg, jax.random.PRNGKey(0))
    assert "shared" in params["layer_0"]["moe"]
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, 8),
                                           0, shared_cfg.vocab_size))

    # the shared path participates: zeroing it changes the output
    full = np.asarray(FORWARD(params, jnp.asarray(tokens), shared_cfg))
    import copy

    zeroed = copy.deepcopy(jax.device_get(params))
    for i in range(shared_cfg.num_layers):
        sh = zeroed[f"layer_{i}"]["moe"]["shared"]
        sh["w2"] = np.zeros_like(sh["w2"])
    out_z = np.asarray(FORWARD(jax.tree_util.tree_map(jnp.asarray, zeroed),
                               jnp.asarray(tokens), shared_cfg))
    assert np.abs(full - out_z).max() > 1e-6

    # decode parity with forward
    cache = init_kv_cache(shared_cfg, 2, max_len=8)
    for t in range(8):
        logits, cache = DECODE_STEP(params, cache,
                                    jnp.asarray(tokens[:, t]), t,
                                    shared_cfg)
        np.testing.assert_allclose(np.asarray(logits), full[:, t],
                                   atol=2e-4, rtol=2e-4)

    # trains; shared expert receives gradient
    tx = optax.adam(1e-2)
    opt = tx.init(params)
    step = make_train_step(shared_cfg, tx)
    jt = jnp.asarray(np.tile(tokens, (2, 1)))
    first = None
    for _ in range(6):
        params, opt, loss = step(params, opt, jt)
        first = first if first is not None else float(loss)
    assert float(loss) < first
    _, g = LOSS_AND_GRAD(params, jt, shared_cfg)
    assert np.abs(np.asarray(
        g["layer_0"]["moe"]["shared"]["w1"])).sum() > 0

    # specs structure matches params
    jax.tree_util.tree_map(lambda p, s: None, params,
                           param_specs(shared_cfg))
