"""Pipeline-parallelism tests: GPipe schedule over the virtual CPU mesh
must match sequential stage application exactly, for values and grads."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from elephas_tpu.parallel.pipeline import make_pipeline_fn, stack_stage_params


def _mesh(pipe=4):
    devices = np.array(jax.devices()[:pipe])
    return Mesh(devices, ("pipe",))


def _stage_fn(params, x):
    h = jnp.tanh(x @ params["w1"] + params["b1"])
    return h @ params["w2"] + params["b2"] + x  # residual, shape-preserving


def _stage_params(key, d=8, hidden=16):
    k1, k2 = jax.random.split(key)
    return {"w1": jax.random.normal(k1, (d, hidden)) * 0.3,
            "b1": jnp.zeros((hidden,)),
            "w2": jax.random.normal(k2, (hidden, d)) * 0.3,
            "b2": jnp.zeros((d,))}


def _sequential(per_stage, x):
    for p in per_stage:
        x = _stage_fn(p, x)
    return x


@pytest.mark.parametrize("num_micro", [4, 8])
def test_pipeline_matches_sequential(num_micro):
    mesh = _mesh(4)
    per_stage = [_stage_params(jax.random.PRNGKey(i)) for i in range(4)]
    stacked = stack_stage_params(per_stage)
    x = jax.random.normal(jax.random.PRNGKey(9), (16, 8))

    pipe_fn = make_pipeline_fn(_stage_fn, mesh, num_microbatches=num_micro)
    got = np.asarray(jax.jit(pipe_fn)(stacked, x))
    want = np.asarray(_sequential(per_stage, x))
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_pipeline_gradients_match_sequential():
    mesh = _mesh(4)
    per_stage = [_stage_params(jax.random.PRNGKey(i)) for i in range(4)]
    stacked = stack_stage_params(per_stage)
    x = jax.random.normal(jax.random.PRNGKey(9), (8, 8))
    y = jax.random.normal(jax.random.PRNGKey(10), (8, 8))

    pipe_fn = make_pipeline_fn(_stage_fn, mesh)

    def loss_pipe(p):
        return jnp.mean((pipe_fn(p, x) - y) ** 2)

    def loss_seq(per):
        return jnp.mean((_sequential(per, x) - y) ** 2)

    g_pipe = jax.jit(jax.grad(loss_pipe))(stacked)
    g_seq = jax.grad(loss_seq)(per_stage)
    g_seq_stacked = stack_stage_params(g_seq)
    for a, b in zip(jax.tree_util.tree_leaves(g_pipe),
                    jax.tree_util.tree_leaves(g_seq_stacked)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5,
                                   rtol=1e-4)


def test_pipeline_with_transformer_blocks():
    """Pipeline the flagship's transformer blocks: 8 layers, 4 stages of 2,
    parity with the unpipelined forward."""
    from elephas_tpu.models.transformer import (TransformerConfig,
                                                _layer_norm, init_params)

    config = TransformerConfig(vocab_size=32, num_layers=8, num_heads=2,
                               d_model=16, d_ff=32, max_seq_len=16,
                               dtype=jnp.float32)
    params = init_params(config, jax.random.PRNGKey(0))

    def block(layer, x):
        from elephas_tpu.ops.attention import attention

        h = _layer_norm(x, layer["ln1"]["gamma"], layer["ln1"]["beta"])
        q = jnp.einsum("btd,dhk->bhtk", h, layer["attn"]["wq"])
        k = jnp.einsum("btd,dhk->bhtk", h, layer["attn"]["wk"])
        v = jnp.einsum("btd,dhk->bhtk", h, layer["attn"]["wv"])
        o = attention(q, k, v, causal=True)
        x = x + jnp.einsum("bhtk,hkd->btd", o, layer["attn"]["wo"])
        h = _layer_norm(x, layer["ln2"]["gamma"], layer["ln2"]["beta"])
        h = jax.nn.gelu(h @ layer["mlp"]["w1"] + layer["mlp"]["b1"])
        return x + h @ layer["mlp"]["w2"] + layer["mlp"]["b2"]

    def stage_fn(stage_params, x):
        # two consecutive blocks per stage
        for j in range(2):
            layer = jax.tree_util.tree_map(lambda p: p[j], stage_params)
            x = block(layer, x)
        return x

    per_stage = [stack_stage_params(
        [params[f"layer_{2 * s + j}"] for j in range(2)]) for s in range(4)]
    stacked = stack_stage_params(per_stage)

    x = jax.random.normal(jax.random.PRNGKey(3), (8, 16, config.d_model))
    mesh = _mesh(4)
    pipe_fn = make_pipeline_fn(stage_fn, mesh, num_microbatches=4)
    got = np.asarray(jax.jit(pipe_fn)(stacked, x))

    @jax.jit        # one program: op by op, the blocks are many compiles
    def sequential(params, x):
        for i in range(8):
            x = block(params[f"layer_{i}"], x)
        return x

    want = sequential(params, x)
    np.testing.assert_allclose(got, np.asarray(want), atol=1e-4, rtol=1e-4)


def test_pipelined_lm_training_matches_sequential():
    """End-to-end pipelined transformer training (embed/head outside the
    stage stack, optimizer over stage-stacked params): per-step losses and
    final weights must match unpipelined training on the same data."""
    import dataclasses

    import optax

    from elephas_tpu.models.transformer import (TransformerConfig,
                                                init_params, make_train_step)
    from elephas_tpu.parallel.pipeline import (make_pipelined_train_step,
                                               merge_transformer_stages,
                                               shard_pipelined_params,
                                               split_transformer_stages)

    config = TransformerConfig(vocab_size=32, num_layers=4, num_heads=2,
                               d_model=16, d_ff=32, max_seq_len=16,
                               dtype=jnp.float32, attention_impl="xla")
    params = init_params(config, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0,
                                config.vocab_size)

    # split BEFORE the oracle runs: the jitted steps donate their param
    # buffers, so the flat pytree is consumed by sequential training
    mesh = _mesh(2)
    pipe_params = shard_pipelined_params(
        split_transformer_stages(params, config, num_stages=2), mesh)

    # sequential oracle — deep-copied: the donating seq step would
    # otherwise delete buffers the replicated pipe params alias on CPU
    tx = optax.adam(1e-2)
    seq_params = jax.tree_util.tree_map(lambda p: jnp.array(p, copy=True),
                                        params)
    seq_opt = tx.init(seq_params)
    seq_step = make_train_step(config, tx)
    seq_losses = []
    for _ in range(4):
        seq_params, seq_opt, loss = seq_step(seq_params, seq_opt, tokens)
        seq_losses.append(float(loss))

    # pipelined: 4 layers over 2 stages, 4 microbatches
    pipe_opt = jax.jit(tx.init)(pipe_params)
    pipe_step = make_pipelined_train_step(config, tx, mesh,
                                          num_microbatches=4)
    pipe_losses = []
    for _ in range(4):
        pipe_params, pipe_opt, loss = pipe_step(pipe_params, pipe_opt,
                                                tokens)
        pipe_losses.append(float(loss))

    np.testing.assert_allclose(pipe_losses, seq_losses, atol=1e-5,
                               rtol=1e-5)
    merged = merge_transformer_stages(jax.device_get(pipe_params), config)
    for a, b in zip(jax.tree_util.tree_leaves(merged),
                    jax.tree_util.tree_leaves(seq_params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                                   rtol=1e-4)
    assert pipe_losses[-1] < pipe_losses[0]  # actually trained


def test_split_merge_roundtrip():
    from elephas_tpu.models.transformer import (TransformerConfig,
                                                init_params)
    from elephas_tpu.parallel.pipeline import (merge_transformer_stages,
                                               split_transformer_stages)

    config = TransformerConfig(vocab_size=32, num_layers=4, num_heads=2,
                               d_model=16, d_ff=32, max_seq_len=16,
                               dtype=jnp.float32)
    params = init_params(config, jax.random.PRNGKey(0))
    merged = merge_transformer_stages(
        split_transformer_stages(params, config, 2), config)
    for a, b in zip(jax.tree_util.tree_leaves(merged),
                    jax.tree_util.tree_leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    with pytest.raises(ValueError, match="split"):
        split_transformer_stages(params, config, 3)


def test_batch_not_divisible_raises():
    mesh = _mesh(4)
    stacked = stack_stage_params(
        [_stage_params(jax.random.PRNGKey(i)) for i in range(4)])
    pipe_fn = make_pipeline_fn(_stage_fn, mesh, num_microbatches=4)
    with pytest.raises(ValueError):
        pipe_fn(stacked, jnp.zeros((6, 8)))


def test_stage_count_mismatch_raises():
    mesh = _mesh(4)
    stacked = stack_stage_params(
        [_stage_params(jax.random.PRNGKey(i)) for i in range(8)])
    pipe_fn = make_pipeline_fn(_stage_fn, mesh)
    with pytest.raises(ValueError, match="stages"):
        pipe_fn(stacked, jnp.zeros((8, 8)))


def test_pipelined_remat_matches_baseline():
    """config.remat reruns each block in the backward sweep; values and
    the training trajectory must be unchanged."""
    import dataclasses

    import optax

    from elephas_tpu.models.transformer import TransformerConfig, init_params
    from elephas_tpu.parallel.pipeline import (make_pipelined_train_step,
                                               shard_pipelined_params,
                                               split_transformer_stages)

    base = TransformerConfig(vocab_size=32, num_layers=4, num_heads=2,
                             d_model=16, d_ff=32, max_seq_len=16,
                             dtype=jnp.float32, attention_impl="xla")
    mesh = Mesh(np.array(jax.devices()[:2]), ("pipe",))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 8), 0, 32)
    tx = optax.adam(1e-2)

    results = []
    for remat in (False, True):
        config = dataclasses.replace(base, remat=remat)
        params = shard_pipelined_params(
            split_transformer_stages(init_params(config,
                                                 jax.random.PRNGKey(0)),
                                     config, num_stages=2), mesh)
        opt = jax.jit(tx.init)(params)
        step = make_pipelined_train_step(config, tx, mesh,
                                         num_microbatches=2)
        losses = []
        for _ in range(3):
            params, opt, loss = step(params, opt, tokens)
            losses.append(float(loss))
        results.append(losses)
    np.testing.assert_allclose(results[0], results[1], atol=1e-5, rtol=1e-5)
    assert results[0][-1] < results[0][0]


def test_pipelined_dp_x_pp_matches_sequential_training():
    """dp x pp composition: tokens shard over 'data', stages over
    'pipe'; the optimization trajectory must match plain single-device
    training on the same global batch."""
    import dataclasses

    import optax

    from elephas_tpu.models.transformer import (TransformerConfig,
                                                init_params, make_train_step)
    from elephas_tpu.parallel.pipeline import (make_pipelined_train_step,
                                               merge_transformer_stages,
                                               shard_pipelined_params,
                                               split_transformer_stages)
    from jax.sharding import NamedSharding, PartitionSpec as P

    config = TransformerConfig(vocab_size=32, num_layers=4, num_heads=2,
                               d_model=16, d_ff=32, max_seq_len=16,
                               dtype=jnp.float32, attention_impl="xla")
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 8), 0, 32)
    tx = optax.sgd(1e-2)

    # oracle: plain unsharded training
    ref_params = init_params(config, jax.random.PRNGKey(0))
    ref_opt = tx.init(ref_params)
    ref_step = make_train_step(config, tx)
    ref_losses = []
    for _ in range(3):
        ref_params, ref_opt, loss = ref_step(ref_params, ref_opt, tokens)
        ref_losses.append(float(loss))

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("data", "pipe"))
    params = shard_pipelined_params(
        split_transformer_stages(init_params(config, jax.random.PRNGKey(0)),
                                 config, num_stages=2), mesh)
    opt = jax.jit(tx.init)(params)
    tok_sharded = jax.device_put(tokens,
                                 NamedSharding(mesh, P("data", None)))
    step = make_pipelined_train_step(config, tx, mesh, num_microbatches=2,
                                     batch_axis="data")
    losses = []
    for _ in range(3):
        params, opt, loss = step(params, opt, tok_sharded)
        losses.append(float(loss))
    np.testing.assert_allclose(losses, ref_losses, atol=1e-5, rtol=1e-5)

    merged = merge_transformer_stages(jax.device_get(params), config)
    for a, b in zip(jax.tree_util.tree_leaves(merged),
                    jax.tree_util.tree_leaves(jax.device_get(ref_params))):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)
