"""BERT encoder / MLM tests: masking recipe, training, padding
semantics, sharded parity."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from elephas_tpu.models.bert import (BertConfig, encode, init_params,
                                     make_mlm_train_step, mask_tokens,
                                     mlm_loss, param_specs, pool,
                                     shard_params)

# compiled whole, once a shape and config: op by op, a pass is a
# hundred small compiles
ENCODE = jax.jit(encode, static_argnames="config")
MLM_LOSS = jax.jit(mlm_loss, static_argnums=5)


def _config(**kw):
    base = dict(vocab_size=64, num_layers=2, num_heads=4, d_model=32,
                d_ff=64, max_seq_len=32, mask_token_id=3, pad_token_id=0,
                max_predictions=8, dtype=jnp.float32)
    base.update(kw)
    return BertConfig(**base)


def _tokens(n=8, t=16, seed=1, config=None):
    c = config or _config()
    rng = np.random.default_rng(seed)
    # ids >= 4 are "real" tokens; 0 pad, 3 mask
    x = rng.integers(4, c.vocab_size, size=(n, t))
    return jnp.asarray(x.astype("int32"))


def test_encode_shapes_and_pooler():
    config = _config()
    params = init_params(config, jax.random.PRNGKey(0))
    tokens = _tokens()
    hidden = ENCODE(params, tokens, config=config)
    assert hidden.shape == (8, 16, 32)
    pooled = pool(params, hidden, config)
    assert pooled.shape == (8, 32)
    assert np.all(np.abs(np.asarray(pooled)) <= 1.0)  # tanh range


def test_padding_does_not_leak_into_real_positions():
    """Changing content under the pad mask must not change real
    positions' hidden states (the mask actually works)."""
    config = _config()
    params = init_params(config, jax.random.PRNGKey(0))
    tokens = np.asarray(_tokens(2, 12))
    padded = tokens.copy()
    padded[:, 8:] = config.pad_token_id
    h1 = np.asarray(ENCODE(params, jnp.asarray(padded), config=config))
    # same prefix, garbage AT pad positions' embeddings can't be changed
    # via tokens (pad id is fixed), so instead: extending the pad run
    # with different *lengths* must keep the shared real prefix equal
    padded2 = tokens.copy()
    padded2[:, 8:] = config.pad_token_id
    padded2[:, 11] = config.pad_token_id  # same — sanity
    h2 = np.asarray(ENCODE(params, jnp.asarray(padded2), config=config))
    np.testing.assert_allclose(h1[:, :8], h2[:, :8], atol=1e-6)
    # and a genuinely different suffix BEHIND the mask: replace pad ids
    # with other tokens but mask them out via a shorter sequence compare
    short = np.asarray(ENCODE(params, jnp.asarray(padded[:, :8]),
                              config=config))
    np.testing.assert_allclose(h1[:, :8], short, atol=1e-4)


def test_mask_tokens_recipe():
    config = _config()
    tokens = _tokens(16, 32)
    masked, positions, weights = mask_tokens(tokens, jax.random.PRNGKey(0),
                                             config, mask_rate=0.15)
    assert masked.shape == tokens.shape
    assert positions.shape == (16, 8) and weights.shape == (16, 8)
    t, m, w = (np.asarray(tokens), np.asarray(masked), np.asarray(weights))
    pos = np.asarray(positions)
    # unchosen positions are untouched
    changed = (t != m)
    for b in range(16):
        assert set(np.flatnonzero(changed[b])) <= set(pos[b][w[b] > 0])
    # roughly 15% masked, mostly [MASK] tokens
    frac = w.sum() / t.size
    assert 0.05 < frac < 0.3, frac
    mask_frac = (m[changed] == config.mask_token_id).mean() if changed.any() else 0
    assert mask_frac > 0.5


def test_mlm_training_decreases_loss():
    config = _config()
    params = init_params(config, jax.random.PRNGKey(0))
    tokens = _tokens(16, 16)
    tx = optax.adam(1e-2)
    opt = tx.init(params)
    step = make_mlm_train_step(config, tx)
    losses = []
    for i in range(12):
        params, opt, loss = step(params, opt, tokens,
                                 jax.random.PRNGKey(100 + i))
        losses.append(float(loss))
    assert np.isfinite(losses[-1])
    assert np.mean(losses[-3:]) < np.mean(losses[:3])


def test_mlm_sharded_matches_unsharded():
    config = _config()
    params = init_params(config, jax.random.PRNGKey(0))
    tokens = _tokens(8, 16)
    masked, positions, weights = mask_tokens(tokens, jax.random.PRNGKey(5),
                                             config)
    labels = jax.vmap(jnp.take)(tokens, positions)
    ref = float(MLM_LOSS(params, masked, positions, labels, weights,
                         config))

    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))
    sp = shard_params(params, config, mesh)
    sharded_inputs = [jax.device_put(a, NamedSharding(
        mesh, P("data", *([None] * (a.ndim - 1)))))
        for a in (masked, positions, labels, weights)]
    got = float(jax.jit(lambda p, m, po, l, w: mlm_loss(
        p, m, po, l, w, config))(sp, *sharded_inputs))
    np.testing.assert_allclose(got, ref, atol=2e-4, rtol=2e-4)


def test_config_validation_and_specs_structure():
    with pytest.raises(ValueError):
        _config(num_heads=5)
    with pytest.raises(ValueError):
        _config(num_kv_heads=3)
    config = _config(num_kv_heads=2)
    params = init_params(config, jax.random.PRNGKey(0))
    jax.tree_util.tree_map(lambda p, s: None, params, param_specs(config))
    assert params["layer_0"]["attn"]["wk"].shape == (32, 2, 8)


@pytest.mark.parametrize("freeze", [False, True])
def test_bert_classification_finetune(freeze):
    """Fine-tune (or linear-probe) a classifier head: loss drops and, in
    the frozen case, the encoder is bit-identical afterwards."""
    from elephas_tpu.models.bert import (classify, init_classifier_head,
                                         make_classifier_train_step)

    config = _config()
    params = init_params(config, jax.random.PRNGKey(0))
    head = init_classifier_head(config, 3, jax.random.PRNGKey(1))
    frozen_copy = jax.tree_util.tree_map(lambda x: np.asarray(x).copy(),
                                         params)
    # task: class = first token id modulo 3 (CLS can attend to it)
    tokens = _tokens(32, 12)
    labels = jnp.asarray(np.asarray(tokens)[:, 0] % 3, dtype=jnp.int32)

    tx = optax.adam(5e-3)
    state = {"params": params, "head": head}
    opt = tx.init({"head": head} if freeze else state)
    step = make_classifier_train_step(config, tx, freeze_encoder=freeze)
    first = None
    for _ in range(15):
        state, opt, loss = step(state, opt, tokens, labels)
        if first is None:
            first = float(loss)
    assert np.isfinite(float(loss)) and float(loss) < first
    if freeze:
        for a, b in zip(jax.tree_util.tree_leaves(state["params"]),
                        jax.tree_util.tree_leaves(frozen_copy)):
            np.testing.assert_array_equal(np.asarray(a), b)
    logits = classify(state["params"], state["head"], tokens, config)
    assert logits.shape == (32, 3)


def test_bert_dropout_training_and_deterministic_inference():
    config = _config(dropout_rate=0.1)
    params = init_params(config, jax.random.PRNGKey(0))
    tokens = _tokens(8, 12)
    a = np.asarray(ENCODE(params, tokens, config=config))
    b = np.asarray(ENCODE(params, tokens, config=config))
    np.testing.assert_array_equal(a, b)
    d = np.asarray(ENCODE(params, tokens, config=config,
                          dropout_key=jax.random.PRNGKey(1)))
    assert np.abs(d - a).max() > 1e-6
    tx = optax.adam(1e-3)
    opt = tx.init(params)
    from elephas_tpu.models.bert import make_mlm_train_step
    step = make_mlm_train_step(config, tx)
    params, opt, loss = step(params, opt, _tokens(8, 12),
                             jax.random.PRNGKey(5))
    assert np.isfinite(float(loss))
