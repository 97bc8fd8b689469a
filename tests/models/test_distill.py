"""Draft distillation: KL training against a frozen target must raise
speculative-decoding acceptance — the end-to-end point of the module."""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from elephas_tpu.models.distill import distill_loss, make_distill_step
from elephas_tpu.models.speculative import speculative_generate
from elephas_tpu.models.transformer import (TransformerConfig, init_params,
                                            make_train_step)
from elephas_tpu.utils.text import ByteTokenizer

# compiled whole: op by op, two forwards are a hundred small compiles
DISTILL_LOSS = jax.jit(distill_loss, static_argnums=(3, 4),
                       static_argnames=("temperature", "hard_weight"))


@pytest.fixture(scope="module")
def trained_target():
    tok = ByteTokenizer()
    config = TransformerConfig(vocab_size=tok.vocab_size, num_layers=2,
                               num_heads=4, d_model=48, d_ff=96,
                               max_seq_len=64, dtype=jnp.float32)
    rows = tok.corpus_to_sequences(["abcdabcdabcd " * 6] * 8, seq_len=32)
    params = init_params(config, jax.random.PRNGKey(0))
    tx = optax.adam(1e-2)
    opt = tx.init(params)
    step = make_train_step(config, tx)
    for _ in range(30):
        params, opt, _ = step(params, opt, jnp.asarray(rows))
    return params, config, jnp.asarray(rows), tok


def _draft_config(tok):
    return TransformerConfig(vocab_size=tok.vocab_size, num_layers=1,
                             num_heads=2, d_model=24, d_ff=48,
                             max_seq_len=64, dtype=jnp.float32)


@pytest.fixture(scope="module")
def distilled(trained_target):
    """One distillation of 120 steps, which both tests below read: the
    untrained draft, the distilled one, and the loss at each step."""
    params, config, rows, tok = trained_target
    dcfg = _draft_config(tok)
    draft0 = init_params(dcfg, jax.random.PRNGKey(5))
    tx = optax.adam(3e-3)
    opt = tx.init(draft0)
    step = make_distill_step(dcfg, config, tx, temperature=2.0,
                             hard_weight=0.1)
    draft, losses = draft0, []
    for _ in range(120):
        draft, opt, loss = step(draft, params, opt, rows)
        losses.append(loss)
    return dcfg, draft0, draft, [float(loss) for loss in losses]


def test_distill_loss_decreases(distilled):
    *_, losses = distilled
    first, last = losses[0], losses[59]      # over the first 60 steps
    assert np.isfinite(last) and last < first * 0.7, (first, last)


def test_distilled_draft_raises_acceptance(trained_target, distilled):
    """The reason this module exists: on the same prompts, the distilled
    draft's speculative acceptance beats the undistilled one's, and the
    output stays exactly the target's greedy decode either way."""
    params, config, rows, _ = trained_target
    dcfg, draft0, draft, _ = distilled
    prompt = np.asarray(rows[:4, :8])
    out0, stats0 = speculative_generate(
        params, draft0, prompt, 16, config, dcfg, gamma=4,
        return_stats=True)
    out1, stats1 = speculative_generate(
        params, draft, prompt, 16, config, dcfg, gamma=4,
        return_stats=True)
    np.testing.assert_array_equal(np.asarray(out0), np.asarray(out1))
    assert stats1["draft_acceptance"] > stats0["draft_acceptance"] + 0.15, (
        stats0, stats1)
    assert stats1["rounds"] < stats0["rounds"], (stats0, stats1)


def test_hard_weight_zero_pure_kl(trained_target):
    params, config, rows, tok = trained_target
    dcfg = _draft_config(tok)
    draft = init_params(dcfg, jax.random.PRNGKey(6))
    l0 = float(DISTILL_LOSS(draft, params, rows, dcfg, config))
    l_hard = float(DISTILL_LOSS(draft, params, rows, dcfg, config,
                                hard_weight=0.5))
    assert np.isfinite(l0) and l_hard > l0  # CE term adds mass
