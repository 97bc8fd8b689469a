"""The plain reference against the program at toy widths on the CPU, for
the Mistral-shaped branches: GQA, RoPE, RMSNorm, SiLU gate, a window
shorter than the sequence, an untied head."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check
from chipbench.spec import Spec

from ._util import REPO, json_lines

#: float32 against float32 at "highest" precision: only the order of
#: summation differs (observed 4e-6 on logits of magnitude 4)
F32_ATOL = 5e-5
#: the program's bf16 compute against the float32 reference at toy width:
#: observed 0.06-0.11 (bf16 has 8 bits of mantissa; logits of magnitude 4
#: through two gated blocks). A wrong mask or rotation moves logits by
#: more than 1.
BF16_ATOL = 0.3


@pytest.fixture(scope="module")
def setup():
    spec = Spec(str(REPO / "BENCHMARK.json"))
    family = spec.load_module("families", "dense_decoder")
    reference = spec.load_module("reference", "dense_decoder")
    cfg = spec.config("mistral-7b-l16-serve")
    sizes = dict(family.model_sizes(cfg, True), sliding_window=24)
    config = family.program_config(sizes, max_seq_len=64,
                                   param_dtype="float32")
    assert (config.num_kv_heads, config.positional, config.norm,
            config.mlp_variant, config.tied_embedding,
            config.attention_window) == (2, "rope", "rmsnorm", "swiglu",
                                         False, 24)
    params = family.make_params(config, 2**31 + 1)
    weights = family.to_reference(params, config)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 48), 1,
                                sizes["vocab_size"])
    return family, reference, sizes, config, params, weights, tokens


def test_forward_logits_match_the_program_in_float32(setup):
    from elephas_tpu.models.transformer import forward

    _, reference, sizes, config, params, weights, tokens = setup
    exact = dataclasses.replace(config, dtype=jnp.float32,
                                attention_impl="xla")
    with jax.default_matmul_precision("highest"):
        got = forward(params, tokens, exact)
    want = reference.forward(weights, tokens, sizes)
    assert float(jnp.abs(got - want).max()) < F32_ATOL
    # the window binds: a reference without it differs
    wide = reference.forward(weights, tokens,
                             dict(sizes, sliding_window=None))
    assert float(jnp.abs(wide - want).max()) > 100 * F32_ATOL


def test_lm_loss_matches_the_program(setup):
    from elephas_tpu.models.transformer import lm_loss

    _, reference, sizes, config, params, weights, tokens = setup
    exact = dataclasses.replace(config, dtype=jnp.float32,
                                attention_impl="xla")
    with jax.default_matmul_precision("highest"):
        got = float(lm_loss(params, tokens, exact))
    want = float(reference.loss(weights, tokens, sizes))
    assert abs(got - want) / want < 1e-5
    # bf16 compute where f32 is stated shows: ~1e-4 relative or more
    assert abs(float(lm_loss(params, tokens, config)) - want) / want > 2e-6


def test_prefill_and_paged_decode_through_the_cache(setup):
    _, reference, sizes, config, params, weights, _ = setup

    def ref_logits(rows):
        return np.asarray(reference.forward(weights, jnp.asarray(rows),
                                            sizes))

    # 40 cached positions: past the 24-position window, mid-block
    diff = check.paged_step_vs_reference(params, config, ref_logits,
                                         rows=3, cached=40, block_size=8,
                                         seed=3)
    assert diff < BF16_ATOL
    exact = dataclasses.replace(config, dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        diff32 = check.paged_step_vs_reference(
            params, exact, ref_logits, rows=3, cached=40, block_size=8,
            seed=3)
    assert diff32 < F32_ATOL


def test_logit_margin_is_zero_for_the_argmax_and_positive_otherwise(setup):
    _, reference, sizes, _, _, weights, _ = setup

    def ref_logits(rows):
        return np.asarray(reference.forward(weights, jnp.asarray(rows),
                                            sizes))

    prompt = [5, 9, 17, 3]
    row = np.zeros((1, 16), np.int32)
    row[0, :4] = prompt
    best = int(ref_logits(row)[0, 3].argmax())
    assert check.logit_margins(ref_logits, [prompt], [[best]], 16) == [0.0]
    other = (best + 1) % sizes["vocab_size"]
    assert check.logit_margins(ref_logits, [prompt], [[other]], 16)[0] > 0


def test_losses_ok():
    assert check.losses_ok([3.0, 2.5, 2.0])
    assert not check.losses_ok([3.0, 3.5])
    assert not check.losses_ok([3.0, float("nan"), 2.0])


# ----------------------------------------------------------- the control
CONTROL_SEEDS = (2**31 + 41, 2**31 + 42, 7)


@pytest.fixture(scope="module")
def control():
    """``chipbench/tools/control.py`` at the rehearsal's widths: the
    plain reference with every matrix rounded through float8, the
    nearest precision below the configuration's bfloat16, in the
    program's place (on the chip at the cell's own size: PERF.md
    section 4)."""
    import os
    import subprocess
    import sys

    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, str(REPO / "chipbench" / "tools" / "control.py"),
         "--workload", "mistral7b-chat-steady", "--rehearse", "--seeds",
         ",".join(str(s) for s in CONTROL_SEEDS)],
        capture_output=True, text=True, env=env, cwd=str(REPO), timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    import json
    return {row["seed"]: row for row in map(json.loads,
                                            json_lines(proc.stdout))}


@pytest.mark.parametrize("seed", CONTROL_SEEDS)
def test_the_float8_control_is_not_correct(control, seed):
    """The control breaks both of the cell's limits, each by three
    times or more of what the bf16 program reads under them (0.06-0.11
    at these widths: ``BF16_ATOL``'s note)."""
    row = control[seed]
    assert {"step_max_dlogit", "token_worst_below_best"} <= set(row["fails"])
    assert row["step_max_dlogit"] > 3 * 0.11
    assert row["token_worst_below_best"] > row["limits"][
        "token_worst_below_best"]
    assert row["positions"] >= 300
