"""A hybrid state-space model through ``DecodeEngine``: recurrent state
per slot beside the paged K/V pool.

Toy widths of ``chipbench/families/falcon_h1.py`` (a Mamba-2 mixer beside
grouped-query attention in both blocks, every multiplier away from 1),
float32 on the CPU. The oracle is the solo greedy ``generate``, which
``tests/models/test_mamba2.py`` holds to the plain reference through the
same cache path.
"""
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.evidence import parse_prometheus
from elephas_tpu.models.transformer import (TransformerConfig, generate,
                                            init_params)
from elephas_tpu.serving_engine import DecodeEngine

REPO = Path(__file__).resolve().parent.parent
_spec = importlib.util.spec_from_file_location(
    "h1_family", REPO / "chipbench" / "families" / "falcon_h1.py")
FAMILY = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(FAMILY)
SIZES = dict(FAMILY.REHEARSE_SIZES, rms_norm_eps=1e-5, rope_theta=1e11,
             tie_word_embeddings=False, mamba_rms_norm=True,
             mamba_norm_before_gate=False, mamba_proj_bias=False,
             mamba_conv_bias=True)
NEW = 7


@pytest.fixture(scope="module")
def model():
    config = FAMILY.program_config(SIZES, max_seq_len=64,
                                   param_dtype="float32", dtype=jnp.float32)
    return FAMILY.make_params(config, 5), config


@pytest.fixture(scope="module")
def engine(model):
    """The cell's engine at toy sizes: two slots, a paged pool, prompts
    in chunks of 8. One for the module."""
    params, config = model
    return DecodeEngine(params, config, max_slots=2, max_len=64,
                        paged=(40, 4), prefill_chunk=8)


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(34)
    # 5 and 8 fit one chunk; 11, 19 and 26 cross one, two and three
    # chunk boundaries
    return [rng.integers(1, 512, n) for n in (19, 5, 26, 11, 8)]


@pytest.fixture(scope="module")
def oracle(model, prompts):
    params, config = model
    solo = jax.jit(lambda p, t: generate(p, t, NEW, config))
    return [list(np.asarray(solo(params, jnp.asarray(p)[None]))[0])
            for p in prompts]


def _counters(engine):
    return parse_prometheus(engine.registry.render())


def test_a_reused_slot_serves_what_a_fresh_engine_serves(engine, prompts,
                                                         oracle):
    """Five requests through two slots: three of them sit in a slot that
    another row retired from, its state still there, and a step was in
    flight for it when it retired. Each gets the tokens of a solo
    decode."""
    before = _counters(engine)
    assert engine._kv_cache is None              # no automatic prefix cache
    assert engine.run(prompts, max_new_tokens=NEW) == oracle
    after = _counters(engine)
    layers = engine.config.num_layers
    scanned = (after["serving_ssm_scan_tokens_total"]
               - before["serving_ssm_scan_tokens_total"])
    assert scanned == layers * sum(p.size for p in prompts)
    updates = (after["serving_ssm_row_updates_total"]
               - before["serving_ssm_row_updates_total"])
    dispatches = (after["serving_decode_steps_total"]
                  - before["serving_decode_steps_total"])
    assert 0 < updates <= 2 * layers * dispatches
    # every token but a request's first comes from a decode step
    assert updates >= layers * len(prompts) * (NEW - 1)
    # two slots x two layers x (4 heads x 16 x 16 float32 + 3 inputs of
    # the convolution, 64 + 2 x 2 x 16 wide, float32)
    assert after["serving_ssm_state_bytes"] == 2 * 2 * (4096 + 3 * 128 * 4)
    assert engine.stats["blocks_free"] == engine.stats["blocks_total"]


def test_a_row_admitted_while_others_decode_leaves_their_tokens(
        engine, prompts, oracle):
    first = engine.submit(prompts[0], NEW)
    for _ in range(3):
        engine.step()
    second = engine.submit(prompts[2], NEW)      # three chunks, mid-decode
    third = engine.submit(prompts[3], NEW)       # waits for a slot
    done = {}
    while engine.pending:
        engine.step()
        for rid in (first, second, third):
            if rid not in done and (out := engine.result(rid)) is not None:
                done[rid] = out
    assert [done[first], done[second], done[third]] == [
        oracle[0], oracle[2], oracle[3]]


def test_the_other_engine_shapes_serve_the_same_tokens(model, prompts,
                                                       oracle):
    """Interleaved prefill (the row carries its state from one engine
    iteration to the next) and the contiguous cache (state beside the
    strips, installed by the same program)."""
    params, config = model
    interleaved = DecodeEngine(params, config, max_slots=2, max_len=64,
                               paged=(40, 4), prefill_chunk=8,
                               interleave_prefill=True)
    assert interleaved.run(prompts[:4], max_new_tokens=NEW) == oracle[:4]
    assert _counters(interleaved)[
        "serving_prefill_chunks_interleaved_total"] > 0
    strips = DecodeEngine(params, config, max_slots=2, max_len=64)
    assert strips.run(prompts[:3], max_new_tokens=NEW) == oracle[:3]


@pytest.mark.parametrize("what,call", [
    ("register_prefix", lambda e: e.register_prefix([1, 2, 3])),
    ("enable_prefix_cache", lambda e: e.enable_prefix_cache()),
    ("enable_kv_spill", lambda e: e.enable_kv_spill()),
    ("enable_session_store", lambda e: e.enable_session_store()),
    ("export_prefill", lambda e: e.export_prefill([1, 2, 3])),
    ("submit_prefilled", lambda e: e.submit_prefilled([1, 2, 3], {}, 1, 4)),
    ("_preempt_slot", lambda e: e._preempt_slot(0)),
])
def test_what_moves_positions_without_their_state_is_refused_by_name(
        engine, what, call):
    with pytest.raises(ValueError, match=what) as err:
        call(engine)
    assert "snapshot of the recurrent state" in str(err.value)


def test_a_draft_model_and_an_explicit_prefix_cache_are_refused(model):
    params, config = model
    with pytest.raises(ValueError, match="draft model"):
        DecodeEngine(params, config, max_slots=2, max_len=64,
                     draft_params=params, draft_config=config)
    with pytest.raises(ValueError, match="prefix cache"):
        DecodeEngine(params, config, max_slots=2, max_len=64,
                     paged=(40, 4), prefix_cache=True)


def test_an_engine_without_a_mixer_counts_no_state():
    config = TransformerConfig(vocab_size=64, num_layers=1, num_heads=2,
                               d_model=16, d_ff=32, max_seq_len=32,
                               dtype=jnp.float32)
    engine = DecodeEngine(init_params(config, jax.random.PRNGKey(0)), config,
                          max_slots=2, paged=(16, 4), prefill_chunk=8)
    assert engine._kv_cache is not None          # the default stays on
    assert engine.run([[1, 2, 3]], max_new_tokens=3)
    series = _counters(engine)
    assert series["serving_ssm_row_updates_total"] == 0
    assert series["serving_ssm_scan_tokens_total"] == 0
    assert series["serving_ssm_state_bytes"] == 0
