"""A prefill chunk attends over the narrowest width of a ladder that
covers the positions its row holds (``decode_block``'s
``attend_widths``), not over the row's whole length: every width that
covers ``pos0 + S`` must give the full-length program's logits and
cache, the device must pick the narrowest one, and the engine's chunked
admission must serve the tokens it served before."""
import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elephas_tpu import serving_engine
from elephas_tpu.models.transformer import (TransformerConfig, attend_width,
                                            decode_block, init_kv_cache,
                                            init_params, prefill_ladder)
from elephas_tpu.serving_engine import DecodeEngine

from .._greedy import greedy_reference

REPO = Path(__file__).resolve().parent.parent.parent
MAX_LEN = 64
CHUNK = 8
LADDER = (8, 16, 32, 64)
S = 5                   # block length of the decode_block cases
VOCAB = 64


def _latent_config():
    """DeepSeek-V2's layers at the benchmark's rehearsal widths: MLA,
    YaRN, a dense layer and two expert layers."""
    path = REPO / "chipbench" / "families" / "deepseek_v2.py"
    spec = importlib.util.spec_from_file_location("widths_dsv2", path)
    family = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(family)
    with open(REPO / "chipbench" / "configs" /
              "deepseek-v2-l5-e40-serve.json") as fh:
        sizes = family.model_sizes(json.load(fh), True)
    sizes = dict(sizes, vocab_size=VOCAB)
    return family.program_config(sizes, MAX_LEN, "float32",
                                 dtype=jnp.float32)


CONFIGS = {
    "gqa-window-rope": lambda: TransformerConfig(
        vocab_size=VOCAB, num_layers=2, num_heads=8, num_kv_heads=2,
        d_model=32, d_ff=64, max_seq_len=MAX_LEN, positional="rope",
        attention_window=11, dtype=jnp.float32),
    "mla": _latent_config,
}


def test_the_ladder_comes_from_the_shapes():
    assert prefill_ladder(CHUNK, MAX_LEN) == LADDER
    assert prefill_ladder(512, 2048) == (512, 1024, 2048)
    assert prefill_ladder(512, 4096) == (512, 1024, 2048, 4096)
    assert prefill_ladder(16, 24) == (16, 24)
    assert prefill_ladder(64, 24) == (24,)
    assert [attend_width(LADDER, n) for n in (1, 8, 9, 33, 64)] == \
        [8, 8, 16, 64, 64]


@pytest.fixture(scope="module", params=sorted(CONFIGS))
def programs(request):
    """Per configuration: parameters, a row cache full of finite
    garbage, and the block program jitted twice, over the whole row
    (what ran before) and over the ladder; ``pos0`` is traced, so one
    program serves every offset."""
    config = CONFIGS[request.param]()
    params = init_params(config, jax.random.PRNGKey(3))
    rng = np.random.default_rng(17)

    def garbage(batch):
        return jax.tree_util.tree_map(
            lambda a: jnp.asarray(rng.normal(0, 3.0, a.shape), a.dtype),
            init_kv_cache(config, batch, MAX_LEN))

    def program(widths):
        return jax.jit(lambda cache, tokens, pos0: decode_block(
            params, cache, tokens, pos0, config, attend_widths=widths))

    return config, params, garbage, program(()), program(LADDER), rng


def _poison(cache, start):
    """NaN at every cached position from ``start`` on: a program that
    reads one of them returns NaN (0 x NaN)."""
    return jax.tree_util.tree_map(
        lambda a: a.at[:, :, start:].set(jnp.nan), cache)


def _assert_same(got, want, width):
    """Logits, and the cache before ``width`` (a poisoned tail beyond it
    is never written)."""
    (g_logits, g_cache), (w_logits, w_cache) = got, want
    assert np.isfinite(np.asarray(g_logits)).all()
    np.testing.assert_allclose(np.asarray(g_logits), np.asarray(w_logits),
                               atol=2e-5)
    for g, w in zip(jax.tree_util.tree_leaves(g_cache),
                    jax.tree_util.tree_leaves(w_cache)):
        np.testing.assert_allclose(np.asarray(g[:, :, :width]),
                                   np.asarray(w[:, :, :width]), atol=2e-5)


EDGES = [(rung, need) for rung in LADDER
         for need in (rung - 1, rung, rung + 1) if need <= MAX_LEN]


@pytest.mark.parametrize("rung,need", EDGES)
def test_every_rung_gives_the_full_length_result(programs, rung, need):
    """A block ending at ``need - 1``: one under a rung, on it and one
    over it. The ladder program must (a) equal the full-length program,
    logits and written cache, and (b) have read nothing beyond the
    narrowest rung that covers ``need`` -- those positions hold NaN."""
    _, _, garbage, full, laddered, rng = programs
    width = attend_width(LADDER, need)
    assert width == (rung if need <= rung else 2 * rung)
    cache = garbage(1)
    tokens = jnp.asarray(rng.integers(1, VOCAB, (1, S)), jnp.int32)
    pos0 = jnp.int32(need - S)
    want = full(cache, tokens, pos0)
    _assert_same(laddered(_poison(cache, width), tokens, pos0), want, width)


@pytest.mark.parametrize("offsets", [(0, 3, 2), (11, 1, 6), (4, 27, 12),
                                     (59, 0, 33)])
def test_a_vector_of_offsets_takes_the_rung_of_the_largest(programs,
                                                           offsets):
    _, _, garbage, full, laddered, rng = programs
    width = attend_width(LADDER, max(offsets) + S)
    cache = garbage(len(offsets))
    tokens = jnp.asarray(rng.integers(1, VOCAB, (len(offsets), S)),
                         jnp.int32)
    pos0 = jnp.asarray(offsets, jnp.int32)
    want = full(cache, tokens, pos0)
    _assert_same(laddered(_poison(cache, width), tokens, pos0), want, width)


def test_widths_must_ascend_to_the_rows_length(programs):
    config, params, garbage, _, _, _ = programs
    tokens = jnp.ones((1, S), jnp.int32)
    for bad in ((16, 8, MAX_LEN), (8, 16), (8, 8, MAX_LEN),
                (8, 2 * MAX_LEN)):
        with pytest.raises(ValueError, match="attend_widths"):
            decode_block(params, garbage(1), tokens, 0, config,
                         attend_widths=bad)


# ------------------------------------------------------------ the engine
#: prompts of 1 to 4 whole chunks, each with a tail, and a tail alone
PROMPT_LENGTHS = (3, CHUNK + 3, 2 * CHUNK + 5, 3 * CHUNK + 2, 4 * CHUNK + 1)
NEW_TOKENS = 6


def _engine(params, config):
    return DecodeEngine(params, config, max_slots=2, max_len=MAX_LEN,
                        paged=(48, 4), prefill_chunk=CHUNK)


def _counters(engine):
    out = {}
    for line in engine.registry.render().splitlines():
        if line.startswith("serving_prefill_"):
            name, value = line.rsplit(" ", 1)
            out[name] = float(value)
    return out


@pytest.fixture(scope="module", params=sorted(CONFIGS))
def served(request):
    """Per configuration: the prompts, what the engine serves with the
    ladder, and what it served before -- the same engine built with a
    ladder of one rung, the row's length, which is the old program."""
    config = CONFIGS[request.param]()
    params = init_params(config, jax.random.PRNGKey(5))
    rng = np.random.default_rng(23)
    prompts = [rng.integers(1, VOCAB, n) for n in PROMPT_LENGTHS]
    engine = _engine(params, config)
    tokens = engine.run(prompts, max_new_tokens=NEW_TOKENS)
    patch = pytest.MonkeyPatch()
    patch.setattr(serving_engine, "prefill_ladder",
                  lambda chunk, length: (length,))
    try:
        before = _engine(params, config)
    finally:
        patch.undo()
    assert before._prefill_ladder == (MAX_LEN,)
    return (config, params, prompts, engine, tokens,
            before.run(prompts, max_new_tokens=NEW_TOKENS), before)


@pytest.mark.parametrize("which", range(len(PROMPT_LENGTHS)))
def test_the_engine_serves_what_it_served_before(served, which):
    config, params, prompts, _, tokens, before, _ = served
    assert tokens[which] == before[which]
    assert tokens[which] == greedy_reference(params, config, prompts[which],
                                             NEW_TOKENS)


def test_the_counters_count_what_the_chunks_read(served):
    _, _, _, engine, _, _, before = served
    assert engine._prefill_ladder == LADDER
    chunks = [(start, min(CHUNK, n - start)) for n in PROMPT_LENGTHS
              for start in range(0, n, CHUNK)]
    held = sum(pos + s for pos, s in chunks)
    read = [attend_width(LADDER, pos + s) for pos, s in chunks]
    got = _counters(engine)
    assert got["serving_prefill_positions_held_total"] == held
    assert got["serving_prefill_positions_read_total"] == sum(read)
    assert held <= sum(read) <= MAX_LEN * len(chunks)
    by_width = {w: got.get(f'serving_prefill_chunks_total{{width="{w}"}}',
                           0.0) for w in LADDER}
    assert by_width == {w: float(read.count(w)) for w in LADDER}
    assert sum(by_width.values()) == len(chunks)       # a step a chunk
    # the old program read the whole row for every chunk
    old = _counters(before)
    assert old["serving_prefill_positions_held_total"] == held
    assert old["serving_prefill_positions_read_total"] == \
        MAX_LEN * len(chunks)


def test_one_program_a_suffix_shape(served):
    """The ladder lives inside the program: the admission programs are
    still one a suffix shape, whatever widths their chunks ran at."""
    _, _, _, engine, _, _, before = served
    shapes = {min(CHUNK, n - start) for n in PROMPT_LENGTHS
              for start in range(0, n, CHUNK)}
    for eng in (engine, before):
        assert (eng._extend_fn._cache_size()
                + eng._extend_owned_fn._cache_size()) == len(shapes)
