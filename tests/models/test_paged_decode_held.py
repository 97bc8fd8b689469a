"""The S=1 paged decode step attends over the flat list of the blocks
its rows hold (``held_blocks`` wide), not over every row's whole table:
its logits must equal the contiguous ``decode_step``'s for ragged rows
under every attention variant, whatever the padding reads, and the
engine's one step program must pick a wide-enough branch of its ladder."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elephas_tpu.models.paged_decode import (held_block_count,
                                             init_paged_pool,
                                             install_row_paged)
from elephas_tpu.models.transformer import TransformerConfig, init_params
from elephas_tpu.serving_engine import DecodeEngine

from ._transformer_util import DECODE_PAGED, DECODE_STEP, PREFILL

BS = 4                  # block size
MAX_LEN = 32            # 8 table entries a row
# cached positions per row: 0 is an INACTIVE row (pos 0, zero table);
# the others leave the step at pos 1, bs-1, bs, bs+1 and several blocks
CACHED = (1, BS - 1, 0, BS, BS + 1, 3 * BS + 2, 7 * BS + 1)

VARIANTS = {
    "rope-g1": dict(positional="rope"),
    "rope-g4": dict(positional="rope", num_heads=8, num_kv_heads=2),
    "alibi-g1": dict(positional="alibi"),
    "alibi-g4": dict(positional="alibi", num_heads=8, num_kv_heads=2),
    "window-g1": dict(positional="rope", attention_window=6),
    "window-g4": dict(positional="alibi", attention_window=9, num_heads=8,
                      num_kv_heads=2),
}


def _config(**overrides):
    base = dict(vocab_size=64, num_layers=2, num_heads=4, d_model=32,
                d_ff=64, max_seq_len=MAX_LEN, dtype=jnp.float32)
    base.update(overrides)
    return TransformerConfig(**base)


@pytest.fixture(scope="module", params=sorted(VARIANTS))
def ragged(request):
    """Per variant: parameters, a pool holding ragged rows (filled with
    garbage everywhere a row does not own, scratch block 0 included),
    their tables, and the contiguous ``decode_step``'s logits."""
    config = _config(**VARIANTS[request.param])
    params = init_params(config, jax.random.PRNGKey(7))
    rng = np.random.default_rng(11)
    rows, mb = len(CACHED), MAX_LEN // BS
    prompts = rng.integers(1, 64, (rows, MAX_LEN - 1)).astype(np.int32)
    pool = init_paged_pool(config, 1 + rows * mb, BS)
    pool = jax.tree_util.tree_map(
        lambda a: jnp.asarray(rng.normal(0, 50.0, a.shape), a.dtype), pool)
    tables = np.zeros((rows, mb), np.int32)
    want = np.zeros((rows, 64), np.float32)
    last = np.zeros(rows, np.int32)
    pos = np.zeros(rows, np.int32)
    # one prefill of every row's whole prompt, compiled once: the model
    # is causal, so a row's cache below position n is its n-token
    # prefill's, and the step at n writes position n and reads no later
    _, caches = PREFILL(params, jnp.asarray(prompts), config, MAX_LEN)
    for r, n in enumerate(CACHED):
        if n == 0:
            continue
        cache = jax.tree_util.tree_map(lambda a: a[r:r + 1], caches)
        need = n // BS + 1
        # interleaved ids: a row's blocks are not adjacent in the pool
        tables[r, :need] = 1 + r + rows * np.arange(need)
        pool = install_row_paged(pool, cache, tables[r], need)
        last[r], pos[r] = prompts[r, n], n
        logits, _ = DECODE_STEP(params, cache, jnp.asarray(last[r:r + 1]),
                                jnp.asarray(pos[r:r + 1]), config)
        want[r] = np.asarray(logits[0])
    return config, params, pool, tables, last, pos, want


def _held(config, pos):
    return held_block_count(pos, BS, MAX_LEN // BS, config.attention_window)


@pytest.mark.parametrize("width", ["exact", "padded", "ladder-mid",
                                   "ladder-top", None])
def test_held_step_matches_contiguous_step(ragged, width):
    config, params, pool, tables, last, pos, want = ragged
    total = _held(config, pos)
    # a ladder's narrower widths do not cover these rows: the step has
    # to pick, on the device, the first that does
    held = {"exact": total, "padded": total + 5,
            "ladder-mid": (2, total - 1, total, total + 8),
            "ladder-top": (total - 2, total + 6), None: None}[width]
    got, _ = DECODE_PAGED(params, pool, jnp.asarray(tables),
                          jnp.asarray(last), jnp.asarray(pos), config,
                          held_blocks=held)
    got = np.asarray(got)
    assert np.isfinite(got).all()
    active = np.asarray(CACHED) > 0
    np.testing.assert_allclose(got[active], want[active], rtol=2e-4,
                               atol=2e-4)


def test_padding_slots_never_change_a_logit(ragged):
    """Two pools that differ only in scratch block 0 and in blocks no
    row owns give the same logits at every width."""
    config, params, pool, tables, last, pos, _ = ragged
    owned = np.unique(tables)
    owned = owned[owned > 0]
    rng = np.random.default_rng(12)

    def scribble(a):
        noise = jnp.asarray(rng.normal(0, 1e3, a.shape), a.dtype)
        return noise.at[owned].set(a[owned])

    other = jax.tree_util.tree_map(scribble, pool)
    total = _held(config, pos)
    active = np.asarray(CACHED) > 0
    for held in (total, total + 9, (total - 1, total + 3), None):
        a, b = (np.asarray(DECODE_PAGED(
            params, pl, jnp.asarray(tables), jnp.asarray(last),
            jnp.asarray(pos), config, held_blocks=held)[0])
            for pl in (pool, other))
        np.testing.assert_array_equal(a[active], b[active])


@pytest.mark.parametrize("pos, window, want", [
    ([0, 0, 0], None, 3),                 # idle rows: one scratch block each
    ([1, BS - 1, BS, BS + 1], None, 1 + 1 + 2 + 2),
    ([MAX_LEN - 1], None, MAX_LEN // BS),
    ([MAX_LEN + 7], None, MAX_LEN // BS),  # a retired row's surplus steps
    ([9], 6, 2),                          # window 4..9: blocks 1 and 2
    ([9], 2, 1),                          # window 8..9: block 2
    ([11], 5, 2),                         # window 7..11: blocks 1 and 2
])
def test_held_block_count(pos, window, want):
    assert held_block_count(np.asarray(pos), BS, MAX_LEN // BS,
                            window) == want


# ------------------------------------------------------------------ engine
@pytest.fixture(scope="module")
def model():
    config = _config(max_seq_len=64)
    return init_params(config, jax.random.PRNGKey(0)), config


def _widths(engine):
    fam = engine.registry.get("serving_decode_steps_total")
    return {int(key[0]): child.value for key, child in fam.series().items()}


def test_ladder_comes_from_the_shapes(model):
    params, config = model
    eng = DecodeEngine(params, config, max_slots=4, max_len=64,
                       paged=(40, 4))
    # 16 table entries a row: doubling up to 4 x 16, at most 6 widths,
    # none narrower than the batch (every row holds at least one block)
    assert eng._held_ladder == (4, 8, 16, 32, 64)
    wide = DecodeEngine(params, config, max_slots=2, max_len=64,
                        paged=(40, 1))
    assert wide._held_ladder == (4, 8, 16, 32, 64, 128)
    assert DecodeEngine(params, config, max_slots=3, max_len=8,
                        paged=(9, 4))._held_ladder == (3, 6)


@pytest.mark.parametrize("prefill_chunk", [None, 4])
def test_engine_crosses_widths_without_compiling(model, prefill_chunk):
    """Rows that grow across two ladder widths: the contiguous engine's
    tokens, no compile after ``warmup()``, and counters that add up
    (with ``prefill_chunk``: the benchmark's own configuration)."""
    params, config = model
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 64, n) for n in (3, 9, 5, 14)]
    plain = DecodeEngine(params, config, max_slots=4, max_len=64)
    expected = plain.run(prompts, max_new_tokens=30)

    eng = DecodeEngine(params, config, max_slots=4, max_len=64,
                       paged=(64, 4), prefill_chunk=prefill_chunk)
    eng.warmup(prompt_lengths=sorted({len(p) for p in prompts}))
    reg = eng.registry
    compiles = reg.get("serving_jit_compiles_total").value
    # every width's series exists, and the warm-up counted no step
    assert _widths(eng) == dict.fromkeys(eng._held_ladder, 0)
    assert eng.run(prompts, max_new_tokens=30) == expected
    assert reg.get("serving_jit_compiles_total").value == compiles

    used = _widths(eng)
    assert sum(1 for n in used.values() if n) >= 2
    assert sum(used.values()) == reg.get("serving_steps_total").value
    held = reg.get("serving_decode_blocks_held_total").value
    read = reg.get("serving_decode_blocks_read_total").value
    assert 0 < held <= read
    assert read == sum(w * n for w, n in used.items())


def test_width_below_what_the_rows_hold_is_refused(model):
    params, config = model
    eng = DecodeEngine(params, config, max_slots=2, max_len=64,
                       paged=(40, 4))
    assert eng._held_width(np.asarray([0, 0])) == (2, 2)
    assert eng._held_width(np.asarray([17, 3])) == (6, 8)
    assert eng._held_width(np.asarray([63, 63])) == (32, 32)
    with pytest.raises(AssertionError):
        eng._held_width(np.asarray([63, 63, 63]))
