"""An admission dispatches its chunks, one fresh row, one install and one
sampler: nothing on the path runs op by op.

The count is taken from a CPU ``jax.profiler`` trace: every execution of
a compiled program is one ``PjRtCpuExecutable::Execute`` event on the
host's plane, and the engine's own ``elephas.loop.prefill`` span says
where the admission starts and ends. A fresh row made leaf by leaf, a
``jnp.int32(pos)`` a chunk, a ``logits[0]`` or an eager ``argmax`` each
add executions, and the test then fails: that is what it is for.

The values are held beside the count: the one-program row is
``init_kv_cache``'s tree, and the one-program sampler draws the token the
eager formulation draws.
"""
import dataclasses
import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.trace_reduce import find_xplane
from elephas_tpu.models.transformer import (TransformerConfig, init_kv_cache,
                                            init_params)
from elephas_tpu.serving_engine import DecodeEngine, _filter_logits_rows

REPO = Path(__file__).resolve().parent.parent
MAX_LEN, CHUNK = 48, 8
EXECUTE = "PjRtCpuExecutable::Execute"
ADMISSION = "elephas.loop.prefill"


def _family(name):
    path = REPO / "chipbench" / "families" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"adm_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _per_head():
    config = TransformerConfig(vocab_size=64, num_layers=2, num_heads=4,
                               d_model=32, d_ff=64, max_seq_len=MAX_LEN,
                               dtype=jnp.float32)
    return init_params(config, jax.random.PRNGKey(0)), config


def _latent():
    family = _family("deepseek_v2")
    with open(REPO / "chipbench" / "configs" /
              "deepseek-v2-l5-e40-serve.json") as fh:
        sizes = family.model_sizes(json.load(fh), True)
    config = family.program_config(sizes, MAX_LEN, "float32",
                                   dtype=jnp.float32)
    return family.make_params(config, 3), config


def _hybrid():
    family = _family("falcon_h1")
    sizes = dict(family.REHEARSE_SIZES, rms_norm_eps=1e-5, rope_theta=1e11,
                 tie_word_embeddings=False, mamba_rms_norm=True,
                 mamba_norm_before_gate=False, mamba_proj_bias=False,
                 mamba_conv_bias=True)
    config = family.program_config(sizes, max_seq_len=MAX_LEN,
                                   param_dtype="float32", dtype=jnp.float32)
    return family.make_params(config, 5), config


LAYOUTS = {"per_head": _per_head, "latent": _latent, "hybrid": _hybrid}


@pytest.fixture(scope="module")
def engines():
    """One warm paged engine a cache layout, built when first asked for."""
    made = {}

    def get(layout):
        if layout not in made:
            params, config = LAYOUTS[layout]()
            eng = DecodeEngine(params, config, max_slots=2, max_len=MAX_LEN,
                               paged=(40, 4), prefill_chunk=CHUNK)
            eng.warmup(prompt_lengths=[5, 13])
            made[layout] = eng
        return made[layout]

    return get


def _serve(eng, prompt, **how):
    rid = eng.submit(prompt, max_new_tokens=2, **how)
    out = None
    while eng.pending:
        eng.step()
        out = eng.result(rid) if out is None else out
    return out


def _executions_in_admission(eng, prompt, log_dir, **how):
    """(programs executed inside the one admission's span, their jitted
    functions' names) while ``prompt`` is served."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=options)
    try:
        _serve(eng, prompt, **how)
    finally:
        jax.profiler.stop_trace()
    spans, runs, calls = [], [], []
    trace = jax.profiler.ProfileData.from_file(find_xplane(str(log_dir)))
    for plane in trace.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == ADMISSION:
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns))
                elif ev.name == EXECUTE:
                    runs.append(ev.start_ns)
                elif ev.name.startswith("PjitFunction("):
                    calls.append((ev.start_ns, ev.name[13:-1]))
    assert len(spans) == 1, spans
    lo, hi = spans[0]
    return (sum(lo <= t < hi for t in runs),
            sorted({name for t, name in calls if lo <= t < hi}))


@pytest.mark.parametrize("chunks", [1, 2])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_an_admission_makes_chunks_plus_three_device_calls(
        engines, layout, chunks, tmp_path):
    """Fresh row, a program a chunk, install, sampler: ``chunks + 3``
    executions between the start and the end of a warm admission."""
    eng = engines(layout)
    rng = np.random.default_rng(35 + chunks)
    length = 5 if chunks == 1 else 13
    # a prompt the engine has not seen (no cached block to gather), after
    # one of the same length, so that every shape is warm
    _serve(eng, rng.integers(1, 60, length).astype(np.int32))
    count, names = _executions_in_admission(
        eng, rng.integers(1, 60, length).astype(np.int32), tmp_path)
    assert names == ["_extend", "_first_greedy", "_fresh_row", "_install"]
    assert count == chunks + 3, (count, names)


@pytest.mark.parametrize("how", [dict(temperature=0.8, top_k=5, seed=7),
                                 dict(temperature=0.8, top_p=0.9)],
                         ids=["seeded", "engine_key"])
def test_a_sampled_admission_makes_as_many(engines, how, tmp_path):
    """The filter and the draw are inside the one sampler program, and so
    is the key's derivation."""
    eng = engines("per_head")
    rng = np.random.default_rng(135 + len(how))
    _serve(eng, rng.integers(1, 60, 13).astype(np.int32), **how)
    count, names = _executions_in_admission(
        eng, rng.integers(1, 60, 13).astype(np.int32), tmp_path, **how)
    assert names == ["_extend", "_first_sampled", "_fresh_row", "_install"]
    assert count == 2 + 3, (count, names)


def test_an_admission_over_cached_blocks_makes_no_more(engines, tmp_path):
    """A prompt whose head the block cache holds: the gather takes the
    fresh row's place, the remainder is one chunk."""
    eng = engines("per_head")
    prompt = np.random.default_rng(235).integers(1, 60, 13).astype(np.int32)
    first = _serve(eng, prompt)
    _serve(eng, prompt)                  # the remainder's shape, warm
    count, names = _executions_in_admission(eng, prompt, tmp_path)
    assert names == ["_extend", "_first_greedy", "_gather_jit", "_install"]
    assert count == 1 + 3, (count, names)
    assert _serve(eng, prompt) == first


def _quantized():
    params, config = _per_head()
    return params, dataclasses.replace(config, kv_cache_quant=True)


@pytest.mark.parametrize("layout", sorted(LAYOUTS) + ["quantized"])
def test_the_fresh_row_is_init_kv_caches_tree(engines, layout):
    """Structure, shape, dtype and zeros, leaf for leaf; new buffers every
    call, which the donating extend may take."""
    if layout == "quantized":
        params, config = _quantized()
        eng = DecodeEngine(params, config, max_slots=2, max_len=MAX_LEN,
                           prefill_chunk=CHUNK)
    else:
        eng = engines(layout)
    want = init_kv_cache(eng.config, 1, MAX_LEN)
    row = eng._fresh_row_fn()
    assert (jax.tree_util.tree_structure(row)
            == jax.tree_util.tree_structure(want))
    for got, ref in zip(jax.tree_util.tree_leaves(row),
                        jax.tree_util.tree_leaves(want)):
        assert got.shape == ref.shape and got.dtype == ref.dtype
        assert not np.asarray(got).any()
    leaves = jax.tree_util.tree_leaves(row)
    other = jax.tree_util.tree_leaves(eng._fresh_row_fn())
    held = {leaf.unsafe_buffer_pointer() for leaf in leaves}
    assert len(held) == len(leaves)              # no leaf shares a buffer
    assert not held & {leaf.unsafe_buffer_pointer() for leaf in other}
    # the donating extend takes the row and writes what the plain one does
    blk = np.arange(1, 6, dtype=np.int32)[None]
    logits, _ = eng._extend_owned_fn(eng.params, row, blk, np.int32(0))
    plain, _ = eng._extend_fn(eng.params, want, blk, np.int32(0))
    np.testing.assert_array_equal(logits, plain)


def _eager_first_token(logits, temp, topk, topp, key):
    """The formulation the one-program sampler replaced, op by op, over
    final-position logits ``(vocab,)``."""
    if temp > 0:
        filt = _filter_logits_rows(
            logits[None] / temp, jnp.asarray([topk], jnp.int32),
            jnp.asarray([topp], jnp.float32))[0]
        return int(jax.random.categorical(key, filt))
    return int(jnp.argmax(logits))


@pytest.mark.parametrize("topp", [1.0, 0.7], ids=["no_top_p", "top_p"])
@pytest.mark.parametrize("topk", [0, 6], ids=["no_top_k", "top_k"])
@pytest.mark.parametrize("mode", ["greedy", "seeded", "engine_key"])
def test_the_sampler_draws_the_eager_formulations_token(engines, mode, topk,
                                                        topp):
    eng = engines("per_head")
    rng = np.random.default_rng(3)
    temp = 0.0 if mode == "greedy" else 0.9
    for trial in range(8):
        logits = jnp.asarray(rng.normal(0, 2, (1, 64)), jnp.float32)
        seed, fold = 2 ** 31 - 1 - trial, 11 + trial
        key0 = eng._key
        if mode == "seeded":
            key = jax.random.fold_in(jax.random.PRNGKey(seed), fold)
        else:
            after, key = jax.random.split(key0)
        want = _eager_first_token(logits[0], temp, topk, topp, key)
        got = eng._sample_first(logits, temp, topk, topp,
                                seed=seed if mode == "seeded" else None,
                                fold=fold)
        assert got == want, (trial, got, want)
        if mode == "engine_key":
            # the draw consumed one split of the engine key, as before
            np.testing.assert_array_equal(eng._key, after)
        else:
            assert eng._key is key0


def test_export_prefill_refuses_a_seed_no_slot_could_hold():
    """The sampler takes the seed as the step does, an int32 with -1 for
    none: what ``submit`` refuses, ``export_prefill`` refuses too."""
    params, config = _per_head()
    eng = DecodeEngine(params, config, max_slots=2, max_len=MAX_LEN,
                       prefill_chunk=CHUNK)
    prompt = np.arange(1, 10, dtype=np.int32)
    for seed in (-1, 2 ** 31):
        with pytest.raises(ValueError, match="seed must be in"):
            eng.export_prefill(prompt, temperature=0.8, seed=seed)
    solo = eng.export_prefill(prompt, temperature=0.8, seed=2 ** 31 - 1)
    again = eng.export_prefill(prompt, temperature=0.8, seed=2 ** 31 - 1)
    assert solo["first_token"] == again["first_token"]
