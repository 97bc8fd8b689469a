"""DeepSeek-V2's layers on the normal path, against the plain reference.

Toy widths with every branch on (``chipbench/families/deepseek_v2.py``
``REHEARSE_SIZES``: a dense layer and two expert layers, 8 experts in 4
groups, 2 groups kept, 2 experts held, a shared expert, both LoRA ranks,
YaRN), seeded weights, on the CPU. The reference is the benchmark's own
file, ``chipbench/reference/deepseek_v2.py``: independent of the
program's model code.
"""
import functools
import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elephas_tpu import DecodeEngine
from elephas_tpu.models import grouped_experts, paged_decode
from elephas_tpu.models.transformer import (TransformerConfig, chunked_blocks,
                                            init_kv_cache, init_params,
                                            param_specs,
                                            prefill_cache_chunked)

from ._transformer_util import (DECODE_BLOCK, DECODE_PAGED, DECODE_STEP,
                                FORWARD, PREFILL)

REPO = Path(__file__).resolve().parent.parent.parent


def _load(kind, name):
    path = REPO / "chipbench" / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"dsv2_{kind}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


FAMILY = _load("families", "deepseek_v2")
REFERENCE = _load("reference", "deepseek_v2")
with open(REPO / "chipbench" / "configs" /
          "deepseek-v2-l5-e40-serve.json") as _fh:
    PUBLISHED = json.load(_fh)
SIZES = FAMILY.model_sizes(PUBLISHED, True)
MAX_LEN = 96


def _model(param_dtype="float32", dtype=jnp.float32, seed=3, **sizes):
    cfg = FAMILY.program_config(dict(SIZES, **sizes), MAX_LEN, param_dtype,
                                dtype=dtype)
    return cfg, FAMILY.make_params(cfg, seed), dict(SIZES, **sizes)


# compiled whole once a shape, as ``_transformer_util``'s entry points are
PREFILL_CHUNKED = jax.jit(prefill_cache_chunked, static_argnums=(2, 3),
                          static_argnames="chunk")


ROUTE = jax.jit(grouped_experts.route, static_argnums=2)
EXPERTS_APPLY = jax.jit(grouped_experts.experts_apply, static_argnums=2)


@functools.lru_cache(maxsize=None)
def _reference_program(name, cfg, sizes_json, **kw):
    sizes = json.loads(sizes_json)
    if name == "forward":
        return jax.jit(lambda p, t: REFERENCE.forward_with_routing(
            FAMILY.to_reference(p, cfg), t, sizes))
    return jax.jit(lambda *a: getattr(REFERENCE, name)(*a, sizes, **kw))


def _sized(name, sizes, *args, cfg=None, **kw):
    """``REFERENCE.<name>(*args, sizes, **kw)``: one program a
    configuration, compiled once a shape"""
    return _reference_program(name, cfg, json.dumps(sizes, sort_keys=True),
                              **kw)(*args)


def _reference(params, cfg, sizes, tokens):
    return _sized("forward", sizes, params, tokens, cfg=cfg)


@pytest.fixture(scope="module")
def model():
    return _model()


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(1), (3, 41), 1,
                              SIZES["vocab_size"])


def test_forward_agrees_with_the_reference_in_float32(model, tokens):
    cfg, params, sizes = model
    want, _, _ = _reference(params, cfg, sizes, tokens)
    got = FORWARD(params, tokens, cfg)
    # float32 on both sides, different order of operations (grouped
    # against masked experts, fused against split rope): rounding only
    np.testing.assert_allclose(got, want, atol=5e-5)


def test_bf16_agrees_but_for_flipped_picks(tokens):
    cfg, params, sizes = _model("bfloat16", jnp.bfloat16)
    want, _, _ = _reference(params, cfg, sizes, tokens)
    got = np.asarray(FORWARD(params, tokens, cfg), np.float32)
    worst = np.abs(got - np.asarray(want)).max(axis=-1)   # per position
    # bf16 has 8 bits of mantissa and the logits a magnitude of 4: most
    # positions differ by rounding. Where a near-tied pick flips, here or
    # at an earlier position, a logit moves by whole units at these widths
    # (2 picks of 4 eligible experts, times 16): a minority, left to the
    # near-tie rule of chipbench/check_routed.py
    assert np.median(worst) < 0.15 and np.isfinite(worst).all()
    assert (worst < 0.5).mean() > 0.6
    # the same bf16 weights computed in float32: rounding only
    cfg32, _, _ = _model("bfloat16", jnp.float32)
    np.testing.assert_allclose(FORWARD(params, tokens, cfg32), want,
                               atol=1e-4)


def test_cached_paths_agree_with_the_forward(model, tokens):
    cfg, params, _ = model
    full = FORWARD(params, tokens, cfg)
    logits, cache = PREFILL(params, tokens[:, :24], cfg, MAX_LEN)
    np.testing.assert_allclose(logits, full[:, 23], atol=5e-5)
    logits, cache = DECODE_STEP(params, cache, tokens[:, 24],
                                jnp.full((3,), 24), cfg)
    np.testing.assert_allclose(logits, full[:, 24], atol=5e-5)
    logits, _ = PREFILL_CHUNKED(params, tokens, cfg, MAX_LEN, chunk=16)
    np.testing.assert_allclose(logits, full[:, -1], atol=5e-5)


def _paged_rows(cfg, params, prompts, block_size=4):
    """Rows of different lengths prefilled in chunks and installed."""
    mb = MAX_LEN // block_size
    pool = paged_decode.init_paged_pool(cfg, 1 + len(prompts) * mb,
                                        block_size)
    tables = np.zeros((len(prompts) + 1, mb), np.int32)
    for r, prompt in enumerate(prompts):
        # prefill_cache_chunked's loop, each chunk a program compiled once
        # a chunk length (its position traced), not once a prompt length
        _, row = chunked_blocks(
            lambda cache, blk, pos, _: DECODE_BLOCK(params, cache, blk, pos,
                                                    cfg),
            init_kv_cache(cfg, 1, MAX_LEN), jnp.asarray(prompt)[None], 0, 16)
        need = len(prompt) // block_size + 1
        tables[r, :need] = 1 + r * mb + np.arange(need)
        pool = paged_decode.install_row_paged(pool, row, tables[r], need)
    return pool, tables


LENGTHS = [13, 5, 45, 29, 61, 64]


@pytest.fixture(scope="module")
def paged(model):
    """Six rows in the pool, and the reference on the same rows (in one
    right-padded batch: causal, so the padding touches nothing before
    it)."""
    cfg, params, sizes = model
    rng = np.random.default_rng(0)
    rows = [rng.integers(1, sizes["vocab_size"], n + 1) for n in LENGTHS]
    pool, tables = _paged_rows(cfg, params, [r[:-1] for r in rows])
    padded = np.zeros((len(rows), max(LENGTHS) + 1), np.int32)
    for r, row in enumerate(rows):
        padded[r, :len(row)] = row
    want, picks, _ = _reference(params, cfg, sizes, jnp.asarray(padded))
    at = np.arange(len(rows)), np.asarray(LENGTHS)
    return (pool, tables, [int(r[-1]) for r in rows],
            np.asarray(want)[at], np.asarray(picks)[:, at[0], at[1]])


@pytest.mark.parametrize("held_blocks", [None, 96, (16, 48, 96)])
def test_paged_absorbed_step_agrees_with_the_reference(model, paged,
                                                       held_blocks):
    cfg, params, _ = model
    pool, tables, last, want, want_picks = paged
    pos = jnp.asarray(LENGTHS + [0], jnp.int32)    # one idle row
    logits, new_pool, stats = DECODE_PAGED(
        params, pool, jnp.asarray(tables), jnp.asarray(last + [0]), pos,
        cfg, held_blocks=held_blocks, with_stats=True)
    np.testing.assert_allclose(logits[:-1], want, atol=5e-5)
    np.testing.assert_array_equal(np.sort(stats["picks"][:, :-1]),
                                  np.sort(want_picks))
    # the idle row makes no pick; 6 live rows x 2 picks x 2 expert layers
    assert int(stats["counts"][0]) == 24 and int(stats["counts"][3]) == 2
    # the step wrote each row's new latent into the block that owns it
    blk, off = tables[2, 45 // 4], 45 % 4
    assert np.asarray(new_pool["layer_1"]["latent"][blk, 0, off]).any()
    assert not np.asarray(pool["layer_1"]["latent"][blk, 0, off]).any()


def test_the_engine_serves_it_through_the_normal_path(model):
    cfg, params, sizes = model
    engine = DecodeEngine(params, cfg, max_slots=4, max_len=MAX_LEN,
                          paged=(64, 8), prefill_chunk=16)
    engine.warmup(prompt_lengths=[16, 32])
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, sizes["vocab_size"], n).tolist()
               for n in (16, 32, 16, 32, 16, 32)]
    outputs = engine.run(prompts, 12)
    for prompt, output in zip(prompts, outputs):
        assert len(output) == 12
        want, _, _ = _reference(params, cfg, sizes,
                                jnp.asarray([prompt + output]))
        for j, tok in enumerate(output):
            at = np.asarray(want[0, len(prompt) + j - 1])
            # greedy, float32: the reference picks the same token
            assert at.max() - at[tok] < 1e-4
    text = engine.registry.render()
    counts = {line.split()[0]: float(line.split()[1])
              for line in text.splitlines()
              if line.startswith("serving_moe_")}
    assert counts["serving_moe_picks_total"] > 0
    assert counts["serving_moe_layer_steps_total"] % 2 == 0
    # about a quarter of the picks fall on the 2 held experts of 8
    share = (counts["serving_moe_held_picks_total"]
             / counts["serving_moe_picks_total"])
    assert 0.05 < share < 0.6
    assert counts["serving_moe_experts_touched_total"] <= \
        2 * counts["serving_moe_layer_steps_total"]
    assert engine._held_ladder[-1] == 4 * 16 and all(
        w % paged_decode.LATENT_TILE_BLOCKS == 0
        for w in engine._held_ladder)


def test_group_limited_greedy_picks_equal_the_references(model):
    cfg, params, sizes = model
    h = jax.random.normal(jax.random.PRNGKey(5), (64, cfg.d_model))
    gate = params["layer_1"]["moe"]["gate"]
    weights, picks, _ = ROUTE(h, gate, cfg)
    want_w, want_p, margin, _ = _sized("route", sizes, h, gate)
    decided = np.asarray(margin) > 1e-4
    assert decided.sum() >= 60
    np.testing.assert_array_equal(np.asarray(picks)[decided],
                                  np.asarray(want_p)[decided])
    np.testing.assert_allclose(np.asarray(weights)[decided],
                               np.asarray(want_w)[decided], rtol=1e-5)
    # no renormalisation: 16 x the probabilities, which do not sum to 16
    assert float(jnp.abs(weights.sum(-1) - 16.0).min()) > 0.1
    # all picks of a token lie in 2 of the 4 groups
    assert (np.array([len({int(e) // 2 for e in row})
                      for row in np.asarray(picks)]) <= 2).all()


def test_the_shares_add_up_to_the_uncut_layer():
    """Four ranks, each holding 2 of the 8 experts, the shared expert
    counted once: their parts sum to the reference's whole layer."""
    whole_sizes = dict(SIZES, n_routed_experts=8, held_first=0)
    cfg, params, _ = _model(n_routed_experts=8, held_first=0)
    moe = params["layer_1"]["moe"]
    h = jax.random.normal(jax.random.PRNGKey(7), (2, 24, cfg.d_model))
    whole = FAMILY.to_reference(params, cfg)["layers"][1]
    want, _, _ = _sized("expert_layer", whole_sizes, whole, h)
    total = jnp.zeros_like(want)
    for rank in range(4):
        rank_cfg = FAMILY.program_config(
            dict(SIZES, n_routed_experts=2, held_first=2 * rank), MAX_LEN,
            "float32", dtype=jnp.float32)
        part = {"gate": moe["gate"],
                **{k: moe[k][2 * rank:2 * rank + 2]
                   for k in ("w1", "w3", "w2")}}
        if rank == 0:
            part["shared"] = moe["shared"]
        out, stats = EXPERTS_APPLY(h, part, rank_cfg)
        total = total + out
        # and each rank's part is the reference's part for that share
        share, _, _ = _sized(
            "expert_layer",
            dict(SIZES, n_routed_experts=2, held_first=2 * rank),
            {**whole, **{k: whole[k][2 * rank:2 * rank + 2]
                         for k in ("e_gate", "e_up", "e_down")}}, h,
            shared=rank == 0)
        np.testing.assert_allclose(out, share, atol=2e-5)
    np.testing.assert_allclose(total, want, atol=5e-5)


def test_no_token_is_dropped_when_every_row_picks_the_same_experts(model):
    cfg, params, sizes = model
    moe = dict(params["layer_1"]["moe"])
    # a router that sends every token to experts 2 and 3: both held
    gate = np.zeros((cfg.d_model, 8), np.float32)
    h = jnp.abs(jax.random.normal(jax.random.PRNGKey(9),
                                  (5, 40, cfg.d_model)))
    gate[:, 2], gate[:, 3] = 0.02, 0.01
    moe["gate"] = jnp.asarray(gate)
    out, stats = EXPERTS_APPLY(h, moe, cfg)
    assert stats["counts"].tolist() == [400, 400, 2, 1]
    layer = {**FAMILY.to_reference(params, cfg)["layers"][1],
             "router": moe["gate"]}
    want, picks, _ = _sized("expert_layer", sizes, layer, h)
    assert set(np.asarray(picks).ravel().tolist()) == {2, 3}
    np.testing.assert_allclose(out, want, atol=5e-5)
    # rows that are not live get no expert, only the shared one
    live = jnp.zeros((5, 40), bool).at[0].set(True)
    out, stats = EXPERTS_APPLY(h, moe, cfg, live=live)
    assert stats["counts"].tolist() == [80, 80, 2, 1]
    np.testing.assert_allclose(out[0], want[0], atol=5e-5)
    only_shared, _, _ = _sized(
        "expert_layer", sizes,
        {**layer, "e_down": jnp.zeros_like(layer["e_down"])}, h)
    np.testing.assert_allclose(out[1:], only_shared[1:], atol=5e-5)


def test_latent_pool_round_trips(model, tokens):
    cfg, params, _ = model
    # 32 + 8 values, padded to the TPU's 128
    assert cfg.cache_leaves() == {"latent": (1, 128)}
    _, row = PREFILL(params, tokens[:1, :37], cfg, MAX_LEN)
    assert jax.tree_util.tree_map(jnp.shape, row) == \
        jax.tree_util.tree_map(jnp.shape, init_kv_cache(cfg, 1, MAX_LEN))
    pool = paged_decode.init_paged_pool(cfg, 40, 8)
    ids = np.array([7, 3, 11, 5, 9], np.int32)
    pool = paged_decode.install_row_paged(pool, row, ids, 5)
    back = paged_decode.gather_blocks_to_row(pool, ids, MAX_LEN)
    for name in row:
        np.testing.assert_array_equal(back[name]["latent"][:, :, :37],
                                      row[name]["latent"][:, :, :37])
    # a prefix hit installs only the private remainder
    other = paged_decode.install_row_paged(
        paged_decode.init_paged_pool(cfg, 40, 8), row, ids, 5, start=2)
    assert not np.asarray(other["layer_0"]["latent"][ids[:2]]).any()
    np.testing.assert_array_equal(other["layer_2"]["latent"][ids[2:]],
                                  pool["layer_2"]["latent"][ids[2:]])
    # the wire form, by the cache's own leaf names
    arrays = paged_decode.export_kv_blocks(row, 37, 8)
    assert len(arrays) == cfg.num_layers and arrays[0].shape == (5, 1, 8, 128)
    again = paged_decode.import_kv_blocks(arrays, 37, MAX_LEN,
                                          leaves=cfg.cache_leaves())
    np.testing.assert_array_equal(again["layer_1"]["latent"],
                                  np.asarray(row["layer_1"]["latent"]))
    with pytest.raises(ValueError, match="per layer"):
        paged_decode.import_kv_blocks(arrays, 37, MAX_LEN)   # 3 != (k, v)
    # host payloads: one tuple of leaves a layer
    payloads = paged_decode.export_pool_blocks(pool, [7, 3])
    assert [len(p["layer_0"]) for p in payloads] == [1, 1]
    fresh = paged_decode.install_pool_blocks(
        paged_decode.init_paged_pool(cfg, 40, 8), payloads, [1, 2])
    np.testing.assert_array_equal(
        fresh["layer_1"]["latent"][1:3],
        pool["layer_1"]["latent"][np.array([7, 3])])


def test_held_blocks_count_whole_tiles():
    pos = np.array([0, 5, 130, 700])
    assert paged_decode.held_block_count(pos, 16, 128) == 1 + 1 + 9 + 44
    assert paged_decode.held_block_count(pos, 16, 128, tile=8) == \
        8 + 8 + 16 + 48


def test_what_has_no_latent_form_says_so(model):
    cfg, params, _ = model
    engine = DecodeEngine(params, cfg, max_slots=2, max_len=MAX_LEN,
                          paged=(32, 8), prefill_chunk=16)
    with pytest.raises(ValueError, match="no latent-cache form"):
        engine.enable_kv_spill()
    with pytest.raises(ValueError, match="no latent-cache form"):
        engine.export_prefill([1, 2, 3])
    pool = paged_decode.init_paged_pool(cfg, 8, 8)
    with pytest.raises(ValueError, match="no latent-cache form"):
        paged_decode.decode_block_paged(
            params, pool, jnp.zeros((1, 12), jnp.int32),
            jnp.zeros((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32), cfg)


def test_config_refuses_what_does_not_fit_together():
    base = dict(vocab_size=64, num_layers=2, num_heads=2, d_model=16,
                d_ff=32)
    with pytest.raises(ValueError, match="needs q_lora_rank"):
        TransformerConfig(**base, attention_kind="mla")
    with pytest.raises(ValueError, match="mlp_kinds"):
        TransformerConfig(**base, mlp_kinds=("dense", "experts"))
    with pytest.raises(ValueError, match="expert_variant='swiglu'"):
        TransformerConfig(**base, num_experts=4, held_experts=(0, 2))
    with pytest.raises(ValueError, match="no range"):
        TransformerConfig(**base, num_experts=4, expert_variant="swiglu",
                          expert_d_ff=8, held_experts=(3, 2))
    with pytest.raises(ValueError, match="expert_variant='swiglu'"):
        TransformerConfig(**base, num_experts=4, moe_n_groups=2)
    with pytest.raises(ValueError, match="group-limited routing needs"):
        TransformerConfig(**base, num_experts=8, expert_variant="swiglu",
                          expert_d_ff=8, expert_top_k=4,
                          moe_n_groups=4, moe_topk_groups=1)


def test_param_specs_mirror_the_parameters(model):
    cfg, params, _ = model
    specs = param_specs(cfg)
    assert jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda a: 0, params)) == \
        jax.tree_util.tree_structure(jax.tree_util.tree_map(
            lambda s: 0, specs, is_leaf=lambda s: isinstance(
                s, jax.sharding.PartitionSpec)))


def test_rms_norm_epsilon_is_a_config_field():
    from elephas_tpu.models.transformer import _norm

    x = jnp.full((1, 4), 1e-3)
    sub = {"gamma": jnp.ones((4,)), "beta": jnp.zeros((4,))}
    base = dict(vocab_size=64, num_layers=1, num_heads=2, d_model=4,
                d_ff=8, norm="rmsnorm")
    assert TransformerConfig(**base).rms_norm_eps == 1e-5
    loose = _norm(x, sub, TransformerConfig(**base))
    tight = _norm(x, sub, TransformerConfig(**base, rms_norm_eps=1e-6))
    np.testing.assert_allclose(loose, 1e-3 / np.sqrt(1e-6 + 1e-5),
                               rtol=1e-5)
    np.testing.assert_allclose(tight, 1e-3 / np.sqrt(1e-6 + 1e-6),
                               rtol=1e-5)
    # the dense family's guard stays true: it passes no epsilon on
    dense = _load("families", "dense_decoder")
    with open(REPO / "chipbench" / "configs" /
              "mistral-7b-l16-serve.json") as fh:
        mistral = json.load(fh)
    assert dense.program_config(dense.model_sizes(mistral, True), 64,
                                "float32").rms_norm_eps == 1e-5
    with pytest.raises(ValueError, match="epsilon"):
        dense.program_config(dict(dense.model_sizes(mistral, True),
                                  rms_norm_eps=1e-6), 64, "float32")


@pytest.fixture(scope="module")
def v5e():
    """A described v5e host: the installed libtpu compiles for it with
    no chip attached (on-chip-measurement guide, section 2)."""
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:                       # no libtpu here
        pytest.skip(f"no TPU compiler in this installation: {exc}")


def test_the_real_size_step_compiles_for_a_v5e_and_fits(v5e):
    from jax.sharding import SingleDeviceSharding

    cfg = FAMILY.program_config(PUBLISHED, PUBLISHED["engine"]["max_len"],
                                "bfloat16")
    eng = PUBLISHED["engine"]
    where = SingleDeviceSharding(v5e.devices[0])

    def on_chip(tree):
        return jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=where), tree)

    params = on_chip(jax.eval_shape(lambda k: init_params(cfg, k),
                                    jax.random.PRNGKey(0)))
    pool = on_chip(jax.eval_shape(lambda: paged_decode.init_paged_pool(
        cfg, *eng["paged"])))
    weights = sum(a.size * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(params))
    assert abs(weights - 10.33e9) < 0.01e9          # the file's arithmetic
    rows, table = eng["max_slots"], eng["max_len"] // eng["paged"][1]
    ladder = tuple(rows * table >> k for k in range(5))

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=where)

    step = jax.jit(lambda p, pl, tb, tk, ps: paged_decode.decode_step_paged(
        p, pl, tb, tk, ps, cfg, held_blocks=ladder, with_stats=True),
        donate_argnums=(1,))
    compiled = step.lower(params, pool, ints(rows, table), ints(rows),
                          ints(rows)).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert total < 15.0e9, total                    # of the chip's 16 GB
    # the experts run as grouped matmuls (3 a layer, 4 expert layers),
    # not as 40 dense ones
    assert compiled.as_text().count("ragged-dot-none") >= 12


def test_the_real_size_chunk_program_holds_a_branch_a_rung(v5e):
    """The admission program of a 512-token chunk at the cell's sizes:
    per layer one ``conditional`` over the ladder's three widths, and
    the donated row is rewritten in place."""
    from jax.sharding import SingleDeviceSharding

    from elephas_tpu.models.transformer import decode_block, prefill_ladder

    eng = PUBLISHED["engine"]
    cfg = FAMILY.program_config(PUBLISHED, eng["max_len"], "bfloat16")
    where = SingleDeviceSharding(v5e.devices[0])

    def on_chip(tree):
        return jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=where), tree)

    params = on_chip(jax.eval_shape(lambda k: init_params(cfg, k),
                                    jax.random.PRNGKey(0)))
    row = on_chip(jax.eval_shape(
        lambda: init_kv_cache(cfg, 1, eng["max_len"])))
    ladder = prefill_ladder(eng["prefill_chunk"], eng["max_len"])
    assert ladder == (512, 1024, 2048)
    extend = jax.jit(lambda p, r, t, pos: decode_block(
        p, r, t, pos, cfg, attend_widths=ladder), donate_argnums=(1,))
    compiled = extend.lower(
        params, row,
        jax.ShapeDtypeStruct((1, eng["prefill_chunk"]), jnp.int32,
                             sharding=where),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=where)).compile()
    text = compiled.as_text()
    assert text.count(" conditional(") == cfg.num_layers
    assert text.count("branch_computations={") == cfg.num_layers
    mem = compiled.memory_analysis()
    row_bytes = sum(a.size * a.dtype.itemsize
                    for a in jax.tree_util.tree_leaves(row))
    assert mem.alias_size_in_bytes >= row_bytes
    assert mem.temp_size_in_bytes < 1.0e9
