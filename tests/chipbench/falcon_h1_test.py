"""The family ``falcon_h1`` in the benchmark: its cost functions against
the arithmetic its configuration file states, the reader it brings on a
recorded trace and hand-made counters, the layer-at-a-time reference
against the reference in one piece, and the step comparison's own
guarantees (a chunk boundary, a reused slot)."""
import json
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check_hybrid, costs_falcon_h1 as h1, trace_reduce
from chipbench.readers import cost_ratio_h1, trace_scope_time
from chipbench.spec import Spec

from ._util import REPO

CELL = "falcon-h1-chat-saturated"
TRACE = REPO / "chipbench" / "testdata" / "tiny-v5e.xplane.pb"


@pytest.fixture(scope="module")
def cfg():
    with open(REPO / "chipbench" / "configs" /
              "falcon-h1-34b-l6-serve.json") as fh:
        return json.load(fh)


# ----------------------------------------------------------------- costs
def test_costs_by_hand(cfg):
    attention = 5120 * (2560 + 512 + 512) + 2560 * 5120
    assert h1.attention_params(cfg) == attention == 31_457_280
    mixer = (5120 * 9248 + 4096 * 5120 + 5120 * 5 + 4096 + 96)
    assert h1.mixer_params(cfg) == mixer == 68_351_072
    assert h1.mlp_params(cfg) == 3 * 5120 * 21504 == 330_301_440
    assert h1.layer_params(cfg) == 430_120_032          # 0.860 GB in bf16
    assert h1.vocab_params(cfg) == 261120 * 5120 == 1_336_934_400
    assert h1.total_params(cfg) == 6 * 430_120_032 + 2 * 1_336_934_400 + 5120
    # 4 KV heads of 128, K and V, bf16
    assert h1.kv_bytes_per_position(cfg, layers=1) == 2048
    # 32 x 128 x 256 float32 = 4.19 MB, and 3 x 5120 bf16 beside it
    assert h1.state_bytes_per_row(cfg, layers=1) == 4_194_304 + 30_720
    # a row's state weighs as much as 2,063 cached positions
    assert h1.state_bytes_per_row(cfg) // h1.kv_bytes_per_position(cfg) \
        == 2063
    # what every step reads: all but the embedding
    assert h1.step_weight_bytes(cfg, "bfloat16") == 2 * (
        6 * 430_120_032 + 1_336_934_400)
    flops = h1.serve_token_flops(cfg)
    assert flops["head"] == 2 * 1_336_934_400
    # 5.2 GFLOP a prompt token, 7.8 with the head (the norms and the
    # mixer's vectors multiply nothing)
    assert round(flops["body"] / 1e9, 1) == 5.2
    assert round((flops["body"] + flops["head"]) / 1e9, 1) == 7.8


def test_the_file_states_its_own_arithmetic(cfg):
    stated = cfg["arithmetic_bf16"]
    assert stated["attention_a_layer"] == h1.attention_params(cfg)
    assert stated["mixer_a_layer"] == h1.mixer_params(cfg)
    assert stated["feed_forward_a_layer"] == h1.mlp_params(cfg)
    assert stated["layer"] == h1.layer_params(cfg)
    assert stated["embedding"] == stated["head"] == h1.vocab_params(cfg)
    assert stated["total_parameters"] == h1.total_params(cfg)
    assert stated["bytes"] == 2 * h1.total_params(cfg)
    assert stated["kv_bytes_a_position_a_layer"] == \
        h1.kv_bytes_per_position(cfg, layers=1)
    assert stated["state_bytes_a_row_a_layer"] == \
        h1.state_bytes_per_row(cfg, layers=1)
    slots, (blocks, size) = cfg["engine"]["max_slots"], cfg["engine"]["paged"]
    assert stated["slot_state_bytes"] == slots * h1.state_bytes_per_row(cfg)
    assert stated["pool_bytes"] == blocks * size * \
        h1.kv_bytes_per_position(cfg)
    # weights + state + pool: 13.7 GB of the chip's 17.18
    total = (stated["bytes"] + stated["slot_state_bytes"]
             + stated["pool_bytes"])
    assert 13.7e9 < total < 13.8e9


def test_the_file_is_the_catalog_row_but_for_its_depth(cfg):
    with open(REPO / "BENCHMARK.json") as fh:
        entry = next(c for c in json.load(fh)["configs"]
                     if c["name"] == "falcon-h1-34b-l6-serve")
    assert entry["reduced"] == ["num_hidden_layers"] == list(cfg["reduced"])
    assert entry["source"] == cfg["source"]
    assert (cfg["num_hidden_layers"], cfg["published"]) == (
        6, {"num_hidden_layers": 72})
    # every width as published
    assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"],
            cfg["vocab_size"], cfg["mamba_d_ssm"], cfg["mamba_d_state"],
            cfg["mamba_n_heads"], cfg["mamba_n_groups"]) == (
                5120, 21504, 128, 261120, 4096, 256, 32, 2)
    config = Spec().load_module("families", "falcon_h1").program_config(
        cfg, max_seq_len=2048, param_dtype="bfloat16")
    assert config.head_dim == 128 and config.kv_heads == 4
    assert config.ssm.in_dim == 9248 and config.ssm.chunk == 128
    assert config.multipliers.ssm == tuple(cfg["ssm_multipliers"])
    assert config.state_leaves()["ssm"] == ((32, 128, 256), jnp.float32)


def test_step_bytes_follow_the_rows_updated_and_the_positions_held(cfg):
    weights = h1.step_weight_bytes(cfg, "bfloat16")
    full = h1.decode_step_bytes(cfg, "bfloat16", 64 * 700, 64 * 6)
    state = 64 * 6 * 2 * (4_194_304 + 30_720)          # read and written
    assert full == weights + 64 * 700 * 2048 * 6 + state
    # the issue's 28% of a full step's bytes is state
    assert round(state / full, 2) == 0.28
    assert h1.decode_step_bytes(cfg, "bfloat16", 0, 0) == weights
    update = h1.update_cost(cfg, 384)
    assert update["bytes"] == state
    assert update["flops"] == 5 * 32 * 128 * 256 * 384
    scan = h1.scan_cost(cfg, tokens=512 * 6, chunks=6)
    per_token = 2 * 128 * 256 * 2 + 2 * 128 * 128 * 32 + 4 * 128 * 256 * 32
    assert scan["flops"] == per_token * 512 * 6
    assert scan["bytes"] == 512 * 6 * (5120 * 2 + 32 * 4 + 4096 * 2) \
        + 6 * 2 * 4_194_304


# ---------------------------------------------------------------- reader
PROM = ("serving_ssm_row_updates_total {updates}\n"
        'serving_decode_steps_total{{width="64"}} {steps}\n'
        'serving_decode_steps_total{{width="128"}} {steps}\n'
        "serving_ssm_scan_tokens_total {scanned}\n"
        'serving_prefill_chunks_total{{width="512"}} {chunks}\n')


def traced(cfg):
    run = SimpleNamespace(seconds=4.0, cell={"chips": 1}, device={
        "platform": "tpu", "kind": "TPU v5 lite"})
    ev = SimpleNamespace(run=run, trace=trace_reduce.load(str(TRACE)),
                         trace_dir="unused: the operations are cached here",
                         _device_ops=trace_scope_time.device_ops(str(TRACE)),
                         sizes=cfg, param_dtype="bfloat16",
                         trace_window=[100.0, 104.0])
    ev.samples = [{"events": [[90.0, 1]] * 10, "end": "open", "t_end": None,
                   "prompt_len": 690}] * 64
    ev.prom_start = PROM.format(updates=1000, steps=50, scanned=7, chunks=3)
    ev.prom_end = PROM.format(updates=1000 + 200 * 384, steps=150,
                              scanned=7 + 40 * 6 * 320, chunks=43)
    return ev


def test_shares_from_the_counters_and_the_trace(cfg):
    ev = traced(cfg)
    assert cost_ratio_h1.per_dispatch(
        ev, "serving_ssm_row_updates_total",
        "serving_decode_steps_total") == 384            # 64 rows x 6 layers
    assert cost_ratio_h1.per_dispatch(
        ev, "serving_ssm_scan_tokens_total",
        "serving_prefill_chunks_total") == 6 * 320
    program = ev.trace.program_time()["jit_alpha"]
    count, seconds = program["count"], program["seconds"]
    under = trace_scope_time.scope_seconds(ev, r"jit\(alpha\)", "jit_alpha")
    step = cost_ratio_h1.read(ev, "decode_step_roofline", "jit_alpha")
    needed = h1.decode_step_bytes(cfg, "bfloat16", 64 * 700, 384)
    assert step == pytest.approx(100 * needed / 819e9 / (seconds / count),
                                 rel=1e-6)
    update = cost_ratio_h1.read(ev, "update_roofline", "jit_alpha",
                                scope=r"jit\(alpha\)")
    assert update == pytest.approx(
        100 * 384 * 2 * 4_225_024 / 819e9 / (under / count), rel=1e-6)
    scan = cost_ratio_h1.read(ev, "scan_roofline", "jit_alpha",
                              scope=r"jit\(alpha\)")
    cost = h1.scan_cost(cfg, 6 * 320, 6)
    assert scan == pytest.approx(100 * max(
        cost["flops"] / 197e12, cost["bytes"] / 819e9) / (under / count),
        rel=1e-6)
    share = cost_ratio_h1.read(ev, "step_share", "jit_alpha",
                               scope=r"jit\(alpha\)")
    assert share == pytest.approx(100 * under / seconds) and share <= 100
    # the state is counted once read and once written, for the rows the
    # engine stepped and no others: more rows than slots cannot be read
    assert 384 == cfg["engine"]["max_slots"] * cfg["num_hidden_layers"]


def test_nothing_to_read_reads_none(cfg):
    """A program without the scope or the counters (the parent commit),
    a run that was not traced, a run off the TPU."""
    ev = traced(cfg)
    for what in ("update_roofline", "scan_roofline", "step_share"):
        assert cost_ratio_h1.read(ev, what, "jit_alpha",
                                  scope=r"elephas\.ssm\.") is None
    assert cost_ratio_h1.read(ev, "step_share", "no_such_program",
                              scope="jit") is None
    ev.prom_start = ev.prom_end = "serving_steps_total 5\n"
    for what in ("decode_step_roofline", "update_roofline", "scan_roofline"):
        assert cost_ratio_h1.read(ev, what, "jit_alpha",
                                  scope=r"jit\(alpha\)") is None
    ev = traced(cfg)
    ev.trace = None
    assert cost_ratio_h1.read(ev, "decode_step_roofline", "jit_alpha") is None
    ev = traced(cfg)
    ev.run.device["platform"] = "cpu"
    for what in ("decode_step_roofline", "update_roofline", "scan_roofline",
                 "step_share"):
        assert cost_ratio_h1.read(ev, what, "jit_alpha",
                                  scope=r"jit\(alpha\)") is None
    with pytest.raises(ValueError, match="unknown"):
        cost_ratio_h1.read(traced(cfg), "nonsense", "jit_alpha")


def test_every_new_metric_has_its_file_and_its_cell():
    spec = Spec()
    added = {m["name"]: m for m in spec.data["per_layer"]
             if m["name"].endswith(".h1")}
    assert set(added) == {
        "compile.in_window.h1", "decode.step_device_ms.h1",
        "prefill.device_share.h1", "serve_mfu.h1",
        "decode_step_roofline.h1", "ssm.update_roofline.h1",
        "ssm.scan_roofline.h1", "ssm.step_share.h1"}
    for name, entry in added.items():
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "serve_tokens_per_s"
        definition = spec.load_json("metrics", name)
        assert callable(spec.load_module("readers",
                                         definition["reader"]).read)
    assert added["serve_mfu.h1"]["source"] == "host_clock"
    shared = [m["name"] for m in spec.data["per_layer"]
              if CELL in m.get("workloads", ()) and m["name"] not in added]
    assert len(shared) == 11
    assert CELL in next(m for m in spec.data["end_to_end"]
                        if m["name"] == "serve_tokens_per_s")["workloads"]


# ------------------------------------------------------------- reference
@pytest.fixture(scope="module")
def toy():
    spec = Spec()
    family = spec.load_module("families", "falcon_h1")
    reference = spec.load_module("reference", "falcon_h1")
    sizes = family.model_sizes(spec.config("falcon-h1-34b-l6-serve"), True)
    config = family.program_config(sizes, max_seq_len=128,
                                   param_dtype="float32", dtype=jnp.float32)
    params = family.make_params(config, 11)
    return family, reference, sizes, config, params


def test_a_layer_at_a_time_is_the_reference_in_one_piece(toy, monkeypatch):
    family, reference, sizes, config, params = toy
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(0), (2, 12),
                                           1, 512))
    whole = np.asarray(reference.forward(
        family.to_reference(params, config), tokens, sizes))
    # a head in blocks that do not divide the vocabulary
    monkeypatch.setattr(check_hybrid, "HEAD_BLOCK", 200)
    pieces = check_hybrid.Reference(reference, family, params, config, sizes)
    np.testing.assert_allclose(pieces.logits(tokens), whole, atol=1e-5)
    np.testing.assert_allclose(pieces.last_logits(tokens), whole[:, -1],
                               atol=1e-5)
    rounded = check_hybrid.Reference(
        reference, family, params, config, sizes,
        state_round=lambda s: s.astype(jnp.bfloat16).astype(jnp.float32))
    moved = np.abs(rounded.last_logits(tokens) - whole[:, -1]).max()
    assert 1e-5 < moved < 0.05


def test_the_step_comparison_crosses_a_chunk_and_reuses_a_slot(toy):
    family, reference, sizes, config, params = toy
    ref = check_hybrid.Reference(reference, family, params, config, sizes)
    engine_sizes = {"paged": [64, 8], "prefill_chunk": 16, "max_len": 128}
    found = check_hybrid.paged_step_vs_reference(
        params, config, ref.last_logits, rows=2, cached=40,
        engine_sizes=engine_sizes, seed=3)
    assert found["chunks"] == 3
    assert found["max_abs_dlogit"] < 1e-4 and found["rms_dlogit"] < 3e-5
    with pytest.raises(ValueError, match="prefill_chunk"):
        check_hybrid.paged_step_vs_reference(
            params, config, ref.last_logits, rows=2, cached=12,
            engine_sizes=engine_sizes, seed=3)
    # a program that is NOT the reference reads whole units
    other = family.make_params(config, 12)
    found = check_hybrid.paged_step_vs_reference(
        other, config, ref.last_logits, rows=2, cached=40,
        engine_sizes=engine_sizes, seed=3)
    assert found["max_abs_dlogit"] > 0.5
