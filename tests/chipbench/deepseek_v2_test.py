"""The family ``deepseek_v2`` in the benchmark: its cost functions by
hand, the routed comparison's verdicts, the readers it brings on a
recorded and on a hand-made trace, its configuration file against the
arithmetic it states, and the rehearsal of its cell."""
import json
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench import check_routed, costs_deepseek_v2 as dsv2, trace_reduce
from chipbench.readers import cost_ratio_dsv2, trace_scope_time

from ._util import REPO, last_line, run_broken, run_cell

CELL = "deepseek-v2-chat-saturated"
TRACE = REPO / "chipbench" / "testdata" / "tiny-v5e.xplane.pb"


@pytest.fixture(scope="module")
def cfg():
    with open(REPO / "chipbench" / "configs" /
              "deepseek-v2-l5-e40-serve.json") as fh:
        return json.load(fh)


# ----------------------------------------------------------------- costs
def test_costs_by_hand(cfg):
    attention = (5120 * 1536 + 1536 * 128 * 192 + 5120 * 576
                 + 512 * 128 * 256 + 128 * 128 * 5120)
    assert attention == 149_225_472                    # 149.2 M a layer
    assert dsv2.attention_params(cfg) == attention
    assert dsv2.expert_params(cfg) == 3 * 5120 * 1536 == 23_592_960
    assert dsv2.shared_params(cfg) == 47_185_920       # two, summed
    assert dsv2.router_params(cfg) == 819_200          # 160 wide
    assert dsv2.dense_mlp_params(cfg) == 3 * 5120 * 12288
    expert_layer = attention + 47_185_920 + 819_200 + 40 * 23_592_960
    assert expert_layer == 1_140_948_992               # 1,140.9 M
    total = (attention + 3 * 5120 * 12288) + 4 * expert_layer \
        + 2 * 5120 * 25600
    assert dsv2.total_params(cfg) == total == 5_163_909_120
    # a block: 16 positions x 576 values x 2 B x 5 layers
    assert dsv2.latent_bytes_per_position(cfg, layers=1) == 1152
    assert 16 * dsv2.latent_bytes_per_position(cfg) == 92_160   # 102,400 as stored
    # what every step reads whatever the routing: all but the embedding
    # and the routed experts
    resident = total - 5120 * 25600 - 4 * 40 * 23_592_960
    assert dsv2.resident_matrix_bytes(cfg, "bfloat16") == 2 * resident
    # 73% of the parameters are routed experts
    assert round(4 * 40 * 23_592_960 / total, 2) == 0.73


def test_the_file_states_its_own_arithmetic(cfg):
    stated = cfg["arithmetic_bf16"]
    assert stated["total_parameters"] == dsv2.total_params(cfg)
    assert stated["bytes"] == 2 * dsv2.total_params(cfg)
    assert stated["attention_a_layer"] == dsv2.attention_params(cfg)
    assert stated["one_routed_expert"] == dsv2.expert_params(cfg)
    assert stated["matrices_a_decode_step_can_read_bytes"] == \
        dsv2.resident_matrix_bytes(cfg, "bfloat16") + dsv2.experts_bytes(
            cfg, "bfloat16", 4 * 40)
    assert stated["pool_bytes"] == cfg["engine"]["paged"][0] * \
        stated["latent_block_bytes"]
    # the cut is written down: what is reduced, from what, and the share
    assert set(cfg["reduced"]) == {"num_hidden_layers", "n_routed_experts",
                                   "vocab_size"}
    assert cfg["published"] == {"num_hidden_layers": 60,
                                "n_routed_experts": 160,
                                "vocab_size": 102400}
    assert cfg["router_experts"] == 160 and cfg["n_group"] == 8
    assert cfg["n_routed_experts"] == 40 and "four v5e chips" in \
        cfg["stands_for"]
    for key in ("paged_logits_atol", "near_tie_margin",
                "max_flipped_share", "token_logit_margin",
                "token_share_within_margin", "token_logit_margin_worst"):
        assert len(cfg["check"][f"{key}_why"]) > 40


def test_step_bytes_follow_the_experts_touched_and_the_rows(cfg):
    base = dsv2.decode_step_bytes(cfg, "bfloat16", 0, 0)
    assert base == dsv2.resident_matrix_bytes(cfg, "bfloat16")
    more = dsv2.decode_step_bytes(cfg, "bfloat16", 64 * 700, 4 * 36.4)
    assert more - base == pytest.approx(
        64 * 700 * 5 * 1152 + 4 * 36.4 * 2 * 23_592_960)
    cost = dsv2.attend_cost(cfg, 1000)
    assert cost["flops"] == 2 * 128 * (576 + 512) * 1000 * 5
    assert cost["bytes"] == 1000 * 5 * 1152
    # at the v5e's ridge: 197 TFLOP/s over 819 GB/s
    assert cost["flops"] / cost["bytes"] == pytest.approx(241.8, rel=1e-3)


# ------------------------------------------------- the routed comparison
TOL = {"paged_logits_atol": 0.2, "paged_logits_rms": 0.12,
       "near_tie_margin": 0.05, "max_flipped_share": 0.4}


def verdict(got_shift=0.0, picks=None, margins=None):
    ref_picks = np.array([[[0, 50, 7], [3, 90, 39], [41, 5, 6]]])
    want = np.zeros((3, 4))
    got = want + got_shift
    return check_routed.judge(
        got, want, ref_picks if picks is None else np.asarray(picks),
        ref_picks, np.full((1, 3), 0.5) if margins is None
        else np.asarray(margins), (0, 40), TOL)


def test_every_row_is_held_to_the_logits():
    assert verdict(0.1)["ok"] and verdict(0.1)["flipped_choices"] == 0
    assert not verdict(0.3)["ok"]
    assert not verdict(float("nan"))["ok"]
    # within the worst-case limit everywhere, over the mean-square one
    assert verdict(0.1)["rms_dlogit"] == pytest.approx(0.1)
    assert not verdict(0.15)["ok"]
    # a flipped near-tie does not excuse its row's logits: the reference
    # was given the program's picks
    flipped = [[[0, 50, 8], [3, 90, 39], [41, 5, 6]]]   # 7 -> 8, held
    assert not verdict(np.array([[0.3], [0.0], [0.0]]), picks=flipped,
                       margins=[[0.01, 0.5, 0.5]])["ok"]


def test_only_held_picks_count_and_order_does_not():
    # 50 -> 51 and 90 -> 159 are absent experts; row 2 reordered
    out = verdict(picks=[[[0, 51, 7], [3, 159, 39], [6, 41, 5]]])
    assert out["ok"] and out["flipped_choices"] == 0


def test_a_flipped_choice_must_be_a_near_tie():
    flipped = [[[0, 50, 8], [3, 90, 39], [41, 5, 6]]]   # 7 -> 8, held
    near = verdict(picks=flipped, margins=[[0.01, 0.5, 0.5]])
    assert near["ok"] and near["flipped_choices"] == 1
    assert near["choices"] == 3
    assert near["worst_flipped_margin"] == pytest.approx(0.01)
    decided = verdict(picks=flipped, margins=[[0.3, 0.5, 0.5]])
    assert not decided["ok"]
    # two of three choices flipped are too many, near-ties or not
    two = verdict(picks=[[[0, 50, 8], [3, 90, 38], [41, 5, 6]]],
                  margins=[[0.01, 0.01, 0.5]])
    assert not two["ok"] and two["flipped_choices"] == 2


def test_rows_without_a_flipped_choice_are_reported_alone():
    flipped = [[[0, 50, 8], [3, 90, 39], [41, 5, 6]]]   # row 0 flipped
    out = verdict(np.array([[0.15], [0.05], [0.1]]), picks=flipped,
                  margins=[[0.01, 0.5, 0.5]])
    assert out["ok"] and out["agreeing_rows"] == 2
    assert out["max_abs_dlogit"] == pytest.approx(0.15)
    assert out["max_abs_dlogit_agreeing"] == pytest.approx(0.1)
    assert verdict(0.1)["agreeing_rows"] == 3


def test_every_slot_is_held_to_the_reference_that_followed_its_picks():
    # 4 slots: prompts 0, 1, 0, 1. Slot 2 breaks a tie the other way than
    # slot 0 (held 7 -> 8); slot 3 differs from slot 1 on an absent
    # expert alone, which adds nothing on either side
    picks = np.array([[[0, 50, 7], [3, 90, 39], [0, 50, 8], [3, 91, 39]]])
    calls = []

    def reference(prompt, slot_picks):
        assert slot_picks.shape == (1, 1, 3)
        calls.append((prompt, slot_picks[0, 0].tolist()))
        held = sorted(e for e in slot_picks[0, 0] if e < 40)
        return (np.full(4, 10.0 * prompt + sum(held)),
                np.array([[0, 50, 7], [3, 90, 39]][prompt])[None],
                np.array([0.01 * (prompt + 1)]))

    want, ref_picks, margins, runs = check_routed.follow_each_slot(
        picks, [0, 1, 0, 1], (0, 40), reference)
    assert runs == 3 and calls == [
        (0, [0, 50, 7]), (1, [3, 90, 39]), (0, [0, 50, 8])]
    assert want[:, 0].tolist() == [7.0, 52.0, 8.0, 52.0]
    assert ref_picks.shape == (1, 4, 3) and margins.shape == (1, 4)
    assert margins[0].tolist() == [0.01, 0.02, 0.01, 0.02]
    # each slot against its own: one near-tie flipped, every logit held,
    # where slot 2 against slot 0's reference would read 1.0 off
    out = check_routed.judge(want, want, picks, ref_picks, margins,
                             (0, 40), TOL)
    assert out["ok"] and out["flipped_choices"] == 1
    assert out["agreeing_rows"] == 3


def test_the_compared_step_sits_on_the_timed_windows_rung(cfg):
    from chipbench.families import deepseek_v2 as family
    from elephas_tpu.models.paged_decode import held_ladder
    engine, check = cfg["engine"], cfg["check"]
    config = family.program_config(cfg, max_seq_len=engine["max_len"],
                                   param_dtype=cfg["param_dtype"])
    block = engine["paged"][1]
    # 64 slots x 128 table entries in tiles of 8, halved five times
    assert held_ladder(config, engine["max_slots"],
                       engine["max_len"] // block) == (
        512, 1024, 2048, 4096, 8192)
    # every slot at the check's 44 blocks, in whole tiles: the 4,096
    # rung, where the timed window's steps run (PERF.md section 4)
    need = -(-(check["paged_cached"] // block + 1) // 8) * 8
    assert 2048 < engine["max_slots"] * need <= 4096
    assert 1 + engine["max_slots"] * (
        check["paged_cached"] // block + 1) <= engine["paged"][0]


def test_tokens_are_judged_by_their_share_and_their_worst():
    tol = {"token_logit_margin": 0.3, "token_share_within_margin": 0.7,
           "token_logit_margin_worst": 2.0}
    below = np.array([0.0, 0.1, 1.5, 0.2])
    out = check_routed.judge_tokens(below, tol)
    assert out["ok"] and out["tokens"] == 4
    assert out["share_within_margin"] == 0.75 and out["worst"] == 1.5
    # a second token off, one token far off, nothing to judge, a nan
    assert not check_routed.judge_tokens(
        np.array([0.0, 0.4, 1.5, 0.2]), tol)["ok"]
    assert not check_routed.judge_tokens(
        np.array([0.0, 0.1, 3.5, 0.2]), tol)["ok"]
    assert not check_routed.judge_tokens(np.array([]), tol)["ok"]
    assert not check_routed.judge_tokens(
        np.array([0.0, float("nan")]), tol)["ok"]


def test_token_margins_teacher_force_prompt_and_answer():
    # a reference whose logits say "the next token is this one + 1"
    def ref_logits(rows):
        out = np.zeros(rows.shape + (8,), np.float32)
        for t in range(rows.shape[1]):
            out[0, t, (rows[0, t] + 1) % 8] = 1.0
        return out

    below = check_routed.token_margins(
        ref_logits, [[1, 2]], [[3, 4, 6]], pad_to=8)
    np.testing.assert_array_equal(below, [0.0, 0.0, 1.0])


# --------------------------------------------------------------- readers
def test_device_ops_reads_the_origins_the_profiler_recorded():
    ops = trace_scope_time.device_ops(str(TRACE))
    reduced = trace_reduce.load(str(TRACE))
    first = reduced.devices[min(reduced.devices)]["ops"]
    # the same events, on the same clock, as jax's own reader gives
    assert [(s, e) for _, s, e in ops] == [(s, e) for _, s, e in first]
    origins = {origin for origin, _, _ in ops}
    assert "jit(alpha)/dot_general:" in origins
    assert "jit(beta)/reduce_sum:" in origins


def traced(tmp_path=None):
    reduced = trace_reduce.load(str(TRACE))
    run = SimpleNamespace(device={"platform": "tpu", "kind": "TPU v5 lite"})
    ev = SimpleNamespace(trace=reduced, trace_dir=None, run=run,
                         _device_ops=trace_scope_time.device_ops(str(TRACE)))
    ev.trace_dir = "unused: the operations are cached above"
    return ev


def test_scope_time_is_the_union_of_the_matching_operations():
    ev = traced()
    alpha = trace_scope_time.scope_seconds(ev, r"jit\(alpha\)")
    lo, hi = ev.trace.window
    by_hand = trace_reduce.union_ns(
        [(max(s, lo), min(e, hi)) for origin, s, e in ev._device_ops
         if origin.startswith("jit(alpha)") and e > lo and s < hi])
    assert alpha == pytest.approx(sum(e - s for s, e in by_hand) / 1e9)
    assert 0 < alpha < ev.trace.busy_s()
    assert trace_scope_time.scope_seconds(ev, "elephas.moe") is None
    share = trace_scope_time.read(ev, r"jit\(alpha\)", "share_of_busy")
    assert share == pytest.approx(100 * alpha / ev.trace.busy_s())
    per = trace_scope_time.read(ev, r"jit\(alpha\)", "ms_per_execution",
                                program="jit_alpha")
    count = ev.trace.program_time()["jit_alpha"]["count"]
    assert per == pytest.approx(1e3 * alpha / count)
    assert trace_scope_time.read(ev, "nothing", "share_of_busy") is None
    # only what runs inside an execution of the named program counts
    assert trace_scope_time.scope_seconds(ev, "jit", "jit_alpha") == alpha
    assert trace_scope_time.scope_seconds(ev, r"jit\(alpha\)",
                                          "jit_beta") is None


PROM = ("serving_steps_total {steps}\n"
        "serving_moe_experts_touched_total {touched}\n")


def test_rooflines_from_the_counters_and_the_trace(cfg):
    ev = traced()
    ev.sizes, ev.param_dtype = cfg, "bfloat16"
    ev.trace_window = [100.0, 104.0]
    ev.samples = [{"events": [[90.0, 1]] * 10, "end": "open", "t_end": None,
                   "prompt_len": 690}] * 64
    ev.prom_start = PROM.format(steps=100, touched=10_000)
    ev.prom_end = PROM.format(steps=300, touched=10_000 + 200 * 4 * 36)
    assert cost_ratio_dsv2.experts_touched_per_step(ev) == 144
    count = ev.trace.program_time()["jit_alpha"]["count"]
    seconds = ev.trace.program_time()["jit_alpha"]["seconds"]
    share = cost_ratio_dsv2.read(ev, "decode_step_roofline", "jit_alpha")
    needed = dsv2.decode_step_bytes(cfg, "bfloat16", 64 * 700, 144)
    assert share == pytest.approx(
        100 * needed / 819e9 / (seconds / count), rel=1e-6)
    under = trace_scope_time.scope_seconds(ev, r"jit\(alpha\)")
    experts = cost_ratio_dsv2.read(ev, "experts_roofline", "jit_alpha",
                                   scope=r"jit\(alpha\)")
    assert experts == pytest.approx(
        100 * 144 * 2 * 23_592_960 / 819e9 / (under / count), rel=1e-6)
    attend = cost_ratio_dsv2.read(ev, "attend_roofline", "jit_alpha",
                                  scope=r"jit\(alpha\)")
    cost = dsv2.attend_cost(cfg, 64 * 700)
    assert attend == pytest.approx(100 * max(
        cost["flops"] / 197e12, cost["bytes"] / 819e9) / (under / count),
        rel=1e-6)
    # a program without the scope, or without the counters: nothing
    assert cost_ratio_dsv2.read(ev, "experts_roofline", "jit_alpha",
                                scope="elephas.moe.experts") is None
    ev.prom_end = ev.prom_start = "serving_steps_total 5\n"
    assert cost_ratio_dsv2.read(ev, "decode_step_roofline",
                                "jit_alpha") is None
    ev.run.device["platform"] = "cpu"
    assert cost_ratio_dsv2.read(ev, "attend_roofline", "jit_alpha",
                                scope="x") is None


# ------------------------------------------------------------- rehearsal
@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses(trace):
    proc = run_cell("--workload", CELL, "--seed", str(2**31 + 28),
                    "--seconds", "3", "--trace", str(trace), "--rehearse")
    line = last_line(proc)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    with open(REPO / "BENCHMARK.json") as fh:
        bench = json.load(fh)
    if trace:
        counters = {m["name"] for m in bench["per_layer"]
                    if CELL in m.get("workloads", ())
                    and m["source"] == "program_counter"}
        assert counters == set(line["metrics"])
        assert line["metrics"]["moe.held_pick_share.dsv2"]["value"] == \
            pytest.approx(25.0, abs=12.0)
        assert line["metrics"]["compile.in_window.dsv2"]["value"] == 0
    else:
        assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert "paged step vs plain reference" in proc.stdout
    assert "compiles inside the window: 0" in proc.stdout


@pytest.mark.parametrize("fault", [None, "token_altered"])
def test_an_altered_token_is_seen_where_the_toy_limits_have_teeth(
        fault, tmp_path, cfg):
    """The configuration's rehearsal limits are vacuous on purpose (a
    flipped pick at toy widths moves whole logits), so this copy of it
    holds the rehearsal's tokens to the chip's share rule (0.8 within
    0.25): the sound path passes it, and the path whose engine emits the
    neighbour of every token does not."""
    with open(REPO / "BENCHMARK.json") as fh:
        bench = json.load(fh)
    toy = json.loads(json.dumps(cfg))
    toy["rehearse"]["check"].update(token_logit_margin=0.25,
                                    token_share_within_margin=0.8)
    entry = next(c for c in bench["configs"]
                 if c["file"].endswith("deepseek-v2-l5-e40-serve.json"))
    path = tmp_path / entry["file"]
    path.parent.mkdir(parents=True)
    path.write_text(json.dumps(toy))
    bench["configs"] = [entry]
    bench["workloads"] = [w for w in bench["workloads"] if w["name"] == CELL]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    args = ("--benchmark-json", str(tmp_path / "BENCHMARK.json"),
            "--workload", CELL, "--seed", str(2**31 + 29), "--seconds", "3",
            "--trace", "0")
    proc = (run_broken(fault, *args) if fault
            else run_cell(*args, "--rehearse"))
    line = last_line(proc)
    share = line["checks"]["token_share_within_margin"]
    assert share["limit"] == 0.8 and line["failed"] == 0
    if fault:
        assert line["correct"] is False and share["value"] < 0.5
        assert proc.stderr.strip().splitlines()[-1] == "correct: False"
    else:
        assert line["correct"] is True and share["value"] >= 0.8
