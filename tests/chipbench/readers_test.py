"""The metric readers on evidence written by hand."""
from types import SimpleNamespace

import pytest

from chipbench.evidence import parse_prometheus
from chipbench import costs, costs_deepseek_v2
from chipbench.readers import (client_samples, prometheus_delta,
                               prometheus_gauge, serve_mfu, train_rate)


def sample(i, due, first, last, n, timed=True, prompt_len=128, end="done"):
    """A request that got ``n`` tokens, one a line, evenly from ``first``
    to ``last``."""
    step = (last - first) / (n - 1) if n > 1 else 0.0
    return {"i": i, "due": due, "sent": due + 0.001, "timed": timed,
            "prompt_len": prompt_len, "asked": n, "http": 200, "end": end,
            "t_end": last, "tokens": [1] * n,
            "events": [[first + k * step, 1] for k in range(n)]}


def evidence(**fields):
    base = dict(samples=[], window=[10.0, 20.0], polls=[], prom_start=None,
                prom_end=None, engine_sizes={"paged": [100, 16]},
                epoch_ends=[], tokens_per_epoch=None)
    return SimpleNamespace(**{**base, **fields})


def test_latencies_count_from_the_instant_a_request_was_due():
    ev = evidence(samples=[
        sample(0, due=10.0, first=10.5, last=11.5, n=11),
        sample(1, due=12.0, first=12.1, last=13.1, n=6),
        sample(2, due=5.0, first=5.1, last=6.0, n=4, timed=False)])
    assert client_samples.read(ev, "ttft", 1.0) == pytest.approx(500.0)
    assert client_samples.read(ev, "ttft", 0.0) == pytest.approx(100.0)
    assert client_samples.read(ev, "ttft", 0.5) == pytest.approx(300.0)
    # per request (last - first) / (n - 1): 100 ms and 200 ms
    assert client_samples.read(ev, "tpot", 0.0) == pytest.approx(100.0)
    assert client_samples.read(ev, "tpot", 1.0) == pytest.approx(200.0)
    assert client_samples.read(ev, "late", 0.9) == pytest.approx(1.0)


def test_tokens_per_second_counts_what_arrived_inside_the_window():
    ev = evidence(samples=[
        sample(0, due=9.0, first=9.5, last=10.4, n=10),    # 5 inside
        sample(1, due=19.0, first=19.55, last=20.45, n=10)])   # 5 inside
    assert client_samples.read(ev, "tokens_per_s") == pytest.approx(1.0)
    assert client_samples.read(evidence(), "tokens_per_s") is None


def test_quantile_interpolates():
    assert client_samples.quantile([1, 2, 3, 4, 5], 0.9) == pytest.approx(4.6)
    assert client_samples.quantile([7], 0.9) == 7


def test_sweep_row_and_knee():
    limits = {"ttft_ms": 1000, "tpot_ms": 100, "share": 0.9, "settle_s": 5}
    good = [sample(i, due=float(i), first=i + 0.2, last=i + 1.2, n=21)
            for i in range(20)]
    row = client_samples.sweep_row(good, 0.0, 30.0, limits)
    assert row["attainment"] == 1.0 and row["backlog_end"] == 0
    assert row["ttft_p50_ms"] == pytest.approx(200.0)
    late = [sample(i, due=float(i), first=i + 3.0, last=i + 4.0, n=21)
            for i in range(20)]
    assert client_samples.sweep_row(late, 0.0, 30.0, limits)[
        "attainment"] == 0.0
    rows = [dict(rate_per_s=1.0, attainment=1.0, backlog_mid=0,
                 backlog_end=1, requests=40),
            dict(rate_per_s=2.0, attainment=0.95, backlog_mid=1,
                 backlog_end=2, requests=80),
            dict(rate_per_s=3.0, attainment=0.5, backlog_mid=2,
                 backlog_end=5, requests=120),
            dict(rate_per_s=4.0, attainment=0.1, backlog_mid=20,
                 backlog_end=60, requests=160)]
    assert client_samples.knee(rows, limits) == {
        "knee_per_s": 2.0, "met_limits": True, "capacity_per_s": 3.0}
    for r in rows:
        r["attainment"] = 0.3
    assert client_samples.knee(rows, limits) == {
        "knee_per_s": 3.0, "met_limits": False, "capacity_per_s": 3.0}


PROM = """# HELP serving_steps_total steps
# TYPE serving_steps_total counter
serving_steps_total {v}
serving_tokens_emitted_total{{tenant="a"}} {t}
serving_tokens_emitted_total{{tenant="b"}} 10
serving_queue_wait_seconds_bucket{{le="0.1"}} 3
serving_queue_wait_seconds_sum 1.5
serving_queue_wait_seconds_count 3
serving_paged_blocks_free {free}
serving_kv_cache_reclaimable_blocks 10
"""


def test_prometheus_text_is_summed_over_label_sets():
    parsed = parse_prometheus(PROM.format(v=4, t=30, free=50))
    assert parsed["serving_tokens_emitted_total"] == 40
    assert "serving_queue_wait_seconds_bucket" not in parsed


def test_deltas_and_gauges():
    ev = evidence(prom_start=PROM.format(v=4, t=30, free=80),
                  prom_end=PROM.format(v=14, t=330, free=50),
                  polls=[(11.0, PROM.format(v=5, t=40, free=70)),
                         (15.0, PROM.format(v=9, t=90, free=30)),
                         (25.0, PROM.format(v=9, t=90, free=0))])
    assert prometheus_delta.read(ev, "serving_tokens_emitted_total",
                                 "serving_steps_total") == pytest.approx(30)
    assert prometheus_delta.read(ev, "no_such_series") is None
    assert prometheus_delta.read(evidence(), "serving_steps_total") is None
    # the poll at 25.0 lies outside the window; min(free + reclaimable)
    # = 40 of 100 blocks, so 60% were in use at the peak
    assert prometheus_gauge.read(
        ev, ["serving_paged_blocks_free",
             "serving_kv_cache_reclaimable_blocks"], reduce="min",
        of_total="engine.paged.0", used=True) == pytest.approx(60.0)
    assert prometheus_gauge.read(ev, "serving_paged_blocks_free",
                                 reduce="max") == 70


def test_train_rate_drops_nothing_but_the_first_stamp():
    ev = evidence(epoch_ends=[100.0, 102.0, 104.0, 106.0],
                  tokens_per_epoch=1000)
    assert train_rate.read(ev) == pytest.approx(500.0)
    assert train_rate.read(evidence(epoch_ends=[1.0],
                                    tokens_per_epoch=10)) is None


def test_train_mfu_by_hand():
    """9.24 GFLOP a token (``costs_test``) x 25,000 tokens/s over four
    chips of 197 TFLOP/s = 29.3%."""
    from chipbench.readers import cost_ratio

    from .rehearse_train_test import CONFIG

    run = SimpleNamespace(device={"platform": "tpu", "kind": "TPU v5 lite"})
    ev = evidence(run=run, epoch_ends=[0.0, 4.0, 8.0], tokens_per_epoch=100_000,
                  sizes=CONFIG, seq_len=4096, chips=4)
    want = 100 * 9.24229632e9 * 25_000 / (4 * 197e12)
    assert cost_ratio.read(ev, "train_mfu") == pytest.approx(want)
    assert want == pytest.approx(29.3, abs=0.1)
    run.device = {"platform": "cpu", "kind": "cpu"}
    assert cost_ratio.read(ev, "train_mfu") is None   # never from a CPU


def test_collective_share_reads_the_trace():
    from chipbench.readers import trace_collectives, trace_idle

    from .trace_reduce_test import hand_trace

    ev = evidence(trace=hand_trace())
    # 15 ms of all-reduce, 10 ms of it exposed, over 50 ms busy
    assert trace_collectives.read(ev) == pytest.approx(30.0)
    assert trace_collectives.read(ev, exposed=True) == pytest.approx(20.0)
    assert trace_idle.read(ev) == pytest.approx(50.0)
    assert trace_collectives.read(evidence(trace=None)) is None


def test_decode_roofline_by_hand():
    """One step of 50 ms that had to read 8.19 GB reads 20% of the
    roofline: 8.19 GB / 819 GB/s = 10 ms."""
    from chipbench import costs
    from chipbench.readers import cost_ratio

    sizes = {"hidden_size": 4096, "num_attention_heads": 32,
             "num_key_value_heads": 8, "intermediate_size": 14336,
             "num_hidden_layers": 16, "vocab_size": 32000}
    weights = costs.weight_bytes(sizes, "bfloat16")
    held = (8.19e9 - weights) / costs.kv_bytes_per_token(sizes)
    trace = SimpleNamespace(program_time=lambda: {
        "jit__step_paged": {"count": 4.0, "seconds": 0.2},
        "jit__extend": {"count": 1.0, "seconds": 0.5}})
    # one request in flight through the whole traced window, holding
    # `held` positions: its prompt, no token yet counted
    first = {"prompt_len": held, "end": "open", "t_end": None,
             "events": [[9.0, 0]]}
    run = SimpleNamespace(device={"platform": "tpu", "kind": "TPU v5 lite"})
    ev = evidence(run=run, trace=trace, trace_window=[10.0, 14.0],
                  samples=[first], sizes=sizes, param_dtype="bfloat16")
    assert cost_ratio.read(ev, "decode_hbm_roofline",
                           match="step_paged") == pytest.approx(20.0)


# ------------------------------------------------------------- serve_mfu
def _config(name):
    import json

    from ._util import REPO
    with open(REPO / "chipbench" / "configs" / f"{name}.json") as fh:
        return json.load(fh)


@pytest.mark.parametrize("name, module, counters, body_params", [
    # 16 blocks of 218,103,808 matrix parameters
    ("mistral-7b-l16-serve", "costs", None, 16 * 218_103_808),
    # attention 149.2 M x 5, the dense MLP, and per expert layer the
    # shared experts, the router and 6 x 0.3 picks on held experts
    ("deepseek-v2-l5-e40-serve", "costs_deepseek_v2", (1000, 300), None)])
def test_serve_mfu_counts_served_tokens_and_the_prompts_that_started(
        name, module, counters, body_params):
    cfg = _config(name)
    run = SimpleNamespace(device={"platform": "tpu", "kind": "TPU v5 lite"})
    ev = evidence(
        run=run, sizes=cfg, chips=1, window=[10.0, 20.0], samples=[
            # first token inside the window: its 128 prompt tokens count,
            # and its 11 tokens (all inside)
            sample(0, due=10.0, first=10.5, last=11.5, n=11),
            # first token before the window: only the 5 tokens inside
            sample(1, due=9.0, first=9.5, last=10.4, n=10,
                   prompt_len=4096)])
    if counters:
        ev.prom_start = ("serving_moe_picks_total 0\n"
                         "serving_moe_held_picks_total 0\n")
        ev.prom_end = (f"serving_moe_picks_total {counters[0]}\n"
                       f"serving_moe_held_picks_total {counters[1]}\n")
        d = costs_deepseek_v2
        body_params = (5 * d.attention_params(cfg) + d.dense_mlp_params(cfg)
                       + 4 * (d.shared_params(cfg) + d.router_params(cfg)
                              + 6 * 0.3 * d.expert_params(cfg)))
        # without the counters: held / scored experts = 40 / 160
        assert d.serve_token_flops(cfg)["body"] < 2.0 * body_params
    head = cfg["hidden_size"] * cfg["vocab_size"]
    by_hand = 2.0 * ((16 + 128) * body_params + (16 + 1) * head)
    assert serve_mfu.read(ev, module) == pytest.approx(
        100 * by_hand / (197e12 * 10.0), rel=1e-9)
    assert 0 < serve_mfu.read(ev, module) < 1
    run.device["platform"] = "cpu"
    assert serve_mfu.read(ev, module) is None


def test_serve_mfu_at_the_readings_of_a_saturated_window_is_under_100():
    """Mistral at 950 served and 4,000 prompt tokens a second: 18%."""
    per = costs.serve_token_flops(_config("mistral-7b-l16-serve"))
    flops = 950 * (per["body"] + per["head"]) + 4000 * per["body"]
    assert 15 < 100 * flops / 197e12 < 20
