"""Reader ``cost_ratio_dsv2``: what the shapes of the family
``deepseek_v2`` say a decode step needs (``chipbench/
costs_deepseek_v2.py``), over what the device took.

Three shares, all per execution of the programs ``match`` names, all
from a traced run:

- ``decode_step_roofline``: the bytes a step must read -- the matrices
  outside the routed experts, the weights of the experts its live rows
  TOUCHED (the program's counter ``serving_moe_experts_touched_total``
  over ``serving_steps_total``, window deltas) and the latents of the
  positions its rows held (the client's samples) -- over the peak
  memory bandwidth, over the step's device time. Bytes-bound: at most
  64 rows, 2 FLOPs a weight byte a row.
- ``experts_roofline``: the touched experts' bytes over the peak
  bandwidth, over the device time of the operations whose origin
  matches ``scope`` inside the step (``trace_scope_time``: the scope's
  own operations and the grouped matmuls, whose origin XLA renames).
  Bytes-bound for the same reason.
- ``attend_roofline``: the larger of the absorbed attention's FLOPs
  over the peak bf16 rate and its bytes over the peak bandwidth, over
  the device time under ``scope``; which of the two bounds it goes to
  the log (at DeepSeek-V2's sizes they are within 1% of each other:
  128 heads read one 1,152-byte vector a position).

None off the TPU, without a trace, without the counters or the scope
(the parent commit has neither).
"""
from chipbench import costs, costs_deepseek_v2 as dsv2, device
from chipbench.evidence import parse_prometheus
from chipbench.readers import (cost_ratio, trace_program_time,
                               trace_scope_time)


def experts_touched_per_step(evidence):
    """Held experts touched per decode dispatch, all expert layers added
    up: delta ``serving_moe_experts_touched_total`` / delta
    ``serving_steps_total`` over the window."""
    if evidence.prom_start is None or evidence.prom_end is None:
        return None
    before = parse_prometheus(evidence.prom_start)
    after = parse_prometheus(evidence.prom_end)
    name = "serving_moe_experts_touched_total"
    if name not in after or "serving_steps_total" not in after:
        return None
    steps = after["serving_steps_total"] - before.get(
        "serving_steps_total", 0.0)
    return (after[name] - before.get(name, 0.0)) / steps if steps else None


def read(evidence, what: str, match: str, scope: str = None):
    if evidence.run.device["platform"] != "tpu":
        return None
    trace = evidence.trace
    if trace is None or not evidence.trace_window:
        return None
    peak = costs.peaks(evidence.run.device["kind"])
    count, seconds = trace_program_time.matching(trace, match)
    if not count:
        return None
    sizes, dtype = evidence.sizes, evidence.param_dtype
    bandwidth = peak["hbm_bytes_per_s"]
    if what == "attend_roofline":
        under = trace_scope_time.scope_seconds(evidence, scope, match)
        if not under:
            return None
        cost = dsv2.attend_cost(sizes, cost_ratio.kv_tokens_held(evidence))
        by_flops = cost["flops"] / peak["bf16_flops_per_s"]
        by_bytes = cost["bytes"] / bandwidth
        device.log("cost_ratio_dsv2", f"attend: {cost['flops']:.3e} FLOPs "
                   f"= {by_flops * 1e6:.1f} us, {cost['bytes']:.3e} bytes "
                   f"= {by_bytes * 1e6:.1f} us a step; bound by "
                   f"{'compute' if by_flops > by_bytes else 'bytes'}; "
                   f"{1e3 * under / count:.3f} ms a step under {scope}")
        return 100.0 * max(by_flops, by_bytes) / (under / count)
    touched = experts_touched_per_step(evidence)
    if touched is None:
        return None
    if what == "experts_roofline":
        under = trace_scope_time.scope_seconds(evidence, scope, match)
        if not under:
            return None
        needed = dsv2.experts_bytes(sizes, dtype, touched)
        # the parts, so that a reading over 100% leaves them in the log
        device.log("cost_ratio_dsv2", f"experts: {touched:.2f} experts "
                   f"touched a step = {needed:.4e} bytes = "
                   f"{1e3 * needed / bandwidth:.3f} ms at the peak; "
                   f"{1e3 * under / count:.3f} ms a step under {scope} "
                   f"over {count:g} steps")
        return 100.0 * (needed / bandwidth) / (under / count)
    if what == "decode_step_roofline":
        needed = dsv2.decode_step_bytes(
            sizes, dtype, cost_ratio.kv_tokens_held(evidence), touched)
        return 100.0 * (needed / bandwidth) / (seconds / count)
    raise ValueError(f"unknown cost ratio {what!r}")
