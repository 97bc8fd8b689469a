"""Reader ``cost_ratio_h1``: what the shapes of the family ``falcon_h1``
say the work needs (``chipbench/costs_falcon_h1.py``), over what the
device took. All from a traced run; ``match`` names the programs.

- ``decode_step_roofline``: the bytes a decode step must move -- the
  weights outside the embedding, the K and V of the positions its rows
  held (the client's samples) and the state of the rows it updated, read
  once and written once -- over the peak memory bandwidth, over the
  step's device time. Bytes-bound: at most 64 rows.
- ``update_roofline``: the larger of the state updates' bytes over the
  peak bandwidth and their FLOPs over the peak rate, over the device
  time under ``scope`` (``elephas.ssm.update``) a step.
- ``scan_roofline``: the same two bounds of the chunk scan, a prompt
  chunk, over the device time under ``scope`` (``elephas.ssm.scan``) an
  execution of the chunk programs.
- ``step_share``: device time under ``scope`` inside the step over the
  step's, in percent: the mixer's share of a step.

Rows updated a step are the program's count, ``serving_ssm_row_updates_
total`` over ``serving_decode_steps_total`` (window deltas; both rise by
one dispatch at a time, so the quotient is live rows x layers, at most
slots x layers): only the rows the engine stepped are counted, each once
read and once written. Tokens scanned a chunk are ``serving_ssm_scan_
tokens_total`` over ``serving_prefill_chunks_total``. Which of the two
bounds holds goes to the log.

None off the TPU, without a trace, without the counters or the scope
(the parent commit has neither).
"""
from chipbench import costs, costs_falcon_h1 as h1, device
from chipbench.evidence import parse_prometheus
from chipbench.readers import (cost_ratio, trace_program_time,
                               trace_scope_time)


def per_dispatch(evidence, counter: str, dispatches: str):
    """Window delta of ``counter`` over window delta of ``dispatches``,
    or None where either series is missing or nothing was dispatched."""
    if evidence.prom_start is None or evidence.prom_end is None:
        return None
    before = parse_prometheus(evidence.prom_start)
    after = parse_prometheus(evidence.prom_end)
    if counter not in after or dispatches not in after:
        return None
    count = after[dispatches] - before.get(dispatches, 0.0)
    if not count:
        return None
    return (after[counter] - before.get(counter, 0.0)) / count


def _bound(cost: dict, peak: dict, what: str, per: str) -> float:
    """Seconds the chip needs at its peaks; the parts go to the log so
    that a reading over 100% leaves them there."""
    by_flops = cost["flops"] / peak["bf16_flops_per_s"]
    by_bytes = cost["bytes"] / peak["hbm_bytes_per_s"]
    device.log("cost_ratio_h1", f"{what}: {cost['flops']:.3e} FLOPs = "
               f"{by_flops * 1e6:.1f} us, {cost['bytes']:.3e} bytes = "
               f"{by_bytes * 1e6:.1f} us {per}; bound by "
               f"{'compute' if by_flops > by_bytes else 'bytes'}")
    return max(by_flops, by_bytes)


def read(evidence, what: str, match: str, scope: str = None):
    if evidence.run.device["platform"] != "tpu":
        return None
    trace = evidence.trace
    if trace is None or not evidence.trace_window:
        return None
    count, seconds = trace_program_time.matching(trace, match)
    if not count:
        return None
    peak = costs.peaks(evidence.run.device["kind"])
    sizes = evidence.sizes
    under = (trace_scope_time.scope_seconds(evidence, scope, match)
             if scope else None)
    if what == "step_share":
        return 100.0 * under / seconds if under else None
    if what == "scan_roofline":
        tokens = per_dispatch(evidence, "serving_ssm_scan_tokens_total",
                              "serving_prefill_chunks_total")
        if not under or not tokens:
            return None
        need = _bound(h1.scan_cost(sizes, tokens,
                                   int(sizes["num_hidden_layers"])),
                      peak, f"scan of {tokens:.0f} tokens x layers",
                      "a chunk")
        device.log("cost_ratio_h1", f"{1e3 * under / count:.3f} ms a "
                   f"chunk under {scope} over {count:g} chunks")
        return 100.0 * need / (under / count)
    updates = per_dispatch(evidence, "serving_ssm_row_updates_total",
                           "serving_decode_steps_total")
    if updates is None:
        return None
    if what == "update_roofline":
        if not under:
            return None
        need = _bound(h1.update_cost(sizes, updates), peak,
                      f"{updates:.1f} row updates", "a step")
        device.log("cost_ratio_h1", f"{1e3 * under / count:.3f} ms a "
                   f"step under {scope} over {count:g} steps")
        return 100.0 * need / (under / count)
    if what == "decode_step_roofline":
        held = cost_ratio.kv_tokens_held(evidence)
        needed = h1.decode_step_bytes(sizes, evidence.param_dtype, held,
                                      updates)
        device.log("cost_ratio_h1", f"step: {needed:.4e} bytes "
                   f"({held:.0f} positions held, {updates:.1f} row "
                   f"updates) = {1e3 * needed / peak['hbm_bytes_per_s']:.3f}"
                   f" ms at the peak; {1e3 * seconds / count:.3f} ms a "
                   f"step over {count:g} steps")
        return 100.0 * (needed / peak["hbm_bytes_per_s"]) / (
            seconds / count)
    raise ValueError(f"unknown cost ratio {what!r}")
