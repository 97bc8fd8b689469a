"""Reader ``cost_ratio``: what the shapes say the work needs, over what
the device took (``chipbench/costs.py`` over a measured time).

``what``:

- ``decode_hbm_roofline``: the bytes one decode step must read -- the
  weights once plus the K and V of the positions the batch's rows held
  during the trace (from the client's samples: prompt plus tokens
  received so far, over the requests in flight) -- over the peak memory
  bandwidth, over the device time of one execution of the programs
  ``match`` names. Bytes-bound: a decode step does 2 FLOPs per weight
  byte per row, far under the ridge.
- ``train_mfu``: model FLOPs per token (no recomputation counted) times
  tokens per second, over chips times the peak. An end-to-end
  utilization, not a kernel's roofline share.
"""
from chipbench import costs
from chipbench.readers import trace_program_time


def kv_tokens_held(evidence, instants: int = 20) -> float:
    """Mean over the traced window of the positions held by requests in
    flight: for each, its prompt plus the tokens it had received."""
    start, end = evidence.trace_window
    total = 0.0
    for k in range(instants):
        at = start + (end - start) * (k + 0.5) / instants
        for s in evidence.samples:
            if not s["events"] or s["events"][0][0] > at:
                continue
            if s["end"] == "done" and s["t_end"] is not None \
                    and s["t_end"] < at:
                continue
            total += s["prompt_len"] + sum(
                count for t, count in s["events"] if t <= at)
    return total / instants


def read(evidence, what: str, match: str = None):
    kind = evidence.run.device["kind"]
    if evidence.run.device["platform"] != "tpu":
        return None
    peak = costs.peaks(kind)
    if what == "decode_hbm_roofline":
        trace = evidence.trace
        if trace is None or not evidence.trace_window:
            return None
        count, seconds = trace_program_time.matching(trace, match)
        if not count:
            return None
        needed = costs.decode_step_bytes(
            evidence.sizes, evidence.param_dtype, kv_tokens_held(evidence))
        return 100.0 * (needed / peak["hbm_bytes_per_s"]) / (seconds / count)
    if what == "train_mfu":
        from chipbench.readers import train_rate

        rate = train_rate.read(evidence)
        if rate is None:
            return None
        flops = costs.train_flops_per_token(evidence.sizes,
                                            evidence.seq_len)
        return 100.0 * flops * rate / (
            evidence.chips * peak["bf16_flops_per_s"])
    raise ValueError(f"unknown cost ratio {what!r}")
