"""Reader ``client_samples``: latency and throughput as the client saw
them (the load generator's samples, host clock).

``what``: ``ttft`` (first token minus the instant the request was DUE),
``tpot`` (per request, (last - first) / (tokens - 1)), ``late`` (sent
minus due: how late the generator ran), each over the requests due in
the window, at quantile ``q``, in milliseconds; or ``tokens_per_s``
(output tokens that reached the client inside the window, over it).
"""
import statistics


def quantile(values, q: float) -> float:
    """Linear-interpolated quantile (q in [0, 1]) of a non-empty list."""
    ordered = sorted(values)
    if len(ordered) == 1:
        return ordered[0]
    at = q * (len(ordered) - 1)
    lo = int(at)
    hi = min(lo + 1, len(ordered) - 1)
    return ordered[lo] + (ordered[hi] - ordered[lo]) * (at - lo)


def ttft_ms(sample):
    if not sample["events"]:
        return None
    return (sample["events"][0][0] - sample["due"]) * 1e3


def tpot_ms(sample):
    n = sum(count for _, count in sample["events"])
    if n < 2 or len(sample["events"]) < 2:
        return None
    return (sample["events"][-1][0] - sample["events"][0][0]) / (n - 1) * 1e3


def late_ms(sample):
    if sample["sent"] is None:
        return None
    return (sample["sent"] - sample["due"]) * 1e3


_PER_REQUEST = {"ttft": ttft_ms, "tpot": tpot_ms, "late": late_ms}


def tokens_in(samples, start: float, end: float) -> int:
    return sum(count for s in samples for t, count in s["events"]
               if start <= t < end)


def read(evidence, what: str, q: float = None):
    samples = evidence.samples
    if not samples or not evidence.window:
        return None
    start, end = evidence.window
    if what == "tokens_per_s":
        return tokens_in(samples, start, end) / (end - start)
    values = [v for v in (_PER_REQUEST[what](s) for s in samples
                          if s["timed"]) if v is not None]
    return quantile(values, q) if values else None


def sweep_row(samples, start: float, end: float, limits: dict) -> dict:
    """One step of the knee sweep (see ``drivers/serve.py`` ``sweep``).
    Attainment is over the requests due at least ``settle_s`` before the
    cut, so that each had time to show its first token; a request with no
    first token by the cut, or refused, misses. Requests of a lead-in
    (not ``timed``) count in the backlog and in the tokens, not in the
    attainment."""
    settle = float(limits.get("settle_s", 5.0))
    judged = [s for s in samples if s["timed"] and s["due"] < end - settle]
    met = 0
    for s in judged:
        ttft, tpot = ttft_ms(s), tpot_ms(s)
        if (ttft is not None and ttft <= limits["ttft_ms"]
                and (tpot is None or tpot <= limits["tpot_ms"])
                and s["end"] in ("done", "open")):
            met += 1

    def backlog(at):
        """Requests due by ``at`` that had no first token by then."""
        return sum(1 for s in samples if s["due"] <= at
                   and not (s["events"] and s["events"][0][0] <= at))

    ttfts = [v for v in map(ttft_ms, judged) if v is not None]
    tpots = [v for v in map(tpot_ms, judged) if v is not None]
    row = {"requests": len(samples), "judged": len(judged),
           "attainment": met / len(judged) if judged else None,
           "backlog_mid": backlog((start + end) / 2),
           "backlog_end": backlog(end),
           "tokens_per_s": tokens_in(samples, start, end) / (end - start),
           "refused": sum(s["end"] in ("refused", "error")
                          for s in samples)}
    for name, values in (("ttft", ttfts), ("tpot", tpots)):
        if values:
            row[f"{name}_p50_ms"] = statistics.median(values)
            row[f"{name}_p90_ms"] = quantile(values, 0.9)
    return row


def knee(rows, limits: dict):
    """The knee of a sweep: the highest swept rate at which at least
    ``limits["share"]`` of the judged requests met both limits and the
    backlog did not grow (at the step's end no larger than at its
    middle, give or take ``slack`` requests: two, or a twentieth of the
    step's requests, since a Poisson step ends a request or two off by
    chance). ``capacity_per_s`` is the highest rate with no growing
    backlog, limits or not; if no rate meets the limits it is the knee,
    and ``met_limits`` says so."""
    def steady(row):
        slack = max(2, row["requests"] // 20)
        return row["backlog_end"] <= row["backlog_mid"] + slack

    flat = [r["rate_per_s"] for r in rows if steady(r)]
    ok = [r["rate_per_s"] for r in rows if steady(r)
          and (r["attainment"] or 0.0) >= limits["share"]]
    capacity = max(flat) if flat else None
    return {"knee_per_s": max(ok) if ok else capacity,
            "met_limits": bool(ok), "capacity_per_s": capacity}
