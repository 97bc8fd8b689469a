"""The one traffic generator: a traffic file's parameters -> a schedule.

Standard library only (the load-generator child imports it and must never
touch JAX). Everything is drawn from seeds:

- the *shape* of the traffic -- the multiset of (prompt length, output
  length) pairs and the multiset of inter-arrival gaps, for the lead-in
  and for the window apart -- comes from the traffic file's own
  ``shape_seed``, so every ``--seed`` offers exactly the same timed work
  at exactly the same mean rate;
- ``--seed`` orders both multisets and draws the token values, so runs
  with different seeds see different prompts in a different order without
  one run carrying 10% more tokens than another (the heavy tails of the
  lognormals would otherwise decide the spread between seeds). ``order``
  says how: ``"shuffle"`` (the default) shuffles gaps and sizes apart;
  ``"rotate"`` keeps the one arrangement the ``shape_seed`` drew and
  begins it at a request the seed picks -- for a cell judged by a tail,
  where WHICH short answer meets WHICH long prompt decides the 90th
  percentile and a shuffle moved it by +-10% from seed to seed.

A traffic file for a serving mix holds::

    {"driver": "serve",
     "arrivals": {"rate_per_s": 3.0, "cv": 1.0},
     "prompt_tokens": {"median": 512, "sigma": 0.8,
                       "grid": 128, "min": 128, "max": 2048},
     "output_tokens": {"median": 128, "sigma": 0.7, "min": 8, "max": 512},
     "shape_seed": 1, "order": "shuffle", "lead_in_s": 10, "drain_s": 45,
     "cut": false}

``arrivals.cv`` is the coefficient of variation of the gaps: 1 is a
Poisson process, above 1 is bursty (gamma gaps, BurstGPT-like).
``grid`` snaps a drawn length to a multiple of itself -- the engine
compiles a program per prompt tail, so warm-up can only cover a grid.
"""
import math
import random

__all__ = ["draw_length", "grid_lengths", "make_schedule", "prompt_tokens"]


def draw_length(rng: random.Random, dist: dict) -> int:
    """One lognormal length, snapped to ``grid`` (if any) and clipped."""
    value = rng.lognormvariate(math.log(dist["median"]), dist["sigma"])
    grid = int(dist.get("grid", 1))
    value = int(round(value / grid)) * grid
    return max(int(dist["min"]), min(int(dist["max"]), value))


def grid_lengths(dist: dict) -> list:
    """Every length :func:`draw_length` can return for a gridded
    distribution -- what warm-up has to cover."""
    grid = int(dist["grid"])
    lo, hi = int(dist["min"]), int(dist["max"])
    if lo % grid or hi % grid:
        raise ValueError(f"min {lo} and max {hi} must lie on the grid {grid}")
    return list(range(lo, hi + 1, grid))


def _part(rng: random.Random, traffic: dict, arrivals: dict,
          duration: float):
    """``round(rate * duration)`` gaps (at least one), scaled so that
    they fill exactly ``duration``, and one pair of sizes for each: a
    part offers its nominal rate whatever the ``shape_seed`` (gaps drawn
    until they passed the duration let one 40-s lead-in offer 7.4 req/s
    in a 6.5 req/s file: PERF.md section 6, PR 31)."""
    if duration <= 0:
        return [], []
    rate = float(arrivals["rate_per_s"])
    cv = float(arrivals.get("cv", 1.0))
    shape = 1.0 / (cv * cv)
    scale = 1.0 / (rate * shape)
    count = max(1, round(rate * duration))
    gaps = [rng.gammavariate(shape, scale) for _ in range(count)]
    total = sum(gaps)
    gaps = [gap * duration / total for gap in gaps]
    sizes = [(draw_length(rng, traffic["prompt_tokens"]),
              draw_length(rng, traffic["output_tokens"])) for _ in gaps]
    return gaps, sizes


def make_schedule(traffic: dict, seed: int, seconds: float,
                  rate_per_s: float = None) -> dict:
    """The requests of one run: ``lead_in_s`` seconds of untimed traffic,
    then ``seconds`` of timed traffic, from one arrival process. The
    lead-in and the window each have their own multiset of gaps and
    sizes, so the timed requests are the same set for every seed.

    Returns ``{"requests": [{"i", "due_s", "prompt_len", "max_new_tokens",
    "timed"}], "window": [start_s, end_s]}`` with times relative to the
    schedule's start. ``rate_per_s`` overrides the file's rate (the sweep).
    """
    arrivals = dict(traffic["arrivals"])
    if rate_per_s is not None:
        arrivals["rate_per_s"] = rate_per_s
    lead_in = float(traffic.get("lead_in_s", 0.0))
    shape_rng = random.Random(int(traffic["shape_seed"]))
    order_rng = random.Random(int(seed))
    requests, begin = [], 0.0
    for duration, timed in ((lead_in, False), (float(seconds), True)):
        gaps, sizes = _part(shape_rng, traffic, arrivals, duration)
        if traffic.get("order", "shuffle") == "rotate":
            # one arrangement for every seed, begun at another request
            k = order_rng.randrange(len(gaps)) if gaps else 0
            gaps, sizes = gaps[k:] + gaps[:k], sizes[k:] + sizes[:k]
        else:
            order_rng.shuffle(gaps)
            order_rng.shuffle(sizes)
        due = begin
        for gap, (prompt_len, new) in zip(gaps, sizes):
            requests.append({"i": len(requests), "due_s": due,
                             "prompt_len": prompt_len,
                             "max_new_tokens": new, "timed": timed})
            due += gap
        begin += duration
    return {"requests": requests, "window": [lead_in, lead_in + seconds]}


def prompt_tokens(seed: int, i: int, length: int, vocab: int) -> list:
    """Request ``i``'s prompt: uniform over the vocabulary (id 0 left
    out), from the run's seed -- the parent regenerates any prompt it
    needs for the correctness check without shipping them around."""
    rng = random.Random((int(seed) << 24) ^ int(i))
    return rng.choices(range(1, vocab), k=length)
