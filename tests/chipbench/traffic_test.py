"""The traffic generator: exact repeats for a seed, another order for
another seed, the same work for every seed, every prompt on the grid."""
import json
from collections import Counter

import pytest

from chipbench import traffic

from ._util import REPO

MIXES = sorted(p.stem for p in (REPO / "chipbench" / "traffic").glob("*.json")
               if json.loads(p.read_text())["driver"] == "serve")


def load(mix):
    with open(REPO / "chipbench" / "traffic" / f"{mix}.json") as fh:
        return json.load(fh)


@pytest.mark.parametrize("mix", MIXES)
def test_schedule_repeats_for_a_seed_and_differs_for_another(mix):
    spec = load(mix)
    a = traffic.make_schedule(spec, 2**31 + 5, 45)
    b = traffic.make_schedule(spec, 2**31 + 5, 45)
    c = traffic.make_schedule(spec, 6, 45)
    assert a == b
    assert a["requests"] != c["requests"]
    assert traffic.prompt_tokens(7, 3, 64, 32000) == \
        traffic.prompt_tokens(7, 3, 64, 32000)
    assert traffic.prompt_tokens(7, 3, 64, 32000) != \
        traffic.prompt_tokens(8, 3, 64, 32000)


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_offers_the_same_work(mix):
    """The multisets of sizes and of gaps come from the mix's own
    ``shape_seed``, for the lead-in and the window apart: two seeds
    differ by order alone, so no run carries more work than another."""
    spec = load(mix)
    runs = [traffic.make_schedule(spec, seed, 45)["requests"]
            for seed in (1, 2, 2**31 + 3)]
    for timed in (False, True):
        sizes = [Counter((r["prompt_len"], r["max_new_tokens"])
                         for r in reqs if r["timed"] == timed)
                 for reqs in runs]
        assert sizes[0] == sizes[1] == sizes[2]
    orders = [[r["prompt_len"] for r in reqs] for reqs in runs]
    assert orders[0] != orders[1] != orders[2]


@pytest.mark.parametrize("mix", MIXES)
def test_every_prompt_is_on_the_grid_that_warm_up_covers(mix):
    spec = load(mix)
    for part in (spec, dict(spec, **spec["rehearse"])):
        grid = set(traffic.grid_lengths(part["prompt_tokens"]))
        out = part["output_tokens"]
        for seed in range(5):
            for r in traffic.make_schedule(part, seed, 60)["requests"]:
                assert r["prompt_len"] in grid
                assert out["min"] <= r["max_new_tokens"] <= out["max"]
        assert max(grid) + out["max"] <= 4096


def test_lead_in_requests_are_not_timed_and_rate_is_kept():
    spec = load(MIXES[0])
    sched = traffic.make_schedule(spec, 9, 45)
    start, end = sched["window"]
    assert start == spec["lead_in_s"] and end == start + 45
    for r in sched["requests"]:
        assert r["timed"] == (r["due_s"] >= start)
        assert r["due_s"] < end
    assert sum(r["timed"] for r in sched["requests"]) >= 100
    rate = len(sched["requests"]) / end
    assert abs(rate - spec["arrivals"]["rate_per_s"]) \
        < 0.25 * spec["arrivals"]["rate_per_s"]
    dues = [r["due_s"] for r in sched["requests"]]
    assert dues == sorted(dues)
