"""The traffic generator: exact repeats for a seed, another order for
another seed, the same work for every seed, every prompt on the grid."""
import json
from collections import Counter

import pytest

from chipbench import traffic

from ._util import REPO

MIXES = sorted(p.stem for p in (REPO / "chipbench" / "traffic").glob("*.json")
               if json.loads(p.read_text())["driver"].startswith("serve"))


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


@pytest.mark.parametrize("part", ["lead-in", "window"])
def test_rotate_keeps_one_arrangement_and_begins_it_elsewhere(part):
    """With ``"order": "rotate"`` every seed offers the same cyclic
    sequence of (gap, sizes), begun at another request: neighbours stay
    neighbours, so the same answers meet the same prompts."""
    spec = dict(load(MIXES[0]), order="rotate")
    timed = part == "window"

    def sequence(seed):
        reqs = [r for r in traffic.make_schedule(spec, seed, 45)["requests"]
                if r["timed"] == timed]
        dues = [r["due_s"] for r in reqs] + [
            spec["lead_in_s"] + (45 if timed else 0)]
        return [(round(b - a, 9), r["prompt_len"], r["max_new_tokens"])
                for r, a, b in zip(reqs, dues, dues[1:])]

    one, other = sequence(1), sequence(2**31 + 9)
    assert one != other and sorted(one) == sorted(other)
    k = other.index(one[0])
    while other[k:] + other[:k] != one:       # a repeated triple: go on
        k = other.index(one[0], k + 1)
    shuffled = dict(spec, order="shuffle")
    assert sorted(
        (r["prompt_len"], r["max_new_tokens"])
        for r in traffic.make_schedule(shuffled, 1, 45)["requests"]) == \
        sorted((r["prompt_len"], r["max_new_tokens"])
               for r in traffic.make_schedule(spec, 1, 45)["requests"])


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


def bench():
    with open(REPO / "BENCHMARK.json") as fh:
        return json.load(fh)


@pytest.mark.parametrize("seed", [9, 2**31 + 7, 2**31 + 2**20])
@pytest.mark.parametrize("mix", MIXES)
def test_lead_in_and_window_each_offer_the_nominal_rate(mix, seed):
    """Both parts hold exactly ``round(rate x duration)`` requests,
    whatever the ``shape_seed`` and the seed (gaps drawn until they
    passed the duration once let a 40-s lead-in offer 7.4 req/s in a
    6.5 req/s file); lead-in requests are not timed; dues rise and end
    inside the window."""
    spec = load(mix)
    seconds = bench()["run_seconds"]
    rate, lead_in = spec["arrivals"]["rate_per_s"], spec["lead_in_s"]
    sched = traffic.make_schedule(spec, seed, seconds)
    start, end = sched["window"]
    assert start == lead_in and end == start + seconds
    for r in sched["requests"]:
        assert r["timed"] == (r["due_s"] >= start)
        assert r["due_s"] < end
    timed = sum(r["timed"] for r in sched["requests"])
    assert timed == round(rate * seconds)
    assert len(sched["requests"]) - timed == round(rate * lead_in)
    dues = [r["due_s"] for r in sched["requests"]]
    assert dues == sorted(dues)
    # the first request of each part is due at the part's start
    assert dues[0] == 0.0
    assert min(r["due_s"] for r in sched["requests"] if r["timed"]) \
        == pytest.approx(start)


@pytest.mark.parametrize("rate, duration, count", [
    (6.5, 40, 260), (1.6, 51, 82), (0.01, 5, 1), (3.0, 0, 0),
    (2.5, 0.5, 1)])
def test_a_part_holds_the_rounded_count_and_at_least_one(rate, duration,
                                                         count):
    spec = dict(load(MIXES[0]), lead_in_s=0.0)
    sched = traffic.make_schedule(spec, 3, duration, rate_per_s=rate)
    assert len(sched["requests"]) == count


def cells_reporting(metric):
    data = bench()
    entry = next(m for m in data["end_to_end"] if m["name"] == metric)
    return [w for w in data["workloads"]
            if "workloads" not in entry or w["name"] in entry["workloads"]]


@pytest.mark.parametrize(
    "cell", cells_reporting("tpot_p90_ms"), ids=lambda w: w["name"])
def test_a_cell_judged_by_a_90th_percentile_times_a_hundred_requests(cell):
    """Ten requests have to lie beyond the percentile."""
    spec = load(cell["traffic"])
    sched = traffic.make_schedule(spec, 1, bench()["run_seconds"])
    assert sum(r["timed"] for r in sched["requests"]) >= 100


@pytest.mark.parametrize(
    "cell", cells_reporting("serve_tokens_per_s"), ids=lambda w: w["name"])
def test_a_cut_cell_says_its_rate_and_leads_in_four_requests_a_slot(cell):
    """A throughput cell is cut at the window's end, says why its rate
    and lead-in are what they are, and its lead-in offers every slot
    four requests at least (five turnovers by the time a row lives)."""
    spec = load(cell["traffic"])
    assert spec["cut"] is True and spec["drain_s"] == 0
    assert spec["rate_why"] and spec["lead_in_why"]
    with open(REPO / next(c["file"] for c in bench()["configs"]
                          if c["name"] == cell["config"])) as fh:
        slots = json.load(fh)["engine"]["max_slots"]
    sched = traffic.make_schedule(spec, 1, bench()["run_seconds"])
    lead_in = sum(not r["timed"] for r in sched["requests"])
    assert lead_in >= 4 * slots
