"""One decode step in flight: plain stepping dispatches step k+1 before
it reads step k's tokens, and every request's tokens must still be the
synchronous loop's, whatever joins or leaves while a step is in the air.
The engines and the oracle are those of ``test_serving_engine.py``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elephas_tpu.models.transformer import init_params
from elephas_tpu.serving_engine import DecodeEngine

from ._greedy import greedy_reference as _ref
from .models._transformer_util import DECODE_STEP, PREFILL
from .test_serving_engine import _config


@pytest.fixture(scope="module")
def model():
    config = _config()
    params = init_params(config, jax.random.PRNGKey(0))
    return params, config


# -------------------------------------------- one decode step in flight
# Plain stepping dispatches step k+1 before it reads step k's tokens.
# Tokens must stay the synchronous loop's, row for row, whatever joins
# or leaves while a step is in the air. (Prompts of 5 and 6 tokens and
# references of 12 throughout: every engine and every reference length
# is a compile.)
_KINDS = {"contiguous": {}, "paged": {"paged": (40, 4)}}


@pytest.fixture(params=sorted(_KINDS))
def kind(request):
    return _KINDS[request.param]


def _drain(eng, streamed=None):
    while eng.pending:
        for rid, toks in eng.step().items():
            if streamed is not None:
                streamed.setdefault(rid, []).extend(toks)


def _prompts(seed, *lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 64, n) for n in lengths]


@pytest.mark.parametrize("mode", ["greedy", "seeded"])
def test_one_ahead_parity_joining_and_leaving_mid_flight(model, kind,
                                                         mode):
    """Requests of different budgets join (queued behind full slots,
    submitted between steps) and leave while a step is in flight: every
    row decodes as it does alone."""
    params, config = model
    prompts = _prompts(31, 5, 6, 6, 5, 6, 5, 6)
    budgets = [3, 11, 6, 2, 9, 5, 7]
    sampling = [dict(temperature=0.9, seed=100 + i) if mode == "seeded"
                else {} for i in range(len(prompts))]
    eng = DecodeEngine(params, config, max_slots=3, **kind)
    rids = []
    for i, (p, n) in enumerate(zip(prompts, budgets)):
        rids.append(eng.submit(p, n, **sampling[i]))
        if i >= 3:
            eng.step()          # the later ones arrive mid-flight
    _drain(eng)
    solo = DecodeEngine(params, config, max_slots=1, **kind)
    for i, (p, n) in enumerate(zip(prompts, budgets)):
        if mode == "seeded":
            rid = solo.submit(p, n, **sampling[i])
            _drain(solo)
            ref = solo.result(rid)
        else:
            ref = _ref(params, config, p, 12)[:n]
        assert eng.result(rids[i]) == ref, (mode, i)
    # known budgets, no eos: nothing in flight was ever dropped
    assert eng.stats["surplus_rows"] == 0


def test_one_ahead_unseeded_rows_sample_alike_whoever_leaves_mid_flight(
        model, kind):
    """Unseeded sampled rows draw from the engine key, which travels
    device to device and is split once a step: a row's draw depends on
    the number of splits, its slot and its own logits only. So rows
    that leave mid-flight (budgets 4, 9, 7) sample the prefixes of what
    they sample when every budget is equal, no row leaves before the
    others and nothing is dispatched ahead for a row that is gone."""
    params, config = model
    prompts = _prompts(37, 5, 6, 6)
    outs = []
    for budgets in ([4, 9, 7], [9, 9, 9]):
        eng = DecodeEngine(params, config, max_slots=3, temperature=0.8,
                           seed=5, **kind)
        rids = [eng.submit(p, n) for p, n in zip(prompts, budgets)]
        _drain(eng)
        outs.append([eng.result(r) for r in rids])
        assert [len(o) for o in outs[-1]] == budgets
        assert eng.stats["surplus_rows"] == 0
    assert outs[0] == [o[:n] for o, n in zip(outs[1], [4, 9, 7])]
    assert len({tuple(o[:4]) for o in outs[1]}) == 3   # sampled, not argmax


@pytest.mark.parametrize("slots", [1, 2])
def test_one_ahead_eos_surplus_is_dropped_and_blocks_are_reusable(
        model, kind, slots):
    """eos with the row's next step already in flight: the surplus
    token is neither returned, stored nor streamed, the counter has it,
    and a request admitted at once into the freed slot -- and, paged,
    into the freed blocks: the pool holds no others -- decodes as it
    does alone. With one slot the surplus step is abandoned (no live
    row is left), with two it is collected for the other row."""
    params, config = model
    prompt, *others = _prompts(3, 6, *[5] * slots)
    full = _ref(params, config, prompt, 12)
    cut = next(i for i, t in enumerate(full) if i >= 2
               and t not in full[:i])
    eos = full[cut]
    # blocks for exactly `slots` requests of 6 + 12 positions
    tight = {"paged": (1 + slots * 5, 4)} if kind else {}
    eng = DecodeEngine(params, config, max_slots=slots, eos_id=eos,
                       **tight)
    r0 = eng.submit(prompt, 12)
    rest = [eng.submit(p, 12) for p in others]   # the last one queued
    streamed = {}
    _drain(eng, streamed)
    assert eng.result(r0) == streamed[r0] == full[:cut]
    assert eos not in streamed[r0]
    steps = [cut]                # decode steps each request was read for
    for rid, p in zip(rest, others):
        ref = _ref(params, config, p, 12)
        steps.append(ref.index(eos) if eos in ref else 11)
        if eos in ref:
            ref = ref[:ref.index(eos)]
        assert eng.result(rid) == streamed.get(rid, []) == ref
    assert eng.stats["surplus_rows"] >= 1
    assert eng._ahead is None and not eng.pending
    if slots == 1:
        # each abandoned step gave the engine key back: it stands where
        # the same tokens retired by budget leave it, so later unseeded
        # rows sample as if no surplus step had been dispatched
        plain = DecodeEngine(params, config, max_slots=1, **tight)
        for p, n in zip([prompt] + others, steps):
            plain.submit(p, n + 1)
        _drain(plain)
        assert plain.stats["surplus_rows"] == 0
        assert (np.asarray(eng._key) == np.asarray(plain._key)).all()


def test_one_ahead_cancel_with_a_step_in_flight(model, kind):
    params, config = model
    pa, pb, pc = _prompts(41, 6, 6, 5)
    eng = DecodeEngine(params, config, max_slots=2, **kind)
    ra, rb = eng.submit(pa, 10), eng.submit(pb, 10)
    eng.step()
    eng.step()
    assert eng._ahead is not None        # rb's next token is in flight
    assert eng.cancel(rb) is True
    rc = eng.submit(pc, 6)               # takes rb's slot (and blocks)
    streamed = {}
    _drain(eng, streamed)
    assert rb not in streamed
    assert eng.result(ra) == _ref(params, config, pa, 12)[:10]
    assert eng.result(rc) == _ref(params, config, pc, 12)[:6]
    assert eng.result(rb) is None
    assert eng.stats["surplus_rows"] == 1
    # cancelling the only live row leaves nothing to wait for
    rd = eng.submit(pa, 10)
    eng.step()
    assert eng.cancel(rd) is True and not eng.pending
    re_ = eng.submit(pb, 5)
    _drain(eng)
    assert eng.result(re_) == _ref(params, config, pb, 12)[:5]
    assert eng.stats["surplus_rows"] == 2 and eng._ahead is None


def test_one_ahead_deadline_with_a_step_in_flight(model, kind):
    params, config = model
    pa, pb = _prompts(43, 6, 5)
    now = [0.0]
    eng = DecodeEngine(params, config, max_slots=2,
                       clock=lambda: now[0], **kind)
    ra = eng.submit(pa, 20, deadline_ms=100)
    rb = eng.submit(pb, 9)
    eng.step()
    eng.step()
    now[0] += 0.2                # passes with ra's next token in flight
    _drain(eng)
    info = eng.result_info(ra)
    assert info["timeout"]
    assert info["tokens"] == _ref(params, config, pa, 12)[:3]
    assert eng.result(rb) == _ref(params, config, pb, 12)[:9]
    assert eng.stats["surplus_rows"] == 1


def test_one_ahead_counts_every_step_but_the_first_of_a_busy_period(
        model, kind):
    """What the benchmark's cells run -- no eos, known budgets, requests
    arriving while others decode: no surplus row ever, and every
    dispatch but the one that found nothing in flight ran ahead."""
    import json
    import os
    from types import SimpleNamespace

    from chipbench.readers import prometheus_delta

    params, config = model
    rng = np.random.default_rng(47)
    eng = DecodeEngine(params, config, max_slots=3, **kind)
    for period in range(2):              # two busy periods, idle between
        for n in (6, 2, 9, 4, 7):
            eng.submit(rng.integers(0, 64, 6), n)
            eng.step()
        _drain(eng)
        stats = eng.stats
        assert stats["surplus_rows"] == 0
        assert stats["steps_ahead"] == stats["steps"] - (period + 1)
    assert stats["tokens_emitted"] == 2 * (6 + 2 + 9 + 4 + 7)
    # ... and the two metric files read that share off two scrapes
    before = eng.registry.render()
    eng.submit(rng.integers(0, 64, 6), 11)
    _drain(eng)
    ev = SimpleNamespace(prom_start=before, prom_end=eng.registry.render())
    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chipbench", "metrics")
    for name in ("decode.ahead_share.steady", "decode.ahead_share.overload"):
        with open(os.path.join(root, name + ".json")) as f:
            metric = json.load(f)
        assert metric["reader"] == "prometheus_delta"
        # 10 decode steps for 11 tokens, the first not ahead
        assert prometheus_delta.read(ev, **metric["args"]) == \
            pytest.approx(90.0)


def test_one_ahead_weight_swap_lands_behind_the_step_in_flight(model,
                                                               kind):
    """A swap staged with a step in flight applies from the next
    dispatch on: tokens match a plain greedy loop over one cache that
    changes its parameters at the same token."""
    params, config = model
    # other weights altogether, so that a swap one token early or late
    # gives other tokens
    p2 = init_params(config, jax.random.PRNGKey(7))
    [prompt] = _prompts(53, 6)
    eng = DecodeEngine(params, config, max_slots=1, **kind)
    rid = eng.submit(prompt, 12)
    for _ in range(3):
        eng.step()
    # 3 decode tokens read, the 4th in flight under the old weights
    eng.stage_params(p2, 1)
    _drain(eng)
    assert eng.weights_version == 1

    logits, cache = PREFILL(params, jnp.asarray(prompt)[None], config,
                            config.max_seq_len)
    want = [int(jnp.argmax(logits[0]))]
    for i in range(11):
        logits, cache = DECODE_STEP(
            params if i < 4 else p2, cache,
            jnp.asarray(want[-1:], jnp.int32), len(prompt) + i, config)
        want.append(int(jnp.argmax(logits[0])))
    assert eng.result(rid) == want
    assert want != _ref(params, config, prompt, 12)      # the swap shows
