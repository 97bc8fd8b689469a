"""Adaptive engine scheduling: chunked-prefill interleaving and
acceptance-steered speculative gamma.

Two invariants carry every test here:

* Interleaving only reorders WHEN admission prefill chunks run — each
  chunk replays the exact ``chunked_blocks`` program at the exact
  positions run-to-completion admission would use — so every output
  must equal its solo greedy decode no matter how chunks lace between
  decode steps (or how the interleave races preemption, cancellation
  and the prefix cache).
* Greedy speculative verification accepts exactly the target argmax
  prefix at ANY draft depth, so the adaptive controller may move gamma
  freely without touching tokens — staleness is a throughput event,
  never a correctness event.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elephas_tpu.models.transformer import (TransformerConfig, generate,
                                            init_params)
from elephas_tpu.obs import MetricsRegistry
from elephas_tpu.serving_engine import DecodeEngine
from elephas_tpu.serving_qos import TenantQoS


def _config(**overrides):
    # f32: every parity oracle below compares argmax tokens across
    # DIFFERENT compiled programs (chunked vs fused prefill) — the
    # standard cross-program near-tie caveat
    base = dict(vocab_size=64, num_layers=2, num_heads=4, d_model=32,
                d_ff=64, max_seq_len=64, dtype=jnp.float32)
    base.update(overrides)
    return TransformerConfig(**base)


def _draft_config(**overrides):
    base = dict(vocab_size=64, num_layers=1, num_heads=2, d_model=16,
                d_ff=32, max_seq_len=64, dtype=jnp.float32)
    base.update(overrides)
    return TransformerConfig(**base)


@pytest.fixture(scope="module")
def model():
    config = _config()
    params = init_params(config, jax.random.PRNGKey(0))
    dcfg = _draft_config()
    draft = init_params(dcfg, jax.random.PRNGKey(9))
    return params, config, draft, dcfg


def _ref(params, config, prompt, n):
    return list(np.asarray(
        generate(params, jnp.asarray(prompt)[None], n, config))[0])


def _prompt(seed, n=8):
    return list(np.random.default_rng(seed).integers(0, 64, n))


def _drain(eng):
    while eng.pending:
        eng.step()


# ------------------------------------------- interleaved prefill parity
@pytest.mark.slow
def test_interleave_token_identical_staggered_slots(model):
    """The tentpole pin: long prompts admitted chunk-by-chunk BETWEEN
    decode steps of already-running slots emit exactly the tokens of
    run-to-completion admission (and of the solo oracle) — for every
    request on both sides of the interleave."""
    params, config, _, _ = model
    rng = np.random.default_rng(7)
    live = [rng.integers(0, 64, 5).tolist() for _ in range(2)]
    long = [rng.integers(0, 64, int(n)).tolist() for n in (33, 41)]

    def run(interleave):
        eng = DecodeEngine(params, config, max_slots=4, paged=(40, 8),
                           prefill_chunk=8,
                           interleave_prefill=interleave)
        rids = [eng.submit(p, 16) for p in live]
        for _ in range(3):
            eng.step()                 # decodes in flight before burst
        rids += [eng.submit(p, 10) for p in long]
        _drain(eng)
        return [eng.result(r) for r in rids], eng.stats

    outs_off, _ = run(False)
    outs_on, stats = run(True)
    assert outs_on == outs_off
    for p, o, n in zip(live + long, outs_on, [16, 16, 10, 10]):
        assert o == _ref(params, config, p, n)
    assert stats["prefill_chunks_interleaved"] > 0
    assert stats["pending_prefills"] == 0
    assert stats["blocks_free"] == stats["blocks_total"]


@pytest.mark.slow
def test_interleave_with_prefix_cache_token_identical(model):
    """Interleaved admission composes with automatic prefix caching:
    the pending slot's table is parked on the scratch sink while shared
    blocks stay claimed, so live decodes' garbage writes can never
    poison a cache-hit chain mid-interleave."""
    params, config, _, _ = model
    rng = np.random.default_rng(11)
    stem = rng.integers(0, 64, 24).tolist()
    long_a = stem + rng.integers(0, 64, 12).tolist()
    long_b = stem + rng.integers(0, 64, 17).tolist()
    eng = DecodeEngine(params, config, max_slots=3, paged=(48, 8),
                       prefill_chunk=8, interleave_prefill=True,
                       prefix_cache=True)
    r0 = eng.submit(_prompt(0, 5), 14)
    eng.step()
    ra = eng.submit(long_a, 8)         # interleaves, fills the cache
    _drain(eng)
    r1 = eng.submit(_prompt(1, 5), 14)
    eng.step()
    rb = eng.submit(long_b, 8)         # interleaves ON a cache hit
    _drain(eng)
    assert eng.result(ra) == _ref(params, config, long_a, 8)
    assert eng.result(rb) == _ref(params, config, long_b, 8)
    for r, s in ((r0, 0), (r1, 1)):
        assert eng.result(r) == _ref(params, config, _prompt(s, 5), 14)
    assert eng.stats["kv_cache"]["hits"] >= 1
    assert eng.stats["prefill_chunks_interleaved"] > 0


@pytest.mark.slow
def test_interleave_with_speculative_adaptive_gamma(model):
    """The full composition: paged + speculative + adaptive gamma +
    interleaved admission, staggered. Greedy exactness must survive
    chunks lacing between VERIFY rounds at whatever depth the
    controller currently runs."""
    params, config, draft, dcfg = model
    rng = np.random.default_rng(13)
    eng = DecodeEngine(params, config, max_slots=3, paged=(48, 8),
                       prefill_chunk=8, interleave_prefill=True,
                       draft_params=draft, draft_config=dcfg, gamma=3,
                       adaptive_gamma=True)
    short = [rng.integers(0, 64, 6).tolist() for _ in range(2)]
    rids = [eng.submit(p, 14) for p in short]
    eng.step()
    long = rng.integers(0, 64, 37).tolist()
    rids.append(eng.submit(long, 12))
    _drain(eng)
    for p, r in zip(short, rids):
        assert eng.result(r) == _ref(params, config, p, 14)
    assert eng.result(rids[2]) == _ref(params, config, long, 12)
    assert eng.stats["prefill_chunks_interleaved"] > 0


@pytest.mark.slow
def test_interleave_survives_qos_preemption_mid_interleave(model):
    """A high-priority admission preempts a live decode WHILE another
    slot is mid-interleave: the pending prefill is not a preemption
    victim (its slot holds no decodable request yet), the victim parks
    and resumes, and all three outputs stay token-identical."""
    params, config, _, _ = model
    qos = TenantQoS(tenants={"batch": {"priority": "low"},
                             "live": {"priority": "high"}})
    eng = DecodeEngine(params, config, max_slots=2, paged=(32, 8),
                       prefill_chunk=8, interleave_prefill=True,
                       qos=qos)
    pa, pc = _prompt(3, 6), _prompt(4, 4)
    pb = _prompt(5, 35)
    ra = eng.submit(pa, 18, tenant="batch")
    for _ in range(3):
        eng.step()
    rb = eng.submit(pb, 6, tenant="batch")   # pending interleave
    eng.step()
    assert eng.stats["pending_prefills"] == 1
    rc = eng.submit(pc, 4, tenant="live")    # preempts ra, not rb
    _drain(eng)
    assert eng.result(ra) == _ref(params, config, pa, 18)
    assert eng.result(rb) == _ref(params, config, pb, 6)
    assert eng.result(rc) == _ref(params, config, pc, 4)
    assert eng.stats["preemptions"] == 1
    assert eng.stats["tenants"]["batch"]["preempted"] == 1


def test_cancel_pending_interleaved_prefill_releases_everything(model):
    """Cancelling a request mid-interleave frees its slot and blocks;
    the concurrent decode is untouched."""
    params, config, _, _ = model
    eng = DecodeEngine(params, config, max_slots=2, paged=(32, 8),
                       prefill_chunk=8, interleave_prefill=True)
    pa = _prompt(6, 5)
    ra = eng.submit(pa, 12)
    eng.step()
    rb = eng.submit(_prompt(7, 30), 8)
    eng.step()
    assert eng.stats["pending_prefills"] == 1
    assert eng.cancel(rb) is True
    assert eng.cancel(rb) is False           # one-shot, like any cancel
    _drain(eng)
    assert eng.result(ra) == _ref(params, config, pa, 12)
    assert eng.result(rb) is None            # never decoded a token
    assert eng.stats["pending_prefills"] == 0
    assert eng.stats["blocks_free"] == eng.stats["blocks_total"]


def test_interleave_requires_prefill_chunk(model):
    params, config, _, _ = model
    with pytest.raises(ValueError, match="prefill_chunk"):
        DecodeEngine(params, config, max_slots=2, paged=(16, 8),
                     interleave_prefill=True)


# --------------------------------------- acceptance-steered gamma
@pytest.mark.slow
def test_gamma_walks_down_on_stale_draft_and_resets_on_restage(model):
    """The controller's contract: a collapsed acceptance rate shrinks
    the operating depth toward ``gamma_min`` within a few rounds; a
    fresh draft staged through the live weight plane snaps it back to
    the ceiling. Tokens are pinned to the solo oracle throughout."""
    params, config, draft, dcfg = model
    stale = jax.tree_util.tree_map(lambda a: a * 0.02, draft)
    eng = DecodeEngine(params, config, max_slots=2, paged=(32, 8),
                       draft_params=draft, draft_config=dcfg, gamma=4,
                       adaptive_gamma=True)
    assert eng.stats["gamma"] == eng.stats["gamma_ceiling"] == 4

    eng.stage_draft_params(stale, version=2)
    prompts = [_prompt(20, 6), _prompt(21, 9)]
    rids = [eng.submit(p, 28) for p in prompts]
    _drain(eng)
    for p, r in zip(prompts, rids):
        assert eng.result(r) == _ref(params, config, p, 28)
    assert eng.stats["gamma"] < 4          # converged down on staleness
    assert eng.stats["gamma_ceiling"] == 4

    eng.stage_draft_params(draft, version=3)   # re-stage -> reset
    eng.apply_staged_params()
    assert eng.stats["gamma"] == 4             # snapped to the ceiling
    rids = [eng.submit(p, 10) for p in prompts]
    _drain(eng)
    for p, r in zip(prompts, rids):
        assert eng.result(r) == _ref(params, config, p, 10)


@pytest.mark.slow
def test_adaptive_gamma_token_identical_to_fixed(model):
    """Adaptive vs fixed gamma over the same staggered traffic with a
    degraded draft: identical outputs, depth visibly below the
    ceiling on the adaptive engine."""
    params, config, draft, dcfg = model
    stale = jax.tree_util.tree_map(lambda a: a * 0.05, draft)

    def run(adaptive):
        eng = DecodeEngine(params, config, max_slots=2, paged=(32, 8),
                           draft_params=stale, draft_config=dcfg,
                           gamma=3, adaptive_gamma=adaptive)
        rids = [eng.submit(_prompt(s, 7), 20) for s in (30, 31, 32)]
        _drain(eng)
        return [eng.result(r) for r in rids], eng.stats

    outs_fixed, _ = run(False)
    outs_adapt, stats = run(True)
    assert outs_adapt == outs_fixed
    assert stats["gamma"] < 3
    for s, o in zip((30, 31, 32), outs_adapt):
        assert o == _ref(params, config, _prompt(s, 7), 20)


def test_adaptive_gamma_requires_draft(model):
    params, config, _, _ = model
    with pytest.raises(ValueError, match="adaptive_gamma"):
        DecodeEngine(params, config, max_slots=1, adaptive_gamma=True)


def test_gamma_min_bounds(model):
    params, config, draft, dcfg = model
    with pytest.raises(ValueError, match="gamma_min"):
        DecodeEngine(params, config, max_slots=1, draft_params=draft,
                     draft_config=dcfg, gamma=3, adaptive_gamma=True,
                     gamma_min=5)


# ------------------------------------------------------- obs surfaces
@pytest.mark.slow
def test_metrics_expose_gamma_and_interleave_counter(model):
    """The catalog rows behind the runbook: ``serving_gamma`` tracks
    the OPERATING depth (ceiling at rest, lower under staleness) and
    ``serving_prefill_chunks_interleaved_total`` counts chunks the
    scheduler laced between decode steps."""
    params, config, draft, dcfg = model
    reg = MetricsRegistry()
    eng = DecodeEngine(params, config, max_slots=2, paged=(32, 8),
                       prefill_chunk=8, interleave_prefill=True,
                       draft_params=draft, draft_config=dcfg, gamma=3,
                       adaptive_gamma=True, registry=reg)
    r0 = eng.submit(_prompt(60, 5), 12)
    eng.step()
    r1 = eng.submit(_prompt(61, 30), 6)
    _drain(eng)
    assert eng.result(r0) is not None and eng.result(r1) is not None
    text = reg.render()

    def sample(name):
        for ln in text.splitlines():
            if ln.startswith(name) and not ln.startswith("#"):
                return float(ln.split()[-1])
        raise AssertionError(f"{name} not rendered")

    # the gauge is the OPERATING depth: somewhere in [gamma_min,
    # ceiling] after traffic (a random-init draft's acceptance steers
    # it), never outside
    assert 1 <= sample("serving_gamma") <= 3
    assert sample("serving_prefill_chunks_interleaved_total") >= 1
