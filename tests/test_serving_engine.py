"""Continuous batching: per-request engine output must be token-
identical to running ``generate`` alone on that request (slots are
isolated by the batch axis + per-row positions), across staggered
admission, mixed prompt lengths, eos early-exit, and slot reuse."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elephas_tpu.models.transformer import (TransformerConfig, generate,
                                            init_params)
from elephas_tpu.serving_engine import DecodeEngine


def _config(**overrides):
    # f32 compute: the parity oracle compares tokens across DIFFERENT
    # compiled programs (the engine's per-step jit vs generate's fused
    # scan); under bf16 their rounding differs by ~5e-4, enough to flip
    # argmax near-ties of a random flat model. f32 makes the comparison
    # deterministic; bf16 serving works identically modulo such ties.
    base = dict(vocab_size=64, num_layers=2, num_heads=4, d_model=32,
                d_ff=64, max_seq_len=48, dtype=jnp.float32)
    base.update(overrides)
    return TransformerConfig(**base)


def _ref(params, config, prompt, n):
    return list(np.asarray(
        generate(params, jnp.asarray(prompt)[None], n, config))[0])


@pytest.fixture(scope="module")
def model():
    config = _config()
    params = init_params(config, jax.random.PRNGKey(0))
    return params, config


def test_single_request_matches_generate(model):
    params, config = model
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 64, 7)
    eng = DecodeEngine(params, config, max_slots=4)
    [out] = eng.run([prompt], max_new_tokens=10)
    assert out == _ref(params, config, prompt, 10)


def test_more_requests_than_slots_mixed_lengths(model):
    """8 requests through 3 slots: admission happens mid-flight at
    whatever positions the running slots are at — every output must
    still match the request's solo greedy decode."""
    params, config = model
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 64, int(n))
               for n in rng.integers(3, 12, size=8)]
    eng = DecodeEngine(params, config, max_slots=3)
    outs = eng.run(prompts, max_new_tokens=9)
    for p, o in zip(prompts, outs):
        assert o == _ref(params, config, p, 9)


def test_incremental_submission(model):
    """Requests submitted while others are mid-decode (the online
    pattern) still match their solo decodes."""
    params, config = model
    rng = np.random.default_rng(2)
    p1, p2, p3 = (rng.integers(0, 64, n) for n in (5, 8, 4))
    eng = DecodeEngine(params, config, max_slots=2)
    r1 = eng.submit(p1, 8)
    r2 = eng.submit(p2, 8)
    for _ in range(3):
        eng.step()
    r3 = eng.submit(p3, 8)  # queued: both slots busy
    while eng.pending:
        eng.step()
    assert eng.result(r1) == _ref(params, config, p1, 8)
    assert eng.result(r2) == _ref(params, config, p2, 8)
    assert eng.result(r3) == _ref(params, config, p3, 8)


def test_eos_frees_slot_early(model):
    params, config = model
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, 64, 6)
    full = _ref(params, config, prompt, 12)
    # force an early stop: pick the eos at a token's FIRST occurrence
    # (a fixed full[k] silently breaks when that token also appears
    # earlier in the decode — which depends on the machine's numerics)
    cut = next(i for i, t in enumerate(full) if i >= 1
               and t not in full[:i])
    eos = full[cut]
    eng = DecodeEngine(params, config, max_slots=1, eos_id=eos)
    [out] = eng.run([prompt], max_new_tokens=12)
    assert out == full[:cut]
    # the freed slot serves the next request correctly
    p2 = rng.integers(0, 64, 5)
    [out2] = eng.run([p2], max_new_tokens=6)
    ref2 = _ref(params, config, p2, 6)
    # ref2 may itself hit eos
    if eos in ref2:
        ref2 = ref2[:ref2.index(eos)]
    assert out2 == ref2


def test_validation(model):
    params, config = model
    eng = DecodeEngine(params, config, max_slots=1, max_len=16)
    with pytest.raises(ValueError, match="max_len"):
        eng.submit(np.zeros(10, np.int32), 10)
    with pytest.raises(ValueError, match="at least one"):
        eng.submit(np.zeros(0, np.int32), 2)
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit(np.zeros(3, np.int32), 0)
    with pytest.raises(ValueError, match="max_seq_len"):
        DecodeEngine(params, config, max_len=1024)


def test_streamed_tokens_reconstruct_outputs(model):
    """Every token — including each request's admission-time first
    token — surfaces through step()'s {rid: token} returns, so a
    streaming server relaying step() output delivers complete
    responses."""
    params, config = model
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 64, int(n)) for n in (4, 7, 5)]
    eng = DecodeEngine(params, config, max_slots=2)
    rids = [eng.submit(p, 6) for p in prompts]
    streamed = {r: [] for r in rids}
    while eng.pending:
        for rid, toks in eng.step().items():
            streamed[rid].extend(toks)
    for rid, p in zip(rids, prompts):
        assert streamed[rid] == _ref(params, config, p, 6)
        assert eng.result(rid) == streamed[rid]


def test_streaming_edge_cases(model):
    """max_new_tokens=1 requests retire at admission — their token must
    still surface through step(); an eos token is neither in result()
    nor in the stream."""
    params, config = model
    rng = np.random.default_rng(6)
    prompt = rng.integers(0, 64, 5)
    eng = DecodeEngine(params, config, max_slots=1)
    rid = eng.submit(prompt, 1)
    streamed = []
    while eng.pending:
        for r, toks in eng.step().items():
            assert r == rid
            streamed.extend(toks)
    assert streamed == eng.result(rid) == _ref(params, config, prompt, 1)

    full = _ref(params, config, prompt, 10)
    eos = full[3]
    eng2 = DecodeEngine(params, config, max_slots=1, eos_id=eos)
    rid2 = eng2.submit(prompt, 10)
    streamed2 = []
    while eng2.pending:
        for _, toks in eng2.step().items():
            streamed2.extend(toks)
    assert eos not in streamed2
    assert streamed2 == eng2.result(rid2) == full[:3]


def test_sampling_mode_runs(model):
    params, config = model
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 64, 5), rng.integers(0, 64, 7)]
    eng = DecodeEngine(params, config, max_slots=2, temperature=0.8,
                      seed=11)
    outs = eng.run(prompts, max_new_tokens=6)
    for o in outs:
        assert len(o) == 6 and all(0 <= t < 64 for t in o)


def test_speculative_mode_matches_generate(model):
    """Speculative stepping (draft per slot + verify round) preserves
    per-request greedy parity with solo generate, across staggered
    admission and an unrelated random draft."""
    params, config = model
    dcfg = _config(num_layers=1, num_heads=2, d_model=16, d_ff=32)
    draft = init_params(dcfg, jax.random.PRNGKey(9))
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 64, int(n))
               for n in rng.integers(3, 10, size=6)]
    eng = DecodeEngine(params, config, max_slots=2, draft_params=draft,
                       draft_config=dcfg, gamma=3)
    outs = eng.run(prompts, max_new_tokens=9)
    for p, o in zip(prompts, outs):
        assert o == _ref(params, config, p, 9)


def test_speculative_mode_self_draft_fewer_steps(model):
    """Draft == target: every proposal accepted, so draining takes
    ~1/(gamma+1) the host steps of plain mode."""
    params, config = model
    rng = np.random.default_rng(8)
    prompt = rng.integers(0, 64, 6)
    eng = DecodeEngine(params, config, max_slots=1, draft_params=params,
                       draft_config=config, gamma=3)
    rid = eng.submit(prompt, 12)
    steps = 0
    while eng.pending:
        eng.step()
        steps += 1
    assert eng.result(rid) == _ref(params, config, prompt, 12)
    assert steps <= 4   # ceil((12-1)/4) rounds + the drain step


def test_speculative_mode_eos_mid_chunk(model):
    """An eos inside an accepted chunk truncates the output exactly as
    the plain engine would, and frees the slot for the next request."""
    params, config = model
    rng = np.random.default_rng(9)
    prompt = rng.integers(0, 64, 5)
    full = _ref(params, config, prompt, 12)
    # eos must be a token whose FIRST occurrence is the intended cut,
    # and not at a chunk boundary by construction (any index works —
    # chunks are gamma+1 = 5 wide, cut at first-occurrence semantics)
    cut, eos = next((k, t) for k, t in enumerate(full)
                    if full.index(t) == k and k >= 2)
    eng = DecodeEngine(params, config, max_slots=1, draft_params=params,
                       draft_config=config, gamma=4, eos_id=eos)
    [out] = eng.run([prompt], max_new_tokens=12)
    assert out == full[:cut]
    p2 = rng.integers(0, 64, 7)
    [out2] = eng.run([p2], max_new_tokens=5)
    ref2 = _ref(params, config, p2, 5)
    if eos in ref2:
        ref2 = ref2[:ref2.index(eos)]
    assert out2 == ref2


def test_speculative_mode_validation(model):
    params, config = model
    import dataclasses
    with pytest.raises(ValueError, match="go together"):
        DecodeEngine(params, config, draft_params=params)
    with pytest.raises(ValueError, match="vocab"):
        DecodeEngine(params, config, draft_params=params,
                     draft_config=dataclasses.replace(config,
                                                      vocab_size=32))
    eng = DecodeEngine(params, config, max_slots=1, max_len=16,
                       draft_params=params, draft_config=config, gamma=4)
    with pytest.raises(ValueError, match="gamma"):
        eng.submit(np.zeros(4, np.int32), 10)   # 4 + 10 + 4 > 16


def test_per_request_temperature(model):
    """One batch, mixed sampling settings: the temperature-0 request
    still matches its solo greedy decode while a sampled request rides
    the same steps."""
    params, config = model
    rng = np.random.default_rng(10)
    p_greedy, p_sampled = rng.integers(0, 64, 6), rng.integers(0, 64, 8)
    eng = DecodeEngine(params, config, max_slots=2, temperature=0.0)
    r1 = eng.submit(p_greedy, 8)                    # engine default: greedy
    r2 = eng.submit(p_sampled, 8, temperature=0.9)  # per-request override
    while eng.pending:
        eng.step()
    assert eng.result(r1) == _ref(params, config, p_greedy, 8)
    out2 = eng.result(r2)
    assert len(out2) == 8 and all(0 <= t < 64 for t in out2)
    # speculative mode rejects the override explicitly
    spec = DecodeEngine(params, config, max_slots=1, draft_params=params,
                        draft_config=config, gamma=2)
    with pytest.raises(ValueError, match="speculative"):
        spec.submit(p_greedy, 4, temperature=0.5)
    with pytest.raises(ValueError, match="finite"):
        eng.submit(p_greedy, 4, temperature=-0.7)
    with pytest.raises(ValueError, match="finite"):
        eng.submit(p_greedy, 4, temperature=float("nan"))


def test_stats_counters(model):
    params, config = model
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 64, 5), rng.integers(0, 64, 7)]
    eng = DecodeEngine(params, config, max_slots=2)
    eng.run(prompts, max_new_tokens=6)
    s = eng.stats
    assert s["requests_finished"] == 2
    assert s["tokens_emitted"] == 12
    # two slots emit <= 2 per step, plus the two admission-time first
    # tokens that ride along free of any step
    assert 0 < s["tokens_per_step"] <= 2.5
    assert "draft_acceptance" not in s

    spec = DecodeEngine(params, config, max_slots=1, draft_params=params,
                        draft_config=config, gamma=3)
    spec.run([prompts[0]], max_new_tokens=8)
    ss = spec.stats
    assert ss["draft_acceptance"] == 1.0     # self-draft accepts all
    assert ss["tokens_per_step"] > 1.5       # speculation's payoff


# ------------------------------------------------------------ prefix cache

def test_prefix_cache_parity(model):
    """Requests hitting a registered prefix must produce tokens identical
    to the no-prefix engine (and to solo generate): the cached-prefix +
    suffix decode_block admission is numerically the full prefill."""
    params, config = model
    rng = np.random.default_rng(7)
    prefix = list(rng.integers(0, 64, 6))
    prompts = [np.asarray(prefix + list(rng.integers(0, 64, int(n))))
               for n in (1, 4, 9)]
    prompts.append(rng.integers(0, 64, 5))        # no shared prefix
    eng = DecodeEngine(params, config, max_slots=2)
    eng.register_prefix(prefix)
    outs = eng.run(prompts, max_new_tokens=8)
    for p, o in zip(prompts, outs):
        assert o == _ref(params, config, p, 8)
    stats = eng.stats
    assert stats["prefix_hits"] == 3
    assert stats["prefix_tokens_reused"] == 18


def test_prefix_cache_exact_match_prompt(model):
    """A prompt that IS the registered prefix: admission reuses the
    stored last-position logits, no extra forward at all."""
    params, config = model
    rng = np.random.default_rng(8)
    prefix = rng.integers(0, 64, 9)
    eng = DecodeEngine(params, config, max_slots=2)
    eng.register_prefix(prefix)
    [out] = eng.run([prefix], max_new_tokens=10)
    assert out == _ref(params, config, prefix, 10)
    assert eng.stats["prefix_hits"] == 1


def test_prefix_cache_longest_match_wins(model):
    params, config = model
    rng = np.random.default_rng(9)
    short = list(rng.integers(0, 64, 4))
    long = short + list(rng.integers(0, 64, 5))
    eng = DecodeEngine(params, config, max_slots=2)
    eng.register_prefix(short)
    eng.register_prefix(long)
    prompt = np.asarray(long + list(rng.integers(0, 64, 3)))
    [out] = eng.run([prompt], max_new_tokens=6)
    assert out == _ref(params, config, prompt, 6)
    assert eng.stats["prefix_tokens_reused"] == 9   # the LONG prefix

    eng.clear_prefixes()
    [out2] = eng.run([prompt], max_new_tokens=6)
    assert out2 == out
    assert "prefix_hits" not in eng.stats


def test_prefix_cache_speculative_mode(model):
    """Prefix caching composes with speculative stepping: both target
    and draft caches are prefix-reused, output still ≡ solo generate."""
    params, config = model
    draft_params = init_params(config, jax.random.PRNGKey(3))
    rng = np.random.default_rng(10)
    prefix = list(rng.integers(0, 64, 5))
    prompts = [np.asarray(prefix + list(rng.integers(0, 64, int(n))))
               for n in (2, 6)]
    eng = DecodeEngine(params, config, max_slots=2,
                       draft_params=draft_params, draft_config=config,
                       gamma=3)
    eng.register_prefix(prefix)
    outs = eng.run(prompts, max_new_tokens=7)
    for p, o in zip(prompts, outs):
        assert o == _ref(params, config, p, 7)
    assert eng.stats["prefix_hits"] == 2


# ---------------------------------------------------- TP-sharded params

def test_engine_with_tp_sharded_params():
    """DecodeEngine with tensor-parallel GSPMD-sharded params (2x2
    data x model mesh) must emit exactly the unsharded engine's tokens —
    prefix caching included. Pins the docstring's
    'replicated or GSPMD-sharded' params claim for the engine."""
    from jax.sharding import Mesh

    from elephas_tpu.models.transformer import shard_params

    config = _config(dtype=jnp.float32)
    params = init_params(config, jax.random.PRNGKey(0))
    rng = np.random.default_rng(21)
    prefix = list(rng.integers(0, 64, 5))
    prompts = [np.asarray(prefix + list(rng.integers(0, 64, int(n))))
               for n in (3, 6, 4)]

    def run(p):
        eng = DecodeEngine(p, config, max_slots=2)
        eng.register_prefix(prefix)
        return eng.run(prompts, max_new_tokens=8)

    expected = run(params)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                ("data", "model"))
    got = run(shard_params(params, config, mesh))
    assert got == expected
    for p, o in zip(prompts, expected):
        assert o == _ref(params, config, p, 8)


# -------------------------------------------- per-request sampling knobs

def test_filter_rows_matches_scalar_filter():
    """The engine's per-row top-k/top-p filter must reproduce the scalar
    _filter_logits used by generate, for every (k, p) combination."""
    from elephas_tpu.models.transformer import _filter_logits
    from elephas_tpu.serving_engine import _filter_logits_rows

    logits = jax.random.normal(jax.random.PRNGKey(5), (4, 32)) * 3
    for k, p in [(None, None), (5, None), (None, 0.7), (3, 0.9),
                 (1, None), (None, 1.0), (32, 0.2)]:
        want = np.asarray(_filter_logits(logits, k, p))
        got = np.asarray(_filter_logits_rows(
            logits,
            jnp.full(4, 0 if k is None else k, jnp.int32),
            jnp.full(4, 1.0 if p is None else p, jnp.float32)))
        np.testing.assert_allclose(got, want, err_msg=f"k={k} p={p}")


def test_per_request_topk1_equals_greedy(model):
    """top_k=1 with temperature>0 collapses sampling to argmax — output
    must equal the greedy solo decode even though the slot 'samples';
    mixed with a plain greedy request in the same batch."""
    params, config = model
    rng = np.random.default_rng(22)
    p1, p2 = rng.integers(0, 64, 6), rng.integers(0, 64, 9)
    eng = DecodeEngine(params, config, max_slots=2, seed=3)
    r1 = eng.submit(p1, 8, temperature=1.0, top_k=1)
    r2 = eng.submit(p2, 8)                   # engine-default greedy
    while eng.pending:
        eng.step()
    assert eng.result(r1) == _ref(params, config, p1, 8)
    assert eng.result(r2) == _ref(params, config, p2, 8)


def test_per_request_sampling_rejected_in_spec_mode(model):
    params, config = model
    eng = DecodeEngine(params, config, max_slots=2, draft_params=params,
                       draft_config=config)
    with pytest.raises(ValueError, match="sampling settings"):
        eng.submit([1, 2, 3], 4, top_k=5)
    with pytest.raises(ValueError, match="top_p"):
        DecodeEngine(params, config).submit([1], 4, top_p=1.5)


# ---------------------------------------------------------- cancellation

def test_cancel_queued_and_active(model):
    """Cancelling a queued request prevents admission; cancelling an
    active one frees its slot for the next queued request; the others'
    outputs are untouched."""
    params, config = model
    rng = np.random.default_rng(23)
    prompts = [rng.integers(0, 64, int(n)) for n in (5, 7, 4, 6)]
    eng = DecodeEngine(params, config, max_slots=2)
    rids = [eng.submit(p, 10) for p in prompts]
    # rids[0]/rids[1] hold the slots; rids[2]/rids[3] are queued
    assert eng.cancel(rids[2]) is True       # queued: dropped pre-admission
    eng.step()
    assert eng.cancel(rids[1]) is True       # active: slot freed mid-flight
    while eng.pending:
        eng.step()
    assert eng.result(rids[0]) == _ref(params, config, prompts[0], 10)
    assert eng.result(rids[3]) == _ref(params, config, prompts[3], 10)
    assert eng.result(rids[1]) is None and eng.result(rids[2]) is None
    assert eng.cancel(rids[0]) is False      # finished: not cancellable


# ------------------------------------------------------- chunked prefill

def test_prefill_chunk_parity_and_bounded_compiles(model):
    """prefill_chunk=4: many distinct prompt lengths must (a) produce
    exactly the unchunked engine's outputs, and (b) compile at most
    `chunk` distinct extend-block shapes — admission cost stops scaling
    with prompt-length diversity."""
    params, config = model
    rng = np.random.default_rng(31)
    prompts = [rng.integers(0, 64, int(n))
               for n in (3, 4, 5, 7, 8, 9, 11, 13)]

    plain = DecodeEngine(params, config, max_slots=2)
    chunked = DecodeEngine(params, config, max_slots=2, prefill_chunk=4)
    expected = plain.run(prompts, max_new_tokens=6)
    got = chunked.run(prompts, max_new_tokens=6)
    assert got == expected
    for p, o in zip(prompts, expected):
        assert o == _ref(params, config, p, 6)
    # block shapes seen: 4 (full) + tails {3, 1, 2} -> ≤ chunk compiles
    # (fresh rows are engine-owned, so blocks ride the donating variant)
    assert (chunked._extend_owned_fn._cache_size()
            + chunked._extend_fn._cache_size()) <= 4
    # the whole-prompt prefill path was never compiled
    assert chunked._prefill_fn._cache_size() == 0


def test_prefill_chunk_composes_with_prefix_cache(model):
    params, config = model
    rng = np.random.default_rng(32)
    prefix = list(rng.integers(0, 64, 6))
    prompts = [np.asarray(prefix + list(rng.integers(0, 64, int(n))))
               for n in (2, 5, 9)]
    eng = DecodeEngine(params, config, max_slots=2, prefill_chunk=3)
    eng.register_prefix(prefix)
    outs = eng.run(prompts, max_new_tokens=7)
    for p, o in zip(prompts, outs):
        assert o == _ref(params, config, p, 7)
    assert eng.stats["prefix_hits"] == 3


def test_prefill_chunk_speculative_prefix(model):
    """prefill_chunk + speculative + prefix registration: target AND
    draft caches both ride the chunked block path; output ≡ solo."""
    params, config = model
    draft_params = init_params(config, jax.random.PRNGKey(9))
    rng = np.random.default_rng(33)
    prefix = list(rng.integers(0, 64, 7))
    prompt = np.asarray(prefix + list(rng.integers(0, 64, 4)))
    eng = DecodeEngine(params, config, max_slots=2, prefill_chunk=3,
                       draft_params=draft_params, draft_config=config,
                       gamma=3)
    eng.register_prefix(prefix)
    [out] = eng.run([prompt], max_new_tokens=6)
    assert out == _ref(params, config, prompt, 6)
    assert eng.stats["prefix_hits"] == 1


# ------------------------------------------------------ warmup + latency

def test_warmup_precompiles_all_traffic_shapes(model):
    """After warmup(lengths), serving prompts of exactly those lengths
    compiles NOTHING new — the first request pays no jit latency."""
    params, config = model
    rng = np.random.default_rng(50)
    eng = DecodeEngine(params, config, max_slots=2)
    eng.warmup(prompt_lengths=(4, 7))
    sizes = (eng._step_fn._cache_size(), eng._prefill_fn._cache_size(),
             eng._install_fn._cache_size())
    prompts = [rng.integers(0, 64, 4), rng.integers(0, 64, 7)]
    outs = eng.run(prompts, max_new_tokens=6)
    for p, o in zip(prompts, outs):
        assert o == _ref(params, config, p, 6)
    assert (eng._step_fn._cache_size(), eng._prefill_fn._cache_size(),
            eng._install_fn._cache_size()) == sizes
    # warmup on a busy engine is refused
    eng.submit(rng.integers(0, 64, 4), 30)
    with pytest.raises(RuntimeError, match="idle"):
        eng.warmup((4,))


def test_warmup_paged(model):
    params, config = model
    rng = np.random.default_rng(51)
    eng = DecodeEngine(params, config, max_slots=2, paged=(16, 8),
                       prefill_chunk=4)
    eng.warmup(prompt_lengths=(5, 9))
    n_ext = (eng._extend_owned_fn._cache_size()
             + eng._extend_fn._cache_size())
    n_step = eng._step_paged_fn._cache_size()
    prompts = [rng.integers(0, 64, 5), rng.integers(0, 64, 9)]
    outs = eng.run(prompts, max_new_tokens=7)
    for p, o in zip(prompts, outs):
        assert o == _ref(params, config, p, 7)
    assert (eng._extend_owned_fn._cache_size()
            + eng._extend_fn._cache_size()) == n_ext
    assert eng._step_paged_fn._cache_size() == n_step


def test_latency_stats(model):
    params, config = model
    rng = np.random.default_rng(52)
    eng = DecodeEngine(params, config, max_slots=1)
    eng.run([rng.integers(0, 64, 5), rng.integers(0, 64, 6)],
            max_new_tokens=5)
    s = eng.stats
    assert 0 < s["latency_p50_s"] <= s["latency_p99_s"]
    # the second request waited for the single slot
    assert s["queue_wait_mean_s"] > 0


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
    from elephas_tpu.models.transformer import decode_step, prefill_cache

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

    logits, cache = prefill_cache(params, jnp.asarray(prompt)[None],
                                  config, config.max_seq_len)
    want = [int(jnp.argmax(logits[0]))]
    for i in range(11):
        logits, cache = decode_step(
            params if i < 4 else p2, cache,
            jnp.asarray(want[-1:], jnp.int32), len(prompt) + i, config)
        want.append(int(jnp.argmax(logits[0])))
    assert eng.result(rid) == want
    assert want != _ref(params, config, prompt, 12)      # the swap shows
