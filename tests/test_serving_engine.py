"""Continuous batching: per-request engine output must be token-
identical to running ``generate`` alone on that request (slots are
isolated by the batch axis + per-row positions), across staggered
admission, mixed prompt lengths, eos early-exit, and slot reuse."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elephas_tpu.models.transformer import TransformerConfig, init_params
from elephas_tpu.serving_engine import DecodeEngine

from ._greedy import greedy_reference as _ref


def _config(**overrides):
    # f32 compute: the parity oracle compares tokens across DIFFERENT
    # compiled programs (the engine's per-step jit vs generate's fused
    # scan); under bf16 their rounding differs by ~5e-4, enough to flip
    # argmax near-ties of a random flat model. f32 makes the comparison
    # deterministic; bf16 serving works identically modulo such ties.
    # One layer: what these tests exist for is the engine's scheduling,
    # which a second layer only compiles again.
    base = dict(vocab_size=64, num_layers=1, num_heads=4, d_model=32,
                d_ff=64, max_seq_len=48, dtype=jnp.float32)
    base.update(overrides)
    return TransformerConfig(**base)


@pytest.fixture(scope="module")
def model():
    config = _config()
    params = init_params(config, jax.random.PRNGKey(0))
    return params, config


@pytest.fixture(scope="module")
def two_slots(model):
    """A plain two-slot engine shared by the tests that leave it as they
    found it: every request drained or cancelled and every result read.
    An engine compiles its programs anew; these tests need no new one."""
    return DecodeEngine(*model, max_slots=2)


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


def test_incremental_submission(model, two_slots):
    """Requests submitted while others are mid-decode (the online
    pattern) still match their solo decodes."""
    params, config = model
    rng = np.random.default_rng(2)
    p1, p2, p3 = (rng.integers(0, 64, n) for n in (5, 8, 4))
    eng = two_slots
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


def test_streamed_tokens_reconstruct_outputs(model, two_slots):
    """Every token — including each request's admission-time first
    token — surfaces through step()'s {rid: token} returns, so a
    streaming server relaying step() output delivers complete
    responses."""
    params, config = model
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 64, int(n)) for n in (4, 7, 5)]
    eng = two_slots
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


def test_per_request_temperature(model, two_slots):
    """One batch, mixed sampling settings: the temperature-0 request
    still matches its solo greedy decode while a sampled request rides
    the same steps."""
    params, config = model
    rng = np.random.default_rng(10)
    p_greedy, p_sampled = rng.integers(0, 64, 6), rng.integers(0, 64, 8)
    eng = two_slots                                 # temperature 0.0
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

def test_cancel_queued_and_active(model, two_slots):
    """Cancelling a queued request prevents admission; cancelling an
    active one frees its slot for the next queued request; the others'
    outputs are untouched."""
    params, config = model
    rng = np.random.default_rng(23)
    prompts = [rng.integers(0, 64, int(n)) for n in (5, 7, 4, 6)]
    eng = two_slots
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

def test_prefill_chunk_parity_and_bounded_compiles(model, two_slots):
    """prefill_chunk=4: many distinct prompt lengths must (a) produce
    exactly the unchunked engine's outputs, and (b) compile at most
    `chunk` distinct extend-block shapes — admission cost stops scaling
    with prompt-length diversity."""
    params, config = model
    rng = np.random.default_rng(31)
    prompts = [rng.integers(0, 64, int(n))
               for n in (3, 4, 5, 7, 8, 9, 11, 13)]

    chunked = DecodeEngine(params, config, max_slots=2, prefill_chunk=4)
    expected = two_slots.run(prompts, max_new_tokens=6)
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
