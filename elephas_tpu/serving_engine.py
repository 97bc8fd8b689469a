"""Continuous-batching decode engine (slot-based online serving).

``DecodeEngine`` keeps a fixed device batch of ``max_slots`` decode
slots, each at its OWN sequence position — requests join a running
batch the moment a slot frees up (vLLM-style continuous batching,
without paged attention: each slot owns a contiguous cache row). This
rides the vector-position support in
:func:`~elephas_tpu.models.transformer.decode_step`: one jitted step
advances every active slot regardless of where in its sequence each
one is, so short requests never wait for long ones and the chip never
idles between requests.

Per-request output is token-identical to running
:func:`~elephas_tpu.models.transformer.generate` alone on that request
(greedy; the parity oracle in ``tests/test_serving_engine.py``) — slots
are isolated by the batch axis and the per-row causal length mask. One
caveat applies to ALL cross-program comparisons: under bf16 compute the
engine's per-step program and ``generate``'s fused scan round
differently (~5e-4 on logits), so an argmax near-tie can resolve
differently between them; f32 compute is deterministic.

The step loop is host-driven by design: an online server admits and
retires requests between steps, which is exactly the host round trip.
Plain stepping hides that round trip instead of paying it: it keeps
ONE step in flight (``_step_plain``), dispatching step k+1 -- whose
inputs are step k's tokens, taken on the device, and positions one
further -- before it reads step k's tokens, so reading, recording,
handing over, admitting and uploading run beside the device. Tokens
are the synchronous loop's, row for row (greedy, seeded, and unseeded
rows stepped together); what the host cannot know a step ahead (eos, a
cancel, a deadline, a preemption) costs one surplus token, dropped.
The speculative loop, whose next position depends on what a round
returns, stays synchronous.
For offline batch generation, :func:`generate`'s single fused scan is
the faster shape.

With a draft model (``draft_params``/``draft_config``), stepping
switches to SPECULATIVE rounds: each ``step()`` runs one
draft-propose / target-verify round per slot, so a slot advances by
``1 + accepted`` tokens per host round trip — continuous batching and
speculative decoding compose because both ride the same per-row cache
positions (rows accept different counts and simply advance
independently). Speculative mode is a first-class SERVING mode: it
composes with the paged pool (the verify pass scatters into the slot's
own blocks — admission budgets ``gamma`` positions of verify slack per
slot, and rejected positions are masked in the slot's own allocation,
never a neighbor's), with the automatic prefix cache (the TARGET
model's KV is the cacheable state — chain keys, admission, parking all
unchanged; draft KV is recomputed at admission and never cached), and
with disaggregated decode (``submit_prefilled`` installs shipped
TARGET KV, then prefills the draft locally before the first round).
Draft params hot-swap through their own channel
(:meth:`stage_draft_params`) so a continuously re-distilled draft
stays fresh: a stale draft costs acceptance rate — the verify pass is
exact with respect to the target — never output correctness.

Automatic prefix caching: with ``prefix_cache`` on (the DEFAULT in
paged mode), the engine content-addresses every FULL ``block_size``
block of every admitted prompt by the hash chain of its token contents
and the live ``weights_version``
(:mod:`~elephas_tpu.models.block_cache`). Admission walks the longest
chain of cached blocks first and prefills only the remainder — no
registration, no operator curation: any two requests sharing a prompt
head share its KV. In paged mode the cached blocks live IN the pool
and a hit installs table POINTERS (zero copy, zero recompute; entries
are refcounted while any slot's table points at them and parked on an
LRU free list when unreferenced, so pool pressure reclaims cold
prefixes instead of failing admission — correctness needs no
copy-on-write because decode only ever writes the private blocks past
the prompt's full-block head). On a contiguous engine (or a
disaggregated prefill worker) the cache stores host block arrays: a
hit pays one host-to-device copy instead of the prefix's prefill
FLOPs. Keying on ``weights_version`` means a live hot-swap (PR 8)
invalidates the whole cache BY CONSTRUCTION — post-swap chains hash
differently, no flush pause, and old-version blocks age out of the
LRU rather than ever being served.

``register_prefix`` survives as the explicit PINNING layer on top of
the automatic cache: it precomputes a shared prompt head (a system
prompt) ahead of traffic and pins its full blocks with a refcount
floor of one — never parked, never evicted — while sub-block tails
keep riding the registered row (longest registered match wins when it
covers more than the block chain).

Multi-tenant QoS (``qos=``, :mod:`~elephas_tpu.serving_qos`): requests
carry a ``tenant`` + priority class; admission replaces the FIFO pop
with token-budget weighted fair queueing across tenants
(deficit-round-robin over queued tokens), per-tenant quotas shed with
429 + a quota-aware ``retry_after_ms`` while under-quota tenants keep
admitting, and — in paged mode with the prefix cache — a
strictly-higher-priority request under pool pressure PREEMPTS a
low-priority in-flight decode: the victim's full KV blocks park in the
block cache (release → LRU), the request re-queues at the front of its
tenant lane, and on re-admission the chain walk reclaims the parked
blocks, so resume ≈ a prefix-cache hit plus a short remainder prefill
— greedy output token-identical to the never-preempted run.

The reference has no serving path at all (inference is Spark
``mapPartitions`` batch prediction, ``elephas/spark_model.py:235-272``);
continuous batching is a beyond-parity serving feature.
"""
import contextlib
import threading
import time
from collections import deque
from functools import partial
from typing import (Dict, List, NamedTuple, Optional, Sequence, Tuple,
                    Union)

import jax
import jax.numpy as jnp
import numpy as np

from .models.transformer import (NEG_INF, TransformerConfig, attend_width,
                                 chunked_blocks, decode_block, decode_step,
                                 init_kv_cache, prefill_cache,
                                 prefill_ladder)
from .obs.context import current_context, use_context
from .obs.events import FlightRecorder
from .obs.events import emit as emit_event
from .obs.metrics import (MetricsRegistry, counter_baseline,
                          since_baseline)
from .obs.profiler import LoopProfiler
from .obs.spans import add_span, default_span_store, start_span
from .obs.trace import span_if_counted
from .serving_qos import (DEFAULT_TENANT, FairQueue, QueuedRequest,
                          TenantQoS)
from .utils.faults import InjectedFault, fault_site


class QueueFullError(RuntimeError):
    """Admission rejected: accepting the request would exceed the
    engine's queue-depth or queued-token bound (or a ``serving.submit``
    fault-plan ``drop`` simulated the same). Carries ``retry_after_ms``,
    a backoff hint derived from recent request latency and the current
    backlog — the HTTP layer forwards it with its 429."""

    def __init__(self, message: str, retry_after_ms: int = 100):
        super().__init__(message)
        self.retry_after_ms = int(retry_after_ms)


class DeadlineExceededError(RuntimeError):
    """A request's deadline passed before any work was dispatched for
    it (the blocking :class:`~elephas_tpu.serving.TextGenerator` path;
    the engine itself never raises this — it sheds expired requests and
    marks their results instead)."""


def _filter_logits_rows(logits: jnp.ndarray, top_k: jnp.ndarray,
                        top_p: jnp.ndarray) -> jnp.ndarray:
    """Per-ROW top-k / nucleus filters over ``(B, V)`` logits — the
    vectorized form of the scalar
    :func:`~elephas_tpu.models.transformer._filter_logits` (same
    keep-until-mass-passes semantics, always keeping the top token).
    ``top_k[b] <= 0`` and ``top_p[b] >= 1`` disable the respective
    filter for that row, so one batched program serves every mix of
    per-request settings."""
    v = logits.shape[-1]
    # top-k first, then the nucleus over the top-k SURVIVORS — the same
    # sequential composition as the scalar filter (the nucleus mass is
    # renormalized within the top-k set, so the two are not independent)
    sorted_desc = jnp.sort(logits, axis=-1)[:, ::-1]
    kidx = jnp.clip(top_k - 1, 0, v - 1)
    kth = jnp.take_along_axis(sorted_desc, kidx[:, None], axis=-1)
    k_thr = jnp.where(((top_k > 0) & (top_k < v))[:, None], kth, -jnp.inf)
    logits = jnp.where(logits >= k_thr, logits, NEG_INF)
    # top-k masking cannot reorder survivors, so masking the FIRST sort
    # gives the sorted view of the masked logits — no second sort
    sorted_desc = jnp.where(sorted_desc >= k_thr, sorted_desc, NEG_INF)
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep_sorted = jnp.concatenate(
        [jnp.ones_like(cum[:, :1], bool), cum[:, :-1] < top_p[:, None]],
        axis=-1)
    p_kth = jnp.min(jnp.where(keep_sorted, sorted_desc, jnp.inf),
                    axis=-1, keepdims=True)
    p_thr = jnp.where(top_p[:, None] < 1.0, p_kth, -jnp.inf)
    return jnp.where(logits >= p_thr, logits, NEG_INF)

__all__ = ["DecodeEngine", "QueueFullError", "DeadlineExceededError",
           "validate_sampling_overrides", "INTER_TOKEN_BUCKETS"]

#: bucket bounds for ``serving_inter_token_seconds`` — finer at the
#: bottom than the latency defaults (a healthy decode step is
#: sub-millisecond on-chip; chunked emission's intra-chunk gaps are ~0)
INTER_TOKEN_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                       0.1, 0.25, 0.5, 1.0, 2.5)

#: reusable no-op context for profiler-less engines (nullcontext is
#: stateless, so one instance serves every section site)
_NULL_SECTION = contextlib.nullcontext()

#: adaptive-gamma controller: EWMA smoothing of per-round pooled
#: acceptance. 0.4 weights the last ~4 rounds — fast enough to catch a
#: draft going stale mid-request, smooth enough that one unlucky round
#: doesn't move the depth
GAMMA_EWMA_ALPHA = 0.4

#: adaptive-gamma controller: rounds between depth adjustments (and
#: each adjustment moves ONE step). Hysteresis against chattering —
#: recompiles are cached per depth, but verify-cost thrash is not free
GAMMA_ADJUST_EVERY = 4

#: interleaved prefill: iterations between prefill-budget recomputes.
#: The budget reads the profiler's utilization(), which walks the
#: ring-buffer under a lock — cheap, but not every-iteration cheap
#: against a sub-millisecond decode step
PREFILL_BUDGET_EVERY = 16

#: interleaved prefill: most chunks one iteration may feed. The budget
#: scales from 1 (decode-saturated loop — in-flight requests first) up
#: to this (decode mostly idle — drain the pending prompt fast)
MAX_INTERLEAVE_CHUNKS = 4


def validate_sampling_overrides(temperature, top_k, top_p) -> None:
    """THE per-request sampling validation — shared by every submit
    surface (engine submit, prefill export, the disaggregated front
    end), so an admission-rule change cannot silently diverge their
    400-at-submit behavior. ``None`` always means "engine default"."""
    if temperature is not None:
        if not (temperature >= 0 and np.isfinite(temperature)):
            raise ValueError("temperature must be >= 0 and finite, "
                             f"got {temperature}")
    if top_k is not None and top_k < 1:
        raise ValueError(f"top_k must be >= 1, got {top_k}")
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")


class _Flight(NamedTuple):
    """A dispatched decode step whose tokens the host has not read."""

    home: jax.Array      # what comes home: tokens, then routing counts
    tokens: jax.Array    # the tokens alone: the next step's ``prev``
    rows: np.ndarray     # (max_slots,) bool: the slots it stepped
    key_in: jax.Array    # the engine key it was given ...
    key_out: jax.Array   # ... and the one it returned


class DecodeEngine:
    """Slot-based continuous batching over one parameter pytree.

    :param params: transformer parameters (replicated or GSPMD-sharded)
    :param config: the model's :class:`TransformerConfig`
    :param max_slots: device batch width (concurrent requests)
    :param max_len: cache length per slot (default
        ``config.max_seq_len``); each request needs
        ``len(prompt) + max_new_tokens <= max_len``
    :param temperature: 0 = greedy (parity with ``generate``),
        otherwise categorical sampling
    :param eos_id: optional stop token — a request finishes early when
        it emits this id (the id itself is not part of the output)
    :param draft_params: optional draft-model parameters switching every
        slot to SPECULATIVE stepping: each ``step()`` runs one
        draft-propose / target-verify round
        (:func:`~elephas_tpu.models.speculative.speculative_round`), so
        a slot advances by ``1 + accepted`` tokens per step instead of
        one — continuous batching composed with speculative decoding.
        Per-request greedy output is unchanged (still ≡ solo
        ``generate``); only the number of host steps shrinks.
    :param draft_config: the draft model's config (same vocabulary)
    :param gamma: draft tokens proposed per round (speculative mode)
    :param prefill_chunk: when set, admission prefills prompts in
        fixed ``prefill_chunk``-token blocks (plus one natural-size
        tail), so jit compilation stops scaling with distinct prompt
        lengths: an online server sees at most ``prefill_chunk`` block
        shapes ever, instead of one compile per new length. Numerically
        identical to whole-prompt prefill; composes with prefix caching
        (the suffix is what gets chunked). A chunk attends over the
        narrowest width of a small ladder (``max_len`` halved three
        times, none narrower than a chunk) that covers the positions
        its row holds, picked on the device inside the one program a
        block shape has; ``serving_prefill_positions_held_total`` /
        ``..._read_total`` count what that saves.
    :param paged: ``(num_blocks, block_size)`` switches the KV cache to
        a shared block pool with per-slot block tables (vLLM's paged
        memory model): cache memory scales with tokens in flight
        instead of ``max_slots × max_len``, requests queue while the
        pool is momentarily empty, and blocks return on retirement.
        A decode step gathers the blocks the rows hold, so its cost
        follows the tokens in flight too (the step program picks, on
        the device, from a ladder of widths derived from ``max_slots``
        and the table width; see
        :mod:`~elephas_tpu.models.paged_decode`). Composes with prefix
        caching, chunked prefill and speculative mode
        (each slot's allocation budgets ``gamma`` extra positions of
        verify slack); not with ``kv_cache_quant`` or MoE.
    :param max_queue: admission bound on the backlog of queued
        (not-yet-admitted) requests; a :meth:`submit` that would push the
        backlog past it raises :class:`QueueFullError` instead of
        queueing forever (``None`` = unbounded, the pre-overload-safety
        behavior). Must be >= 1: the HTTP server submits with
        ``admit=False``, so every request passes through the queue even
        when a slot is free.
    :param max_queued_tokens: companion bound on the TOTAL prompt tokens
        waiting in the queue — a few enormous prompts can exhaust
        prefill capacity long before ``max_queue`` counts them.
    :param clock: monotonic time source for deadline bookkeeping
        (``time.monotonic``); injectable so chaos tests drive expiry
        deterministically without sleeping.
    :param tier: the serving tier this engine plays in a disaggregated
        topology — the ``tier`` label on its
        ``serving_queue_wait_seconds`` series. ``"colocated"`` (the
        default) is the classic one-engine-does-both deployment, whose
        queue wait INCLUDES head-of-line prefill blocking;
        ``"decode"`` marks a decode worker fed precomputed KV
        (:meth:`submit_prefilled`), whose queue wait is pure
        decode-stage backlog. The prefill tier's companion series is
        observed by :class:`~elephas_tpu.disagg.PrefillWorker` under
        ``tier="prefill"``.
    :param prefix_cache: the AUTOMATIC content-addressed KV block cache
        (see the module docstring). ``None`` means "on in paged mode,
        off otherwise"; pass ``False`` to disable (the bench A/B
        baseline) or ``True`` to enable the host-array-backed cache on
        a contiguous engine. Composes with speculative mode: the
        TARGET model's KV is what gets cached (draft KV is recomputed
        at admission, never cached), so chain keys stay seeded by the
        target's ``weights_version`` and a draft swap invalidates
        nothing.
    :param prefix_cache_block_size: cache granularity in tokens for the
        HOST-mode cache (contiguous engines; default 64). Paged engines
        always cache at the pool's ``block_size`` — passing a different
        value raises.
    :param prefix_cache_capacity: host-mode bound on cached blocks
        (LRU-evicted past it; default 1024; pinned registered-prefix
        blocks are exempt). Ignored in paged mode, where the pool
        itself is the capacity and reclaim happens under admission
        pressure.
    :param qos: a :class:`~elephas_tpu.serving_qos.TenantQoS` (or its
        ctor-kwargs dict) switching admission to per-tenant weighted
        fair queueing with quotas and priority preemption (see the
        module docstring). ``None`` (the default) keeps the exact
        FIFO semantics tenants or not — requests still carry a
        ``tenant`` for attribution, but no policy acts on it.
    :param registry: the :class:`~elephas_tpu.obs.MetricsRegistry` this
        engine's series land in. Defaults to a FRESH per-engine registry
        (not the process default): the registry counters are the single
        source of truth behind :attr:`stats`, which is a per-engine
        surface. Injecting a shared registry supports the sequential
        weight-reload flow — the replacement engine snapshots the
        counters at construction, so its stats start at zero while the
        scraped series keep pooled totals — but two CONCURRENTLY-live
        engines on one registry do pool counts (and the newest engine's
        queue gauges win); keep simultaneous engines on their default
        fresh registries. The HTTP server merges this registry with the
        process default registry on its ``GET /metrics`` route.
    :param profiler: the engine-loop continuous profiler
        (:class:`~elephas_tpu.obs.LoopProfiler`): the loop's sections
        (swap/admit/prefill/decode dispatch/decode wait/emit + idle)
        as ``serving_loop_phase_seconds_total{phase}`` counters,
        ``serving_loop_utilization{phase}`` gauges, the slow-iteration
        record and, under a ``jax.profiler`` session, ``elephas.loop.*``
        spans on the device trace's clock; jit compiles and garbage
        collections tracked separately. ``None`` (the default) creates
        one on this engine's registry — it is meant to be always-on
        (its measured cost: ``PERF.md``). Pass ``False`` to disable
        (the bench A/B baseline) or an instance to share one across
        wrappers.
    :param adaptive_gamma: steer the speculation depth per engine from
        measured draft acceptance: ``gamma`` becomes the CEILING (all
        capacity/slack accounting stays sized to it, so shrinking is
        always safe) and the operating depth walks between
        ``gamma_min`` and the ceiling as the acceptance EWMA moves — a
        stale draft shrinks gamma within a few rounds (recovering the
        wasted draft steps long before fleet-level acceptance alerts),
        and a draft re-stage resets it to the ceiling. Greedy engines
        stay token-identical under ANY gamma schedule (the verify emit
        is an exact argmax-prefix match).
    :param gamma_min: adaptive gamma's floor (default 1 = one draft
        token per round at zero acceptance).
    :param interleave_prefill: schedule chunked admission prefills
        BETWEEN decode steps instead of running each to completion at
        admission: every engine iteration feeds at most a budgeted
        number of ``prefill_chunk``-token chunks (budget derived from
        the profiler's decode-phase utilization), so a long prompt's
        admission no longer stalls in-flight decodes — their
        inter-token latency stays flat while the long request's TTFT
        degrades gracefully. Requires ``prefill_chunk``. Outputs are
        token-identical to run-to-completion admission (same chunk
        shapes, same math; slots are isolated).
    """

    #: flight-recorder decode sampling: one ``step`` timeline event per
    #: this many emitted tokens per request (every token would blow the
    #: per-request event cap on long generations for no diagnostic gain)
    TRACE_STEP_EVERY = 8

    def __init__(self, params: Dict, config: TransformerConfig,
                 max_slots: int = 8, max_len: Optional[int] = None,
                 temperature: float = 0.0, eos_id: Optional[int] = None,
                 seed: int = 0, draft_params: Optional[Dict] = None,
                 draft_config: Optional[TransformerConfig] = None,
                 gamma: int = 4,
                 prefill_chunk: Optional[int] = None,
                 paged: Optional[Tuple[int, int]] = None,
                 max_queue: Optional[int] = None,
                 max_queued_tokens: Optional[int] = None,
                 clock=time.monotonic, tier: str = "colocated",
                 registry: Optional[MetricsRegistry] = None,
                 prefix_cache: Optional[bool] = None,
                 prefix_cache_block_size: Optional[int] = None,
                 prefix_cache_capacity: Optional[int] = None,
                 qos: Optional[TenantQoS] = None,
                 profiler: Union[None, bool, LoopProfiler] = None,
                 kv_spill=None, session_store=None,
                 adaptive_gamma: bool = False, gamma_min: int = 1,
                 interleave_prefill: bool = False):
        self.params = params
        self.config = config
        self.max_slots = int(max_slots)
        self.max_len = int(max_len or config.max_seq_len)
        if self.max_len > config.max_seq_len:
            raise ValueError(f"max_len {self.max_len} exceeds "
                             f"config.max_seq_len {config.max_seq_len}")
        self.temperature = float(temperature)
        self.eos_id = eos_id
        if (draft_params is None) != (draft_config is None):
            raise ValueError("draft_params and draft_config go together")
        if draft_config is not None:
            from .models.paged_decode import require_stateless_cache

            require_stateless_cache(config, "a draft model (speculative "
                                            "decoding rolls rows back)")
            if draft_config.vocab_size != config.vocab_size:
                raise ValueError(
                    f"draft vocab {draft_config.vocab_size} != target "
                    f"vocab {config.vocab_size}")
            if gamma < 1:
                raise ValueError("gamma must be >= 1")
            if self.max_len > draft_config.max_seq_len:
                raise ValueError(
                    f"max_len {self.max_len} exceeds draft max_seq_len "
                    f"{draft_config.max_seq_len}")
        self.draft_params = draft_params
        self.draft_config = draft_config
        self.gamma = int(gamma)
        # adaptive speculative gamma: ``self.gamma`` is the CEILING —
        # every capacity rule (verify slack, the paged per-slot block
        # budget) stays sized to it, so the acceptance controller can
        # only ever SHRINK the speculation depth below what admission
        # reserved, never outgrow it. ``_gamma_now`` is the operating
        # depth, steered per engine from measured acceptance (see
        # ``_steer_gamma``); fixed-gamma engines keep it pinned.
        self.adaptive_gamma = bool(adaptive_gamma)
        self.gamma_min = int(gamma_min)
        if self.adaptive_gamma and draft_config is None:
            raise ValueError("adaptive_gamma requires a draft model "
                             "(draft_params/draft_config)")
        if draft_config is not None and not (
                1 <= self.gamma_min <= self.gamma):
            raise ValueError(f"gamma_min {self.gamma_min} must satisfy "
                             f"1 <= gamma_min <= gamma ({self.gamma})")
        self._gamma_now = self.gamma
        # EWMA of per-round batch acceptance fraction (None until the
        # first speculative round samples it) + rounds since the last
        # gamma adjustment (hysteresis: move at most one step every
        # GAMMA_ADJUST_EVERY rounds)
        self._accept_ewma: Optional[float] = None
        self._rounds_since_adjust = 0
        # verify slack: a speculative round writes up to gamma positions
        # past the last emitted token, so every capacity rule (the
        # max_len bound AND the paged per-slot block budget) reserves
        # gamma extra positions per slot — the CEILING, under adaptive
        # gamma, so shrinking mid-flight is always safe
        self._slack = self.gamma if draft_config is not None else 0
        self.prefill_chunk = (None if prefill_chunk is None
                              else int(prefill_chunk))
        if self.prefill_chunk is not None and self.prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")
        self.paged = None
        if paged is not None:
            from .models.paged_decode import validate_paged_config

            num_blocks, block_size = int(paged[0]), int(paged[1])
            validate_paged_config(config)
            if draft_config is not None:
                from .models.paged_decode import require_per_head_cache

                require_per_head_cache(config, "paged speculative "
                                               "decoding")
            if block_size < 1 or num_blocks < 2:
                raise ValueError("paged needs block_size >= 1 and "
                                 "num_blocks >= 2 (block 0 is the "
                                 "reserved scratch sink)")
            self.paged = (num_blocks, block_size)
            # per-slot table width: enough blocks to cover max_len
            self._mb = -(-self.max_len // block_size)
        # chunked-prefill interleaving (ctor docstring): pending
        # admissions whose prompt is still being fed chunk-by-chunk
        # between decode steps. slot -> state dict (see
        # _begin_interleaved_prefill for the fields); the slot is
        # RESERVED (excluded from _free_slots) but not yet decoding.
        self.interleave_prefill = bool(interleave_prefill)
        if self.interleave_prefill and self.prefill_chunk is None:
            raise ValueError("interleave_prefill requires prefill_chunk")
        self._pending_prefill: Dict[int, Dict] = {}
        # chunks-per-iteration budget, recomputed from the profiler's
        # decode-phase utilization every PREFILL_BUDGET_EVERY iterations
        # (one utilization() ring walk costs ~the profiler's whole
        # per-step budget, so it is cached, not read per step)
        self._prefill_budget = 1
        self._budget_age = 0
        self._key = jax.random.PRNGKey(seed)
        if self.paged is not None:
            from .models.paged_decode import init_paged_pool

            nb, bsz = self.paged
            self.cache = None        # the pool replaces the contiguous cache
            # (a config whose rows keep recurrent state gets it per
            # slot, in the same donated tree as the blocks)
            self.pool = init_paged_pool(config, nb, bsz,
                                        slots=self.max_slots)
            self._tables = np.zeros((self.max_slots, self._mb), np.int32)
            self._free_block_ids = deque(range(1, nb))  # 0 = scratch
            self._slot_blocks: List[List[int]] = [
                [] for _ in range(self.max_slots)]
        else:
            self.cache = init_kv_cache(config, self.max_slots,
                                       self.max_len)
        # per-slot SHARED prefix-cache entries the slot's table points
        # at (refcounted; released on retirement) — disjoint from
        # _slot_blocks, which holds the slot's PRIVATE block ids
        self._slot_cached: List[List] = [[] for _ in range(self.max_slots)]
        self.draft_cache = (init_kv_cache(draft_config, self.max_slots,
                                          self.max_len)
                            if draft_config is not None else None)
        # host-side slot state: position of the last PROCESSED token,
        # the pending (emitted, not yet processed) token, budgets
        self._pos = np.zeros(self.max_slots, np.int32)
        self._last = np.zeros(self.max_slots, np.int32)
        self._budget = np.zeros(self.max_slots, np.int32)
        # the plain path keeps one decode step in flight (_step_plain):
        # the step whose tokens the host has not read yet, and the
        # slots whose pending token the HOST set since the last
        # dispatch (an admission's first token) -- every other live
        # row's pending token is that step's output, on the device
        self._ahead: Optional[_Flight] = None
        self._last_set = np.zeros(self.max_slots, bool)
        self._temp = np.full(self.max_slots, self.temperature, np.float32)
        self._topk = np.zeros(self.max_slots, np.int32)    # 0 = off
        self._topp = np.ones(self.max_slots, np.float32)   # 1 = off
        # per-slot request seed (-1 = unseeded: the engine's shared
        # key samples, exactly as before per-request seeds existed)
        self._slot_seed = np.full(self.max_slots, -1, np.int32)
        self._rid = [None] * self.max_slots
        # multi-tenant QoS: the policy object (None = plain FIFO) and
        # the admission queue enforcing it; per-slot tenant/priority/
        # prompt metadata backs preemption and per-tenant accounting
        self.qos = TenantQoS.coerce(qos)
        self._queue: FairQueue = FairQueue(self.qos)
        self._slot_prompt: List[Optional[np.ndarray]] = (
            [None] * self.max_slots)
        # output tokens already FOLDED INTO _slot_prompt: a resumed
        # request's admission prompt is original-prompt + everything
        # emitted before its preemption, so a SECOND preemption must
        # only append the tokens emitted since (else they duplicate)
        self._slot_prior = np.zeros(self.max_slots, np.int64)
        self._slot_tenant: List[Optional[str]] = [None] * self.max_slots
        self._slot_priority = np.zeros(self.max_slots, np.int32)
        # weights_version each slot was ADMITTED under: a preempted
        # slot's KV only parks when the engine still serves that
        # version (post-swap chain keys would address old-weight KV)
        self._slot_wv = np.zeros(self.max_slots, np.int64)
        # tiered KV spill + resumable sessions (:mod:`~elephas_tpu.
        # kvtier`) — wired up after the prefix-cache block below;
        # the slot state lives here with its siblings. _slot_lossy
        # taints a slot that admitted over a LOSSY (Q8-round-tripped)
        # promoted block: nothing it computes may register, park, or
        # persist under chain keys (the lossy-parity rule).
        self._kv_spill = None
        self._session_store = None
        self._lossy_promote = False
        # (rid, version, start_block, promos) — the tier walk's memo,
        # invalidated whenever the DEVICE hit count at the same rid
        # changes (another admission may have registered more of the
        # chain while this candidate waited, shifting the walk start)
        self._promo_memo: Optional[Tuple] = None
        # per-admission demotion tally: set around the allocation loop
        # so a large allocation's evictions flush as ONE kv_demote
        # event instead of flooding the per-rid recorder cap
        self._demote_accum: Optional[Dict[str, int]] = None
        self._m_spill_demote = None
        self._m_spill_promote = None
        self._m_spill_bytes = None
        self._m_session_hits = None
        self._m_session_misses = None
        self._slot_lossy = [False] * self.max_slots
        # slot -> [(SpilledBlock, source_tier)] claimed by _admit's
        # tier walk, consumed by the admission prefill's install
        self._slot_promos: Dict[int, List] = {}
        # rid -> session id (rid-keyed so it survives preemption
        # re-queues, like _seed); dropped at retirement/cancel
        self._session: Dict[int, str] = {}
        # rid -> {"outputs": [...], "preempts": n} for requests
        # preempted mid-decode and re-queued for resume
        self._resume: Dict[int, Dict] = {}
        # rid -> per-request RNG seed: rid-keyed (not queue-item state)
        # so it survives preemption re-queues; dropped at retirement
        self._seed: Dict[int, int] = {}
        self._outputs: Dict = {}
        self._done: Dict = {}
        # rid -> [tokens]: admission-time tokens awaiting step() — a
        # list, because a request preempted before its first step and
        # resumed owes the stream BOTH admissions' first tokens
        self._fresh: Dict = {}
        # rid -> (kv_blocks, first_token) for requests whose prefill
        # happened off-engine (submit_prefilled); consumed at admission
        self._prefilled_kv: Dict[int, Tuple] = {}
        self._next_rid = 0
        # overload safety: admission bounds + per-request deadlines
        self.max_queue = None if max_queue is None else int(max_queue)
        if self.max_queue is not None and self.max_queue < 1:
            raise ValueError("max_queue must be None or >= 1 (the HTTP "
                             "server's admit=False submits always pass "
                             "through the queue)")
        self.max_queued_tokens = (None if max_queued_tokens is None
                                  else int(max_queued_tokens))
        if (self.max_queued_tokens is not None
                and self.max_queued_tokens < 1):
            raise ValueError("max_queued_tokens must be None or >= 1")
        self._clock = clock
        self._queued_tokens = 0              # prompt tokens in the queue
        self._deadline: Dict[int, float] = {}  # rid -> absolute deadline
        # distributed tracing: the context captured at submit (the HTTP
        # handler thread's), restored around THIS request's share of the
        # engine loop's work, plus the per-request flight recorder the
        # trace endpoints read (every event stamped with the trace id)
        self._trace_ctx: Dict[int, object] = {}
        self.recorder = FlightRecorder()
        self._expired: set = set()   # shed while queued (never prefilled)
        self._timed_out: set = set()  # deadline hit mid-decode (partial)
        # observability: the registry is the single store behind .stats
        # (per-engine by default — see the registry param docstring)
        self.registry = reg = (registry if registry is not None
                               else MetricsRegistry())
        # label-less children are resolved ONCE (.labels() with no
        # labels): per-token hot paths pay one child-lock inc, never a
        # family lock + dict lookup per token
        self._m_steps = reg.counter(
            "serving_steps_total",
            "device round trips (engine steps)").labels()
        self._m_emitted = reg.counter(
            "serving_tokens_emitted_total", "output tokens emitted"
            ).labels()
        self._m_ahead = reg.counter(
            "serving_decode_steps_ahead_total",
            "decode steps dispatched while the step before them was "
            "still in flight, its tokens unread (plain stepping runs "
            "one ahead; of serving_steps_total, all but the first "
            "after an idle engine)").labels()
        self._m_surplus = reg.counter(
            "serving_decode_surplus_rows_total",
            "row-steps whose token was dropped: the row retired (eos, "
            "cancel, deadline, preemption) with its next step already "
            "in flight").labels()
        self._m_finished = reg.counter(
            "serving_requests_finished_total",
            "requests retired at eos or budget").labels()
        self._m_shed = reg.counter(
            "serving_requests_shed_total",
            "admission rejections (queue full / injected shed; HTTP 429)"
            ).labels()
        self._m_expired = reg.counter(
            "serving_requests_expired_total",
            "deadline passed while queued — shed before prefill (504)"
            ).labels()
        self._m_timed_out = reg.counter(
            "serving_requests_timed_out_total",
            "deadline passed mid-decode — partial output returned"
            ).labels()
        # flight-recorder ring evictions, split by whether the evicted
        # request was still in flight: a truncated ACTIVE timeline is
        # the one that reads as "request never existed"
        self.recorder.bind_eviction_counter(reg.counter(
            "flight_recorder_evictions_total",
            "flight-recorder timelines evicted by the ring bound, "
            "by request state at eviction", labels=("state",)))
        # gauge callbacks hold a WEAK reference: with an injected
        # long-lived registry, a discarded engine (weight reload) must
        # not be pinned — with its params — by its own scrape callbacks
        import weakref

        ref = weakref.ref(self)
        self._m_queue_depth = reg.gauge(
            "serving_queue_depth", "requests backlogged, not yet admitted")
        self._m_queue_depth.set_function(
            lambda: float(len(e._queue))
            if (e := ref()) is not None else 0.0)
        self._m_queued_tokens = reg.gauge(
            "serving_queued_tokens", "prompt tokens waiting in the queue")
        self._m_queued_tokens.set_function(
            lambda: float(e._queued_tokens)
            if (e := ref()) is not None else 0.0)
        self._m_step_latency = reg.histogram(
            "serving_step_latency_seconds",
            "wall time of one engine step (admission + device dispatch)"
            ).labels()
        self._m_request_latency = reg.histogram(
            "serving_request_latency_seconds",
            "submit-to-retirement wall time per finished request",
            exemplars=True).labels()
        # labeled by serving tier: a disaggregated deployment's headline
        # claim — decode-tier queue wait free of prefill head-of-line
        # blocking — must be readable straight off /metrics, next to the
        # prefill tier's series (PrefillWorker observes tier="prefill"
        # into the same family)
        self.tier = str(tier)
        self._m_queue_wait = reg.histogram(
            "serving_queue_wait_seconds",
            "submit-to-admission wall time per admitted request, by "
            "serving tier", labels=("tier",)).labels(tier=self.tier)
        # per-request wall-clock: submit time per rid + a bounded window
        # of completed (queue_wait_s, total_s) samples for percentiles
        # (kept alongside the histograms: _retry_after_ms needs raw
        # medians over exactly this window)
        self._submit_t: Dict[int, float] = {}
        self._admit_t: Dict[int, float] = {}
        self._latency_window: deque = deque(maxlen=1024)
        # user-experienced latency decomposition: time-to-first-token
        # (submit -> first output token; exemplar-enabled so a p99
        # outlier links to its flight-recorder timeline) and the gap
        # between consecutive tokens of one request. These observe off
        # HOST dicts keyed by rid — never the bounded flight-recorder
        # ring, whose eviction must not cost a histogram sample.
        self._m_ttft = reg.histogram(
            "serving_ttft_seconds",
            "submit-to-first-token wall time per request (disagg "
            "front ends pass their submit stamp through, so the "
            "prefill tier's queue+ship time lands inside)",
            exemplars=True).labels()
        self._m_inter_token = reg.histogram(
            "serving_inter_token_seconds",
            "wall time between consecutive output tokens of one "
            "request (chunked/speculative emission: intra-chunk gaps "
            "are ~0 with one chunk-interval sample — exactly what a "
            "non-streaming client experiences)",
            buckets=INTER_TOKEN_BUCKETS).labels()
        # rid -> monotonic stamp of the FRONT-END submit, when it
        # precedes this engine's own (submit_prefilled's submitted_at);
        # rid -> last token emission stamp; rid -> observed ttft for
        # the terminal flight-recorder event
        self._ttft_origin: Dict[int, float] = {}
        self._last_tok_t: Dict[int, float] = {}
        self._ttft_val: Dict[int, float] = {}
        # engine-loop continuous profiler (see the ctor docstring):
        # False disables, None builds one on this registry
        if profiler is False:
            self.profiler: Optional[LoopProfiler] = None
        elif profiler is None or profiler is True:
            self.profiler = LoopProfiler(reg)
        else:
            self.profiler = profiler
        self._m_accepted = reg.counter(
            "serving_draft_tokens_accepted_total",
            "speculative draft tokens accepted by the target model"
            ).labels()
        self._m_proposed = reg.counter(
            "serving_draft_tokens_proposed_total",
            "speculative draft tokens proposed").labels()
        if draft_config is not None:
            self._m_spec_rounds = reg.counter(
                "serving_speculative_rounds_total",
                "draft-propose/target-verify rounds run (one per "
                "active slot per step)").labels()
            # the registry half of per-engine acceptance: the live
            # accepted/proposed ratio as a scrapeable gauge (the same
            # number stats/the fleet prober read — baselined like
            # stats, so an injected shared registry's predecessor
            # counts never pool in). NaN (not 0.0) before any
            # proposal, mirroring stats' None: an idle replica must
            # not trip a stale-draft (low-acceptance) alert
            reg.gauge(
                "serving_speculative_acceptance",
                "draft acceptance rate (accepted / proposed draft "
                "tokens, engine lifetime; NaN before any proposal)"
                ).set_function(
                lambda: (e._since_init(e._m_accepted) / p
                         if (e := ref()) is not None
                         and (p := e._since_init(e._m_proposed))
                         else float("nan")))
            # the adaptive controller's operating depth (== the ctor
            # gamma, constantly, when adaptive_gamma is off). Watching
            # this gauge against serving_speculative_acceptance shows
            # the control loop working: an acceptance dip drags gamma
            # down within a few rounds, a draft re-stage snaps it back
            # to the ceiling
            reg.gauge(
                "serving_gamma",
                "speculative depth currently proposed per round "
                "(adaptive engines steer this between gamma_min and "
                "the ctor gamma ceiling)").set_function(
                lambda: (float(e._gamma_now) if (e := ref()) is not None
                         else 0.0))
        # rid -> [accepted, proposed] draft-token counts for the
        # request's flight-recorder terminal event (per-request
        # acceptance observability; survives preemption — keyed by rid)
        self._accept: Dict[int, List[int]] = {}
        if self.paged is not None:
            # widths of the decode step's flat block list, derived from
            # the shapes
            from .models.paged_decode import held_ladder, held_tile

            # (a latent pool's rows take whole tiles of the list)
            self._held_tile = held_tile(config)
            self._held_ladder = held_ladder(config, self.max_slots,
                                            self._mb)
            self._m_blocks_held = reg.counter(
                "serving_decode_blocks_held_total",
                "KV blocks the batch's rows held, summed over decode "
                "dispatches").labels()
            self._m_blocks_read = reg.counter(
                "serving_decode_blocks_read_total",
                "KV blocks the decode program gathered per layer (the "
                "ladder width it picked), summed over decode dispatches; "
                "held / read is the share a row really holds").labels()
            fam = reg.counter(
                "serving_decode_steps_total",
                "decode dispatches by the width of their flat block "
                "list", labels=("width",))
            self._m_steps_by_width = {
                w: fam.labels(width=str(w)) for w in self._held_ladder}
            # routing counts the paged step returns with its tokens
            # (models/grouped_experts.STATS, summed over the step's
            # swiglu expert layers); they stay 0 for other models
            self._m_moe = [reg.counter(name, doc).labels() for name, doc in (
                ("serving_moe_picks_total",
                 "expert picks made by live rows (rows x top-k x expert "
                 "layers), summed over decode dispatches"),
                ("serving_moe_held_picks_total",
                 "of those, picks that fell on an expert this engine "
                 "holds"),
                ("serving_moe_experts_touched_total",
                 "held experts that got at least one pick, summed over "
                 "expert layers and decode dispatches: the experts whose "
                 "weights a step had to read"),
                ("serving_moe_layer_steps_total",
                 "expert layers run, summed over decode dispatches"))]
            reg.gauge("serving_paged_blocks_free",
                      "allocatable KV blocks currently free"
                      ).set_function(
                lambda: float(len(e._free_block_ids))
                if (e := ref()) is not None else 0.0)
            # every paged engine says what admission pressure could
            # reclaim, so that free + reclaimable reads the pool's room
            # whether or not a prefix cache holds blocks (without one,
            # as for a config with per-slot state: 0)
            self._reclaimable_gauge()
        # widths of an admission chunk's attention, derived from the
        # shapes (no chunks, no ladder: a whole-prompt extend reads the
        # whole row)
        self._prefill_ladder = (
            () if self.prefill_chunk is None
            else prefill_ladder(self.prefill_chunk, self.max_len))
        if self._prefill_ladder:
            self._m_positions_held = reg.counter(
                "serving_prefill_positions_held_total",
                "cached positions an admission chunk's queries could see "
                "(its last position + 1), summed over chunks").labels()
            self._m_positions_read = reg.counter(
                "serving_prefill_positions_read_total",
                "cached positions the chunk's attention ran over (the "
                "ladder width it picked), summed over chunks; held / "
                "read is the share that was not masked away").labels()
            fam = reg.counter(
                "serving_prefill_chunks_total",
                "admission chunks by the width of their attention",
                labels=("width",))
            self._m_chunks_by_width = {
                w: fam.labels(width=str(w)) for w in self._prefill_ladder}
        # a state-space mixer's work and what it keeps (0 for a config
        # without one): a row's state is read and written once per
        # decode dispatch and layer, a prompt's tokens are scanned once
        # per layer, and the slots' state stays on the device
        self._m_ssm_updates = reg.counter(
            "serving_ssm_row_updates_total",
            "recurrent-state updates by decode dispatches: live rows x "
            "layers with a state-space mixer").labels()
        self._m_ssm_scanned = reg.counter(
            "serving_ssm_scan_tokens_total",
            "tokens run through the chunk scan by admission chunks: a "
            "chunk's tokens x layers with a state-space mixer").labels()
        self._ssm_layers = (config.num_layers if config.ssm is not None
                            else 0)
        reg.gauge(
            "serving_ssm_state_bytes",
            "recurrent state resident on the device: slots x layers x "
            "the bytes of a row's state leaves").set(float(
                self.max_slots * self._ssm_layers * sum(
                    int(np.prod(shape)) * jnp.dtype(dtype).itemsize
                    for shape, dtype in config.state_leaves().values())))
        self._m_interleaved = reg.counter(
            "serving_prefill_chunks_interleaved_total",
            "prompt-prefill chunks fed between decode steps by the "
            "interleaving scheduler (0 on run-to-completion engines)"
            ).labels()
        # live weight plane: params staged by a WeightSubscriber (any
        # thread) swap in atomically between decode steps — the same
        # point KV installs use. weights_version names what the engine
        # is CURRENTLY serving (0 = construction-time params; a
        # subscriber stamps it with the parameter plane's version).
        self.weights_version = 0
        self._staged_lock = threading.Lock()
        self._staged_params: Optional[Tuple] = None
        # the DRAFT's own staging channel (speculative mode): a second
        # WeightSubscriber keeps a continuously re-distilled draft
        # fresh. Versioned independently of the target — draft chain
        # keys never exist (draft KV is not cached), so a draft swap
        # invalidates nothing and costs only the registered prefixes'
        # draft-row recompute.
        self.draft_weights_version = 0
        self._staged_draft: Optional[Tuple] = None
        if draft_config is not None:
            reg.gauge("serving_draft_weights_version",
                      "draft-model weight version currently proposing "
                      "(0 = construction-time draft params)"
                      ).set_function(
                lambda: float(e.draft_weights_version)
                if (e := ref()) is not None else 0.0)
        reg.gauge("serving_weights_version",
                  "weight version the engine is currently serving "
                  "(0 = construction-time params)").set_function(
            lambda: float(e.weights_version)
            if (e := ref()) is not None else 0.0)
        self._m_weight_swaps = reg.counter(
            "serving_weight_swaps_total",
            "live weight hot-swaps applied between decode steps"
            ).labels()
        self._m_swap_pause = reg.histogram(
            "serving_weight_swap_seconds",
            "engine-loop blockage per weight swap (param pointer swap "
            "+ registered-prefix recompute)").labels()
        self._m_preemptions = reg.counter(
            "serving_preemptions_total",
            "in-flight decodes preempted by a higher-priority "
            "admission (KV parked, request re-queued)").labels()
        if self.qos is not None:
            # per-tenant series: configured tenants get their own label
            # (client-chosen names fold into "other" — label domains
            # must stay bounded); the queued-tokens gauge children are
            # registered lazily per label with weakref callbacks, the
            # engines' gauge convention
            self._m_tenant_queued = reg.gauge(
                "serving_tenant_queued_tokens",
                "prompt tokens waiting in the queue, by tenant",
                labels=("tenant",))
            self._m_tenant_admitted = reg.counter(
                "serving_tenant_admitted_total",
                "requests admitted to a decode slot, by tenant",
                labels=("tenant",))
            self._m_tenant_preempt = reg.counter(
                "serving_tenant_preemptions_total",
                "in-flight decodes preempted, by (victim) tenant",
                labels=("tenant",))
            self._m_tenant_shed = reg.counter(
                "serving_tenant_sheds_total",
                "admission rejections by tenant and reason "
                "(tenant_quota = the per-tenant 429)",
                labels=("tenant", "reason"))
            self._tenant_gauge_labels: set = set()

        cfg = config
        temp = self.temperature

        def _sample_tok(logits, temps, topk, topp, seeds, pos, key):
            # per-slot sampling settings: each request samples at its
            # own temperature (0 = greedy) / top-k / top-p inside one
            # batched step — all branches are computed and where() picks
            # per row, one sort + categorical over (B, V), noise next to
            # the model forward. THE sampling body: every step variant
            # (contiguous/paged) calls it, so modes cannot
            # drift. Order matches generate: temperature scales first,
            # THEN the nucleus is chosen on the scaled logits
            key, sub = jax.random.split(key)
            safe = jnp.maximum(temps, 1e-6)[:, None]
            # the sort/softmax/cumsum filter only runs when some SAMPLED
            # row asked for it — the default all-greedy engine pays
            # nothing (one compiled program either way via cond)
            need = jnp.any(((topk > 0) | (topp < 1.0)) & (temps > 0))
            filtered = jax.lax.cond(
                need, lambda x: _filter_logits_rows(x, topk, topp),
                lambda x: x, logits / safe)
            sampled = jax.random.categorical(sub, filtered, axis=-1)
            # per-request seeds (seed >= 0): the row's key is a pure
            # function of (seed, absolute position of the token being
            # sampled) — independent of batch composition, engine-key
            # history, and sibling slots — so a request resumed on
            # ANOTHER replica (or after preemption) re-samples its
            # remaining tokens identically. Unseeded rows keep the
            # shared engine key bit-for-bit as before.
            any_seeded = jnp.any((seeds >= 0) & (temps > 0))

            def _seeded_rows(f):
                row_keys = jax.vmap(lambda s, p: jax.random.fold_in(
                    jax.random.PRNGKey(s), p + 1))(seeds, pos)
                return jax.vmap(jax.random.categorical)(row_keys, f)

            seeded = jax.lax.cond(any_seeded, _seeded_rows,
                                  lambda f: sampled, filtered)
            sampled = jnp.where(seeds >= 0, seeded, sampled)
            tok = jnp.where(temps > 0, sampled,
                            jnp.argmax(logits, axis=-1))
            return tok.astype(jnp.int32), key

        def _one_step(params, cache, last, pos, temps, topk, topp,
                      seeds, key):
            logits, cache = decode_step(params, cache, last, pos, cfg)
            tok, key = _sample_tok(logits, temps, topk, topp, seeds,
                                   pos, key)
            return tok, cache, key

        def _ride(last, prev):
            # one step is kept in flight (_step_plain): a row still
            # riding the step before this one has -1 for its token on
            # the host and takes that step's output here, on the
            # device; a row the host set since (an admission's first
            # token) overrides it
            return jnp.where(last >= 0, last, prev)

        @partial(jax.jit, donate_argnums=(1,))
        def _step(params, cache, last, prev, pos, temps, topk, topp,
                  seeds, key):
            return _one_step(params, cache, _ride(last, prev), pos,
                             temps, topk, topp, seeds, key)

        if self.paged is not None:
            from .models.paged_decode import decode_step_paged

            # one program holds a branch per ladder width and picks
            # among them on the device, from the positions it is given
            ladder = self._held_ladder

            def _one_step_paged(params, pool, tables, last, pos, temps,
                                topk, topp, seeds, key):
                logits, pool, stats = decode_step_paged(
                    params, pool, tables, last, pos, cfg,
                    held_blocks=ladder, with_stats=True)
                tok, key = _sample_tok(logits, temps, topk, topp, seeds,
                                       pos, key)
                # a model with routing counts sends them home behind the
                # tokens, in the same array: no second transfer
                if stats is not None:
                    tok = jnp.concatenate([tok, stats["counts"]])
                return tok, pool, key

            @partial(jax.jit, donate_argnums=(1,))
            def _step_paged(params, pool, tables, last, prev, pos, temps,
                            topk, topp, seeds, key):
                home, pool, key = _one_step_paged(
                    params, pool, tables, _ride(last, prev), pos, temps,
                    topk, topp, seeds, key)
                # the tokens a second time, without what rides home
                # behind them: the next step's ``prev``, which never
                # leaves the device
                return home, home[:last.shape[0]], pool, key

            self._step_paged_fn = _step_paged

        @partial(jax.jit, donate_argnums=(0,))
        def _install(cache, row_cache, slot):
            # slot is traced: one compilation serves every slot index;
            # the engine cache is donated (like _step's) so neither hot
            # path copies the multi-layer k/v buffers
            return jax.tree_util.tree_map(
                lambda big, row: jax.lax.dynamic_update_index_in_dim(
                    big, row[0], slot, 0), cache, row_cache)

        max_len = self.max_len
        chunk_widths = self._prefill_ladder

        @jax.jit
        def _prefill(params, prompt):
            # jit caches one executable per prompt-length shape: the
            # "one compile per distinct prompt length" admission cost
            return prefill_cache(params, prompt, cfg, max_len)

        def _make_extend(xcfg, donate=False):
            # two variants: the non-donating one serves shared prefix
            # entries (reused by every admission that hits them); the
            # donating one serves engine-OWNED rows — fresh prefill rows
            # and every chunk after the first — so chunked admission
            # rewrites one buffer instead of copying the full row cache
            # per block
            def _extend(params, row_cache, suffix, pos0):
                # continue a batch-1 prefill past what the row cache
                # already holds: the suffix attends to the cached k/v,
                # over the narrowest width of the ladder that covers it
                # (one program a suffix shape, a branch a width)
                # (a config with a mixer runs the head for the last
                # position alone; the others keep the programs they
                # were compiled as)
                logits, row_cache = decode_block(
                    params, row_cache, suffix, pos0, xcfg,
                    attend_widths=chunk_widths,
                    last_only=xcfg.ssm is not None)
                return logits[:, -1], row_cache
            if donate:
                return partial(jax.jit, donate_argnums=(1,))(_extend)
            return jax.jit(_extend)

        def _make_fresh_row(xcfg):
            # an admission's empty row in ONE program (made leaf by leaf
            # it is two tiny programs a leaf, the device empty between
            # them); its outputs are new buffers every call, so the
            # donating extend may take them
            @jax.jit
            def _fresh_row():
                return init_kv_cache(xcfg, 1, max_len)
            return _fresh_row

        @jax.jit
        def _first_greedy(logits):
            # the admission's first token, from the last chunk's
            # (1, vocab) logits as the program returned them
            return jnp.argmax(logits[0])

        @jax.jit
        def _first_sampled(logits, temp, topk, topp, seed, fold, key):
            # the step's own sampling body over the one row: a seed >= 0
            # keys the draw by (seed, position of the token), any other
            # draw consumes a split of the engine key, which comes back
            # beside the token (``fold`` is the token's own position,
            # one past the last the row holds)
            tok, key = _sample_tok(logits, temp[None], topk[None],
                                   topp[None], seed[None], fold[None] - 1,
                                   key)
            return tok[0], key

        self._step_fn = _step
        self._install_fn = _install
        self._prefill_fn = _prefill
        self._extend_fn = _make_extend(cfg)
        self._extend_owned_fn = _make_extend(cfg, donate=True)
        self._fresh_row_fn = _make_fresh_row(cfg)
        self._first_greedy_fn = _first_greedy
        self._first_sampled_fn = _first_sampled
        # registered shared prompt prefixes, longest first:
        # (tokens, last-position logits (1, vocab), target row cache,
        # draft row cache)
        self._prefixes: List = []
        self._m_prefix_hits = reg.counter(
            "serving_prefix_hits_total",
            "admissions that reused a registered prompt prefix").labels()
        self._m_prefix_tokens = reg.counter(
            "serving_prefix_tokens_reused_total",
            "prompt tokens whose prefill was skipped via a prefix hit"
            ).labels()
        # automatic content-addressed KV block cache (module docstring):
        # default ON in paged mode, opt-in host-backed otherwise
        self._kv_cache = None
        self._kv_cache_bs: Optional[int] = None
        if prefix_cache is None:
            # (a hit skips tokens whose recurrent state nobody kept)
            prefix_cache = self.paged is not None and config.ssm is None
        if prefix_cache:
            self.enable_prefix_cache(
                block_size=prefix_cache_block_size,
                capacity=prefix_cache_capacity)
        elif (prefix_cache_block_size is not None
                or prefix_cache_capacity is not None):
            raise ValueError("prefix_cache_block_size/"
                             "prefix_cache_capacity given with "
                             "prefix_cache disabled")
        # tiered KV spill / resumable sessions: True = defaults, a
        # dict = enable_* kwargs, an instance = share it (the shared-
        # instance form is the cross-replica session topology)
        if kv_spill:
            if kv_spill is True:
                self.enable_kv_spill()
            elif isinstance(kv_spill, dict):
                self.enable_kv_spill(**kv_spill)
            else:
                self.enable_kv_spill(spill=kv_spill)
        if session_store:
            if session_store is True:
                self.enable_session_store()
            elif isinstance(session_store, dict):
                self.enable_session_store(**session_store)
            else:
                self.enable_session_store(store=session_store)
        # construction-time baselines: an INJECTED shared registry may
        # already carry a predecessor engine's totals (weight-reload
        # flow) — stats must report THIS engine's deltas, never pooled
        # counts. With the default fresh registry every baseline is
        # zero and stats equals the scraped series exactly.
        self._stat_base = counter_baseline(
            self._m_steps, self._m_emitted, self._m_finished,
            self._m_ahead, self._m_surplus, self._m_shed,
            self._m_expired, self._m_timed_out,
            self._m_accepted, self._m_proposed,
            self._m_prefix_hits, self._m_prefix_tokens,
            self._m_weight_swaps,
            *([self._m_spec_rounds] if draft_config is not None
              else []))

        if draft_config is not None:
            from .models.speculative import speculative_round

            dcfg = draft_config

            # per-gamma compiled speculative rounds: gamma is baked into
            # the traced program (the draft-propose python loop), so an
            # adaptive engine holds one executable per depth it has
            # visited — bounded by [gamma_min, gamma], compiled lazily.
            # Fixed-gamma engines only ever build the ceiling's.
            def _make_spec_step(g):
                @partial(jax.jit, donate_argnums=(2, 3))
                def _spec_step(params, draft_params, cache, d_cache,
                               last, pos, key):
                    emit, a, nxt, cache, d_cache, key = (
                        speculative_round(
                            params, draft_params, cache, d_cache, last,
                            pos, g, cfg, dcfg,
                            jnp.float32(temp if temp > 0 else 1.0),
                            key, not temp > 0))
                    return emit, a, nxt, cache, d_cache, key

                return _spec_step

            self._spec_fns: Dict[int, object] = {}

            def _spec_step_for(g: int):
                fn = self._spec_fns.get(g)
                if fn is None:
                    fn = self._spec_fns[g] = _make_spec_step(g)
                return fn

            self._spec_step_for = _spec_step_for

            @jax.jit
            def _prefill_draft(draft_params, prompt):
                return prefill_cache(draft_params, prompt, dcfg, max_len)

            # _install handles any cache pytree (jit specializes per
            # structure), so the draft cache reuses it
            self._install_draft_fn = _install
            self._prefill_draft_fn = _prefill_draft
            self._extend_draft_fn = _make_extend(dcfg)
            self._extend_draft_owned_fn = _make_extend(dcfg, donate=True)
            self._fresh_draft_row_fn = _make_fresh_row(dcfg)
            if self.paged is not None:
                from .models.speculative import speculative_round_paged

                def _make_spec_step_paged(g):
                    @partial(jax.jit, donate_argnums=(2, 3))
                    def _spec_step_paged(params, draft_params, pool,
                                         d_cache, tables, last, pos,
                                         key):
                        # paged speculative round: the target verifies
                        # into the slots' own block tables (verify slack
                        # budgeted at admission — at the gamma CEILING,
                        # so every depth <= it fits); the draft cache
                        # stays contiguous
                        emit, a, nxt, pool, d_cache, key = (
                            speculative_round_paged(
                                params, draft_params, pool, tables,
                                d_cache, last, pos, g, cfg, dcfg,
                                jnp.float32(temp if temp > 0 else 1.0),
                                key, not temp > 0))
                        return emit, a, nxt, pool, d_cache, key

                    return _spec_step_paged

                self._spec_fns_paged: Dict[int, object] = {}

                def _spec_step_paged_for(g: int):
                    fn = self._spec_fns_paged.get(g)
                    if fn is None:
                        fn = self._spec_fns_paged[g] = (
                            _make_spec_step_paged(g))
                    return fn

                self._spec_step_paged_for = _spec_step_paged_for

    # ------------------------------------------------------------ warmup
    def warmup(self, prompt_lengths: Sequence[int] = ()):
        """Compile the hot programs BEFORE traffic arrives: the decode
        step (paged or contiguous) plus, for each
        length in ``prompt_lengths``, the admission prefill path exactly
        as a real admission runs it (the fresh row, chunked block shapes
        when ``prefill_chunk`` is set, whole-prompt prefill otherwise),
        the cache-install program and the first token's sampler (greedy
        or sampled, as the engine's ``temperature`` picks it). Call on an
        IDLE engine (it scribbles
        into free slots' cache rows, which the next admission
        overwrites); afterwards the first real request pays no jit
        latency for any warmed shape."""
        if (any(r is not None for r in self._rid) or self._queue
                or self._pending_prefill):
            raise RuntimeError("warmup() needs an idle engine")
        dummy = dict(last=jnp.zeros(self.max_slots, jnp.int32),
                     pos=jnp.zeros(self.max_slots, jnp.int32),
                     temps=jnp.asarray(self._temp),
                     topk=jnp.asarray(self._topk),
                     topp=jnp.asarray(self._topp),
                     seeds=jnp.asarray(self._slot_seed),
                     key=jax.random.PRNGKey(0))
        # the step fns donate the cache argument, so warming on the
        # engine's OWN cache (idle: every slot free, paged writes land
        # on scratch block 0) costs zero extra device memory — an
        # engine sized to fill the chip can still warm up
        if self.paged is not None and self.draft_config is not None:
            out = self._spec_step_paged_for(self._gamma_now)(
                self.params, self.draft_params, self.pool,
                self.draft_cache, jnp.asarray(self._tables),
                dummy["last"], dummy["pos"], dummy["key"])
            self.pool, self.draft_cache = out[3], out[4]
        elif self.paged is not None:
            # (the plain step also takes the tokens of the step before it)
            self.pool = self._step_paged_fn(
                self.params, self.pool, jnp.asarray(self._tables),
                dummy["last"], dummy["last"], dummy["pos"], dummy["temps"],
                dummy["topk"], dummy["topp"], dummy["seeds"],
                dummy["key"])[-2]
        elif self.draft_config is not None:
            out = self._spec_step_for(self._gamma_now)(
                self.params, self.draft_params, self.cache,
                self.draft_cache, dummy["last"], dummy["pos"],
                dummy["key"])
            self.cache, self.draft_cache = out[3], out[4]
        else:
            _, self.cache, _ = self._step_fn(
                self.params, self.cache, dummy["last"], dummy["last"],
                dummy["pos"], dummy["temps"], dummy["topk"], dummy["topp"],
                dummy["seeds"], dummy["key"])
        for length in sorted(set(int(n) for n in prompt_lengths)):
            if not 1 <= length < self.max_len:
                raise ValueError(f"prompt length {length} out of range")
            fake = np.zeros(length, np.int32)
            logits, row = self._prefill_with_prefixes(
                fake, self._extend_fn, self._extend_owned_fn,
                self._prefill_fn, self.params, None, 2,
                self._fresh_row_fn)
            # the first token's sampler, by the engine's own setting
            # (and the engine key left where it is). The other one
            # compiles when a request first overrides the setting: the
            # sort inside costs the TPU's compiler some twenty seconds,
            # which a greedy server's every cold start would pay
            if self.temperature > 0:
                self._first_sampled_fn(
                    logits, np.float32(self.temperature), np.int32(0),
                    np.float32(1.0), np.int32(-1), np.int32(1), self._key)
            else:
                self._first_greedy_fn(logits)
            if self.paged is not None:
                from .models.paged_decode import install_row_paged

                nprefill = -(-length // self.paged[1])
                self.pool = install_row_paged(
                    self.pool, row, self._tables[0], nprefill, slot=0)
            else:
                self.cache = self._install_fn(self.cache, row, 0)
            if self.draft_config is not None:
                _, d_row = self._prefill_with_prefixes(
                    fake, self._extend_draft_fn,
                    self._extend_draft_owned_fn, self._prefill_draft_fn,
                    self.draft_params, None, 3, self._fresh_draft_row_fn)
                self.draft_cache = self._install_draft_fn(
                    self.draft_cache, d_row, 0)

    # ---------------------------------------------------------- prefixes
    def register_prefix(self, tokens: Sequence[int]) -> None:
        """Precompute and pin the KV state of a shared prompt prefix
        (e.g. a system prompt). Any subsequent request whose prompt
        starts with these tokens skips the prefix's share of prefill:
        admission installs the cached k/v and runs one
        :func:`~elephas_tpu.models.transformer.decode_block` over just
        the suffix. Longest registered match wins. Each registration
        holds one batch-1 cache row (``num_layers × kv_heads × max_len ×
        head_dim`` k+v, per model) on device until
        :meth:`clear_prefixes`."""
        from .models.paged_decode import require_stateless_cache

        require_stateless_cache(self.config, "register_prefix")
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        if tokens.size < 1:
            raise ValueError("prefix must hold at least one token")
        if tokens.size >= self.max_len:
            raise ValueError(f"prefix ({tokens.size}) must leave room "
                             f"below max_len {self.max_len}")
        if self.prefill_chunk is not None:
            # registration rides the same bounded block shapes as
            # admission — distinct prefix lengths cost no new compiles
            logits, row = self._extend_chunked(
                self.params, self._fresh_row_fn(), tokens, 0,
                self._extend_fn, self._extend_owned_fn, owned=True)
        else:
            logits, row = self._prefill_fn(self.params,
                                           jnp.asarray(tokens[None]))
        d_row = None
        if self.draft_config is not None:
            if self.prefill_chunk is not None:
                _, d_row = self._extend_chunked(
                    self.draft_params, self._fresh_draft_row_fn(),
                    tokens, 0, self._extend_draft_fn,
                    self._extend_draft_owned_fn, owned=True)
            else:
                _, d_row = self._prefill_draft_fn(
                    self.draft_params, jnp.asarray(tokens[None]))
        self._prefixes.append((tokens, logits, row, d_row))
        self._prefixes.sort(key=lambda e: -e[0].size)
        if self._kv_cache is not None:
            self._pin_prefix_blocks(tokens, row)

    def _pin_prefix_blocks(self, tokens: np.ndarray, row) -> None:
        """The pinning layer over the automatic cache: a registered
        prefix's FULL blocks enter the block cache with a refcount
        floor of one (never parked, never evicted), so every matching
        admission hits them through the ordinary chain walk. The
        sub-block tail keeps riding the registered row. A pool too
        full to hold a pin skips it (the row still serves matches) and
        says so on the event log."""
        from .models.block_cache import chain_keys

        cache, bs = self._kv_cache, self._kv_cache_bs
        nfull = tokens.size // bs
        if nfull == 0:
            return
        keys = chain_keys(tokens[:nfull * bs], bs, self.weights_version)
        if self.paged is not None:
            from .models.paged_decode import install_row_paged

            # batch consecutive absent keys into ONE install each: a
            # per-block install would compile one (start, nblocks)
            # specialization per block — K compiles for a K-block
            # system prompt, again on every post-hot-swap re-pin
            pend_start, pend_ids = None, []

            def flush():
                if not pend_ids:
                    return
                n = pend_start + len(pend_ids)
                ids = np.zeros(n, np.int32)
                ids[pend_start:] = pend_ids
                self.pool = install_row_paged(self.pool, row, ids, n,
                                              start=pend_start)

            for i, key in enumerate(keys):
                entry = cache.get(key)
                if entry is None:
                    if (not self._free_block_ids
                            and not cache.reclaimable_count()):
                        flush()
                        emit_event("serving.prefix_pin_skipped",
                                   tokens=int(tokens.size),
                                   pinned_blocks=i)
                        return
                    bid = self._alloc_block()
                    if (pend_start is None
                            or pend_start + len(pend_ids) != i):
                        flush()
                        pend_start, pend_ids = i, []
                    pend_ids.append(bid)
                    entry = cache.insert(key, bid, (i + 1) * bs)
                cache.pin(entry)
            flush()
            return
        missing = [i for i, key in enumerate(keys)
                   if cache.get(key) is None]
        payloads = dict(zip(missing,
                            self._host_cache_payloads(row, missing)))
        for i, key in enumerate(keys):
            entry = cache.get(key)
            if entry is None:
                entry = cache.insert(key, payloads[i], (i + 1) * bs)
            cache.pin(entry)

    def clear_prefixes(self) -> None:
        """Drop every registered prefix (frees their device cache rows
        and lifts the block cache's pins — unpinned entries park on the
        LRU reclaim list and age out under pressure)."""
        self._prefixes = []
        if self._kv_cache is not None:
            self._kv_cache.unpin_all()

    def _match_prefix(self, prompt: np.ndarray):
        for entry in self._prefixes:  # longest first
            p = entry[0]
            if p.size <= prompt.size and np.array_equal(prompt[:p.size], p):
                return entry
        return None

    def _extend_chunked(self, params, row, tokens: np.ndarray, pos0: int,
                        extend_fn, extend_owned_fn, owned: bool):
        """Feed ``tokens`` (1-D) through the extend fns in
        ``prefill_chunk``-sized blocks — at most ``prefill_chunk``
        distinct block shapes ever compile, regardless of how many
        prompt lengths an online server sees. ``owned`` marks the INPUT
        row as engine-owned (donatable); blocks after the first always
        operate on engine-owned intermediates."""
        def block(cache, blk, pos, first):
            fn = extend_owned_fn if (owned or not first) else extend_fn
            return self._extend_block(fn, params, cache, blk, pos)

        return chunked_blocks(block, row, tokens[None], int(pos0),
                              self.prefill_chunk)

    def _extend_block(self, fn, params, row, blk: np.ndarray, pos: int):
        """Dispatch one admission chunk ``blk`` (1, S) at position
        ``pos`` of ``row``, and count what its attention reads: the
        host's copy of the width the program picks on the device."""
        need = pos + blk.shape[1]
        width = attend_width(self._prefill_ladder, need)
        self._m_positions_held.inc(need)
        self._m_positions_read.inc(width)
        self._m_chunks_by_width[width].inc()
        self._m_ssm_scanned.inc(blk.shape[1] * self._ssm_layers)
        # (host values: the call transfers them, no program converts)
        return fn(params, row, blk, np.int32(pos))

    def _prefill_with_prefixes(self, prompt: np.ndarray, extend_fn,
                               extend_owned_fn, prefill_fn, params, entry,
                               cache_idx: int, fresh_fn):
        """Batch-1 prefill that reuses a matched prefix entry's cache row.
        Returns (last-position logits (1, vocab), row cache)."""
        chunked = self.prefill_chunk is not None
        if entry is None:
            if chunked:
                with self._psec("elephas.loop.prefill.row_init"):
                    row = fresh_fn()
                with self._psec("elephas.loop.prefill.chunks"):
                    return self._extend_chunked(
                        params, row, prompt, 0, extend_fn,
                        extend_owned_fn, owned=True)
            with self._psec("elephas.loop.prefill.chunks"):
                return prefill_fn(params, prompt[None])
        ptoks, plogits = entry[0], entry[1]
        row = entry[cache_idx]
        if prompt.size == ptoks.size:
            return plogits, row
        with self._psec("elephas.loop.prefill.chunks"):
            if chunked:
                return self._extend_chunked(
                    params, row, prompt[ptoks.size:], int(ptoks.size),
                    extend_fn, extend_owned_fn, owned=False)
            return extend_fn(params, row, prompt[None, ptoks.size:],
                             np.int32(ptoks.size))

    # ------------------------------------------------- automatic KV cache
    def enable_prefix_cache(self, block_size: Optional[int] = None,
                            capacity: Optional[int] = None) -> None:
        """Turn on the automatic content-addressed KV block cache (see
        the module docstring) — paged engines have it on by default;
        contiguous engines (a fleet replica, a disaggregated prefill
        worker's export engine) call this to get the host-array-backed
        variant. Call BEFORE traffic: enabling is not synchronized
        against a running engine loop. No-op when already enabled."""
        if self._kv_cache is not None:
            return
        from .models.block_cache import BlockCache
        from .models.paged_decode import require_stateless_cache

        require_stateless_cache(self.config, "the prefix cache "
                                             "(enable_prefix_cache)")

        if self.paged is not None:
            if (block_size is not None
                    and int(block_size) != self.paged[1]):
                raise ValueError(
                    f"paged engines cache at the pool block size "
                    f"{self.paged[1]}, got prefix_cache_block_size="
                    f"{block_size}")
            self._kv_cache_bs = self.paged[1]
            # pooled mode: the pool IS the capacity; eviction returns
            # the entry's block to the free list (reclaim-over-shed)
            self._kv_cache = BlockCache(on_evict=self._on_cache_evict)
        else:
            self._kv_cache_bs = int(block_size or 64)
            if not 1 <= self._kv_cache_bs < self.max_len:
                raise ValueError(
                    f"prefix_cache_block_size {self._kv_cache_bs} out "
                    f"of range [1, max_len={self.max_len})")
            self._kv_cache = BlockCache(
                capacity=1024 if capacity is None else int(capacity),
                on_evict=self._on_cache_evict)
        self._chain_memo = None   # (rid, version, walk_keys, ins_keys)
        reg = self.registry
        self._m_kv_hits = reg.counter(
            "serving_kv_cache_hits_total",
            "admissions/exports that reused >= 1 cached KV block"
            ).labels()
        self._m_kv_misses = reg.counter(
            "serving_kv_cache_misses_total",
            "admissions/exports with >= 1 full block and zero cache "
            "reuse").labels()
        self._m_kv_evictions = reg.counter(
            "serving_kv_cache_evictions_total",
            "cold cached blocks reclaimed under pool/capacity pressure"
            ).labels()
        import weakref

        ref = weakref.ref(self)
        reg.gauge("serving_kv_cache_blocks",
                  "KV blocks currently held by the prefix cache"
                  ).set_function(
            lambda: float(len(e._kv_cache))
            if (e := ref()) is not None and e._kv_cache is not None
            else 0.0)
        self._reclaimable_gauge()

    def _reclaimable_gauge(self) -> None:
        import weakref

        ref = weakref.ref(self)
        self.registry.gauge(
            "serving_kv_cache_reclaimable_blocks",
            "cached blocks on the LRU free list (zero-ref, unpinned — "
            "reclaimable by admission pressure)").set_function(
            lambda: float(e._kv_cache.reclaimable_count())
            if (e := ref()) is not None and e._kv_cache is not None
            else 0.0)

    # ------------------------------------------------- tiered KV spill
    def enable_kv_spill(self, spill=None, *,
                        host_capacity_blocks: Optional[int] = 4096,
                        storage_url: Optional[str] = None,
                        storage_compress: str = "q8",
                        storage_capacity_blocks: Optional[int] = None,
                        lossy_promote: bool = False):
        """Turn on the tiered KV spill plane (:mod:`~elephas_tpu.
        kvtier`): block-cache evictions DEMOTE to host RAM (and
        optionally to ``storage_url``'s object store, Q8-compressed)
        instead of discarding, and admission chain walks fall through
        device → host → storage, promoting spilled blocks back with
        one host→device copy each. Implies the prefix cache. Call
        BEFORE traffic, like :meth:`enable_prefix_cache`.

        ``lossy_promote`` opts in to promoting Q8 (storage-tier)
        blocks: the dequantized KV serves the admitting request —
        saving its re-prefill at a bounded-error cost — but the slot
        is tainted so nothing computed over it ever registers, parks,
        or persists under chain keys (lossy-parity rule; default off
        keeps outputs bit-identical to spill-off). Returns the
        :class:`~elephas_tpu.kvtier.TieredSpill` (pass ``spill`` to
        share one across engines)."""
        if self._kv_spill is not None:
            return self._kv_spill
        from .models.paged_decode import (require_per_head_cache,
                                          require_stateless_cache)

        require_per_head_cache(self.config, "the KV spill tier (kvtier)")
        require_stateless_cache(self.config, "the KV spill tier "
                                             "(enable_kv_spill)")
        if self._kv_cache is None:
            self.enable_prefix_cache()
        from .kvtier import TieredSpill

        if spill is None:
            spill = TieredSpill(
                host_capacity_blocks=host_capacity_blocks,
                storage_url=storage_url,
                storage_compress=storage_compress,
                storage_capacity_blocks=storage_capacity_blocks)
        self._kv_spill = spill
        self._lossy_promote = bool(lossy_promote)
        self._ensure_spill_metrics()
        spill.bind_metrics(self._m_spill_demote, self._m_spill_bytes)
        return spill

    def enable_session_store(self, store=None, *,
                             url: Optional[str] = None,
                             compress: str = "none",
                             capacity_blocks: Optional[int] = 16384):
        """Turn on resumable cross-request sessions (:mod:`~elephas_tpu.
        kvtier`): a request submitted with ``session=<id>`` persists
        its final sequence's full KV blocks here at retirement, keyed
        by content-addressed chain + ``weights_version``, and a later
        request for the same conversation admits as a chain hit — on
        ANY engine sharing the backend (pass one
        :class:`~elephas_tpu.kvtier.SessionStore` instance to several
        engines, or point them at one ``url``). Persistence needs a
        paged engine (blocks are exported straight off the pool);
        lookup/promotion works on any engine with the prefix cache.
        Hot-swap invalidation is free by construction — post-swap
        chains hash differently. Implies the prefix cache."""
        if self._session_store is not None:
            return self._session_store
        from .models.paged_decode import require_stateless_cache

        require_stateless_cache(self.config, "the session store "
                                             "(enable_session_store)")
        if self._kv_cache is None:
            self.enable_prefix_cache()
        from .kvtier import SessionStore

        if store is None:
            store = SessionStore(url=url, compress=compress,
                                 capacity_blocks=capacity_blocks)
        self._session_store = store
        self._ensure_spill_metrics()
        return store

    def _ensure_spill_metrics(self) -> None:
        """The spill/session metric families, shared by both enable
        paths (promotions may source from either plane). Baselined
        like every engine counter so stats stays per-engine on an
        injected shared registry."""
        if self._m_spill_promote is not None:
            return
        reg = self.registry
        self._m_spill_demote = reg.counter(
            "serving_kv_spill_demotions_total",
            "KV blocks demoted into a spill tier, by destination tier",
            labels=("tier",))
        self._m_spill_promote = reg.counter(
            "serving_kv_spill_promotions_total",
            "spilled KV blocks promoted back to device, by source "
            "tier ('session' = the session store)", labels=("tier",))
        self._m_spill_bytes = reg.counter(
            "serving_kv_spill_bytes_total",
            "payload bytes written into a spill tier, by tier",
            labels=("tier",))
        self._m_session_hits = reg.counter(
            "serving_kv_session_hits_total",
            "session-tagged admissions that reused >= 1 chain block "
            "(device, spill, or session tier)").labels()
        self._m_session_misses = reg.counter(
            "serving_kv_session_misses_total",
            "session-tagged admissions with a walkable chain and "
            "zero reuse (cold resume: full re-prefill)").labels()
        self._spill_stat_base = counter_baseline(
            self._m_session_hits, self._m_session_misses)
        import weakref

        ref = weakref.ref(self)
        g_blocks = reg.gauge(
            "serving_kv_tier_blocks",
            "KV blocks resident per spill/session tier",
            labels=("tier",))
        g_bytes = reg.gauge(
            "serving_kv_tier_bytes",
            "payload bytes resident per spill/session tier",
            labels=("tier",))

        def _tier_stat(tier, field):
            e = ref()
            if e is None:
                return 0.0
            if tier == "session":
                return (float(e._session_store.stats()[field])
                        if e._session_store is not None else 0.0)
            spill = e._kv_spill
            if spill is None:
                return 0.0
            if tier == "storage" and spill.storage is None:
                return 0.0
            src = spill.host if tier == "host" else spill.storage
            return float(len(src) if field == "blocks" else src.nbytes)

        for tier in ("host", "storage", "session"):
            g_blocks.labels(tier=tier).set_function(
                partial(_tier_stat, tier, "blocks"))
            g_bytes.labels(tier=tier).set_function(
                partial(_tier_stat, tier, "bytes"))

    def _pool_block_payload(self, bid: int) -> Dict:
        """One pool block as a host payload dict — the demotion read.
        Must run BEFORE the block id is reused (i.e. inside the
        eviction callback, before the free list hands it out)."""
        return {name: tuple(np.asarray(lc[leaf][bid])
                            for leaf in sorted(lc))
                for name, lc in self.pool.items()}

    def _on_cache_evict(self, entry) -> None:
        spill = self._kv_spill
        if spill is not None and int(getattr(entry, "tokens", 0)) > 0:
            # demote instead of discard. Inside an allocation loop
            # (_demote_accum set) paged payloads are STAGED and read
            # out in one batched per-layer gather at the flush — a
            # per-eviction device read syncs the stream once per block
            # and dominates warm-TTFT otherwise. The staged block id
            # may rejoin the free list and even be re-allocated to the
            # admitting request, but its pool contents are untouched
            # until that request installs — which happens strictly
            # after the flush.
            if self.paged is not None:
                if self._demote_accum is not None:
                    self._demote_accum.setdefault("staged", []).append(
                        (entry.key, int(entry.payload),
                         int(entry.tokens)))
                else:
                    # eviction outside an admission (register_prefix
                    # pressure): read out NOW, before the id rejoins
                    # the free list. Sources are always EXACT — lossy
                    # blocks never become cache entries.
                    spill.demote(
                        entry.key,
                        self._pool_block_payload(int(entry.payload)),
                        entry.tokens)
            else:
                spill.demote(entry.key, entry.payload, entry.tokens)
                if self._demote_accum is not None:
                    self._demote_accum["blocks"] = (
                        self._demote_accum.get("blocks", 0) + 1)
        if self.paged is not None:
            self._free_block_ids.append(entry.payload)
        self._m_kv_evictions.inc()

    def _flush_demotions(self, accum) -> int:
        """Batch-demote the evictions an allocation loop staged: ONE
        device->host gather per layer for every staged block (the
        export_pool_blocks path), then the per-key spill puts. Returns
        the number of blocks demoted (staged + contiguous-mode
        immediates)."""
        staged = accum.get("staged", ())
        if staged:
            from .models.paged_decode import export_pool_blocks

            payloads = export_pool_blocks(
                self.pool, [bid for _, bid, _ in staged])
            for (key, _, tokens), payload in zip(staged, payloads):
                self._kv_spill.demote(key, payload, tokens)
        return accum.get("blocks", 0) + len(staged)

    def _tier_lookup(self, key: bytes):
        """One chain key's spill/session resolution: ``(block,
        source_tier)`` or ``None`` — spill tiers first (host RAM beats
        a storage read), then the session store."""
        if self._kv_spill is not None:
            found = self._kv_spill.lookup(key)
            if found is not None:
                return found
        if self._session_store is not None:
            block = self._session_store.get_block(key)
            if block is not None:
                return block, "session"
        return None

    def _tier_walk(self, rid: Optional[int], keys, start: int,
                   allow_lossy: bool = False) -> List:
        """Continue an admission's chain walk past the device cache:
        the longest run of consecutive ``keys`` resolvable in the
        spill tiers / session store, as ``[(SpilledBlock, tier)]``.
        Memoized per (rid, version, start): a queue head waiting for
        capacity re-walks every step, and the tier reads (a storage
        GET per key) are the expensive half. ``start`` — the device
        hit count — keys the memo because another admission may
        register more of the chain while this candidate waits; promos
        computed at the old offset would then overlap the new hits."""
        if self._kv_spill is None and self._session_store is None:
            return []
        memo = self._promo_memo
        if (rid is not None and memo is not None and memo[0] == rid
                and memo[1] == self.weights_version
                and memo[2] == start):
            return memo[3]
        promos: List = []
        for key in keys:
            found = self._tier_lookup(key)
            if found is None:
                break
            block, src = found
            if block.lossy:
                if allow_lossy:
                    # a lossy block still ends the walk: everything
                    # after it is served freshly anyway once the slot
                    # is tainted, and stopping bounds the blast radius
                    promos.append((block, src))
                break
            promos.append((block, src))
        if rid is not None:
            self._promo_memo = (rid, self.weights_version, start,
                                promos)
        return promos

    def _cache_chain_keys(self, prompt: np.ndarray):
        """(walk_keys, insert_keys) for ``prompt``: insert keys cover
        every full block (``size // bs``); the WALK is capped one block
        earlier when the prompt is block-aligned (``(size-1) // bs``)
        so the remainder prefill is never empty — it is what produces
        the final-position logits the first token samples from."""
        from .models.block_cache import chain_keys

        bs = self._kv_cache_bs
        nfull = prompt.size // bs
        ins_keys = chain_keys(prompt[:nfull * bs], bs,
                              self.weights_version)
        return ins_keys[:(prompt.size - 1) // bs], ins_keys

    def _chain_keys_for(self, rid: Optional[int], prompt: np.ndarray):
        """Memoized :meth:`_cache_chain_keys` keyed on (rid, version):
        one admission consults the chain up to three times (the
        availability walk, the prefill walk, the insert), and a queue
        head waiting for capacity re-walks EVERY step — the prompt and
        version are unchanged throughout, so hash once. ``rid=None``
        (exports) skips the memo."""
        if rid is None:
            return self._cache_chain_keys(prompt)
        memo = self._chain_memo
        if (memo is not None and memo[0] == rid
                and memo[1] == self.weights_version):
            return memo[2], memo[3]
        walk, ins = self._cache_chain_keys(prompt)
        self._chain_memo = (rid, self.weights_version, walk, ins)
        return walk, ins

    def _alloc_block(self) -> int:
        """One free block id — reclaiming the coldest parked cache
        entry when the free list is dry (callers checked availability
        = free + reclaimable inside the admission math)."""
        if not self._free_block_ids:
            self._kv_cache.evict_lru()     # on_evict refills free list
        return self._free_block_ids.popleft()

    def _insert_full_blocks(self, slot: int, prompt: np.ndarray,
                            skip: int = 0,
                            rid: Optional[int] = None) -> None:
        """Register the slot's freshly prefilled full blocks
        (``skip..nfull``) in the pooled cache: each absent chain key's
        block moves from the slot's PRIVATE list to its SHARED list,
        refcounted by this slot from birth — a same-prefix request
        admitted one step later already hits."""
        if self._slot_lossy[slot]:
            # the slot admitted over a lossy promoted block: its fresh
            # blocks were computed attending to dequantized KV and must
            # never register as the exact content their tokens address
            return
        cache, bs = self._kv_cache, self._kv_cache_bs
        nfull = prompt.size // bs
        if nfull <= skip:
            return
        _, ins_keys = self._chain_keys_for(rid, prompt)
        for i in range(skip, nfull):
            key = ins_keys[i]
            if cache.get(key) is not None:
                # an equal-content entry exists elsewhere (another
                # slot inserted it first, or an orphaned chain tail
                # survived an eviction): keep ours private
                continue
            bid = int(self._tables[slot, i])
            entry = cache.insert(key, bid, (i + 1) * bs, acquire=True)
            self._slot_blocks[slot].remove(bid)
            self._slot_cached[slot].append(entry)

    def _host_cache_payloads(self, row, indices):
        """Host payloads for blocks ``indices`` of a device row — ONE
        device-to-host transfer per layer k/v (not per block: a long
        prompt's miss would otherwise issue 2·layers·blocks small
        blocking transfers on the prefill hot path), sliced and copied
        host-side so a payload never pins the whole row."""
        if not indices:
            return []
        bs = self._kv_cache_bs
        host = {name: tuple(np.asarray(lc[leaf][0]) for leaf in sorted(lc))
                for name, lc in row.items()}
        return [{name: tuple(a[:, i * bs:(i + 1) * bs].copy()
                             for a in parts)
                 for name, parts in host.items()}
                for i in indices]

    def _host_cache_row(self, hits):
        """Device row whose head positions ``[0, len(hits)*bs)`` are the
        cached host blocks — the host-mode hit's one copy (vs the
        prefix's prefill FLOPs)."""
        from .models.paged_decode import import_kv_blocks

        flat = []
        names = sorted(hits[0].payload,
                       key=lambda n: int(n.split("_", 1)[1]))
        leaves = sorted(self.config.cache_leaves())
        for name in names:
            for j in range(len(leaves)):
                flat.append(np.stack([e.payload[name][j] for e in hits]))
        row_np = import_kv_blocks(flat, len(hits) * self._kv_cache_bs,
                                  self.max_len, leaves=leaves)
        return jax.tree_util.tree_map(
            lambda a: jnp.asarray(a, self.config.dtype), row_np)

    def _extend_remainder(self, row, prompt: np.ndarray, pos0: int):
        """Prefill ``prompt[pos0:]`` on top of a row holding
        ``[0, pos0)`` — the remainder half of every cache hit. ``row``
        is always engine-owned here (a fresh gather/import), so the
        donating extend variants apply. Returns (last-position logits
        ``(1, vocab)``, full row)."""
        suffix = prompt[pos0:]
        with self._psec("elephas.loop.prefill.chunks"):
            if self.prefill_chunk is not None:
                return self._extend_chunked(
                    self.params, row, suffix, pos0, self._extend_fn,
                    self._extend_owned_fn, owned=True)
            return self._extend_owned_fn(self.params, row, suffix[None],
                                         np.int32(pos0))

    def _host_cache_prefill(self, rid: Optional[int],
                            prompt: np.ndarray):
        """The host-mode cached prefill shared by contiguous admission
        and :meth:`export_prefill`: longest cached chain (or the longer
        registered row) supplies the prompt head, the remainder
        prefills, and the freshly computed full blocks insert. Returns
        (last-position logits ``(1, vocab)``, row, cache_tokens_reused,
        registered_tokens_reused) — at most one of the two reuse counts
        is nonzero (whichever layer covered more served)."""
        cache, bs = self._kv_cache, self._kv_cache_bs
        walk_keys, ins_keys = self._chain_keys_for(rid, prompt)
        hits = cache.match_chain(walk_keys)
        # host-mode tier fall-through: LOSSLESS spilled blocks only
        # (the payload joins the row head exactly like a cache hit, and
        # re-registers below — a lossy payload could do neither without
        # slot-taint machinery the contiguous engine doesn't carry)
        promos = self._tier_walk(rid, walk_keys[len(hits):], len(hits))
        j = len(hits) + len(promos)
        entry = self._match_prefix(prompt)
        reg_len = 0 if entry is None else int(entry[0].size)
        reg_used = 0
        if reg_len > j * bs:
            # the pinned row covers more (a sub-block registered head,
            # or a cold cache): classic registered-prefix path — the
            # computed row still warms the cache below
            if entry is not None:
                self._m_prefix_hits.inc()
                self._m_prefix_tokens.inc(reg_len)
                reg_used = reg_len
            logits, row = self._prefill_with_prefixes(
                prompt, self._extend_fn, self._extend_owned_fn,
                self._prefill_fn, self.params, entry, 2,
                self._fresh_row_fn)
            j, reused, promos = 0, 0, []
        elif j > 0:
            for e in hits:
                cache.touch(e)
            reused = j * bs
            self._m_kv_hits.inc()
            self._m_prefix_tokens.inc(reused)
            cache.record_walk(j, True)
            if rid is not None:
                self.recorder.record(rid, "kv_cache_hit", blocks=j,
                                     tokens_reused=reused,
                                     promoted=len(promos))
            row = self._host_cache_row(
                hits + [blk for blk, _ in promos])
            logits, row = self._extend_remainder(row, prompt, reused)
            for blk, src in promos:
                if self._m_spill_promote is not None:
                    self._m_spill_promote.labels(tier=src).inc()
                if cache.get(blk.key) is None:
                    # exact payload: re-register under the chain key
                    # so the next same-chain admission device-hits
                    cache.insert(blk.key, blk.payload, blk.tokens)
                if self._kv_spill is not None:
                    self._kv_spill.consumed(blk.key)
            if promos:
                self._promo_memo = None
                if rid is not None:
                    self.recorder.record(rid, "kv_promote",
                                         blocks=len(promos))
                emit_event("serving.kv_promote", rid=rid,
                           blocks=len(promos))
        else:
            if walk_keys:
                self._m_kv_misses.inc()
            cache.record_walk(0, bool(walk_keys))
            logits, row = self._prefill_with_prefixes(
                prompt, self._extend_fn, self._extend_owned_fn,
                self._prefill_fn, self.params, None, 2,
                self._fresh_row_fn)
            reused = 0
        missing = [i for i in range(j, len(ins_keys))
                   if cache.get(ins_keys[i]) is None]
        for i, payload in zip(missing, self._host_cache_payloads(row,
                                                                 missing)):
            cache.insert(ins_keys[i], payload, (i + 1) * bs)
        return logits, row, reused, reg_used

    # ------------------------------------------------------- live weights
    def stage_params(self, params: Dict, version: int,
                     trace_id: Optional[str] = None) -> None:
        """Stage a new parameter pytree for an atomic hot-swap. Safe
        from ANY thread (a :class:`~elephas_tpu.weightsync.
        WeightSubscriber`'s background puller): the engine applies it
        between decode steps — the same atomic point KV installs use —
        on its next :meth:`step` (or via an explicit
        :meth:`apply_staged_params` on engines that never step, e.g. a
        prefill worker's). Latest staging wins; in-flight requests
        finish on whichever version they step under. ``params`` should
        already be device arrays in the engine's tree structure — the
        conversion belongs OFF the engine loop, which is why staging
        and applying are split. ``trace_id`` (the stager's active
        trace) rides to the ``weights.swapped`` event so a canary
        rollout's whole story joins on one id.

        Speculative mode swaps only the TARGET params: speculative
        sampling is exact with respect to the target model, so a stale
        draft costs acceptance rate, never correctness."""
        with self._staged_lock:
            self._staged_params = (params, int(version), trace_id,
                                   time.monotonic())

    def stage_draft_params(self, draft_params: Dict, version: int,
                           trace_id: Optional[str] = None) -> None:
        """Stage new DRAFT-model params for the same atomic
        between-decode-steps swap as :meth:`stage_params` — the second
        :class:`~elephas_tpu.weightsync.WeightSubscriber` channel that
        keeps a continuously re-distilled draft
        (:mod:`~elephas_tpu.models.distill`) fresh alongside the
        target. Versioned independently (``draft_weights_version``);
        safe from any thread, latest staging wins. A draft swap can
        never change output: speculative sampling is exact with respect
        to the TARGET model, so draft freshness buys acceptance rate
        (tokens per round) and nothing else — which is also why draft
        KV is never cached and no chain key ever hashes the draft
        version."""
        if self.draft_config is None:
            raise ValueError("stage_draft_params needs a speculative "
                             "engine (draft_params/draft_config)")
        with self._staged_lock:
            self._staged_draft = (draft_params, int(version), trace_id,
                                  time.monotonic())

    def apply_staged_params(self) -> Optional[int]:
        """Apply a staged swap NOW, if any; returns the new version (or
        None). Must be called from whatever context owns the engine's
        step/prefill serialization — ``step()`` calls it between decode
        steps, and a :class:`~elephas_tpu.disagg.PrefillWorker` calls
        it between jobs. Registered prefixes are recomputed under the
        new params before the swap returns (their cached KV was
        computed under the old weights — serving it after the swap
        would hand out stale state the same way an unstamped shipped-KV
        frame would), so the swap pause scales with the number of
        pinned prefixes; the ``serving_weight_swap_seconds`` histogram
        measures exactly this blockage."""
        with self._staged_lock:
            staged, self._staged_params = self._staged_params, None
            staged_draft, self._staged_draft = self._staged_draft, None
        if staged_draft is not None:
            self._apply_staged_draft(staged_draft)
        if staged is None:
            return None
        params, version, trace_id, staged_t = staged
        t0 = time.monotonic()
        self.params = params
        self.weights_version = int(version)
        if self._kv_cache is not None:
            # version-keyed invalidation by construction: post-swap
            # chains hash under the NEW version, so every old entry
            # simply stops matching — no flush pause. Lifting old pins
            # here lets old-version pinned blocks park and age out of
            # the LRU (the recompute below re-pins under the new
            # version); an in-use old block stays referenced until its
            # request retires, then parks, never to be served again.
            self._kv_cache.unpin_all()
        if self._kv_spill is not None:
            # spilled blocks share the construction: old-version chains
            # can never match again, so the host tier's RAM comes back
            # NOW rather than at LRU age-out (storage entries are
            # equally unreachable and age out under write-capacity LRU)
            self._kv_spill.clear_host()
        self._promo_memo = None
        if self._prefixes:
            # re-pin every registered prefix under the new weights;
            # register_prefix re-sorts, so matching behavior is
            # unchanged
            tokens = [entry[0] for entry in self._prefixes]
            self._prefixes = []
            for toks in tokens:
                self.register_prefix(toks)
        pause = time.monotonic() - t0
        self._m_weight_swaps.inc()
        self._m_swap_pause.observe(pause)
        emit_event("weights.swapped", trace_id=trace_id,
                   version=int(version), tier=self.tier,
                   prefixes_recomputed=len(self._prefixes),
                   staged_for_s=round(t0 - staged_t, 6),
                   pause_s=round(pause, 6))
        return int(version)

    def _apply_staged_draft(self, staged: Tuple) -> None:
        """Swap the draft params in (between decode steps — the caller
        is :meth:`apply_staged_params`). In-flight requests keep their
        draft KV computed under the OLD draft: mixed draft state skews
        what the draft proposes, which only moves the acceptance rate —
        the target's verify pass makes output exact regardless, so
        unlike a target swap nothing needs recomputing for correctness.
        Registered prefixes' draft rows ARE refreshed (one batch-1
        draft prefill per pin) so steady-state acceptance doesn't decay
        for pinned heads."""
        draft_params, version, trace_id, staged_t = staged
        t0 = time.monotonic()
        self.draft_params = draft_params
        self.draft_weights_version = int(version)
        # a fresh draft resets the adaptive-gamma controller to the
        # ceiling: the EWMA's memory of the STALE draft's acceptance
        # would otherwise hold the depth down for dozens of rounds
        # after the cause is gone
        self._gamma_now = self.gamma
        self._accept_ewma = None
        self._rounds_since_adjust = 0
        if self._prefixes:
            fresh = []
            for entry in self._prefixes:
                toks = entry[0]
                if self.prefill_chunk is not None:
                    _, d_row = self._extend_chunked(
                        self.draft_params, self._fresh_draft_row_fn(),
                        toks, 0, self._extend_draft_fn,
                        self._extend_draft_owned_fn, owned=True)
                else:
                    _, d_row = self._prefill_draft_fn(
                        self.draft_params, jnp.asarray(toks[None]))
                fresh.append((entry[0], entry[1], entry[2], d_row))
            self._prefixes = fresh
        emit_event("weights.draft_swapped", trace_id=trace_id,
                   version=int(version), tier=self.tier,
                   prefixes_recomputed=len(self._prefixes),
                   staged_for_s=round(t0 - staged_t, 6),
                   pause_s=round(time.monotonic() - t0, 6))

    # ------------------------------------------------------------ queue
    def check_admissible(self, prompt_size: int,
                         max_new_tokens: int,
                         prompt: Optional[np.ndarray] = None,
                         tenant: Optional[str] = None) -> None:
        """Raise ``ValueError`` when a request is PERMANENTLY
        inadmissible on this engine — it exceeds ``max_len`` (plus the
        speculative verify slack), could never fit the paged block
        pool, or its prompt alone exceeds ``max_queued_tokens``. A
        retryable :class:`QueueFullError` (429 + backoff) for these
        would have well-behaved clients retrying forever. THE shared
        validator: the engine's own submit paths and the disaggregated
        front end (:class:`~elephas_tpu.disagg.DisaggEngine`) both call
        it, so an inadmissible request always 400s at submit instead of
        failing at KV-install time inside an engine loop."""
        # speculative rounds write verify blocks up to gamma positions
        # past the last emitted token
        slack = self._slack
        if prompt_size + max_new_tokens + slack > self.max_len:
            raise ValueError(
                f"prompt ({prompt_size}) + max_new_tokens "
                f"({max_new_tokens})"
                + (f" + gamma ({slack})" if slack else "")
                + f" exceeds max_len {self.max_len}")
        if self.paged is not None:
            # the same slack bounds the paged budget: verify writes land
            # up to gamma positions past the budgeted output, so the
            # slot's table must own those blocks too
            needed = -(-(prompt_size + max_new_tokens + slack)
                       // self.paged[1])
            allocatable = self.paged[0] - 1     # block 0 never allocates
            if self._kv_cache is not None:
                # PINNED registered-prefix blocks are never reclaimable
                # (the refcount floor), so they permanently shrink what
                # a request can allocate — EXCEPT the leading pinned
                # blocks the prompt itself would reuse, which need no
                # allocation (its table points at them). Unpinned cache
                # entries don't count: admission pressure reclaims them.
                pinned = self._kv_cache.pinned_count()
                if pinned and prompt is not None:
                    walk_keys, _ = self._cache_chain_keys(
                        np.asarray(prompt, np.int32).reshape(-1))
                    # only the LEADING RUN of pinned entries is a
                    # permanent guarantee — a transient entry between
                    # pinned ones may be evicted, breaking the walk
                    for e in self._kv_cache.match_chain(walk_keys):
                        if not e.pinned:
                            break
                        needed -= 1
                allocatable -= pinned
            if needed > allocatable:
                raise ValueError(
                    f"request needs {needed} blocks but the pool only "
                    f"has {allocatable} allocatable — it could "
                    "never be admitted")
        if (self.max_queued_tokens is not None
                and prompt_size > self.max_queued_tokens):
            raise ValueError(
                f"prompt of {prompt_size} tokens exceeds "
                f"max_queued_tokens={self.max_queued_tokens} — it could "
                "never be admitted")
        if self.qos is not None and tenant is not None:
            # per-tenant quota, permanent half: a prompt LARGER than
            # its tenant's token quota can never be queued — that is a
            # 400 at submit, not a retryable 429 (the transient half
            # lives in check_tenant_admissible)
            _, token_quota = self.qos.quota(tenant)
            if token_quota is not None and prompt_size > token_quota:
                raise ValueError(
                    f"prompt of {prompt_size} tokens exceeds tenant "
                    f"{tenant!r}'s max_queued_tokens quota "
                    f"{token_quota} — it could never be admitted")

    def submit(self, prompt: Sequence[int], max_new_tokens: int,
               temperature: Optional[float] = None,
               top_k: Optional[int] = None,
               top_p: Optional[float] = None,
               admit: bool = True,
               deadline_ms: Optional[float] = None,
               tenant: Optional[str] = None,
               priority=None,
               seed: Optional[int] = None,
               resume_from: int = 0,
               session: Optional[str] = None) -> int:
        """Queue a request; returns its id. Admission happens lazily on
        the next :meth:`step` (or immediately if a slot is free).
        ``temperature``/``top_k``/``top_p`` override the engine defaults
        for THIS request (plain stepping only — speculative mode samples
        every slot at the engine temperature, since the accept/resample
        rule is compiled for one setting). ``admit=False`` skips the
        immediate admission attempt entirely, deferring it — and any
        prefill jit compile a new prompt length triggers — to the next
        :meth:`step`; callers that serialize engine access behind a lock
        (the HTTP server) use this so submitting never holds that lock
        across a multi-second compile.

        ``deadline_ms`` bounds the request's TOTAL time in the engine:
        if it is still queued when the deadline passes it is shed before
        prefill (``result_info`` reports ``expired``); if the deadline
        passes mid-decode the slot is freed and the tokens emitted so
        far become the final output (``timeout``). Raises
        :class:`QueueFullError` when ``max_queue``/``max_queued_tokens``
        is configured and the backlog is at capacity — overload answers
        immediately instead of queueing unboundedly.

        ``tenant`` names who this request belongs to (``"default"``
        when omitted) and ``priority`` overrides the tenant's class
        (a :data:`~elephas_tpu.serving_qos.PRIORITY_CLASSES` name or
        int) — with a ``qos`` policy configured these drive weighted
        fair queueing, per-tenant quotas (a breach sheds with the
        quota-aware 429), and priority preemption; without one they
        are attribution only.

        ``seed`` pins THIS request's sampling RNG: each sampled token's
        key derives purely from ``(seed, absolute position)``, so the
        same seeded request replays the same output on any engine —
        and a request resumed elsewhere (``resume_from``) continues
        sampling exactly the sequence the original would have emitted.
        Plain stepping only (speculative mode shares one engine key).
        Greedy requests ignore it.

        ``resume_from=N`` declares the LAST ``N`` tokens of ``prompt``
        to be output this request already emitted elsewhere (a killed
        replica's journaled stream, a checkpointed session): admission
        prefills the full sequence as a forced prefix — often a
        prefix-cache chain hit — and the request's output starts with
        those ``N`` tokens followed by ``max_new_tokens`` freshly
        decoded ones, exactly as the uninterrupted request would have
        continued (token-identical under greedy decoding).

        ``session`` names a resumable conversation (needs
        :meth:`enable_session_store`): at retirement the request's
        final sequence's full KV blocks persist, content-addressed by
        chain + ``weights_version``, and the conversation's NEXT
        request — whose prompt starts with this one's prompt +
        completion — admits as a chain hit on any engine sharing the
        store, paying a short remainder prefill instead of the whole
        history's."""
        return self._submit_impl(prompt, max_new_tokens, temperature,
                                 top_k, top_p, admit, deadline_ms, None,
                                 tenant, priority, seed=seed,
                                 resume_from=resume_from,
                                 session=session)

    def submit_prefilled(self, prompt: Sequence[int],
                         max_new_tokens: int, kv_blocks, first_token: int,
                         temperature: Optional[float] = None,
                         top_k: Optional[int] = None,
                         top_p: Optional[float] = None,
                         admit: bool = True,
                         deadline_ms: Optional[float] = None,
                         weights_version: Optional[int] = None,
                         tenant: Optional[str] = None,
                         priority=None,
                         submitted_at: Optional[float] = None,
                         seed: Optional[int] = None,
                         resume_from: int = 0) -> int:
        """Queue a request whose prefill ALREADY HAPPENED off-engine —
        the decode half of disaggregated serving. ``kv_blocks`` is the
        prompt's KV state in wire-block form
        (:func:`~elephas_tpu.models.paged_decode.export_kv_blocks`, as
        produced by :meth:`export_prefill` on a prefill worker) and
        ``first_token`` the token its final-position logits emitted.
        Admission installs the shipped blocks into the slot's cache row
        (or paged blocks) between decode steps — the same atomic point
        where ordinary admissions install their own prefill — so the
        request's queue wait is pure decode-stage backlog. Everything
        else (admission bounds, deadlines, sampling overrides for the
        DECODE steps, cancel, results) behaves exactly like
        :meth:`submit`. On a SPECULATIVE engine the shipped blocks are
        the TARGET model's KV (the prefill tier runs target-only);
        admission prefills the draft locally before the first round —
        draft KV never crosses the wire.

        ``weights_version`` stamps which LIVE weight version the KV was
        computed under: admission re-checks it against the engine's
        current version at the moment of install — the caller's own
        gate (the disaggregated front end's) necessarily runs earlier,
        and a hot-swap staged in between would otherwise decode this
        request's whole output over mismatched state. A stale stamp
        falls back to a LOCAL prefill of the prompt (correct output,
        one admission's worth of extra compute on this engine) rather
        than failing the request; ``None`` skips the check.

        ``submitted_at`` is the FRONT END's ``time.monotonic()`` stamp
        of the original client submit: when given, this engine's
        ``serving_ttft_seconds`` measures first-token latency from
        THAT moment — so the prefill tier's queue wait, compute, and
        KV ship time land inside TTFT, where the user experienced
        them — while queue-wait/request-latency series keep measuring
        this engine's own decode stage (the disaggregation headline
        those series exist to isolate)."""
        # shape/coverage validation happens HERE, at submit: a malformed
        # KV payload failing at admission time would raise inside the
        # server's engine loop and read as engine death (500s for
        # everyone) instead of one bad request's 400
        from .models.paged_decode import (require_per_head_cache,
                                          require_stateless_cache)

        require_per_head_cache(self.config, "submit_prefilled (the "
                                            "disaggregated wire)")
        require_stateless_cache(self.config, "submit_prefilled (the "
                                             "disaggregated wire)")
        prompt_size = int(np.asarray(prompt).size)
        if isinstance(kv_blocks, dict):
            # prebuilt batch-1 row cache (``import_kv_blocks`` output):
            # a receiver thread can do the block reassembly OFF the
            # engine loop and hand the row in directly — admission then
            # only pays the device install
            blocks = kv_blocks
            if len(blocks) != self.config.num_layers:
                raise ValueError(
                    f"prebuilt KV row must hold {self.config.num_layers}"
                    f" layers, got {len(blocks)}")
            for name, lc in blocks.items():
                for part in ("k", "v"):
                    arr = lc[part]
                    if arr.ndim != 4 or arr.shape[2] < prompt_size:
                        raise ValueError(
                            f"prebuilt KV row {name}/{part} must be "
                            f"(1, heads, >= {prompt_size}, head_dim), "
                            f"got shape {tuple(arr.shape)}")
        else:
            blocks = [np.asarray(b) for b in kv_blocks]
            expected = 2 * self.config.num_layers
            if len(blocks) != expected:
                raise ValueError(f"expected {expected} KV block tensors "
                                 f"(k, v per layer), got {len(blocks)}")
            for b in blocks:
                if b.ndim != 4:
                    raise ValueError(
                        "KV block tensors must be (nblocks, heads, "
                        f"block_size, head_dim), got shape "
                        f"{tuple(b.shape)}")
                if b.shape[0] * b.shape[2] < prompt_size:
                    raise ValueError(
                        f"{b.shape[0]} blocks of {b.shape[2]} positions"
                        f" cannot cover the {prompt_size}-token prompt")
        return self._submit_impl(
            prompt, max_new_tokens, temperature, top_k, top_p, admit,
            deadline_ms,
            (blocks, int(first_token),
             None if weights_version is None else int(weights_version)),
            tenant, priority, submitted_at=submitted_at, seed=seed,
            resume_from=resume_from)

    def _submit_impl(self, prompt, max_new_tokens, temperature, top_k,
                     top_p, admit, deadline_ms, prefilled,
                     tenant=None, priority=None,
                     submitted_at=None, seed=None,
                     resume_from=0, session=None) -> int:
        if (temperature is not None or top_k is not None
                or top_p is not None):
            if self.draft_config is not None:
                raise ValueError("per-request sampling settings are not "
                                 "supported in speculative mode")
        if seed is not None:
            if self.draft_config is not None:
                raise ValueError("per-request seeds are not supported "
                                 "in speculative mode (the accept/"
                                 "resample rule samples every slot "
                                 "from one engine key)")
            seed = int(seed)
            if not 0 <= seed < 2 ** 31:
                raise ValueError(
                    f"seed must be in [0, 2**31), got {seed}")
        validate_sampling_overrides(temperature, top_k, top_p)
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("prompt must hold at least one token")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        resume_from = int(resume_from)
        if resume_from and not 0 < resume_from < prompt.size:
            raise ValueError(
                f"resume_from ({resume_from}) must leave at least one "
                f"true prompt token below the {prompt.size}-token "
                "prompt (it counts already-emitted output folded into "
                "the prompt's tail)")
        tenant = DEFAULT_TENANT if tenant is None else str(tenant)
        prio = (self.qos.priority(tenant, priority)
                if self.qos is not None
                else TenantQoS._parse_class(
                    "normal" if priority is None else priority))
        self.check_admissible(int(prompt.size), int(max_new_tokens),
                              prompt=prompt, tenant=tenant)
        if deadline_ms is not None and not deadline_ms > 0:
            raise ValueError(f"deadline_ms must be > 0, got {deadline_ms}")
        # expired backlog entries must not hold capacity against a live
        # admission decision
        self._shed_expired_queued()
        if fault_site("serving.submit"):
            # a plan 'drop' here is a deterministic shed: the request is
            # rejected exactly as if the queue were at capacity
            self.record_shed(tenant, "injected")
            raise QueueFullError("admission rejected (injected shed)",
                                 self._retry_after_ms())
        if (self.max_queue is not None
                and len(self._queue) >= self.max_queue):
            self.record_shed(tenant, "max_queue",
                             queue_depth=len(self._queue))
            raise QueueFullError(
                f"queue full: {len(self._queue)} requests backlogged "
                f"(max_queue={self.max_queue})", self._retry_after_ms())
        if (self.max_queued_tokens is not None
                and self._queued_tokens + prompt.size
                > self.max_queued_tokens):
            self.record_shed(tenant, "max_queued_tokens",
                             queued_tokens=self._queued_tokens)
            raise QueueFullError(
                f"queue full: {self._queued_tokens} prompt tokens "
                f"backlogged + {prompt.size} would exceed "
                f"max_queued_tokens={self.max_queued_tokens}",
                self._retry_after_ms())
        try:
            self.check_tenant_admissible(tenant, int(prompt.size))
        except QueueFullError:
            # the per-tenant quota 429: the offender sheds while
            # under-quota tenants keep admitting through the very same
            # submit path
            self.record_shed(tenant, "tenant_quota",
                             tenant_queued_tokens=self._queue
                             .tenant_queued_tokens(tenant))
            raise
        rid = self._next_rid
        self._next_rid += 1
        self._submit_t[rid] = time.monotonic()
        if submitted_at is not None:
            # the front end's own submit stamp: TTFT measures from the
            # moment the CLIENT's request entered the serving stack,
            # not from this engine's (later) decode-stage submit
            self._ttft_origin[rid] = float(submitted_at)
        # capture the submitter's trace context HERE: the engine loop
        # thread that admits/steps/retires this request later runs
        # without it, so the flight recorder stamps every event with
        # the id now, and _admit restores the context per request
        ctx = current_context()
        if ctx is not None:
            # the request's tree root is a CHILD of the submitter's
            # span: every span this engine records for rid (admission
            # wait, spill promote/demote, prefill, decode) parents to
            # the request-root span id, and the root span itself is
            # materialized retroactively at retirement
            self._trace_ctx[rid] = ctx.child()
            ctx = self._trace_ctx[rid]
        self.recorder.start(rid,
                            trace_id=None if ctx is None else ctx.trace_id,
                            prompt_tokens=int(prompt.size),
                            max_new_tokens=int(max_new_tokens),
                            tenant=tenant, priority=prio,
                            **({"prefilled": True} if prefilled is not None
                               else {}))
        if prefilled is not None:
            self._prefilled_kv[rid] = prefilled
        if seed is not None:
            self._seed[rid] = seed
        if session is not None:
            self._session[rid] = str(session)
        if resume_from:
            # ride the preemption-resume machinery: admission pops this
            # entry, pre-seeds the request's outputs with the forced
            # prefix (so result()/streams carry the FULL output and the
            # router's token-index dedupe works), sets _slot_prior, and
            # emits the ``resumed`` flight-recorder event
            self._resume[rid] = {
                "outputs": [int(t) for t in prompt[-resume_from:]],
                "preempts": 0}
        if deadline_ms is not None:
            self._deadline[rid] = self._clock() + deadline_ms / 1000.0
        self._queue.append(QueuedRequest(
            rid, prompt, int(max_new_tokens),
            self.temperature if temperature is None
            else float(temperature),
            0 if top_k is None else int(top_k),
            1.0 if top_p is None else float(top_p), tenant, prio,
            session=None if session is None else str(session)))
        self._queued_tokens += int(prompt.size)
        self._tenant_gauge(tenant)
        if admit:
            self._admit()
        return rid

    def record_shed(self, tenant: str, reason: str,
                    **event_attrs) -> None:
        """Admission-rejection bookkeeping: the global shed counter,
        the per-tenant labeled counter (QoS only), and the
        tenant-stamped ``serving.shed`` event — one helper so every
        shed path tells the same story. Public because front ends that
        enforce this engine's tenant quotas at their own submit (the
        disaggregated engine) owe the same bookkeeping."""
        self._m_shed.inc()
        if self.qos is not None:
            self._m_tenant_shed.labels(
                tenant=self.qos.label(tenant), reason=reason).inc()
        emit_event("serving.shed", reason=reason, tenant=tenant,
                   **event_attrs)

    def _tenant_gauge(self, tenant: str) -> None:
        """Lazily register the ``serving_tenant_queued_tokens`` gauge
        child for ``tenant``'s label (weakref callback over the fair
        queue, the engines' gauge convention). No-op without QoS."""
        if self.qos is None:
            return
        label = self.qos.label(tenant)
        if label in self._tenant_gauge_labels:
            return
        self._tenant_gauge_labels.add(label)
        import weakref

        ref = weakref.ref(self)
        self._m_tenant_queued.labels(tenant=label).set_function(
            lambda label=label: float(
                e._queue.tokens_for_label(label, e.qos))
            if (e := ref()) is not None else 0.0)

    def export_prefill(self, prompt: Sequence[int],
                       temperature: Optional[float] = None,
                       top_k: Optional[int] = None,
                       top_p: Optional[float] = None,
                       block_size: int = 64,
                       seed: Optional[int] = None) -> Dict:
        """Run this engine's prefix-aware prefill path for ``prompt``
        and EXPORT the result instead of occupying a slot — the prefill
        half of disaggregated serving. Rides exactly the machinery an
        ordinary admission uses (``_prefill``/chunked ``decode_block``
        extends, registered-prefix reuse, the engine's sampling rule for
        the first token), so a shipped prefill is token-identical to a
        colocated one.

        Returns ``{"first_token", "kv_blocks", "block_size",
        "prompt_tokens", "prefix_tokens", "prefill_s"}`` where
        ``kv_blocks`` is the host-side block-unit KV export
        (:func:`~elephas_tpu.models.paged_decode.export_kv_blocks`) a
        decode worker feeds to :meth:`submit_prefilled` — directly, or
        over the wire via :mod:`elephas_tpu.disagg`. Not supported on a
        SPECULATIVE engine: draft KV never ships — run the prefill tier
        on plain target-only engines and give the DECODE workers the
        draft (they recompute draft KV at admission)."""
        from .models.paged_decode import (export_kv_blocks,
                                          require_per_head_cache,
                                          require_stateless_cache)

        require_per_head_cache(self.config, "export_prefill (the "
                                            "disaggregated wire)")
        require_stateless_cache(self.config, "export_prefill (the "
                                             "disaggregated wire)")
        if self.draft_config is not None:
            raise ValueError(
                "export_prefill does not compose with speculative mode:"
                " draft KV never ships — run the prefill tier on plain "
                "(target-only) engines; speculative DECODE workers "
                "accept shipped target KV via submit_prefilled and "
                "recompute draft KV at admission")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("prompt must hold at least one token")
        if prompt.size >= self.max_len:
            raise ValueError(f"prompt ({prompt.size}) must leave room "
                             f"below max_len {self.max_len}")
        validate_sampling_overrides(temperature, top_k, top_p)
        if seed is not None and not 0 <= int(seed) < 2 ** 31:
            # (the decode side's rule: a slot's seed is an int32)
            raise ValueError(f"seed must be in [0, 2**31), got {seed}")
        temp = (self.temperature if temperature is None
                else float(temperature))
        topk = 0 if top_k is None else int(top_k)
        topp = 1.0 if top_p is None else float(top_p)
        start = time.monotonic()
        cached_tokens = 0
        if self._kv_cache is not None and self.paged is None:
            # the prefill TIER's automatic cache: a repeat prefix skips
            # its prefill compute BEFORE the KV ever hits the wire (the
            # shipped frame is identical either way — the decode side
            # cannot tell a cached export from a computed one)
            logits, row, cached_tokens, reg_used = (
                self._host_cache_prefill(None, prompt))
            prefix_tokens = max(cached_tokens, reg_used)
        else:
            entry = self._match_prefix(prompt)
            if entry is not None:
                self._m_prefix_hits.inc()
                self._m_prefix_tokens.inc(int(entry[0].size))
            logits, row = self._prefill_with_prefixes(
                prompt, self._extend_fn, self._extend_owned_fn,
                self._prefill_fn, self.params, entry, 2,
                self._fresh_row_fn)
            prefix_tokens = 0 if entry is None else int(entry[0].size)
        t0 = self._sample_first(logits, temp, topk, topp, seed=seed,
                                fold=int(prompt.size))
        blocks = export_kv_blocks(row, int(prompt.size), int(block_size))
        return {"first_token": t0, "kv_blocks": blocks,
                "block_size": int(block_size),
                "prompt_tokens": int(prompt.size),
                "prefix_tokens": int(prefix_tokens),
                "cached_tokens": int(cached_tokens),
                # the version this KV was computed under: a disagg
                # decode engine REJECTS a frame whose stamp mismatches
                # its own live version (decoding new-weight steps over
                # old-weight KV is silently wrong output, not a crash)
                "weights_version": int(self.weights_version),
                "prefill_s": round(time.monotonic() - start, 6)}

    def would_shed(self, prompt_tokens: int,
                   tenant: Optional[str] = None) -> bool:
        """Whether a submit of ``prompt_tokens`` would be shed RIGHT NOW
        by the admission bounds (``max_queue`` / ``max_queued_tokens``,
        plus ``tenant``'s per-tenant quotas when given and QoS is
        configured) — the same arithmetic :meth:`submit` applies,
        exposed so front ends (the disaggregated install retry) can
        pre-check without the shed bookkeeping a real rejected submit
        records (counter + event per attempt). Keep in lockstep with
        ``_submit_impl``'s bound checks."""
        if (self.max_queue is not None
                and len(self._queue) >= self.max_queue):
            return True
        if (self.max_queued_tokens is not None
                and self._queued_tokens + int(prompt_tokens)
                > self.max_queued_tokens):
            return True
        if self.qos is not None and tenant is not None:
            try:
                self.check_tenant_admissible(tenant, int(prompt_tokens))
            except QueueFullError:
                return True
        return False

    def check_tenant_admissible(self, tenant: str,
                                prompt_tokens: int) -> None:
        """Raise :class:`QueueFullError` (the HTTP 429) when queueing
        ``prompt_tokens`` for ``tenant`` would breach its per-tenant
        quota — THE shared transient-quota validator: the engine's own
        submit paths and the disaggregated front end both call it, so
        a quota-breached tenant sheds identically at every surface
        while under-quota tenants keep admitting. No-op without a QoS
        config. Callers own the shed bookkeeping (counter + event);
        this only decides."""
        if self.qos is None:
            return
        depth_quota, token_quota = self.qos.quota(tenant)
        if (depth_quota is not None
                and self._queue.tenant_depth(tenant) >= depth_quota):
            raise QueueFullError(
                f"tenant {tenant!r} quota: "
                f"{self._queue.tenant_depth(tenant)} requests "
                f"backlogged (max_queue={depth_quota})",
                self._retry_after_ms(tenant))
        if token_quota is not None:
            queued = self._queue.tenant_queued_tokens(tenant)
            if queued + int(prompt_tokens) > token_quota:
                raise QueueFullError(
                    f"tenant {tenant!r} quota: {queued} prompt tokens "
                    f"backlogged + {int(prompt_tokens)} would exceed "
                    f"max_queued_tokens={token_quota}",
                    self._retry_after_ms(tenant))

    def retry_after_ms(self, tenant: Optional[str] = None) -> int:
        """Public read of the shed-backoff hint a
        :class:`QueueFullError` would carry right now (quota-aware
        when ``tenant`` is given — see :meth:`_retry_after_ms`)."""
        return self._retry_after_ms(tenant)

    def _retry_after_ms(self, tenant: Optional[str] = None) -> int:
        """Backoff hint for a shed request: roughly how long until the
        backlog drains enough to retry, from the median observed request
        latency scaled by the queue's depth relative to slot capacity
        (clamped to a sane window; 100ms before any sample exists).
        With ``tenant`` and a QoS config the depth is the OFFENDING
        tenant's own backlog — a quota 429's hint scales with how far
        over its share that tenant is, not with the global queue."""
        depth = len(self._queue)
        if tenant is not None and self.qos is not None:
            depth = self._queue.tenant_depth(tenant)
        if self._latency_window:
            med = float(np.quantile(
                [t for _, t, _ in self._latency_window], 0.5))
            est = 1000.0 * med * max(1, depth) / self.max_slots
        else:
            est = 100.0
        return int(min(10000.0, max(50.0, est)))

    def cancel(self, rid: int) -> bool:
        """Abort a request: drop it from the queue, or free its slot and
        discard its partial output. Returns whether anything was
        cancelled (False for unknown or already-finished ids —
        :meth:`result` still serves finished ones)."""
        item = self._queue.remove_rid(rid)
        if item is not None:
            self._queued_tokens -= int(item.prompt.size)
            self._submit_t.pop(rid, None)
            self._deadline.pop(rid, None)
            cctx = self._trace_ctx.pop(rid, None)
            if cctx is not None:
                # close the tree (client-initiated, not an SLO story:
                # retained only if it ranks slowest-k, i.e. never
                # without a latency — this is the store's GC path)
                default_span_store().finish(cctx.trace_id)
            self._prefilled_kv.pop(rid, None)
            self._resume.pop(rid, None)
            self._seed.pop(rid, None)
            self._session.pop(rid, None)
            # a preempted-then-re-queued request may still hold an
            # un-surfaced admission token: the next step() must not
            # report tokens for a cancelled rid
            self._fresh.pop(rid, None)
            self._accept.pop(rid, None)
            # a preempted-then-re-queued rid still carries token-time
            # stamps from its first life
            self._ttft_origin.pop(rid, None)
            self._last_tok_t.pop(rid, None)
            self._ttft_val.pop(rid, None)
            self.recorder.record(rid, "cancelled", stage="queued")
            return True
        for slot, st in list(self._pending_prefill.items()):
            if st["rid"] != rid:
                continue
            # mid-interleaved-prefill: the chunks already computed are
            # discarded with the slot's blocks — nothing was emitted yet
            self._abort_pending_prefill(slot)
            self._submit_t.pop(rid, None)
            self._admit_t.pop(rid, None)
            self._deadline.pop(rid, None)
            cctx = self._trace_ctx.pop(rid, None)
            if cctx is not None:
                default_span_store().finish(cctx.trace_id)
            self._seed.pop(rid, None)
            self._session.pop(rid, None)
            self._fresh.pop(rid, None)
            self._accept.pop(rid, None)
            self._ttft_origin.pop(rid, None)
            self._last_tok_t.pop(rid, None)
            self._ttft_val.pop(rid, None)
            self.recorder.record(rid, "cancelled", stage="prefilling")
            return True
        for slot, r in enumerate(self._rid):
            # the explicit None guard matters: a caller holding a
            # None/absent id must not "cancel" a FREE slot (None == None)
            if r is not None and r == rid:
                tokens = len(self._outputs.get(rid, ()))
                self._outputs.pop(rid, None)
                self._fresh.pop(rid, None)
                self._accept.pop(rid, None)
                self._rid[slot] = None
                self._release_blocks(slot)
                self._clear_slot_meta(slot)
                self._submit_t.pop(rid, None)
                self._admit_t.pop(rid, None)
                self._deadline.pop(rid, None)
                cctx = self._trace_ctx.pop(rid, None)
                if cctx is not None:
                    default_span_store().finish(cctx.trace_id)
                self._seed.pop(rid, None)
                self._session.pop(rid, None)
                self._ttft_origin.pop(rid, None)
                self._last_tok_t.pop(rid, None)
                self._ttft_val.pop(rid, None)
                self.recorder.record(rid, "cancelled", stage="decoding",
                                     tokens=tokens)
                return True
        return False

    def _free_slots(self) -> List[int]:
        # a slot mid-interleaved-prefill is reserved, not free: its rid
        # is unset (the decode loop must treat it as inactive) but its
        # blocks/cache row belong to the pending request
        return [s for s in range(self.max_slots)
                if self._rid[s] is None and s not in self._pending_prefill]

    def _shed_expired_queued(self):
        """Drop every queued request whose deadline already passed —
        BEFORE it ever reaches prefill. Each becomes a finished result
        with no tokens, marked ``expired`` (the HTTP layer's 504)."""
        if not self._deadline or not len(self._queue):
            return
        now = self._clock()
        dropped = self._queue.remove_if(
            lambda item: (dl := self._deadline.get(item.rid)) is not None
            and now >= dl)
        for item in dropped:
            rid = item.rid
            self._queued_tokens -= int(item.prompt.size)
            self._deadline.pop(rid, None)
            self._prefilled_kv.pop(rid, None)
            t_sub = self._submit_t.pop(rid, None)
            saved = self._resume.pop(rid, None)
            self._seed.pop(rid, None)
            self._session.pop(rid, None)
            ectx = self._trace_ctx.pop(rid, None)
            if ectx is not None:
                # a deadline miss is exactly the SLO-violating trace
                # the tail-based store exists to keep
                default_span_store().finish(
                    ectx.trace_id,
                    latency_s=(None if t_sub is None
                               else time.monotonic() - t_sub),
                    violated=True)
            self._ttft_origin.pop(rid, None)
            self._last_tok_t.pop(rid, None)
            self._ttft_val.pop(rid, None)
            if saved is not None:
                # preempted mid-decode and the deadline passed while
                # re-queued: the tokens already emitted are the final
                # (partial) output — a mid-decode timeout, not an
                # expired-before-prefill shed
                self._done[rid] = saved["outputs"]
                self._timed_out.add(rid)
                self._m_timed_out.inc()
                a_p = self._accept.pop(rid, None)
                self.recorder.record(
                    rid, "timed_out", stage="preempted_queued",
                    tokens=len(saved["outputs"]),
                    **({} if a_p is None
                       else {"draft_accepted": a_p[0],
                             "draft_proposed": a_p[1]}))
            else:
                self._done[rid] = []
                self._expired.add(rid)
                self._m_expired.inc()
                self.recorder.record(
                    rid, "expired",
                    queue_wait_s=(None if t_sub is None
                                  else round(time.monotonic() - t_sub,
                                             6)))

    def _enforce_active_deadlines(self):
        """Retire every ACTIVE slot whose request deadline passed: the
        slot (and its paged blocks) frees immediately and the tokens
        emitted so far become the final output, marked ``timeout``."""
        if not self._deadline:
            return
        now = self._clock()
        for slot, rid in enumerate(self._rid):
            if rid is None or self._deadline.get(rid, now + 1) > now:
                continue
            # _fresh stays: an admission-time token not yet surfaced by
            # step() still reaches streaming clients on the next call
            self._retire_slot(slot, "timed_out")
            self._timed_out.add(rid)
            self._m_timed_out.inc()

    def _admit(self):
        # profiled as one "admit" section whose nested prefill/swap
        # children are EXCLUDED (the profiler's exclusive accounting),
        # so admission scheduling cost and prefill compute are separate
        # answers on serving_loop_utilization. The steady-decode case —
        # empty queue, nothing staged — skips the sections entirely:
        # _admit runs twice per step, and timing its ~µs no-op as
        # "admit" would double the profiler's per-step cost to
        # attribute time that belongs in idle anyway.
        if self.profiler is None or (not len(self._queue)
                                     and self._staged_params is None
                                     and self._staged_draft is None):
            return self._admit_impl()
        with self.profiler.section("elephas.loop.admit"):
            self._admit_impl()

    def _admit_impl(self):
        # a staged live-weight swap lands FIRST — admission prefills
        # must run under the params their requests will decode under
        # (this covers both entry points: step()'s between-decode-steps
        # call and an immediate submit(admit=True) admission)
        if self._staged_params is None and self._staged_draft is None:
            # unlocked peek is safe: a staging racing this read lands
            # on the next step — exactly the contract stage_params has
            self.apply_staged_params()
        else:
            with self._psec("elephas.loop.swap"):
                self.apply_staged_params()
        self._shed_expired_queued()
        self._enforce_active_deadlines()
        self._enforce_pending_deadlines()
        while len(self._queue):
            slots = self._free_slots()
            if not slots:
                # every slot busy: a strictly-higher-priority candidate
                # may preempt a lower-priority in-flight decode (QoS
                # with the paged cache only) — otherwise admission
                # waits for a retirement exactly as before
                if not self._maybe_preempt_for(self._queue.peek()):
                    return
                continue
            with self._psec("elephas.loop.admit.request"):
                if not self._admit_request(slots[0]):
                    return

    def _admit_request(self, slot: int) -> bool:
        """One admission into the free ``slot``: claim what serves the
        scheduled candidate (cached blocks, fresh blocks), pop it,
        prefill and install it. Returns False when admission has to
        wait (no blocks and no one to preempt), True when the loop
        in :meth:`_admit_impl` may go on."""
        cand = self._queue.peek()
        self._pmeta(rid=cand.rid, prompt_tokens=int(cand.prompt.size))
        with self._psec("elephas.loop.admit.claim"):
            if self.paged is not None:
                # allocate BEFORE popping: when the pool is momentarily
                # empty the scheduled candidate simply waits (no
                # overtaking past the fair-queue choice, so no
                # starvation)
                cand = self._queue.peek()
                nxt_rid, nxt_prompt, nxt_max_new = (cand.rid, cand.prompt,
                                                    cand.max_new)
                bsz = self.paged[1]
                # verify slack rides every paged allocation in
                # speculative mode (zero otherwise) — the blocks the
                # rejected-tail writes are confined to
                needed = -(-(nxt_prompt.size + nxt_max_new
                             + self._slack) // bsz)
                hits = []
                promos = []
                if (self._kv_cache is not None
                        and nxt_rid not in self._prefilled_kv):
                    # cached full blocks need no allocation: the slot's
                    # table will POINT at them
                    walk_keys, _ = self._chain_keys_for(nxt_rid,
                                                        nxt_prompt)
                    hits = self._kv_cache.match_chain(walk_keys)
                    # HBM miss != re-prefill: the walk falls through to
                    # the spill tiers / session store. Promoted blocks
                    # DO allocate (they install into private blocks),
                    # so they don't change `needed` below — they trade
                    # the remainder's prefill FLOPs, not its HBM. The
                    # walk's tier reads (a storage GET per key) run
                    # under the candidate's trace context so the spill
                    # layer's spans land on its tree.
                    with use_context(self._trace_ctx.get(nxt_rid)):
                        promos = self._tier_walk(
                            nxt_rid, walk_keys[len(hits):], len(hits),
                            allow_lossy=self._lossy_promote)
                    if hits or promos:
                        # longest registered match still wins: when the
                        # pinned ROW covers more than the block chain
                        # (a sub-block tail, or a partially pinned
                        # prefix), skip the claim and let the classic
                        # registered path serve the whole head — but
                        # ONLY when a full private allocation is
                        # permanently satisfiable. check_admissible
                        # admitted this request crediting its leading
                        # pinned run; dropping the claim while pins
                        # make `needed` private blocks impossible
                        # would wedge the FIFO head forever for a
                        # sub-block tail's worth of reuse.
                        reg = self._match_prefix(nxt_prompt)
                        if (reg is not None and int(reg[0].size)
                                > (len(hits) + len(promos)) * bsz
                                and needed <= self.paged[0] - 1
                                - self._kv_cache.pinned_count()):
                            hits, promos = [], []
                avail = len(self._free_block_ids)
                if self._kv_cache is not None:
                    # parked (zero-ref) cached blocks are reclaimable —
                    # minus any this very admission is about to reuse
                    avail += (self._kv_cache.reclaimable_count()
                              - sum(1 for e in hits
                                    if self._kv_cache.is_parked(e)))
                if avail < needed - len(hits):
                    # pool pressure: a higher-priority candidate may
                    # preempt a lower-priority decode (its blocks park
                    # or free, and the loop re-evaluates availability);
                    # otherwise the candidate keeps its turn and waits
                    if not self._maybe_preempt_for(cand):
                        return False
                    return True
                # claim the hit chain FIRST (refcount++, unpark): the
                # remainder allocation below may evict LRU entries and
                # must never reclaim the blocks this request reuses
                for e in hits:
                    self._kv_cache.acquire(e)
                self._slot_cached[slot] = list(hits)
                # demotions this allocation triggers flush as ONE
                # kv_demote event (per-block events would flood the
                # recorder's per-rid cap on a large allocation)
                self._demote_accum = {}
                blocks = [self._alloc_block()
                          for _ in range(needed - len(hits))]
                accum, self._demote_accum = self._demote_accum, None
                if accum.get("staged") or accum.get("blocks"):
                    # demotions bill to the ADMITTING request (its
                    # allocation forced them): flush under its context
                    # as a spill_demote stage span
                    with use_context(self._trace_ctx.get(nxt_rid)), \
                            start_span("serving.kv_demote",
                                       stage="spill_demote"):
                        demoted = self._flush_demotions(accum)
                else:
                    demoted = self._flush_demotions(accum)
                if demoted:
                    self.recorder.record(nxt_rid, "kv_demote",
                                         blocks=demoted)
                    emit_event("serving.kv_demote", rid=nxt_rid,
                               blocks=demoted)
                if promos:
                    self._slot_promos[slot] = promos
                self._slot_blocks[slot] = blocks
                self._tables[slot, :] = 0      # unused entries -> scratch
                self._tables[slot, :needed] = (
                    [e.payload for e in hits] + blocks)
        item = self._queue.pop()
        rid, prompt, max_new = item.rid, item.prompt, item.max_new
        temp, topk, topp = item.temperature, item.top_k, item.top_p
        self._queued_tokens -= int(prompt.size)
        resume = self._resume.pop(rid, None)
        # queue wait ends HERE — prefill compute/compile time below
        # belongs to total latency, not to time-spent-queued
        self._admit_t[rid] = time.monotonic()
        t_sub = self._submit_t.get(rid)
        self.recorder.record(
            rid, "admitted", slot=slot, tenant=item.tenant,
            # the weight version this request will decode under —
            # the flight-recorder half of "which weights served
            # this request" (a mid-decode swap shows up as
            # weights.swapped events between its step events)
            weights_version=self.weights_version,
            queue_wait_s=(None if t_sub is None
                          else round(self._admit_t[rid] - t_sub, 6)),
            # the sampling seed, when pinned — the repro handle: a
            # trace reader can replay THIS request's exact output
            **({"seed": self._seed[rid]}
               if rid in self._seed else {}))
        if t_sub is not None and rid in self._trace_ctx:
            # queue time as a retroactive stage span: monotonic
            # wait projected back from the current wall clock
            wait_s = self._admit_t[rid] - t_sub
            add_span("serving.admission_wait", time.time() - wait_s,
                     wait_s, stage="admission_wait",
                     ctx=self._trace_ctx[rid])
        # per-request context restore: this loop runs on the engine
        # thread, but prefill (and any span/fault/event it emits)
        # belongs to the request whose context was captured at
        # submit — None for requests submitted without one
        pre = self._prefilled_kv.pop(rid, None)
        with use_context(self._trace_ctx.get(rid)):
            if (pre is not None and len(pre) > 2
                    and pre[2] is not None
                    and int(pre[2]) != int(self.weights_version)):
                # the shipped KV's weight-version stamp went stale
                # between the caller's gate and THIS install (a
                # hot-swap staged in the window): decoding over it
                # would be silently wrong output. Fall back to a
                # local prefill — correct, never a failed request,
                # one admission's worth of extra compute.
                self.recorder.record(
                    rid, "kv_install_stale",
                    frame_version=int(pre[2]),
                    engine_version=int(self.weights_version),
                    fallback="local_prefill")
                emit_event("serving.kv_install_stale",
                           frame_version=int(pre[2]),
                           engine_version=int(self.weights_version))
                pre = None
            if pre is not None:
                # disaggregated admission: the shipped KV blocks
                # install straight into the slot (between decode
                # steps — this loop IS the atomic point); no
                # prefill compute, no prefix lookup
                # shipped frames deliberately do NOT seed the
                # decode-side cache: a pure-disagg decode tier
                # never walks it for prefilled requests (dead
                # entries would only inflate eviction churn), and
                # a Q8 frame's dequantized KV is content-addressed
                # by TOKENS — letting a later LOCAL admission hit
                # lossy blocks would break its cache-off parity
                with self._psec("elephas.loop.prefill"), \
                        start_span("serving.kv_install",
                                   stage="prefill"):
                    t0 = self._install_prefilled(slot, prompt, pre)
                self.recorder.record(
                    rid, "kv_install",
                    prompt_tokens=int(prompt.size),
                    duration_s=round(
                        time.monotonic() - self._admit_t[rid], 6))
            else:
                if self._interleave_ok(slot, prompt):
                    # defer the chunk loop: the slot is reserved
                    # (blocks allocated, hit chain claimed) but its
                    # prompt feeds between the coming decode steps
                    # — _interleave_prefills() finishes the
                    # admission when the last chunk lands
                    self._begin_interleaved_prefill(
                        rid, slot, item, prompt, resume, temp,
                        topk, topp)
                    return True
                with self._psec("elephas.loop.prefill"), \
                        start_span("serving.prefill",
                                   stage="prefill"):
                    t0 = self._admit_prefill(rid, slot, prompt,
                                             temp, topk, topp)
        self._rid[slot] = rid
        # a RESUMED request keeps the tokens it emitted before its
        # preemption — the new first token (sampled from the full
        # resubmitted sequence's final-position logits) is exactly
        # the next token the never-preempted decode would emit
        self._outputs[rid] = ([] if resume is None
                              else resume["outputs"])
        self._slot_prompt[slot] = prompt
        self._slot_prior[slot] = len(self._outputs[rid])
        self._slot_tenant[slot] = item.tenant
        self._slot_priority[slot] = item.priority
        self._slot_wv[slot] = self.weights_version
        self._pos[slot] = prompt.size - 1
        self._last[slot] = t0
        self._last_set[slot] = True
        self._budget[slot] = max_new
        self._temp[slot] = temp
        self._topk[slot] = topk
        self._topp[slot] = topp
        self._slot_seed[slot] = self._seed.get(rid, -1)
        if self.qos is not None:
            self._m_tenant_admitted.labels(
                tenant=self.qos.label(item.tenant)).inc()
        if resume is not None:
            self.recorder.record(
                rid, "resumed", tokens_so_far=len(self._outputs[rid]),
                remaining_tokens=int(max_new),
                preemptions=resume["preempts"])
        if self._record(slot, t0):
            # surfaced by the next step(); append — a preempted-
            # and-resumed request may still owe its PREVIOUS
            # admission's un-surfaced first token
            self._fresh.setdefault(rid, []).append(t0)
        return True

    # --------------------------------------------------------- preemption
    @property
    def _preempt_enabled(self) -> bool:
        """Preemption needs somewhere cheap to PARK the victim's KV:
        the paged pool + block cache (park = release to LRU, resume =
        chain-walk reclaim). QoS on other engine shapes still gets
        fair queueing and quotas, never preemption."""
        return (self.qos is not None and self.qos.preempt
                and self.paged is not None
                and self._kv_cache is not None)

    def _maybe_preempt_for(self, cand) -> bool:
        """Preempt ONE in-flight decode of strictly lower priority
        than queued candidate ``cand`` (lowest class first; among
        equals the slot with the fewest emitted tokens — the cheapest
        resume). Returns whether a victim was preempted; the admission
        loop re-evaluates capacity after each one."""
        if cand is None or not self._preempt_enabled:
            return False
        victim = None
        for slot, rid in enumerate(self._rid):
            if rid is None:
                continue
            prio = int(self._slot_priority[slot])
            if prio >= int(cand.priority):
                continue
            key = (prio, len(self._outputs.get(rid, ())))
            if victim is None or key < victim[0]:
                victim = (key, slot)
        if victim is None:
            return False
        self._preempt_slot(victim[1])
        return True

    def _preempt_slot(self, slot: int) -> None:
        """Evict the slot's request mid-decode, parking its KV: every
        full block of the sequence decoded so far enters the block
        cache (release → LRU — resident but reclaimable, exactly like
        a retired request's shared prefix), and the request re-queues
        at the FRONT of its tenant lane with prompt = original prompt
        + tokens emitted so far and budget = what remains. On
        re-admission the chain walk reclaims the parked blocks, so
        resume costs a short remainder prefill, not a recompute — and
        greedy output is token-identical to the never-preempted run.

        ``serving.preempt`` fault site: ``delay`` = a slow park,
        ``drop``/``error`` = the parking path failing — the blocks
        free instead of parking and the request still re-queues
        (resume recomputes; a preemption fault may cost compute, never
        the request)."""
        from .models.paged_decode import require_stateless_cache

        # (not reachable through _preempt_enabled, which asks for the
        # prefix cache; refused by name all the same)
        require_stateless_cache(self.config, "preemption (_preempt_slot)")
        rid = self._rid[slot]
        tenant = self._slot_tenant[slot] or DEFAULT_TENANT
        priority = int(self._slot_priority[slot])
        prompt = self._slot_prompt[slot]
        outputs = self._outputs.pop(rid)
        remaining = int(self._budget[slot])
        # only the tokens emitted SINCE this slot's admission extend
        # the prompt — a resumed request's prompt already folds in its
        # pre-preemption output (_slot_prior), and re-appending it
        # would corrupt the sequence on a second preemption
        seq = np.concatenate(
            [prompt, np.asarray(outputs[int(self._slot_prior[slot]):],
                                np.int32)])
        parked = 0
        try:
            if fault_site("serving.preempt"):
                raise InjectedFault("injected preempt-park drop")
            # KV through position _pos[slot] is on device: park its
            # full blocks (the pending last token was never processed,
            # so the parked chain covers seq[:-1])
            parked = self._park_slot_blocks(
                slot, seq[:int(self._pos[slot]) + 1])
        except InjectedFault:
            parked = 0     # park failed: blocks free below instead —
            # the resume recomputes the prefix, the request survives
        resume = self._resume.get(rid)
        preempts = 1 + (0 if resume is None else resume["preempts"])
        self._rid[slot] = None
        self._release_blocks(slot)
        self._clear_slot_meta(slot)
        self._admit_t.pop(rid, None)
        if self._chain_memo is not None and self._chain_memo[0] == rid:
            # the resume prompt differs from the one this rid's memo
            # hashed — a stale memo would walk the wrong chain
            self._chain_memo = None
        if self._promo_memo is not None and self._promo_memo[0] == rid:
            self._promo_memo = None
        self._resume[rid] = {"outputs": outputs, "preempts": preempts}
        self._queue.appendleft(QueuedRequest(
            rid, seq, remaining, float(self._temp[slot]),
            int(self._topk[slot]), float(self._topp[slot]), tenant,
            priority, session=self._session.get(rid)))
        self._queued_tokens += int(seq.size)
        self._m_preemptions.inc()
        if self.qos is not None:
            self._m_tenant_preempt.labels(
                tenant=self.qos.label(tenant)).inc()
        self.recorder.record(rid, "preempted", tokens=len(outputs),
                             parked_blocks=parked,
                             remaining_tokens=remaining)
        emit_event("serving.preempted", rid=rid, tenant=tenant,
                   tokens=len(outputs), parked_blocks=parked)

    def _park_slot_blocks(self, slot: int, seq_kv: np.ndarray) -> int:
        """Move the slot's PRIVATE full blocks over ``seq_kv`` (the
        tokens whose KV the slot holds) into the block cache, keyed by
        the sequence's chain — un-referenced, so they park on the LRU
        immediately: resident for the resume's walk, reclaimable under
        pool pressure like any cold prefix. Blocks whose chain key is
        already cached (admission-time hits/inserts) stay where they
        are — :meth:`_release_blocks` parks those via their refcounts.
        Returns how many blocks parked here."""
        if int(self._slot_wv[slot]) != int(self.weights_version):
            # a hot-swap landed mid-decode: this KV was (partly)
            # computed under other weights — parking it under the
            # CURRENT version's chain keys would serve stale state to
            # a post-swap admission. Free instead of park.
            return 0
        if self._slot_lossy[slot]:
            # lossy-tainted slot (admitted over a dequantized promoted
            # block): same parity rule as _insert_full_blocks — free,
            # never park under chain keys
            return 0
        from .models.block_cache import chain_keys

        bs = self._kv_cache_bs
        nfull = seq_kv.size // bs
        if nfull == 0:
            return 0
        keys = chain_keys(seq_kv[:nfull * bs], bs, self.weights_version)
        private = set(self._slot_blocks[slot])
        parked = 0
        for i, key in enumerate(keys):
            if self._kv_cache.get(key) is not None:
                continue
            bid = int(self._tables[slot, i])
            if bid not in private:
                continue           # shared under a different key: leave
            self._kv_cache.insert(key, bid, (i + 1) * bs)
            self._slot_blocks[slot].remove(bid)
            private.discard(bid)
            parked += 1
        return parked

    def _clear_slot_meta(self, slot: int) -> None:
        self._slot_prompt[slot] = None
        self._slot_prior[slot] = 0
        self._slot_tenant[slot] = None
        self._slot_priority[slot] = 0
        self._slot_wv[slot] = 0
        self._slot_seed[slot] = -1
        self._slot_lossy[slot] = False
        self._slot_promos.pop(slot, None)

    def _admit_prefill(self, rid: int, slot: int, prompt: np.ndarray,
                       temp: float, topk: int, topp: float) -> int:
        """The colocated admission body: prefix-aware prefill on THIS
        engine, slot install, first-token sample. Runs under the
        request's restored trace context (the caller's ``use_context``)."""
        if self._kv_cache is not None:
            if self.paged is not None:
                return self._admit_prefill_paged_cached(
                    rid, slot, prompt, temp, topk, topp)
            logits, row, reused, reg_used = self._host_cache_prefill(
                rid, prompt)
            self._pmeta(prefix_tokens=max(reused, reg_used))
            with self._psec("elephas.loop.prefill.install"):
                self.cache = self._install_fn(self.cache, row, slot)
            if self.draft_config is not None:
                # the cache served (some of) the TARGET's prefill; the
                # draft's KV is never cached and recomputes in full
                self._install_draft_row(slot, prompt)
            t0 = self._sample_first(logits, temp, topk, topp,
                                    seed=self._seed.get(rid),
                                    fold=int(prompt.size))
            self.recorder.record(
                rid, "prefill", prompt_tokens=int(prompt.size),
                prefix_tokens=max(reused, reg_used),
                duration_s=round(
                    time.monotonic() - self._admit_t[rid], 6))
            return t0
        # exact-length prefill: one compile per distinct prompt
        # length (an online server batches by length bucket
        # upstream if compile churn matters); a registered-
        # prefix hit reuses the prefix's cached k/v and
        # prefills only the suffix
        entry = self._match_prefix(prompt)
        if entry is not None:
            self._m_prefix_hits.inc()
            self._m_prefix_tokens.inc(int(entry[0].size))
        self._pmeta(
            prefix_tokens=0 if entry is None else int(entry[0].size))
        logits, row_cache = self._prefill_with_prefixes(
            prompt, self._extend_fn, self._extend_owned_fn,
            self._prefill_fn, self.params, entry, 2,
            self._fresh_row_fn)
        with self._psec("elephas.loop.prefill.install"):
            if self.paged is not None:
                from .models.paged_decode import install_row_paged

                nprefill = -(-prompt.size // self.paged[1])
                self.pool = install_row_paged(
                    self.pool, row_cache, self._tables[slot], nprefill,
                    slot=slot)
            else:
                self.cache = self._install_fn(self.cache, row_cache,
                                              slot)
        if self.draft_config is not None:
            self._install_draft_row(slot, prompt, entry=entry)
        t0 = self._sample_first(logits, temp, topk, topp,
                                seed=self._seed.get(rid),
                                fold=int(prompt.size))
        self.recorder.record(
            rid, "prefill", prompt_tokens=int(prompt.size),
            prefix_tokens=(0 if entry is None else int(entry[0].size)),
            duration_s=round(time.monotonic() - self._admit_t[rid], 6))
        return t0

    def _admit_prefill_paged_cached(self, rid: int, slot: int,
                                    prompt: np.ndarray, temp: float,
                                    topk: int, topp: float) -> int:
        """Paged admission with the automatic block cache: the hit
        chain (claimed by ``_admit`` — its blocks are ALREADY the head
        of the slot's table, pure pointer install) is gathered into a
        row head, only the remainder prefills, and the freshly
        computed full blocks register in the cache so the next
        same-head request hits. Zero hits degrades to the classic
        prefix-aware full prefill (plus the cache insert)."""
        from .models.paged_decode import (gather_blocks_to_row,
                                          install_row_paged)

        cache, bs = self._kv_cache, self._kv_cache_bs
        # COUNT of device hits, not the list: _install_promotions
        # appends the promoted entries to _slot_cached[slot] (they are
        # cache-registered, slot-referenced blocks from then on), so
        # the live list grows past the device-hit prefix
        nhits = len(self._slot_cached[slot])
        promos = self._slot_promos.pop(slot, [])
        walk_keys, _ = self._chain_keys_for(rid, prompt)
        nprefill = -(-prompt.size // bs)
        if promos:
            # spilled/session blocks claimed by _admit's tier walk:
            # one host->device copy each into the already-allocated
            # table entries just past the device hits, then the chain
            # continues exactly as if they had been device hits
            self._install_promotions(rid, slot, nhits, promos)
        j = nhits + len(promos)
        if (self._session.get(rid) is not None
                and self._m_session_hits is not None and walk_keys):
            # resume observability: did this session-tagged admission
            # find ANY of its chain (device, spill, or session tier)?
            (self._m_session_hits if j > 0
             else self._m_session_misses).inc()
        if j > 0:
            reused = j * bs
            self._m_kv_hits.inc()
            self._m_prefix_tokens.inc(reused)
            cache.record_walk(j, True)
            self.recorder.record(rid, "kv_cache_hit", blocks=j,
                                 tokens_reused=reused,
                                 promoted=len(promos))
            self._pmeta(prefix_tokens=reused)
            with self._psec("elephas.loop.prefill.row_init"):
                row = gather_blocks_to_row(
                    self.pool,
                    [int(b) for b in self._tables[slot, :j]],
                    self.max_len)
            logits, row = self._extend_remainder(row, prompt, reused)
        else:
            # classic path, registered row included (longest match
            # wins — _admit skips the chain claim when the pinned row
            # covers more than the cached chain)
            entry = self._match_prefix(prompt)
            reused = 0
            if entry is not None:
                self._m_prefix_hits.inc()
                self._m_prefix_tokens.inc(int(entry[0].size))
                reused = int(entry[0].size)
            elif walk_keys:
                # a registered-row-served admission is the PINNING
                # layer's reuse (counted just above), not a cache miss
                self._m_kv_misses.inc()
                cache.record_walk(0, True)
            self._pmeta(prefix_tokens=reused)
            logits, row = self._prefill_with_prefixes(
                prompt, self._extend_fn, self._extend_owned_fn,
                self._prefill_fn, self.params, entry, 2,
                self._fresh_row_fn)
        # install ONLY the remainder blocks: positions [j*bs, ...) —
        # the shared head blocks already hold their positions and other
        # slots may be reading them this very step
        with self._psec("elephas.loop.prefill.install"):
            self.pool = install_row_paged(self.pool, row,
                                          self._tables[slot], nprefill,
                                          start=j, slot=slot)
            self._insert_full_blocks(slot, prompt, skip=j, rid=rid)
        if self.draft_config is not None:
            # speculative paged admission: the chain hit (or miss) above
            # served the TARGET cache only — the draft recomputes its
            # whole-prompt KV into its contiguous cache
            self._install_draft_row(slot, prompt)
        t0 = self._sample_first(logits, temp, topk, topp,
                                seed=self._seed.get(rid),
                                fold=int(prompt.size))
        self.recorder.record(
            rid, "prefill", prompt_tokens=int(prompt.size),
            # whichever layer served: the chain's blocks or the
            # registered row (the classic path stamps the same field)
            prefix_tokens=int(reused),
            duration_s=round(time.monotonic() - self._admit_t[rid], 6))
        return t0

    # ------------------------------------------- interleaved prefill
    def _interleave_ok(self, slot: int, prompt: np.ndarray) -> bool:
        """Should THIS admission's chunk loop defer between decode
        steps? Only worth it when decodes are actually in flight (an
        empty engine prefills fastest run-to-completion) and more than
        one chunk of compute remains after prefix/cache reuse. The
        contiguous host-cache path stays run-to-completion: its payload
        import and insert steps are woven through the compute. The
        decision never affects output tokens — both paths feed
        identical chunk shapes — only who waits for whom."""
        if (not self.interleave_prefill
                or not any(r is not None for r in self._rid)):
            return False
        if self.paged is None and self._kv_cache is not None:
            return False
        if self.paged is not None and self._kv_cache is not None:
            est = (len(self._slot_cached[slot])
                   + len(self._slot_promos.get(slot, []))
                   ) * self._kv_cache_bs
            if est == 0:
                entry = self._match_prefix(prompt)
                est = 0 if entry is None else int(entry[0].size)
        else:
            entry = self._match_prefix(prompt)
            est = 0 if entry is None else int(entry[0].size)
        return prompt.size - est > self.prefill_chunk

    def _begin_interleaved_prefill(self, rid: int, slot: int, item,
                                   prompt: np.ndarray, resume,
                                   temp: float, topk: int,
                                   topp: float) -> None:
        """The front half of admission, minus the chunk loop: claim
        whatever serves the prompt head (cache-hit chain, tier
        promotions, or a registered prefix row) exactly as the
        run-to-completion paths do, then park the admission as pending
        state for :meth:`_interleave_prefills` to advance. The slot's
        table resets to the scratch sink while pending — inactive
        slots' decode-step garbage writes land on block 0, and this
        slot's REAL blocks (some shared with live decodes via the
        cache) must not take them."""
        reused, j, entry, row, owned = 0, 0, None, None, True
        if self.paged is not None and self._kv_cache is not None:
            from .models.paged_decode import gather_blocks_to_row

            bs = self._kv_cache_bs
            nhits = len(self._slot_cached[slot])
            promos = self._slot_promos.pop(slot, [])
            walk_keys, _ = self._chain_keys_for(rid, prompt)
            if promos:
                self._install_promotions(rid, slot, nhits, promos)
            j = nhits + len(promos)
            if (self._session.get(rid) is not None
                    and self._m_session_hits is not None and walk_keys):
                (self._m_session_hits if j > 0
                 else self._m_session_misses).inc()
            if j > 0:
                reused = j * bs
                self._m_kv_hits.inc()
                self._m_prefix_tokens.inc(reused)
                self._kv_cache.record_walk(j, True)
                self.recorder.record(rid, "kv_cache_hit", blocks=j,
                                     tokens_reused=reused,
                                     promoted=len(promos))
                row = gather_blocks_to_row(
                    self.pool,
                    [int(b) for b in self._tables[slot, :j]],
                    self.max_len)
            else:
                entry = self._match_prefix(prompt)
                if entry is not None:
                    self._m_prefix_hits.inc()
                    self._m_prefix_tokens.inc(int(entry[0].size))
                    reused = int(entry[0].size)
                elif walk_keys:
                    self._m_kv_misses.inc()
                    self._kv_cache.record_walk(0, True)
        else:
            entry = self._match_prefix(prompt)
            if entry is not None:
                self._m_prefix_hits.inc()
                self._m_prefix_tokens.inc(int(entry[0].size))
                reused = int(entry[0].size)
        if row is None:
            row = (self._fresh_row_fn() if entry is None else entry[2])
            owned = entry is None
        self._pmeta(prefix_tokens=int(reused))
        self._pending_prefill[slot] = dict(
            rid=rid, item=item, resume=resume, prompt=prompt,
            temp=temp, topk=topk, topp=topp, row=row,
            suffix=prompt[int(reused):], cursor=0, first=True,
            owned=owned, entry=entry, logits=None, reused=int(reused),
            j=j, wv0=int(self.weights_version),
            table=(self._tables[slot].copy()
                   if self.paged is not None else None),
            t0=time.monotonic(), ctx=self._trace_ctx.get(rid))
        if self.paged is not None:
            self._tables[slot, :] = 0

    def _refresh_prefill_budget(self) -> None:
        """Recompute the chunks-per-iteration budget from the
        profiler's decode-phase share of wall time, every
        :data:`PREFILL_BUDGET_EVERY` iterations (``utilization()``
        walks the ring under a lock — reading it per step would put
        the profiler's whole per-step cost on the scheduler). Decode
        saturating the loop → 1 chunk/step (in-flight inter-token
        latency wins); decode mostly waiting → up to
        :data:`MAX_INTERLEAVE_CHUNKS` (drain the prompt, TTFT wins).
        Profiler off → the conservative 1."""
        self._budget_age += 1
        if self._budget_age < PREFILL_BUDGET_EVERY:
            return
        self._budget_age = 0
        if self.profiler is None:
            self._prefill_budget = 1
            return
        util = self.profiler.utilization()
        decode = util["decode"] + util["decode_dispatch"]
        self._prefill_budget = max(
            1, int(round((1.0 - decode) * MAX_INTERLEAVE_CHUNKS)))

    def _interleave_prefills(self) -> None:
        """Advance pending interleaved prefills by at most the current
        chunk budget (total, across pending slots — oldest first, so
        the earliest admission reaches its first token soonest), and
        complete any whose last chunk landed."""
        self._refresh_prefill_budget()
        budget = self._prefill_budget
        for slot in list(self._pending_prefill):
            while budget > 0:
                budget -= 1
                if self._feed_prefill_chunk(slot):
                    self._finish_interleaved_prefill(slot)
                    break
            if budget <= 0:
                return

    def _feed_prefill_chunk(self, slot: int) -> bool:
        """Feed ONE ``prefill_chunk``-sized block of the pending
        prompt. Chunk boundaries and fn choice (the first chunk over a
        registered row must not donate it) mirror
        :meth:`_extend_chunked` exactly, so the interleaved admission
        computes the identical program sequence — identical compiles,
        identical logits — as run-to-completion, just spread across
        iterations. Returns True when the suffix is exhausted."""
        st = self._pending_prefill[slot]
        suffix, cur = st["suffix"], st["cursor"]
        blk = suffix[cur:cur + self.prefill_chunk]
        fn = (self._extend_owned_fn if (st["owned"] or not st["first"])
              else self._extend_fn)
        with use_context(st["ctx"]):
            st["logits"], st["row"] = self._extend_block(
                fn, self.params, st["row"], blk[None], st["reused"] + cur)
        st["cursor"] = cur + int(blk.size)
        st["first"] = False
        self._m_interleaved.inc()
        return st["cursor"] >= suffix.size

    def _finish_interleaved_prefill(self, slot: int) -> None:
        """The back half of admission, once every chunk has fed:
        install the finished row, register fresh cache blocks, draft
        prefill, first-token sample, and all the slot bookkeeping the
        run-to-completion path does inline."""
        st = self._pending_prefill.pop(slot)
        rid, prompt, item = st["rid"], st["prompt"], st["item"]
        resume = st["resume"]
        with use_context(st["ctx"]):
            if self.paged is not None:
                from .models.paged_decode import install_row_paged

                self._tables[slot] = st["table"]
                nprefill = -(-prompt.size // self.paged[1])
                self.pool = install_row_paged(
                    self.pool, st["row"], self._tables[slot], nprefill,
                    start=st["j"], slot=slot)
                # a weight swap landed mid-pendency: the row mixes KV
                # from two versions — registering it under the NEW
                # version's chain keys would poison the cache
                if (self._kv_cache is not None
                        and int(self.weights_version) == st["wv0"]):
                    self._insert_full_blocks(slot, prompt,
                                             skip=st["j"], rid=rid)
            else:
                self.cache = self._install_fn(self.cache, st["row"],
                                              slot)
            if self.draft_config is not None:
                if self.paged is not None and self._kv_cache is not None:
                    self._install_draft_row(slot, prompt)
                else:
                    self._install_draft_row(slot, prompt,
                                            entry=st["entry"])
            t0 = self._sample_first(st["logits"], st["temp"],
                                    st["topk"], st["topp"],
                                    seed=self._seed.get(rid),
                                    fold=int(prompt.size))
            now = time.monotonic()
            if st["ctx"] is not None:
                # the prefill stage span, retroactive: begin-to-finish
                # wall time — the interleaved decode steps inside it
                # are exactly the graceful-TTFT trade the scheduler made
                dur = now - st["t0"]
                add_span("serving.prefill", time.time() - dur, dur,
                         stage="prefill", interleaved=True,
                         ctx=st["ctx"])
            self.recorder.record(
                rid, "prefill", prompt_tokens=int(prompt.size),
                prefix_tokens=st["reused"], interleaved=True,
                duration_s=round(now - self._admit_t[rid], 6))
        self._rid[slot] = rid
        self._outputs[rid] = [] if resume is None else resume["outputs"]
        self._slot_prompt[slot] = prompt
        self._slot_prior[slot] = len(self._outputs[rid])
        self._slot_tenant[slot] = item.tenant
        self._slot_priority[slot] = item.priority
        # the version the row was (mostly) computed under — a
        # mid-pendency swap leaves this != weights_version, which the
        # park/persist guards already treat as "do not cache"
        self._slot_wv[slot] = st["wv0"]
        self._pos[slot] = prompt.size - 1
        self._last[slot] = t0
        self._last_set[slot] = True
        self._budget[slot] = item.max_new
        self._temp[slot] = st["temp"]
        self._topk[slot] = st["topk"]
        self._topp[slot] = st["topp"]
        self._slot_seed[slot] = self._seed.get(rid, -1)
        if self.qos is not None:
            self._m_tenant_admitted.labels(
                tenant=self.qos.label(item.tenant)).inc()
        if resume is not None:
            self.recorder.record(
                rid, "resumed", tokens_so_far=len(self._outputs[rid]),
                remaining_tokens=int(item.max_new),
                preemptions=resume["preempts"])
        if self._record(slot, t0):
            self._fresh.setdefault(rid, []).append(t0)

    def _abort_pending_prefill(self, slot: int) -> Dict:
        """Drop a pending interleaved prefill (cancel/deadline): the
        slot's table restores so its private blocks free and its
        claimed hit chain releases, exactly like an active slot's
        teardown. Returns the pending state for the caller's
        request-level bookkeeping."""
        st = self._pending_prefill.pop(slot)
        if self.paged is not None:
            self._tables[slot] = st["table"]
        self._release_blocks(slot)
        self._clear_slot_meta(slot)
        return st

    def _enforce_pending_deadlines(self) -> None:
        """Retire pending interleaved prefills whose deadline passed —
        the mid-prefill mirror of :meth:`_enforce_active_deadlines`
        (``timed_out``: the request WAS admitted; a preempted-resumed
        one keeps its earlier tokens as the partial output)."""
        if not self._deadline or not self._pending_prefill:
            return
        now = self._clock()
        for slot in list(self._pending_prefill):
            rid = self._pending_prefill[slot]["rid"]
            if self._deadline.get(rid, now + 1) > now:
                continue
            st = self._abort_pending_prefill(slot)
            saved = st["resume"]
            self._done[rid] = ([] if saved is None
                               else saved["outputs"])
            self._timed_out.add(rid)
            self._m_timed_out.inc()
            self._deadline.pop(rid, None)
            self._seed.pop(rid, None)
            self._session.pop(rid, None)
            t_sub = self._submit_t.pop(rid, None)
            self._admit_t.pop(rid, None)
            self._fresh.pop(rid, None)
            a_p = self._accept.pop(rid, None)
            ctx = self._trace_ctx.pop(rid, None)
            if ctx is not None:
                default_span_store().finish(
                    ctx.trace_id,
                    latency_s=(None if t_sub is None
                               else time.monotonic() - t_sub),
                    violated=True)
            self._ttft_origin.pop(rid, None)
            self._last_tok_t.pop(rid, None)
            self._ttft_val.pop(rid, None)
            self.recorder.record(
                rid, "timed_out", stage="prefilling",
                tokens=len(self._done[rid]),
                **({} if a_p is None
                   else {"draft_accepted": a_p[0],
                         "draft_proposed": a_p[1]}))

    def _install_promotions(self, rid: int, slot: int, start: int,
                            promos: List) -> None:
        """Install tier-walk promotions into the slot's table entries
        ``start..start+len(promos)`` (private blocks _admit allocated):
        one batched host->device scatter, then per block — LOSSLESS
        payloads re-register under their chain key (device copy is
        exact content again; the next same-chain admission device-hits)
        while LOSSY ones stay private and taint the slot (parity rule:
        nothing computed over dequantized KV may ever enter the cache,
        park, or persist)."""
        from .models.paged_decode import install_pool_blocks

        cache = self._kv_cache
        with start_span("serving.kv_promote", stage="spill_promote",
                        blocks=len(promos)):
            bids = [int(self._tables[slot, start + i])
                    for i in range(len(promos))]
            self.pool = install_pool_blocks(
                self.pool, [blk.payload for blk, _ in promos], bids)
            tiers: Dict[str, int] = {}
            for (blk, src), bid in zip(promos, bids):
                tiers[src] = tiers.get(src, 0) + 1
                if self._m_spill_promote is not None:
                    self._m_spill_promote.labels(tier=src).inc()
                if blk.lossy:
                    self._slot_lossy[slot] = True
                elif cache.get(blk.key) is None:
                    # guard against a duplicate registered between walk
                    # and install (another admission prefilled the same
                    # chain): insert raises on duplicates — keep ours
                    # private then, mirroring _insert_full_blocks
                    entry = cache.insert(blk.key, bid, blk.tokens,
                                         acquire=True)
                    self._slot_blocks[slot].remove(bid)
                    self._slot_cached[slot].append(entry)
                if self._kv_spill is not None:
                    # device is canonical again: drop the host copy
                    # (re-eviction re-demotes); storage copies stay as
                    # the cross-replica durability layer
                    self._kv_spill.consumed(blk.key)
        self._promo_memo = None
        self.recorder.record(rid, "kv_promote", blocks=len(promos),
                             tiers=tiers)
        emit_event("serving.kv_promote", rid=rid, blocks=len(promos),
                   tiers=tiers)

    def _install_draft_row(self, slot: int, prompt: np.ndarray,
                           entry=...) -> None:
        """Prefill the DRAFT model's KV for ``prompt`` and install it
        into the slot's contiguous draft cache — the admission step
        speculative mode adds on every admission path: the classic
        prefill (which passes its already-matched ``entry``) and every
        path where the TARGET's prefill was (partly) served from
        elsewhere: a prefix-cache hit, a shipped disaggregated frame.
        Draft KV is proposer-private state — never cached, shipped, or
        paged — so it is recomputed here under the CURRENT draft
        params, which also means no admission can ever decode over
        draft state from an older draft version. A registered prefix's
        draft row still serves as the head (``entry`` is the
        ``_match_prefix`` result; ``...`` = look it up here)."""
        if entry is ...:
            entry = self._match_prefix(prompt)
        _, d_row = self._prefill_with_prefixes(
            prompt, self._extend_draft_fn, self._extend_draft_owned_fn,
            self._prefill_draft_fn, self.draft_params, entry, 3,
            self._fresh_draft_row_fn)
        self.draft_cache = self._install_draft_fn(self.draft_cache,
                                                  d_row, slot)

    def _sample_first(self, logits, temp: float, topk: int,
                      topp: float, seed: Optional[int] = None,
                      fold: int = 0) -> int:
        """Sample the admission-time first token from final-position
        prefill logits ``(1, vocab)``, as the last chunk's program
        returned them, in ONE program — the mirror of the step fns'
        ``_sample_tok`` (same filter order: temperature scales, then
        top-k/top-p on the scaled logits). A per-request ``seed``
        derives the key from ``fold_in(PRNGKey(seed), fold)`` where
        ``fold`` is the sampled token's absolute sequence position —
        the same rule the step fns use, so a resumed request's
        admission token re-samples exactly what the original decode
        emitted at that position. The settings go in as numpy scalars
        (plain transfers); ``int()`` of the token is the only wait."""
        with self._psec("elephas.loop.prefill.first_token"):
            if not temp > 0:
                return int(self._first_greedy_fn(logits))
            tok, key = self._first_sampled_fn(
                logits, np.float32(temp), np.int32(topk), np.float32(topp),
                np.int32(-1 if seed is None else seed), np.int32(fold),
                self._key)
            if seed is None:
                self._key = key
            return int(tok)

    def _install_prefilled(self, slot: int, prompt: np.ndarray,
                           pre: Tuple) -> int:
        """Install shipped KV blocks into ``slot`` and return the
        prefill worker's first token. The imported row is cast to the
        cache dtype, so an fp32-wire transfer installs cleanly into a
        bf16 decode cache."""
        from .models.paged_decode import (import_kv_blocks,
                                          install_row_paged)

        blocks, t0 = pre[0], pre[1]   # pre[2] (version stamp) is the
        # caller's/_admit's concern — checked before this install runs
        if isinstance(blocks, dict):
            row_np = blocks        # prebuilt off-loop by the receiver
        else:
            row_np = import_kv_blocks(blocks, int(prompt.size),
                                      self.max_len)
        row = jax.tree_util.tree_map(
            lambda a: jnp.asarray(a, self.config.dtype), row_np)
        with self._psec("elephas.loop.prefill.install"):
            if self.paged is not None:
                nprefill = -(-prompt.size // self.paged[1])
                self.pool = install_row_paged(
                    self.pool, row, self._tables[slot], nprefill,
                    slot=slot)
            else:
                self.cache = self._install_fn(self.cache, row, slot)
        if self.draft_config is not None:
            # disaggregated speculative decode: the shipped frame holds
            # TARGET KV only — prefill the draft locally BEFORE the
            # first draft round (draft KV never crosses the wire)
            self._install_draft_row(slot, prompt)
        return int(t0)

    def _record(self, slot: int, tok: int) -> bool:
        """Book one emitted token for the slot's request; retire the
        request when it hits eos or exhausts its budget. Returns whether
        the token is part of the output (eos is not — and is therefore
        never streamed either, keeping step() ≡ result())."""
        rid = self._rid[slot]
        if self.eos_id is not None and tok == self.eos_id:
            self._finish(slot)
            return False
        self._outputs[rid].append(tok)
        self._m_emitted.inc()
        # latency decomposition, off HOST state only (a flight-recorder
        # eviction must never cost a histogram sample): the request's
        # FIRST token stamps TTFT — against the front-end submit time
        # when one was passed through (disagg) — and every later token
        # stamps the gap since its predecessor. A resumed preempted
        # request keeps its stamp history (rid-keyed), so its
        # preemption gap lands in the inter-token tail, which is
        # exactly what its client experienced.
        now_tok = time.monotonic()
        last_tok = self._last_tok_t.get(rid)
        if last_tok is None:
            origin = self._ttft_origin.get(rid)
            if origin is None:
                origin = self._submit_t.get(rid)
            if origin is not None:
                ctx = self._trace_ctx.get(rid)
                ttft = now_tok - origin
                self._m_ttft.observe(
                    ttft, trace_id=None if ctx is None
                    else ctx.trace_id)
                self._ttft_val[rid] = ttft
        else:
            self._m_inter_token.observe(now_tok - last_tok)
        self._last_tok_t[rid] = now_tok
        n = len(self._outputs[rid])
        if n % self.TRACE_STEP_EVERY == 0:
            # sampled decode progress on the flight recorder: enough to
            # see a request advancing (or stalled) without one event
            # per token
            self.recorder.record(rid, "step", tokens=n,
                                 pos=int(self._pos[slot]))
        self._budget[slot] -= 1
        if self._budget[slot] <= 0:
            self._finish(slot)
        return True

    def _release_blocks(self, slot: int):
        # A row that retires in a way the host could not know a step
        # ahead (eos, cancel, deadline, preemption) may still be in the
        # step in flight (_step_plain), which writes ONE position into
        # a block released here. That is safe, by the device's order:
        # the step was given its tables when it was dispatched, so it
        # writes to a block the row owned then, at a decode position --
        # past the prompt, hence never in a shared (cached) block, and
        # past every full block _park_slot_blocks parks (those end at
        # _pos, the surplus write is at _pos + 1). Whatever the host
        # enqueues afterwards for the block's next owner (prefill
        # chunks, install_row_paged) or reads from it (demotion,
        # session save) runs behind that step on the device, and from
        # the next dispatch on this slot's table points at scratch.
        if self.paged is not None and (self._slot_blocks[slot]
                                       or self._slot_cached[slot]):
            self._free_block_ids.extend(self._slot_blocks[slot])
            self._slot_blocks[slot] = []
            # shared cached blocks: drop this slot's reference — the
            # last release PARKS the entry on the LRU reclaim list
            # (its KV stays resident for future hits) instead of
            # freeing the block
            for entry in self._slot_cached[slot]:
                self._kv_cache.release(entry)
            self._slot_cached[slot] = []
            self._tables[slot, :] = 0          # back to the scratch sink

    def _retire_slot(self, slot: int, outcome: str = "finished") -> int:
        """Slot-retirement bookkeeping shared by normal completion and
        deadline enforcement: tokens move to ``_done``, the slot (and
        paged blocks) frees, the deadline drops, latency is recorded,
        and the flight recorder gets the terminal ``outcome`` event with
        the per-stage durations. Callers bump their own outcome
        counter/marker."""
        rid = self._rid[slot]
        self._done[rid] = self._outputs.pop(rid)
        sid = self._session.pop(rid, None)
        if sid is not None:
            # persist the conversation's tail KV BEFORE the blocks
            # free: the next request for this session admits as a
            # chain hit, on this replica (parked blocks) or any other
            # sharing the store (persisted blocks). The save runs
            # under the retiring request's trace context — it is this
            # request's time — as a session_save stage span.
            if self._session_store is not None:
                with use_context(self._trace_ctx.get(rid)), \
                        start_span("serving.session_save",
                                   stage="session_save", session=sid):
                    self._persist_session(slot, rid, sid)
            else:
                self._persist_session(slot, rid, sid)
        self._rid[slot] = None
        self._release_blocks(slot)
        self._clear_slot_meta(slot)
        self._deadline.pop(rid, None)
        self._seed.pop(rid, None)
        now = time.monotonic()
        t_sub = self._submit_t.pop(rid, None)
        t_adm = self._admit_t.pop(rid, now)
        ctx = self._trace_ctx.get(rid)
        if t_sub is not None:
            self._latency_window.append((t_adm - t_sub, now - t_sub,
                                         len(self._done[rid])))
            self._m_queue_wait.observe(t_adm - t_sub)
            # exemplar-enabled: a p99 latency bucket names the trace
            # whose retained tree explains it
            self._m_request_latency.observe(
                now - t_sub,
                trace_id=None if ctx is None else ctx.trace_id)
        self._finish_trace(rid, ctx, outcome, now, t_sub)
        self._trace_ctx.pop(rid, None)
        extra = {}
        a_p = self._accept.pop(rid, None)
        if a_p is not None:
            # per-request speculative acceptance on the terminal event:
            # the counters answer "how is the engine doing", this
            # answers "how did THIS request's draft do"
            extra = {"draft_accepted": a_p[0], "draft_proposed": a_p[1]}
        # the latency decomposition's terminal stamp (+ host-dict
        # cleanup — these are rid-keyed and must not outlive retirement)
        ttft = self._ttft_val.pop(rid, None)
        self._last_tok_t.pop(rid, None)
        self._ttft_origin.pop(rid, None)
        if ttft is not None:
            extra["ttft_s"] = round(ttft, 6)
        self.recorder.record(
            rid, outcome, tokens=len(self._done[rid]),
            queue_wait_s=(None if t_sub is None
                          else round(t_adm - t_sub, 6)),
            total_s=(None if t_sub is None else round(now - t_sub, 6)),
            **extra)
        return rid

    def _finish_trace(self, rid: int, ctx, outcome: str, now: float,
                      t_sub: Optional[float]) -> None:
        """Materialize the request's retroactive spans — the
        ``serving.request`` root (the span id every live span under
        this request already parents to) and the decode stage (first
        token -> last token) — then hand the tree to the span store's
        tail-based retention decision. A request submitted without a
        trace context never touched the store and has nothing to
        finish."""
        if ctx is None:
            return
        origin = self._ttft_origin.get(rid, t_sub)
        if origin is None:
            origin = t_sub
        ttft = self._ttft_val.get(rid)
        if origin is not None:
            total = now - origin
            wall0 = time.time() - total
            root_attrs = {"rid": rid, "outcome": outcome}
            if ttft is not None:
                root_attrs["ttft_s"] = round(ttft, 6)
            add_span("serving.request", wall0, total, ctx=ctx,
                     span_id=ctx.span_id, parent_id=ctx.parent_id,
                     **root_attrs)
            last_tok = self._last_tok_t.get(rid)
            if ttft is not None and last_tok is not None:
                dec = last_tok - (origin + ttft)
                if dec > 0:
                    add_span("serving.decode", wall0 + ttft, dec,
                             stage="decode", ctx=ctx)
        default_span_store().finish(
            ctx.trace_id,
            latency_s=None if origin is None else now - origin,
            ttft_s=ttft,
            # a missed deadline IS the SLO violation the tail keeps
            violated=outcome in ("expired", "timed_out"),
            errored=outcome not in ("finished", "expired", "timed_out",
                                    "cancelled"))

    def _persist_session(self, slot: int, rid: int, sid: str) -> None:
        """Write the retiring slot's full KV blocks into the session
        store, keyed by the FINAL sequence's chain (prompt + emitted
        tokens, current ``weights_version``) — only keys the store
        doesn't already hold are exported off the pool. The blocks
        also park locally, so a same-replica follow-up resumes
        straight off the device cache without touching the store.
        Paged engines only (blocks export straight off the pool);
        best-effort — a failed persist costs the next turn a
        re-prefill, never this request."""
        store = self._session_store
        if (store is None or self.paged is None
                or self._kv_cache is None or self._slot_lossy[slot]
                or int(self._slot_wv[slot]) != int(self.weights_version)):
            return
        prompt = self._slot_prompt[slot]
        if prompt is None:
            return
        from .models.block_cache import chain_keys
        from .models.paged_decode import export_pool_blocks

        bs = self._kv_cache_bs
        # the sequence whose KV the slot holds: prompt + tokens emitted
        # since admission (a resumed request's prompt already folds in
        # its earlier output — the _preempt_slot convention), truncated
        # to the last PROCESSED position (the pending token's KV was
        # never written)
        seq = np.concatenate(
            [prompt,
             np.asarray(self._done[rid][int(self._slot_prior[slot]):],
                        np.int32)])
        seq_kv = seq[:int(self._pos[slot]) + 1]
        nfull = seq_kv.size // bs
        if nfull == 0:
            return
        keys = chain_keys(seq_kv[:nfull * bs], bs, self.weights_version)
        missing = [i for i, k in enumerate(keys) if not store.has(k)]
        if missing:
            payloads = export_pool_blocks(
                self.pool, [int(self._tables[slot, i]) for i in missing])
            nbytes = 0
            for i, payload in zip(missing, payloads):
                nbytes += store.put_block(keys[i], payload,
                                          (i + 1) * bs)
            if self._m_spill_bytes is not None and nbytes:
                self._m_spill_bytes.labels(tier="session").inc(nbytes)
        store.note_session(sid, nfull)
        self.recorder.record(rid, "session_saved", session=sid,
                             blocks=nfull, new_blocks=len(missing))
        # park the slot's private full blocks under the same chain:
        # free same-replica resume, reclaimable under pool pressure
        # (where eviction now demotes instead of discarding)
        self._park_slot_blocks(slot, seq_kv)

    def _finish(self, slot: int):
        self._retire_slot(slot, "finished")
        self._m_finished.inc()

    @property
    def stats(self) -> Dict[str, float]:
        """Serving counters since construction: ``steps`` (device round
        trips), ``tokens_emitted``, ``requests_finished``,
        ``tokens_per_step`` (the continuous-batching + speculation
        payoff), and in speculative mode ``draft_acceptance`` (accepted
        / proposed over active slots). Every counter is a read of this
        engine's :attr:`registry` minus this engine's construction-time
        baseline — zero for the default fresh registry, so stats and
        ``GET /metrics`` agree exactly; with a shared injected registry
        the scrape keeps process-lifetime totals while stats stays
        per-engine."""
        steps = int(self._since_init(self._m_steps))
        emitted = int(self._since_init(self._m_emitted))
        out = {"steps": steps,
               "tokens_emitted": emitted,
               "requests_finished": int(self._since_init(self._m_finished)),
               "tokens_per_step": (emitted / steps if steps else 0.0),
               # plain stepping keeps one step in flight: the steps
               # dispatched ahead of their predecessor's tokens, and
               # the row-steps dropped because the row retired meanwhile
               "steps_ahead": int(self._since_init(self._m_ahead)),
               "surplus_rows": int(self._since_init(self._m_surplus)),
               # overload-safety counters: admission rejections (429),
               # queued-deadline sheds (504), mid-decode timeouts, and
               # the live backlog the admission bounds act on
               "requests_shed": int(self._since_init(self._m_shed)),
               "requests_expired": int(self._since_init(self._m_expired)),
               "requests_timed_out": int(
                   self._since_init(self._m_timed_out)),
               "queue_depth": len(self._queue),
               "queued_tokens": self._queued_tokens,
               # live weight plane: what the engine serves NOW and how
               # many hot-swaps it has applied (gauge + counter on
               # /metrics; same numbers here so the surfaces agree)
               "weights_version": int(self.weights_version),
               "weight_swaps": int(self._since_init(
                   self._m_weight_swaps))}
        if self._prefixes or self._kv_cache is not None:
            out["prefix_hits"] = int(self._since_init(self._m_prefix_hits))
            out["prefix_tokens_reused"] = int(
                self._since_init(self._m_prefix_tokens))
        if self.paged is not None:
            out["blocks_total"] = self.paged[0] - 1
            # "free" = ALLOCATABLE: the raw free list plus parked cache
            # blocks (zero-ref, unpinned) admission pressure may
            # reclaim — the number the admission math actually acts on
            free = len(self._free_block_ids)
            if self._kv_cache is not None:
                free += self._kv_cache.reclaimable_count()
            out["blocks_free"] = free
        if self._kv_cache is not None:
            ks = self._kv_cache.stats()
            ks["block_size"] = self._kv_cache_bs
            out["kv_cache"] = ks
        if self._kv_spill is not None or self._session_store is not None:
            tiers: Dict[str, Dict] = {}
            if self._kv_spill is not None:
                tiers.update(self._kv_spill.stats())
            if self._session_store is not None:
                ss = self._session_store.stats()
                ss["hits"] = int(since_baseline(
                    self._spill_stat_base, self._m_session_hits))
                ss["misses"] = int(since_baseline(
                    self._spill_stat_base, self._m_session_misses))
                tiers["session"] = ss
            if self._m_spill_promote is not None:
                promotions = {
                    labels[0]: int(child.value)
                    for labels, child in
                    self._m_spill_promote.series().items()}
                if promotions:
                    tiers["promotions"] = promotions
            out["kv_tiers"] = tiers
        if self.qos is not None:
            out["preemptions"] = int(
                self._since_init(self._m_preemptions))
            # per-tenant story on one read: live queue numbers plus
            # the labeled counters (the metric IS the store)
            tenants: Dict[str, Dict] = {}
            for t in self._queue.live_tenants():
                label = self.qos.label(t)
                entry = tenants.setdefault(
                    label, {"queue_depth": 0, "queued_tokens": 0})
                entry["queue_depth"] += self._queue.tenant_depth(t)
                entry["queued_tokens"] += (
                    self._queue.tenant_queued_tokens(t))
            for metric, key in ((self._m_tenant_admitted, "admitted"),
                                (self._m_tenant_preempt, "preempted")):
                for labels, child in metric.series().items():
                    entry = tenants.setdefault(
                        labels[0], {"queue_depth": 0,
                                    "queued_tokens": 0})
                    entry[key] = int(child.value)
            for labels, child in self._m_tenant_shed.series().items():
                entry = tenants.setdefault(
                    labels[0], {"queue_depth": 0, "queued_tokens": 0})
                entry.setdefault("sheds", {})[labels[1]] = int(
                    child.value)
            out["tenants"] = tenants
        out["tier"] = self.tier
        # latency decomposition + loop profile: the same numbers the
        # scraped serving_ttft_seconds / serving_inter_token_seconds /
        # serving_loop_utilization series carry, on the JSON surface
        ttft_p50 = self._m_ttft.quantile(0.5)
        if ttft_p50 is not None:
            out["ttft_p50_s"] = round(ttft_p50, 6)
            out["ttft_p95_s"] = round(self._m_ttft.quantile(0.95), 6)
        itl_p50 = self._m_inter_token.quantile(0.5)
        if itl_p50 is not None:
            out["inter_token_p50_s"] = round(itl_p50, 6)
            out["inter_token_p99_s"] = round(
                self._m_inter_token.quantile(0.99), 6)
        if self.profiler is not None:
            out["loop"] = self.profiler.snapshot()
        if self._latency_window:
            totals = [t for _, t, _ in self._latency_window]
            waits = [w for w, _, _ in self._latency_window]
            # per-request decode rate: tokens delivered per second of a
            # request's wall time — with the acceptance rate, THE pair
            # of numbers that says what speculation is buying (surfaced
            # per replica on the fleet router's /stats)
            rates = [n / t for _, t, n in self._latency_window
                     if t > 0 and n > 0]
            if rates:
                out["request_tokens_per_s_p50"] = round(
                    float(np.quantile(rates, 0.5)), 3)
            out["latency_p50_s"] = round(float(np.quantile(totals, 0.5)),
                                         4)
            out["latency_p99_s"] = round(float(np.quantile(totals, 0.99)),
                                         4)
            out["queue_wait_mean_s"] = round(sum(waits) / len(waits), 4)
            # the tier-labeled headline, readable off /stats too: this
            # engine's queue-wait distribution tail (tier="decode" on a
            # disaggregated decode worker excludes prefill blocking;
            # the prefill tier's wait rides DisaggEngine's stats)
            out["queue_wait_p50_s"] = round(
                float(np.quantile(waits, 0.5)), 6)
            out["queue_wait_p99_s"] = round(
                float(np.quantile(waits, 0.99)), 6)
        if self.draft_config is not None:
            proposed = self._since_init(self._m_proposed)
            # None (not 0.0) before any proposal: an idle or freshly
            # scaled-up replica must not read as a zero-acceptance
            # (stale-draft) signal — the fleet prober's
            # draft_acceptance_min skips None
            out["draft_acceptance"] = (
                self._since_init(self._m_accepted) / proposed
                if proposed else None)
            out["speculative_rounds"] = int(
                self._since_init(self._m_spec_rounds))
            out["draft_weights_version"] = int(self.draft_weights_version)
            # operating depth vs ceiling: equal unless adaptive_gamma
            # has steered down (the gap IS the staleness signal)
            out["gamma"] = int(self._gamma_now)
            out["gamma_ceiling"] = int(self.gamma)
        # the one paged decode-attention path there is. The key stays
        # because both benchmark drivers log it (chipbench/drivers/
        # serve.py, serve_routed.py); it goes when they stop (ROADMAP D9)
        out["kernel"] = "gather"
        if self.interleave_prefill:
            out["prefill_chunks_interleaved"] = int(
                self._since_init(self._m_interleaved))
            out["pending_prefills"] = len(self._pending_prefill)
        return out

    def _since_init(self, metric) -> float:
        """This engine's share of a counter: current value minus the
        construction-time baseline (see ``_stat_base``)."""
        return since_baseline(self._stat_base, metric)

    # ------------------------------------------------------------- step
    @property
    def pending(self) -> int:
        """Work remaining: requests queued or in flight, plus emitted
        tokens not yet surfaced by step() — so the canonical
        ``while eng.pending: eng.step()`` loop always delivers a
        request's tokens even when it retires at admission time
        (``max_new_tokens=1``). A staged weight swap counts too: an
        idle server's engine loop must still pick it up within one
        idle-sleep, not wait for the next request."""
        with self._staged_lock:
            staged = (self._staged_params is not None
                      or self._staged_draft is not None)
        return (len(self._queue)
                + sum(r is not None for r in self._rid)
                + len(self._pending_prefill)
                + len(self._fresh)
                + (1 if staged else 0))

    def _psec(self, name: str):
        """The profiler section for the span ``name`` (a shared no-op
        context when profiling is off — the hot path pays one attribute
        read)."""
        prof = self.profiler
        return _NULL_SECTION if prof is None else prof.section(name)

    def _pmeta(self, **metadata) -> None:
        """Arguments for the trace span of the admission in progress
        (``elephas.loop.admit.request``); spans of one request share
        its ``rid``."""
        if self.profiler is not None:
            self.profiler.annotate("elephas.loop.admit.request",
                                   **metadata)

    def _steer_gamma(self, accepted: int, proposed: int) -> None:
        """One control-loop tick of the adaptive speculative depth.

        Feeds this round's pooled acceptance into an EWMA and, at most
        every :data:`GAMMA_ADJUST_EVERY` rounds, moves ``_gamma_now``
        ONE step toward the depth that acceptance currently pays for:
        with per-token acceptance rate ``a``, proposing beyond
        ``~a * ceiling`` drafts tokens the verifier will mostly throw
        away, while proposing fewer leaves accepted tokens on the
        table. The one-step/hysteresis pairing keeps the loop from
        chattering between adjacent depths on acceptance noise, yet an
        acceptance collapse (stale draft) still walks gamma from the
        ceiling to the floor in ``GAMMA_ADJUST_EVERY * (ceiling -
        floor)`` rounds — minutes before a draft_acceptance_min alert
        would fire. Token streams are unaffected by ANY depth schedule:
        greedy verification emits the exact argmax prefix at every
        depth, so steering changes only how much verify work each
        emitted token costs.
        """
        if not proposed:
            return
        acc = accepted / proposed
        self._accept_ewma = (acc if self._accept_ewma is None else
                             GAMMA_EWMA_ALPHA * acc
                             + (1.0 - GAMMA_EWMA_ALPHA)
                             * self._accept_ewma)
        self._rounds_since_adjust += 1
        if self._rounds_since_adjust < GAMMA_ADJUST_EVERY:
            return
        self._rounds_since_adjust = 0
        target = max(self.gamma_min,
                     min(self.gamma,
                         1 + int(self._accept_ewma * self.gamma + 0.5)))
        if target > self._gamma_now:
            self._gamma_now += 1
        elif target < self._gamma_now:
            self._gamma_now -= 1

    def step(self) -> Dict[int, List[int]]:
        """Advance every active slot — by one token (plain mode) or by
        ``1 + accepted`` tokens (speculative mode, up to ``gamma+1``);
        returns ``{request_id: [tokens]}`` emitted since the last call
        (admission-time first tokens ride along too). Finished requests
        retire and queued ones join automatically; expired queued
        requests are shed before prefill and over-deadline active slots
        are freed (their partial output finishes as a ``timeout``).

        Plain mode runs one step ahead (:meth:`_step_plain`): the call
        returns step k's tokens with step k+1 already queued on the
        device, so a request admitted now joins the step dispatched
        next, and a row that retires at eos, by ``cancel``, deadline or
        preemption leaves one surplus token in flight, which is dropped
        (``serving_decode_surplus_rows_total``)."""
        if self.profiler is not None:
            # iteration boundary: wall time since the LAST tick —
            # including the server loop's idle sleep — closes into the
            # rolling window, so utilization reads as a share of real
            # wall time, not of busy time
            self.profiler.tick()
        # slow steps (a prefill-compile-heavy one) also land on the
        # slow-span ring by name
        with self._psec("elephas.loop.step"), \
                span_if_counted("serving.step", self._m_steps,
                                histogram=self._m_step_latency):
            return self._step_impl()

    def _step_impl(self) -> Dict[int, List[int]]:
        # chaos site: 'error' = engine crash mid-serve (the HTTP loop
        # records it and /health turns red), 'delay' = a slow step
        fault_site("serving.step")
        self._admit()
        if self._pending_prefill:
            # feed this iteration's chunk budget BEFORE reading _fresh:
            # an admission completing here surfaces its first token in
            # this very step, matching run-to-completion semantics
            with self._psec("elephas.loop.prefill"):
                self._interleave_prefills()
        emitted = {rid: list(toks) for rid, toks in self._fresh.items()}
        self._fresh = {}
        active = np.asarray([r is not None for r in self._rid])
        if not active.any():
            return emitted
        # one choice, made from what the engine knows of itself
        if self.draft_config is None:
            return self._step_plain(active, emitted)
        return self._step_speculative(active, emitted)

    def _step_speculative(self, active: np.ndarray,
                          emitted: Dict[int, List[int]]
                          ) -> Dict[int, List[int]]:
        """One draft-propose / target-verify round: every active slot
        advances by its own ``1 + accepted`` tokens in one dispatch. The
        round is synchronous (a row's next position is what the round
        returns). It runs at the adaptive operating depth (``self.gamma``
        on fixed-gamma engines); verify slack was budgeted at the
        ceiling, so any depth up to it writes safely."""
        # inactive slots decode garbage at position 0 (static batch
        # shape); their writes are overwritten by the next admission's
        # prefill and masked until then
        pos = np.where(active, self._pos + 1, 0).astype(np.int32)
        self._m_steps.inc()
        g_now = self._gamma_now
        with self._psec("elephas.loop.decode.dispatch"):
            if self.paged is not None:
                (emit, acc, nxt, self.pool, self.draft_cache,
                 self._key) = self._spec_step_paged_for(g_now)(
                    self.params, self.draft_params, self.pool,
                    self.draft_cache, jnp.asarray(self._tables),
                    jnp.asarray(self._last), jnp.asarray(pos),
                    self._key)
            else:
                (emit, acc, nxt, self.cache, self.draft_cache,
                 self._key) = self._spec_step_for(g_now)(
                    self.params, self.draft_params, self.cache,
                    self.draft_cache, jnp.asarray(self._last),
                    jnp.asarray(pos), self._key)
        with self._psec("elephas.loop.decode.wait"):
            emit, acc, nxt = (np.asarray(emit), np.asarray(acc),
                              np.asarray(nxt))
        n_active = int(active.sum())
        n_accepted = int(acc[active].sum())
        self._m_accepted.inc(n_accepted)
        self._m_proposed.inc(g_now * n_active)
        self._m_spec_rounds.inc(n_active)
        if self.adaptive_gamma:
            self._steer_gamma(n_accepted, g_now * n_active)
        with self._psec("elephas.loop.emit"):
            for slot in np.nonzero(active)[0]:
                rid = self._rid[slot]
                # per-request acceptance for the flight recorder's
                # terminal event (engine counters above are pooled)
                a_p = self._accept.setdefault(rid, [0, 0])
                a_p[0] += int(acc[slot])
                a_p[1] += g_now
                self._pos[slot] += 1 + acc[slot]
                self._last[slot] = nxt[slot]
                for tok in emit[slot, :acc[slot] + 1]:
                    if self._rid[slot] is None:
                        break   # retired mid-chunk (eos or budget)
                    if self._record(slot, int(tok)):
                        emitted.setdefault(rid, []).append(int(tok))
        self._admit()
        return emitted

    def _step_plain(self, live: np.ndarray,
                    emitted: Dict[int, List[int]]
                    ) -> Dict[int, List[int]]:
        """One token for every live row, with the NEXT step dispatched
        before this one's tokens are read, so the host's round trip
        (read, record, hand over, admit, upload) runs beside the device
        instead of between its steps. Nothing in the next step's inputs
        needs the host: a riding row's token is the in-flight step's
        output (taken on the device, ``_ride``), its position one
        further; tables and sampling settings are fixed at admission;
        a budget that ends with the in-flight token is known now.

        What the host cannot know a step ahead -- eos, a cancel, a
        deadline, a preemption -- leaves one surplus token in flight:
        it is dropped and counted when its step is collected. Tokens
        are the synchronous loop's, row for row; a row admitted while a
        step is in flight joins the one dispatched next."""
        flight = self._ahead
        # rows whose pending token is the in-flight step's output
        riding = (live & ~self._last_set if flight is not None
                  else np.zeros_like(live))
        if flight is not None and not riding.any():
            self._abandon(flight)
            flight = None
        if flight is None:
            flight, riding = self._launch(live, riding, None), live
        assert not (riding & ~flight.rows).any(), (riding, flight.rows)
        self._ahead = self._launch(live, riding, flight)
        # a round trip is counted where it ends: one a call, as in the
        # synchronous loop (a step abandoned in flight is none)
        self._m_steps.inc()
        with self._psec("elephas.loop.decode.wait"):
            toks = self._split_counts(np.asarray(flight.home))
        self._m_surplus.inc(int((flight.rows & ~riding).sum()))
        with self._psec("elephas.loop.emit"):
            for slot in np.nonzero(riding)[0]:
                rid = self._rid[slot]
                self._pos[slot] += 1
                self._last[slot] = toks[slot]
                if self._record(slot, int(toks[slot])):
                    emitted.setdefault(rid, []).append(int(toks[slot]))
        if self._ahead is not None and not any(
                r is not None for r in self._rid):
            # the last live rows retired in a way the host could not
            # know a step ahead: nothing waits for the surplus step
            self._abandon(self._ahead)
        self._admit()
        return emitted

    def _launch(self, live: np.ndarray, riding: np.ndarray,
                flight: Optional[_Flight]) -> Optional[_Flight]:
        """Dispatch a decode step for the live rows: those ``riding``
        ``flight`` (the step in the air, None when there is none) one
        position further, but for the ones whose budget ends with its
        token, and the rest from the host's token. None when no row is
        left to step."""
        rows = live & (~riding | (self._budget > 1))
        if not rows.any():
            return None
        # left-out slots decode garbage at position 0 (static batch
        # shape) into the scratch block, or into their own contiguous
        # row, which the next admission's install overwrites
        pos = np.where(rows, self._pos + 1 + riding, 0).astype(np.int32)
        last = np.where(riding, -1, self._last).astype(np.int32)
        self._last_set[:] = False
        if flight is not None:
            self._m_ahead.inc()
        self._m_ssm_updates.inc(int(rows.sum()) * self._ssm_layers)
        key_in = self._key
        with self._psec("elephas.loop.decode.dispatch"):
            last = jnp.asarray(last)
            # copies: an upload reads its host array when the transfer
            # runs, not when it is enqueued (seen on the TPU), and the
            # next admission writes these arrays with this step in
            # flight; the speculative loop waits for its round first
            args = (last, last if flight is None else flight.tokens,
                    jnp.asarray(pos), jnp.asarray(self._temp.copy()),
                    jnp.asarray(self._topk.copy()),
                    jnp.asarray(self._topp.copy()),
                    jnp.asarray(self._slot_seed.copy()), self._key)
            if self.paged is not None:
                self._count_held(pos)
                # a row left out while it still holds its blocks (its
                # budget ends in flight) must not take the garbage
                # write: its first block may be shared (and a copy)
                tables = np.where(rows[:, None], self._tables, 0)
                home, tokens, self.pool, self._key = self._step_paged_fn(
                    self.params, self.pool, jnp.asarray(tables), *args)
            else:
                home, self.cache, self._key = self._step_fn(
                    self.params, self.cache, *args)
                tokens = home
            # start the tokens' way home behind the step
            home.copy_to_host_async()
        return _Flight(home, tokens, rows, key_in, self._key)

    def _abandon(self, flight: _Flight) -> None:
        """Drop a step in flight that no live row rides: every row of
        it is surplus, and nothing waits for it. The engine key goes
        back to what the step was given unless something drew from it
        since, so that unseeded rows sample as they would have without
        the abandoned step."""
        self._m_surplus.inc(int(flight.rows.sum()))
        if self._key is flight.key_out:
            self._key = flight.key_in
        if self._ahead is flight:
            self._ahead = None

    def _split_counts(self, toks: np.ndarray) -> np.ndarray:
        """A step's tokens, ``(max_slots,)``; what a paged step sent
        behind them (its routing counts, one per counter) goes to the
        counters."""
        if toks.shape[0] > self.max_slots:
            for metric, value in zip(self._m_moe, toks[self.max_slots:]):
                metric.inc(int(value))
            toks = toks[:self.max_slots]
        return toks

    def _held_width(self, pos: np.ndarray) -> Tuple[int, int]:
        """(blocks the rows at ``pos`` hold, the ladder width the step
        program picks for them): the host's copy of the device's
        arithmetic, for the counters."""
        from .models.paged_decode import held_block_count

        window = self.config.attention_window
        held = held_block_count(pos, self.paged[1], self._mb, window)
        need = (held if self._held_tile == 1 else held_block_count(
            pos, self.paged[1], self._mb, window, self._held_tile))
        assert need <= self._held_ladder[-1], (need, self._held_ladder)
        return held, next(w for w in self._held_ladder if w >= need)

    def _count_held(self, pos: np.ndarray):
        held, width = self._held_width(pos)
        self._m_blocks_held.inc(held)
        self._m_blocks_read.inc(width)
        self._m_steps_by_width[width].inc()

    def run(self, requests: Sequence[Sequence[int]],
            max_new_tokens: int) -> List[List[int]]:
        """Convenience batch driver: submit every request, step until
        drained, return outputs in request order."""
        rids = [self.submit(p, max_new_tokens) for p in requests]
        while self.pending:
            self.step()
        return [self.result(r) for r in rids]

    def result(self, rid: int) -> Optional[List[int]]:
        """Finished output for ``rid`` (None while still in flight).
        Pops the entry: a long-running server does not accumulate every
        finished request's tokens; call once per request."""
        info = self.result_info(rid)
        return None if info is None else info["tokens"]

    def result_info(self, rid: int) -> Optional[Dict]:
        """Like :meth:`result` but returns the full outcome:
        ``{"tokens": [...], "timeout": bool, "expired": bool}``.
        ``expired`` — the deadline passed while queued (no token was
        ever decoded; the request never reached prefill); ``timeout`` —
        the deadline cut the request short (set for BOTH cases; for a
        mid-decode cut ``tokens`` holds the partial output). One-shot,
        like :meth:`result`."""
        if rid not in self._done:
            return None
        tokens = self._done.pop(rid)
        expired = rid in self._expired
        timed_out = expired or rid in self._timed_out
        self._expired.discard(rid)
        self._timed_out.discard(rid)
        return {"tokens": tokens, "timeout": timed_out,
                "expired": expired}

    # ---------------------------------------------------------- tracing
    def request_trace(self, rid: int) -> Optional[Dict]:
        """The request's flight-recorder timeline ``{"id", "trace_id",
        "events": [...]}`` — every event stamped with the trace id
        captured at submit. Unlike :meth:`result` this is NOT one-shot
        (it answers "what happened", possibly long after the result was
        fetched), but it IS a bounded ring: old requests eventually
        evict. None for unknown/evicted ids."""
        return self.recorder.trace(rid)

    def recent_traces(self, limit: int = 32) -> List[Dict]:
        """The newest ``limit`` request timelines, oldest first (the
        ``GET /debug/trace/recent`` payload)."""
        return self.recorder.recent(limit)
