"""DisaggEngine: the decode-worker front of disaggregated serving.

Implements the engine API a
:class:`~elephas_tpu.serving_http.ServingServer` drives (``submit`` /
``step`` / ``pending`` / ``result_info`` / ``cancel`` / ``stats`` /
flight-recorder traces), but splits the request lifecycle across two
tiers:

1. ``submit`` hands the prompt to the least-backlogged live
   :class:`~.prefill.PrefillWorker` (prefill is compute-bound and
   bursty — it runs OFF the decode engine's loop).
2. The worker prefills, packs paged KV blocks, and ships them to this
   engine's :class:`~.wire.KVReceiver` (Q8 on the wire by default).
3. ``step`` — called by the server's engine loop, the single driver of
   the device program — first INSTALLS every received frame into the
   decode engine between decode steps
   (:meth:`~elephas_tpu.serving_engine.DecodeEngine.submit_prefilled`:
   the atomic slot install), then steps the decode batch.

The decode engine never runs a prefill, so its queue-wait series
(``serving_queue_wait_seconds{tier="decode"}``) is pure decode-stage
backlog — the p99 the colocated engine's prefill head-of-line blocking
inflates. Retry policy: a prefill job that fails (killed worker,
severed transfer, injected fault) re-dispatches to a sibling worker;
with no live worker it parks and retries as workers return. A replayed
frame (ack lost mid-kill) deduplicates by request id. One trace id
spans the whole path: the context captured at submit rides the job, the
wire's traceparent frame, and the decode engine's own recorder.
"""
import threading
import time
from collections import OrderedDict, deque
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..fleet.resilience import (MAX_STALE_KV_RETRIES as
                                _MAX_STALE_KV_RETRIES)
from ..fleet.resilience import (PREFILL_RETRY_BUDGET, CircuitBreaker)
from ..fleet.resilience import STALE_KV_RETRY_S as _STALE_KV_RETRY_S
from ..obs.context import current_context
from ..obs.events import FlightRecorder
from ..obs.events import emit as emit_event
from ..serving_engine import QueueFullError, validate_sampling_overrides
from .prefill import PrefillJob, PrefillWorker
from .wire import KVReceiver

__all__ = ["DisaggEngine"]


class DisaggEngine:
    """Decode worker + prefill-tier dispatcher behind one engine API.

    :param decode_engine: a
        :class:`~elephas_tpu.serving_engine.DecodeEngine` (construct it
        with ``tier="decode"`` so its queue-wait series lands on the
        decode-tier label); paged or contiguous both work, and so does
        SPECULATIVE mode — the shipped frames are the TARGET model's
        KV, which the engine installs before its first draft round
        (draft KV is recomputed locally at admission, never shipped).
        The PREFILL tier stays target-only either way: give its
        workers plain engines built from the same target params.
    :param prefill_workers: the prefill tier — shared freely between
        several DisaggEngines (that is the independent-scaling point).
    :param max_queue: bound on requests in the PREFILL stage (queued at
        workers, parked, or in transfer); breaching it sheds with
        :class:`~elephas_tpu.serving_engine.QueueFullError` (HTTP 429).
        The decode engine's own admission bounds still apply beneath.
    :param host, port: bind address for this engine's KV receiver.
    """

    def __init__(self, decode_engine, prefill_workers:
                 Sequence[PrefillWorker],
                 max_queue: Optional[int] = None,
                 host: str = "127.0.0.1", port: int = 0,
                 clock=time.monotonic):
        if not prefill_workers:
            raise ValueError("need at least one prefill worker")
        self.decode = decode_engine
        self.workers = list(prefill_workers)
        self.max_queue = None if max_queue is None else int(max_queue)
        self._clock = clock
        self.registry = reg = decode_engine.registry
        self.recorder = FlightRecorder()
        self._lock = threading.Lock()
        self._next_rid = 0
        # rid -> {"state": queued|imported|decoding|done, "job",
        #         "drid", "deadline", "retries", "tenant", "ptokens"}
        self._stage: Dict[int, Dict] = {}
        # tenant -> prompt tokens currently staged in the PREFILL tier
        # (queued at workers, parked, or in transfer): the decode
        # engine's per-tenant quota only sees its own queue, which a
        # disagg request enters at KV-install time — counting staged
        # tokens at submit is what makes the quota bite at THIS front
        # end instead of letting a tenant pile work into the prefill
        # stage bounded only by the global max_queue
        self._tenant_staged: Dict[str, int] = {}
        self._rid_of_drid: Dict[int, int] = {}
        # rid -> decode rid kept for trace merging AFTER the result is
        # fetched (the live _stage entry pops then); bounded like the
        # recorder ring it serves
        self._trace_drid: "OrderedDict[int, int]" = OrderedDict()
        self._imports: deque = deque()   # (meta, arrays, nbytes)
        self._parked: deque = deque()    # jobs with no live worker
        # (job, not_before) — version-mismatch rejections waiting out
        # the rollout window before re-dispatching (see the STALE_KV_*
        # constants at the gate)
        self._stale_retry: deque = deque()
        self._results: Dict[int, Dict] = {}   # disagg-terminal outcomes
        # per-prefill-worker circuit breaker: a worker failing jobs
        # repeatedly is skipped by dispatch while siblings exist, then
        # probed with one job after the cooldown
        self._prefill_circuits = CircuitBreaker(
            registry=reg, scope="prefill_worker", clock=clock)
        self._m_requests = reg.counter(
            "disagg_requests_total",
            "requests accepted by the disaggregated front end").labels()
        self._m_retries = reg.counter(
            "disagg_prefill_retries_total",
            "prefill jobs re-dispatched after a worker failure").labels()
        self._m_frames = reg.counter(
            "disagg_kv_frames_total",
            "KV frames received and installed, by codec",
            labels=("codec",))
        self._m_kv_bytes = reg.counter(
            "disagg_kv_bytes_total",
            "KV payload bytes received, by codec", labels=("codec",))
        import weakref

        ref = weakref.ref(self)
        reg.gauge("disagg_prefill_stage_depth",
                  "requests in the prefill stage (queued at workers, "
                  "parked, or in transfer)").set_function(
            lambda: float(e._prefill_stage_depth())
            if (e := ref()) is not None else 0.0)
        self.receiver = KVReceiver(self._on_frame, host=host,
                                   port=int(port)).start()

    # ----------------------------------------------------------- lifecycle
    def stop(self):
        """Close the KV receiver. The prefill workers are a shared tier
        owned by whoever built them (:class:`~.pool.DisaggPool`)."""
        self.receiver.stop()

    # -------------------------------------------------------------- submit
    def submit(self, prompt: Sequence[int], max_new_tokens: int,
               temperature: Optional[float] = None,
               top_k: Optional[int] = None,
               top_p: Optional[float] = None,
               admit: bool = True,
               deadline_ms: Optional[float] = None,
               tenant: Optional[str] = None,
               priority=None,
               seed: Optional[int] = None,
               resume_from: int = 0) -> int:
        """Queue a request; the prefill tier computes its KV state and
        this engine decodes it. Same argument semantics as
        :meth:`~elephas_tpu.serving_engine.DecodeEngine.submit`
        (``admit`` is accepted for interface parity; admission is
        always deferred to the engine loop here — prefill runs
        off-thread regardless). ``tenant``/``priority`` ride the wire
        meta to the decode engine, whose QoS policy (fair queueing,
        quotas, preemption) acts on them at KV-install admission.
        ``seed``/``resume_from`` compose the same way: the seed keys
        the prefill worker's first-token sample and every decode step
        (position-deterministic), and ``resume_from`` rides the wire
        meta to the decode engine's forced-prefix admission — so a
        dead decode worker's requests resume on a sibling exactly like
        the aggregated fleet's, shipped-frame path unchanged."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        # fail fast with the decode engine's own validation messages:
        # an inadmissible request must 400 at submit, not die on a
        # worker thread after shipping
        if prompt.size < 1:
            raise ValueError("prompt must hold at least one token")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        # the decode engine's own permanently-inadmissible rules, run
        # HERE so they 400 at submit — failing them at KV-install time
        # would raise inside the server's engine loop and read as
        # engine death (500s for everyone) instead of one bad request
        self.decode.check_admissible(int(prompt.size),
                                     int(max_new_tokens), prompt=prompt,
                                     tenant=tenant)
        validate_sampling_overrides(temperature, top_k, top_p)
        if (getattr(self.decode, "draft_config", None) is not None
                and (temperature is not None or top_k is not None
                     or top_p is not None)):
            # mirror the decode engine's own submit rule so the 400
            # lands HERE instead of at KV-install time inside the
            # engine loop (which would terminate the request late,
            # after a prefill and a wire round trip)
            raise ValueError("per-request sampling settings are not "
                             "supported in speculative mode")
        if deadline_ms is not None and not deadline_ms > 0:
            raise ValueError(f"deadline_ms must be > 0, got {deadline_ms}")
        # the decode engine's own seed/resume rules, enforced at THIS
        # submit so they 400 here instead of dying at KV-install time
        if seed is not None:
            if getattr(self.decode, "draft_config", None) is not None:
                raise ValueError("per-request seeds are not supported "
                                 "in speculative mode")
            seed = int(seed)
            if not 0 <= seed < 2 ** 31:
                raise ValueError(
                    f"seed must be in [0, 2**31), got {seed}")
        resume_from = int(resume_from)
        if resume_from and not 0 < resume_from < prompt.size:
            raise ValueError(
                f"resume_from ({resume_from}) must leave at least one "
                f"real prompt token (prompt has {prompt.size})")
        if tenant is not None:
            # the per-tenant quota 429, enforced at THIS front end's
            # submit exactly like the decode engine's own (the shared
            # validator — a quota-breached tenant sheds identically at
            # every surface, with the quota-aware backoff hint and the
            # same counter/event bookkeeping). The tenant's tokens
            # already staged in the prefill tier count against the
            # quota too — they haven't reached the decode queue yet,
            # but they are committed work the quota exists to bound.
            with self._lock:
                staged = self._tenant_staged.get(tenant, 0)
            try:
                self.decode.check_tenant_admissible(
                    tenant, int(prompt.size) + staged)
            except QueueFullError:
                self.decode.record_shed(tenant, "tenant_quota",
                                        staged_tokens=staged)
                raise
        with self._lock:
            if (self.max_queue is not None
                    and self._prefill_depth_locked() >= self.max_queue):
                emit_event("serving.shed", reason="disagg_max_queue",
                           queue_depth=self._prefill_depth_locked())
                raise QueueFullError(
                    f"prefill stage full: {self._prefill_depth_locked()}"
                    f" requests in flight (max_queue={self.max_queue})",
                    self.decode.retry_after_ms())
            rid = self._next_rid
            self._next_rid += 1
        ctx = current_context()
        deadline = (None if deadline_ms is None
                    else self._clock() + float(deadline_ms) / 1000.0)
        self.recorder.start(
            rid, trace_id=None if ctx is None else ctx.trace_id,
            prompt_tokens=int(prompt.size),
            max_new_tokens=int(max_new_tokens),
            **({} if tenant is None else {"tenant": str(tenant)}))
        job = PrefillJob(rid, prompt, max_new_tokens,
                         temperature=temperature, top_k=top_k,
                         top_p=top_p, deadline=deadline,
                         target=self.receiver.addr, ctx=ctx,
                         on_failed=self._job_failed, clock=self._clock,
                         tenant=tenant, priority=priority,
                         seed=seed, resume_from=resume_from)
        with self._lock:
            self._stage[rid] = {"state": "queued", "job": job,
                                "drid": None, "deadline": deadline,
                                "retries": 0, "tenant": tenant,
                                # the CLIENT-submit stamp, passed to
                                # submit_prefilled at KV install so
                                # the decode engine's TTFT includes
                                # the prefill tier's queue+ship time
                                "submit_mono": time.monotonic(),
                                "ptokens": (int(prompt.size)
                                            if tenant is not None
                                            else 0)}
            if tenant is not None:
                self._tenant_staged[tenant] = (
                    self._tenant_staged.get(tenant, 0)
                    + int(prompt.size))
        self._m_requests.inc()
        self._dispatch(job)
        return rid

    def add_worker(self, worker: PrefillWorker) -> None:
        """Register a prefill worker added after construction (the
        autoscaler growing the tier): the next dispatch — including
        parked jobs retried by the engine loop — considers it like any
        sibling. Idempotent."""
        with self._lock:
            if worker not in self.workers:
                self.workers.append(worker)

    # ------------------------------------------------------------ dispatch
    def _dispatch(self, job: PrefillJob) -> None:
        """Least-backlogged live worker, or park until one returns.
        Workers whose circuit is OPEN are skipped while an allowed
        sibling exists; with every circuit open the full candidate
        list is used (fail-static beats parking forever)."""
        candidates = sorted((w for w in self.workers if w.alive),
                            key=lambda w: w.backlog())
        allowed = [w for w in candidates
                   if self._prefill_circuits.allow(w.name)]
        if allowed:
            candidates = allowed
        for worker in candidates:
            try:
                worker.submit(job)
            except RuntimeError:
                continue          # died between the check and the submit
            self.recorder.record(job.rid, "prefill_dispatched",
                                 worker=worker.name,
                                 attempt=job.attempts + 1)
            job.attempts += 1
            return
        with self._lock:
            self._parked.append(job)
        self.recorder.record(job.rid, "prefill_parked",
                             reason="no live prefill workers")

    #: retry budget per request: a job failing this many times is
    #: systemically broken (every worker rejects it, or the receiver is
    #: unreachable) — it terminates with an ``expired`` outcome instead
    #: of recomputing the same prefill in a hot loop forever. Sourced
    #: from the fleet-wide defaults in :mod:`..fleet.resilience`.
    MAX_PREFILL_RETRIES = PREFILL_RETRY_BUDGET

    #: spacing for version-mismatch KV re-dispatches: the rollout
    #: window where the prefill tier lags the decode tier heals on the
    #: prefill subscribers' poll cadence (default 0.25 s), so retrying
    #: hotter than this only burns prefill compute and wire bytes on
    #: frames guaranteed to bounce
    STALE_KV_RETRY_S = _STALE_KV_RETRY_S
    #: spaced mismatch retries before a job falls through to the
    #: systemic :data:`MAX_PREFILL_RETRIES` path (>= 10 s of rollout
    #: window at the default spacing) — a prefill tier that never
    #: converges is a dead subscriber, not a rollout
    MAX_STALE_KV_RETRIES = _MAX_STALE_KV_RETRIES

    def _job_failed(self, job: PrefillJob, worker: str, error: str):
        """A worker failed a job (its own thread calls this): re-queue
        on a sibling — the client request is retried, never failed —
        up to :data:`MAX_PREFILL_RETRIES`, past which it terminates
        (an unbounded deterministic failure must not spin a core). A
        job whose propagated deadline has already passed terminates
        NOW — a retry could never answer in time, so re-prefilling is
        pure waste — with the expiry attributed to its stage."""
        with self._lock:
            st = self._stage.get(job.rid)
            if st is None or st["state"] != "queued":
                return            # cancelled, or a duplicate completion
            st["retries"] += 1
            exhausted = st["retries"] >= self.MAX_PREFILL_RETRIES
            past_deadline = (not exhausted
                             and job.deadline is not None
                             and self._clock() >= job.deadline)
            terminal = exhausted or past_deadline
            if terminal:
                st["state"] = "done"
                self._release_stage_locked(st)
                self._results[job.rid] = {
                    "tokens": [], "timeout": True, "expired": True,
                    "stage": ("prefill_retries_exhausted" if exhausted
                              else "prefill_retry_past_deadline"),
                    "error": error}
        self._prefill_circuits.record_failure(worker)
        self._m_retries.inc()
        emit_event("disagg.prefill_retried", rid=job.rid, worker=worker,
                   error=error, exhausted=terminal)
        self.recorder.record(job.rid, "prefill_retry", worker=worker,
                             error=error)
        if terminal:
            self.recorder.record(
                job.rid, "expired",
                stage=("prefill_retries_exhausted" if exhausted
                       else "prefill_retry_past_deadline"),
                error=error)
            return
        self._dispatch(job)

    # ------------------------------------------------------------ receiver
    def _on_frame(self, meta: Dict, arrays: List[np.ndarray],
                  nbytes: int) -> None:
        """KV frame delivery (receiver connection thread): enqueue for
        installation by the next ``step``. Duplicates (a replayed frame
        after a lost ack) and frames for cancelled rids drop here."""
        rid = int(meta.get("rid", -1))
        with self._lock:
            st = self._stage.get(rid)
            if st is None or st["state"] != "queued":
                return
        # reassemble the row HERE, on the receiver thread: the engine
        # loop then pays only the device install, not the host-side
        # block unpacking (which would serialize with decode steps)
        from ..models.paged_decode import import_kv_blocks

        row = import_kv_blocks(arrays, int(meta["prompt_tokens"]),
                               self.decode.max_len)
        with self._lock:
            st = self._stage.get(rid)
            if st is None or st["state"] != "queued":
                return
            st["state"] = "imported"
            self._imports.append((meta, row, int(nbytes)))
        self.recorder.record(
            rid, "kv_transfer", bytes=int(nbytes),
            worker=meta.get("worker"),
            prefill_s=meta.get("prefill_s"),
            prefill_queue_wait_s=meta.get("queue_wait_s"))

    # ---------------------------------------------------------------- step
    @property
    def pending(self) -> int:
        """Work the engine loop can advance by calling :meth:`step`:
        frames awaiting install, parked jobs awaiting a live worker
        (counted only while one IS alive — with the whole tier down a
        parked job cannot progress, and counting it would busy-spin the
        engine loop at 100% doing nothing), prefill-stage requests
        whose deadline needs enforcing, and the decode engine's own
        pending count. Requests merely WAITING on a prefill worker do
        not count — the loop idles (5 ms cadence) instead of spinning
        while the network does its thing."""
        any_alive = any(w.alive for w in self.workers)
        with self._lock:
            n = len(self._imports)
            if any_alive:
                n += len(self._parked)
            now = self._clock()
            # stale-KV re-dispatches count only once DUE — while they
            # wait out their delay the loop idles instead of spinning
            n += sum(1 for _, at in self._stale_retry if now >= at)
            n += sum(1 for st in self._stage.values()
                     if st["state"] == "queued"
                     and st["deadline"] is not None
                     and now >= st["deadline"])
        return n + self.decode.pending

    def step(self) -> Dict[int, List[int]]:
        """Install received KV frames into the decode engine (between
        decode steps — the atomic point), retry parked jobs, enforce
        prefill-stage deadlines, then advance the decode batch. Returns
        ``{rid: [tokens]}`` keyed by THIS engine's request ids."""
        # apply any staged live-weight swap BEFORE gating frames: the
        # version gate below must compare against the version this
        # step's installs will actually decode under, not one a
        # decode.step()-internal swap is about to replace
        self.decode.apply_staged_params()
        self._sweep_deadlines()
        self._retry_stale()
        self._retry_parked()
        self._install_imports()
        emitted = self.decode.step() if self.decode.pending else {}
        if not emitted:
            return {}
        with self._lock:
            return {self._rid_of_drid.get(drid, drid): toks
                    for drid, toks in emitted.items()}

    def _sweep_deadlines(self):
        """Expire prefill-stage requests whose deadline passed before
        their KV ever arrived — the disagg mirror of the decode
        engine's shed-while-queued (HTTP 504)."""
        now = self._clock()
        expired: List[int] = []
        with self._lock:
            for rid, st in self._stage.items():
                if (st["state"] == "queued"
                        and st["deadline"] is not None
                        and now >= st["deadline"]):
                    st["state"] = "done"
                    self._release_stage_locked(st)
                    if st["job"] is not None:
                        # a worker still holding this job skips it
                        st["job"].abandoned = True
                    self._results[rid] = {"tokens": [], "timeout": True,
                                          "expired": True}
                    expired.append(rid)
            for rid in expired:
                self._drop_parked_locked(rid)
        for rid in expired:
            self.recorder.record(rid, "expired", stage="prefill")

    def _drop_parked_locked(self, rid: int) -> None:
        self._parked = deque(j for j in self._parked if j.rid != rid)
        self._stale_retry = deque((j, t) for j, t in self._stale_retry
                                  if j.rid != rid)

    def _retry_stale(self):
        """Re-dispatch version-mismatch rejections whose delay elapsed
        (their jobs recompute the prefill — under the worker's by-then
        hopefully-swapped weights)."""
        now = self._clock()
        due: List = []
        with self._lock:
            keep: deque = deque()
            for job, at in self._stale_retry:
                if now >= at:
                    due.append(job)
                else:
                    keep.append((job, at))
            self._stale_retry = keep
        for job in due:
            if not job.abandoned:
                self._dispatch(job)

    def _retry_parked(self):
        with self._lock:
            jobs = list(self._parked)
            self._parked.clear()
        for job in jobs:
            self._dispatch(job)   # re-parks itself if still no worker

    def _install_imports(self):
        with self._lock:
            batch = list(self._imports)
            self._imports.clear()
        held: List = []   # tenant-quota-blocked frames: re-queued at
        # the end WITHOUT stopping the loop — one tenant at its quota
        # must never head-of-line-block other tenants' installs
        stop: Optional[int] = None
        for i, (meta, arrays, nbytes) in enumerate(batch):
            rid = int(meta["rid"])
            with self._lock:
                st = self._stage.get(rid)
                if st is None or st["state"] != "imported":
                    continue      # cancelled while in the import queue
                job = st["job"]
            # live-weight version gate: KV computed under one weight
            # version must not install into a decode batch running
            # another — decoding would be silently WRONG output, not an
            # error. A mismatch is NORMAL for the length of a rollout
            # (decode and prefill tiers' subscribers poll
            # independently), so rejected frames re-dispatch on a
            # DELAYED schedule with their own generous budget instead
            # of burning the systemic MAX_PREFILL_RETRIES in a hot
            # recompute/reject loop — only a tier that never converges
            # (a dead subscriber) falls through to the systemic path
            # and terminates the request.
            wire_v = meta.get("weights_version")
            engine_v = int(self.decode.weights_version)
            if wire_v is not None and int(wire_v) != engine_v:
                delayed = False
                with self._lock:
                    st2 = self._stage.get(rid)
                    if st2 is None or st2["state"] != "imported":
                        continue
                    st2["state"] = "queued"   # back to the prefill stage
                    st2["stale_retries"] = st2.get("stale_retries", 0) + 1
                    if (job is not None and st2["stale_retries"]
                            <= self.MAX_STALE_KV_RETRIES):
                        self._stale_retry.append(
                            (job, self._clock() + self.STALE_KV_RETRY_S))
                        delayed = True
                emit_event("disagg.kv_version_mismatch", rid=rid,
                           frame_version=int(wire_v),
                           engine_version=engine_v,
                           worker=meta.get("worker"))
                self.recorder.record(rid, "kv_rejected",
                                     reason="weights_version_mismatch",
                                     frame_version=int(wire_v),
                                     engine_version=engine_v)
                if not delayed and job is not None:
                    self._job_failed(
                        job, str(meta.get("worker", "?")),
                        f"KV weights_version {wire_v} != decode engine "
                        f"version {engine_v} after "
                        f"{self.MAX_STALE_KV_RETRIES} spaced retries")
                continue
            deadline = meta.get("deadline")
            remaining_ms = None
            if deadline is not None:
                remaining_ms = (float(deadline) - self._clock()) * 1000.0
                if remaining_ms <= 0:
                    with self._lock:
                        st["state"] = "done"
                        self._release_stage_locked(st)
                        self._results[rid] = {"tokens": [],
                                              "timeout": True,
                                              "expired": True,
                                              "stage": "kv_import"}
                    self.recorder.record(rid, "expired",
                                         stage="kv_import")
                    continue
            # capacity pre-check WITHOUT the engine's shed bookkeeping:
            # an internal install retry runs every step, and letting it
            # hit the submit bound would inc the shed counter and emit
            # a serving.shed event PER ATTEMPT — flooding the overload
            # signal this metric exists to diagnose. The QueueFullError
            # handler below stays as the backstop for bounds the peek
            # cannot see (injected sheds).
            if self.decode.would_shed(len(meta["prompt"])):
                # GLOBAL backpressure: no frame can install until the
                # next step shrinks the backlog — put the rest back
                stop = i
                break
            tenant = meta.get("tenant")
            if tenant is not None and self.decode.would_shed(
                    len(meta["prompt"]), tenant=tenant):
                # THIS tenant's quota: hold only its frame (it waits
                # for the tenant's own decode backlog to drain,
                # without the shed bookkeeping a bounced submit would
                # record) — frames from other tenants behind it keep
                # installing
                held.append((meta, arrays, nbytes))
                continue
            codec = str(meta.get("codec", "fp"))
            from ..obs.context import use_context

            try:
                with use_context(None if job is None else job.ctx):
                    # the version stamp rides through: the engine
                    # re-gates at the actual install (a swap staged
                    # between OUR gate above and that install falls
                    # back to a local prefill instead of decoding over
                    # mismatched KV)
                    drid = self.decode.submit_prefilled(
                        meta["prompt"], int(meta["max_new_tokens"]),
                        arrays, int(meta["first_token"]),
                        temperature=meta.get("temperature"),
                        top_k=meta.get("top_k"), top_p=meta.get("top_p"),
                        admit=False, deadline_ms=remaining_ms,
                        weights_version=(None if wire_v is None
                                         else int(wire_v)),
                        tenant=meta.get("tenant"),
                        priority=meta.get("priority"),
                        seed=meta.get("seed"),
                        resume_from=int(meta.get("resume_from") or 0),
                        # TTFT measures from the CLIENT's submit: the
                        # prefill tier's queue wait, compute, and KV
                        # ship all land inside it (queue-wait series
                        # stay pure decode-stage, by design)
                        submitted_at=st.get("submit_mono"))
            except QueueFullError:
                # the decode engine's own admission bound (or an
                # injected serving.submit shed): TRANSIENT — put this
                # frame AND the rest of the drained batch back (in
                # order) and retry after the next step shrinks the
                # backlog; raising here would kill the engine loop
                stop = i
                break
            except Exception as exc:  # noqa: BLE001 — an inadmissible
                # request that slipped past submit-time validation is
                # ONE bad request, never whole-server death: terminate
                # it with the error attached
                with self._lock:
                    st2 = self._stage.get(rid)
                    if st2 is not None:
                        st2["state"] = "done"
                        self._release_stage_locked(st2)
                        self._results[rid] = {
                            "tokens": [], "timeout": True,
                            "expired": True,
                            "error": f"{type(exc).__name__}: {exc}"}
                self.recorder.record(rid, "expired",
                                     stage="kv_install_rejected",
                                     error=str(exc))
                continue
            self._m_frames.labels(codec=codec).inc()
            self._m_kv_bytes.labels(codec=codec).inc(nbytes)
            # a delivered-and-installed frame is the worker's health
            # proof: closes its circuit (and resolves a half-open
            # probe claim) after a failure streak
            worker_name = meta.get("worker")
            if worker_name is not None:
                self._prefill_circuits.record_success(str(worker_name))
            with self._lock:
                if self._stage.get(rid) is not st:
                    # cancelled between the check above and the decode
                    # submit: don't decode for nobody
                    self.decode.cancel(drid)
                    continue
                st["state"] = "decoding"
                self._release_stage_locked(st)
                st["drid"] = drid
                st["job"] = None          # the KV blocks can free now
                self._rid_of_drid[drid] = rid
                self._trace_drid[rid] = drid
                while len(self._trace_drid) > self.recorder.max_requests:
                    self._trace_drid.popitem(last=False)
            self.recorder.record(rid, "decode_submitted", decode_rid=drid)
        if held or stop is not None:
            # re-queue in ORIGINAL order: held frames arrived before
            # the globally-stopped tail
            rest = batch[stop:] if stop is not None else []
            with self._lock:
                self._imports.extendleft(reversed(held + rest))

    def _release_stage_locked(self, st: Dict) -> None:
        """Return a request's prompt tokens to its tenant's staged
        budget — called (under the lock) at EVERY transition out of
        the prefill stage: decode handoff, expiry, retry exhaustion,
        cancel. Idempotent: the entry's ``ptokens`` zeroes on first
        release."""
        n, tenant = st.get("ptokens", 0), st.get("tenant")
        st["ptokens"] = 0
        if not n or tenant is None:
            return
        left = self._tenant_staged.get(tenant, 0) - n
        if left > 0:
            self._tenant_staged[tenant] = left
        else:
            self._tenant_staged.pop(tenant, None)

    def _prefill_depth_locked(self) -> int:
        return sum(1 for st in self._stage.values()
                   if st["state"] in ("queued", "imported"))

    def _prefill_stage_depth(self) -> int:
        with self._lock:
            return self._prefill_depth_locked()

    # -------------------------------------------------------------- results
    def result_info(self, rid: int) -> Optional[Dict]:
        with self._lock:
            if rid in self._results:
                self._stage.pop(rid, None)
                return self._results.pop(rid)
            st = self._stage.get(rid)
            drid = None if st is None else st["drid"]
        if drid is None:
            return None           # unknown or still in the prefill stage
        out = self.decode.result_info(drid)
        if out is not None:
            with self._lock:
                self._stage.pop(rid, None)
                self._rid_of_drid.pop(drid, None)
        return out

    def result(self, rid: int) -> Optional[List[int]]:
        info = self.result_info(rid)
        return None if info is None else info["tokens"]

    def cancel(self, rid: int) -> bool:
        with self._lock:
            st = self._stage.get(rid)
            if st is None:
                return False
            if st["state"] == "done":
                # already terminal in the prefill stage (expired /
                # retries exhausted): cancel of a finished request is
                # False by the engine convention — and must NOT fall
                # through to decode.cancel(drid=None). Drop the parked
                # result so an expire-then-cancel client cannot leak
                # an entry per request.
                self._stage.pop(rid, None)
                self._results.pop(rid, None)
                return False
            if st["state"] in ("queued", "imported"):
                # the prefill may still complete on its worker; the
                # late frame (or a replay) drops in _on_frame because
                # the state is no longer "queued" — and the worker
                # skips the job outright if it has not started yet
                if st["job"] is not None:
                    st["job"].abandoned = True
                st["state"] = "done"
                self._release_stage_locked(st)
                self._stage.pop(rid, None)
                self._results.pop(rid, None)
                self._drop_parked_locked(rid)
                self._imports = deque(
                    (m, a, b) for m, a, b in self._imports
                    if int(m.get("rid", -1)) != rid)
                self.recorder.record(rid, "cancelled", stage="prefill")
                return True
            drid = st["drid"]
        cancelled = self.decode.cancel(drid)
        if cancelled:
            with self._lock:
                self._stage.pop(rid, None)
                self._rid_of_drid.pop(drid, None)
        # cancel == False means the decode engine already FINISHED the
        # request (its result is fetchable) — keep the mapping so the
        # client's next poll still collects it, matching the engine's
        # cancel-after-completion contract
        return cancelled

    # -------------------------------------------------------- live weights
    @property
    def params(self):
        """The DECODE engine's live parameter pytree (what a
        :class:`~elephas_tpu.weightsync.WeightSubscriber`'s default
        converter derives its tree structure and dtypes from)."""
        return self.decode.params

    @property
    def weights_version(self) -> int:
        """The DECODE engine's live weight version (what `/stats` and
        the version gate on incoming KV frames read). The prefill
        tier's engines version independently — subscribe each worker's
        engine alongside this one and the KV version gate + retry path
        absorb the rollout window where they briefly differ."""
        return int(self.decode.weights_version)

    def stage_params(self, params, version: int, trace_id=None) -> None:
        """Stage new params for the decode engine (swap applied by the
        engine loop between decode steps, exactly as on a colocated
        engine). NOTE: this updates the decode half only — roll the
        prefill workers' engines through their own subscribers."""
        self.decode.stage_params(params, version, trace_id=trace_id)

    @property
    def draft_config(self):
        """The decode engine's draft config (None on non-speculative
        decode workers) — what a draft-channel
        :class:`~elephas_tpu.weightsync.WeightSubscriber` probes for."""
        return getattr(self.decode, "draft_config", None)

    @property
    def draft_params(self):
        """The decode engine's live DRAFT parameter pytree (speculative
        decode workers; the draft subscriber channel's treedef/dtype
        source)."""
        return getattr(self.decode, "draft_params", None)

    @property
    def draft_weights_version(self) -> int:
        return int(getattr(self.decode, "draft_weights_version", 0))

    def stage_draft_params(self, draft_params, version: int,
                           trace_id=None) -> None:
        """Stage new DRAFT params for a speculative decode engine (the
        draft freshness channel — applied at the same between-steps
        point as target swaps; a stale draft costs acceptance rate,
        never correctness, so no KV gate is needed on this channel)."""
        self.decode.stage_draft_params(draft_params, version,
                                       trace_id=trace_id)

    def apply_staged_params(self):
        """Delegates to the decode engine (the engine loop's step()
        already applies staged swaps; this exists so loop-less drivers
        can force one, mirroring DecodeEngine's surface)."""
        return self.decode.apply_staged_params()

    @property
    def gamma(self) -> Optional[int]:
        """The decode engine's CURRENT speculative depth (the adaptive
        controller's operating point; equal to the ctor gamma on
        fixed-depth engines, None on non-speculative decode workers).
        A fleet prober comparing this against ``gamma_ceiling`` in
        `/stats` sees draft staleness the moment the controller reacts,
        without waiting for the acceptance alert."""
        if getattr(self.decode, "draft_config", None) is None:
            return None
        return int(self.decode._gamma_now)

    # ---------------------------------------------------------------- misc
    def register_prefix(self, tokens) -> None:
        """Register a shared prompt prefix on EVERY prefill worker's
        engine (prefill is where prefix reuse pays). Call before
        traffic — registration does not synchronize with in-flight
        prefills."""
        for worker in self.workers:
            worker.engine.register_prefix(tokens)

    @property
    def stats(self) -> Dict:
        """The decode engine's stats (tier="decode" queue waits and all)
        plus the prefill tier's: per-worker backlog/waits, parked and
        in-transfer counts, retry totals, and KV wire accounting — the
        whole disaggregated story on one ``/stats`` read."""
        out = dict(self.decode.stats)
        out["tier"] = "disagg"
        with self._lock:
            queued = self._prefill_depth_locked()
            parked = len(self._parked)
            imports = len(self._imports)
        waits: List[float] = []
        for w in self.workers:
            sample = getattr(w, "wait_samples", None)
            waits.extend(sample() if sample is not None
                         else list(w.wait_window))
        tier: Dict = {
            "stage_depth": queued,
            "parked": parked,
            "imports_pending": imports,
            "workers_alive": sum(1 for w in self.workers if w.alive),
            "workers": [w.stats() for w in self.workers],
            "prefill_retries": int(self._m_retries.value),
        }
        if waits:
            from ..obs.metrics import percentile

            tier["queue_wait_p50_s"] = round(percentile(waits, 0.5), 6)
            tier["queue_wait_p99_s"] = round(percentile(waits, 0.99), 6)
        out["prefill_tier"] = tier
        out["kv_wire"] = {
            "frames": {c: int(child.value) for c, child in
                       self._frames_by_codec().items()},
            "bytes": {c: int(child.value) for c, child in
                      self._bytes_by_codec().items()},
        }
        return out

    def _frames_by_codec(self):
        return {labels[0]: child
                for labels, child in self._m_frames.series().items()}

    def _bytes_by_codec(self):
        return {labels[0]: child
                for labels, child in self._m_kv_bytes.series().items()}

    # ---------------------------------------------------------- tracing
    def request_trace(self, rid: int) -> Optional[Dict]:
        """The request's merged timeline: this engine's events (queued /
        dispatched / kv_transfer / decode_submitted) interleaved with
        the decode engine's (admitted / kv_install / steps / terminal),
        ordered by wall clock — the KV-transfer stage visible in ONE
        flight-recorder read."""
        own = self.recorder.trace(rid)
        if own is None:
            return None
        with self._lock:
            drid = self._trace_drid.get(rid)
        if drid is not None:
            dec = self.decode.request_trace(drid)
            if dec is not None:
                merged = own["events"] + [
                    dict(e, decode_rid=drid) for e in dec["events"]]
                merged.sort(key=lambda e: e.get("at", 0.0))
                own["events"] = merged
        return own

    def recent_traces(self, limit: int = 32) -> List[Dict]:
        out = []
        for t in self.recorder.recent(limit):
            merged = self.request_trace(t["id"])
            out.append(merged if merged is not None else t)
        return out
