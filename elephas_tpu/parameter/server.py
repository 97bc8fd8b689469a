"""Parameter servers: HTTP and raw-TCP weight services.

Async/hogwild training exchanges weight deltas through a parameter server
process on the coordinator host (the reference's Flask/raw-socket pair,
``elephas/parameter/server.py:42-233``). Differences here, by design:

- Payloads are typed ETPU tensor frames (:mod:`..utils.tensor_codec`),
  never pickle — nothing executable crosses the wire.
- The HTTP server is a stdlib ``ThreadingHTTPServer`` in a daemon thread
  (no Flask dependency, no fork: forking a process with a live JAX runtime
  is unsafe, and the weight state is plain numpy anyway).
- Locking policy is the reference's exactly: a writer-priority RWLock
  serializes pulls/pushes in ``asynchronous`` mode and is bypassed in
  ``hogwild`` mode (lock-free HOGWILD!-style updates).

Both servers hold the authoritative weights as a flat numpy list — the
wire currency — so no JAX device state lives on the serving threads.

The hot path is copy-frugal: pushes decode delta frames as zero-copy
views of the receive buffer (``apply_delta`` only reads them), and pulls
are served from a **cached encoded snapshot** — the wire payload is
rebuilt at most once per weight version (every applied delta bumps the
version) and repeated ``get_parameters`` traffic costs one ``sendall``
of the same immutable buffer, zero encode work (``encoded_weights``;
rebuilds are counted in ``encode_count``).

## Sharding the parameter plane

One server caps async scaling at one process's RPC throughput. With
``ps_shards=N`` (:class:`~elephas_tpu.tpu_model.TPUModel`) the flat
weight list is partitioned across N server instances on consecutive
ports ``port .. port+N-1`` by greedy byte-size bin-packing — tensors
visited largest-first, each placed on the lightest bin, ties broken by
index so every process derives the identical
:class:`~elephas_tpu.parameter.sharding.ShardPlan` without exchanging
it. The matching
:class:`~elephas_tpu.parameter.sharding.ShardedParameterClient` fans
pulls/pushes out over per-shard persistent connections on parallel
threads and reassembles results in plan order, over either transport.

Consistency: each shard applies a worker's delta atomically under its
own lock, and a sharded push is a **two-phase cross-shard commit** by
default: every shard first STAGES the delta (``prepare``, validated
but not applied), and only when every shard has staged does the client
fan out ``commit`` — any prepare failure aborts all shards, so a push
either lands everywhere or nowhere (``ps.commit_aborted`` event +
``ps_commit_aborts_total``; the pre-2PC torn-push failure mode —
``ps.sharded_push_torn`` — cannot occur on this path). Each committed
push advances a monotonically increasing **generation id** (count of
committed updates, paired with an order-independent digest of their
ids), returned to the pusher alongside the per-shard version tuple;
equal (generation, digest) across shards certifies that every shard
holds the same SET of committed updates, which is what live-weight
subscribers check before staging a pull (generation coherence — see
the live-weights guide). A concurrent pull may still observe shard A
before a given push and shard B after it (the generation pair differs
and the puller re-pulls the lagging shard), and the legacy
single-phase path (``two_phase=False``, or sub-clients without the
prepare extension) keeps the documented torn-push trade, now surfaced
as a typed :class:`~elephas_tpu.parameter.sharding.TornPushError`
carrying per-shard outcomes. Supervision is per shard: a dead shard
promotes its hot standby when one is configured (zero applied-update
loss), and is otherwise rebuilt from its own snapshot on its own port
while the survivors keep serving (see the fault-tolerance guide).

## Hot-standby replication and failover

With ``ps_standby=True`` each shard runs a WARM STANDBY server
(ports ``port+N .. port+2N-1``) that subscribes to its primary's
applied-delta stream: every delta the primary applies is forwarded —
synchronously when the standby is healthy, else parked on a bounded
catch-up backlog (``ps_replication_lag_updates``) — and deduplicated
by the same 32-byte update ids client retries use, so the standby's
weights, generation, and update counters track the primary's exactly.
On primary death, supervision PROMOTES the standby onto the primary's
port instead of restarting from a snapshot: no applied update is lost,
in-flight two-phase pushes re-prepare against the promoted server, and
a fresh standby is re-armed behind the new primary. Every promotion
bumps the shard's **fencing epoch**; replication traffic carrying an
older epoch (a zombie primary that was declared dead but kept running)
is rejected, so late writes from the old generation of the shard can
never corrupt the new one. Snapshot-restart remains the fallback when
no (healthy) standby exists — it loses post-snapshot deltas, so the
restarted shard's generation marker is realigned to the surviving
shards' (``ps.generation_realigned``) to keep the plane pullable; the
loss is the documented pre-standby behavior.

## Live weight subscribers

Every applied delta (and every restore) bumps the server's
``weights_version``, exposed as a cheap no-payload poll on both
transports (``GET /version``; socket opcode ``'v'``) plus a versioned
pull (``X-Weights-Version`` on ``/parameters``; socket opcode ``'G'``)
whose (version, payload) pair is read consistently under one lock.
Serving engines subscribe through
:class:`~elephas_tpu.weightsync.WeightSubscriber` and hot-swap new
versions between decode steps — the train-to-serve loop in the
live-weights guide. Repeated pulls of one version ride the cached
encoded snapshot: N subscribers cost N ``sendall``s and ONE encode.

## Pipelined async push

``ps_pipeline=True`` double-buffers the reference-parity worker loops:
the delta push for batch/epoch *k* runs on a background thread over its
own connection while *k+1* computes. At most ONE push is in flight —
a pull can miss at most the single racing push (staleness bounded at
1) — and a push error is parked and re-raised at the worker's next
sync point, so supervisor crash/restart semantics are unchanged. The
overlapped device-resident schedule (``async_overlap=True``) already
pipelines through its communicator thread and subsumes this flag.
"""
import abc
import hashlib
import logging
import selectors
import socket
import struct
import threading
import time
import uuid
from collections import OrderedDict
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Callable, Dict, List, Optional

import numpy as np

_LOG = logging.getLogger(__name__)

from ..obs.context import (parse_traceparent, reset_context, set_context,
                           use_context)
from ..obs.events import emit as emit_event
from ..obs.metrics import default_registry, observe_scrape
from ..utils.faults import fault_site
from ..utils.functional_utils import subtract_params
from ..utils.rwlock import RWLock
from ..utils.sockets import (PS_ABORT_OPCODE, PS_COMMIT_OPCODE,
                             PS_GEN_POLL_OPCODE, PS_GEN_PULL_OPCODE,
                             PS_ID_BYTES, PS_PREPARE_OPCODE,
                             PS_REPLICATE_OPCODE, TRACE_OPCODE,
                             determine_master, receive_frame,
                             receive_traceparent, recv_exact, recv_u64,
                             send_payload)
from ..utils.delta_compression import dequantize_delta
from ..utils.tensor_codec import KIND_DELTA_Q8, decode, encode_weights
from .client import FencedEpochError, UnknownTxnError


def _id_digest(update_id: str) -> int:
    """8-byte blake2b of an update id as an int. Per-server generation
    digests SUM these mod 2**64 — addition commutes, so two shards that
    applied the same SET of updates in different interleavings still
    agree, and a missing/extra update disagrees with overwhelming
    probability."""
    return int.from_bytes(
        hashlib.blake2b(update_id.encode("ascii", "replace"),
                        digest_size=8).digest(), "big")


_DIGEST_MOD = 1 << 64


def _decode_delta(payload: bytes):
    """Decode a delta push, dequantizing int8-compressed frames.

    Zero-copy decode: ``apply_delta`` only READS the delta
    (``subtract_params`` allocates the new weights) and the request body
    is this call's own buffer, so the views never outlive their frame.
    """
    arrays, kind = decode(payload, copy=False)
    if kind == KIND_DELTA_Q8:
        return dequantize_delta(arrays)
    return arrays


class BaseParameterServer(abc.ABC):
    """Holds master weights; serves pulls and applies pushed deltas."""

    def __init__(self, model: Dict[str, Any], port: int, mode: str, **kwargs):
        self.port = port
        self.mode = mode
        self.custom_objects = kwargs.get("custom_objects")
        #: which shard of a sharded parameter plane this server holds
        #: ("0" for the unsharded default) — a metric label, so one
        #: scrape splits RPC traffic per shard
        self.shard = str(kwargs.get("shard", 0))
        # ``model`` is the model_to_dict payload; the server only needs the
        # weight list (the architecture rides along for parity/save paths).
        self.model_config = model.get("model")
        self.weights: List[np.ndarray] = [np.asarray(w, dtype=np.float32)
                                          for w in model["weights"]]
        self.lock = RWLock()
        # cached encoded snapshot of the weights: get-heavy sync traffic
        # serves sendall(cached_bytes) with ZERO encode work. The cache
        # is invalidated by bumping _weights_version on every mutation
        # and rebuilt lazily, at most once per version; encode_count
        # counts actual rebuilds (the no-re-encode test hook).
        self._weights_version = 0
        self._enc_lock = threading.Lock()
        self._enc_cache: Optional[tuple] = None  # (version, payload)
        self.encode_count = 0
        #: applied-update counter — cheap liveness/progress signal surfaced
        #: through the health endpoints (own lock: hogwild bypasses the
        #: weight RWLock, and a bare += would lose increments across threads)
        self.num_updates = 0
        self._counter_lock = threading.Lock()
        # idempotency window: update ids already applied, so a client retry
        # whose first attempt's ack was lost cannot double-apply a delta.
        # Time-based retention (>= the client's worst-case retry horizon)
        # with a generous count cap — a busy cluster must not evict an id
        # before its retry can arrive.
        self._seen_ids: "OrderedDict[str, float]" = OrderedDict()
        self._seen_lock = threading.Lock()
        self._seen_ttl = 600.0
        self._seen_cap = 1 << 17
        # ids whose apply is still in flight: a duplicate resend arriving
        # while the original is mid-apply (the lost-ack retry scenario)
        # waits on the latch instead of racing past the _seen_ids check
        # and double-applying the delta
        self._in_flight: Dict[str, threading.Event] = {}
        # -------- fault-tolerant-plane state (2PC / replication) --------
        #: generation id: committed/applied update count. Monotonic on a
        #: live server and carried across standby promotion; equal
        #: across shards exactly when every push landed everywhere.
        self.generation = 0
        #: order-independent companion to ``generation``: sum (mod 2^64)
        #: of the applied update ids' 8-byte digests. Two shards whose
        #: (generation, digest) pairs match hold the same SET of
        #: updates, regardless of apply interleaving.
        self.gen_digest = 0
        #: fencing epoch: bumped by every standby promotion. Replication
        #: traffic from an older epoch (a zombie primary) is rejected.
        self.epoch = int(kwargs.get("epoch", 0))
        # two-phase-commit staging area: txn id -> (delta copies,
        # staged-at monotonic time). Prepared deltas that never commit
        # (a dead coordinator) are swept after STAGE_TTL.
        self._staged: "OrderedDict[str, tuple]" = OrderedDict()
        self._staged_lock = threading.Lock()
        #: applied-delta hook — a :class:`~elephas_tpu.parameter.
        #: replication.ShardReplicator` attaches here; called as
        #: ``hook(update_id, delta)`` AFTER a successful apply, outside
        #: the weight lock, while the delta arrays are still valid
        #: (the hook must copy or ship before returning). Exceptions
        #: are the hook's problem — they must never fail the ack.
        self._applied_hook: Optional[Callable] = None
        # parameter-plane RPC metrics live in the PROCESS default
        # registry (labeled by transport/op): every PS in the process
        # pools into one scrape surface, exposed via the HTTP server's
        # /metrics route
        reg = default_registry()
        self._m_rpc_latency = reg.histogram(
            "ps_rpc_latency_seconds",
            "parameter-server RPC service time (receive through reply)",
            labels=("transport", "op", "shard"))
        self._m_rpc_total = reg.counter(
            "ps_rpc_total", "parameter-server RPCs served",
            labels=("transport", "op", "status", "shard"))
        self._m_rpc_bytes = reg.counter(
            "ps_rpc_bytes_total",
            "tensor payload bytes moved by PS RPCs",
            labels=("transport", "direction", "shard"))
        self._m_http_requests = reg.counter(
            "ps_http_requests_total",
            "PS HTTP requests by method, path, and status "
            "(the log_message replacement)",
            labels=("method", "path", "status"))

    # ---------------------------------------------------------- metrics
    def _obs_rpc(self, transport: str, op: str, status: str, t0: float,
                 bytes_in: int = 0, bytes_out: int = 0):
        """Record one served RPC (best-effort: dropped connections that
        never reach a reply are not counted as RPCs). Metrics stay
        id-free (an id label would be unbounded cardinality); the
        per-request identity goes to the structured event log instead —
        a ``ps.rpc`` event stamped with the caller's trace id (None for
        context-less callers), joinable against the serving side's
        flight-recorder timelines."""
        duration = time.perf_counter() - t0
        self._m_rpc_latency.labels(transport=transport, op=op,
                                   shard=self.shard).observe(duration)
        self._m_rpc_total.labels(transport=transport, op=op,
                                 status=status, shard=self.shard).inc()
        # the event carries the SAME duration the histogram observed,
        # so joining the two surfaces for one RPC is exact
        emit_event("ps.rpc", transport=transport, op=op, status=status,
                   duration_s=round(duration, 6))
        if bytes_in:
            self._m_rpc_bytes.labels(transport=transport, direction="in",
                                     shard=self.shard).inc(bytes_in)
        if bytes_out:
            self._m_rpc_bytes.labels(transport=transport, direction="out",
                                     shard=self.shard).inc(bytes_out)

    def get_weights(self) -> List[np.ndarray]:
        fault_site("ps.get_weights")
        if self.mode == "asynchronous":
            self.lock.acquire_read()
        try:
            return [w.copy() for w in self.weights]
        finally:
            if self.mode == "asynchronous":
                self.lock.release()

    @property
    def weights_version(self) -> int:
        """The served weights' version counter: bumped exactly once per
        applied delta and once per :meth:`restore`. The cheap
        "anything changed since v?" poll both transports expose — a
        subscriber compares for INEQUALITY (a restarted-from-snapshot
        server resumes past its snapshot's version, which can sit below
        a version the dead server reached after snapshotting), and only
        re-downloads when the answer moved."""
        with self._counter_lock:
            return self._weights_version

    def encoded_weights(self) -> bytes:
        """The current weights as one wire-encoded ETPU payload, served
        from a cached snapshot: invalidated when a delta lands (the
        version counter moves), rebuilt at most once per version —
        get-heavy sync traffic costs ``sendall(cached_bytes)`` and zero
        encode work. Concurrent getters serialize on the rebuild and
        then share the same immutable payload."""
        return self.encoded_weights_versioned()[1]

    def encoded_weights_versioned(self):
        """``(version, payload)`` — the cached encoded snapshot plus
        the version it encodes, read under one lock so the pair is
        CONSISTENT (a live-weight subscriber stamps its pulled params
        with this version; a racing delta simply shows up as the next
        poll's version change)."""
        gen, digest, version, payload = self.encoded_weights_generational()
        return version, payload

    def encoded_weights_generational(self):
        """``(generation, digest, version, payload)`` — the generation
        pair rides the same consistent read the versioned pull uses, so
        a cross-shard coherence check compares states that actually
        correspond to the served payloads."""
        fault_site("ps.get_weights")
        with self._enc_lock:
            if self.mode == "asynchronous":
                self.lock.acquire_read()
            try:
                with self._counter_lock:
                    version = self._weights_version
                    gen = self.generation
                    digest = self.gen_digest
                if (self._enc_cache is not None
                        and self._enc_cache[0] == version):
                    return gen, digest, version, self._enc_cache[1]
                # the encoder's bytearray is served as-is (bytes-like for
                # sendall/HTTP): nothing mutates it after this point —
                # invalidation REPLACES the cache tuple — and a bytes()
                # round would re-copy the whole payload per rebuild
                payload = encode_weights(self.weights)
                self.encode_count += 1
            finally:
                if self.mode == "asynchronous":
                    self.lock.release()
            self._enc_cache = (version, payload)
            return gen, digest, version, payload

    def snapshot(self) -> Dict[str, Any]:
        """Restartable server state: weights, the applied-update counter,
        and the idempotency window. A supervisor snapshots on every
        healthy probe so a crashed server can be rebuilt on the same
        port via :meth:`restore` — client retries after a lost ack stay
        deduplicated across the restart.

        The idempotency window is read BEFORE the weights: a delta that
        lands between the two reads is then present in the weights but
        absent from ``seen_ids``, so a post-restore resend re-applies it
        (at-least-once, a benign duplicate gradient). The reverse order
        would record the id without its weights — a resend after the
        restore would be deduplicated and the acked update silently
        lost."""
        with self._seen_lock:
            seen = list(self._seen_ids.items())
        with self._counter_lock:
            num_updates = self.num_updates
            weights_version = self._weights_version
            generation = self.generation
            gen_digest = self.gen_digest
            epoch = self.epoch
        weights = self.get_weights()  # honors the mode's locking policy
        return {"weights": weights, "num_updates": num_updates,
                "weights_version": weights_version, "seen_ids": seen,
                "generation": generation, "gen_digest": gen_digest,
                "epoch": epoch}

    #: version jump applied by :meth:`restore` when the snapshot's
    #: version is AT OR ABOVE this server's own — the restart-recovery
    #: shape, where a fresh process (counter 0) adopts a dead
    #: predecessor's snapshot. The predecessor's counter kept moving
    #: after the snapshot was taken (deltas this process never saw), so
    #: ``snapshot_version + 1`` could land exactly on — or later climb
    #: through — a version a subscriber already pulled from the dead
    #: server, silently hiding the restart behind an aliased number.
    #: Jumping far past any count of post-snapshot deltas a supervision
    #: window (snapshots ride every healthy probe, seconds apart) could
    #: physically apply keeps the restored trajectory disjoint from the
    #: dead one's. An in-place restore on a LIVE server (own counter >
    #: snapshot's) needs no jump: its own counter already dominates
    #: everything it ever served, so +1 cannot alias — and stays the
    #: "exactly one bump per restore" contract tests pin.
    RESTORE_VERSION_JUMP = 1 << 20

    def restore(self, snapshot: Dict[str, Any]):
        """Adopt a :meth:`snapshot` (typically on a fresh server before
        :meth:`start`, the kill→restart→reconnect recovery path)."""
        if self.mode == "asynchronous":
            self.lock.acquire_write()
        try:
            self.weights = [np.asarray(w, dtype=np.float32).copy()
                            for w in snapshot["weights"]]
            with self._counter_lock:
                snap_version = int(snapshot.get("weights_version", 0))
                if snap_version >= self._weights_version:
                    # restart recovery: the dead predecessor's counter
                    # is unknowable past the snapshot — jump clear of
                    # its whole plausible trajectory (see
                    # RESTORE_VERSION_JUMP)
                    self._weights_version = (snap_version
                                             + self.RESTORE_VERSION_JUMP)
                else:
                    # live in-place restore: our own counter dominates
                    # everything we ever served; one bump (also drops
                    # the cached encoding)
                    self._weights_version += 1
                # the generation marker travels WITH the weights it
                # describes (no jump: cross-shard coherence compares
                # these, and a promoted standby must continue its dead
                # primary's trajectory exactly); the fencing epoch only
                # ever ratchets up
                self.generation = int(snapshot.get("generation", 0))
                self.gen_digest = int(snapshot.get("gen_digest", 0))
                self.epoch = max(self.epoch,
                                 int(snapshot.get("epoch", 0)))
        finally:
            if self.mode == "asynchronous":
                self.lock.release()
        with self._counter_lock:
            self.num_updates = int(snapshot.get("num_updates", 0))
        with self._seen_lock:
            self._seen_ids = OrderedDict(snapshot.get("seen_ids", ()))

    def _validate_delta(self, delta: List[np.ndarray]):
        """Arity/shape gate shared by apply and prepare: subtract_params
        zips the lists, so a short or mis-shaped delta would silently
        truncate/corrupt the served weights for every client until
        restart — validate BEFORE touching anything."""
        if len(delta) != len(self.weights):
            raise ValueError(
                f"delta has {len(delta)} arrays, model has "
                f"{len(self.weights)}")
        for i, (d, w) in enumerate(zip(delta, self.weights)):
            if tuple(np.shape(d)) != tuple(np.shape(w)):
                raise ValueError(
                    f"delta[{i}] shape {np.shape(d)} != weight shape "
                    f"{np.shape(w)}")

    def apply_delta(self, delta: List[np.ndarray],
                    update_id: Optional[str] = None):
        if fault_site("ps.apply_delta"):
            return  # drop: the delta is silently lost (still acked)
        self._validate_delta(delta)
        if update_id is None:
            # mint one: the generation digest and the replication stream
            # both need a stable identity for EVERY applied delta, so an
            # anonymous (legacy 'u'/no-header) push gets a server-side id
            # — dedup semantics for the client are unchanged (it never
            # knows the id, so it can never resend it)
            update_id = uuid.uuid4().hex
        # claim the id before applying. A duplicate of a completed
        # apply returns immediately; a duplicate of an IN-FLIGHT apply
        # waits on its latch and re-checks — it must neither double-
        # apply nor ack before the first apply has actually landed.
        while True:
            with self._seen_lock:
                if update_id in self._seen_ids:
                    return  # duplicate resend from a client retry
                latch = self._in_flight.get(update_id)
                if latch is None:
                    latch = threading.Event()
                    self._in_flight[update_id] = latch
                    break  # we own the apply for this id
            latch.wait(timeout=60.0)
        try:
            if self.mode == "asynchronous":
                self.lock.acquire_write()
            try:
                self.weights = subtract_params(self.weights, delta)
                # invalidate the encoded snapshot (under _counter_lock:
                # hogwild bypasses the RWLock, and a lost increment
                # would leave the cache serving stale weights forever)
                with self._counter_lock:
                    self._weights_version += 1
                    self.generation += 1
                    self.gen_digest = (self.gen_digest
                                       + _id_digest(update_id)) % _DIGEST_MOD
            finally:
                if self.mode == "asynchronous":
                    self.lock.release()
        except BaseException:
            # failed apply: release the claim WITHOUT recording the id,
            # so the client's resend retries the apply instead of being
            # acked for a delta that never landed
            with self._seen_lock:
                self._in_flight.pop(update_id, None)
            latch.set()
            raise
        now = time.monotonic()
        with self._seen_lock:
            self._seen_ids[update_id] = now
            self._in_flight.pop(update_id, None)
            while self._seen_ids and (
                    len(self._seen_ids) > self._seen_cap
                    or next(iter(self._seen_ids.values()))
                    < now - self._seen_ttl):
                self._seen_ids.popitem(last=False)
        latch.set()
        with self._counter_lock:
            self.num_updates += 1
        hook = self._applied_hook
        if hook is not None:
            # outside every lock: the replicator may do wire I/O. The
            # delta views are still valid (we are inside the handler's
            # frame); hook failures must never fail the client's ack.
            try:
                hook(update_id, delta)
            except Exception:  # noqa: BLE001 — replication is best-effort
                _LOG.warning("applied-delta hook failed", exc_info=True)

    # ------------------------------------------------ two-phase commit
    #: staged-but-never-committed transactions are swept after this many
    #: seconds (a coordinator that died between prepare and commit must
    #: not leak its delta copies forever). Comfortably above the
    #: client's worst-case retry horizon, so a slow commit cannot find
    #: its stage swept.
    STAGE_TTL = 600.0

    def prepare_delta(self, delta: List[np.ndarray], txn_id: str):
        """Phase one: validate and STAGE ``delta`` under ``txn_id``
        without applying. The copies are deliberate — the caller's
        arrays are zero-copy views of a receive buffer that dies with
        the request, and the stage must survive until commit."""
        self._validate_delta(delta)
        staged = [np.array(d, dtype=np.float32, copy=True) for d in delta]
        now = time.monotonic()
        with self._staged_lock:
            self._staged[txn_id] = (staged, now)
            self._staged.move_to_end(txn_id)
            while self._staged:
                oldest = next(iter(self._staged))
                if self._staged[oldest][1] >= now - self.STAGE_TTL:
                    break
                self._staged.popitem(last=False)

    def commit_delta(self, txn_id: str):
        """Phase two: apply the staged delta. Returns ``(generation,
        digest, version)`` read after the apply. Idempotent: a retried
        commit whose first attempt's ack was lost finds ``txn_id`` in
        the idempotency window and re-acks with the current counters;
        an id this server has NEVER seen (prepare landed on a dead
        predecessor) raises :class:`UnknownTxnError` so the coordinator
        re-prepares."""
        with self._staged_lock:
            staged = self._staged.pop(txn_id, None)
        if staged is None:
            with self._seen_lock:
                known = txn_id in self._seen_ids
            if not known:
                raise UnknownTxnError(txn_id)
        else:
            self.apply_delta(staged[0], update_id=txn_id)
        with self._counter_lock:
            return self.generation, self.gen_digest, self._weights_version

    def abort_delta(self, txn_id: str):
        """Drop a staged delta. Unknown ids are a no-op: abort is the
        best-effort cleanup fan-out after a prepare failure, and some
        shards never staged anything."""
        with self._staged_lock:
            self._staged.pop(txn_id, None)

    # ------------------------------------------ replication / fencing
    def apply_replicated(self, delta: List[np.ndarray], update_id: str,
                         epoch: int):
        """Apply one delta from a primary's replication stream, fenced
        by epoch: older-epoch traffic (a zombie primary that was failed
        over) raises :class:`FencedEpochError`; a newer epoch is
        adopted. Dedup by ``update_id`` rides the ordinary idempotency
        window, so a catch-up resend after a reconnect is safe."""
        epoch = int(epoch)
        with self._counter_lock:
            if epoch < self.epoch:
                raise FencedEpochError(
                    f"replication epoch {epoch} < fence {self.epoch}")
            if epoch > self.epoch:
                self.epoch = epoch
        self.apply_delta(delta, update_id=update_id)

    def set_applied_hook(self, hook: Optional[Callable]):
        """Attach (or detach, with ``None``) the applied-delta hook the
        replicator rides. One hook at a time — the parameter plane has
        exactly one standby per shard."""
        self._applied_hook = hook

    def generation_info(self):
        """``(generation, digest)`` under one lock — the coherent pair
        cross-shard checks compare."""
        with self._counter_lock:
            return self.generation, self.gen_digest

    def adopt_generation(self, generation: int, digest: int):
        """Overwrite the generation marker — the snapshot-restart
        fallback's realignment (the restarted shard LOST post-snapshot
        deltas; adopting the surviving shards' marker keeps the plane
        pullable, trading the documented lossy-restart semantics for a
        coherence check that would otherwise veto pulls forever)."""
        with self._counter_lock:
            self.generation = int(generation)
            self.gen_digest = int(digest)

    @abc.abstractmethod
    def start(self):
        """Start serving."""

    @abc.abstractmethod
    def stop(self):
        """Stop serving."""


class HttpServer(BaseParameterServer):
    """HTTP parameter server: ``GET /parameters`` and ``POST /update``.

    (Parity surface: ``elephas/parameter/server.py:42-137``.)
    """

    def __init__(self, model: Dict[str, Any], port: int, mode: str, **kwargs):
        super().__init__(model, port, mode, **kwargs)
        self.master_url: Optional[str] = None
        self._httpd: Optional[ThreadingHTTPServer] = None
        self._thread: Optional[threading.Thread] = None

    def start(self):
        server = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *args):
                # quiet on stderr — requests are recorded as
                # ps_http_requests_total{method,path,status} instead,
                # so PS traffic is visible to a scrape, not a terminal
                pass

            def _route(self) -> str:
                # bounded label domain: arbitrary probed paths must not
                # mint new label sets
                if self.path.rstrip("/") in ("", "/"):
                    return "/"
                for known in ("/health", "/metrics", "/parameters",
                              "/update", "/version", "/prepare",
                              "/commit", "/abort", "/replicate"):
                    if self.path.startswith(known):
                        return known
                return "other"

            def _record(self, status: int):
                server._m_http_requests.labels(
                    method=self.command, path=self._route(),
                    status=str(status)).inc()

            def _empty(self, status: int):
                # explicit empty body: a status line with no
                # Content-Length leaves clients to wait for EOF
                self._record(status)
                self.send_response(status)
                self.send_header("Content-Length", "0")
                self.end_headers()

            def do_GET(self):
                # restore the caller's trace context (W3C traceparent
                # header) for this request, so ps.rpc events — and
                # anything else emitted while serving it — carry the
                # originating request's id; no header, no context
                with use_context(parse_traceparent(
                        self.headers.get("traceparent"))):
                    self._handle_get()

            def _handle_get(self):
                t0 = time.perf_counter()
                content_type = "application/elephas-tpu"
                extra_headers = ()
                if self.path.rstrip("/") in ("", "/"):
                    body = b"elephas_tpu"
                elif self.path.startswith("/health"):
                    # liveness + progress: workers and orchestrators probe
                    # this to detect a dead/stuck server (reference has no
                    # failure detection at all, SURVEY.md par.5)
                    body = (b'{"status": "ok", "mode": "%s", '
                            b'"num_updates": %d}'
                            % (server.mode.encode(), server.num_updates))
                elif self.path.startswith("/metrics"):
                    # Prometheus exposition of the process default
                    # registry: PS RPC counters, fault injections, and
                    # any training telemetry co-resident in this
                    # process. The render's own cost lands on
                    # obs_scrape_* (site="ps") — exposition at high
                    # cardinality must itself be visible.
                    body = default_registry().render().encode()
                    observe_scrape(default_registry(), "ps",
                                   time.perf_counter() - t0, len(body))
                    content_type = ("text/plain; version=0.0.4; "
                                    "charset=utf-8")
                elif self.path.startswith("/version"):
                    # the cheap "weights changed since v?" poll: live-
                    # weight subscribers hit this every poll interval
                    # and only download /parameters when it moved; the
                    # generation pair and fencing epoch ride along for
                    # coherence checks and failover diagnostics
                    gen, digest = server.generation_info()
                    body = (b'{"version": %d, "num_updates": %d, '
                            b'"generation": %d, "digest": %d, '
                            b'"epoch": %d}'
                            % (server.weights_version,
                               server.num_updates, gen, digest,
                               server.epoch))
                    content_type = "application/json"
                    server._obs_rpc("http", "get_version", "ok", t0)
                elif self.path.startswith("/parameters"):
                    # cached encoded snapshot: no per-request encode (or
                    # weight copy) while the version is unchanged. The
                    # version AND generation the payload encodes ride
                    # headers, so a subscriber's (generation, version,
                    # weights) triple is consistent without a second
                    # racing RPC.
                    (gen, digest, version,
                     body) = server.encoded_weights_generational()
                    extra_headers = (
                        ("X-Weights-Version", str(version)),
                        ("X-Weights-Generation", str(gen)),
                        ("X-Weights-Digest", str(digest)))
                    server._obs_rpc("http", "get_weights", "ok", t0,
                                    bytes_out=len(body))
                else:
                    self._empty(404)
                    return
                # record BEFORE the body goes out, so a client that
                # scrapes /metrics right after this response already
                # sees its request counted
                self._record(200)
                self.send_response(200)
                self.send_header("Content-Type", content_type)
                self.send_header("Content-Length", str(len(body)))
                for name, value in extra_headers:
                    self.send_header(name, value)
                self.end_headers()
                self.wfile.write(body)

            def do_POST(self):
                with use_context(parse_traceparent(
                        self.headers.get("traceparent"))):
                    self._handle_post()

            def _reply(self, body: bytes,
                       content_type: str = "text/plain"):
                self._record(200)    # before the reply, like do_GET
                self.send_response(200)
                self.send_header("Content-Type", content_type)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def _read_delta(self, op: str, t0: float):
                """Decode the request body as a delta frame; answers the
                400 itself and returns None on a malformed payload."""
                length = int(self.headers.get("Content-Length", "0"))
                try:
                    return _decode_delta(self.rfile.read(length)), length
                except Exception:  # malformed -> clean 400, not a 500
                    server._obs_rpc("http", op, "bad_frame", t0)
                    self._empty(400)
                    return None

            def _handle_post(self):
                t0 = time.perf_counter()
                if self.path.startswith("/update"):
                    decoded = self._read_delta("apply_delta", t0)
                    if decoded is None:
                        return
                    delta, length = decoded
                    try:
                        server.apply_delta(
                            delta,
                            update_id=self.headers.get("X-Update-Id"))
                    except ValueError as err:  # wrong arity/shapes -> 400
                        _LOG.warning("rejected delta: %s", err)
                        server._obs_rpc("http", "apply_delta", "rejected",
                                        t0, bytes_in=length)
                        self._empty(400)
                        return
                    server._obs_rpc("http", "apply_delta", "ok", t0,
                                    bytes_in=length)
                    self._reply(b"Update done")
                elif self.path.startswith("/prepare"):
                    txn_id = self.headers.get("X-Txn-Id", "")
                    decoded = self._read_delta("prepare", t0)
                    if decoded is None:
                        return
                    delta, length = decoded
                    try:
                        server.prepare_delta(delta, txn_id)
                    except ValueError as err:
                        _LOG.warning("rejected prepare: %s", err)
                        server._obs_rpc("http", "prepare", "rejected", t0,
                                        bytes_in=length)
                        self._empty(400)
                        return
                    server._obs_rpc("http", "prepare", "ok", t0,
                                    bytes_in=length)
                    self._reply(b"Staged")
                elif self.path.startswith("/commit"):
                    txn_id = self.headers.get("X-Txn-Id", "")
                    try:
                        gen, digest, version = server.commit_delta(txn_id)
                    except UnknownTxnError:
                        # 404 on the /commit route = unknown txn (the
                        # typed re-prepare signal, not retried)
                        server._obs_rpc("http", "commit", "unknown_txn",
                                        t0)
                        self._empty(404)
                        return
                    except ValueError as err:
                        _LOG.warning("rejected commit: %s", err)
                        server._obs_rpc("http", "commit", "rejected", t0)
                        self._empty(400)
                        return
                    server._obs_rpc("http", "commit", "ok", t0)
                    self._reply(b'{"generation": %d, "digest": %d, '
                                b'"version": %d}' % (gen, digest, version),
                                content_type="application/json")
                elif self.path.startswith("/abort"):
                    server.abort_delta(self.headers.get("X-Txn-Id", ""))
                    server._obs_rpc("http", "abort", "ok", t0)
                    self._reply(b"Aborted")
                elif self.path.startswith("/replicate"):
                    update_id = self.headers.get("X-Update-Id", "")
                    epoch = int(self.headers.get(
                        "X-Replication-Epoch", "0"))
                    decoded = self._read_delta("replicate", t0)
                    if decoded is None:
                        return
                    delta, length = decoded
                    try:
                        server.apply_replicated(delta, update_id, epoch)
                    except FencedEpochError:
                        # 409: the sender is a zombie primary from a
                        # fenced-off epoch — terminal, never retried
                        server._obs_rpc("http", "replicate", "fenced",
                                        t0, bytes_in=length)
                        self._empty(409)
                        return
                    except ValueError as err:
                        _LOG.warning("rejected replicated delta: %s", err)
                        server._obs_rpc("http", "replicate", "rejected",
                                        t0, bytes_in=length)
                        self._empty(400)
                        return
                    server._obs_rpc("http", "replicate", "ok", t0,
                                    bytes_in=length)
                    self._reply(b"Replicated")
                else:
                    self._empty(404)

        host = determine_master(self.port).split(":")[0]
        self._httpd = ThreadingHTTPServer((host, self.port), Handler)
        self._thread = threading.Thread(target=self._httpd.serve_forever,
                                        daemon=True)
        self._thread.start()
        self.master_url = determine_master(self.port)

    def stop(self):
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()
            self._thread.join(timeout=5)
            self._httpd = None
            self._thread = None


class SocketServer(BaseParameterServer):
    """Raw-TCP parameter server with a 1-byte opcode protocol:
    ``'g'`` = get weights, ``'u'`` = apply update, ``'U'`` = apply update
    with a 32-byte idempotency id (safe to resend), ``'h'`` = health
    probe, ``'v'`` = weight-version poll (8-byte big-endian reply — the
    cheap "changed since v?" probe live-weight subscribers ride),
    ``'G'`` = get weights WITH their version (8-byte version, then the
    frame), ``'T'`` = trace-context frame (55-byte ``traceparent``
    applying to the next RPC). ``'v'``/``'G'``/``'T'`` are
    backward-compatible extensions old clients simply never send.

    (Parity surface: ``elephas/parameter/server.py:140-233``; framing is the
    length-prefixed ETPU format instead of pickled payloads.)
    """

    def __init__(self, model: Dict[str, Any], port: int, mode: str, **kwargs):
        super().__init__(model, port, mode, **kwargs)
        self.socket: Optional[socket.socket] = None
        self.runs = False
        self.connections: List[threading.Thread] = []
        self.thread: Optional[threading.Thread] = None
        self._conn_lock = threading.Lock()

    def start(self):
        if self.thread is not None:
            self.stop()
        ready = threading.Event()
        self.thread = threading.Thread(target=self._serve, args=(ready,),
                                       daemon=True)
        self.thread.start()
        if not ready.wait(timeout=10):
            raise RuntimeError("SocketServer failed to start listening")

    def stop(self):
        self.runs = False
        if self.socket is not None:
            # unblock accept() with a self-connection, then close
            try:
                host = determine_master(self.port).split(":")[0]
                with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
                    s.settimeout(1.0)
                    s.connect((host, self.port))
            except OSError:
                pass
        if self.thread is not None:
            self.thread.join(timeout=5)
            self.thread = None
        # the serve thread is joined (or timed out) — snapshot under the
        # lock anyway so a straggling accept can't append to a list this
        # loop never sees
        with self._conn_lock:
            handlers, self.connections = self.connections, []
        for t in handlers:
            t.join(timeout=1)
        if self.socket is not None:
            try:
                self.socket.close()
            except OSError:
                pass
            self.socket = None

    def _serve(self, ready: threading.Event):
        sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        host = determine_master(self.port).split(":")[0]
        sock.bind((host, self.port))
        sock.listen(16)
        self.socket = sock
        self.runs = True
        ready.set()
        while self.runs:
            try:
                conn, _ = sock.accept()
            except OSError:
                break
            if not self.runs:
                conn.close()
                break
            t = threading.Thread(target=self._listen, args=(conn,), daemon=True)
            t.start()
            # prune finished handlers on every accept: a long run with
            # reconnecting clients must hold O(live connections) thread
            # objects, not one per connection ever made
            with self._conn_lock:
                self.connections = [c for c in self.connections
                                    if c.is_alive()]
                self.connections.append(t)
        try:
            sock.close()
        except OSError:
            pass

    #: between-RPC poll interval: a handler waiting on an idle persistent
    #: connection re-checks ``self.runs`` this often, so server stop()
    #: never strands handler threads. The wait is selectors-based (epoll) — the
    #: socket itself stays in blocking mode, because a socket timeout
    #: would disable the native C++ framing fast path for the RPC body
    #: (``utils/sockets._use_native``) and cap stalls the client's own
    #: configurable timeout is meant to govern.
    IDLE_TIMEOUT = 0.5

    def _listen(self, conn: socket.socket):
        # selectors (epoll/kqueue), not select.select: the latter raises
        # ValueError for fds >= FD_SETSIZE (1024), which a busy server
        # (many connections + file-backed data columns) can exceed
        sel = selectors.DefaultSelector()
        pending_ctx = None   # trace context for the NEXT RPC (b"T" frame)
        with conn, sel:
            sel.register(conn, selectors.EVENT_READ)
            while self.runs:
                try:
                    if not sel.select(timeout=self.IDLE_TIMEOUT):
                        continue  # idle persistent connection: poll runs
                    opcode = conn.recv(1)
                except OSError:
                    return
                if not opcode:
                    return
                if opcode == TRACE_OPCODE:
                    # trace-context frame extension: fixed-length
                    # traceparent applying to the one RPC that follows.
                    # Old clients never send it; a malformed payload
                    # parses to None and the stream stays in sync.
                    try:
                        pending_ctx = receive_traceparent(conn)
                    except (ConnectionError, OSError):
                        return
                    continue
                t0 = time.perf_counter()
                token = set_context(pending_ctx)
                pending_ctx = None
                try:
                    if opcode in (b"u", b"U"):
                        update_id = None
                        if opcode == b"U":
                            update_id = bytes(recv_exact(conn, 32)).decode(
                                "ascii", "replace")
                        # copy=False: the delta arrays view the receive
                        # buffer — safe here because apply_delta only
                        # READS them (subtract_params allocates the new
                        # weights), so the hot push path decodes with
                        # zero tensor copies
                        arrays, kind = receive_frame(conn, copy=False)
                        nbytes_in = sum(int(a.nbytes) for a in arrays)
                        delta = (dequantize_delta(arrays)
                                 if kind == KIND_DELTA_Q8 else arrays)
                        try:
                            self.apply_delta(delta, update_id=update_id)
                        except ValueError as err:
                            # the frame was fully read, so the stream is
                            # still in sync: NACK a validation-rejected
                            # delta so the client fails fast instead of
                            # retrying a permanent error
                            _LOG.warning("rejected delta: %s", err)
                            conn.sendall(b"e")
                            self._obs_rpc("socket", "apply_delta",
                                          "rejected", t0,
                                          bytes_in=nbytes_in)
                            continue
                        # counted before the ack: a client that reads the
                        # counter right after its push returns sees it
                        self._obs_rpc("socket", "apply_delta", "ok", t0,
                                      bytes_in=nbytes_in)
                        conn.sendall(b"k")  # ack: delta applied
                    elif opcode == b"g":
                        # cached encoded snapshot: repeated gets cost one
                        # sendall of the same immutable payload — no
                        # weight copy, no re-encode
                        payload = self.encoded_weights()
                        send_payload(conn, payload)
                        self._obs_rpc("socket", "get_weights", "ok", t0,
                                      bytes_out=len(payload))
                    elif opcode == b"G":
                        # versioned get: the 8-byte version prefixes the
                        # SAME cached frame 'g' serves, read as one
                        # consistent pair — the live-weight subscriber's
                        # download path
                        version, payload = self.encoded_weights_versioned()
                        conn.sendall(struct.pack(">Q", version))
                        send_payload(conn, payload)
                        self._obs_rpc("socket", "get_weights", "ok", t0,
                                      bytes_out=len(payload))
                    elif opcode == b"v":
                        # version poll: 8 bytes, no weight payload — a
                        # subscriber polls this every interval and only
                        # downloads when the answer moved
                        conn.sendall(struct.pack(
                            ">Q", self.weights_version))
                        self._obs_rpc("socket", "get_version", "ok", t0)
                    elif opcode == PS_GEN_POLL_OPCODE:
                        gen, digest = self.generation_info()
                        conn.sendall(struct.pack(">QQ", gen, digest))
                        self._obs_rpc("socket", "get_generation", "ok", t0)
                    elif opcode == PS_GEN_PULL_OPCODE:
                        # generational pull: (generation, digest,
                        # version) prefix the SAME cached frame 'g'
                        # serves, read as one consistent quadruple —
                        # the coherence-checked subscriber pull
                        (gen, digest, version,
                         payload) = self.encoded_weights_generational()
                        conn.sendall(struct.pack(">QQQ", gen, digest,
                                                 version))
                        send_payload(conn, payload)
                        self._obs_rpc("socket", "get_weights", "ok", t0,
                                      bytes_out=len(payload))
                    elif opcode == PS_PREPARE_OPCODE:
                        txn_id = bytes(recv_exact(
                            conn, PS_ID_BYTES)).decode("ascii", "replace")
                        arrays, kind = receive_frame(conn, copy=False)
                        nbytes_in = sum(int(a.nbytes) for a in arrays)
                        delta = (dequantize_delta(arrays)
                                 if kind == KIND_DELTA_Q8 else arrays)
                        try:
                            # prepare copies the delta (the views die
                            # with this frame) — stage, don't apply
                            self.prepare_delta(delta, txn_id)
                        except ValueError as err:
                            _LOG.warning("rejected prepare: %s", err)
                            conn.sendall(b"e")
                            self._obs_rpc("socket", "prepare", "rejected",
                                          t0, bytes_in=nbytes_in)
                            continue
                        conn.sendall(b"k")
                        self._obs_rpc("socket", "prepare", "ok", t0,
                                      bytes_in=nbytes_in)
                    elif opcode == PS_COMMIT_OPCODE:
                        txn_id = bytes(recv_exact(
                            conn, PS_ID_BYTES)).decode("ascii", "replace")
                        try:
                            gen, digest, version = self.commit_delta(
                                txn_id)
                        except UnknownTxnError:
                            # 'n': typed re-prepare signal — the staged
                            # delta died with a failed-over predecessor
                            conn.sendall(b"n")
                            self._obs_rpc("socket", "commit",
                                          "unknown_txn", t0)
                            continue
                        except ValueError as err:
                            _LOG.warning("rejected commit: %s", err)
                            conn.sendall(b"e")
                            self._obs_rpc("socket", "commit", "rejected",
                                          t0)
                            continue
                        conn.sendall(b"k" + struct.pack(">QQQ", gen,
                                                        digest, version))
                        self._obs_rpc("socket", "commit", "ok", t0)
                    elif opcode == PS_ABORT_OPCODE:
                        txn_id = bytes(recv_exact(
                            conn, PS_ID_BYTES)).decode("ascii", "replace")
                        self.abort_delta(txn_id)
                        conn.sendall(b"k")
                        self._obs_rpc("socket", "abort", "ok", t0)
                    elif opcode == PS_REPLICATE_OPCODE:
                        epoch = recv_u64(conn)
                        update_id = bytes(recv_exact(
                            conn, PS_ID_BYTES)).decode("ascii", "replace")
                        arrays, kind = receive_frame(conn, copy=False)
                        nbytes_in = sum(int(a.nbytes) for a in arrays)
                        delta = (dequantize_delta(arrays)
                                 if kind == KIND_DELTA_Q8 else arrays)
                        try:
                            self.apply_replicated(delta, update_id, epoch)
                        except FencedEpochError:
                            # 'f': zombie primary from a fenced-off
                            # epoch — terminal for the sender
                            conn.sendall(b"f")
                            self._obs_rpc("socket", "replicate", "fenced",
                                          t0, bytes_in=nbytes_in)
                            continue
                        except ValueError as err:
                            _LOG.warning("rejected replicated delta: %s",
                                         err)
                            conn.sendall(b"e")
                            self._obs_rpc("socket", "replicate",
                                          "rejected", t0,
                                          bytes_in=nbytes_in)
                            continue
                        conn.sendall(b"k")
                        self._obs_rpc("socket", "replicate", "ok", t0,
                                      bytes_in=nbytes_in)
                    elif opcode == b"h":
                        conn.sendall(b"k")  # alive
                        self._obs_rpc("socket", "health", "ok", t0)
                    else:
                        # unknown opcode = desynced or garbage stream;
                        # continuing would interpret payload bytes as
                        # opcodes — drop the connection instead
                        _LOG.warning("dropping connection: unknown "
                                     "opcode %r", opcode)
                        return
                except OSError:
                    # mid-RPC stall or client death: drop silently (the
                    # client's retry opens a fresh one); a half-read
                    # frame must never be applied
                    return
                except (ValueError, struct.error, KeyError) as err:
                    # corrupt/garbage frame (decode errors) or a
                    # validation-rejected delta: drop the connection,
                    # loudly — malformed input must not kill the handler
                    # thread, but repeated drops must be diagnosable
                    _LOG.warning("dropping connection after bad frame/"
                                 "delta: %s", err)
                    return
                finally:
                    # the context applies to exactly one RPC: the next
                    # opcode on this connection starts clean unless the
                    # client sends another b"T" frame
                    reset_context(token)
