"""Engine-loop continuous profiler: where does the loop's wall time go?

The serving engines' ``serving_step_latency_seconds`` says how long a
step took; it cannot say WHY. This module is the engine loop's ONE span
mechanism: the loop wraps its work in :meth:`LoopProfiler.section`
blocks, and every section feeds three outputs at once.

1. **A host span in the profiler's own trace.** While a
   ``jax.profiler`` session runs, each section is also a
   ``jax.profiler.TraceAnnotation`` named ``elephas.<layer>.<what>``
   (:data:`SPANS`), so the loop's phases lie on the same clock as the
   device's ``XLA Ops`` line and every idle gap of the device can be
   named by what the host was doing in it. With no session running a
   section pays one flag check for this, and formats nothing.
2. **Monotone counters.** The section's exclusive seconds go to
   ``serving_loop_phase_seconds_total{phase}`` (with
   ``serving_loop_iterations_total``), so two scrapes of ``/metrics``
   give a window's seconds per phase. The same accumulation backs the
   rolling ``serving_loop_utilization{phase}`` gauges, ``/stats``
   ``loop``, the watchdog's stall attribution and the interleaved
   prefill budget. Time no section claims shows up as ``idle``.
3. **The slow-iteration record.** An iteration (tick to tick) longer
   than :data:`SLOW_ITERATION_S` adds to
   ``serving_loop_slow_iterations_total{phase}`` and
   ``serving_loop_slow_iteration_seconds_total{phase}`` under the
   phase that held most of it, and emits one
   ``engine.slow_iteration`` event with the per-phase split: a pause of
   seconds is named by its phase even when no trace was running.

Jit compiles and Python's garbage collections are tracked SEPARATELY
(phases ``jit`` and ``gc``, excluded from the section they
interrupted; compiles also on ``serving_jit_compiles_total`` +
``serving_jit_compile_seconds``): a post-hot-swap compile storm or a
long collection is the classic incident that otherwise masquerades as
decode latency. Compile detection rides JAX's own monitoring stream
(``backend_compile`` duration events), collections ``gc.callbacks``;
both attribute to the profiler the CURRENT THREAD runs under.

Cost: per section two clock reads, a few float adds and the trace
flag check; per iteration one short locked fold and a counter add per
phase seen; about fifteen sections per engine step. Its cost on the
chip is in ``PERF.md`` (section 6, PR 25).
"""
import gc
import threading
import time
import weakref
from collections import deque
from typing import Dict, Optional

from .context import new_root, use_context
from .events import emit as emit_event
from .metrics import MetricsRegistry

try:
    from jax.profiler import TraceAnnotation as _TraceAnnotation

    #: is a ``jax.profiler`` session recording host spans right now?
    #: (one flag read in the profiler's native library)
    _tracing = _TraceAnnotation.is_enabled
except ImportError:     # no JAX: the sections keep their accounting
    def _tracing() -> bool:     # and write no trace spans
        return False

__all__ = ["LoopProfiler", "PHASES", "SPANS", "SLOW_ITERATION_S"]

#: the phase vocabulary (a fixed label domain): ``swap`` = staged
#: weight-swap apply, ``admit`` = admission scheduling (queue pops,
#: capacity math, block claims — prefill excluded), ``prefill`` =
#: admission prefill / shipped-KV install, ``decode_dispatch`` = the
#: step's uploads and the jitted call's return, ``decode`` = the host
#: waiting for the step's tokens, ``emit`` = host-side token
#: bookkeeping, ``lock_wait`` = the server loop acquiring the serving
#: lock, ``deliver`` = stream hand-off, waking handlers, harvest and
#: per-iteration housekeeping, ``yield`` = the server loop's fairness
#: yield and idle sleep, ``jit`` = XLA compiles and ``gc`` = Python's
#: collector (both tracked separately so they never masquerade as the
#: phase they interrupted), ``idle`` = wall time no section claimed.
PHASES = ("swap", "admit", "prefill", "decode_dispatch", "decode", "emit",
          "lock_wait", "deliver", "yield", "jit", "gc", "idle")

#: the engine loop's trace spans and the phase each one's exclusive
#: seconds go to. ``None`` = a parent-only span: it is written into the
#: trace, and for the accounting it is transparent (its own seconds
#: stay with the section around it, or unclaimed when there is none).
#: The names are a contract: ``chipbench/readers/trace_host_gaps.py``
#: charges the device's idle gaps to them.
SPANS = {
    "elephas.server.lock_wait": "lock_wait",
    "elephas.loop.step": None,
    "elephas.loop.swap": "swap",
    "elephas.loop.admit": "admit",
    "elephas.loop.admit.request": None,
    "elephas.loop.admit.claim": "admit",
    "elephas.loop.prefill": "prefill",
    "elephas.loop.prefill.row_init": None,
    "elephas.loop.prefill.chunks": None,
    "elephas.loop.prefill.install": None,
    "elephas.loop.prefill.first_token": None,
    "elephas.loop.decode.dispatch": "decode_dispatch",
    "elephas.loop.decode.wait": "decode",
    "elephas.loop.emit": "emit",
    "elephas.server.deliver": "deliver",
    "elephas.server.housekeeping": "deliver",
    "elephas.server.yield": "yield",
}

#: an iteration (tick to tick) longer than this is a slow iteration.
#: 1000 ms is the time-to-first-token limit the benchmark's cells are
#: judged against: for that long no client got a token
SLOW_ITERATION_S = 1.0

# one process-wide JAX monitoring listener and one ``gc.callbacks``
# hook fan compiles and collections out to whichever profiler the
# CURRENT THREAD is running under (engine loops are single-threaded by
# design; compiles and collections triggered off-loop — a subscriber's
# weight conversion, a handler thread's allocation — are deliberately
# not attributed)
_tls = threading.local()
_listener_lock = threading.Lock()
_listener_installed = False
_gc_hook_installed = False


def _on_jax_event(event: str, duration: float, **_kw) -> None:
    if "backend_compile" not in event:
        return              # trace/lowering sub-phases of the same
        # compile would multi-count it; backend_compile fires once
    prof = getattr(_tls, "profiler", None)
    if prof is not None:
        prof.record_compile(float(duration))


def _install_jax_listener() -> None:
    global _listener_installed
    with _listener_lock:
        if _listener_installed:
            return
        try:
            import jax.monitoring

            jax.monitoring.register_event_duration_secs_listener(
                _on_jax_event)
            _listener_installed = True
        except Exception:  # noqa: BLE001 — a JAX without the
            # monitoring stream just leaves the compile counters at 0
            _listener_installed = True   # don't retry per profiler


def _on_gc(phase: str, _info) -> None:
    prof = getattr(_tls, "profiler", None)
    if prof is None:
        return
    if phase == "start":
        prof._gc_start = prof._clock()
    elif prof._gc_start is not None:
        prof.record_gc(prof._clock() - prof._gc_start)
        prof._gc_start = None


def _install_gc_hook() -> None:
    global _gc_hook_installed
    with _listener_lock:
        if not _gc_hook_installed:
            gc.callbacks.append(_on_gc)
            _gc_hook_installed = True


class _Section:
    """Reusable per-span context manager (see
    :meth:`LoopProfiler.section`): plain enter/exit, no generator
    machinery, engine-loop thread only."""

    __slots__ = ("_prof", "_name", "_phase")

    def __init__(self, prof: "LoopProfiler", name: str,
                 phase: Optional[str]):
        self._prof = prof
        self._name = name
        self._phase = phase

    def __enter__(self):
        prof = self._prof
        _tls.profiler = prof    # compiles inside a section attribute
        # correctly even on threads that never tick (a direct
        # submit(admit=True) admission prefill)
        span = None
        if _tracing():
            span = _TraceAnnotation(self._name)
            span.__enter__()
        # [section, start, seconds claimed inside, trace span]
        prof._stack.append([self, prof._clock(), 0.0, span])
        return self

    def __exit__(self, *exc):
        prof = self._prof
        now = prof._clock()
        _, st, child, span = prof._stack.pop()
        if span is not None:
            span.__exit__(None, None, None)
        ph = self._phase
        if ph is None:
            # parent-only span: what its children claimed passes up,
            # its own seconds stay with the section around it
            if prof._stack:
                prof._stack[-1][2] += child
            return False
        dur = now - st
        cur = prof._cur
        cur[ph] = cur.get(ph, 0.0) + (dur - child if dur > child
                                      else 0.0)
        if prof._stack:
            prof._stack[-1][2] += dur
        return False


class LoopProfiler:
    """Phase accounting and trace spans for one engine loop.

    The owning loop calls :meth:`tick` once per iteration (the engines
    do it at the top of ``step()``; a server loop also ticks on a pass
    that found nothing to step) and wraps its work in :meth:`section`
    blocks. Sections nest; a parent's time EXCLUDES its children's, so
    ``admit`` never double-counts the ``prefill`` it contains.
    Utilization is computed over the iterations of the last
    ``window_s`` seconds: per phase, seconds-in-phase over wall seconds
    — including the idle gap between iterations, which is what makes
    the numbers read as a utilization breakdown instead of a busy-time
    breakdown.

    :param registry: destination for
        ``serving_loop_phase_seconds_total{phase}``,
        ``serving_loop_iterations_total``, the two slow-iteration
        counters, ``serving_loop_utilization{phase}`` (callback gauges —
        always live), ``serving_jit_compiles_total`` and
        ``serving_jit_compile_seconds``. Normally the engine's own
        registry.
    :param window_s: rolling utilization window. Short enough that a
        compile storm is visible while it is happening; long enough
        that one slow iteration doesn't dominate.
    :param track_jit: attach the process-wide JAX compile listener
        (idempotent; shared by every profiler in the process).
    :param clock: injectable time source for tests.
    """

    def __init__(self, registry: MetricsRegistry,
                 window_s: float = 30.0, track_jit: bool = True,
                 clock=time.perf_counter):
        if window_s <= 0:
            raise ValueError(f"window_s must be > 0, got {window_s}")
        self.window_s = float(window_s)
        #: aggregation granularity: iterations fold into ~64 coarse
        #: buckets per window (see tick) — the always-on cost bound
        self._bucket_s = self.window_s / 64.0
        self._clock = clock
        self._lock = threading.Lock()
        self._stack: list = []          # open sections, see _Section
        self._cur: Dict[str, float] = {}
        self._sections: Dict[str, _Section] = {}
        self._iter_start: Optional[float] = None
        self._gc_start: Optional[float] = None
        # (t_end, wall_s, {phase: seconds}) per completed iteration
        self._ring: deque = deque()
        self._m_compiles = registry.counter(
            "serving_jit_compiles_total",
            "XLA backend compiles observed on the engine loop (a "
            "post-hot-swap/scale-up storm is visible here instead of "
            "masquerading as decode latency)").labels()
        self._m_compile_s = registry.histogram(
            "serving_jit_compile_seconds",
            "wall time per XLA backend compile on the engine loop"
            ).labels()
        self._m_iterations = registry.counter(
            "serving_loop_iterations_total",
            "engine-loop iterations closed (tick to tick; a server "
            "loop's passes with nothing to step included)").labels()
        self._f_phase_s = registry.counter(
            "serving_loop_phase_seconds_total",
            "engine-loop wall seconds per phase, exclusive of nested "
            "sections (idle = no section claimed them); two scrapes "
            "give a window's split", labels=("phase",))
        self._f_slow = registry.counter(
            "serving_loop_slow_iterations_total",
            f"engine-loop iterations longer than {SLOW_ITERATION_S:g} s,"
            f" by the phase that held most of each", labels=("phase",))
        self._f_slow_s = registry.counter(
            "serving_loop_slow_iteration_seconds_total",
            f"wall seconds of the engine-loop iterations longer than "
            f"{SLOW_ITERATION_S:g} s, by the phase that held most of "
            f"each", labels=("phase",))
        # every phase's series exists from the start, so that a scrape
        # reads 0 and not nothing before the first slow iteration
        for fam in (self._f_phase_s, self._f_slow, self._f_slow_s):
            for ph in PHASES:
                fam.labels(phase=ph)
        ref = weakref.ref(self)
        fam = registry.gauge(
            "serving_loop_utilization",
            "fraction of recent engine-loop wall time spent per phase "
            "(rolling window; phases sum to <= 1, remainder = idle)",
            labels=("phase",))
        for ph in PHASES:
            fam.labels(phase=ph).set_function(
                lambda ph=ph: (p.utilization().get(ph, 0.0)
                               if (p := ref()) is not None else 0.0))
        if track_jit:
            _install_jax_listener()
        _install_gc_hook()

    # ------------------------------------------------------------ driving
    def tick(self) -> None:
        """Close the previous iteration (its wall time runs up to NOW,
        so inter-iteration idle lands in it) and open a new one. Also
        binds this thread to this profiler for compile and collection
        attribution.

        Closing an iteration adds its per-phase seconds (and ``idle``,
        the wall time no section claimed) to the monotone counters,
        and an iteration longer than :data:`SLOW_ITERATION_S` to the
        slow-iteration record.

        Iterations AGGREGATE into coarse time buckets (window/64): a
        kHz engine loop folds ~thousands of iterations into each
        bucket instead of ringing one dict per iteration — per-step
        the common case is a few float adds into the open bucket, and
        the ring stays ~64 entries whatever the step rate.

        Threading contract: :meth:`tick` / :meth:`section` /
        :meth:`record_compile` belong to the ONE thread driving the
        engine loop (the engine itself is serialized by its owner —
        the server's lock — so this adds no new requirement); only
        the bucket ring is locked."""
        now = self._clock()
        # the closed iteration's split leaves with a swap: a collection
        # that strikes while it is folded below (any allocation can
        # start one) claims into the NEW iteration's dict, never into
        # one that is being iterated
        cur, self._cur = self._cur, {}
        # an iteration needs a start: sections recorded before the
        # first tick (a direct-submit admission before the loop
        # started) have no wall to attribute against and are dropped
        # (their compiles stayed counted on the jit series)
        if self._iter_start is not None and now > self._iter_start:
            wall = now - self._iter_start
            with self._lock:
                ring = self._ring
                # bucket = [t_start, t_end, wall, iters, {phase: s}]
                if ring and now - ring[-1][0] < self._bucket_s:
                    b = ring[-1]
                    b[1] = now
                    b[2] += wall
                    b[3] += 1
                    phases = b[4]
                    for ph, s in cur.items():
                        phases[ph] = phases.get(ph, 0.0) + s
                else:
                    ring.append([now - wall, now, wall, 1, dict(cur)])
                    self._prune_locked(now)
            cur["idle"] = max(0.0, wall - sum(cur.values()))
            for ph, s in cur.items():
                self._f_phase_s.labels(phase=ph).inc(s)
            self._m_iterations.inc()
            if wall > SLOW_ITERATION_S:
                self._record_slow(wall, cur)
        self._iter_start = now
        _tls.profiler = self

    def section(self, name: str) -> "_Section":
        """The reusable context manager for one span of the loop.
        ``name`` is a span of :data:`SPANS` (its phase comes from the
        table) or a bare phase name (span ``elephas.loop.<phase>``).
        The block's wall time, exclusive of nested sections and of
        compile and collection time recorded while it ran, goes to
        the phase; while a ``jax.profiler`` session runs the block is
        also a ``TraceAnnotation`` of that name. One `_Section` object
        per name, created on first use and reused forever: a plain
        ``__enter__``/``__exit__`` pair costs a fraction of a
        ``@contextmanager`` generator. A span never nests within
        itself on the single engine-loop thread (see :meth:`tick`), so
        reuse is safe."""
        sec = self._sections.get(name)
        if sec is None:
            if name in SPANS:
                sec = _Section(self, name, SPANS[name])
            else:
                sec = _Section(self, f"elephas.loop.{name}", name)
            self._sections[name] = sec
        return sec

    def annotate(self, name: str, **metadata) -> None:
        """Attach ``metadata`` (a request's ``rid``, its token counts)
        to the innermost open section named ``name`` as the trace
        span's arguments. Without a profiler session: the flag check."""
        if not _tracing():
            return
        for section, _, _, span in reversed(self._stack):
            if section._name == name:
                if span is not None:
                    span.set_metadata(**metadata)
                return

    def open_phase(self):
        """``(phase, seconds it has been open)`` of the innermost open
        section that has a phase, or None: the stall watchdog's
        attribution. Racy by design (read from another thread)."""
        now = self._clock()
        for section, start, _, _ in reversed(self._stack):
            if section._phase is not None:
                return section._phase, max(0.0, now - start)
        return None

    def record_compile(self, seconds: float) -> None:
        """One XLA compile observed (the JAX listener's entry point;
        callable directly by tests): counted, histogrammed, attributed
        to the ``jit`` phase and excluded from the enclosing section."""
        seconds = float(seconds)
        self._m_compiles.inc()
        self._m_compile_s.observe(seconds)
        self._claim("jit", seconds)

    def record_gc(self, seconds: float) -> None:
        """One collection of Python's garbage collector on this thread
        (the ``gc.callbacks`` hook's entry point; callable directly by
        tests): attributed to the ``gc`` phase and excluded from the
        enclosing section, as a compile is."""
        self._claim("gc", float(seconds))

    def _claim(self, phase: str, seconds: float) -> None:
        self._cur[phase] = self._cur.get(phase, 0.0) + seconds
        if self._stack:
            self._stack[-1][2] += seconds

    def _record_slow(self, wall: float, split: Dict[str, float]) -> None:
        phase = max(split, key=split.get)
        self._f_slow.labels(phase=phase).inc()
        self._f_slow_s.labels(phase=phase).inc(wall)
        with use_context(new_root()):
            emit_event("engine.slow_iteration", wall_s=round(wall, 6),
                       phase=phase,
                       phases={ph: round(s, 6)
                               for ph, s in split.items() if s > 0})

    def _prune_locked(self, now: float) -> None:
        while self._ring and self._ring[0][1] < now - self.window_s:
            self._ring.popleft()

    def _window_locked(self, now: float):
        """(total wall, total iterations, {phase: seconds}) over the
        live buckets — call under the lock."""
        self._prune_locked(now)
        wall, iters = 0.0, 0
        phases: Dict[str, float] = {}
        for _, _, w, n, ph in self._ring:
            wall += w
            iters += n
            for k, s in ph.items():
                phases[k] = phases.get(k, 0.0) + s
        return wall, iters, phases

    # ------------------------------------------------------------- reading
    def utilization(self) -> Dict[str, float]:
        """``{phase: fraction}`` over the rolling window (``idle``
        included; empty window → all zeros)."""
        now = self._clock()
        with self._lock:
            wall, _, phases = self._window_locked(now)
        out = {ph: 0.0 for ph in PHASES}
        if wall <= 0:
            return out
        busy = 0.0
        for ph, s in phases.items():
            out[ph] = s / wall
        for ph, f in out.items():
            if ph != "idle":
                busy += f
        out["idle"] = max(0.0, 1.0 - busy)
        return out

    def snapshot(self) -> Dict:
        """JSON-able rolling-window summary for ``/stats``: the
        utilization split plus window coverage and compile totals."""
        now = self._clock()
        with self._lock:
            wall, iters, phases = self._window_locked(now)
        util = self.utilization()
        return {"window_s": self.window_s,
                "iterations": iters,
                "wall_s": round(wall, 6),
                "utilization": {ph: round(f, 6)
                                for ph, f in util.items()},
                "jit_compiles": int(self._m_compiles.value),
                "jit_compile_s": round(self._m_compile_s.sum, 6)}
