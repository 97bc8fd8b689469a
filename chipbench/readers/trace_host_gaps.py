"""Reader ``trace_host_gaps``: the device's idle gaps, named by what the
host was doing in each.

The program's engine loop writes its sections into the profiler's trace
as host spans named ``elephas.<layer>.<what>`` (``elephas_tpu/obs/
profiler.py`` ``SPANS``), on the clock of the device's ``XLA Ops`` line.
This reader takes the ``elephas.`` events of the thread line that holds
``elephas.loop.step``, the window and the first device's busy intervals
from the reduced trace, and charges every nanosecond of every idle gap to
the innermost span covering it (:func:`attribute`), the rest to
``unattributed``. A span is known by its path from the outermost span
down, joined by ``/``: ``elephas.loop.step/elephas.loop.admit/
elephas.loop.admit.request/elephas.loop.prefill``.

Arguments: ``match`` (a regular expression searched in the path) with
``per`` = ``decode_step`` (milliseconds of matching idle time per
execution of ``step_paged`` in the window) or ``admission`` (per
``elephas.loop.admit.request`` span that starts in the window); or
``mode`` = ``unattributed_share`` (percent of the window's idle time
under no ``elephas.`` span: the instrumentation's own coverage).

None when the run has no device trace (the CPU rehearsal, a run that
was not traced), and for a ``match`` when the trace holds no engine-loop
line (a program without the spans). Once per run the whole table goes
to the log: span, idle seconds, spans charged.
"""
import re
from collections import defaultdict

from chipbench import device, trace_reduce
from chipbench.readers import trace_program_time

SPAN_PREFIX = "elephas."
LOOP_SPAN = "elephas.loop.step"
ADMISSION_SPAN = "elephas.loop.admit.request"
UNATTRIBUTED = "unattributed"
#: ``per`` -> the count of the run's table that divides the idle time
PER = {"decode_step": "steps", "admission": "admissions"}


def attribute(gaps, spans) -> dict:
    """Charge idle time to host spans.

    ``gaps``: disjoint ``(start, end)`` intervals, sorted. ``spans``:
    ``(name, start, end)`` of one thread, so properly nested or disjoint.
    Returns ``{path: [nanoseconds, spans charged]}``: each nanosecond of
    each gap goes to the innermost span covering it, under the span's
    path from the outermost span down; nanoseconds under no span go to
    ``unattributed`` (counted once per gap that has any).
    """
    # the innermost span over time, as disjoint segments: a sweep over
    # the spans in start order with the open spans on a stack
    segments = []                 # (start, end, path, span number)
    stack = []                    # (end, path, span number, covered to)

    def close(until):
        """Pop every span that ends by ``until``, emitting the tail of
        each that no child covered."""
        while stack and stack[-1][0] <= until:
            end, path, number, at = stack.pop()
            if at < end:
                segments.append((at, end, path, number))
            if stack:
                stack[-1][3] = max(stack[-1][3], end)

    ordered = sorted(spans, key=lambda s: (s[1], -s[2]))
    for number, (name, start, end) in enumerate(ordered):
        close(start)
        if stack:
            # a child that outlasts its parent is cut to it (clock
            # jitter of a nanosecond must not break the nesting)
            end = min(end, stack[-1][0])
            if stack[-1][3] < start:
                segments.append((stack[-1][3], start, stack[-1][1],
                                 stack[-1][2]))
            stack[-1][3] = max(stack[-1][3], start)
        if end <= start:
            continue
        path = f"{stack[-1][1]}/{name}" if stack else name
        stack.append([end, path, number, start])
    close(float("inf"))
    segments.sort()

    charged = defaultdict(lambda: [0, set()])
    at = 0
    for gap_number, (gap_start, gap_end) in enumerate(gaps):
        while at < len(segments) and segments[at][1] <= gap_start:
            at += 1
        k, covered = at, 0
        while k < len(segments) and segments[k][0] < gap_end:
            start, end, path, number = segments[k]
            ns = min(end, gap_end) - max(start, gap_start)
            if ns > 0:
                charged[path][0] += ns
                charged[path][1].add(number)
                covered += ns
            k += 1
        if gap_end - gap_start > covered:
            charged[UNATTRIBUTED][0] += gap_end - gap_start - covered
            charged[UNATTRIBUTED][1].add(("gap", gap_number))
    return {path: [ns, len(who)] for path, (ns, who) in charged.items()}


def loop_spans(path: str) -> list:
    """``[(name, start_ns, end_ns)]``: the ``elephas.`` events of the
    host thread line that holds ``elephas.loop.step`` (the most of them,
    if several do); empty when no line does."""
    from jax.profiler import ProfileData

    best = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = [(ev.name, int(ev.start_ns),
                       int(ev.start_ns) + int(ev.duration_ns))
                      for ev in line.events
                      if ev.name.startswith(SPAN_PREFIX)]
            steps = sum(name == LOOP_SPAN for name, _, _ in events)
            if steps > sum(name == LOOP_SPAN for name, _, _ in best):
                best = events
    return best


def idle_gaps(trace) -> list:
    """The first device's idle intervals inside the window (what
    ``trace_idle`` reads as the idle share, interval by interval)."""
    lo, hi = trace.window
    dev = trace.devices[min(trace.devices)]
    busy = trace_reduce.union_ns(trace_reduce._clip(
        [(s, e) for _, s, e in dev["ops"]], lo, hi))
    return trace_reduce._subtract([(lo, hi)], busy)


def analyse(evidence):
    """The run's table, computed once and kept on the evidence:
    ``{"charged": attribute(...), "idle_ns", "admissions", "steps",
    "has_loop"}``; None without a device trace."""
    cached = getattr(evidence, "_host_gaps", False)
    if cached is not False:
        return cached
    trace = evidence.trace
    result = None
    if trace is not None and trace.window:
        spans = loop_spans(trace_reduce.find_xplane(evidence.trace_dir))
        lo, hi = trace.window
        gaps = idle_gaps(trace)
        steps, _ = trace_program_time.matching(trace, "step_paged")
        result = {
            "charged": attribute(gaps, spans),
            "idle_ns": sum(e - s for s, e in gaps),
            "steps": steps,
            "admissions": sum(1 for name, s, _ in spans
                              if name == ADMISSION_SPAN and lo <= s < hi),
            "has_loop": bool(spans)}
        log_table(result)
    evidence._host_gaps = result
    return result


def log_table(result):
    """One log line: the window's idle time by innermost span."""
    by_name = defaultdict(lambda: [0, 0])
    for path, (ns, count) in result["charged"].items():
        leaf = path.rsplit("/", 1)[-1]
        by_name[leaf][0] += ns
        by_name[leaf][1] += count
    rows = sorted(by_name.items(), key=lambda kv: -kv[1][0])
    table = ", ".join(f"{name} {ns / 1e9:.6f}s x{count}"
                      for name, (ns, count) in rows)
    device.log("host_gaps", f"device idle {result['idle_ns'] / 1e9:.6f}s "
               f"over {result['steps']:g} decode steps and "
               f"{result['admissions']} admissions, by innermost host "
               f"span: {table or 'none'}")


def read(evidence, match: str = None, per: str = None, mode: str = None):
    result = analyse(evidence)
    if result is None:
        return None
    charged = result["charged"]
    if mode == "unattributed_share":
        if not result["idle_ns"]:
            return None
        return 100.0 * charged.get(UNATTRIBUTED, [0])[0] / result["idle_ns"]
    if mode is not None or per not in PER:
        raise ValueError(f"unknown mode {mode!r} or per {per!r}")
    if not result["has_loop"]:
        return None
    pattern = re.compile(match)
    ns = sum(v[0] for path, v in charged.items()
             if path != UNATTRIBUTED and pattern.search(path))
    count = result[PER[per]]
    return ns / 1e6 / count if count else None
