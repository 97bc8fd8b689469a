"""From a profiler trace (``.xplane.pb``) to numbers.

``jax.profiler.ProfileData`` reads the file with nothing but JAX. On a TPU
every chip is a plane ``/device:TPU:<n>`` whose line ``XLA Ops`` holds one
event per executed HLO operation and whose line ``XLA Modules`` holds one
event per executed program (``jit_<function>(<fingerprint>)``); host
threads are lines of the plane ``/host:CPU`` and carry the
``jax.profiler.TraceAnnotation`` spans. All times are nanoseconds on one
clock.

What is computed, per device and then averaged over the devices used:

- busy: the union of the ``XLA Ops`` intervals (an operation running);
  idle share = 1 - busy / window;
- time by program: durations on ``XLA Modules``, by program name;
- collectives: the union of the intervals of all-reduce / all-gather /
  reduce-scatter / collective-permute / all-to-all operations (on ``XLA
  Ops`` and, where they run asynchronously, from ``-start`` to ``-done``
  on ``Async XLA Ops``), and the part of it during which no other
  operation runs on that device;
- the ten kinds of operation (opcode and result shape, the layers'
  copies of one operation added up) that took most time of their own (a
  ``conditional`` or ``while`` less the operations that ran inside it),
  and the idle gaps grouped by the innermost host span over each gap's
  middle (the harness's ``chipbench.`` spans and the program's
  ``elephas.`` spans) and the program that ended the gap (what the
  device was waiting for).

The window is the span between the two marks the harness puts on the
host clock (``chipbench.trace_begin`` / ``chipbench.trace_end``) when the
trace holds them, else the span of the device events.
"""
import bisect
import glob
import os
import re
from collections import defaultdict

__all__ = ["COLLECTIVE", "Trace", "find_xplane", "load", "union_ns",
           "program_name"]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
BEGIN_MARK = "chipbench.trace_begin"
END_MARK = "chipbench.trace_end"
#: host annotations that are kept: the harness's and the program's spans
HOST_SPANS = ("chipbench.", "elephas.")
#: HLO operation names that move data between chips
COLLECTIVE = re.compile(
    r"^%?(all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all)", re.IGNORECASE)


def find_xplane(log_dir: str):
    """The newest ``*.xplane.pb`` the profiler wrote under ``log_dir``."""
    files = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def program_name(event_name: str) -> str:
    """``jit__step_paged(1234)`` -> ``jit__step_paged``."""
    return event_name.split("(", 1)[0]


def op_kind(event_name: str) -> str:
    """An operation's kind and result shape, so that the thirty-two
    copies of one layer-wise operation add up under one name:
    ``%copy.278 = bf16[32,8,256,16,128]{...} copy(...)`` -> ``copy
    bf16[32,8,256,16,128]``. A name that is not HLO text is kept, cut to
    80 characters."""
    _, found, rest = event_name.partition(" = ")
    shape = re.match(r"\(?(\w+\[[\d,]*\])", rest)
    opcode = re.search(r"[\)\}\]] ([\w\-]+)\(", rest)
    if not (found and shape and opcode):
        return event_name[:80]
    label = opcode.group(1)
    fused = re.match(r"^%?([a-z_\-]+?)_fusion[.\d]* =", event_name)
    if label == "fusion" and fused:
        label = f"fusion:{fused.group(1)}"
    return f"{label} {shape.group(1)}"


def union_ns(intervals) -> list:
    """Merge ``(start, end)`` intervals; returns the disjoint union."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged


def _total(intervals) -> int:
    return sum(end - start for start, end in intervals)


def _clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def own_ns(ops, lo, hi) -> list:
    """``[[name, nanoseconds]]`` for the operations of one ``XLA Ops``
    line inside ``lo..hi``: each operation's time less that of the
    operations that ran inside it (a ``conditional`` holds its branch's
    operations as events of the same line), so that the list adds up to
    the line's busy time and nothing counts twice."""
    out, stack = [], []                # stack: (end, index into out)
    for name, start, end in sorted(ops, key=lambda o: (o[1], -o[2])):
        start, end = max(start, lo), min(end, hi)
        if end <= start:
            continue
        while stack and stack[-1][0] < end:
            stack.pop()                # ended, or overlaps without holding
        if stack:
            out[stack[-1][1]][1] -= end - start
        out.append([name, end - start])
        stack.append((end, len(out) - 1))
    return out


def _subtract(a, b) -> list:
    """Parts of the disjoint sorted intervals ``a`` not covered by the
    disjoint sorted intervals ``b``."""
    out, j = [], 0
    for start, end in a:
        cur = start
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < end:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < end:
            out.append((cur, end))
    return out


class Trace:
    """Events of one trace: ``devices[n] = {"ops": [(name, start, end)],
    "async": [...], "modules": [...]}`` and ``host = [(name, start, end)]``
    (annotations whose name starts with one of ``HOST_SPANS``)."""

    def __init__(self, devices: dict, host: list, time_scales=()):
        self.devices = devices
        self.host = host
        #: the profiler's ``Time Scale Multiplier`` stats seen on the
        #: device's operations (1.0 on every machine met so far; logged
        #: because one cell's step reads 13.8-20.2 ms by the machine)
        self.time_scales = sorted(set(time_scales))
        self.window = self._window()

    def _window(self):
        marks = {name: (start, end) for name, start, end in self.host
                 if name in (BEGIN_MARK, END_MARK)}
        if BEGIN_MARK in marks and END_MARK in marks:
            lo, hi = marks[BEGIN_MARK][1], marks[END_MARK][0]
            inside = [1 for dev in self.devices.values()
                      for _, s, e in dev["ops"] if lo <= s and e <= hi]
            if hi > lo and inside:
                return lo, hi
        spans = [(s, e) for dev in self.devices.values()
                 for _, s, e in dev["ops"]]
        if not spans:
            return None
        return min(s for s, _ in spans), max(e for _, e in spans)

    # ----------------------------------------------------------- numbers
    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9 if self.window else 0.0

    def _busy(self, dev) -> list:
        lo, hi = self.window
        return union_ns(_clip([(s, e) for _, s, e in dev["ops"]], lo, hi))

    def busy_s(self) -> float:
        """Seconds an operation ran, averaged over the devices."""
        if not self.devices or not self.window:
            return 0.0
        return sum(_total(self._busy(d)) for d in self.devices.values()) \
            / len(self.devices) / 1e9

    def idle_share(self):
        if not self.window_s:
            return None
        return 1.0 - self.busy_s() / self.window_s

    def program_time(self) -> dict:
        """``{program: {"count": n, "seconds": s}}`` from ``XLA Modules``,
        executions that start inside the window, summed over devices and
        divided by their number."""
        out = defaultdict(lambda: {"count": 0.0, "seconds": 0.0})
        if not self.window:
            return {}
        lo, hi = self.window
        n = len(self.devices)
        for dev in self.devices.values():
            for name, start, end in dev["modules"]:
                if lo <= start < hi:
                    slot = out[program_name(name)]
                    slot["count"] += 1.0 / n
                    slot["seconds"] += (min(end, hi) - start) / 1e9 / n
        return dict(out)

    def collectives(self) -> dict:
        """Seconds with a collective operation running, and the part with
        no other operation beside it, averaged over the devices."""
        if not self.devices or not self.window:
            return {"seconds": 0.0, "exposed_seconds": 0.0}
        lo, hi = self.window
        total = exposed = 0
        for dev in self.devices.values():
            coll = union_ns(_clip(
                [(s, e) for name, s, e in dev["ops"] + dev.get("async", [])
                 if COLLECTIVE.match(name)], lo, hi))
            rest = union_ns(_clip([(s, e) for name, s, e in dev["ops"]
                                   if not COLLECTIVE.match(name)], lo, hi))
            total += _total(coll)
            exposed += _total(_subtract(coll, rest))
        n = len(self.devices)
        return {"seconds": total / n / 1e9,
                "exposed_seconds": exposed / n / 1e9}

    def top_ops(self, limit: int = 10) -> list:
        """``[[operation, seconds], ...]``: most device time of its own
        first (:func:`own_ns`), on the first device (they all run the
        same program)."""
        if not self.devices or not self.window:
            return []
        lo, hi = self.window
        dev = self.devices[min(self.devices)]
        by_name = defaultdict(int)
        for name, ns in own_ns(dev["ops"], lo, hi):
            by_name[op_kind(name)] += ns
        ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:limit]
        return [[name, ns / 1e9] for name, ns in ranked]

    def idle_gaps(self, limit: int = 10) -> list:
        """``[[what ended the gap, seconds], ...]``: the device's idle time
        on the first device, grouped by the program whose start ended
        each gap (so: what the device was waiting for), behind the
        innermost host span (the latest to start) over the gap's middle
        when there is one: ``before elephas.loop.prefill.row_init >
        jit_convert_element_type``."""
        if not self.devices or not self.window:
            return []
        lo, hi = self.window
        dev = self.devices[min(self.devices)]
        busy = self._busy(dev)
        gaps = _subtract([(lo, hi)], busy)
        starts = sorted((s, program_name(name))
                        for name, s, _ in dev["modules"])
        spans = sorted((s, e, n) for n, s, e in self.host
                       if n not in (BEGIN_MARK, END_MARK))
        span_starts = [s for s, _, _ in spans]
        start_ns = [s for s, _ in starts]
        by_cause = defaultdict(int)
        for gap_start, gap_end in gaps:
            at = bisect.bisect_left(start_ns, gap_end - 1)
            cause = starts[at][1] if at < len(starts) else "end of window"
            mid = (gap_start + gap_end) // 2
            k = bisect.bisect_right(span_starts, mid) - 1
            while k >= 0 and spans[k][1] <= mid:
                k -= 1
            if k >= 0:
                cause = f"{spans[k][2]} > {cause}"
            by_cause[f"before {cause}"] += gap_end - gap_start
        ranked = sorted(by_cause.items(), key=lambda kv: -kv[1])[:limit]
        return [[name, ns / 1e9] for name, ns in ranked]

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}


def load(path: str, device_ids=None) -> Trace:
    """Read an ``.xplane.pb``. ``device_ids`` keeps only those chips."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host, scales = {}, [], []
    for plane in data.planes:
        match = DEVICE_PLANE.match(plane.name)
        if match:
            number = int(match.group(1))
            if device_ids is not None and number not in device_ids:
                continue
            dev = {"ops": [], "async": [], "modules": []}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    key = "ops"
                elif line.name == ASYNC_LINE:
                    key = "async"
                elif line.name == MODULES_LINE:
                    key = "modules"
                else:
                    continue
                for ev in line.events:
                    start = int(ev.start_ns)
                    dev[key].append((ev.name, start,
                                     start + int(ev.duration_ns)))
                    if key == "ops" and len(dev[key]) % 5000 == 1:
                        scales += [value for name, value in ev.stats
                                   if name == "Time Scale Multiplier"]
            if dev["ops"]:
                devices[number] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_SPANS):
                        start = int(ev.start_ns)
                        host.append((ev.name, start,
                                     start + int(ev.duration_ns)))
    return Trace(devices, host, scales)
