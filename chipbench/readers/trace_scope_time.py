"""Reader ``trace_scope_time``: device time under a ``jax.named_scope``.

The profiler's ``.xplane.pb`` names every executed HLO operation's
origin in its event metadata (stat ``tf_op``: ``jit(_step_paged)/.../
elephas.moe.experts/ragged_dot``), which ``jax.profiler.ProfileData``
does not expose. This file reads the protobuf wire format itself (the
six message types of ``xplane.proto`` it needs, nothing imported) and
adds up, on the first device's ``XLA Ops`` line and inside the trace's
window, the union of the intervals of the operations whose origin
matches a regular expression: a ``conditional`` and the operations
inside it, both carrying the scope, count once. With ``program`` only
operations that start inside an execution of a program of that name
(the ``XLA Modules`` line) count: the grouped matmuls XLA:TPU makes of
``ragged_dot`` lose their scope (their origin reads ``ragged-dot-none:``)
and are told apart by the program they run in.

``mode``: ``ms_per_execution`` (milliseconds a scope takes per execution
of the programs ``program`` names) or ``share_of_busy`` (percent of the
device's busy time). None when the run has no device trace or no
operation matches (a program without the scope).
"""
import bisect
import re

from chipbench import trace_reduce
from chipbench.readers import trace_program_time

DEVICE_PLANE = trace_reduce.DEVICE_PLANE


# ------------------------------------------------------- protobuf wire
def _varint(buf, at):
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if not byte & 0x80:
            return value, at
        shift += 7


def fields(buf):
    """``(field number, value)`` of one message: ints for varints,
    memoryviews for length-delimited fields; fixed-width fields are
    skipped (none is needed here)."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, at = _varint(buf, at)
            yield number, value
        elif wire == 2:
            size, at = _varint(buf, at)
            yield number, buf[at:at + size]
            at += size
        elif wire == 1:
            at += 8
        elif wire == 5:
            at += 4
        else:
            raise ValueError(f"unknown wire type {wire}")


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_entry(buf):
    key = value = None
    for number, item in fields(buf):
        if number == 1:
            key = item
        elif number == 2:
            value = item
    return key, value


def device_ops(path: str, device: int = None,
               line_name: str = trace_reduce.OPS_LINE, names: bool = False):
    """``[(origin, start_ns, end_ns)]`` of the ``XLA Ops`` line (or
    ``line_name``) of one device plane (the lowest-numbered unless
    ``device`` is given); ``origin`` is the operation's ``tf_op`` (''
    when it has none). With ``names`` each entry leads with the
    operation's name: ``(name, origin, start_ns, end_ns)``."""
    with open(path, "rb") as fh:
        space = memoryview(fh.read())
    planes = {}
    for number, plane in fields(space):
        if number != 1:
            continue
        name = next((_text(v) for n, v in fields(plane) if n == 2), "")
        match = DEVICE_PLANE.match(name)
        if match:
            planes[int(match.group(1))] = plane
    if not planes:
        return []
    plane = planes[min(planes) if device is None else device]
    stat_names, metadata, lines = {}, {}, []
    for number, item in fields(plane):
        if number == 5:                          # stat_metadata
            key, value = _map_entry(item)
            stat_names[key] = next(
                (_text(v) for n, v in fields(value) if n == 2), "")
        elif number == 4:                        # event_metadata
            metadata.__setitem__(*_map_entry(item))
        elif number == 3:
            lines.append(item)
    wanted = {key for key, name in stat_names.items() if name == "tf_op"}
    origin, op_names = {}, {}
    for key, meta in metadata.items():
        for number, stat in fields(meta):
            if number == 2 and names:
                op_names[key] = _text(stat)
            if number != 5:
                continue
            parts = dict(fields(stat))
            if parts.get(1) in wanted:
                if 5 in parts:
                    origin[key] = _text(parts[5])
                elif 7 in parts:
                    origin[key] = stat_names.get(parts[7], "")
    out = []
    for line in lines:
        parts = list(fields(line))
        if next((_text(v) for n, v in parts if n == 2), "") != line_name:
            continue
        base_ns = next((v for n, v in parts if n == 3), 0)
        for number, event in parts:
            if number != 4:
                continue
            ev = dict(fields(event))
            start = base_ns + ev.get(2, 0) // 1000
            entry = (origin.get(ev.get(1), ""), start,
                     start + ev.get(3, 0) // 1000)
            out.append((op_names.get(ev.get(1), "?"), *entry) if names
                       else entry)
    return out


# ------------------------------------------------------------- reading
def scope_seconds(evidence, match: str, program: str = None):
    """Seconds of the window in which an operation whose origin matches
    ran on the first device (inside an execution of ``program``, if
    given), or None."""
    trace = evidence.trace
    if trace is None or not trace.window or not evidence.trace_dir:
        return None
    cached = getattr(evidence, "_device_ops", None)
    if cached is None:
        path = trace_reduce.find_xplane(evidence.trace_dir)
        cached = device_ops(path) if path else []
        evidence._device_ops = cached
    pattern = re.compile(match)
    lo, hi = trace.window
    picked = [(s, e) for origin, s, e in cached
              if e > lo and s < hi and pattern.search(origin)]
    if program is not None:
        runs = sorted((s, e) for name, s, e in
                      trace.devices[min(trace.devices)]["modules"]
                      if re.search(program, name))
        starts = [s for s, _ in runs]

        def inside(at):
            k = bisect.bisect_right(starts, at) - 1
            return k >= 0 and at < runs[k][1]

        picked = [(s, e) for s, e in picked if inside(s)]
    if not picked:
        return None
    return sum(e - s for s, e in trace_reduce.union_ns(
        [(max(s, lo), min(e, hi)) for s, e in picked])) / 1e9


def read(evidence, match: str, mode: str, program: str = None):
    seconds = scope_seconds(evidence, match, program)
    if seconds is None:
        return None
    if mode == "ms_per_execution":
        count, _ = trace_program_time.matching(evidence.trace, program)
        return 1e3 * seconds / count if count else None
    if mode == "share_of_busy":
        busy = evidence.trace.busy_s()
        return 100.0 * seconds / busy if busy else None
    raise ValueError(f"unknown mode {mode!r}")
