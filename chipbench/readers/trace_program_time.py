"""Reader ``trace_program_time``: device time by program, from the
trace's ``XLA Modules`` line. Programs are picked by a regular
expression over their names (``jit__step_paged`` ...).

``mode``: ``ms_per_execution`` (device milliseconds of one execution,
mean), or ``share_of_busy`` (their device time over the device's busy
time, percent).
"""
import re


def matching(trace, match: str):
    pattern = re.compile(match)
    picked = [v for name, v in trace.program_time().items()
              if pattern.search(name)]
    return (sum(v["count"] for v in picked),
            sum(v["seconds"] for v in picked))


def read(evidence, match: str, mode: str):
    trace = evidence.trace
    if trace is None:
        return None
    count, seconds = matching(trace, match)
    if mode == "ms_per_execution":
        return 1e3 * seconds / count if count else None
    if mode == "share_of_busy":
        busy = trace.busy_s()
        return 100.0 * seconds / busy if busy else None
    raise ValueError(f"unknown mode {mode!r}")
