"""What one run leaves for the metric readers.

A driver fills an :class:`Evidence`; each metric's reader
(``chipbench/readers/<reader>.py``) takes what it needs from it and
returns a number, or None when what it reads is not there (a reader of
the device trace in a run that was not traced, say).
"""
import re

__all__ = ["Evidence", "parse_prometheus"]

_SAMPLE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})?\s+([-+0-9.eEinfNa]+)\s*$")


def parse_prometheus(text: str) -> dict:
    """Prometheus exposition text -> ``{series name: sum over its label
    sets}`` (histograms keep their ``_sum`` / ``_count`` series; buckets
    are left out)."""
    out = {}
    for line in (text or "").splitlines():
        if not line or line[0] == "#":
            continue
        match = _SAMPLE.match(line)
        if not match or match.group(1).endswith("_bucket"):
            continue
        try:
            value = float(match.group(3))
        except ValueError:
            continue
        out[match.group(1)] = out.get(match.group(1), 0.0) + value
    return out


class Evidence:
    def __init__(self, run):
        self.run = run
        self.seconds = run.seconds
        self.window = None            # [start, end], time.monotonic()
        self.setup_s = None
        self.samples = []             # the load generator's, per request
        self.prom_start = None        # /metrics text at the window's edges
        self.prom_end = None
        self.polls = []               # [(monotonic, /metrics text)]
        self.compiles_in_window = None
        self.trace_dir = None
        self.trace_window = None      # [start, end] of the profile
        self.sizes = None             # the configuration's sizes as run
        self.param_dtype = None
        self.engine_sizes = None
        self.seq_len = None           # train: tokens a row
        self.epoch_ends = []          # train: monotonic at each epoch end
        self.tokens_per_epoch = None
        self.chips = run.cell["chips"]
        self._trace = False

    @property
    def trace(self):
        """The reduced device trace, or None (not traced; no device
        plane, as on the CPU)."""
        if self._trace is False:
            from chipbench import trace_reduce

            self._trace = None
            path = (trace_reduce.find_xplane(self.trace_dir)
                    if self.trace_dir else None)
            if path:
                loaded = trace_reduce.load(path)
                if loaded.devices:
                    self._trace = loaded
        return self._trace
