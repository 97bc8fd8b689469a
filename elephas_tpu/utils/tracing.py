"""Step timing and profiler hooks.

The reference has no tracing/profiling at all (progress reporting is bare
``print``, SURVEY.md §5); this module provides real step timing plus
``jax.profiler`` trace capture as the upgrade the survey calls for.

:class:`StepTimer` is a thin adapter over the observability layer's
:class:`~elephas_tpu.obs.Histogram`: every recorded step ALSO lands in
the ``training_step_duration_seconds`` histogram of the process default
registry (or an injected one), so training throughput shows up on the
same ``/metrics`` scrape as serving and parameter-plane series, and its
:meth:`StepTimer.summary` percentiles use the registry's shared
nearest-rank :func:`~elephas_tpu.obs.percentile` helper (the old
``durations[n // 2]`` indexing reported the max as the p50 for n=2).
"""
import contextlib
import time
from typing import Dict, List, Optional

from ..obs.metrics import default_registry, percentile


class StepTimer:
    """Collects per-step wall times and derives throughput.

    :param metric: histogram family name the steps are published under
    :param registry: destination registry (process default if None)

    The full ``durations`` list stays on the instance — the per-fit
    summary must be exact for THIS timer even though the registry
    histogram pools every timer in the process (labeled telemetry is
    additive; the summary is not).
    """

    def __init__(self, metric: str = "training_step_duration_seconds",
                 registry=None):
        self.durations: List[float] = []
        self._start: Optional[float] = None
        reg = registry if registry is not None else default_registry()
        self._hist = reg.histogram(
            metric, "training step wall time (StepTimer)")

    def __enter__(self):
        self._start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        duration = time.perf_counter() - self._start
        self.durations.append(duration)
        self._hist.observe(duration)
        self._start = None
        return False

    def start(self):
        self._start = time.perf_counter()

    def stop(self):
        self.__exit__()

    @property
    def total(self) -> float:
        return sum(self.durations)

    @property
    def mean(self) -> float:
        return self.total / len(self.durations) if self.durations else 0.0

    def summary(self) -> Dict[str, float]:
        if not self.durations:
            return {"steps": 0}
        return {
            "steps": len(self.durations),
            "total_s": self.total,
            "mean_s": self.mean,
            # nearest-rank percentiles (shared with Histogram.quantile)
            "p50_s": percentile(self.durations, 0.5),
            "p99_s": percentile(self.durations, 0.99),
        }

    def samples_per_sec(self, samples_per_step: int) -> float:
        return samples_per_step / self.mean if self.mean else 0.0


@contextlib.contextmanager
def profiler_trace(logdir: Optional[str] = None):
    """Capture a ``jax.profiler`` trace (viewable in TensorBoard/Perfetto)
    around the wrapped block; no-op when ``logdir`` is None."""
    if logdir is None:
        yield
        return
    import jax

    with jax.profiler.trace(logdir):
        yield

