"""The device a run is on, the compile cache, and compile accounting."""
import sys
import threading
import time

__all__ = ["CompileWatch", "log", "start_trace", "stop_trace", "NoChip", "configure_cache", "device_report",
           "peak_memory_bytes", "require_devices"]


#: the zero of the log's stamps; ``run.py`` sets it to the process start
LOG_ORIGIN = [time.monotonic()]


def log(tag: str, message: str):
    """One line of the run's log, stamped with the seconds since the
    harness was imported (set-up is most of what a check costs: the log
    says where it goes)."""
    print(f"[{tag} +{time.monotonic() - LOG_ORIGIN[0]:6.1f}s] {message}", flush=True)


class NoChip(SystemExit):
    """JAX found no accelerator, or fewer chips than the cell asks for:
    the run ends with a non-zero code and prints no result line."""

    def __init__(self, message: str):
        print(f"chipbench: {message}", file=sys.stderr, flush=True)
        super().__init__(3)


def configure_cache(rehearse: bool) -> str:
    """JAX's persistent compile cache at the program's fixed place
    (``JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``),
    holding every program however small: a serving cell has some forty
    programs, and a run after the first must compile none of them. The
    CPU rehearsal keeps no cache (nothing of it is timed)."""
    import jax

    if rehearse:
        return ""
    from elephas_tpu.utils.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


def require_devices(chips: int, rehearse: bool) -> list:
    """The devices the cell runs on: the first ``chips`` TPU devices. No
    fallback: anything else ends the run (:class:`NoChip`), except under
    an explicit ``--rehearse``, which takes CPU devices and says so in
    every line it prints."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if rehearse:
        if platform != "cpu":
            raise SystemExit("--rehearse is the CPU rehearsal; JAX found "
                             f"{platform!r}")
    elif platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found platform {platform!r} "
                     f"({len(devices)} device(s)); nothing ran")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chip(s), JAX found "
                     f"{len(devices)}; nothing ran")
    return devices[:chips]


def device_report(devices: list) -> dict:
    """Platform, kind and count as JAX reports them."""
    import jax

    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(jax.devices())}


def peak_memory_bytes(devices: list) -> int:
    """Peak bytes in use on the fullest device (0 where the backend does
    not report it, as on the CPU)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def _mark(name: str) -> float:
    """A zero-length host span in the profiler's trace, and its instant."""
    import jax

    with jax.profiler.TraceAnnotation(name):
        return time.monotonic()


def start_trace(workdir: str) -> tuple:
    """Start the profiler (device and host spans, no Python tracer, no
    HLO dump) into a new directory under ``workdir`` and put the mark
    ``trace_reduce`` takes as the traced window's start. Returns
    ``(directory, instant of the mark)``."""
    import tempfile

    import jax

    from chipbench.trace_reduce import BEGIN_MARK

    trace_dir = tempfile.mkdtemp(prefix="trace-", dir=workdir)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    options.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    return trace_dir, _mark(BEGIN_MARK)


def stop_trace() -> float:
    """Put the window's end mark and stop the profiler; returns the
    mark's instant."""
    import jax

    from chipbench.trace_reduce import END_MARK

    at = _mark(END_MARK)
    jax.profiler.stop_trace()
    return at


class CompileWatch:
    """Compilations as JAX itself reports them (monitoring events; copied
    from ``chip_smoke.py`` ``Phases``): seconds spent in
    ``backend_compile`` (which covers the persistent-cache lookup), cache
    hits and misses, and the instant of every compile, so that the ones
    inside the measured window can be counted. They must be none."""

    def __init__(self):
        import jax.monitoring

        self._lock = threading.Lock()
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        self.compile_times = []          # time.monotonic() of each compile
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.compile_s += duration
                self.compile_times.append(time.monotonic())

    def _on_event(self, event, **_):
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.cache_misses += 1

    def compiles_between(self, t0: float, t1: float) -> int:
        with self._lock:
            return sum(t0 <= t < t1 for t in self.compile_times)

    def summary(self) -> dict:
        return {"compile_s": round(self.compile_s, 3),
                "programs": len(self.compile_times),
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}
