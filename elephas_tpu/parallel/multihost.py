"""Multi-host (DCN) execution helpers.

Scaling past one TPU host follows the single-controller JAX recipe
(SURVEY.md §7 step 7): every host runs the same program,
``jax.distributed.initialize`` wires the processes together over DCN, the
global mesh spans all hosts' devices (collectives ride ICI within a slice
and DCN across), and the parameter server for async modes binds on the
coordinator host (process 0) — workers reach it via
``ELEPHAS_TPU_MASTER_IP``.

Data is host-sharded: each process loads only its slice of the dataset
(:func:`host_local_slice`) and builds global arrays with
``jax.make_array_from_process_local_data``.
"""
import os
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec


def initialize_multihost(coordinator_address: Optional[str] = None,
                         num_processes: Optional[int] = None,
                         process_id: Optional[int] = None):
    """Initialize the JAX distributed runtime (idempotent).

    Arguments default to the standard env vars
    (``JAX_COORDINATOR_ADDRESS``, ``JAX_NUM_PROCESSES``,
    ``JAX_PROCESS_ID``) and to TPU-pod auto-detection when none are set.
    """
    # NOTE: the guard must not touch the XLA backend — jax.process_count()
    # would initialize it, after which jax.distributed.initialize() fails.
    if jax.distributed.is_initialized():
        return
    coordinator_address = coordinator_address or os.environ.get(
        "JAX_COORDINATOR_ADDRESS")
    if coordinator_address is None and num_processes is None:
        try:
            jax.distributed.initialize()  # TPU-pod auto-detection
        except Exception:
            pass  # single-process run
        return
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes or int(os.environ.get("JAX_NUM_PROCESSES", "1")),
        process_id=process_id if process_id is not None
        else int(os.environ.get("JAX_PROCESS_ID", "0")))


def ensure_multihost() -> bool:
    """Entry-point hook for :meth:`TPUModel.fit`: initialize the JAX
    distributed runtime when the standard env vars say this is a
    multi-process launch, and report whether the run spans processes.

    Deliberately env-gated — a plain single-host run must not trigger
    coordinator auto-detection (which could stall probing for a pod).

    Best-effort by construction: ``jax.distributed.initialize`` must run
    before anything touches the XLA backend, and building/compiling a
    model already does. If the backend beat us to it, warn with the fix
    (call :func:`initialize_multihost` — or ``elephas_tpu`` import-time
    auto-init — before building models) instead of crashing the fit.
    """
    if (os.environ.get("JAX_COORDINATOR_ADDRESS")
            or os.environ.get("JAX_NUM_PROCESSES")):
        try:
            initialize_multihost()
        except RuntimeError as err:
            import warnings

            warnings.warn(
                "JAX_COORDINATOR_ADDRESS/JAX_NUM_PROCESSES are set but the "
                "distributed runtime could not be initialized here "
                f"({err}); jax.distributed.initialize must run before any "
                "JAX backend use. Import elephas_tpu (which auto-"
                "initializes from these env vars) or call "
                "elephas_tpu.parallel.initialize_multihost() before "
                "building models. Continuing single-process.",
                RuntimeWarning, stacklevel=2)
    try:
        return jax.process_count() > 1
    except Exception:
        return False


def maybe_initialize_from_env():
    """Import-time hook: initialize the distributed runtime iff the
    standard env vars are present AND no XLA backend exists yet. Safe to
    call unconditionally; never raises."""
    if not (os.environ.get("JAX_COORDINATOR_ADDRESS")
            or os.environ.get("JAX_NUM_PROCESSES")):
        return
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        return
    try:
        initialize_multihost()
    except Exception:
        pass  # fit()'s ensure_multihost will surface the warning


#: set to the barrier name after a timeout: further barriers in this
#: process refuse to run (the abandoned rendezvous could pair with them)
_POISONED_BARRIER: Optional[str] = None


def barrier(name: str, timeout_s: Optional[float] = None):
    """Cross-process rendezvous (no-op single-process).

    Bounded: if a peer process died, its side of the rendezvous never
    arrives and an unguarded ``sync_global_devices`` can block far past
    the coordination service's failure detection. The sync runs on a
    watchdog thread; on timeout (``ELEPHAS_TPU_BARRIER_TIMEOUT_S``,
    default 900 s) the caller gets a clear RuntimeError naming the
    barrier instead of a silent hang — the failure-detection contract
    (SURVEY §5) at the DCN level.

    Recovery requires a process restart: the watchdog thread stays
    parked (leaked) in the abandoned rendezvous, and the process's
    cross-process rendezvous state is undefined from then on — every
    later :func:`barrier` call in this process refuses to run
    (poisoned) rather than risk pairing the stale rendezvous with a
    different barrier on the peers.
    """
    global _POISONED_BARRIER
    if jax.process_count() <= 1:
        return
    if _POISONED_BARRIER is not None:
        # a previous timeout abandoned a watchdog thread still parked in
        # its rendezvous; letting a NEW sync start could pair the stale
        # rendezvous with a different barrier on the peers and corrupt
        # the protocol — this process must restart, not retry
        raise RuntimeError(
            f"barrier {_POISONED_BARRIER!r} timed out earlier; the "
            "cross-process rendezvous state of this process is "
            "undefined. Restart the process — training resumes from "
            "the latest checkpoint.")
    import threading

    from jax.experimental import multihost_utils

    if timeout_s is None:
        timeout_s = float(os.environ.get("ELEPHAS_TPU_BARRIER_TIMEOUT_S",
                                         "900"))
    outcome = {}

    def sync():
        try:
            multihost_utils.sync_global_devices(name)
            outcome["ok"] = True
        except Exception as err:  # noqa: BLE001 — re-raised on the caller
            outcome["err"] = err

    t = threading.Thread(target=sync, daemon=True, name=f"barrier-{name}")
    t.start()
    t.join(timeout_s)
    if t.is_alive():
        _POISONED_BARRIER = name
        raise RuntimeError(
            f"barrier {name!r} timed out after {timeout_s:.0f}s — a peer "
            "process likely died mid-run (crash or preemption), or is "
            "pathologically slow. The watchdog thread remains parked in "
            "the abandoned rendezvous (leaked) and this process's "
            "rendezvous state is now undefined: restart the process to "
            "recover; training resumes from the latest checkpoint. "
            "ELEPHAS_TPU_BARRIER_TIMEOUT_S tunes this deadline.")
    if "err" in outcome:
        raise outcome["err"]


def is_coordinator() -> bool:
    """True on process 0 — where the parameter server and checkpoint
    writes live."""
    return jax.process_index() == 0


def coordinator_bind_env(port: int = 4000) -> Optional[str]:
    """Share the coordinator's address with every process.

    Process 0 resolves its own IP and broadcasts it to all hosts (env vars
    do not cross host boundaries); every process then sets
    ``ELEPHAS_TPU_MASTER_IP`` locally so ``determine_master`` resolves the
    parameter server to the coordinator. Single-process runs just set the
    local env var.
    """
    import socket as pysocket

    preset = os.environ.get("ELEPHAS_TPU_MASTER_IP")
    if preset is not None and jax.process_count() <= 1:
        return preset

    if is_coordinator():
        # a preset on the coordinator wins and is broadcast to every host;
        # presets on non-coordinator hosts are overwritten so all processes
        # agree AND all enter the collective below (a per-host early return
        # would deadlock the others in broadcast_one_to_all)
        host = preset
        if not host:
            try:
                host = pysocket.gethostbyname(pysocket.gethostname())
            except pysocket.gaierror:
                host = "127.0.0.1"
    else:
        host = ""

    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        encoded = np.zeros(64, dtype=np.uint8)
        raw = host.encode("utf8")[:64]
        encoded[:len(raw)] = np.frombuffer(raw, dtype=np.uint8)
        encoded = multihost_utils.broadcast_one_to_all(encoded)
        host = bytes(np.asarray(encoded)).rstrip(b"\x00").decode("utf8")

    os.environ["ELEPHAS_TPU_MASTER_IP"] = host
    return host


def global_data_mesh() -> Mesh:
    """1-D ``data`` mesh over every device of every host."""
    return Mesh(np.array(jax.devices()), ("data",))


def host_local_slice(n: int) -> Tuple[int, int]:
    """Row range [lo, hi) of a length-``n`` dataset this host should load
    (contiguous, balanced across processes)."""
    p = jax.process_count()
    i = jax.process_index()
    base, extra = divmod(n, p)
    lo = i * base + min(i, extra)
    return lo, lo + base + (1 if i < extra else 0)


def global_batch_from_host_data(mesh: Mesh, host_array: np.ndarray,
                                axis: str = "data"):
    """Assemble a globally-sharded array from per-host local rows."""
    spec = PartitionSpec(axis, *([None] * (host_array.ndim - 1)))
    return jax.make_array_from_process_local_data(
        NamedSharding(mesh, spec), host_array)
