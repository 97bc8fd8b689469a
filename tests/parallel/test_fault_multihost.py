"""Process-level fault injection on the DCN path.

The in-process fault suite (``tests/parameter/test_fault_injection.py``)
covers thread-level failures; here a real JAX-distributed *process* is
hard-killed mid-fit (simulated host death / preemption). Contract:

- the surviving process exits with a clear, bounded-time error naming
  the barrier — never a silent hang waiting on a dead peer;
- a restarted run restores the latest checkpoint and finishes training.
"""
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

_DRIVER = os.path.join(os.path.dirname(__file__), "mh_driver.py")
_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_PORT = [31810]


def _ports():
    _PORT[0] += 2
    return _PORT[0], _PORT[0] + 1


def _launch(mode, nprocs, outdir, jax_port, ps_port, timeout=240):
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["ELEPHAS_TPU_BARRIER_TIMEOUT_S"] = "20"
    procs = [subprocess.Popen(
        [sys.executable, _DRIVER, mode, "average", str(i), str(nprocs),
         str(jax_port), str(ps_port), str(outdir)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=env)
        for i in range(nprocs)]
    outputs = []
    for p in procs:
        out, _ = p.communicate(timeout=timeout)
        outputs.append(out)
    return procs, outputs


#: Environment-bound, the same root cause as test_multihost_spawn.py's
#: marker: the jaxlib these tests were first run on raised
#: 'Multiprocess computations aren't implemented on the CPU backend.'
#: at the first cross-process collective, so the crash run failed
#: during training rather than at the injected kill and neither test's
#: premise (a surviving peer mid-fit; a checkpoint to resume from) came
#: about. The installed jaxlib runs cross-process collectives on the CPU
#: over Gloo, and both tests XPASS here, as test_multihost_spawn.py's
#: do. The marker stays non-strict for jaxlib builds without them.
_cpu_multiprocess_xfail = pytest.mark.xfail(
    strict=False,
    reason="environment-bound: a jaxlib whose CPU backend raises "
           "'Multiprocess computations aren't implemented' fails before "
           "the fault-injection premise can establish (see in-file note)")


@pytest.fixture(scope="module")
def crash_run(tmp_path_factory):
    """One 2-process ``async_crash`` run that both tests read: process 1
    hard-exits mid-fit and process 0 survives it. Tests copy what they
    change out of ``outdir`` and leave it as the run left it."""
    outdir = tmp_path_factory.mktemp("crash_run")
    jax_port, ps_port = _ports()
    start = time.monotonic()
    procs, outputs = _launch("async_crash", 2, outdir, jax_port, ps_port)
    return outdir, procs, outputs, time.monotonic() - start


@_cpu_multiprocess_xfail
def test_peer_death_surfaces_clear_error_not_hang(crash_run):
    """Hard-kill process 1 mid-fit: process 0 must exit within the
    barrier deadline with an error naming the barrier."""
    outdir, procs, outputs, elapsed = crash_run

    assert procs[1].returncode == 43, \
        f"crash process should hard-exit 43:\n{outputs[1]}"
    # the survivor must FAIL (nonzero) with a visible, named error: the
    # fit raises (barrier watchdog, or Gloo/coordination-service failure
    # detection when it wins the race) and the driver reports it before
    # exiting. JAX's own distributed shutdown may then abort the
    # interpreter, so the exact code varies — silent success or a hang
    # are the failure modes under test.
    assert procs[0].returncode != 0, f"survivor succeeded?!:\n{outputs[0]}"
    assert "SURVIVOR_ERROR" in outputs[0], outputs[0]
    assert ("timed out" in outputs[0] or "peer" in outputs[0]
            or "heartbeat" in outputs[0]), outputs[0]
    # bounded: the 20s barrier deadline plus training/startup slack,
    # nowhere near the subprocess timeout a hang would hit
    assert elapsed < 180, f"survivor took {elapsed:.0f}s — effectively a hang"
    # the coordinator checkpointed at least one epoch before the failure
    from elephas_tpu.utils.checkpoint import CheckpointManager

    assert CheckpointManager(outdir / "ckpt").latest_step() is not None


@_cpu_multiprocess_xfail
def test_restart_resumes_from_checkpoint(crash_run, tmp_path):
    """The full recovery story: crash run leaves checkpoints; a fresh
    2-process run restores the latest step, finishes, and both hosts
    agree on finite weights."""
    shutil.copytree(crash_run[0] / "ckpt", tmp_path / "ckpt")
    from elephas_tpu.utils.checkpoint import CheckpointManager

    latest = CheckpointManager(tmp_path / "ckpt").latest_step()
    assert latest is not None and latest >= 0

    jax_port, ps_port = _ports()
    procs, outputs = _launch("async_resume", 2, tmp_path, jax_port, ps_port)
    for i, (p, out) in enumerate(zip(procs, outputs)):
        assert p.returncode == 0, f"resume process {i} failed:\n{out}"
        assert f"restored_step={latest}" in out, out

    w0 = np.load(os.path.join(str(tmp_path), "weights_0.npz"))
    w1 = np.load(os.path.join(str(tmp_path), "weights_1.npz"))
    for k in w0.files:
        np.testing.assert_array_equal(w0[k], w1[k])
        assert np.all(np.isfinite(w0[k]))
