"""The chip entry points on a machine without a chip.

``chip_smoke.py`` and ``bench.py`` measure on a TPU or not at all: on the
CPU they must exit non-zero and print nothing that reads as a result.
The smoke's explicit ``--cpu-preflight`` mode is the only way it runs
here, and the compile cache goes where the environment says.
"""
import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

REPO = Path(__file__).resolve().parent.parent


def _run(script, *args, env=None, timeout=600):
    full_env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})}
    return subprocess.run([sys.executable, str(REPO / script), *args],
                          capture_output=True, text=True, env=full_env,
                          cwd=str(REPO), timeout=timeout)


def _json_lines(text):
    return [line for line in text.splitlines()
            if line.lstrip().startswith("{")]


def test_chip_smoke_refuses_the_cpu(tmp_path):
    proc = _run("chip_smoke.py",
                env={"JAX_COMPILATION_CACHE_DIR": str(tmp_path)}, timeout=120)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr          # names the platform it found
    assert not _json_lines(proc.stdout)    # no result line


def test_bench_refuses_the_cpu(tmp_path):
    proc = _run("bench.py",
                env={"JAX_COMPILATION_CACHE_DIR": str(tmp_path)}, timeout=120)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr
    assert not _json_lines(proc.stdout)


def test_chip_smoke_rejects_unknown_flags():
    proc = _run("chip_smoke.py", "--cpu", timeout=60)
    assert proc.returncode != 0 and not _json_lines(proc.stdout)


def test_chip_smoke_cpu_preflight_runs_every_phase(tmp_path):
    """The whole command at toy size, in its own process — and, with
    ``JAX_COMPILATION_CACHE_DIR`` set, the cache lands there and nowhere
    else."""
    cache = tmp_path / "cache"
    proc = _run("chip_smoke.py", "--cpu-preflight",
                env={"JAX_COMPILATION_CACHE_DIR": str(cache),
                     "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"})
    assert proc.returncode == 0, proc.stderr[-4000:]
    summary, verdict = map(json.loads, proc.stdout.splitlines()[-2:])
    # the last line is the verdict: exactly these keys, nothing after it
    assert verdict == {"ok": True, "device": summary["device"]}
    assert list(verdict["device"]) == ["platform", "kind", "count"]
    assert summary["ok"] is True and summary["preflight"] is True
    assert summary["device"]["platform"] == "cpu"   # never reads as a chip
    assert list(summary)[-1] == "claim" and summary["claim"] is None
    assert set(summary["phases"]) == {
        "device", "train_one_chip", "serve", "train_four_chips",
        "elephas_job"}
    assert all(p["ok"] for p in summary["phases"].values())
    assert "skipped" not in summary["phases"]["train_four_chips"]
    assert summary["compile_cache"]["dir"] == str(cache)
    assert any(cache.iterdir()), "no cache entry under the env directory"


def test_compile_cache_default_is_inside_the_checkout(monkeypatch):
    import jax

    from elephas_tpu.utils import compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        assert compile_cache.configure_compile_cache() == str(
            REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(
            REPO / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_env_is_left_to_jax(monkeypatch, tmp_path):
    import jax

    from elephas_tpu.utils import compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # set nothing


def test_unknown_device_kind_has_no_peak():
    import bench          # the repo root is on sys.path under pytest

    assert bench._chip_peak_tflops(
        SimpleNamespace(device_kind="TPU v5 lite")) == 197.0
    with pytest.raises(ValueError, match="TPU v9"):
        bench._chip_peak_tflops(SimpleNamespace(device_kind="TPU v9 mega"))
