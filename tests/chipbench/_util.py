"""Helpers of the chipbench tests: run the harness as the driver does."""
import json
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent.parent
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}


def bench_command():
    with open(REPO / "BENCHMARK.json") as fh:
        command = json.load(fh)["command"]
    return [sys.executable if command[0] == "python3" else command[0],
            *command[1:]]


def run_cell(*args, cwd=REPO, timeout=600, env=None):
    full_env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})}
    full_env.pop("XLA_FLAGS", None)
    return subprocess.run([*bench_command(), *args], capture_output=True,
                          text=True, env=full_env, cwd=str(cwd),
                          timeout=timeout)


def last_line(proc):
    assert proc.returncode == 0, proc.stderr[-3000:] + proc.stdout[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(line) - {"breakdown"} == LINE_KEYS, sorted(line)
    return line


def json_lines(text):
    return [ln for ln in text.splitlines() if ln.lstrip().startswith("{")]


def run_broken(fault, *args, timeout=600):
    """The rehearsal of a cell with the timed path broken underneath
    (``tests/chipbench/_broken_run.py``; it adds ``--rehearse``)."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, str(REPO / "tests" / "chipbench" /
                             "_broken_run.py"), fault, *args],
        capture_output=True, text=True, env=env, cwd=str(REPO),
        timeout=timeout)
