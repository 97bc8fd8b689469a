"""Driver ``serve``: one configuration behind ``ServingServer`` in this
process, open-loop traffic from the load-generator child over HTTP.

Set-up (all of it counted in ``setup_s``): parameters on the device from
the seed, one cache-path step against the plain reference, the engine at
the configuration's sizes with every other option at the program's
default, ``engine.warmup`` over the traffic's grid of prompt lengths, the
server, and an untimed lead-in of the same traffic. Then the window. Then,
outside it, the drain and the checks that decide ``correct``.
"""
import json
import os
import random
import subprocess
import sys
import tempfile
import time
import urllib.request

import numpy as np

from chipbench import check, device, traffic as traffic_mod
from chipbench.evidence import Evidence
from chipbench.spec import HERE

#: seconds between two scrapes of ``/metrics`` in a traced run
POLL_S = 0.5


def log(message: str):
    device.log("serve", message)


# ----------------------------------------------------------------- set-up
class Served:
    """The system under test, set up once: parameters, the reference,
    the engine (warmed) and the HTTP server."""

    def __init__(self, run):
        import jax

        from elephas_tpu import DecodeEngine, ServingServer

        spec, cfg, mix = run.spec, run.config, run.traffic
        family = spec.load_module("families", cfg["family"])
        self.sizes = family.model_sizes(cfg, run.rehearse)
        engine_sizes = dict(cfg["engine"])
        if run.rehearse:
            engine_sizes.update(cfg.get("rehearse", {}).get("engine", {}))
        self.engine_sizes = engine_sizes
        self.config = family.program_config(
            self.sizes, max_seq_len=engine_sizes["max_len"],
            param_dtype=cfg["param_dtype"])
        t0 = time.monotonic()
        self.params = family.make_params(self.config, run.seed)
        jax.block_until_ready(self.params)
        log(f"parameters on the device in {time.monotonic() - t0:.1f}s")

        reference = spec.load_module("reference", family.REFERENCE)
        ref_forward = jax.jit(lambda p, t: reference.forward(
            family.to_reference(p, self.config), t, self.sizes))

        def ref_logits(rows):
            return np.asarray(ref_forward(self.params, np.asarray(rows)))

        self.ref_logits = ref_logits
        tol = cfg["check"]
        t0 = time.monotonic()
        self.paged_diff = check.paged_step_vs_reference(
            self.params, self.config, ref_logits,
            rows=int(tol["paged_rows"]), cached=int(tol["paged_cached"]),
            block_size=int(engine_sizes["paged"][1]), seed=run.seed)
        self.paged_ok = self.paged_diff <= float(tol["paged_logits_atol"])
        log(f"paged step vs plain reference: max |dlogit| "
            f"{self.paged_diff:.5f} (limit {tol['paged_logits_atol']}) in "
            f"{time.monotonic() - t0:.1f}s")

        t0 = time.monotonic()
        self.engine = DecodeEngine(
            self.params, self.config,
            max_slots=int(engine_sizes["max_slots"]),
            max_len=int(engine_sizes["max_len"]),
            paged=tuple(engine_sizes["paged"]),
            prefill_chunk=int(engine_sizes["prefill_chunk"]))
        self.grid = traffic_mod.grid_lengths(mix["prompt_tokens"])
        self.engine.warmup(prompt_lengths=self.grid)
        log(f"engine warmed over {len(self.grid)} prompt lengths in "
            f"{time.monotonic() - t0:.1f}s; kernel="
            f"{self.engine.stats['kernel']}; {run.watch.summary()}")
        # warm-up comes before start(), so that the stall watchdog (a
        # default server option) never meets a compile
        self.server = ServingServer(self.engine).start()
        self.port = self.server.port
        log("server started")

    def get(self, path: str) -> str:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{self.port}{path}", timeout=30) as resp:
            return resp.read().decode()

    def stop(self):
        self.server.stop()


# ------------------------------------------------------------------- load
class Load:
    """One schedule offered by the child process."""

    def __init__(self, run, served, seconds, rate_per_s=None, cut=None,
                 lead_in_s=None, seed=None):
        mix = dict(run.traffic)
        if lead_in_s is not None:
            mix["lead_in_s"] = lead_in_s
        seed = run.seed if seed is None else seed
        schedule = traffic_mod.make_schedule(mix, seed, seconds,
                                             rate_per_s=rate_per_s)
        fd, self.out = tempfile.mkstemp(suffix=".samples.json",
                                        dir=run.workdir)
        os.close(fd)
        plan = dict(schedule, port=served.port, seed=seed,
                    vocab=served.config.vocab_size, out=self.out,
                    drain_s=float(mix.get("drain_s", 0.0)),
                    cut=bool(mix.get("cut", False) if cut is None else cut))
        fd, plan_path = tempfile.mkstemp(suffix=".plan.json",
                                         dir=run.workdir)
        with os.fdopen(fd, "w") as fh:
            json.dump(plan, fh)
        self.stop_after = schedule["window"][1] + (
            0.0 if plan["cut"] else plan["drain_s"])
        self.child = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "loadgen.py"), plan_path],
            stdout=subprocess.PIPE, text=True)
        try:
            first = self.child.stdout.readline()
            self.t_begin = float(json.loads(first)["t_begin"])
        except Exception:
            self.kill()
            raise
        self.window = (self.t_begin + schedule["window"][0],
                       self.t_begin + schedule["window"][1])

    def wait(self) -> list:
        """Wait for the child to end (bounded) and read its samples."""
        budget = self.t_begin + self.stop_after + 30.0 - time.monotonic()
        try:
            self.child.wait(timeout=max(1.0, budget))
        except subprocess.TimeoutExpired:
            self.kill()
            raise RuntimeError("the load generator did not end in time")
        finally:
            self.child.stdout.close()
        if self.child.returncode != 0:
            raise RuntimeError(
                f"the load generator exited {self.child.returncode}")
        with open(self.out) as fh:
            return json.load(fh)["samples"]

    def kill(self):
        if self.child.poll() is None:
            self.child.kill()
        self.child.wait()


def sleep_until(t: float):
    delay = t - time.monotonic()
    if delay > 0:
        time.sleep(delay)


# ----------------------------------------------------------------- window
def measure(run, served, load, evidence):
    """Sit through the window. An end-to-end run (``--trace 0``) does
    nothing but wait; a traced run scrapes ``/metrics`` at the window's
    edges and every ``POLL_S`` in between, and profiles a few seconds in
    the middle."""
    start, end = load.window
    if not run.trace:
        sleep_until(end)
        return
    trace_s = min(float(run.traffic.get("trace_s", 4.0)),
                  (end - start) / 2)
    trace_start = start + (end - start - trace_s) / 2
    sleep_until(start)
    evidence.prom_start = served.get("/metrics")
    state = "before"
    while state != "after" or time.monotonic() < end:
        now = time.monotonic()
        if state == "before" and now >= trace_start:
            evidence.trace_dir, begun = device.start_trace(run.workdir)
            evidence.trace_window = [begun, None]
            state = "tracing"
        elif state == "tracing" and now >= trace_start + trace_s:
            evidence.trace_window[1] = device.stop_trace()
            state = "after"
        if now < end:
            evidence.polls.append((now, served.get("/metrics")))
        time.sleep(max(0.0, min(POLL_S, end - time.monotonic()))
                   if state == "after" else POLL_S)
    evidence.prom_end = served.get("/metrics")


# ------------------------------------------------------------ correctness
def failed_requests(samples, cut: bool) -> list:
    """Timed requests that were refused, errored, ended in anything but
    ``done`` or with another length than asked. With ``cut`` (the
    overload cell) a request still open at the cut is attempted, not
    failed."""
    bad = []
    for s in samples:
        if not s["timed"]:
            continue
        if s["end"] == "open":
            if not cut:
                bad.append((s["i"], "not finished by the end of the drain"))
        elif s["end"] != "done":
            bad.append((s["i"], f"{s['end']} {s.get('error', '')}"))
        elif len(s["tokens"]) != s["asked"]:
            bad.append((s["i"], f"asked {s['asked']} tokens, got "
                                f"{len(s['tokens'])}"))
    return bad


def margins_ok(run, served, samples):
    """For a seeded sample of finished requests, every emitted token's
    reference logit lies within the margin of that position's best."""
    tol = run.config["check"]
    done = [s for s in samples if s["end"] == "done" and s["tokens"]]
    if not done:
        return False
    picker = random.Random(run.seed)
    picked = picker.sample(done, min(int(tol["sample_requests"]), len(done)))
    prompts = [traffic_mod.prompt_tokens(run.seed, s["i"], s["prompt_len"],
                                         served.config.vocab_size)
               for s in picked]
    pad_to = (int(run.traffic["prompt_tokens"]["max"])
              + int(run.traffic["output_tokens"]["max"]))
    t0 = time.monotonic()
    margins = check.logit_margins(served.ref_logits, prompts,
                                  [s["tokens"] for s in picked], pad_to)
    worst = max(margins)
    log(f"f32 logit margin over {len(picked)} requests "
        f"({sum(len(s['tokens']) for s in picked)} tokens): worst "
        f"{worst:.5f} (limit {tol['token_logit_margin']}) in "
        f"{time.monotonic() - t0:.1f}s")
    return worst <= float(tol["token_logit_margin"])


# -------------------------------------------------------------------- run
def run(run) -> dict:
    """One run of a serving cell; returns the result line's fields."""
    served = Served(run)
    evidence = Evidence(run)
    load = None
    try:
        load = Load(run, served, run.seconds)
        evidence.window = list(load.window)
        evidence.setup_s = load.window[0] - run.t_process_start
        measure(run, served, load, evidence)
        evidence.compiles_in_window = run.watch.compiles_between(
            *load.window)
        samples = load.wait()
    finally:
        if load is not None:
            load.kill()
        served.stop()
    evidence.samples = samples
    evidence.sizes = served.sizes
    evidence.engine_sizes = served.engine_sizes
    evidence.param_dtype = run.config["param_dtype"]
    cut = bool(run.traffic.get("cut", False))
    timed = [s for s in samples if s["timed"]]
    bad = failed_requests(samples, cut)
    for i, why in bad[:10]:
        log(f"request {i} failed: {why}")
    correct = bool(served.paged_ok and margins_ok(run, served, samples)
                   and not bad)
    log(f"compiles inside the window: {evidence.compiles_in_window}; "
        f"{run.watch.summary()}")
    return {"correct": correct, "attempted": len(timed),
            "failed": len(bad), "evidence": evidence}


# ------------------------------------------------------------------ sweep
def sweep(run, rates, step_s: float, out_path: str):
    """Find the knee: one set-up, then one open-loop step per rate. Per
    rate: the share of requests that met both limits, the backlog at the
    step's middle and end, tokens per second and the latency quantiles.
    The step is cut at its end so that no backlog carries over."""
    from chipbench.readers import client_samples as cs

    served = Served(run)
    limits = run.traffic["sweep_limits"]
    rows = []
    try:
        for step, rate in enumerate(rates):
            # a seed of its own for every step: with one seed the steps
            # would repeat each other's prompts, hit the prefix cache and
            # compile a program for every new hit length
            load = Load(run, served, step_s, rate_per_s=rate, cut=True,
                        lead_in_s=0.0, seed=run.seed + 7919 * (step + 1))
            try:
                samples = load.wait()
            finally:
                load.kill()
            start, end = load.window
            row = cs.sweep_row(samples, start, end, limits)
            row["rate_per_s"] = rate
            row["compiles"] = run.watch.compiles_between(start, end)
            rows.append(row)
            log(f"sweep {json.dumps(row)}")
            # let the cancelled streams retire before the next step
            deadline = time.monotonic() + 60
            while served.engine.pending and time.monotonic() < deadline:
                time.sleep(0.5)
    finally:
        served.stop()
    found = cs.knee(rows, limits)
    log(f"knee {json.dumps(found)}")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as fh:
        json.dump({"config": run.cell["config"],
                   "traffic": run.cell["traffic"], "step_s": step_s,
                   "limits": limits, "rows": rows, **found}, fh, indent=1)
    return rows
