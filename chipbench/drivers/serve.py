"""Driver ``serve``: one configuration behind ``ServingServer`` in this
process, open-loop traffic from the load-generator child over HTTP.

Set-up (all of it counted in ``setup_s``): parameters on the device from
the seed, the engine at the configuration's sizes with every other option
at the program's default, ``engine.warmup`` over the traffic's grid of
prompt lengths, the server, and an untimed lead-in of the same traffic at
the window's own rate. Then the window. Then, outside it and outside
``setup_s``: the drain, the peak of the device's memory, the engine
dropped, and the comparisons with the plain reference that decide
``correct`` (:meth:`Served.step_check`, :meth:`Served.token_check`), each
number beside its limit in the result line's ``checks``.
"""
import json
import math
import os
import random
import subprocess
import sys
import tempfile
import time
import urllib.request

import numpy as np

from chipbench import check, device, traffic as traffic_mod
from chipbench.evidence import Evidence, parse_prometheus
from chipbench.spec import HERE

#: seconds between two scrapes of ``/metrics`` in a traced run
POLL_S = 0.5


def log(message: str):
    device.log("serve", message)


# ----------------------------------------------------------------- set-up
def verdict(value, limit, at_least: bool = False) -> dict:
    """One compared number beside its limit."""
    value, limit = float(value), float(limit)
    ok = value >= limit if at_least else value <= limit
    if not math.isfinite(value):
        value, ok = None, False              # JSON has no infinity
    return {"value": value, "limit": limit, "ok": bool(ok)}


class Served:
    """The system under test, set up once: parameters, the engine
    (warmed) and the HTTP server; and, for after the window, the plain
    reference and the two comparisons with it. A family whose
    comparisons differ (``serve_routed``) overrides ``make_reference``,
    ``step_check`` and ``token_check``."""

    def __init__(self, run):
        import jax

        from elephas_tpu import DecodeEngine, ServingServer

        spec, cfg, mix = run.spec, run.config, run.traffic
        self.family = family = spec.load_module("families", cfg["family"])
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
            f"{time.monotonic() - t0:.1f}s; {run.watch.summary()}")
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
        """Stop the server and drop the engine with its pool: the
        comparisons that follow hold the parameters alone."""
        import gc

        if self.server is not None:
            self.server.stop()
        self.server = self.engine = None
        gc.collect()

    # ------------------------------------------- the plain reference
    def make_reference(self, run):
        """``self.ref_logits(rows (n, T)) -> float32 logits``, built
        when the first comparison asks for it (after the window)."""
        import jax

        reference = run.spec.load_module("reference",
                                         self.family.REFERENCE)
        forward = jax.jit(lambda p, t: reference.forward(
            self.family.to_reference(p, self.config), t, self.sizes))
        self.ref_logits = lambda rows: np.asarray(
            forward(self.params, np.asarray(rows)))

    def step_check(self, run) -> dict:
        """One cache-path step against the plain reference's logits."""
        tol = run.config["check"]
        t0 = time.monotonic()
        diff = check.paged_step_vs_reference(
            self.params, self.config, self.ref_logits,
            rows=int(tol["paged_rows"]), cached=int(tol["paged_cached"]),
            block_size=int(self.engine_sizes["paged"][1]), seed=run.seed)
        log(f"paged step vs plain reference: max |dlogit| {diff:.5f} "
            f"(limit {tol['paged_logits_atol']}) in "
            f"{time.monotonic() - t0:.1f}s")
        return {"step_max_dlogit": verdict(diff, tol["paged_logits_atol"])}

    def token_check(self, run, picked, prompts, pad_to: int) -> dict:
        """Every served token's reference logit lies within the margin
        of that position's best."""
        tol = run.config["check"]
        margins = check.logit_margins(self.ref_logits, prompts,
                                      [s["tokens"] for s in picked], pad_to)
        return {"token_worst_below_best": verdict(
            max(margins), tol["token_logit_margin"])}


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
            # stamped anew: starting and stopping the profiler take
            # seconds, and a poll's counters are those of its own instant
            evidence.polls.append((time.monotonic(),
                                   served.get("/metrics")))
        time.sleep(max(0.0, min(POLL_S, end - time.monotonic()))
                   if state == "after" else POLL_S)
    evidence.prom_end = served.get("/metrics")
    log_profile_cost(evidence)


def log_profile_cost(evidence):
    """What the profiler costs while it is on: decode steps and tokens
    a second, from the polls of ``/metrics``, inside the profile and in
    the rest of the window. A device time read from the trace is a time
    WITH that cost."""
    if not evidence.trace_window or evidence.trace_window[1] is None:
        return
    lo, hi = evidence.trace_window
    points = [(t, parse_prometheus(text)) for t, text in evidence.polls]
    rates = {}
    for name, keep in (("inside", lambda a, b: lo <= a and b <= hi),
                       ("outside", lambda a, b: b <= lo or a >= hi)):
        spans = [(a, b) for a, b in zip(points, points[1:])
                 if keep(a[0], b[0])]
        seconds = sum(b[0] - a[0] for a, b in spans)
        if not seconds:
            return
        rates[name] = {
            key: sum(b[1].get(series, 0.0) - a[1].get(series, 0.0)
                     for a, b in spans) / seconds
            for key, series in (("steps_per_s", "serving_steps_total"),
                                ("tokens_per_s",
                                 "serving_tokens_emitted_total"))}
    log(f"the profile's cost: inside it {rates['inside']}, in the rest of "
        f"the window {rates['outside']}")


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


def sample_finished(run, samples) -> list:
    """The finished requests the reference is run over: the longest
    answer (the lowest-numbered of them) and a sample of the others
    drawn from the seed, ``check.sample_requests`` in all."""
    done = [s for s in samples if s["end"] == "done" and s["tokens"]]
    if not done:
        return []
    longest = max(done, key=lambda s: (len(s["tokens"]), -s["i"]))
    rest = [s for s in done if s is not longest]
    want = int(run.config["check"]["sample_requests"]) - 1
    return [longest] + random.Random(run.seed).sample(
        rest, min(want, len(rest)))


def compare(run, served, samples) -> dict:
    """The comparisons with the plain reference, after the window:
    ``{name: {"value", "limit", "ok"}}``."""
    served.make_reference(run)
    checks = dict(served.step_check(run))
    picked = sample_finished(run, samples)
    if not picked:
        checks["finished_requests"] = verdict(0, 1, at_least=True)
        return checks
    prompts = [traffic_mod.prompt_tokens(run.seed, s["i"], s["prompt_len"],
                                         served.config.vocab_size)
               for s in picked]
    pad_to = (int(run.traffic["prompt_tokens"]["max"])
              + int(run.traffic["output_tokens"]["max"]))
    t0 = time.monotonic()
    checks.update(served.token_check(run, picked, prompts, pad_to))
    log(f"reference over {len(picked)} finished requests "
        f"({sum(len(s['tokens']) for s in picked)} served tokens, the "
        f"longest {len(picked[0]['tokens'])}) in "
        f"{time.monotonic() - t0:.1f}s")
    return checks


# -------------------------------------------------------------------- run
def run(run, served_class=Served) -> dict:
    """One run of a serving cell; returns the result line's fields."""
    served = served_class(run)
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
    # the peak is the served system's: read before the reference runs
    memory_peak = device.peak_memory_bytes(run.devices)
    evidence.samples = samples
    evidence.sizes = served.sizes
    evidence.engine_sizes = served.engine_sizes
    evidence.param_dtype = run.config["param_dtype"]
    cut = bool(run.traffic.get("cut", False))
    timed = [s for s in samples if s["timed"]]
    bad = failed_requests(samples, cut)
    for i, why in bad[:10]:
        log(f"request {i} failed: {why}")
    checks = compare(run, served, samples)
    checks["failed_requests"] = verdict(len(bad), 0)
    log(f"compiles inside the window: {evidence.compiles_in_window}; "
        f"{run.watch.summary()}")
    return {"correct": all(c["ok"] for c in checks.values()),
            "attempted": len(timed), "failed": len(bad),
            "evidence": evidence, "checks": checks,
            "memory_peak_bytes": memory_peak}


# ------------------------------------------------------------------ sweep
def sweep(run, rates, step_s: float, out_path: str, lead_in_s: float = 0.0,
          served_class=Served):
    """One set-up, then one open-loop step per rate, cut at its end so
    that no backlog carries over. Per rate: the share of requests that
    met both limits, the backlog at the step's middle and end, tokens
    per second, the latency quantiles and, from two scrapes of
    ``/metrics`` at the step's edges, the tokens a decode step emitted.

    With ``lead_in_s`` = 0 each step starts idle: the knee by the
    latency limits. With a lead-in at the step's own rate the step is
    the cut cell itself at that rate, and its tokens per second over the
    mix's mean answer is the capacity (an idle start understates a full
    engine by the seconds it takes to fill the slots)."""
    from chipbench.readers import client_samples as cs

    served = served_class(run)
    limits = run.traffic["sweep_limits"]
    rows = []
    try:
        for step, rate in enumerate(rates):
            # a seed of its own for every step: with one seed the steps
            # would repeat each other's prompts, hit the prefix cache and
            # compile a program for every new hit length
            load = Load(run, served, step_s, rate_per_s=rate, cut=True,
                        lead_in_s=lead_in_s,
                        seed=run.seed + 7919 * (step + 1))
            try:
                start, end = load.window
                sleep_until(start)
                before = parse_prometheus(served.get("/metrics"))
                sleep_until(end)
                after = parse_prometheus(served.get("/metrics"))
                samples = load.wait()
            finally:
                load.kill()
            row = cs.sweep_row(samples, start, end, limits)
            row["rate_per_s"] = rate
            timed = [s for s in samples if s["timed"]]
            row["mean_answer_tokens"] = (
                sum(s["asked"] for s in timed) / len(timed))
            steps = (after.get("serving_steps_total", 0.0)
                     - before.get("serving_steps_total", 0.0))
            if steps:
                row["tokens_per_step"] = (
                    after.get("serving_tokens_emitted_total", 0.0)
                    - before.get("serving_tokens_emitted_total", 0.0)
                ) / steps
                row["steps_per_s"] = steps / (end - start)
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
                   "lead_in_s": lead_in_s, "limits": limits, "rows": rows,
                   **found}, fh, indent=1)
    return rows
