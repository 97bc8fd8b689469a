"""Run one cell of ``BENCHMARK.json`` and print one JSON line last.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
                             --trace <0|1>

With ``--trace 0`` the line's metrics are the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics (and ``device.busy_s``,
``device.window_s`` and ``breakdown`` from the profiler's trace). Without
a TPU, or with fewer chips than the cell asks for, it exits non-zero and
prints no result line.

Not part of the contract: ``--rehearse`` (the CPU rehearsal the tests
use: toy widths, ``device.platform`` says ``cpu``, no number of it is a
device metric), ``--sweep`` (the knee or capacity sweep of a serving
cell), ``--rate-per-s`` (a trial at another offered rate) and
``--benchmark-json`` (another ``BENCHMARK.json``, for the test that the
harness takes additions as data).
"""
import argparse
import json
import os
import sys
import tempfile
import time
import types

T_PROCESS_START = time.monotonic()

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--sweep", default=None, metavar="RATES",
                    help="comma-separated request rates; writes "
                         "chiprun_out/sweep-<cell>.json")
    ap.add_argument("--sweep-step-s", type=float, default=30.0)
    ap.add_argument("--sweep-lead-in-s", type=float, default=0.0,
                    help="a lead-in at each step's own rate: the step is "
                         "then the cut cell at that rate (capacity)")
    ap.add_argument("--rate-per-s", type=float, default=None,
                    help="offer this rate, lead-in and window, in place "
                         "of the traffic file's (a trial: is the reading "
                         "capacity, or the offered rate?)")
    ap.add_argument("--benchmark-json", default=None)
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="copy the traced run's .xplane.pb there")
    ap.add_argument("--keep-samples", default=None, metavar="DIR",
                    help="write the load generator's samples there")
    return ap.parse_args(argv)


def keep_trace(trace_dir: str, keep_dir: str, cell: str):
    import shutil

    from chipbench.trace_reduce import find_xplane

    path = find_xplane(trace_dir)
    if path:
        os.makedirs(keep_dir, exist_ok=True)
        shutil.copy(path, os.path.join(keep_dir, f"{cell}.xplane.pb"))


def main(argv=None) -> int:
    args = parse(argv)
    from chipbench.spec import Spec

    spec = Spec(args.benchmark_json)
    run = types.SimpleNamespace()     # one invocation: cell, files, seed
    run.spec, run.t_process_start = spec, T_PROCESS_START
    run.cell = spec.cell(args.workload)
    run.config = spec.config(run.cell["config"])
    run.traffic = spec.traffic(run.cell["traffic"])
    run.rehearse, run.trace, run.seed = args.rehearse, args.trace, args.seed
    if args.rehearse:
        run.traffic = dict(run.traffic, **run.traffic.get("rehearse", {}))
    if args.rate_per_s is not None:
        run.traffic = dict(run.traffic, arrivals=dict(
            run.traffic["arrivals"], rate_per_s=args.rate_per_s))
    run.seconds = float(args.seconds if args.seconds is not None
                        else spec.data["run_seconds"])

    if args.rehearse:
        # the rehearsal's platform is set before JAX starts a backend
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{max(4, run.cell['chips'])}").strip()
    from chipbench import device

    device.LOG_ORIGIN[0] = T_PROCESS_START
    import jax                                     # noqa: F401

    device.log("run", "jax imported")
    try:
        cache_dir = device.configure_cache(args.rehearse)
    except ImportError as exc:
        print(f"chipbench: the program is not importable here: {exc}",
              file=sys.stderr)
        return 4
    device.log("run", "program imported")
    run.devices = device.require_devices(run.cell["chips"], args.rehearse)
    run.device = device.device_report(run.devices)
    run.watch = device.CompileWatch()
    device.log("run", f"cell {run.cell['name']} seed {run.seed} seconds "
               f"{run.seconds} trace {run.trace} device {run.device} "
               f"compile cache {cache_dir or 'off'}")

    driver = spec.load_module("drivers", run.traffic["driver"])
    with tempfile.TemporaryDirectory(prefix="chipbench-") as workdir:
        run.workdir = workdir
        if args.sweep:
            rates = [float(r) for r in args.sweep.split(",")]
            driver.sweep(run, rates, args.sweep_step_s, os.path.join(
                "chiprun_out", f"sweep-{run.cell['name']}.json"),
                args.sweep_lead_in_s)
            return 0
        result = driver.run(run)
        evidence = result.pop("evidence")
        if args.keep_samples and evidence.samples:
            os.makedirs(args.keep_samples, exist_ok=True)
            with open(os.path.join(
                    args.keep_samples, f"{run.cell['name']}-{run.seed}-"
                    f"{run.trace}.samples.json"), "w") as fh:
                json.dump({"window": evidence.window,
                           "samples": evidence.samples}, fh)
        group = "per_layer" if run.trace else "end_to_end"
        metrics = spec.read_metrics(group, run.cell["name"], evidence)
        report = dict(run.device, memory_peak_bytes=result.get(
            "memory_peak_bytes", device.peak_memory_bytes(run.devices)))
        breakdown = None
        if run.trace:
            # both groups are printed on an earlier line of a traced
            # run, for the reader of the log; the last line keeps to one
            other = spec.read_metrics("end_to_end", run.cell["name"],
                                      evidence)
            print(f"[run] end-to-end metrics of this traced run (not "
                  f"judged): {json.dumps(other)}", flush=True)
            trace = evidence.trace
            if args.keep_trace and evidence.trace_dir:
                keep_trace(evidence.trace_dir, args.keep_trace,
                           run.cell["name"])
            report["busy_s"] = trace.busy_s() if trace else 0.0
            report["window_s"] = trace.window_s if trace else 0.0
            if trace:
                breakdown = trace.breakdown()
                print(f"[run] device time by program: "
                      f"{json.dumps(trace.program_time())}; the "
                      f"profiler's time scale multipliers: "
                      f"{trace.time_scales}", flush=True)
    line = {"correct": result["correct"], "attempted": result["attempted"],
            "failed": result["failed"], "metrics": metrics,
            "device": report}
    if breakdown is not None:
        line["breakdown"] = breakdown
    print(f"[run] peak device memory {report['memory_peak_bytes']} bytes; "
          f"total {time.monotonic() - T_PROCESS_START:.1f}s", flush=True)
    # each number compared beside its limit: last in the line, and the
    # last lines of standard error
    line["checks"] = result.get("checks", {})
    print(json.dumps(line), flush=True)
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']} limit {c['limit']} "
              f"{'ok' if c['ok'] else 'NOT OK'}", file=sys.stderr)
    print(f"correct: {line['correct']}", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
