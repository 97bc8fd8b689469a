"""A look at one kept trace: the operations of a program by kind and
origin, and the asynchronous operations beside them.

    python3 chipbench/tools/trace_ops.py <trace.xplane.pb> [program]

For the executions of the programs whose name matches ``program``
(default ``step_paged``) inside the traced window, on the first device:
per (kind, origin cut to its last scopes) the own time a step
(``trace_reduce.own_ns``) and the count a step, most time first; then
the same for the ``Async XLA Ops`` line (start to done). What
``trace_scope_time`` sums for a scope can be checked against it by hand.
Not part of a run.
"""
import os
import re
import sys
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from chipbench import trace_reduce  # noqa: E402
from chipbench.readers import trace_scope_time as tst  # noqa: E402


def main(argv) -> int:
    path = argv[0]
    program = argv[1] if len(argv) > 1 else "step_paged"
    trace = trace_reduce.load(path)
    lo, hi = trace.window
    runs = sorted((s, e) for name, s, e in
                  trace.devices[min(trace.devices)]["modules"]
                  if re.search(program, name) and lo <= s and e <= hi)
    print(f"{len(runs)} executions of /{program}/ in a window of "
          f"{(hi - lo) / 1e9:.3f}s, "
          f"{sum(e - s for s, e in runs) / 1e6 / max(1, len(runs)):.3f} ms "
          f"each")

    def inside(at):
        import bisect
        k = bisect.bisect_right([s for s, _ in runs], at) - 1
        return k >= 0 and at < runs[k][1]

    for line_name in (trace_reduce.OPS_LINE, trace_reduce.ASYNC_LINE):
        # in own_ns's order, so that its list pairs with the events
        events = sorted((ev for ev in tst.device_ops(path, line_name=line_name, names=True)
                         if inside(ev[2]) and lo <= ev[2] < ev[3] <= hi),
                        key=lambda ev: (ev[2], -ev[3]))
        own = trace_reduce.own_ns(
            [(n, s, e) for n, _, s, e in events], lo, hi) \
            if line_name == trace_reduce.OPS_LINE else \
            [[n, e - s] for n, _, s, e in events]
        table = defaultdict(lambda: [0, 0])
        for (name, ns), (_, origin, _, _) in zip(own, events):
            key = (trace_reduce.op_kind(name),
                   "/".join(origin.split("/")[-3:]))
            table[key][0] += ns
            table[key][1] += 1
        print(f"--- {line_name}: {len(events)} events")
        for (kind, scopes), (ns, count) in sorted(
                table.items(), key=lambda kv: -kv[1][0])[:60]:
            print(f"{ns / 1e6 / max(1, len(runs)):9.4f} ms/step "
                  f"x{count / max(1, len(runs)):7.2f}  {kind}  <- {scopes}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
