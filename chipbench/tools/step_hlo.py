"""A look at the compiled decode step: the optimised HLO of the engine's
``jit__step_paged`` at a cell's sizes, on the chip.

    python3 chipbench/tools/step_hlo.py --workload <cell> [--out DIR]

Builds the cell's engine as driver ``serve`` does (parameters from seed
0, no server, no warm-up), lowers and compiles the step with the
engine's own arguments and writes the text, gzipped, to
``<out>/step_hlo-<config>.txt.gz``. It prints every asynchronous copy
(``copy-start`` / ``copy-done``) with its shape, the memory space of its
result (``S(1)`` in a layout is the chip's fast memory; none is HBM) and
the operations that read the ``copy-done``. Not part of a run: no metric
reads it. It reaches into the engine (``_step_paged_fn``, ``pool``,
``_tables``) because the program offers no other way to the compiled
step.
"""
import argparse
import gzip
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def async_copies(text: str) -> list:
    """``[(name, shape with layout, readers)]`` of every ``copy-done``."""
    out = []
    for match in re.finditer(
            r"^\s*(%?copy-done[\w.\-]*) = (\S+) copy-done\(", text, re.M):
        name, shape = match.group(1), match.group(2)
        readers = re.findall(
            r"^\s*(%?[\w.\-]+) = \S+ ([\w\-]+)\([^\n]*" + re.escape(name)
            + r"[,)]", text, re.M)
        out.append((name, shape, readers))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--out", default="chiprun_out")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp

    from chipbench import device
    from chipbench.spec import Spec

    spec = Spec()
    cell = spec.cell(args.workload)
    cfg = spec.config(cell["config"])
    device.configure_cache(False)
    device.require_devices(cell["chips"], False)
    from elephas_tpu import DecodeEngine

    family = spec.load_module("families", cfg["family"])
    sizes = family.model_sizes(cfg, False)
    eng = cfg["engine"]
    config = family.program_config(sizes, max_seq_len=eng["max_len"],
                                   param_dtype=cfg["param_dtype"])
    params = family.make_params(config, 0)
    engine = DecodeEngine(params, config, max_slots=int(eng["max_slots"]),
                          max_len=int(eng["max_len"]),
                          paged=tuple(eng["paged"]),
                          prefill_chunk=int(eng["prefill_chunk"]))
    slots = int(eng["max_slots"])
    ints = jnp.zeros((slots,), jnp.int32)
    step_args = (engine.params, engine.pool, jnp.asarray(engine._tables),
                 ints, ints, ints, jnp.asarray(engine._temp),
                 jnp.asarray(engine._topk), jnp.asarray(engine._topp),
                 jnp.asarray(engine._slot_seed), jax.random.PRNGKey(0))
    compiled = engine._step_paged_fn.lower(*step_args).compile()
    text = compiled.as_text()
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, f"step_hlo-{cell['config']}.txt.gz")
    with gzip.open(path, "wt") as fh:
        fh.write(text)
    print(f"[step_hlo] {len(text)} characters -> {path}; memory: "
          f"{compiled.memory_analysis()}")
    for name, shape, readers in async_copies(text):
        print(f"[step_hlo] {name} {shape} read by "
              f"{sorted(set(readers))[:6]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
