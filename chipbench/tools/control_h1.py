"""The controls of the hybrid cell's comparisons, on the chip
(``tools/control.py`` runs a family's whole reference in one program,
which does not fit beside Falcon-H1's 10.5 GB; this file runs it through
``check_hybrid.Reference``, a layer at a time).

    python3 chipbench/tools/control_h1.py --workload <cell> --seeds a,b,c

Per seed, over ``check.paged_rows`` seeded prompts of
``check.paged_cached`` + 1 tokens (the step comparison's own), two
controls are put in the program's place and read against the float32
reference:

- ``matrices_3_bits``: every matrix rounded to 3 mantissa bits
  (``tools/control.py``'s ``lower``: the nearest precision below
  bfloat16). It has to break at least one limit.
- ``state_bfloat16``: the reference with its recurrent STATE rounded to
  bfloat16 after every token, everything else float32: what keeping the
  state in the compute dtype would do. Reported beside the limits;
  whether they part it from float32 is a finding, not a requirement.

One JSON line a seed and control: ``step_max_dlogit`` /
``step_rms_dlogit`` (the last position's logits) and
``token_worst_below_best`` (at every position, how far the token the
control puts first lies below the float32 reference's best), each beside
the limit of the cell's configuration file; ``fails`` lists the limits
it breaks. Not part of a run.
"""
import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def readings(ref, ctl) -> dict:
    """``ref`` / ``ctl``: float32 logits (T, V) of one row."""
    import numpy as np

    diff = ctl[-1].astype(np.float64) - ref[-1]
    below = ref.max(-1) - np.take_along_axis(
        ref, ctl.argmax(-1)[:, None], -1)[:, 0]
    return {"max": float(np.abs(diff).max()),
            "sq": float((diff * diff).sum()), "n": int(diff.size),
            "below": float(below.max())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import numpy as np

    from chipbench import check_hybrid, device
    from chipbench.spec import Spec
    from chipbench.tools.control import lower

    spec = Spec()
    cell = spec.cell(args.workload)
    cfg = spec.config(cell["config"])
    device.configure_cache(args.rehearse)
    device.require_devices(cell["chips"], args.rehearse)
    family = spec.load_module("families", cfg["family"])
    reference = spec.load_module("reference", family.REFERENCE)
    sizes = family.model_sizes(cfg, args.rehearse)
    tol = dict(cfg["check"])
    if args.rehearse:
        tol.update(cfg.get("rehearse", {}).get("check", {}))
    engine = dict(cfg["engine"], **(cfg.get("rehearse", {}).get(
        "engine", {}) if args.rehearse else {}))
    config = family.program_config(sizes, max_seq_len=engine["max_len"],
                                   param_dtype=cfg["param_dtype"])
    cached = min(int(tol["paged_cached"]), int(engine["max_len"]) - 2)
    limits = {"step_max_dlogit": tol["paged_logits_atol"],
              "step_rms_dlogit": tol["paged_logits_rms"],
              "token_worst_below_best": tol["token_logit_margin"]}
    controls = {
        "matrices_3_bits": dict(lower=lower),
        # (``reduce_precision``: the TPU compiler folds a pair of
        # ``astype`` away and the control then reads 0.0)
        "state_bfloat16": dict(state_round=lambda s: jax.lax.
                               reduce_precision(s, exponent_bits=8,
                                                mantissa_bits=7))}
    for seed in (int(s) for s in args.seeds.split(",")):
        params = family.make_params(config, seed)
        tokens = np.asarray(jax.random.randint(
            jax.random.PRNGKey(seed), (int(tol["paged_rows"]), cached + 1),
            1, config.vocab_size))
        sound = check_hybrid.Reference(reference, family, params, config,
                                       sizes)
        for name, how in controls.items():
            control = check_hybrid.Reference(reference, family, params,
                                             config, sizes, **how)
            rows = [readings(sound.logits(row[None])[0],
                             control.logits(row[None])[0])
                    for row in tokens]
            got = {"step_max_dlogit": max(r["max"] for r in rows),
                   "step_rms_dlogit": (sum(r["sq"] for r in rows)
                                       / sum(r["n"] for r in rows)) ** 0.5,
                   "token_worst_below_best": max(r["below"] for r in rows)}
            print(json.dumps({
                "cell": cell["name"], "seed": seed, "control": name, **got,
                "limits": limits,
                "fails": [k for k, limit in limits.items()
                          if got[k] > float(limit)]}), flush=True)
        del params, sound, control
        gc.collect()          # the next seed's parameters need the room
    return 0


if __name__ == "__main__":
    sys.exit(main())
