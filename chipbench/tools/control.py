"""The control of a serving cell's comparisons, on the chip: the plain
reference computed in the nearest precision below the configuration's
(bfloat16 -> float8: every matrix rounded to float8_e4m3's 3 mantissa
bits at bfloat16's own exponent range, which is a float8 cast with ideal
scaling: the mildest float8 there is, and so the hardest to tell from
the program), put in the program's place, has to read ABOVE the limits
that sound runs of the program stay under.

    python3 chipbench/tools/control.py --workload <cell> --seeds a,b,c

Per seed, over ``check.paged_rows`` seeded prompts of
``check.paged_cached`` + 1 tokens (the step comparison's own):

- ``step_max_dlogit`` / ``step_rms_dlogit``: the control's logits at the
  last position against the float32 reference's;
- ``token_worst_below_best`` / ``token_share_within_margin``: at every
  position, how far the token the control puts first lies below the
  float32 reference's best.

One JSON line a seed, each number beside the limit of the cell's
configuration file; ``fails`` lists the limits the control breaks (it
has to break one). Not part of a run.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def lower(params):
    """Every matrix rounded to 3 mantissa bits, in its own dtype
    (``reduce_precision``: a pair of ``astype`` is folded away by the
    TPU compiler, which may keep excess precision; the control then read
    0.0 on the chip)."""
    import jax
    import jax.numpy as jnp

    def one(leaf):
        if leaf.ndim < 2 or not jnp.issubdtype(leaf.dtype, jnp.floating):
            return leaf
        return jax.lax.reduce_precision(leaf, exponent_bits=8,
                                        mantissa_bits=3)

    return jax.tree_util.tree_map(one, params)


def readings(ref, ctl, margin: float) -> dict:
    """``ref`` / ``ctl``: float32 logits (rows, T, V)."""
    import numpy as np

    diff = ctl[:, -1].astype(np.float64) - ref[:, -1]
    first = ctl.argmax(-1)
    below = ref.max(-1) - np.take_along_axis(ref, first[..., None],
                                              -1)[..., 0]
    return {"step_max_dlogit": float(np.abs(diff).max()),
            "step_rms_dlogit": float(np.sqrt(np.mean(diff * diff))),
            "token_worst_below_best": float(below.max()),
            "token_share_within_margin": float((below <= margin).mean()),
            "positions": int(below.size)}


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

    from chipbench import device
    from chipbench.spec import Spec

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
    forward = jax.jit(lambda p, t: reference.forward(
        family.to_reference(p, config), t, sizes))
    quantise = jax.jit(lower, donate_argnums=(0,))

    def logits(params, tokens):
        return np.concatenate([np.asarray(forward(params, row[None]))
                               for row in tokens])

    limits = {"step_max_dlogit": tol["paged_logits_atol"],
              "step_rms_dlogit": tol.get("paged_logits_rms"),
              "token_worst_below_best": tol.get(
                  "token_logit_margin_worst", tol["token_logit_margin"])}
    for seed in (int(s) for s in args.seeds.split(",")):
        params = family.make_params(config, seed)
        tokens = np.asarray(jax.random.randint(
            jax.random.PRNGKey(seed), (int(tol["paged_rows"]), cached + 1),
            1, config.vocab_size))
        ref = logits(params, tokens)
        params = quantise(params)
        ctl = logits(params, tokens)
        del params
        got = readings(ref, ctl, float(tol["token_logit_margin"]))
        fails = [name for name, limit in limits.items()
                 if limit is not None and got[name] > float(limit)]
        share = tol.get("token_share_within_margin")
        if share is not None and got["token_share_within_margin"] < share:
            fails.append("token_share_within_margin")
        print(json.dumps({"cell": cell["name"], "seed": seed, **got,
                          "limits": {**limits,
                                     "token_share_within_margin": share},
                          "fails": fails}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
