"""Driver ``serve_routed``: driver ``serve`` for a family whose expert
layers route -- the same set-up, window, sweep and request checks
(``chipbench/drivers/serve.py``, used as it stands), with the two
comparisons of ``chipbench/check_routed.py`` (the cache-path step under
the near-tie rule, the token margins as a share) in place of
``check.py``'s single numbers, and a reference that is run one row at a
time (its float32 temporaries beside 10 GB of weights).
"""
import time

import numpy as np

from chipbench import check_routed
from chipbench.drivers import serve as base
from chipbench.drivers.serve import verdict

log = base.log


class Served(base.Served):
    """``serve.Served`` with the routed comparisons."""

    def make_reference(self, run):
        import jax

        reference = run.spec.load_module("reference",
                                         self.family.REFERENCE)
        forward = jax.jit(lambda p, t: reference.forward(
            self.family.to_reference(p, self.config), t, self.sizes))
        routing = jax.jit(
            lambda p, t, picks: reference.forward_with_routing(
                self.family.to_reference(p, self.config), t, self.sizes,
                last_picks=picks))
        # (rows, T) -> float32 logits, one row at a time
        self.ref_logits = lambda rows: np.concatenate([
            np.asarray(forward(self.params, np.asarray(row)[None]))
            for row in np.asarray(rows)])
        self.ref_routing = lambda tokens, picks: routing(
            self.params, tokens, picks)

    def step_check(self, run) -> dict:
        tol = run.config["check"]
        t0 = time.monotonic()
        found = check_routed.paged_step_vs_reference(
            self.params, self.config, self.ref_routing,
            rows=int(tol["paged_rows"]), cached=int(tol["paged_cached"]),
            engine_sizes=self.engine_sizes, seed=run.seed, tol=tol)
        log(f"paged step vs plain reference: {found} in "
            f"{time.monotonic() - t0:.1f}s")
        return {
            "step_max_dlogit": verdict(found["max_abs_dlogit"],
                                       tol["paged_logits_atol"]),
            "step_rms_dlogit": verdict(found["rms_dlogit"],
                                       tol["paged_logits_rms"]),
            "step_worst_flipped_gap": verdict(
                found["worst_flipped_margin"], tol["near_tie_margin"]),
            "step_flipped_share": verdict(
                found["flipped_choices"] / found["choices"],
                tol["max_flipped_share"])}

    def token_check(self, run, picked, prompts, pad_to: int) -> dict:
        """Tokens that may have followed a flipped pick: judged by
        ``check_routed.judge_tokens``."""
        tol = run.config["check"]
        below = check_routed.token_margins(
            self.ref_logits, prompts, [s["tokens"] for s in picked], pad_to)
        found = check_routed.judge_tokens(below, tol)
        log(f"f32 logit margin: {found}; quantiles 0.5 / 0.9 / 0.99 of "
            f"the distance "
            f"{np.quantile(below, [0.5, 0.9, 0.99]).round(4).tolist()}")
        return {
            "token_share_within_margin": verdict(
                found["share_within_margin"],
                tol["token_share_within_margin"], at_least=True),
            "token_worst_below_best": verdict(
                found["worst"], tol["token_logit_margin_worst"])}


def _limits(run):
    """In a rehearsal, the toy widths' limits."""
    if run.rehearse:
        run.config = dict(run.config, check={
            **run.config["check"],
            **run.config.get("rehearse", {}).get("check", {})})
    return run


def run(run) -> dict:
    return base.run(_limits(run), Served)


def sweep(run, rates, step_s: float, out_path: str, lead_in_s: float = 0.0):
    return base.sweep(_limits(run), rates, step_s, out_path, lead_in_s,
                      Served)
