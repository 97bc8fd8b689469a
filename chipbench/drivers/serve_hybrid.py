"""Driver ``serve_hybrid``: driver ``serve`` for a family whose rows keep
recurrent state beside their cached positions (``falcon_h1``) -- the same
set-up, window, sweep, request checks and token comparison
(``chipbench/drivers/serve.py``, used as it stands), with
``check_hybrid.py``'s cache-path step in place of ``check.py``'s (a chunk
boundary and a reused slot, by its largest and its root-mean-square
difference) and a reference that is run one layer at a time, its head in
blocks of the vocabulary (float32 temporaries beside 10.5 GB of
weights).
"""
import time

from chipbench import check_hybrid
from chipbench.drivers import serve as base
from chipbench.drivers.serve import verdict
from chipbench.drivers.serve_routed import _limits

log = base.log


class Served(base.Served):
    """``serve.Served`` with the hybrid family's step comparison."""

    def stop(self):
        """``serve``'s stop, then the engine's pool and slot state (3.2
        GB) given back at once: handler threads of the streams the cut
        left open may keep the stopped server, and so its engine, alive
        for a while, and the reference needs the room."""
        import jax

        engine = self.engine
        super().stop()
        if engine is not None:
            for leaf in jax.tree_util.tree_leaves(engine.pool):
                leaf.delete()

    def make_reference(self, run):
        reference = check_hybrid.Reference(
            run.spec.load_module("reference", self.family.REFERENCE),
            self.family, self.params, self.config, self.sizes)
        self.ref_logits = reference.logits
        self.ref_last_logits = reference.last_logits

    def step_check(self, run) -> dict:
        tol = run.config["check"]
        t0 = time.monotonic()
        found = check_hybrid.paged_step_vs_reference(
            self.params, self.config, self.ref_last_logits,
            rows=int(tol["paged_rows"]), cached=int(tol["paged_cached"]),
            engine_sizes=self.engine_sizes, seed=run.seed)
        log(f"paged step vs plain reference: {found} (limits "
            f"{tol['paged_logits_atol']}, {tol['paged_logits_rms']}) in "
            f"{time.monotonic() - t0:.1f}s")
        return {"step_max_dlogit": verdict(found["max_abs_dlogit"],
                                           tol["paged_logits_atol"]),
                "step_rms_dlogit": verdict(found["rms_dlogit"],
                                           tol["paged_logits_rms"])}


def run(run) -> dict:
    return base.run(_limits(run), Served)


def sweep(run, rates, step_s: float, out_path: str, lead_in_s: float = 0.0):
    return base.sweep(_limits(run), rates, step_s, out_path, lead_in_s,
                      Served)
