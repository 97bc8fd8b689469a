"""Driver ``serve_routed``: driver ``serve`` for a family whose expert
layers route -- the same set-up, window, sweep and request checks
(``chipbench/drivers/serve.py``, used as it stands), with the two
comparisons of ``chipbench/check_routed.py`` (the cache-path step under
the near-tie rule, the token margins as a share) in place of
``check.py``'s single numbers, and a reference that is run one row at a
time (its float32 temporaries beside 10 GB of weights).
"""
import random
import time

import numpy as np

from chipbench import check_routed, traffic as traffic_mod
from chipbench.drivers import serve as base

log = base.log


class Served(base.Served):
    """``serve.Served`` with the routed comparison; everything from the
    engine on is the same sequence."""

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
        ref_routing = jax.jit(
            lambda p, t, picks: reference.forward_with_routing(
                family.to_reference(p, self.config), t, self.sizes,
                last_picks=picks))

        def ref_logits(rows):
            """(rows, T) -> float32 logits, one row at a time."""
            return np.concatenate([
                np.asarray(ref_forward(self.params, np.asarray(row)[None]))
                for row in np.asarray(rows)])

        self.ref_logits = ref_logits
        self.ref_routing = lambda tokens, picks: ref_routing(
            self.params, tokens, picks)
        tol = cfg["check"]
        t0 = time.monotonic()
        verdict = check_routed.paged_step_vs_reference(
            self.params, self.config, self.ref_routing,
            rows=int(tol["paged_rows"]), cached=int(tol["paged_cached"]),
            engine_sizes=engine_sizes, seed=run.seed, tol=tol)
        self.paged_diff, self.paged_ok = (verdict["max_abs_dlogit"],
                                          verdict["ok"])
        log(f"paged step vs plain reference: {verdict} (limits: atol "
            f"{tol['paged_logits_atol']}, rms {tol['paged_logits_rms']}, "
            f"near tie "
            f"{tol['near_tie_margin']}, flipped share "
            f"{tol['max_flipped_share']}) in "
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
        self.server = ServingServer(self.engine).start()
        self.port = self.server.port
        log("server started")


def margins_ok(run, served, samples):
    """``serve.margins_ok`` for tokens that may have followed a flipped
    pick: the same seeded sample of finished requests, judged by
    ``check_routed.judge_tokens``."""
    tol = run.config["check"]
    done = [s for s in samples if s["end"] == "done" and s["tokens"]]
    if not done:
        return False
    picked = random.Random(run.seed).sample(
        done, min(int(tol["sample_requests"]), len(done)))
    prompts = [traffic_mod.prompt_tokens(run.seed, s["i"], s["prompt_len"],
                                         served.config.vocab_size)
               for s in picked]
    pad_to = (int(run.traffic["prompt_tokens"]["max"])
              + int(run.traffic["output_tokens"]["max"]))
    t0 = time.monotonic()
    below = check_routed.token_margins(
        served.ref_logits, prompts, [s["tokens"] for s in picked], pad_to)
    verdict = check_routed.judge_tokens(below, tol)
    log(f"f32 logit margin over {len(picked)} requests: {verdict} (limits: "
        f"share within {tol['token_logit_margin']} at least "
        f"{tol['token_share_within_margin']}, worst "
        f"{tol['token_logit_margin_worst']}); quantiles 0.5 / 0.9 / 0.99 "
        f"of the distance "
        f"{np.quantile(below, [0.5, 0.9, 0.99]).round(4).tolist()} in "
        f"{time.monotonic() - t0:.1f}s")
    return verdict["ok"]


def _as_served(fn, run, *args):
    """``serve``'s ``run`` / ``sweep`` with this file's set-up (and, in a
    rehearsal, the toy widths' limits)."""
    if run.rehearse:
        run.config = dict(run.config, check={
            **run.config["check"],
            **run.config.get("rehearse", {}).get("check", {})})
    saved = base.Served, base.margins_ok
    base.Served, base.margins_ok = Served, margins_ok
    try:
        return fn(run, *args)
    finally:
        base.Served, base.margins_ok = saved


def run(run) -> dict:
    return _as_served(base.run, run)


def sweep(run, rates, step_s: float, out_path: str):
    return _as_served(base.sweep, run, rates, step_s, out_path)
