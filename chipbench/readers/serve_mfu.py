"""Reader ``serve_mfu``: the whole window's share of the chip's peak.

Model FLOPs the window's work needs -- every token that reached the
client inside it (body and head) and every prompt whose first token
came inside it (its tokens through the body, the head once) -- over the
peak bf16 rate times the window, in percent. ``costs`` names the module
under ``chipbench/`` whose ``serve_token_flops(sizes, held_pick_share)``
says what a token costs (``costs`` for a dense decoder,
``costs_deepseek_v2`` with the share of picks on held experts from the
program's counters). Matrix products only: a floor on the utilisation,
and a bound on every kernel's roofline share: a kernel taken off the
path leaves its roofline silent, this still reads the work done over
the time it took. Prefill and decode both count, so it moves with
tokens a second whichever of them a change speeds up. None off the TPU.
"""
import importlib

from chipbench.readers import client_samples, prometheus_delta


def read(evidence, costs: str):
    device = evidence.run.device
    if device["platform"] != "tpu" or not evidence.samples \
            or not evidence.window:
        return None
    from chipbench import costs as tables

    start, end = evidence.window
    per_token = importlib.import_module(
        f"chipbench.{costs}").serve_token_flops(
            evidence.sizes, prometheus_delta.read(
                evidence, "serving_moe_held_picks_total",
                "serving_moe_picks_total"))
    served = client_samples.tokens_in(evidence.samples, start, end)
    prompts = [s["prompt_len"] for s in evidence.samples
               if s["events"] and start <= s["events"][0][0] < end]
    flops = (served * (per_token["body"] + per_token["head"])
             + sum(prompts) * per_token["body"]
             + len(prompts) * per_token["head"])
    peak = tables.peaks(device["kind"])["bf16_flops_per_s"]
    return 100.0 * flops / (peak * evidence.chips * (end - start))
