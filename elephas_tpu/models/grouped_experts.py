"""Sparse SwiGLU experts without dropped tokens, and their routers.

The expert layer of the DeepSeek-V2 kind: a softmax router over ALL
``num_experts`` (float32), ``expert_top_k`` picks a token, SwiGLU experts
of width ``expert_d_ff``, and an always-on shared SwiGLU of width
``shared_d_ff`` added to the routed sum. The router is
``group_limited_greedy`` (DeepSeek-V2 §2.2.1's device-limited routing):
the experts form ``moe_n_groups`` equal groups, a group scores as its
best expert, only the ``moe_topk_groups`` best groups stay eligible, the
``expert_top_k`` largest probabilities among them are taken as they are
(no renormalisation) and the routed sum is multiplied by
``routed_scaling_factor``. One group (the defaults) is plain top-k.

**Held experts.** A layer is told which experts it holds:
``config.held_experts = (first, count)``, a range of the router's
``num_experts`` (``None``: all of them). Its parameter stacks hold only
those. It routes over every expert and computes the part of the result
that its own experts give; picks that fall on absent experts add
nothing. That is one rank of an expert-parallel layer without its
exchange: the ranks' parts, with the shared expert counted once, sum to
the whole layer's output.

**Compute.** The (token, pick) pairs are sorted by held expert, pairs on
absent experts (and pairs of rows that are not ``live``) last, and the
three matrix products run group by group with
:func:`jax.lax.ragged_dot`, which XLA:TPU compiles to a grouped matmul
that reads an expert's weights only when its group is not empty. Every
pair keeps its row: no capacity, no padding, no dropped token, whatever
the imbalance. Cost follows the tokens an expert gets (prefill) and the
bytes of the experts touched (decode).

Parameters of one expert layer (``layer["moe"]``)::

    gate (D, num_experts)
    w1, w3 (held, D, expert_d_ff)    w2 (held, expert_d_ff, D)
    shared {w1, w3 (D, shared_d_ff), w2 (shared_d_ff, D)}   # if any

Nothing here imports :mod:`~elephas_tpu.models.transformer`.
"""
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["held_range", "init_experts", "expert_specs", "route",
           "experts_apply", "STATS"]

#: what :func:`experts_apply` counts, in this order, as int32
STATS = ("picks", "held_picks", "experts_touched", "layer_steps")


def held_range(c) -> Tuple[int, int]:
    """(first held expert, how many)."""
    return (0, c.num_experts) if c.held_experts is None else tuple(
        int(v) for v in c.held_experts)


def init_experts(c, keys, dense) -> Dict:
    """One expert layer's parameters; ``keys`` at least seven keys."""
    held = held_range(c)[1]
    d, f = c.d_model, c.expert_d_ff
    moe = {"gate": dense(keys[0], (d, c.num_experts), d),
           "w1": dense(keys[1], (held, d, f), d),
           "w3": dense(keys[2], (held, d, f), d),
           "w2": dense(keys[3], (held, f, d), f)}
    if c.shared_d_ff:
        fs = c.shared_d_ff
        moe["shared"] = {"w1": dense(keys[4], (d, fs), d),
                         "w3": dense(keys[5], (d, fs), d),
                         "w2": dense(keys[6], (fs, d), fs)}
    return moe


def expert_specs(c, P, e_ax, ff_ax) -> Dict:
    """PartitionSpecs mirroring :func:`init_experts`: the stacks shard
    their expert axis, the router replicates, the shared expert shards
    like a dense gated MLP."""
    specs = {"gate": P(None, None), "w1": P(e_ax, None, None),
             "w3": P(e_ax, None, None), "w2": P(e_ax, None, None)}
    if c.shared_d_ff:
        specs["shared"] = {"w1": P(None, ff_ax), "w3": P(None, ff_ax),
                           "w2": P(ff_ax, None)}
    return specs


def route(hf, gate, c):
    """Tokens ``hf`` (N, D) -> (``weights`` (N, k) float32, ``picks``
    (N, k) int32 over all ``num_experts``, ``probs`` (N, E) float32).
    The router runs in float32: bf16 scores would tie and pick
    differently."""
    with jax.named_scope("elephas.moe.route"):
        probs = jax.nn.softmax(
            hf.astype(jnp.float32) @ gate.astype(jnp.float32), axis=-1)
        n, e = probs.shape
        groups = c.moe_n_groups
        best = probs.reshape(n, groups, e // groups).max(axis=-1)
        _, kept = jax.lax.top_k(best, c.moe_topk_groups)
        keep = jnp.zeros((n, groups), bool).at[
            jnp.arange(n)[:, None], kept].set(True)
        eligible = jnp.where(jnp.repeat(keep, e // groups, axis=1),
                             probs, 0.0)
        weights, picks = jax.lax.top_k(eligible, c.expert_top_k)
        weights = weights * c.routed_scaling_factor
    return weights, picks.astype(jnp.int32), probs


def _swiglu(h, w, c):
    gate = jax.nn.silu(h @ w["w1"].astype(c.dtype))
    return (gate * (h @ w["w3"].astype(c.dtype))) @ w["w2"].astype(c.dtype)


def experts_apply(h, moe: Dict, c, live: Optional[jnp.ndarray] = None):
    """The expert layer on normalised activations ``h`` (..., D), in the
    compute dtype: this rank's routed part plus the shared expert.
    ``live`` (leading shape of ``h``, bool) marks rows whose result is
    used; the others are given no expert (the static-batch decode step's
    idle rows would otherwise read experts for nothing).

    Returns ``(out, stats)``: ``out`` like ``h``; ``stats`` holds
    ``"counts"``, int32 (:data:`STATS`): picks made by live rows, those
    that fell on held experts, held experts that got at least one of
    them, and 1; and ``"picks"``, the router's picks ``(..., k)`` over
    all ``num_experts`` (for comparisons with a reference; dead code in
    a program that does not return them)."""
    lead, d = h.shape[:-1], h.shape[-1]
    hf = h.reshape(-1, d)
    n, k = hf.shape[0], c.expert_top_k
    first, held = held_range(c)
    weights, picks, _ = route(hf, moe["gate"], c)
    with jax.named_scope("elephas.moe.experts"):
        local = picks.reshape(n * k) - first
        mine = (local >= 0) & (local < held)
        alive = (jnp.ones((n,), bool) if live is None
                 else live.reshape(n))[jnp.arange(n * k) // k]
        mine = mine & alive
        # sort the pairs by held expert; the rest go last, to no group
        key = jnp.where(mine, local, held)
        order = jnp.argsort(key, stable=True)
        sizes = jnp.sum(jax.nn.one_hot(key, held, dtype=jnp.int32), axis=0)
        xs = hf[order // k]
        up = jax.lax.ragged_dot(xs, moe["w3"].astype(c.dtype), sizes)
        act = jax.nn.silu(jax.lax.ragged_dot(
            xs, moe["w1"].astype(c.dtype), sizes)) * up
        rows = jax.lax.ragged_dot(act, moe["w2"].astype(c.dtype), sizes)
        # rows past the last group belong to no expert: whatever the
        # grouped product left there is masked, not multiplied, away
        kept = mine[order]
        scale = weights.reshape(n * k)[order]
        rows = jnp.where(kept[:, None],
                         rows.astype(jnp.float32) * scale[:, None], 0.0)
        back = jnp.zeros((n * k,), jnp.int32).at[order].set(
            jnp.arange(n * k, dtype=jnp.int32))
        out = rows[back].reshape(n, k, d).sum(axis=1).astype(c.dtype)
    if "shared" in moe:
        with jax.named_scope("elephas.moe.shared"):
            out = out + _swiglu(hf, moe["shared"], c)
    counts = jnp.stack([jnp.sum(alive), jnp.sum(mine),
                        jnp.sum(sizes > 0), jnp.ones((), jnp.int32)]
                       ).astype(jnp.int32)
    return out.reshape(*lead, d), {"counts": counts,
                                   "picks": picks.reshape(*lead, k)}
