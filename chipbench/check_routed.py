"""The comparisons that decide ``correct`` for a family with routed
experts.

Routing is a discontinuity: where the float32 reference's last kept and
first dropped scores nearly tie, a bf16 hidden state picks the other
expert, and the logits then differ by an expert's whole output -- and
every later layer routes another hidden state. With seeded weights about
one choice in ten is such a tie, so ``chipbench/check.py``'s one number
does not do here. The cache-path comparison:

- runs the timed path's own step (``prefill_cache`` in small batches,
  ``install_row_paged``, ONE ``decode_step_paged`` at the engine's
  shapes: its ``max_slots`` rows, table width, pool and ladder of
  widths, every slot live) and takes its logits and its routers' picks;
- runs the plain reference row by row WITH THE PROGRAM'S PICKS at the
  compared position (``last_picks``, weighted by the reference's own
  float32 probabilities; each slot's own picks, :func:`follow_each_slot`),
  so that the reference's hidden state follows the slot's through every
  layer, and takes its logits, its own
  picks there and, per expert layer, how decided its own choice was
  (``margin``: the relative gap between the last kept and the first
  dropped score, groups and experts both);
- EVERY row's logits are held to ``paged_logits_atol`` at the worst
  and to ``paged_logits_rms`` over all of them (the root mean square
  scatters far less from seed to seed than the largest of 1.6 million
  values, so it can sit closer to the readings): a flipped pick no
  longer hides in them, and neither does anything else;
- a choice (one row in one expert layer) is FLIPPED when program and
  reference pick other HELD experts (picks on absent experts add
  nothing on either side); every flipped choice must be a near-tie, the
  reference's margin there at most ``near_tie_margin``: a disagreement
  on a decided choice fails the run whatever the logits;
- at most ``max_flipped_share`` of the choices may be flipped;
- a row with no flipped choice in any layer was given nothing but what
  the reference picks itself: ``agreeing_rows`` and
  ``max_abs_dlogit_agreeing`` report those rows alone (the comparison
  ISSUE 28 set out), under the same limit.

The token-margin oracle over finished requests (:func:`token_margins`,
:func:`judge_tokens`) has only tokens to go by, and a served token that
followed a flipped pick lies an expert's output below the reference's
best. So it holds the SHARE of emitted tokens whose reference logit lies
within ``token_logit_margin`` of that position's best to at least
``token_share_within_margin``, and every token to
``token_logit_margin_worst``.
"""
import numpy as np

__all__ = ["paged_step_vs_reference", "follow_each_slot", "judge",
           "token_margins", "judge_tokens"]


def judge(got, want, picks, ref_picks, margins, held, tol) -> dict:
    """The verdict from arrays: ``got`` / ``want`` (rows, V) logits;
    ``picks`` / ``ref_picks`` (layers, rows, k); ``margins`` (layers,
    rows); ``held`` = (first, count)."""
    first, count = held

    def held_sets(p):
        return [[frozenset(int(e) for e in p[l, r]
                           if first <= e < first + count)
                 for r in range(p.shape[1])] for l in range(p.shape[0])]

    mine, theirs = held_sets(np.asarray(picks)), held_sets(
        np.asarray(ref_picks))
    margins = np.asarray(margins)
    layers, rows = margins.shape
    flipped = np.array([[mine[l][r] != theirs[l][r] for r in range(rows)]
                        for l in range(layers)])
    worst_margin = float(margins[flipped].max()) if flipped.any() else 0.0
    finite = bool(np.isfinite(got).all() and np.isfinite(want).all())
    diff = np.asarray(got, np.float64) - np.asarray(want, np.float64)
    max_diff = float(np.abs(diff).max()) if finite else float("inf")
    rms_diff = float(np.sqrt(np.mean(diff * diff))) if finite \
        else float("inf")
    agree = ~flipped.any(axis=0)
    out = {"rows": int(rows), "choices": int(flipped.size),
           "flipped_choices": int(flipped.sum()),
           "max_abs_dlogit": max_diff, "rms_dlogit": rms_diff,
           "worst_flipped_margin": worst_margin,
           "agreeing_rows": int(agree.sum()),
           "max_abs_dlogit_agreeing": float(np.abs(diff[agree]).max())
           if finite and agree.any() else 0.0}
    out["ok"] = bool(
        finite and max_diff <= float(tol["paged_logits_atol"])
        and rms_diff <= float(tol["paged_logits_rms"])
        and worst_margin <= float(tol["near_tie_margin"])
        and flipped.mean() <= float(tol["max_flipped_share"]))
    return out


def follow_each_slot(picks, of_slot, held, reference):
    """The reference's answer for every slot, following THAT slot's
    picks. ``picks`` (layers, slots, k); ``of_slot`` (slots,) the prompt
    a slot holds; ``reference(prompt, picks (layers, 1, k))`` -> (logits
    (V,), its own picks (layers, k), margins (layers,)) at the compared
    position. The copies of one prompt are not bit-identical on the chip:
    a row's place in the batch moves its bf16 roundings, and the copies'
    logits lie 0.02-0.07 apart (``copies_max_abs_dlogit``), so in about
    one seed in ten some copy breaks a close tie the other way than the
    first. A copy held to the reference that followed ANOTHER copy's
    picks then differs by an expert's whole output, agreeing row or not
    (0.79 and 1.55 on the two seeds that showed it). So the reference
    runs once for every distinct (prompt, held picks): once a prompt
    nearly always, 9 or 10 times for 8 prompts on such a seed. Returns (want (slots, V), reference picks
    (layers, slots, k), margins (layers, slots), reference runs)."""
    first, count = held
    picks = np.asarray(picks)
    followed, per_slot = {}, []
    for s, r in enumerate(int(r) for r in of_slot):
        key = (r, tuple(tuple(sorted(int(e) for e in layer
                                     if first <= e < first + count))
                        for layer in picks[:, s]))
        if key not in followed:
            followed[key] = reference(r, picks[:, s:s + 1])
        per_slot.append(followed[key])
    want, ref_picks, margins = (np.stack(part) for part in zip(*per_slot))
    return (want, np.moveaxis(ref_picks, 0, 1), np.moveaxis(margins, 0, 1),
            len(followed))


def paged_step_vs_reference(params, config, ref_routing, rows: int,
                            cached: int, engine_sizes: dict, seed: int,
                            tol: dict, prefill_rows: int = 2) -> dict:
    """The step is compiled at the ENGINE's shapes: ``max_slots`` rows,
    its table width, its pool and its ladder of widths, every slot live
    at ``cached`` positions (slot ``s`` holds prompt ``s % rows`` in
    blocks of its own), so the program picks the rung the timed window
    sits on and every slot's logits are held to the reference's for its
    prompt and that slot's picks. ``ref_routing(tokens (1, T),
    last_picks (L, 1, k))`` -> (logits (1, T, V), picks (L, 1, T, k),
    margins (L, 1, T)) of the plain reference."""
    import jax
    import jax.numpy as jnp

    from elephas_tpu.models.paged_decode import (decode_step_paged,
                                                 held_ladder,
                                                 init_paged_pool,
                                                 install_row_paged)
    from elephas_tpu.models.transformer import prefill_cache

    slots = int(engine_sizes["max_slots"])
    pool_blocks, block_size = (int(v) for v in engine_sizes["paged"])
    table_width = -(-int(engine_sizes["max_len"]) // block_size)
    need = cached // block_size + 1
    if 1 + slots * need > pool_blocks or need > table_width:
        raise ValueError(f"{slots} slots of {need} blocks do not fit the "
                         f"engine's pool ({pool_blocks}, {table_width})")
    ladder = held_ladder(config, slots, table_width)   # the engine's
    prompts = jax.random.randint(jax.random.PRNGKey(seed), (rows, cached),
                                 1, config.vocab_size)
    prefill = jax.jit(lambda p, t: prefill_cache(p, t, config,
                                                 need * block_size))
    pool = init_paged_pool(config, pool_blocks, block_size)
    tables = np.zeros((slots, table_width), np.int32)
    last = []
    for r0 in range(0, rows, prefill_rows):
        logits, cache = prefill(params, prompts[r0:r0 + prefill_rows])
        last.append(jnp.argmax(logits, axis=-1).astype(jnp.int32))
        for r in range(r0, min(rows, r0 + prefill_rows)):
            row = jax.tree_util.tree_map(
                lambda a, k=r - r0: a[k:k + 1], cache)
            for s in range(r, slots, rows):
                tables[s, :need] = 1 + s * need + np.arange(need)
                pool = install_row_paged(pool, row, tables[s, :need], need)
        del cache
    of_slot = np.arange(slots) % rows
    last = jnp.concatenate(last)
    step = jax.jit(lambda p, pl, tb, tk, ps: decode_step_paged(
        p, pl, tb, tk, ps, config, held_blocks=ladder,
        with_stats=True), donate_argnums=(1,))
    got, pool, stats = step(params, pool, jnp.asarray(tables),
                            last[of_slot],
                            jnp.full((slots,), cached, jnp.int32))
    del pool
    picks = np.asarray(stats["picks"])                 # (L, slots, k)
    tokens = np.concatenate([np.asarray(prompts),
                             np.asarray(last)[:, None]], axis=1)

    def reference(r, slot_picks):
        logits, picked, margin = ref_routing(tokens[r:r + 1], slot_picks)
        return (np.asarray(logits)[0, -1], np.asarray(picked)[:, 0, -1],
                np.asarray(margin)[:, 0, -1])

    want, ref_picks, margins, runs = follow_each_slot(
        picks, of_slot, config.held_experts, reference)
    got = np.asarray(got, np.float32)
    verdict = judge(got, want, picks, ref_picks, margins,
                    config.held_experts, tol)
    verdict["reference_runs"] = runs
    # how far a prompt's copies lie from its first one: 0.0 where a
    # row's place in the batch moves nothing
    verdict["copies_max_abs_dlogit"] = float(
        np.abs(got - got[of_slot]).max())
    verdict["ladder"] = list(ladder)
    return verdict


def token_margins(ref_logits, prompts, outputs, pad_to: int):
    """Teacher-force prompt + output through the reference, one request
    at a time in rows of the fixed width ``pad_to`` (causal, so the
    right padding touches nothing before it). Per emitted token: how far
    its reference logit lies below that position's best. One flat
    array."""
    below = []
    for prompt, output in zip(prompts, outputs):
        row = np.zeros((1, pad_to), np.int32)
        seq = list(prompt) + list(output)
        row[0, :len(seq)] = seq
        logits = ref_logits(row)[0]
        for j, tok in enumerate(output):
            at = logits[len(prompt) + j - 1]      # predicts len(prompt)+j
            below.append(float(at.max() - at[tok]))
    return np.asarray(below)


def judge_tokens(below, tol) -> dict:
    """The verdict over the tokens of :func:`token_margins`."""
    sound = bool(below.size and np.isfinite(below).all())
    out = {"tokens": int(below.size),
           "share_within_margin": float(
               (below <= float(tol["token_logit_margin"])).mean())
           if sound else 0.0,
           "worst": float(below.max()) if sound else float("inf")}
    out["ok"] = bool(
        sound and out["share_within_margin"] >= float(
            tol["token_share_within_margin"])
        and out["worst"] <= float(tol["token_logit_margin_worst"]))
    return out
