"""GPipe-style pipeline parallelism over a ``pipe`` mesh axis.

The reference cannot do any model parallelism (``README.md:319-321`` calls
it "practically impossible" under Spark); on TPU it is a mesh axis. This
module implements the classic microbatched pipeline schedule as a pure
function under ``shard_map``:

- stage parameters are stacked along a leading axis sharded over ``pipe``
  (device s holds stage s),
- the batch splits into M microbatches; at tick t stage 0 injects
  microbatch t while every stage processes the activation it received
  last tick and ``ppermute``s its output to the next stage,
- after ``M + S - 1`` ticks the last stage has produced every microbatch;
  outputs are gathered with a masked ``psum`` so the result is replicated.

The schedule lives inside one ``lax.scan`` — XLA sees a static loop of
S-way-parallel stage computations with neighbor-only ICI transfers, which
is exactly the hardware-shaped formulation of GPipe. Differentiable end
to end (``shard_map``/``ppermute``/``scan`` all have transpose rules), so
``jax.grad`` of a pipelined loss just works; the backward pass is the
reverse pipeline.
"""
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["make_pipeline_fn", "stack_stage_params",
           "split_transformer_stages", "merge_transformer_stages",
           "shard_pipelined_params", "make_pipelined_lm_loss",
           "make_pipelined_train_step"]


def stack_stage_params(per_stage_params):
    """Stack a list of per-stage parameter pytrees (identical structure)
    along a new leading axis — the axis that shards over ``pipe``."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                  *per_stage_params)


def make_pipeline_fn(stage_fn: Callable, mesh: Mesh, axis: str = "pipe",
                     num_microbatches: int = None,
                     batch_axis: Optional[str] = None):
    """Build ``fn(stacked_params, x) -> y`` running ``stage_fn`` as a
    microbatched pipeline over ``mesh[axis]``.

    :param stage_fn: ``(stage_params, x_micro) -> y_micro``, shape
        preserving (the activation flowing between stages must keep one
        shape, as in a stack of transformer blocks).
    :param num_microbatches: number of microbatches M (default: pipeline
        depth). The batch dimension must divide by M.
    :param batch_axis: optional data-parallel mesh axis: each dp row of
        the mesh pipelines its own batch shard through the same stage
        stack (dp x pp composition — stage params are sharded over
        ``axis`` and replicated over ``batch_axis``; the gradient
        all-reduce over ``batch_axis`` is inserted by GSPMD where the
        loss averages over the global batch).
    """
    num_stages = mesh.shape[axis]
    M = num_microbatches or num_stages

    def pipelined(stacked_params, x):
        leading = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
        if leading != num_stages:
            raise ValueError(
                f"stacked params hold {leading} stages but mesh axis "
                f"{axis!r} has {num_stages} devices — a mismatched stack "
                "would silently drop stages")
        if x.shape[0] % M:
            raise ValueError(f"batch {x.shape[0]} not divisible by "
                             f"{M} microbatches")
        micro = x.reshape((M, x.shape[0] // M) + x.shape[1:])

        def per_device(params_local, micro_local):
            # params_local leading dim is 1 (this device's stage slice)
            stage_params = jax.tree_util.tree_map(lambda p: p[0],
                                                  params_local)
            idx = jax.lax.axis_index(axis)
            num_ticks = M + num_stages - 1
            state0 = jnp.zeros_like(micro_local[0])

            def tick(state, t):
                # stage 0 injects microbatch t (clamped; injections past
                # M-1 never reach the collected output window)
                inject = jax.lax.dynamic_index_in_dim(
                    micro_local, jnp.minimum(t, M - 1), axis=0,
                    keepdims=False)
                x_in = jnp.where(idx == 0, inject, state)
                y = stage_fn(stage_params, x_in)
                # neighbor-only transfer: stage s -> s+1 over ICI
                state_next = jax.lax.ppermute(
                    y, axis, [(s, s + 1) for s in range(num_stages - 1)])
                return state_next, y

            _, ys = jax.lax.scan(tick, state0, jnp.arange(num_ticks))
            # microbatch m finishes on the LAST stage at tick m + S - 1;
            # mask everyone else and psum to replicate the result
            outs = jax.lax.dynamic_slice_in_dim(ys, num_stages - 1, M,
                                                axis=0)
            outs = jnp.where(idx == num_stages - 1, outs,
                             jnp.zeros_like(outs))
            return jax.lax.psum(outs, axis)

        in_spec = jax.tree_util.tree_map(lambda _: P(axis), stacked_params)
        # micro is (M, B, ...): with a dp axis the per-microbatch batch
        # dim shards over it, so each dp row pipelines its own shard
        x_spec = P(None, batch_axis) if batch_axis is not None else P()
        y = jax.shard_map(per_device, mesh=mesh,
                          in_specs=(in_spec, x_spec), out_specs=x_spec,
                          check_vma=False)(stacked_params, micro)
        return y.reshape(x.shape[0:1] + y.shape[2:])

    return pipelined


# --------------------------------------------------------- pipelined LM
# End-to-end pipeline-parallel training of the flagship transformer:
# embedding and LM head live OUTSIDE the shape-preserving stage stack
# (they change the activation shape, so they cannot be pipeline stages),
# the transformer blocks flow through the GPipe schedule above, and the
# optimizer steps over the stage-stacked parameter pytree. Gradient
# accumulation across microbatches is inherent: the loss averages over
# the full batch, so differentiating through the pipeline's scan sums
# each stage's gradient contributions over all of its microbatches —
# exactly GPipe's accumulate-then-apply semantics, derived by transpose
# instead of hand-scheduled.

def split_transformer_stages(params: Dict, config, num_stages: int) -> Dict:
    """Rearrange a :func:`~elephas_tpu.models.transformer.init_params`
    pytree for pipeline execution:

    ``{"embed", "final_ln", "stages"}`` where ``stages`` stacks the
    ``layer_i`` subtrees as ``(num_stages, layers_per_stage, ...)`` —
    leading axis sharded over ``pipe``, second axis looped inside a stage.
    """
    L = config.num_layers
    if L % num_stages:
        raise ValueError(f"{L} layers do not split into {num_stages} "
                         "equal pipeline stages")
    per_stage = L // num_stages
    stages = [
        jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs),
            *[params[f"layer_{s * per_stage + j}"] for j in range(per_stage)])
        for s in range(num_stages)]
    out = {"embed": params["embed"], "final_ln": params["final_ln"],
           "stages": stack_stage_params(stages)}
    if "head" in params:  # untied LM head rides outside the stage stack
        out["head"] = params["head"]
    return out


def merge_transformer_stages(pipe_params: Dict, config) -> Dict:
    """Inverse of :func:`split_transformer_stages` — back to the flat
    ``layer_i`` layout (checkpoint interop, parity tests)."""
    stages = pipe_params["stages"]
    num_stages = jax.tree_util.tree_leaves(stages)[0].shape[0]
    per_stage = config.num_layers // num_stages
    params = {"embed": pipe_params["embed"],
              "final_ln": pipe_params["final_ln"]}
    if "head" in pipe_params:
        params["head"] = pipe_params["head"]
    for s in range(num_stages):
        for j in range(per_stage):
            params[f"layer_{s * per_stage + j}"] = jax.tree_util.tree_map(
                lambda p: p[s, j], stages)
    return params


def shard_pipelined_params(pipe_params: Dict, mesh: Mesh,
                           axis: str = "pipe") -> Dict:
    """Place the pipelined pytree: stage stack sharded over ``axis``
    (device s holds stage s's layers), embed/head replicated."""
    def put(path_is_stage, p):
        if path_is_stage:
            spec = P(axis, *([None] * (p.ndim - 1)))
        else:
            spec = P()
        return jax.device_put(p, NamedSharding(mesh, spec))

    out = {
        "embed": jax.tree_util.tree_map(lambda p: put(False, p),
                                        pipe_params["embed"]),
        "final_ln": jax.tree_util.tree_map(lambda p: put(False, p),
                                           pipe_params["final_ln"]),
        "stages": jax.tree_util.tree_map(lambda p: put(True, p),
                                         pipe_params["stages"]),
    }
    if "head" in pipe_params:
        out["head"] = jax.tree_util.tree_map(lambda p: put(False, p),
                                             pipe_params["head"])
    return out


def make_pipelined_lm_loss(config, mesh: Mesh, axis: str = "pipe",
                           num_microbatches: Optional[int] = None,
                           batch_axis: Optional[str] = None):
    """Build ``loss(pipe_params, tokens)`` — next-token cross-entropy of
    the transformer LM with its blocks running as a GPipe pipeline.

    Dense configs only: MoE blocks route over the ``model`` axis, which
    composes with tp, not pp-stage stacking. Attention inside a stage is
    always the XLA path (each stage owns the full local sequence; the
    Pallas kernel would need its own shard_map nesting).
    """
    from ..models.transformer import (block_apply, embed_apply, head_logits,
                                      next_token_loss)

    if config.num_experts > 1:
        raise ValueError(
            "pipelined LM training supports dense configs only "
            f"(num_experts={config.num_experts}); shard experts over the "
            "'model' axis with make_train_step instead")
    num_stages = mesh.shape[axis]
    per_stage = config.num_layers // num_stages
    if config.num_layers % num_stages:
        raise ValueError(f"{config.num_layers} layers do not split into "
                         f"{num_stages} equal pipeline stages")

    block = block_apply
    if config.remat:
        # recompute each block in the pipeline's backward sweep: with M
        # microbatches in flight GPipe keeps O(M) activations live per
        # stage, so per-block remat is the difference between activation
        # memory scaling with the *microbatch count* vs the *stage depth*
        block = jax.checkpoint(block_apply, static_argnums=(2,))

    def stage_fn(stage_params, x):
        for j in range(per_stage):
            layer = jax.tree_util.tree_map(lambda p: p[j], stage_params)
            x = block(layer, x, config)
        return x

    pipe_fn = make_pipeline_fn(stage_fn, mesh, axis=axis,
                               num_microbatches=num_microbatches,
                               batch_axis=batch_axis)

    def loss(pipe_params, tokens):
        x = embed_apply(pipe_params["embed"], tokens, config)
        x = pipe_fn(pipe_params["stages"], x)
        logits = head_logits(pipe_params["embed"], pipe_params["final_ln"],
                             x, head=pipe_params.get("head"),
                             norm=config.norm,
                             multipliers=config.multipliers)
        return next_token_loss(logits, tokens)

    return loss


def make_pipelined_train_step(config, tx, mesh: Mesh, axis: str = "pipe",
                              num_microbatches: Optional[int] = None,
                              batch_axis: Optional[str] = None):
    """Jitted ``(pipe_params, opt_state, tokens) -> (pipe_params,
    opt_state, loss)``: forward + backward through the pipeline (gradient
    accumulation over microbatches via the scan transpose) and an optax
    update over the stage-stacked pytree, all in one compiled program.
    With ``batch_axis`` the step runs dp x pp: tokens shard over the
    data axis, each dp row pipelines its shard, and the loss mean makes
    GSPMD all-reduce the gradients across rows."""
    loss_fn = make_pipelined_lm_loss(config, mesh, axis=axis,
                                     num_microbatches=num_microbatches,
                                     batch_axis=batch_axis)

    def step(pipe_params, opt_state, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(pipe_params, tokens)
        updates, opt_state = tx.update(grads, opt_state, pipe_params)
        pipe_params = jax.tree_util.tree_map(lambda p, u: p + u,
                                             pipe_params, updates)
        return pipe_params, opt_state, loss

    return jax.jit(step, donate_argnums=(0, 1))
