"""Driver ``train``: one configuration trained through
``TransformerModel`` -> ``TPUModel(mode="synchronous").fit`` on a
``data x model`` mesh over the cell's chips, as a user calls it.

``fit_tokens`` builds a new train step on every call and ends every
epoch with a fetch that blocks. So: one warm-up ``fit`` of one step
(it compiles, and its loss is the first-step loss that is held against
the plain reference), then ONE measured ``fit`` of many short epochs. A
callback stamps the end of every epoch on the host's clock, opens the
window at the end of the first epoch (which holds the re-trace and the
cache lookup, and so belongs to set-up) and sets ``stop_training`` once
the window is over.
"""
import time

import numpy as np

from chipbench import check, device
from chipbench.evidence import Evidence


def log(message: str):
    device.log("train", message)


def make_stamper(run, evidence, seconds: float, trace_s: float):
    """The callback of the measured ``fit`` (a ``models.callbacks
    .Callback``, built here so that importing this file needs no
    program)."""
    import jax

    from elephas_tpu.models.callbacks import Callback

    class Stamper(Callback):
        def __init__(self):
            super().__init__()
            self.span = None
            self.state = "before" if run.trace else "off"

        def _close_span(self):
            if self.span is not None:
                self.span.__exit__(None, None, None)
                self.span = None

        def on_epoch_end(self, epoch, logs=None):
            now = time.monotonic()
            evidence.epoch_ends.append(now)
            start = evidence.epoch_ends[0]
            self._close_span()
            if self.state == "before" and \
                    now >= start + (seconds - trace_s) / 2:
                evidence.trace_dir, begun = device.start_trace(run.workdir)
                evidence.trace_window = [begun, None]
                self.state = "tracing"
            elif self.state == "tracing" and \
                    now >= evidence.trace_window[0] + trace_s:
                self.stop_trace()
            if self.state == "tracing":
                # the harness's own span around each epoch of the fit
                self.span = jax.profiler.TraceAnnotation("chipbench.epoch")
                self.span.__enter__()
            if now >= start + seconds:
                self.model.stop_training = True

        def stop_trace(self):
            if self.state == "tracing":
                evidence.trace_window[1] = device.stop_trace()
                self.state = "after"

        def on_train_end(self, logs=None):
            self._close_span()
            self.stop_trace()

    return Stamper()


def run(run) -> dict:
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from elephas_tpu import TPUModel
    from elephas_tpu.models import AdamW
    from elephas_tpu.models.transformer import param_specs
    from elephas_tpu.models.transformer_model import TransformerModel

    spec, cfg, job = run.spec, run.config, run.traffic
    family = spec.load_module("families", cfg["family"])
    sizes = family.model_sizes(cfg, run.rehearse)
    trainer = dict(cfg["trainer"])
    if run.rehearse:
        trainer.update(cfg.get("rehearse", {}).get("trainer", {}))
    seq, batch = int(job["seq_len"]), int(job["global_batch"])
    steps = int(job["steps_per_epoch"])
    tp = int(trainer["tensor_parallel"])
    config = family.program_config(
        sizes, max_seq_len=seq, param_dtype=cfg["param_dtype"],
        remat=bool(trainer["remat"]),
        attention_impl=trainer["attention_impl"])
    chips = len(run.devices)
    mesh = Mesh(np.array(run.devices).reshape(chips // tp, tp),
                ("data", "model"))
    shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), param_specs(config, mesh=mesh),
        is_leaf=lambda s: isinstance(s, PartitionSpec))
    t0 = time.monotonic()
    params = family.make_params(config, run.seed, out_shardings=shardings)
    jax.block_until_ready(params)
    log(f"parameters on the mesh {dict(mesh.shape)} in "
        f"{time.monotonic() - t0:.1f}s")

    rng = np.random.default_rng(run.seed)
    tokens = rng.integers(1, config.vocab_size, (steps * batch, seq),
                          dtype=np.int64).astype(np.int32)

    # the plain reference's loss on the first batch, at the initial
    # parameters: before any fit, because a fit donates them
    reference = spec.load_module("reference", family.REFERENCE)
    ref_loss = jax.jit(lambda p, t: reference.loss(
        family.to_reference(p, config), t, sizes))
    t0 = time.monotonic()
    want = float(np.mean([float(ref_loss(params, tokens[r:r + 1]))
                          for r in range(batch)]))
    log(f"plain reference loss on the first batch {want:.6f} in "
        f"{time.monotonic() - t0:.1f}s")

    model = TransformerModel(config, tensor_parallel=tp, mesh=mesh)
    model.params, model.built = params, True
    del params
    model.compile(AdamW(learning_rate=float(trainer["learning_rate"])))
    tpu_model = TPUModel(model, mode="synchronous")
    t0 = time.monotonic()
    tpu_model.fit(tokens[:batch], epochs=1, batch_size=batch,
                  validation_split=0.0)
    got = float(tpu_model.training_histories[-1]["loss"][0])
    rel = abs(got - want) / abs(want)
    tol = cfg["check"]
    first_ok = rel <= float(tol["first_loss_rtol"])
    log(f"warm-up fit (one step) in {time.monotonic() - t0:.1f}s: "
        f"first-step loss {got:.6f} vs reference {want:.6f}, relative "
        f"{rel:.2e} (limit {tol['first_loss_rtol']}); "
        f"{run.watch.summary()}")

    evidence = Evidence(run)
    evidence.sizes, evidence.seq_len = sizes, seq
    evidence.param_dtype = cfg["param_dtype"]
    evidence.tokens_per_epoch = steps * batch * seq
    stamper = make_stamper(run, evidence, run.seconds,
                           float(job.get("trace_s", 4.0)))
    tpu_model.fit(tokens, epochs=int(job["max_epochs"]), batch_size=batch,
                  validation_split=0.0, callbacks=[stamper])
    history = tpu_model.training_histories[-1]
    ends = evidence.epoch_ends
    evidence.window = [ends[0], ends[-1]]
    evidence.setup_s = ends[0] - run.t_process_start
    evidence.compiles_in_window = run.watch.compiles_between(ends[0],
                                                             ends[-1])
    losses = [got] + [float(v) for v in history["loss"]]
    progress = check.losses_ok(losses)
    gaps = np.diff(ends)
    log(f"{len(ends)} epochs of {steps} steps; losses "
        f"{[round(v, 4) for v in losses[:3]]} ... "
        f"{[round(v, 4) for v in losses[-2:]]}; epoch seconds: harness "
        f"median {np.median(gaps):.4f}, program's epoch_time median "
        f"{np.median(history['epoch_time'][1:]):.4f}; compiles inside "
        f"the window: {evidence.compiles_in_window}")
    if len(ends) >= int(job["max_epochs"]):
        log("max_epochs reached before the window's end: raise it")
    return {"correct": bool(first_ok and progress and len(ends) >= 3
                            and len(ends) < int(job["max_epochs"])),
            "attempted": (len(ends) - 1) * steps, "failed": 0,
            "evidence": evidence}
