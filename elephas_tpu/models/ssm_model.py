"""SSMModel: the selective-SSM family behind the framework's model
surface.

Wraps :mod:`.ssm`'s functional core in the same training/serving
contract :class:`~elephas_tpu.models.transformer_model.TransformerModel`
exposes: ``compile`` (optimizer by name or object), ``fit`` over token
arrays with the callback suite (``ModelCheckpoint`` —
sync or async — ``EarlyStopping``, preemption traps, ...),
``training_state``/``restore_training_state`` for bit-exact resume,
``generate``, and one-call HTTP ``serve()`` via
:class:`~elephas_tpu.ssm_engine.SSMEngine`. Data-parallel training over
a mesh rides :func:`~elephas_tpu.models.ssm.make_ssm_train_step`.
"""
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .ssm import (SSMConfig, init_ssm_params, make_ssm_train_step,
                  ssm_generate, ssm_lm_loss)

__all__ = ["SSMModel"]


class SSMModel:
    """Keras-shaped wrapper over the selective-SSM LM."""

    def __init__(self, config: SSMConfig, mesh=None,
                 data_axis: str = "data", name: str = "ssm_model"):
        self.config = config
        self.mesh = mesh
        self.data_axis = data_axis
        self.name = name
        self.params: Optional[Dict] = None
        self.optimizer = None
        self.loss: Optional[str] = None
        self.metrics: list = []
        self._tx = None
        self._opt_state = None
        self._step_fn = None
        self._jit_forward = None
        self._jit_loss = None
        self.stop_training = False

    # ----------------------------------------------------------- build
    def build(self, seed: int = 0):
        self.params = init_ssm_params(self.config,
                                      jax.random.PRNGKey(seed))
        # fresh weights must never inherit moments accumulated on the
        # previous parameters
        self._opt_state = None
        return self

    @property
    def built(self) -> bool:
        return self.params is not None

    def compile(self, optimizer="adam"):
        """Attach an optimizer (name, config dict, or Optimizer object —
        resolved through the shared registry)."""
        from . import optimizers as optimizers_mod

        self.optimizer = optimizers_mod.get(optimizer)
        self.loss = "lm_cross_entropy"
        self._tx = self.optimizer.to_optax()
        self._opt_state = None
        self._step_fn = None
        return self

    @property
    def compiled(self) -> bool:
        return self._tx is not None

    def attach_mesh(self, mesh):
        """Point training at a device mesh (dp over ``data_axis``) and
        invalidate every mesh-dependent cache — the one place that
        knows which caches a mesh change touches."""
        self.mesh = mesh
        self._step_fn = None
        return self

    # ---------------------------------------------------------- weights
    def get_weights(self):
        """Flat list of ndarrays (the cross-family weight-exchange
        contract: EarlyStopping(restore_best_weights=True), save_model,
        and the parameter servers all speak it)."""
        if self.params is None:
            raise ValueError("build() before get_weights()")
        return [np.asarray(leaf)
                for leaf in jax.tree_util.tree_leaves(self.params)]

    def set_weights(self, weights):
        if self.params is None:
            raise ValueError("build() before set_weights()")
        leaves, treedef = jax.tree_util.tree_flatten(self.params)
        if len(weights) != len(leaves):
            raise ValueError(f"expected {len(leaves)} arrays, "
                             f"got {len(weights)}")
        self.params = jax.tree_util.tree_unflatten(
            treedef, [jnp.asarray(w) for w in weights])

    # ------------------------------------------------------------- fit
    def fit(self, tokens: np.ndarray, epochs: int = 1,
            batch_size: int = 32, verbose: int = 0, shuffle: bool = True,
            seed: int = 0, callbacks=None,
            validation_split: float = 0.0) -> Dict:
        """Next-token training over ``(N, T)`` token rows. Returns a
        Keras-style history dict; callbacks get real per-epoch hooks
        (checkpoint/early-stop/preemption all work unchanged).
        ``validation_split`` holds out the trailing fraction of rows and
        reports ``val_loss`` per epoch."""
        from .callbacks import CallbackList

        if self._tx is None:
            raise RuntimeError("compile() before fit()")
        if not self.built:
            self.build(seed=seed)
        tokens = np.asarray(tokens)
        val_tokens = None
        if validation_split and 0.0 < validation_split < 1.0:
            split_at = int(len(tokens) * (1.0 - validation_split))
            tokens, val_tokens = tokens[:split_at], tokens[split_at:]
        if self._step_fn is None:
            self._step_fn = make_ssm_train_step(
                self.config, self._tx, mesh=self.mesh,
                data_axis=self.data_axis)
        if self._opt_state is None:
            self._opt_state = self._tx.init(self.params)

        # full batches only: a ragged tail would break the data-axis
        # sharding constraint on a mesh and force a recompile off one
        # (same drop-last semantics as TransformerModel.fit_tokens)
        nb = len(tokens) // batch_size
        if nb < 1:
            raise ValueError(f"need at least one full batch "
                             f"({len(tokens)} rows < batch_size "
                             f"{batch_size})")
        if self.mesh is not None:
            dp = self.mesh.shape.get(self.data_axis, 1)
            if batch_size % dp:
                raise ValueError(
                    f"batch_size {batch_size} must divide over the "
                    f"data-parallel axis ({dp} devices)")

        cbs = CallbackList(callbacks, self)
        self.stop_training = False
        cbs.train_begin()
        history: Dict[str, list] = {"loss": []}
        rng = np.random.default_rng(seed)
        try:
            for epoch in range(int(epochs)):
                cbs.epoch_begin(epoch)
                order = (rng.permutation(len(tokens)) if shuffle
                         else np.arange(len(tokens)))
                losses = []
                for b in range(nb):
                    batch = jnp.asarray(tokens[
                        order[b * batch_size:(b + 1) * batch_size]])
                    self.params, self._opt_state, loss = self._step_fn(
                        self.params, self._opt_state, batch)
                    # keep the device array — float() here would sync
                    # every step; one conversion at epoch end
                    losses.append(loss)
                epoch_loss = float(np.mean([float(l) for l in losses]))
                history["loss"].append(epoch_loss)
                logs = {"loss": epoch_loss}
                if val_tokens is not None:
                    logs["val_loss"] = self.evaluate(val_tokens)
                    history.setdefault("val_loss", []).append(
                        logs["val_loss"])
                if verbose:
                    print(f"Epoch {epoch + 1}/{epochs} - " + " - ".join(
                        f"{k}: {v:.4f}" for k, v in logs.items()))
                cbs.epoch_end(epoch, logs)
                if self.stop_training:
                    break
        finally:
            cbs.train_end()   # flushes async checkpoint writes
        return history

    def evaluate(self, tokens: np.ndarray, y=None,
                 batch_size: Optional[int] = None, **_) -> float:
        """Mean next-token loss over ``(N, T)`` rows, computed in
        ``batch_size`` chunks so eval memory is bounded (``y`` ignored —
        LM targets are the shifted input; cross-family signature)."""
        tokens = np.asarray(tokens)
        bs = int(batch_size or 8)
        if self._jit_loss is None:
            config = self.config
            self._jit_loss = jax.jit(
                lambda p, t: ssm_lm_loss(p, t, config))
        total = n = 0.0
        for start in range(0, len(tokens), bs):
            chunk = tokens[start:start + bs]
            total += float(self._jit_loss(
                self.params, jnp.asarray(chunk))) * len(chunk)
            n += len(chunk)
        return total / n

    def predict(self, tokens: np.ndarray, batch_size: int = 8,
                verbose: int = 0,
                out: Optional[np.ndarray] = None) -> np.ndarray:
        """Logits ``(rows, seq, vocab)`` in input order (the same
        contract as ``TransformerModel.predict``, including ``out=``
        streaming into a preallocated array/memmap)."""
        from .ssm import ssm_forward
        from ._streaming import batched_logits_predict

        config = self.config
        if self._jit_forward is None:
            self._jit_forward = jax.jit(
                lambda p, t: ssm_forward(p, t, config))
        return batched_logits_predict(self._jit_forward, self.params,
                                      tokens, batch_size, out=out)

    # ------------------------------------------------ checkpoint contract
    def training_state(self) -> Dict:
        """Same contract as the other model families', so
        :class:`~elephas_tpu.models.callbacks.ModelCheckpoint` drives
        this model unchanged."""
        from .saving import pack_training_state

        if self.params is None:
            raise ValueError("build() before training_state()")
        return pack_training_state(self.params, self._opt_state)

    def restore_training_state(self, directory: str,
                               step: Optional[int] = None) -> Optional[int]:
        from ..utils.checkpoint import CheckpointManager
        from .saving import unpack_training_state

        if not self.built:
            raise RuntimeError("build() before restore_training_state")
        manager = CheckpointManager(directory)
        params, opt_state = unpack_training_state(manager.restore(step),
                                                  self._tx, self.params)
        self.params = params
        if opt_state is not None:
            self._opt_state = opt_state
        return step if step is not None else manager.latest_step()

    def to_json(self, **kwargs) -> str:
        import json

        from .saving import config_to_dict

        return json.dumps(
            {"class_name": "SSMModel",
             "config": {"ssm_config": config_to_dict(self.config),
                        "name": self.name,
                        "data_axis": self.data_axis}}, **kwargs)

    @classmethod
    def from_config(cls, config: Dict,
                    custom_objects: Optional[Dict] = None) -> "SSMModel":
        from .saving import config_from_dict

        return cls(config_from_dict(config["ssm_config"]),
                   data_axis=config.get("data_axis", "data"),
                   name=config.get("name", "ssm_model"))

    # -------------------------------------------------------- inference
    def generate(self, prompt: np.ndarray, max_new_tokens: int,
                 temperature: float = 0.0, seed: int = 0) -> np.ndarray:
        return np.asarray(ssm_generate(
            self.params, jnp.asarray(prompt), int(max_new_tokens),
            self.config, temperature=temperature,
            key=jax.random.PRNGKey(seed)))

    def engine(self, **engine_kwargs):
        """A :class:`~elephas_tpu.ssm_engine.SSMEngine` over this
        model's parameters."""
        from ..ssm_engine import SSMEngine

        if self.params is None:
            raise RuntimeError("build() or load weights before serving")
        return SSMEngine(self.params, self.config, **engine_kwargs)

    def serve(self, host: str = "127.0.0.1", port: int = 0,
              tokenizer=None, warmup_lengths: Sequence[int] = (),
              **engine_kwargs):
        """Trained model → running HTTP server in one call (the SSM
        mirror of ``TransformerModel.serve``)."""
        from ..serving_http import ServingServer

        eng = self.engine(**engine_kwargs)
        if warmup_lengths:
            eng.warmup(prompt_lengths=warmup_lengths)
        return ServingServer(eng, host=host, port=port,
                             tokenizer=tokenizer).start()
