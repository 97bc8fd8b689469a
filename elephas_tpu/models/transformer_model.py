"""TransformerModel: the flagship transformer behind the TPUModel API.

Round-1 left two worlds disjoint: the functional transformer stack
(:mod:`~elephas_tpu.models.transformer` — ``init_params`` /
``make_train_step`` pytrees over a mesh) and the framework's distributed
driver (:class:`~elephas_tpu.tpu_model.TPUModel` with callbacks,
checkpointing and histories, the capability mirror of the reference's
``SparkModel``, ``elephas/spark_model.py:28-308``). This adapter unifies
them: it exposes the BaseModel surface TPUModel and the callback suite
expect (``compile``/``get_weights``/``training_state``/``to_json``/...)
while training runs through the jitted, mesh-sharded
``make_train_step`` — so the flagship LM trains via ``TPUModel.fit`` with
``EarlyStopping``/``ModelCheckpoint`` and resumes bit-exact.
"""
import dataclasses
import json
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from .optimizers import Optimizer
from .optimizers import get as get_optimizer
from .transformer import (TransformerConfig, forward, init_params, lm_loss,
                          make_train_step, select_moe_dispatch, shard_params)
from .transformer import generate as _generate

__all__ = ["TransformerModel"]

#: dataclass fields that hold dtypes (serialized by numpy name)
_DTYPE_FIELDS = ("dtype", "param_dtype")


def _config_to_dict(config: TransformerConfig) -> Dict:
    out = dataclasses.asdict(config)
    for f in _DTYPE_FIELDS:
        out[f] = np.dtype(out[f]).name
    return out


def _config_from_dict(d: Dict) -> TransformerConfig:
    d = dict(d)
    for f in _DTYPE_FIELDS:
        if isinstance(d.get(f), str):
            d[f] = getattr(jnp, d[f])
    return TransformerConfig(**d)


class TransformerModel:
    """Decoder-only transformer LM with the framework's model surface.

    Data convention: "x" is a ``(rows, seq_len)`` int array of token ids;
    there is no separate label column (next-token targets are the shifted
    input, ``transformer.next_token_loss``).

    :param config: :class:`~elephas_tpu.models.transformer.TransformerConfig`
    :param tensor_parallel: Megatron-style model-axis size the training
        mesh uses (1 = pure data parallelism over all visible devices)
    :param zero_optimizer: shard the optimizer state over the data axis
        (ZeRO-1: optimizer memory scales down with the data-parallel
        degree instead of being replicated)
    :param fsdp: fully shard parameters, gradients, AND optimizer state
        over the data axis (ZeRO-3 via
        :func:`~elephas_tpu.models.transformer.fsdp_param_specs`);
        composes with ``tensor_parallel``, supersedes ``zero_optimizer``
    :param sequence_parallel: mesh size of the ``seq`` axis — long-
        context training via ring attention (k/v shards stream around
        the seq ring); sequence length must divide by it
    :param ema_decay: keep an exponential moving average of the
        parameters (updated on-device each optimizer step) — the
        standard serving-quality trick; ``apply_ema()`` swaps it in
    :param mesh: explicit training mesh (e.g. a
        :func:`~elephas_tpu.parallel.hybrid_mesh` spanning hosts) —
        must carry a ``data`` axis and, for tp/sp, ``model``/``seq``
        axes; overrides the tensor_parallel/sequence_parallel-derived
        mesh
    :param grad_accum: accumulate gradients over this many microbatches
        per optimizer step (each fit batch splits into ``grad_accum``
        microbatches; identical numerics, 1/``grad_accum`` the activation
        memory)
    """

    def __init__(self, config: TransformerConfig,
                 tensor_parallel: int = 1, name: Optional[str] = None,
                 zero_optimizer: bool = False, grad_accum: int = 1,
                 fsdp: bool = False, sequence_parallel: int = 1,
                 ema_decay: Optional[float] = None,
                 mesh: Optional[Mesh] = None):
        if fsdp and zero_optimizer:
            raise ValueError("fsdp supersedes zero_optimizer — pick one")
        if ema_decay is not None and not 0.0 < ema_decay < 1.0:
            raise ValueError("ema_decay must be in (0, 1)")
        self.config = config
        self.tensor_parallel = int(tensor_parallel)
        self.sequence_parallel = int(sequence_parallel)
        self.ema_decay = ema_decay
        self.ema_params: Optional[Dict] = None
        if mesh is not None and "data" not in mesh.axis_names:
            raise ValueError("an explicit mesh must carry a 'data' axis")
        self._explicit_mesh = mesh
        self.fsdp = bool(fsdp)
        self.zero_optimizer = bool(zero_optimizer)
        self.grad_accum = max(1, int(grad_accum))
        self.name = name or "transformer_model"
        self.params: Optional[Dict] = None
        self.built = False
        self.stop_training = False
        self.optimizer: Optional[Optimizer] = None
        self.loss: Optional[str] = None
        self.metrics: List = []
        self._tx = None
        self._opt_state = None
        self._seed = 0
        # jitted forward/loss, built once per model (config is static; a
        # fresh jax.jit(lambda) per call would retrace every invocation)
        self._jit_forward = None
        self._jit_loss = None

    # ------------------------------------------------------------ lifecycle
    def build(self, input_shape=None, seed: Optional[int] = None):
        if seed is not None:
            self._seed = seed
        self.params = init_params(self.config,
                                  jax.random.PRNGKey(self._seed))
        self.built = True
        self._opt_state = None
        return self

    def compile(self, optimizer="adam", loss: Optional[str] = None,
                metrics: Optional[Sequence] = None,
                seed: Optional[int] = None, **kwargs):
        """``loss``/``metrics`` exist for API parity; the training loss is
        always next-token cross-entropy (+ the MoE aux term)."""
        self.optimizer = get_optimizer(optimizer)
        self.loss = loss or "lm_cross_entropy"
        self.metrics = list(metrics or [])
        self._tx = self.optimizer.to_optax()
        if self.config.num_experts > 1 and self.config.moe_dispatch == "auto":
            # pin 'auto' to one concrete dispatch now, resolved against
            # the TRAINING mesh: otherwise a tp-sharded fit would train
            # dense (exact) while unsharded predict/evaluate routed
            # (capacity drops) — silent train/serve numeric skew
            mesh = self._training_mesh()
            self.config = dataclasses.replace(
                self.config,
                moe_dispatch=select_moe_dispatch(
                    self.config, mesh, "model" if mesh is not None else None))
        self._jit_forward = None  # config may have changed: rebuild lazily
        self._jit_loss = None
        if not self.built:
            self.build(seed=seed)
        elif seed is not None and seed != self._seed:
            self.build(seed=seed)
        self._opt_state = None
        return self

    @property
    def compiled(self) -> bool:
        return self._tx is not None

    # -------------------------------------------------------------- weights
    def get_weights(self) -> List[np.ndarray]:
        """Flat leaf list in jax pytree order (sorted dict keys — stable
        across instances of the same config)."""
        if self.params is None:
            raise ValueError("Model must be built before get_weights()")
        return [np.asarray(leaf)
                for leaf in jax.tree_util.tree_leaves(self.params)]

    def set_weights(self, weights: Sequence[np.ndarray]):
        if self.params is None:
            raise ValueError("Model must be built before set_weights()")
        leaves, treedef = jax.tree_util.tree_flatten(self.params)
        if len(leaves) != len(weights):
            raise ValueError(
                f"Expected {len(leaves)} weight arrays, got {len(weights)}")
        new_leaves = []
        for ref, w in zip(leaves, weights):
            w = jnp.asarray(w, dtype=ref.dtype)
            if w.shape != ref.shape:
                raise ValueError(
                    f"Shape mismatch: {w.shape} vs {ref.shape}")
            new_leaves.append(w)
        self.params = jax.tree_util.tree_unflatten(treedef, new_leaves)

    # ------------------------------------------------------- checkpoint api
    def training_state(self) -> Dict:
        """Same contract as ``BaseModel.training_state`` so
        :class:`~elephas_tpu.models.callbacks.ModelCheckpoint` drives this
        model unchanged."""
        from .saving import pack_training_state

        if self.params is None:
            raise ValueError("Model must be built before training_state()")
        return pack_training_state(self.params, self._opt_state)

    def restore_training_state(self, directory: str,
                               step: Optional[int] = None) -> Optional[int]:
        """Restore params + optimizer moments saved by ModelCheckpoint;
        bit-exact resume (no layer renaming needed — the param pytree keys
        are positional and stable)."""
        from ..utils.checkpoint import CheckpointManager
        from .saving import unpack_training_state

        if not self.built:
            raise RuntimeError("build()/compile() before "
                               "restore_training_state")
        manager = CheckpointManager(directory)
        params, opt_state = unpack_training_state(manager.restore(step),
                                                  self._tx, self.params)
        self.params = params
        if opt_state is not None:
            self._opt_state = opt_state
        return step if step is not None else manager.latest_step()

    # -------------------------------------------------------- serialization
    def get_config(self) -> Dict:
        return {"name": self.name,
                "tensor_parallel": self.tensor_parallel,
                "sequence_parallel": self.sequence_parallel,
                "zero_optimizer": self.zero_optimizer,
                "grad_accum": self.grad_accum,
                "fsdp": self.fsdp,
                "ema_decay": self.ema_decay,
                "transformer_config": _config_to_dict(self.config)}

    def to_json(self, **kwargs) -> str:
        return json.dumps({"class_name": "TransformerModel",
                           "config": self.get_config()}, **kwargs)

    @classmethod
    def from_config(cls, config: Dict,
                    custom_objects: Optional[Dict] = None
                    ) -> "TransformerModel":
        return cls(_config_from_dict(config["transformer_config"]),
                   tensor_parallel=config.get("tensor_parallel", 1),
                   name=config.get("name"),
                   zero_optimizer=config.get("zero_optimizer", False),
                   grad_accum=config.get("grad_accum", 1),
                   fsdp=config.get("fsdp", False),
                   sequence_parallel=config.get("sequence_parallel", 1),
                   ema_decay=config.get("ema_decay"))

    # ------------------------------------------------------------- training
    def _training_mesh(self) -> Optional[Mesh]:
        """dp×tp(×sp) mesh over the visible devices (None on one chip)."""
        if self._explicit_mesh is not None:
            return self._explicit_mesh
        devices = jax.devices()
        tp, sp = self.tensor_parallel, self.sequence_parallel
        if len(devices) == 1 and tp == 1 and sp == 1:
            return None
        if len(devices) % (tp * sp):
            raise ValueError(
                f"tensor_parallel={tp} x sequence_parallel={sp} does not "
                f"divide the {len(devices)}-device mesh")
        dp = len(devices) // (tp * sp)
        if sp > 1:
            return Mesh(np.array(devices).reshape(dp, tp, sp),
                        ("data", "model", "seq"))
        return Mesh(np.array(devices).reshape(dp, tp), ("data", "model"))

    def fit_tokens(self, tokens: np.ndarray, epochs: int = 1,
                   batch_size: int = 32, validation_split: float = 0.0,
                   seed: int = 0, verbose: int = 0,
                   epoch_callback: Optional[Callable] = None) -> Dict:
        """Mesh-sharded LM training; the engine behind ``TPUModel.fit``.

        ``epoch_callback(epoch_idx, logs) -> stop?`` fires after each
        epoch with ``{'loss': ..., 'val_loss': ...}`` logs (val only with
        a validation split), mirroring ``SyncStepTrainer.fit`` so
        TPUModel's callback plumbing drives both trainers identically.
        Returns a Keras-style history dict.
        """
        if not self.compiled:
            raise RuntimeError("compile() the model before fit")
        tokens = np.asarray(tokens)
        if tokens.ndim != 2:
            raise ValueError(f"tokens must be (rows, seq), got {tokens.shape}")

        mesh = self._training_mesh()
        dp = (dict(zip(mesh.axis_names, mesh.devices.shape))["data"]
              if mesh is not None else 1)
        if batch_size % dp:
            raise ValueError(
                f"batch_size={batch_size} must divide over the data-"
                f"parallel axis ({dp} devices)")
        n_val = int(round(tokens.shape[0] * validation_split))
        # the val batch shards over the data axis too: trim to a dp
        # multiple (a sub-dp remainder can't be laid out on the mesh)
        n_val -= n_val % dp
        if n_val:
            tokens, val_tokens = tokens[:-n_val], tokens[-n_val:]

        from ..parallel.mesh import shard_leading

        params = self.params
        if mesh is not None:
            params = shard_params(
                params, self.config, mesh,
                fsdp_axis="data" if self.fsdp else None)
        if batch_size % self.grad_accum:
            raise ValueError(
                f"batch_size={batch_size} does not split into "
                f"{self.grad_accum} gradient-accumulation microbatches")
        sp = self.sequence_parallel
        if mesh is not None and "seq" in mesh.axis_names:
            sp = max(sp, dict(zip(mesh.axis_names,
                                  mesh.devices.shape))["seq"])
        step = make_train_step(self.config, self._tx, mesh=mesh,
                               seq_axis="seq" if sp > 1 else None,
                               zero_optimizer=self.zero_optimizer,
                               accum_steps=self.grad_accum,
                               fsdp=self.fsdp and mesh is not None)
        opt_state = (self._opt_state if self._opt_state is not None
                     else jax.jit(self._tx.init)(params))

        eval_loss = jax.jit(
            lambda p, t: lm_loss(p, t, self.config,
                                 mesh=mesh,
                                 seq_axis=("seq" if mesh is not None
                                           and sp > 1 else None),
                                 batch_axis="data" if mesh else None,
                                 model_axis="model" if mesh else None))

        from ..utils.tracing import StepTimer

        ema_update = None
        if self.ema_decay is not None:
            decay = float(self.ema_decay)
            ema_update = jax.jit(lambda e, p: jax.tree_util.tree_map(
                lambda a, b: decay * a + (1.0 - decay) * b, e, p))
            if self.ema_params is None:
                # a REAL copy: the train step donates its param buffers,
                # so aliasing them here would read deleted memory
                self.ema_params = jax.tree_util.tree_map(jnp.copy, params)

        rng = np.random.default_rng(seed)
        use_dropout = self.config.dropout_rate > 0
        dropout_base = jax.random.PRNGKey(seed)
        n = tokens.shape[0]
        nb = n // batch_size
        if nb == 0:
            raise ValueError(
                f"fewer token rows ({n}) than batch_size ({batch_size})")
        history: Dict[str, List[float]] = {"loss": []}
        if n_val:
            history["val_loss"] = []
        history["epoch_time"] = []
        self.timer = timer = StepTimer()

        for epoch in range(epochs):
            timer.start()
            order = rng.permutation(n)
            shuffled = tokens[order]
            losses = []
            for i in range(nb):
                xb = shuffled[i * batch_size:(i + 1) * batch_size]
                if mesh is not None and sp > 1:
                    from jax.sharding import NamedSharding
                    from jax.sharding import PartitionSpec as _P

                    xb = jax.device_put(
                        jnp.asarray(xb),
                        NamedSharding(mesh, _P("data", "seq")))
                elif mesh is not None:
                    # shard_leading routes through global-array assembly
                    # on process-spanning meshes (multi-host DCN), plain
                    # device_put otherwise
                    xb = shard_leading(mesh, "data", xb)
                else:
                    xb = jnp.asarray(xb)
                if use_dropout:
                    params, opt_state, loss = step(
                        params, opt_state, xb,
                        jax.random.fold_in(dropout_base, epoch * nb + i))
                else:
                    params, opt_state, loss = step(params, opt_state, xb)
                losses.append(loss)
                if ema_update is not None:
                    self.ema_params = ema_update(self.ema_params, params)
            # the float() fetches block on the epoch's dispatched steps,
            # so the recorded wall time is real (tracing requirement)
            logs = {"loss": float(np.mean([float(l) for l in losses]))}
            timer.stop()
            history["epoch_time"].append(timer.durations[-1])
            if n_val:
                if mesh is not None and sp > 1:
                    from jax.sharding import NamedSharding
                    from jax.sharding import PartitionSpec as _P

                    vb = jax.device_put(
                        jnp.asarray(val_tokens),
                        NamedSharding(mesh, _P("data", "seq")))
                elif mesh is not None:
                    vb = shard_leading(mesh, "data", val_tokens)
                else:
                    vb = jnp.asarray(val_tokens)
                logs["val_loss"] = float(eval_loss(params, vb))
            for k, v in logs.items():
                history[k].append(v)
            if verbose:
                print(f"epoch {epoch + 1}/{epochs} - " +
                      " - ".join(f"{k}: {v:.4f}" for k, v in logs.items()))
            # sync resumable state so callbacks observe current weights
            # and checkpoints carry the optimizer moments
            self.params = params
            self._opt_state = opt_state
            if epoch_callback is not None and epoch_callback(epoch, logs):
                break

        self.params = params
        self._opt_state = opt_state
        return history

    # fit() keeps the (x, y) surface of BaseModel: y is ignored (LM
    # targets are the shifted input)
    def fit(self, x, y=None, epochs: int = 1, batch_size: int = 32,
            verbose: int = 0, validation_split: float = 0.0,
            callbacks=None, seed: int = 0, **kwargs) -> Dict:
        from .callbacks import CallbackList

        cbs = CallbackList(callbacks, self)
        self.stop_training = False
        cbs.train_begin()

        def epoch_cb(epoch, logs):
            cbs.epoch_end(epoch, logs)
            return bool(self.stop_training)

        # finally: async ModelCheckpoint flushes background writes in
        # train_end — it must run even when training raises
        try:
            history = self.fit_tokens(
                x, epochs=epochs, batch_size=batch_size,
                validation_split=validation_split, seed=seed, verbose=verbose,
                epoch_callback=epoch_cb if cbs else None)
        finally:
            cbs.train_end()
        return history

    def apply_ema(self):
        """Swap the EMA average in as the live parameters (returns the
        raw training params so callers can swap back)."""
        if self.ema_params is None:
            raise RuntimeError("no EMA state — set ema_decay and fit first")
        raw = self.params
        self.params = jax.tree_util.tree_map(jnp.asarray, self.ema_params)
        return raw

    def save(self, filepath: str, overwrite: bool = True,
             include_optimizer: bool = True):
        from .saving import save_model

        save_model(self, filepath, overwrite, include_optimizer)

    # ------------------------------------------------------ inference/eval
    def predict(self, tokens: np.ndarray, batch_size: int = 8,
                verbose: int = 0,
                out: Optional[np.ndarray] = None) -> np.ndarray:
        """Logits ``(rows, seq, vocab)`` in input order.

        ``out``: optional preallocated ``(rows, seq, vocab)`` array
        (e.g. a writable memmap) receiving each batch's logits in
        place — with a file-backed token column neither the inputs nor
        the (rows×seq×vocab, typically huge) outputs ever fully
        materialize in memory."""
        from ._streaming import batched_logits_predict

        if self._jit_forward is None:
            config = self.config
            self._jit_forward = jax.jit(
                lambda p, t: forward(p, t, config))
        return batched_logits_predict(self._jit_forward, self.params,
                                      tokens, batch_size, out=out)

    def generate(self, prompt: np.ndarray, max_new_tokens: int,
                 temperature: float = 0.0, seed: int = 0,
                 top_k: Optional[int] = None,
                 top_p: Optional[float] = None,
                 prompt_lengths=None) -> np.ndarray:
        """Autoregressive continuation of ``(batch, prompt_len)`` token
        ids via the KV-cache decode loop (one lax.scan, compiled once per
        shape): ``temperature=0`` greedy, otherwise categorical sampling,
        optionally top-k and/or nucleus (top-p) filtered."""
        key = jax.random.PRNGKey(seed)
        return np.asarray(_generate(self.params, np.asarray(prompt),
                                    int(max_new_tokens), self.config,
                                    temperature=temperature, key=key,
                                    top_k=top_k, top_p=top_p,
                                    prompt_lengths=prompt_lengths))

    def engine(self, draft: Optional["TransformerModel"] = None,
               **engine_kwargs):
        """A :class:`~elephas_tpu.serving_engine.DecodeEngine` over this
        model's parameters (continuous batching, prefix caching,
        paged KV — see the serving guide). Pass
        ``draft=`` for speculative stepping."""
        from ..serving_engine import DecodeEngine

        if self.params is None:
            raise RuntimeError("build() or load weights before serving")
        if draft is not None:
            if draft.params is None:
                raise RuntimeError("the draft model needs build() or "
                                   "loaded weights before serving")
            engine_kwargs.setdefault("draft_params", draft.params)
            engine_kwargs.setdefault("draft_config", draft.config)
        return DecodeEngine(self.params, self.config, **engine_kwargs)

    def serve(self, host: str = "127.0.0.1", port: int = 0,
              tokenizer=None, draft: Optional["TransformerModel"] = None,
              warmup_lengths=(), **engine_kwargs):
        """One call from a trained model to a RUNNING HTTP server:
        builds the engine, optionally warms the given prompt lengths,
        and starts a :class:`~elephas_tpu.serving_http.ServingServer`
        (returned started; ``.port`` has the bound port, ``.stop()``
        shuts down)."""
        from ..serving_http import ServingServer

        eng = self.engine(draft=draft, **engine_kwargs)
        if warmup_lengths:
            eng.warmup(prompt_lengths=warmup_lengths)
        return ServingServer(eng, host=host, port=port,
                             tokenizer=tokenizer).start()

    def speculative_generate(self, draft: "TransformerModel",
                             prompt: np.ndarray, max_new_tokens: int,
                             gamma: int = 4, temperature: float = 0.0,
                             seed: int = 0, return_stats: bool = False):
        """Draft-and-verify decoding: ``draft`` (a smaller
        TransformerModel sharing this model's vocabulary) proposes
        ``gamma`` tokens per round and this model verifies them in one
        cached block forward. Greedy output is token-identical to
        :meth:`generate`; the speedup is ``1 + gamma * acceptance``
        emitted tokens per target weight read."""
        from .speculative import speculative_generate as _spec

        out = _spec(self.params, draft.params, np.asarray(prompt),
                    int(max_new_tokens), self.config, draft.config,
                    gamma=gamma, temperature=temperature,
                    key=jax.random.PRNGKey(seed),
                    return_stats=return_stats)
        if return_stats:
            return np.asarray(out[0]), out[1]
        return np.asarray(out)

    def beam_search(self, prompt: np.ndarray, max_new_tokens: int,
                    num_beams: int = 4, length_penalty: float = 0.0,
                    eos_id: Optional[int] = None):
        """Beam-search continuations ``(batch, num_beams, max_new_tokens)``
        with per-beam scores, best first."""
        from .transformer import beam_search as _beam_search

        seqs, scores = _beam_search(self.params, np.asarray(prompt),
                                    int(max_new_tokens), self.config,
                                    num_beams=num_beams,
                                    length_penalty=length_penalty,
                                    eos_id=eos_id)
        return np.asarray(seqs), np.asarray(scores)

    def evaluate(self, tokens: np.ndarray, y=None, batch_size: int = 8,
                 verbose: int = 0) -> float:
        """Mean next-token loss over the rows (batch-weighted)."""
        tokens = np.asarray(tokens)
        if self._jit_loss is None:
            config = self.config
            self._jit_loss = jax.jit(lambda p, t: lm_loss(p, t, config))
        total, count = 0.0, 0
        for i in range(0, tokens.shape[0], batch_size):
            chunk = tokens[i:i + batch_size]
            total += float(self._jit_loss(
                self.params, jnp.asarray(chunk))) * len(chunk)
            count += len(chunk)
        return total / max(count, 1)
