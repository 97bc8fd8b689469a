"""Subprocess entry point for the multi-host integration tests.

Each invocation is one JAX-distributed process (CPU backend, 2 local
virtual devices) running the SAME TPUModel.fit program — the
single-controller multi-host recipe. Results are written to
``<outdir>/weights_<pid>.npz`` for the parent test to compare.

Usage: python mh_driver.py <mode> <sync_mode> <pid> <nprocs> <jax_port> \
       <ps_port> <outdir>
"""
import os
import sys


def main():
    mode, sync_mode, pid, nprocs, jax_port, ps_port, outdir = sys.argv[1:8]
    pid, nprocs, jax_port, ps_port = (int(pid), int(nprocs), int(jax_port),
                                      int(ps_port))

    import jax

    # platform and device count go through jax.config BEFORE any backend
    # initialization
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 2 if nprocs > 1 else 4)
    if nprocs > 1:
        # a crash run's survivor learns of its dead peer from the
        # coordination service's heartbeat timeout before it can exit
        # (JAX's shutdown barrier waits for every task): 10 s, not the
        # default 100, keeps that wait short
        kw = ({"heartbeat_timeout_seconds": 10} if mode == "async_crash"
              else {})
        jax.distributed.initialize(
            coordinator_address=f"127.0.0.1:{jax_port}",
            num_processes=nprocs, process_id=pid, **kw)

    import numpy as np

    if mode == "hybrid_mesh":
        # hybrid DCN x ICI mesh: the data axis spans the two processes
        # (gradient-style psum over DCN), the model axis stays local
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from elephas_tpu.parallel.mesh import hybrid_mesh, shard_leading

        mesh = hybrid_mesh((("data", 2 * nprocs), ("model", 1)))
        assert mesh.shape == {"data": 2 * nprocs, "model": 1}, mesh.shape
        # each data-axis row is one device; consecutive pairs must belong
        # to one process (ici inside, dcn across)
        procs = [d.process_index for d in mesh.devices[:, 0]]
        assert procs == sorted(procs), procs
        assert len(set(procs)) == nprocs, procs
        x = np.arange(4 * nprocs, dtype=np.float32).reshape(2 * nprocs, 2)
        xd = shard_leading(mesh, "data", x)
        total = jax.jit(
            lambda a: jnp.sum(a),
            out_shardings=NamedSharding(mesh, P()))(xd)
        np.testing.assert_allclose(np.asarray(total), x.sum())
        np.savez(os.path.join(outdir, f"weights_{pid}.npz"),
                 ok=np.asarray([1.0]))
        print(f"proc {pid}: OK", flush=True)
        return

    from elephas_tpu.models import SGD, Dense, Sequential
    from elephas_tpu.tpu_model import TPUModel

    # deterministic separable 3-class problem, identical on every process
    rng = np.random.default_rng(0)
    x = rng.normal(size=(256, 8)).astype(np.float32)
    w_true = rng.normal(size=(8, 3)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[np.argmax(x @ w_true, axis=1)]

    model = Sequential([Dense(16, input_dim=8, activation="relu"),
                        Dense(3, activation="softmax")])
    model.compile(SGD(learning_rate=0.1), "categorical_crossentropy",
                  metrics=["acc"], seed=0)

    if mode in ("async_crash", "async_resume"):
        # DCN-level fault injection: "async_crash" hard-kills the last
        # process mid-fit (simulated host death / preemption) while the
        # coordinator checkpoints each epoch; "async_resume" restarts
        # fresh processes that restore the latest checkpoint and finish.
        from elephas_tpu.models.callbacks import Callback
        from elephas_tpu.utils.checkpoint import CheckpointManager

        mgr = CheckpointManager(os.path.join(outdir, "ckpt"),
                                max_to_keep=20)

        if mode == "async_crash" and pid == nprocs - 1:
            import elephas_tpu.worker as worker_mod

            real_train = worker_mod.AsyncWorker.train

            def dying_train(self, xt, yt):
                orig_emit = self._emit

                def emit(epoch, loss):
                    orig_emit(epoch, loss)
                    if epoch >= 1:
                        os._exit(43)  # hard death: no cleanup, no barriers
                self._emit = emit
                return real_train(self, xt, yt)

            worker_mod.AsyncWorker.train = dying_train

        restored_step = -1
        if mode == "async_resume":
            latest = mgr.latest_step()
            if latest is not None:
                state = mgr.restore()
                model.set_weights(
                    [state["weights"][str(i)]
                     for i in range(len(state["weights"]))])
                restored_step = latest

        callbacks = []
        if pid == 0:
            class CkptEveryEpoch(Callback):
                def on_epoch_end(cb_self, epoch, logs=None):
                    mgr.save(epoch, {"weights": {
                        str(i): w for i, w in
                        enumerate(cb_self.model.get_weights())}})

            callbacks = [CkptEveryEpoch()]

        tpu_model = TPUModel(model, mode="asynchronous", frequency="epoch",
                             num_workers=2, batch_size=32, port=ps_port,
                             parameter_server_mode="http")
        try:
            tpu_model.fit((x, y), epochs=4, batch_size=32,
                          validation_split=0.0, verbose=0,
                          callbacks=callbacks)
        except Exception as err:  # noqa: BLE001 — the test asserts on this
            print(f"SURVIVOR_ERROR: {type(err).__name__}: {err}",
                  flush=True)
            sys.exit(3)
        weights = tpu_model.master_network.get_weights()
        np.savez(os.path.join(outdir, f"weights_{pid}.npz"),
                 *[np.asarray(w) for w in weights])
        print(f"proc {pid}: OK restored_step={restored_step}", flush=True)
        return

    kwargs = {"sync_mode": sync_mode} if mode == "synchronous" else {}
    tpu_model = TPUModel(model, mode=mode, num_workers=4, batch_size=32,
                         port=ps_port, parameter_server_mode="http", **kwargs)
    tpu_model.fit((x, y), epochs=3, batch_size=32, validation_split=0.0,
                  verbose=0)

    weights = tpu_model.master_network.get_weights()
    np.savez(os.path.join(outdir, f"weights_{pid}.npz"),
             *[np.asarray(w) for w in weights])
    # distributed predict must also work across hosts
    preds = tpu_model.predict(x[:32])
    np.savez(os.path.join(outdir, f"preds_{pid}.npz"), preds=np.asarray(preds))
    print(f"proc {pid}: OK", flush=True)


if __name__ == "__main__":
    main()
