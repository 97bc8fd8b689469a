"""Virtual-mesh weak-scaling harness for the sync-step trainer.

The BASELINE.md scaling row (sync-SGD efficiency 8->32 chips) needs more
chips than one four-chip host; this harness is the
correctness-plus-trend proxy: fixed PER-DEVICE batch,
device counts swept over a virtual CPU mesh
(``--xla_force_host_platform_device_count``), parallel efficiency =
per-device throughput at N devices / per-device throughput at 1.

On real multi-chip TPU hardware the same harness runs unchanged over the
physical mesh (`jax.devices()`), which is how the row gets filled when
hardware shows up. The epoch runs as ONE jitted program (scan mode), so
the virtual-device numbers measure the program XLA would run on chips,
not per-step dispatch overhead.

Run: ``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8
python benchmarks/weak_scaling.py``
Prints one JSON line: {"rows": [{n, samples_per_sec, per_device, eff}...]}
"""
import json
import time

import numpy as np


def measure(n_devices: int, per_device_batch: int = 64,
            batches_per_epoch: int = 8, epochs: int = 3,
            hidden: int = 256, features: int = 784, classes: int = 10):
    """Samples/sec of the sync-step trainer on an ``n_devices`` data mesh
    with a fixed per-device batch (weak scaling)."""
    import jax
    from jax.sharding import Mesh

    from elephas_tpu.models import SGD, Activation, Dense, Sequential
    from elephas_tpu.parallel.sync_trainer import SyncStepTrainer

    devices = jax.devices()[:n_devices]
    if len(devices) < n_devices:
        raise ValueError(f"need {n_devices} devices, have {len(devices)}")
    mesh = Mesh(np.array(devices), ("data",))

    global_batch = per_device_batch * n_devices
    n = global_batch * batches_per_epoch
    rng = np.random.default_rng(0)
    x = rng.random((n, features), dtype=np.float32)
    y = np.eye(classes, dtype=np.float32)[rng.integers(0, classes, n)]

    model = Sequential([Dense(hidden, input_dim=features),
                        Activation("relu"), Dense(hidden),
                        Activation("relu"), Dense(classes),
                        Activation("softmax")])
    model.compile(SGD(learning_rate=0.1), "categorical_crossentropy",
                  seed=0)
    trainer = SyncStepTrainer(model, model.optimizer,
                              "categorical_crossentropy", mesh=mesh)
    w0 = model.get_weights()
    trainer.fit(w0, x, y, epochs=1, batch_size=global_batch,
                validation_split=0.0, timing=False)  # warmup: compile
    start = time.perf_counter()
    trainer.fit(w0, x, y, epochs=epochs, batch_size=global_batch,
                validation_split=0.0, timing=False)
    elapsed = time.perf_counter() - start
    return n * epochs / elapsed


def sweep(device_counts=(1, 2, 4, 8), **kwargs):
    rows = []
    base_per_device = None
    for n in device_counts:
        sps = measure(n, **kwargs)
        per_device = sps / n
        if base_per_device is None:
            base_per_device = per_device
        rows.append({"n": n, "samples_per_sec": round(sps, 1),
                     "per_device": round(per_device, 1),
                     "eff": round(per_device / base_per_device, 4)})
    return rows


if __name__ == "__main__":
    print(json.dumps({"metric": "weak_scaling_sync_step",
                      "rows": sweep()}))
