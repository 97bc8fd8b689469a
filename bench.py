"""Benchmark: framework training throughput on a TPU.

Workloads, run in this one process, in this order:

1. **MNIST-MLP sync-step** (the reference's canonical config,
   ``examples/mnist_mlp_spark_synchronous.py``): samples/sec of
   ``TPUModel(sync_mode='step')`` vs a hand-rolled pure-JAX loop of the
   same model — the ">=90% of single-process JAX throughput" bar from
   BASELINE.md. This is the headline metric/vs_baseline.
2. **Transformer LM** (the flagship model): tokens/sec and **MFU**
   (model FLOPs / chip peak FLOPs) of a jitted train step, measured for
   the Pallas flash-attention path AND the XLA attention path so the
   kernel's win is a number, not a claim; plus the chunked-vocab-loss
   A/B and a batch-32 probe.

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": "samples/sec", "vs_baseline": R,
     "backend": "tpu", "device": KIND, "device_count": C,
     "transformer": {"tokens_per_sec": T, "mfu": M,
                     "xla_tokens_per_sec": Tx, "flash_speedup": S, ...}}
where vs_baseline = framework_throughput / pure_jax_throughput.

A number here is a chip number or nothing: without a TPU the script
exits non-zero before measuring anything, a device kind missing from the
peaks table raises, and a row that raises ends the run. One process
holds the chip; no child is started.

``python bench.py --row NAME [args]`` runs one row alone.
"""
import json
import sys
import time

import numpy as np

#: advertised peak dense-matmul TFLOP/s per JAX device (bf16), by device
#: kind prefix — the MFU denominator (Google Cloud TPU documentation,
#: per-generation system-architecture pages). v2/v3 expose one device per
#: CORE (half a chip); v4+ expose one megacore device per chip, so those
#: entries are full-chip peaks (v4 275, v5p 459, v5e 197, v6e 918).
_PEAK_TFLOPS = {
    "TPU v2": 22.5, "TPU v3": 61.0, "TPU v4": 275.0, "TPU v5 lite": 197.0,
    "TPU v5e": 197.0, "TPU v5p": 459.0, "TPU v5": 459.0, "TPU v6": 918.0,
}


def _chip_peak_tflops(device) -> float:
    kind = getattr(device, "device_kind", "")
    for prefix in sorted(_PEAK_TFLOPS, key=len, reverse=True):
        if kind.startswith(prefix):
            return _PEAK_TFLOPS[prefix]
    raise ValueError(f"no peak FLOP/s on record for device kind {kind!r}; "
                     "add it to _PEAK_TFLOPS with its source")


def _require_tpu():
    """Start the backend (with the shared compile cache configured) and
    refuse to measure on anything but a TPU."""
    import jax

    from elephas_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise SystemExit(f"bench.py measures on a TPU; JAX found "
                         f"platform {platform!r} — nothing measured")


def _data(n=8192, dim=784, classes=10, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.random((n, dim), dtype=np.float32)
    y = np.eye(classes, dtype=np.float32)[rng.integers(0, classes, n)]
    return x, y


def bench_framework(x, y, batch_size, epochs=3):
    from elephas_tpu.models import SGD, Activation, Dense, Sequential
    from elephas_tpu.tpu_model import TPUModel
    from elephas_tpu.utils.dataset_utils import to_dataset

    model = Sequential([Dense(128, input_dim=784), Activation("relu"),
                        Dense(128), Activation("relu"),
                        Dense(10), Activation("softmax")])
    model.compile(SGD(learning_rate=0.1), "categorical_crossentropy", seed=0)
    tpu_model = TPUModel(model, mode="synchronous", sync_mode="step",
                         batch_size=batch_size)
    dataset = to_dataset(x, y)
    # warmup: compile
    tpu_model.fit(dataset, epochs=1, batch_size=batch_size, verbose=0,
                  validation_split=0.0)
    start = time.perf_counter()
    tpu_model.fit(dataset, epochs=epochs, batch_size=batch_size, verbose=0,
                  validation_split=0.0)
    elapsed = time.perf_counter() - start
    return (x.shape[0] * epochs) / elapsed


def bench_pure_jax(x, y, batch_size, epochs=3):
    """Hand-rolled minimal JAX training loop — the baseline."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(key, 3)

    def glorot(k, shape):
        limit = np.sqrt(6.0 / (shape[0] + shape[1]))
        return jax.random.uniform(k, shape, jnp.float32, -limit, limit)

    params = {
        "w1": glorot(k1, (784, 128)), "b1": jnp.zeros(128),
        "w2": glorot(k2, (128, 128)), "b2": jnp.zeros(128),
        "w3": glorot(k3, (128, 10)), "b3": jnp.zeros(10),
    }

    def loss_fn(p, xb, yb):
        h = jax.nn.relu(xb @ p["w1"] + p["b1"])
        h = jax.nn.relu(h @ p["w2"] + p["b2"])
        logits = h @ p["w3"] + p["b3"]
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.sum(yb * logp, axis=-1))

    lr = 0.1

    @jax.jit
    def step(p, xb, yb):
        grads = jax.grad(loss_fn)(p, xb, yb)
        return jax.tree_util.tree_map(lambda w, g: w - lr * g, p, grads)

    n = x.shape[0]
    nb = n // batch_size
    rng = np.random.default_rng(0)

    def run_epochs(p, count):
        # same workload as the framework: shuffled mini-batch SGD per epoch
        for _ in range(count):
            order = rng.permutation(n)
            xs, ys = x[order], y[order]
            for i in range(nb):
                xb = xs[i * batch_size:(i + 1) * batch_size]
                yb = ys[i * batch_size:(i + 1) * batch_size]
                p = step(p, xb, yb)
        # hard completion barrier: fetch a scalar from the last step
        float(p["b3"][0])
        return p

    params = run_epochs(params, 1)  # warmup/compile
    start = time.perf_counter()
    params = run_epochs(params, epochs)
    elapsed = time.perf_counter() - start
    return (nb * batch_size * epochs) / elapsed


def bench_transformer(attention_impl: str, steps: int = 20,
                      loss_vocab_chunk=None, batch: int = 8):
    """Tokens/sec + MFU of a jitted transformer LM train step on the
    current chip, for the given attention implementation (optionally with
    the chunked-vocab streamed loss)."""
    import jax
    import optax

    from elephas_tpu.models.transformer import (TransformerConfig,
                                                init_params, make_train_step)

    config = TransformerConfig(vocab_size=32000, num_layers=8, num_heads=16,
                               d_model=1024, d_ff=4096, max_seq_len=1024,
                               attention_impl=attention_impl,
                               loss_vocab_chunk=loss_vocab_chunk)
    seq = 1024
    params = init_params(config, jax.random.PRNGKey(0))
    tx = optax.adamw(3e-4)
    opt_state = tx.init(params)
    step = make_train_step(config, tx)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0,
                                config.vocab_size)

    # float() forces a host fetch of the scalar — a hard completion barrier
    params, opt_state, loss = step(params, opt_state, tokens)  # compile
    float(loss)
    start = time.perf_counter()
    for _ in range(steps):
        params, opt_state, loss = step(params, opt_state, tokens)
    float(loss)  # all steps chain through donated buffers
    elapsed = time.perf_counter() - start

    tokens_per_step = batch * seq
    tokens_per_sec = tokens_per_step * steps / elapsed

    # Model FLOPs per step (PaLM-appendix accounting): matmul fwd cost is
    # 2*P FLOPs/token for the P non-embedding-lookup params the token
    # touches, plus causal attention scores/values
    # (2 matmuls * 2 FLOPs * seq/2 avg causal length * d_model); backward
    # is 2x forward. Embedding gather and softmax are excluded (not MXU
    # work) — standard MFU convention, slightly conservative.
    c = config
    p_matmul = (c.num_layers * (4 * c.d_model * c.d_model
                                + 2 * c.d_model * c.d_ff)
                + c.d_model * c.vocab_size)  # tied LM head projection
    attn_flops = 2 * 2 * (seq / 2) * c.d_model  # per token per layer
    flops_per_token = 3 * (2 * p_matmul + c.num_layers * attn_flops)
    mfu = (flops_per_token * tokens_per_sec
           / (_chip_peak_tflops(jax.devices()[0]) * 1e12))
    return tokens_per_sec, mfu


# ---------------------------------------------------------------------------
# Rows — each returns one result dict.
# ---------------------------------------------------------------------------

def _env_fields():
    import jax
    dev = jax.devices()[0]
    return {"backend": dev.platform, "device": dev.device_kind,
            "device_count": len(jax.devices())}


def row_mnist():
    batch_size = 64
    x, y = _data()
    framework = bench_framework(x, y, batch_size)
    pure = bench_pure_jax(x, y, batch_size)
    return {"metric": "mnist_mlp_sync_samples_per_sec",
            "value": round(framework, 1), "unit": "samples/sec",
            "vs_baseline": round(framework / pure, 4), **_env_fields()}


def row_tx(attn: str, chunk=None, batch: int = 8, steps: int = 20):
    tps, mfu = bench_transformer(attn, steps=steps, loss_vocab_chunk=chunk,
                                 batch=batch)
    return {"metric": "transformer_tokens_per_sec", "value": round(tps, 1),
            "unit": "tokens/sec", "mfu": round(mfu, 4), "attention": attn,
            "loss_vocab_chunk": chunk, "batch": batch, **_env_fields()}


def run_row(argv):
    if not argv:
        raise SystemExit("usage: bench.py --row "
                         "{mnist|tx_xla|tx_flash|tx_chunked ATTN"
                         "|tx_b32 ATTN CHUNK}")
    name = argv[0]
    if name == "mnist":
        return row_mnist()
    if name == "tx_xla":
        return row_tx("xla")
    if name == "tx_flash":
        return row_tx("flash")
    if name == "tx_chunked":
        if len(argv) < 2:
            raise SystemExit("usage: bench.py --row tx_chunked {flash|xla}")
        return row_tx(argv[1], chunk=8192)
    if name == "tx_b32":
        if len(argv) < 3:
            raise SystemExit(
                "usage: bench.py --row tx_b32 {flash|xla} {8192|none}")
        chunk = int(argv[2]) if argv[2] != "none" else None
        return row_tx(argv[1], chunk=chunk, batch=32, steps=10)
    raise SystemExit(f"unknown row {name!r}")


def _registry_metrics():
    """The process registry snapshot: the training-step histogram
    (StepTimer publishes into it) rides along with the scalars, so the
    record carries latency DISTRIBUTIONS, not just throughput."""
    from elephas_tpu.obs import default_registry

    return {name: fam for name, fam in default_registry()
            .snapshot().items()
            if any(s.get("count") or s.get("value")
                   for s in fam["series"])}


def main():
    """Every row, in order, in this process; the first failure ends the
    run with its traceback and no result line."""
    result = row_mnist()
    xla = row_tx("xla")
    flash = row_tx("flash")
    best_attn = "flash" if flash["value"] >= xla["value"] else "xla"
    best = flash if best_attn == "flash" else xla
    chunked = row_tx(best_attn, chunk=8192)
    chunk_won = chunked["value"] > best["value"]
    b32 = row_tx(best_attn, chunk=8192 if chunk_won else None, batch=32,
                 steps=10)
    config = "L8 d1024 ff4096 h16 seq1024 batch8 bf16 adamw"
    if chunk_won:
        best = chunked
        config += f" {best_attn}-attention chunked-vocab-loss"
    result["transformer"] = {
        "tokens_per_sec": best["value"], "mfu": best["mfu"],
        "config": config,
        "xla_tokens_per_sec": xla["value"],
        "flash_tokens_per_sec": flash["value"],
        "flash_speedup": round(flash["value"] / xla["value"], 4),
        "chunked_loss_tokens_per_sec": chunked["value"],
        "chunked_loss_attention": best_attn,
        "b32_tokens_per_sec": b32["value"], "b32_mfu": b32["mfu"]}
    return result


if __name__ == "__main__":
    _require_tpu()
    if "--row" in sys.argv[1:]:
        out = run_row(sys.argv[sys.argv.index("--row") + 1:])
    else:
        out = main()
    metrics = _registry_metrics()
    if metrics:
        out["metrics"] = metrics
    out["measured_at"] = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    print(json.dumps(out))
