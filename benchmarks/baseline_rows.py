"""Measure the BASELINE.md rows beyond bench.py's two headline configs.

Row: Otto-style tabular pipeline (parity with the reference's
``examples/ml_pipeline_otto.py`` Spark pipeline) — Estimator.fit
throughput through the full ML-pipeline stack (DataFrame adapter ->
TPUModel -> sync trainer) plus transform accuracy.

Row: ResNet-50 on CIFAR-10 shapes, synchronous per-step SGD — the conv
workload BASELINE.md names twice. Uses the full TPUModel sync-step path
(whole epoch jitted, donated buffers).

Prints one JSON line per row. Run on the real chip:
    python benchmarks/baseline_rows.py [otto|resnet50]
"""
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "examples"))


def measure_otto(epochs=8):
    from common import otto_like

    from elephas_tpu.ml import Estimator, to_data_frame
    from elephas_tpu.models import (Activation, Adam, Dense, Dropout,
                                    Sequential, serialize_optimizer)

    x, labels = otto_like(n=8192)
    classes, indexed = np.unique(labels, return_inverse=True)
    nb_classes = len(classes)
    mean, std = x.mean(axis=0), x.std(axis=0) + 1e-8
    x = (x - mean) / std
    split = int(0.8 * len(x))
    train_df = to_data_frame(x[:split], indexed[:split].astype(float),
                             categorical=False)
    test_df = to_data_frame(x[split:], indexed[split:].astype(float),
                            categorical=False)

    def make_estimator(n_epochs):
        model = Sequential([Dense(256, input_dim=x.shape[1]),
                            Activation("relu"), Dropout(0.3),
                            Dense(256), Activation("relu"), Dropout(0.3),
                            Dense(nb_classes), Activation("softmax")])
        model.build()
        return Estimator(
            model_config=model.to_json(),
            optimizer_config=serialize_optimizer(Adam(learning_rate=1e-3)),
            loss="categorical_crossentropy", metrics=["acc"],
            mode="synchronous", categorical=True, nb_classes=nb_classes,
            epochs=n_epochs, batch_size=128, validation_split=0.1,
            num_workers=4, verbose=0, seed=0)

    make_estimator(1).fit(train_df)  # warmup: compile
    est = make_estimator(epochs)
    start = time.perf_counter()
    fitted = est.fit(train_df)
    elapsed = time.perf_counter() - start
    result = fitted.transform(test_df)
    acc = float(np.mean([int(np.argmax(p)) == int(label) for p, label
                         in zip(result["prediction"], result["label"])]))
    return {"metric": "otto_pipeline_sync_samples_per_sec",
            "value": round(split * epochs / elapsed, 1),
            "unit": "samples/sec", "epochs": epochs, "n_train": split,
            "test_accuracy": round(acc, 4),
            "config": "93->256->256->9 MLP, adam, batch 128, sync average, "
                      "4 workers, full ML-pipeline stack"}


def measure_resnet50(epochs=2, n=4096, batch_size=128):
    from elephas_tpu.models import SGD
    from elephas_tpu.models.resnet import build_resnet50
    from elephas_tpu.tpu_model import TPUModel
    from elephas_tpu.utils.dataset_utils import to_dataset

    rng = np.random.default_rng(0)
    x = rng.normal(0.0, 1.0, (n, 32, 32, 3)).astype("float32")
    y = np.eye(10, dtype="float32")[rng.integers(0, 10, n)]

    model = build_resnet50(input_shape=(32, 32, 3), num_classes=10)
    model.compile(SGD(learning_rate=0.05, momentum=0.9),
                  "categorical_crossentropy", seed=0)
    tpu_model = TPUModel(model, mode="synchronous", sync_mode="step",
                         batch_size=batch_size)
    dataset = to_dataset(x, y)
    tpu_model.fit(dataset, epochs=1, batch_size=batch_size, verbose=0,
                  validation_split=0.0)  # warmup: compile
    start = time.perf_counter()
    tpu_model.fit(dataset, epochs=epochs, batch_size=batch_size, verbose=0,
                  validation_split=0.0)
    elapsed = time.perf_counter() - start
    return {"metric": "resnet50_cifar_sync_step_samples_per_sec",
            "value": round(n * epochs / elapsed, 1),
            "unit": "samples/sec", "epochs": epochs, "n": n,
            "batch_size": batch_size,
            "config": "ResNet-50 bottleneck (He et al.), 32x32x3 inputs, "
                      "10 classes, SGD+momentum, sync-step (whole epoch "
                      "jitted)"}


def measure_async(epochs=3, n=8192, batch_size=64):
    """Asynchronous-mode row: plain reference-parity loop vs the
    overlapped device-resident schedule, socket PS, batch frequency,
    2 workers."""
    import random

    from elephas_tpu.models import SGD, Activation, Dense, Sequential
    from elephas_tpu.tpu_model import TPUModel
    from elephas_tpu.utils.dataset_utils import to_dataset

    rng = np.random.default_rng(0)
    x = rng.random((n, 784), dtype=np.float32)
    y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, n)]
    dataset = to_dataset(x, y)

    def run(**extra):
        model = Sequential([Dense(128, input_dim=784), Activation("relu"),
                            Dense(128), Activation("relu"),
                            Dense(10), Activation("softmax")])
        model.compile(SGD(learning_rate=0.1), "categorical_crossentropy",
                      seed=0)
        tpu_model = TPUModel(model, mode="asynchronous",
                             parameter_server_mode="socket",
                             frequency="batch", num_workers=2,
                             port=random.randint(42000, 60000), **extra)
        tpu_model.fit(dataset, epochs=1, batch_size=batch_size, verbose=0,
                      validation_split=0.0)  # warmup: compile
        start = time.perf_counter()
        tpu_model.fit(dataset, epochs=epochs, batch_size=batch_size,
                      verbose=0, validation_split=0.0)
        return n * epochs / (time.perf_counter() - start)

    plain = run()
    overlapped = run(async_overlap=True, async_accum=8)
    return {"metric": "mnist_mlp_async_samples_per_sec",
            "value": round(overlapped, 1), "unit": "samples/sec",
            "plain_loop": round(plain, 1),
            "overlap_speedup": round(overlapped / plain, 2),
            "config": "async socket PS, batch frequency, 2 workers; "
                      "value = overlapped schedule (async_accum=8), "
                      "plain_loop = reference-parity 2-RPCs-per-batch"}


def measure_ps_plane(payload_mb=16.0, shards=4, rounds=6):
    """Parameter-plane row: get+push MB/s through one server vs a
    sharded plane vs the pipelined push loop — the BENCH_r* trace of
    the async-training RPC ceiling (shard servers in separate
    processes; see benchmarks/ps_rpc_bench.py for the sweep)."""
    import ps_rpc_bench as bench  # sibling module (script dir on sys.path)

    port = 27351
    sweep = bench.measure_payload_sweep(
        port, sizes_mb=(payload_mb,), shard_counts=(1, shards),
        rounds=rounds)
    row = sweep["rows"][0]
    pipeline = bench.measure_pipeline(port + 10, mb=payload_mb,
                                      rounds=rounds)
    return {"metric": "ps_plane_mb_per_sec",
            "value": row[f"shards{shards}_mb_per_sec"],
            "unit": "MB/s (get+push, socket loopback)",
            "payload_mb": payload_mb, "rounds": rounds,
            "single_mb_per_sec": row["shards1_mb_per_sec"],
            "sharded_mb_per_sec": row[f"shards{shards}_mb_per_sec"],
            "sharded_speedup": row.get("sharded_speedup"),
            "pipelined_rounds_per_sec": pipeline["value"],
            "pipeline_overlap_speedup": pipeline["overlap_speedup"],
            "config": f"{payload_mb:g} MB payload, {shards} shards in "
                      "separate processes, persistent sockets, "
                      "cached-snapshot gets, zero-copy decode"}


def measure_ps_failover(smoke=False):
    """Fault-tolerant-parameter-plane row: hot-standby failover wall
    time (primary killed mid-push-stream -> standby promoted -> next
    push lands), the zero-lost-updates invariant checked against a
    never-killed oracle, and 2PC push rounds/s with replication on vs
    off (the cost of the standby's synchronous applied-delta stream).

    In-process servers by design: promotion IS an in-process control
    action (`promote_shard`), and the replication on/off comparison
    biases both lanes identically — the row's story is failover latency
    and replication overhead, not absolute RPC ceilings (ps_plane's
    subprocess sweep owns those)."""
    import threading

    from elephas_tpu.parameter.factory import (create_sharded_client,
                                               create_sharded_server)

    rng = np.random.default_rng(0)
    n_elem = 4_000 if smoke else 250_000     # ~1 MB fp32 plane full-size
    sizes = (n_elem, n_elem // 2, n_elem // 4, n_elem // 8)
    ws = [rng.random(n).astype(np.float32) for n in sizes]
    rounds = 4 if smoke else 40
    port = 27460

    def push_rounds(standby):
        group = create_sharded_server(
            "socket", {"model": None, "weights": ws}, port,
            "asynchronous", 2, standby=standby)
        group.start()
        try:
            client = create_sharded_client(
                "socket", port, {"model": None, "weights": ws}, 2,
                timeout=10.0, backoff=0.05)
            delta = [np.full_like(w, 0.001) for w in ws]
            client.update_parameters(delta)          # warm both lanes
            start = time.perf_counter()
            for _ in range(rounds):
                client.update_parameters(delta)
            elapsed = time.perf_counter() - start
            client.close()
            return rounds / elapsed
        finally:
            group.stop()

    rps_replicated = push_rounds(standby=True)
    rps_plain = push_rounds(standby=False)

    # failover: kill primary 0 mid-stream; a monitor promotes; measure
    # kill -> next push acked (the client-visible outage window)
    group = create_sharded_server(
        "socket", {"model": None, "weights": ws}, port + 8,
        "asynchronous", 2, standby=True)
    group.start()
    client = create_sharded_client(
        "socket", port + 8, {"model": None, "weights": ws}, 2,
        timeout=10.0, backoff=0.02)
    n_before, n_after = (2, 2) if smoke else (6, 6)
    value = np.float32(0.001)
    applied = 0
    try:
        from elephas_tpu.parameter.sharding import CommitAbortedError

        def push_once():
            for _ in range(80):
                try:
                    client.update_parameters(
                        [np.full_like(w, value) for w in ws])
                    return
                except CommitAbortedError:
                    time.sleep(0.02)
            raise RuntimeError("push never landed through the failover")

        for _ in range(n_before):
            push_once()
            applied += 1

        promoted = threading.Event()

        def monitor():
            while not group.promote_shard(0):
                time.sleep(0.01)
            promoted.set()

        t0 = time.perf_counter()
        # SIGKILL-shaped death: close the socket out from under the
        # server, no graceful handler joins (stop() would spend ~0.5s
        # of bookkeeping that a real process kill never performs —
        # promote_shard does the corpse cleanup off the timed path)
        group.servers[0].runs = False
        group.servers[0].socket.close()
        threading.Thread(target=monitor, daemon=True).start()
        push_once()                              # blocks through outage
        applied += 1
        failover_ms = (time.perf_counter() - t0) * 1e3
        promoted.wait(timeout=10)
        for _ in range(n_after - 1):
            push_once()
            applied += 1

        oracle = [w - applied * value for w in ws]
        final = client.get_parameters()
        zero_lost = all(
            np.allclose(f, o, rtol=1e-5, atol=1e-7)
            for f, o in zip(final, oracle))
        client.close()
    finally:
        group.stop()

    return {"metric": "ps_failover_ms", "value": round(failover_ms, 2),
            "unit": "ms (primary killed mid-stream -> next push acked)",
            "zero_lost_updates": bool(zero_lost),
            "pushes_through_failover": applied,
            "rounds_per_sec_replicated": round(rps_replicated, 2),
            "rounds_per_sec_unreplicated": round(rps_plain, 2),
            "replication_overhead": round(rps_plain / rps_replicated, 3)
            if rps_replicated else None,
            "config": f"2 socket shards + hot standbys, ~{4 * sum(sizes) / 1e6:.1f} MB "
                      f"fp32 plane, {rounds} 2PC push rounds/lane, "
                      "in-process servers (control-plane row; see "
                      "ps_plane for subprocess RPC ceilings)"}


def measure_decode(batch=8, prompt_len=16, max_new_tokens=128):
    """Decode-throughput row: tokens/sec of the jitted KV-cache scan on
    the flagship LM config (serving path), bf16 weights vs weight-only
    int8 (decode is HBM-bandwidth-bound: int8 halves weight traffic)."""
    import jax

    from elephas_tpu.models.quantization import quantize_lm_params
    from elephas_tpu.models.transformer import (TransformerConfig,
                                                generate, init_params)

    c = TransformerConfig(vocab_size=32000, num_layers=8, num_heads=16,
                          d_model=1024, d_ff=4096,
                          max_seq_len=prompt_len + max_new_tokens)
    params = init_params(c, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (batch, prompt_len),
                                0, c.vocab_size)

    def tps(p, cfg):
        np.asarray(generate(p, prompt, max_new_tokens, cfg))  # compile
        start = time.perf_counter()
        np.asarray(generate(p, prompt, max_new_tokens, cfg))
        return batch * max_new_tokens / (time.perf_counter() - start)

    import dataclasses

    fp = tps(params, c)
    qp = quantize_lm_params(params)
    int8 = tps(qp, c)
    full_int8 = tps(qp, dataclasses.replace(c, kv_cache_quant=True))

    # speculative-decoding primitive: per-token cost of the gamma+1-wide
    # verify block vs the sequential scan above — the weight-read
    # amortization that bounds spec-decode's speedup (1 + gamma*accept),
    # measured draft-free so it is model-quality-independent
    import jax.numpy as jnp
    from functools import partial

    from elephas_tpu.models.transformer import decode_block, prefill_cache

    gamma1 = 5
    blk_tokens = jax.random.randint(jax.random.PRNGKey(2), (batch, gamma1),
                                    0, c.vocab_size)

    @partial(jax.jit, static_argnames=())
    def verify_rounds(p, cache):
        def body(i, carry):
            cache, acc = carry
            lg, cache = decode_block(p, cache, blk_tokens,
                                     prompt_len + i * gamma1, c)
            return cache, acc + lg.sum()
        return jax.lax.fori_loop(0, max_new_tokens // gamma1, body,
                                 (cache, jnp.float32(0)))[1]

    _, cache0 = prefill_cache(params, prompt, c, c.max_seq_len)
    float(verify_rounds(params, cache0))  # compile
    start = time.perf_counter()
    float(verify_rounds(params, cache0))
    verify_tps = (batch * gamma1 * (max_new_tokens // gamma1)
                  / (time.perf_counter() - start))
    # fp is the stable headline (the row's historical meaning); the int8
    # variants are candidate columns, promoted explicitly once chip runs
    # show a consistent win — max(noisy samples) would bias upward and
    # silently flip variants between runs
    return {"metric": "decode_tokens_per_sec",
            "value": round(fp, 1),
            "unit": "tokens/sec", "batch": batch,
            "max_new_tokens": max_new_tokens,
            "int8_tokens_per_sec": round(int8, 1),
            "int8_speedup": round(int8 / fp, 3),
            "int8_kvq_tokens_per_sec": round(full_int8, 1),
            "int8_kvq_speedup": round(full_int8 / fp, 3),
            "spec_verify_tokens_per_sec": round(verify_tps, 1),
            "spec_verify_speedup": round(verify_tps / fp, 3),
            "config": "L8 d1024 ff4096 h16 greedy KV-cache decode; "
                      "int8 = weight-only per-channel quantization; "
                      "kvq adds the int8 KV cache; spec_verify = "
                      "5-token decode_block rounds (speculative "
                      "decoding's verify primitive, draft-free ceiling)"}


def measure_fleet_router(n_replicas=3, n_groups=6, n_requests=60,
                         prefix_len=8, suffix_len=4, max_new_tokens=4,
                         smoke=False):
    """Fleet-router row: consistent-hash vs round-robin routing over an
    in-process ``ReplicaPool`` with lazy per-replica prefix caching —
    the prefix-cache hit-rate win cache-aware placement buys (and the
    CPU-measurable proxy-path round trip). A cold head is a MISS (no
    cached block for it was resident on the routed-to replica — the
    automatic block cache that replaced PR 6's lazy registration);
    hit rate is ``1 - misses/requests``."""
    import json as _json
    import urllib.request

    import jax
    import jax.numpy as jnp

    from elephas_tpu.fleet import FleetRouter, ReplicaPool
    from elephas_tpu.models.transformer import (TransformerConfig,
                                                init_params)
    from elephas_tpu.serving_engine import DecodeEngine

    if smoke:
        n_groups, n_requests = 3, 12
    c = TransformerConfig(vocab_size=300, num_layers=2, num_heads=4,
                          d_model=32, d_ff=64, max_seq_len=48,
                          dtype=jnp.float32)
    params = init_params(c, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    groups = [[int(t) for t in rng.integers(0, 300, prefix_len)]
              for _ in range(n_groups)]
    prompts = [groups[i % n_groups]
               + [int(t) for t in rng.integers(0, 300, suffix_len)]
               for i in range(n_requests)]
    # shuffle: a strict i%G group cycle can ALIAS with round-robin's
    # i%N replica cycle (G and N sharing a factor gives round-robin
    # accidental perfect affinity) — real traffic interleaves prefixes
    rng.shuffle(prompts)

    def run(policy):
        pool = ReplicaPool(
            lambda: DecodeEngine(params, c, max_slots=2), n=n_replicas,
            auto_prefix_tokens=prefix_len).start()
        try:
            with FleetRouter(pool.urls, policy=policy,
                             prefix_tokens=prefix_len,
                             probe_interval=0.5,
                             spill_threshold=None) as router:
                start = time.perf_counter()
                for p in prompts:
                    req = urllib.request.Request(
                        f"http://127.0.0.1:{router.port}/v1/generate",
                        data=_json.dumps(
                            {"prompt": p,
                             "max_new_tokens": max_new_tokens}).encode(),
                        headers={"Content-Type": "application/json"})
                    with urllib.request.urlopen(req, timeout=120) as r:
                        r.read()
                elapsed = time.perf_counter() - start
                misses = sum(e.misses for e in pool.engines)
            return 1 - misses / n_requests, n_requests / elapsed
        finally:
            pool.stop()

    rr_rate, rr_rps = run("round_robin")
    ch_rate, ch_rps = run("prefix_hash")
    return {"metric": "fleet_router_prefix_hit_rate",
            "value": round(ch_rate, 4),
            "unit": "prefix-cache hit rate (consistent-hash routing)",
            "round_robin_hit_rate": round(rr_rate, 4),
            "hit_rate_gain": round(ch_rate - rr_rate, 4),
            "consistent_hash_requests_per_sec": round(ch_rps, 1),
            "round_robin_requests_per_sec": round(rr_rps, 1),
            "replicas": n_replicas, "prefix_groups": n_groups,
            "requests": n_requests,
            "config": f"{n_replicas} in-process replicas, "
                      f"{n_groups} shared {prefix_len}-token prefixes, "
                      f"{n_requests} proxied generates, automatic "
                      "per-replica block cache (miss = no cached block "
                      "for the routed head)"}


def measure_crash_resume(n_replicas=3, max_new_tokens=24,
                         step_delay_s=0.04, kill_after=6, iters=3,
                         smoke=False):
    """Crash-resume row: kill the replica serving a live greedy stream
    and measure the CLIENT-observed continuation gap — the largest
    inter-token arrival gap after the kill (the dying replica's
    already-buffered tokens arrive instantly, so kill->next-token
    would flatter both modes; the resume stall is what dominates the
    worst inter-arrival gap) — for the router's two resume modes.
    ``prefix`` resubmits prompt+journaled tokens as a forced prefix
    (the sibling decodes only NEW tokens, often over a prefix-cache
    chain hit), ``recompute`` replays the request from scratch and
    relies on the router's index dedupe, so its gap grows with the
    tokens already streamed — the gap ratio is the headline. Both
    modes must stay token-identical to a never-killed oracle (the
    ``token_identical`` guard), or the row is measuring a bug."""
    import json as _json
    import urllib.request

    import jax
    import jax.numpy as jnp

    from elephas_tpu.fleet import FleetRouter, ReplicaPool
    from elephas_tpu.models.transformer import (TransformerConfig,
                                                generate, init_params)
    from elephas_tpu.serving_engine import DecodeEngine

    if smoke:
        iters, max_new_tokens = 1, 16
    c = TransformerConfig(vocab_size=300, num_layers=2, num_heads=4,
                          d_model=32, d_ff=64, max_seq_len=64,
                          dtype=jnp.float32)
    params = init_params(c, jax.random.PRNGKey(0))
    prompt = [2, 7, 1, 8, 2, 8]
    oracle = [int(t) for t in np.asarray(generate(
        params, jnp.asarray(prompt)[None], max_new_tokens, c))[0]]

    class _Slow(DecodeEngine):
        # paces decode so the kill reliably lands mid-stream and the
        # continuation gap is dominated by resume work, not step jitter
        def step(self):
            out = super().step()
            time.sleep(step_delay_s)
            return out

    def _warm(url):
        # engines compile prefill per distinct prompt length; warm the
        # initial length (max_new=2 also compiles the decode step) and
        # the lengths a prefix resume can land on, so the measured gap
        # is resume work, not first-touch XLA compiles
        lens = [len(prompt)] + list(range(len(prompt) + kill_after,
                                          len(prompt) + kill_after + 4))
        for i, length in enumerate(lens):
            wreq = urllib.request.Request(
                f"{url}/v1/generate",
                data=_json.dumps({"prompt": [1] * length,
                                  "max_new_tokens": 2 if i == 0
                                  else 1}).encode(),
                headers={"Content-Type": "application/json"})
            urllib.request.urlopen(wreq, timeout=120).read()

    def run(mode):
        from concurrent.futures import ThreadPoolExecutor

        gaps, identical = [], True
        for _ in range(iters):
            pool = ReplicaPool(lambda: _Slow(params, c, max_slots=2),
                               n=n_replicas).start()
            try:
                with ThreadPoolExecutor(n_replicas) as ex:
                    list(ex.map(_warm, pool.urls))
                with FleetRouter(pool.urls, probe_interval=0.2,
                                 evict_after=2,
                                 stream_resume=mode) as router:
                    req = urllib.request.Request(
                        f"http://127.0.0.1:{router.port}/v1/generate",
                        data=_json.dumps(
                            {"prompt": prompt, "stream": True,
                             "max_new_tokens": max_new_tokens}).encode(),
                        headers={"Content-Type": "application/json"})
                    streamed = []
                    killed_at, worst_gap, prev = None, 0.0, None
                    with urllib.request.urlopen(req, timeout=120) as r:
                        for raw in r:
                            line = _json.loads(raw)
                            if "status" in line:
                                continue
                            now = time.perf_counter()
                            if killed_at is not None and prev is not None:
                                worst_gap = max(worst_gap,
                                                now - max(prev, killed_at))
                            prev = now
                            streamed.extend(line["tokens"])
                            if (killed_at is None
                                    and len(streamed) >= kill_after):
                                with urllib.request.urlopen(
                                        f"http://127.0.0.1:"
                                        f"{router.port}/stats",
                                        timeout=30) as s:
                                    stats = _json.loads(s.read())
                                victim = next(
                                    u for u, info in
                                    stats["replicas"].items()
                                    if info["in_flight"] > 0)
                                pool.kill(pool.urls.index(victim))
                                killed_at = time.perf_counter()
                    identical &= streamed == oracle
                    gaps.append(worst_gap)
            finally:
                pool.stop()
        return sorted(gaps)[len(gaps) // 2], identical

    prefix_gap, p_ok = run("prefix")
    recompute_gap, r_ok = run("recompute")
    return {"metric": "crash_resume_continuation_gap_s",
            "value": round(prefix_gap, 4),
            "unit": "s worst client inter-token gap after replica "
                    "kill (prefix resume, median)",
            "recompute_gap_s": round(recompute_gap, 4),
            "resume_speedup": round(recompute_gap / prefix_gap, 2),
            "token_identical": bool(p_ok and r_ok),
            "replicas": n_replicas, "kill_after_tokens": kill_after,
            "max_new_tokens": max_new_tokens, "iters": iters,
            "config": f"{n_replicas} in-process replicas, "
                      f"{step_delay_s * 1000:.0f} ms/step pacing, "
                      f"replica killed after {kill_after} streamed "
                      "tokens; gap = worst post-kill inter-token "
                      "arrival gap"}


def measure_resilience(n_replicas=3, n_requests=40, gray_delay_s=0.08,
                       smoke=False):
    """Network-resilience row: one replica behind a one-way partition
    (router->replica traffic blackholes) and another on a gray link
    (every dispatch and probe toward it eats ``gray_delay_s``), under
    sustained blocking load — measured WITH the resilience plane
    (retry budgets, circuit breakers, gray-failure demotion) and
    WITHOUT (``resilience=False``, the pre-plane router). The plane's
    story: the gray replica is demoted and drained, so the tail stops
    paying the slow link; request amplification (dispatches per client
    request) stays bounded by the retry-rate cap in both arms here,
    but only the plane *enforces* it."""
    import json as _json
    import urllib.error
    import urllib.request

    import jax
    import jax.numpy as jnp

    from elephas_tpu.fleet import FleetRouter, ReplicaPool
    from elephas_tpu.fleet.resilience import CircuitBreaker
    from elephas_tpu.models.transformer import (TransformerConfig,
                                                init_params)
    from elephas_tpu.obs.metrics import MetricsRegistry
    from elephas_tpu.serving_engine import DecodeEngine
    from elephas_tpu.utils.faults import (FaultEvent, FaultPlan,
                                          clear_plan, install_plan)

    if smoke:
        n_requests = 10
    c = TransformerConfig(vocab_size=300, num_layers=2, num_heads=4,
                          d_model=32, d_ff=64, max_seq_len=64,
                          dtype=jnp.float32)
    params = init_params(c, jax.random.PRNGKey(0))

    def _post(port, payload):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/generate",
            data=_json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as resp:
            return _json.loads(resp.read())

    def run(resilient):
        pool = ReplicaPool(lambda: DecodeEngine(params, c, max_slots=4),
                           n=n_replicas).start()
        part = pool.urls[0].replace("http://", "")
        lag = pool.urls[1].replace("http://", "")
        rng = np.random.default_rng(0)
        reg = MetricsRegistry()
        lats, failures = [], 0
        try:
            with FleetRouter(
                    pool.urls, probe_interval=0.2, evict_after=2,
                    hedge=False, registry=reg, resilience=resilient,
                    circuit_breaker=CircuitBreaker(
                        failure_threshold=1, open_for_s=1.0,
                        registry=reg, scope="replica"),
                    degrade_latency_s=gray_delay_s / 2,
                    degrade_drain_after=4) as router:
                deadline = time.time() + 10
                while (time.time() < deadline and
                       len(router.membership.ring_nodes()) < n_replicas):
                    time.sleep(0.05)
                for _ in range(3):       # warm prefill/decode compiles
                    p = [int(t) for t in rng.integers(0, 300, 6)]
                    _post(router.port, {"prompt": p, "max_new_tokens": 2})
                base = router.stats()["requests_rerouted"]
                install_plan(FaultPlan([
                    FaultEvent("fleet.post_replica", "partition",
                               times=None, delay=0.0, peer=part),
                    FaultEvent("fleet.probe", "partition", times=None,
                               delay=0.0, peer=part),
                    FaultEvent("fleet.post_replica", "delay", times=None,
                               delay=gray_delay_s, peer=lag),
                    FaultEvent("fleet.probe", "delay", times=None,
                               delay=gray_delay_s, peer=lag),
                ], seed=5))
                for _ in range(n_requests):
                    p = [int(t) for t in rng.integers(0, 300, 6)]
                    t0 = time.perf_counter()
                    try:
                        _post(router.port,
                              {"prompt": p, "max_new_tokens": 2})
                    except urllib.error.HTTPError:
                        failures += 1
                    lats.append(time.perf_counter() - t0)
                stats = router.stats()
                rerouted = stats["requests_rerouted"] - base
                hedged = stats["hedge"]["requests_hedged"]
        finally:
            clear_plan()
            pool.stop()
        lats.sort()
        p99 = lats[min(len(lats) - 1, int(round(0.99 * (len(lats) - 1))))]
        amp = (n_requests + rerouted + hedged) / n_requests
        return p99, failures, amp

    p99_with, fail_with, amp_with = run(True)
    p99_without, fail_without, amp_without = run(False)
    return {"metric": "resilience_p99_latency_s",
            "value": round(p99_with, 4),
            "unit": "s p99 request latency under partition + gray "
                    "replica (resilience plane ON)",
            "without_plane_p99_s": round(p99_without, 4),
            "p99_speedup": round(p99_without / max(p99_with, 1e-9), 2),
            "amplification_with": round(amp_with, 3),
            "amplification_without": round(amp_without, 3),
            "failed_requests_with": fail_with,
            "failed_requests_without": fail_without,
            "requests": n_requests, "replicas": n_replicas,
            "config": f"{n_replicas} in-process replicas; replica 0 "
                      "one-way partitioned, replica 1 behind "
                      f"{gray_delay_s * 1000:.0f} ms injected link "
                      "delay; blocking generates, amplification = "
                      "dispatches per client request"}


class _UniformSlowStep:
    """Engine shim: every step() stalls a fixed amount — scales one
    replica's capacity DOWN so a tiny CPU model saturates under a few
    closed-loop clients and the autoscaler has something to scale."""

    def __init__(self, engine, delay_s):
        self._engine = engine
        self._delay_s = float(delay_s)

    def step(self):
        time.sleep(self._delay_s)
        return self._engine.step()

    def __getattr__(self, name):
        return getattr(self._engine, name)


class _IntermittentSlowStep:
    """Engine shim for the hedging A/B: every ``every``-th submitted
    request is CURSED — steps stall while it is in flight — an
    intermittently degraded replica (GC-pause / noisy-neighbor shape),
    the tail hedged retries exist to cut. The stall is strictly
    per-request: cancelling the cursed request (the hedge's
    loser-cancel path) or fetching its result lifts it, so one curse
    slows exactly one request, hedging on or off."""

    def __init__(self, engine, delay_s, every=4):
        self._engine = engine
        self._delay_s = float(delay_s)
        self._every = int(every)
        self._n_submits = 0
        self._cursed: set = set()

    def submit(self, *args, **kwargs):
        rid = self._engine.submit(*args, **kwargs)
        self._n_submits += 1
        if self._n_submits % self._every == 0:
            self._cursed.add(rid)
        return rid

    def step(self):
        if self._cursed:
            time.sleep(self._delay_s)
        return self._engine.step()

    def result_info(self, rid):
        out = self._engine.result_info(rid)
        if out is not None:
            self._cursed.discard(rid)
        return out

    def cancel(self, rid):
        self._cursed.discard(rid)
        return self._engine.cancel(rid)

    def __getattr__(self, name):
        return getattr(self._engine, name)


def measure_autoscaler(smoke=False):
    """Autoscaler + hedging row, all CPU-measurable:

    - **Load step up**: closed-loop clients triple against a 1-replica
      fleet; the row reports how many probe windows the autoscaler
      needs to reach the new replica count and the steady-state client
      p99 after convergence vs the pre-step baseline.
    - **Load step down**: the burst ends; the fleet drains back to the
      floor gracefully while a light client keeps running — the row
      reports the drained scale-down and the failed-request count
      (MUST be zero; drain, never kill).
    - **Hedging A/B**: one replica of three intermittently stalled;
      same request sequence with hedging off vs on — end-to-end p99
      cut and the hedged-duplicate fraction vs the 10% cap.
    """
    import threading as _threading
    import urllib.request

    import jax
    import jax.numpy as jnp

    from elephas_tpu.fleet import (FleetAutoscaler, FleetRouter,
                                   ReplicaPool, ReplicaPoolTier,
                                   TierPolicy)
    from elephas_tpu.models.transformer import (TransformerConfig,
                                                init_params)
    from elephas_tpu.obs.metrics import percentile
    from elephas_tpu.serving_engine import DecodeEngine

    c = TransformerConfig(vocab_size=300, num_layers=2, num_heads=4,
                          d_model=32, d_ff=64, max_seq_len=48,
                          dtype=jnp.float32)
    params = init_params(c, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    max_new = 8

    def _gen(port, prompt, timeout=120):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/generate",
            data=json.dumps({"prompt": prompt,
                             "max_new_tokens": max_new}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=timeout) as r:
            r.read()

    # ------------------------------------------------ load step up/down
    probe_w = 0.3
    pre_s, step_s = (2.0, 5.0) if smoke else (4.0, 12.0)
    pool = ReplicaPool(
        lambda: _UniformSlowStep(
            DecodeEngine(params, c, max_slots=2), 0.02),
        n=1).start()
    router = FleetRouter(pool.urls, probe_interval=0.15, join_after=1,
                         evict_after=2, hedge=False).start()
    tier = ReplicaPoolTier(
        router, pool,
        TierPolicy(min_replicas=1, max_replicas=2, high_depth=1.5,
                   low_depth=0.8, up_after=1, down_after=3),
        drain_timeout=30.0)
    scaler = FleetAutoscaler([tier], probe_interval=probe_w).start()
    lock = _threading.Lock()
    lats: list = []
    failures = [0]
    stop_light = _threading.Event()
    stop_heavy = _threading.Event()

    def client(stop_evt):
        lrng = np.random.default_rng(_threading.get_ident() % 2**31)
        while not stop_evt.is_set():
            p = [int(t) for t in lrng.integers(0, 300, 6)]
            t0 = time.perf_counter()
            try:
                _gen(router.port, p)
            except Exception:  # noqa: BLE001 — ANY client-visible error
                with lock:     # is a failed request; the row reports it
                    failures[0] += 1
                continue
            with lock:
                lats.append(time.perf_counter() - t0)

    try:
        _gen(router.port, [1, 2, 3])   # warm replica 0's compile
        light = _threading.Thread(target=client, args=(stop_light,),
                                  daemon=True)
        light.start()
        time.sleep(pre_s)
        with lock:
            # guard the empty sample (an overloaded runner can starve
            # the light client out of the whole pre window): the row
            # then reports None instead of the step dying
            pre_p99 = percentile(lats, 0.99) if lats else None
            lats.clear()
        # 3x load step: two more closed-loop clients
        t_step = time.monotonic()
        heavies = [_threading.Thread(target=client, args=(stop_heavy,),
                                     daemon=True) for _ in range(2)]
        for t in heavies:
            t.start()
        up_windows = None
        while time.monotonic() - t_step < step_s:
            if up_windows is None and tier.count() >= 2:
                up_windows = (time.monotonic() - t_step) / probe_w
            time.sleep(0.02)
        with lock:
            tail = lats[len(lats) // 2:]   # post-convergence steady state
            step_p99 = percentile(tail, 0.99) if tail else None
            lats.clear()
        # load step down: burst ends, the light client keeps running
        # THROUGH the drain — zero failures is the acceptance bar
        stop_heavy.set()
        for t in heavies:
            t.join(timeout=30)
        t_down = time.monotonic()
        down_windows = None
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            if (tier.count() == 1 and tier.draining() == 0
                    and len(router.membership.candidate_urls()) == 1):
                down_windows = (time.monotonic() - t_down) / probe_w
                break
            time.sleep(0.05)
        time.sleep(0.5)                 # light traffic over the shrunk fleet
        stop_light.set()
        light.join(timeout=30)
    finally:
        stop_light.set()
        stop_heavy.set()
        scaler.stop()
        router.stop()
        pool.stop()
    n_failed = failures[0]

    # ------------------------------------------------------- hedging A/B
    n_warm, n_meas = (16, 36) if smoke else (30, 60)
    hedge_cap = 0.10
    builds: list = []

    def hedge_factory():
        eng = DecodeEngine(params, c, max_slots=2)
        if not builds:   # replica 0 is the intermittently slow one
            eng = _IntermittentSlowStep(eng, 0.1, every=6)
        builds.append(eng)
        return eng

    hpool = ReplicaPool(hedge_factory, n=3).start()
    prompts = [[int(t) for t in rng.integers(0, 300, 6)]
               for _ in range(n_warm + n_meas)]
    hedge_results = {}
    try:
        for mode, kwargs in (("off", dict(hedge=False)),
                             ("on", dict(hedge=True, hedge_quantile=0.9,
                                         hedge_min_s=0.15,
                                         hedge_max_fraction=hedge_cap,
                                         hedge_min_samples=16,
                                         hedge_poll_s=0.005))):
            with FleetRouter(hpool.urls, probe_interval=0.15,
                             join_after=1, **kwargs) as hrouter:
                deadline = time.monotonic() + 15
                while hrouter.membership.ring_size() < 3:
                    if time.monotonic() > deadline:
                        raise RuntimeError("replicas never joined")
                    time.sleep(0.02)
                mlats = []
                for i, p in enumerate(prompts):
                    t0 = time.perf_counter()
                    _gen(hrouter.port, p)
                    if i >= n_warm:   # warm segment arms the window
                        mlats.append(time.perf_counter() - t0)
                stats = hrouter.stats()
                hedge_results[mode] = {
                    "p99": percentile(mlats, 0.99),
                    "p50": percentile(mlats, 0.5),
                    "hedged": stats["hedge"]["requests_hedged"],
                }
    finally:
        hpool.stop()
    off, on = hedge_results["off"], hedge_results["on"]
    hedged_fraction = on["hedged"] / len(prompts)

    return {"metric": "autoscaler_scale_up_probe_windows",
            "value": (round(up_windows, 2) if up_windows is not None
                      else None),
            "unit": "probe windows from load step to target replicas",
            "scale_down_probe_windows": (round(down_windows, 2)
                                         if down_windows is not None
                                         else None),
            "pre_step_p99_s": (round(pre_p99, 4)
                               if pre_p99 is not None else None),
            "post_step_steady_p99_s": (round(step_p99, 4)
                                       if step_p99 is not None
                                       else None),
            "steady_p99_vs_pre": (round(step_p99 / pre_p99, 3)
                                  if pre_p99 and step_p99 is not None
                                  else None),
            "failed_requests": n_failed,
            "hedge_off_p99_s": round(off["p99"], 4),
            "hedge_on_p99_s": round(on["p99"], 4),
            "hedge_p99_cut": round(off["p99"] / on["p99"], 3),
            "hedge_off_p50_s": round(off["p50"], 4),
            "hedge_on_p50_s": round(on["p50"], 4),
            "hedged_requests": on["hedged"],
            "hedged_fraction": round(hedged_fraction, 4),
            "hedge_cap": hedge_cap,
            "probe_window_s": probe_w,
            "config": "1->2 replica autoscale under a 3x closed-loop "
                      "load step (drain-only scale-down, zero-failure "
                      "bar), then hedging A/B over 3 replicas with "
                      "replica 0 intermittently stalled (every 6th "
                      "submit, 0.1s/step): same prompt sequence, "
                      "hedge off vs on"}


def _disagg_model(max_seq_len: int):
    """The disagg row's tiny-but-real LM, shared by the parent and the
    prefill child process (identical seed => identical weights)."""
    import jax
    import jax.numpy as jnp

    from elephas_tpu.models.transformer import (TransformerConfig,
                                                init_params)

    c = TransformerConfig(vocab_size=300, num_layers=2, num_heads=4,
                          d_model=32, d_ff=64, max_seq_len=max_seq_len,
                          dtype=jnp.float32)
    return init_params(c, jax.random.PRNGKey(0)), c


def run_disagg_prefill_child(argv):
    """``--disagg-prefill-child MAX_SEQ_LEN QUANT BLOCK_SIZE`` — host a
    PrefillWorker in THIS process and serve dispatch over stdin/stdout
    (one JSON job per line in; ``ready``/``shipped``/``failed`` events
    out). The prefill tier living in its own process is the production
    topology (and the measurement point: in-process threads share one
    GIL and understate the architecture, the ps_rpc_bench lesson)."""
    import json as _json
    import threading

    from elephas_tpu.disagg import PrefillWorker
    from elephas_tpu.obs.context import parse_traceparent
    from elephas_tpu.disagg.prefill import PrefillJob
    from elephas_tpu.serving_engine import DecodeEngine

    max_seq_len, quant, block = (int(argv[0]), argv[1] == "1",
                                 int(argv[2]))
    params, c = _disagg_model(max_seq_len)
    out_lock = threading.Lock()

    def emit(ev):
        with out_lock:
            print(_json.dumps(ev), flush=True)

    worker = PrefillWorker(DecodeEngine(params, c, max_slots=1),
                           quant=quant, block_size=block,
                           name="prefill-child").start()
    orig_ship = worker.shipper.ship

    def ship(addr, meta, arrays, quant=True, ctx=None):
        n = orig_ship(addr, meta, arrays, quant=quant, ctx=ctx)
        emit({"ev": "shipped", "rid": meta["rid"], "bytes": n,
              "codec": "q8" if quant else "fp"})
        return n

    worker.shipper.ship = ship
    emit({"ev": "ready"})
    for line in sys.stdin:
        line = line.strip()
        if not line:
            continue
        try:
            req = _json.loads(line)
        except ValueError:
            continue               # a torn line must not kill the tier
        job = PrefillJob(
            req["rid"], req["prompt"], req["max_new_tokens"],
            temperature=req.get("temperature"),
            top_k=req.get("top_k"), top_p=req.get("top_p"),
            deadline=req.get("deadline"),
            target=tuple(req["target"]),
            ctx=parse_traceparent(req.get("traceparent")),
            on_failed=lambda j, w, e: emit(
                {"ev": "failed", "rid": j.rid, "error": e}))
        worker.submit(job)
    worker.stop()


class _ChildPrefillProxy:
    """Parent-side handle on a prefill-worker child process, quacking
    like a PrefillWorker as far as DisaggEngine's dispatch needs
    (submit / backlog / alive / name / stats)."""

    def __init__(self, max_seq_len, quant, block_size):
        import json as _json
        import subprocess
        import threading
        from collections import deque

        self.name = "prefill-child"
        self.quant = quant
        self.wait_window: deque = deque()
        self.bytes = {"fp": 0, "q8": 0}
        self._json = _json
        self._lock = threading.Lock()
        self._outstanding = {}
        self._proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--disagg-prefill-child", str(max_seq_len),
             "1" if quant else "0", str(block_size)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            bufsize=1)
        # block until the child compiled its imports and is serving
        line = self._proc.stdout.readline()
        if _json.loads(line).get("ev") != "ready":
            raise RuntimeError("prefill child failed to start")
        self._reader = threading.Thread(target=self._read_loop,
                                        daemon=True)
        self._reader.start()

    def _read_loop(self):
        for line in self._proc.stdout:
            try:
                ev = self._json.loads(line)
            except ValueError:
                continue
            rid = ev.get("rid")
            with self._lock:
                job = self._outstanding.pop(rid, None)
            if ev.get("ev") == "shipped":
                with self._lock:
                    self.bytes[ev["codec"]] += int(ev["bytes"])
            elif ev.get("ev") == "failed" and job is not None:
                if job.on_failed is not None:
                    job.on_failed(job, self.name, ev.get("error", "?"))

    @property
    def alive(self):
        return self._proc.poll() is None

    def submit(self, job):
        if not self.alive:
            raise RuntimeError("prefill child is dead")
        ctx = job.ctx
        line = self._json.dumps({
            "rid": job.rid, "prompt": job.prompt,
            "max_new_tokens": job.max_new_tokens,
            "temperature": job.temperature, "top_k": job.top_k,
            "top_p": job.top_p, "deadline": job.deadline,
            "target": list(job.target),
            "traceparent": (None if ctx is None
                            else ctx.to_traceparent())}) + "\n"
        with self._lock:
            # the write happens UNDER the lock: submit is reachable
            # from the dispatcher AND from the reader thread's failure
            # callback, and interleaved text-mode writes would corrupt
            # the child's line protocol
            self._outstanding[job.rid] = job
            self._proc.stdin.write(line)
            self._proc.stdin.flush()

    def backlog(self):
        with self._lock:
            return len(self._outstanding)

    def stats(self):
        return {"name": self.name, "alive": self.alive,
                "backlog": self.backlog()}

    def stop(self):
        try:
            self._proc.stdin.close()
        except OSError:
            pass
        try:
            self._proc.wait(timeout=10)
        except Exception:  # noqa: BLE001 — wedged child
            self._proc.kill()


def measure_disagg(smoke=False):
    """Disaggregated prefill/decode row: under a prefill burst, what
    happens to the DECODE-stage queue-wait tail and combined
    throughput, colocated vs disaggregated at equal total resources —
    plus the Q8-vs-fp32 KV wire-bytes ratio. CPU-measurable (the whole
    topology is in-process servers + loopback sockets).

    Topologies (2 workers and the same total decode-slot KV memory
    each way — the burst is sized so prefill is roughly HALF of each
    colocated worker's compute, the regime the 1-prefill + 1-decode
    split is built for; a decode-dominated mix wants more decode
    workers per prefill worker, which is exactly the independent
    scaling knob this architecture adds):

    - **colocated**: 2 engines behind ServingServer-shaped driver
      loops, round-robin submits — every engine runs prefill AND
      decode on one loop, so a burst of long prompts head-of-line
      blocks the steady short requests behind their prefills.
    - **disagg**: 1 ``PrefillWorker`` + 1 ``DisaggEngine`` decode
      worker — the burst's prefills run on the prefill tier (real KV
      frames over a loopback socket) while the decode engine's
      admissions just install shipped KV.

    Workload: ``n_burst`` long prompts submitted at t=0, then
    ``n_steady`` short latency-bound requests. The headline compares
    the steady requests' decode-stage queue wait (flight-recorder
    ``admitted.queue_wait_s`` on the engines that DECODE them) and the
    combined tokens/s of everything."""
    import jax
    import jax.numpy as jnp

    from elephas_tpu.models.transformer import (TransformerConfig,
                                                init_params)
    from elephas_tpu.obs import percentile
    from elephas_tpu.serving_engine import DecodeEngine

    # slots cover the whole in-flight set (equal total slot rows both
    # ways): queue wait then measures ADMISSION blocking — prefill
    # head-of-line on the colocated engines, KV-install wait on the
    # decode workers — not slot scarcity, which would hit both
    # topologies alike and dilute the signal this row exists to isolate
    n_steady, n_burst = (6, 4) if smoke else (10, 14)
    slots_co = -(-(n_steady + n_burst) // 2)   # per colocated engine
    slots_dg = n_steady + n_burst              # the one decode worker
    steady_len, steady_new = 8, (16 if smoke else 32)
    burst_len, burst_new = (96, 2) if smoke else (240, 4)
    params, c = _disagg_model(burst_len + 32)
    rng = np.random.default_rng(0)
    steady = [[int(t) for t in rng.integers(0, 300, steady_len)]
              for _ in range(n_steady)]
    burst = [[int(t) for t in rng.integers(0, 300, burst_len)]
             for _ in range(n_burst)]
    total_tokens = n_steady * steady_new + n_burst * burst_new

    import threading as _threading

    class _Driver:
        """One worker's engine loop, the ServingServer shape without
        the HTTP layer (handler-thread wake churn on a 2-core box
        otherwise dominates what this row is trying to measure): a
        single thread steps the engine and harvests results; submits
        come from the workload threads under the same lock."""

        def __init__(self, engine):
            self.engine = engine
            self.lock = _threading.Lock()
            self.results = {}
            self._tracked = set()
            self._stop = False
            self._thread = _threading.Thread(target=self._loop,
                                             daemon=True)
            self._thread.start()

        def _loop(self):
            while not self._stop:
                with self.lock:
                    if self.engine.pending:
                        self.engine.step()
                    for rid in list(self._tracked):
                        info = self.engine.result_info(rid)
                        if info is not None:
                            self.results[rid] = info
                            self._tracked.discard(rid)
                    idle = not self.engine.pending
                time.sleep(0.002 if idle else 0)

        def submit(self, prompt, max_new):
            with self.lock:
                rid = self.engine.submit(prompt, max_new, admit=False)
                self._tracked.add(rid)
            return rid

        def wait(self, rids, timeout=300.0):
            deadline = time.monotonic() + timeout
            while time.monotonic() < deadline:
                with self.lock:
                    if all(r in self.results for r in rids):
                        return
                time.sleep(0.002)
            raise RuntimeError("requests never finished")

        def stop(self):
            self._stop = True
            self._thread.join(timeout=10)

    rounds = 1 if smoke else 8

    def _run(drivers, decode_recorders):
        """Warmup compiles, then ``rounds`` timed burst-then-steady
        passes; returns (median elapsed_s, pooled steady queue-wait
        samples) — the median is the ps_rpc_bench convention (single
        passes on a shared box carry scheduler noise), applied
        symmetrically to both topologies; the latency samples pool
        across every pass."""
        # warmup: every engine sees both prompt lengths (prefill
        # compiles) and steps (decode compiles) before the clock
        warm = []
        for i, d in enumerate(drivers * 2):
            warm.append((d, d.submit(steady[i % n_steady], steady_new)))
            warm.append((d, d.submit(burst[i % n_burst], burst_new)))
        for d, rid in warm:
            d.wait([rid])
        elapsed_rounds, waits = [], []
        for _ in range(rounds):
            marks = [len(r.recent(limit=256)) for r in decode_recorders]
            start = time.perf_counter()
            # the whole burst lands first (that is what makes it a
            # burst: every long prompt is queued before the steady
            # traffic), then the steady requests — submits are cheap
            # (admit=False), so the burst is fully queued within a
            # millisecond
            rids = [(drivers[i % len(drivers)],
                     drivers[i % len(drivers)].submit(p, burst_new))
                    for i, p in enumerate(burst)]
            rids += [(drivers[i % len(drivers)],
                      drivers[i % len(drivers)].submit(p, steady_new))
                     for i, p in enumerate(steady)]
            for d in drivers:
                d.wait([rid for dd, rid in rids if dd is d])
            elapsed_rounds.append(time.perf_counter() - start)
            for rec, mark in zip(decode_recorders, marks):
                for t in rec.recent(limit=256)[mark:]:
                    evs = t["events"]
                    if (not evs
                            or evs[0].get("prompt_tokens") != steady_len):
                        continue       # only the steady (short) requests
                    for e in evs:
                        if (e["event"] == "admitted"
                                and e.get("queue_wait_s") is not None):
                            waits.append(e["queue_wait_s"])
        return percentile(elapsed_rounds, 0.5), waits

    # ---- colocated baseline: 2 engines, each prefill + decode
    drivers = [_Driver(DecodeEngine(params, c, max_slots=slots_co))
               for _ in range(2)]
    try:
        co_elapsed, co_waits = _run(
            drivers, [d.engine.recorder for d in drivers])
    finally:
        for d in drivers:
            d.stop()

    # ---- disaggregated: 1 prefill worker (its OWN process — the
    # production topology; an in-process worker thread shares the
    # decode loop's GIL and understates the architecture, exactly the
    # ps_rpc_bench in-process-shards lesson) + 1 decode worker, twice
    # (fp then q8) for the wire-bytes A/B
    def run_disagg(quant):
        from elephas_tpu.disagg import DisaggEngine

        worker = _ChildPrefillProxy(c.max_seq_len, quant, 16)
        deng = DisaggEngine(
            DecodeEngine(params, c, max_slots=slots_dg, tier="decode"),
            [worker])
        driver = _Driver(deng)
        try:
            elapsed, waits = _run([driver], [deng.decode.recorder])
            nbytes = worker.bytes["q8" if quant else "fp"]
            return elapsed, waits, nbytes
        finally:
            driver.stop()
            deng.stop()
            worker.stop()

    # the topology A/B holds the wire codec CONSTANT (fp): on this
    # deliberately tiny CPU model the frames are ~60 KB, so Q8's
    # host-side quantize cost is not amortized by wire savings the way
    # multi-MB real-model frames amortize it — the q8 run is reported
    # alongside as the wire-bytes lever it is, not folded into the
    # topology headline
    dg_elapsed, dg_waits, fp_bytes = run_disagg(quant=False)
    q8_elapsed, _, q8_bytes = run_disagg(quant=True)

    co_p50, co_p99 = (percentile(co_waits, 0.5), percentile(co_waits, 0.99))
    dg_p50, dg_p99 = (percentile(dg_waits, 0.5), percentile(dg_waits, 0.99))
    co_tps = total_tokens / co_elapsed
    dg_tps = total_tokens / dg_elapsed
    # every run shipped identical prompt sets (plus identical warmups),
    # so the byte counters divide into a clean codec ratio
    return {"metric": "disagg_decode_queue_wait_p99_cut",
            "value": round(co_p99 / max(dg_p99, 1e-9), 2),
            "unit": "x (colocated p99 / disagg p99, steady requests "
                    "under a prefill burst)",
            "colocated_queue_wait_p50_s": round(co_p50, 6),
            "colocated_queue_wait_p99_s": round(co_p99, 6),
            "disagg_queue_wait_p50_s": round(dg_p50, 6),
            "disagg_queue_wait_p99_s": round(dg_p99, 6),
            "colocated_tokens_per_sec": round(co_tps, 1),
            "disagg_tokens_per_sec": round(dg_tps, 1),
            "tokens_per_sec_ratio": round(dg_tps / co_tps, 3),
            "disagg_q8_tokens_per_sec": round(total_tokens / q8_elapsed,
                                              1),
            "kv_wire_bytes_fp": int(fp_bytes),
            "kv_wire_bytes_q8": int(q8_bytes),
            "q8_wire_ratio": round(q8_bytes / max(fp_bytes, 1), 3),
            "steady_requests": n_steady, "burst_requests": n_burst,
            "burst_prompt_tokens": burst_len,
            "config": f"L2 d32 V300; {n_burst}x{burst_len}-tok burst + "
                      f"{n_steady}x{steady_len}-tok steady; colocated = "
                      f"2 engines x {slots_co} slots (prefill+decode "
                      f"each); disagg = 1 prefill worker + 1 decode "
                      f"worker x {slots_dg} slots, block 16; headline "
                      "+ ratio at fp wire, q8 columns = the wire-bytes "
                      "lever; in-process driver loops, loopback KV "
                      "sockets"}


#: candidate (block_q, block_k) pairs for the flash kernel sweep — all
#: multiples of the MXU-friendly 128 lane tile
_BLOCK_GRID = ((128, 128), (128, 256), (256, 256), (256, 512),
               (512, 512), (512, 1024))


def measure_flash_scaling(seqs=(1024, 2048, 4096, 8192), heads=16,
                          head_dim=64, steps=10, dtype="bfloat16",
                          sweep_blocks=True):
    """Seq-scaling table: fwd+bwd attention time, Pallas flash (best
    block config per seq) vs the XLA path, constant token budget per
    row. The VERDICT-r2 item-2 evidence: where does flash pull away?"""
    import jax
    import jax.numpy as jnp
    from functools import partial

    from elephas_tpu.ops.attention import attention
    from elephas_tpu.ops.pallas_attention import flash_attention

    batch_for = {1024: 8, 2048: 4, 4096: 2, 8192: 1}
    rows = []
    for s in seqs:
        b = batch_for.get(s, 1)
        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        q, k, v = (jax.random.normal(kk, (b, heads, s, head_dim),
                                     jnp.dtype(dtype)) for kk in keys)

        def bench(fn):
            grad = jax.jit(jax.grad(
                lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32)),
                argnums=(0, 1, 2)))
            g = grad(q, k, v)
            float(jnp.sum(g[0][0, 0, 0]))  # compile + completion barrier
            start = time.perf_counter()
            for _ in range(steps):
                g = grad(q, k, v)
            float(jnp.sum(g[0][0, 0, 0]))
            return (time.perf_counter() - start) / steps * 1e3  # ms

        xla_ms = bench(partial(attention, causal=True))
        row = {"seq": s, "batch": b, "xla_ms": round(xla_ms, 2)}
        best = None
        grid = _BLOCK_GRID if sweep_blocks else _BLOCK_GRID[3:4]
        # flash_attention clamps blocks to the (rounded) seq length, so
        # oversize grid entries collapse — dedupe after clamping
        seen = set()
        for bq, bk in grid:
            bq, bk = min(bq, s), min(bk, s)
            if (bq, bk) in seen:
                continue
            seen.add((bq, bk))
            ms = bench(partial(flash_attention, causal=True, block_q=bq,
                               block_k=bk))
            if best is None or ms < best[0]:
                best = (ms, bq, bk)
        row.update(flash_ms=round(best[0], 2), block_q=best[1],
                   block_k=best[2],
                   speedup=round(xla_ms / best[0], 3))
        rows.append(row)
    return {"metric": "flash_vs_xla_seq_scaling",
            "unit": "ms/step (fwd+bwd)", "dtype": dtype, "rows": rows}


def measure_engine(max_slots=8, n_requests=16, prompt_len=16,
                   max_new_tokens=128, prefix_len=12):
    """Online-serving row: DecodeEngine (continuous batching) draining
    ``n_requests`` through ``max_slots`` slots on the flagship LM config,
    plus the prefix-caching admission win (``prefix_len`` of every
    prompt is a registered shared prefix — the system-prompt pattern).
    The engine is host-driven (one dispatch per token), so this row also
    captures what dispatch latency does to online serving vs the
    fused offline scan in the ``decode`` row."""
    import jax

    from elephas_tpu.models.transformer import TransformerConfig, init_params
    from elephas_tpu.serving_engine import DecodeEngine

    c = TransformerConfig(vocab_size=32000, num_layers=8, num_heads=16,
                          d_model=1024, d_ff=4096,
                          max_seq_len=prompt_len + max_new_tokens)
    params = init_params(c, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prefix = list(rng.integers(0, c.vocab_size, prefix_len))
    prompts = [np.asarray(prefix + list(
        rng.integers(0, c.vocab_size, prompt_len - prefix_len)))
        for _ in range(n_requests)]
    total = n_requests * max_new_tokens

    def drain(eng):
        start = time.perf_counter()
        eng.run(prompts, max_new_tokens)
        return total / (time.perf_counter() - start)

    eng = DecodeEngine(params, c, max_slots=max_slots)
    drain(eng)                       # compile prefill/step/install
    plain_tps = drain(eng)
    # per-stage latency from the flight-recorder timelines of the
    # measured drain (newest n_requests): queue-wait and prefill
    # percentiles, not just end-to-end throughput
    stage_metrics = _stage_percentiles(eng.recorder, n_requests)

    eng_pc = DecodeEngine(params, c, max_slots=max_slots)
    eng_pc.register_prefix(prefix)
    drain(eng_pc)                    # compile suffix-extend path
    prefix_tps = drain(eng_pc)

    # paged KV gather cost, UNCONFOUNDED: pool sized so all slots stay
    # concurrent (same occupancy as the contiguous engine) — the ratio
    # then isolates the per-step block gather; the capacity story
    # (oversubscribed pool, queued admission) is pinned by CPU tests
    per_req = -(-(prompt_len + max_new_tokens) // 16)
    eng_pg = DecodeEngine(params, c, max_slots=max_slots,
                          paged=(1 + max_slots * per_req, 16))
    drain(eng_pg)
    paged_tps = drain(eng_pg)

    # admission cost per request, warm: all slots free, so every submit
    # admits immediately (prefill for the plain engine, suffix
    # decode_block for the prefix engine)
    def admission_ms(engine):
        start = time.perf_counter()
        rids = [engine.submit(p, max_new_tokens) for p in prompts[:max_slots]]
        cost = (time.perf_counter() - start) * 1000 / max_slots
        while engine.pending:
            engine.step()
        for r in rids:
            engine.result(r)
        return cost

    plain_adm = admission_ms(eng)
    prefix_adm = admission_ms(eng_pc)
    return {"metric": "engine_serving_tokens_per_sec",
            "value": round(plain_tps, 1), "unit": "tokens/sec",
            "max_slots": max_slots, "n_requests": n_requests,
            "max_new_tokens": max_new_tokens,
            "prefix_tokens_per_sec": round(prefix_tps, 1),
            "paged_tokens_per_sec": round(paged_tps, 1),
            "paged_vs_contiguous": round(paged_tps / plain_tps, 3),
            "admission_ms": round(plain_adm, 2),
            "prefix_admission_ms": round(prefix_adm, 2),
            "prefix_admission_speedup": round(plain_adm / prefix_adm, 3),
            "tokens_per_step": round(eng.stats["tokens_per_step"], 3),
            "metrics": stage_metrics,
            "config": f"L8 d1024 ff4096 h16 continuous batching, "
                      f"{n_requests} reqs x {prompt_len}-tok prompts "
                      f"({prefix_len} shared prefix) through "
                      f"{max_slots} slots, greedy"}


def measure_weight_swap(smoke=False):
    """Live-weight-plane row: what does hot-swapping weights cost a
    serving engine? Two numbers, both CPU-measurable:

    - **swap pause**: engine-loop blockage per applied swap (the
      ``serving_weight_swap_seconds`` histogram — a param-pointer
      assignment; host→device conversion happens on the subscriber
      thread by construction, so it never appears here);
    - **tokens/s under continuous swapping** vs the no-swap baseline
      on identical traffic — the "zero dropped requests, how much
      throughput?" question.
    """
    import threading

    import jax
    import jax.numpy as jnp

    from elephas_tpu.models.transformer import (TransformerConfig,
                                                init_params)
    from elephas_tpu.serving_engine import DecodeEngine

    if smoke:
        dims = dict(vocab_size=300, num_layers=2, num_heads=4,
                    d_model=32, d_ff=64, max_seq_len=48)
        n_requests, max_new, swap_every_s = 8, 12, 0.02
    else:
        dims = dict(vocab_size=8000, num_layers=4, num_heads=8,
                    d_model=256, d_ff=1024, max_seq_len=160)
        n_requests, max_new, swap_every_s = 16, 128, 0.05
    c = TransformerConfig(**dims, dtype=jnp.float32)
    p0 = init_params(c, jax.random.PRNGKey(0))
    # same shapes/dtypes, different values: what a training delta does
    p1 = jax.tree_util.tree_map(lambda a: a * 1.0001, p0)
    rng = np.random.default_rng(0)
    prompts = [np.asarray(rng.integers(0, c.vocab_size, 16))
               for _ in range(n_requests)]
    total = n_requests * max_new

    def drain(eng):
        start = time.perf_counter()
        rids = [eng.submit(p, max_new) for p in prompts]
        while eng.pending:
            eng.step()
        for r in rids:
            eng.result(r)
        return total / (time.perf_counter() - start)

    eng = DecodeEngine(p0, c, max_slots=8)
    drain(eng)                        # compile prefill/step/install
    baseline_tps = drain(eng)

    # continuous swapping: a background stager alternates two ready
    # device pytrees at swap_every_s (the WeightSubscriber shape — the
    # engine loop only ever pays the apply)
    stop = threading.Event()

    def stager():
        version = 1
        while not stop.is_set():
            eng.stage_params(p1 if version % 2 else p0, version)
            version += 1
            time.sleep(swap_every_s)

    swaps_before = eng.stats["weight_swaps"]
    thread = threading.Thread(target=stager, daemon=True)
    thread.start()
    try:
        swap_tps = drain(eng)
    finally:
        stop.set()
        thread.join(timeout=5)
    eng.step()                        # apply any last staged swap
    swaps = eng.stats["weight_swaps"] - swaps_before
    hist = eng.registry.get("serving_weight_swap_seconds")
    p50 = hist.quantile(0.5) or 0.0
    p99 = hist.quantile(0.99) or 0.0
    return {"metric": "weight_swap_pause_ms",
            "value": round(p50 * 1000, 3), "unit": "ms (p50 per swap)",
            "swap_pause_p99_ms": round(p99 * 1000, 3),
            "swaps_during_run": int(swaps),
            "swap_interval_s": swap_every_s,
            "tokens_per_sec_swapping": round(swap_tps, 1),
            "tokens_per_sec_baseline": round(baseline_tps, 1),
            "throughput_ratio": round(swap_tps / baseline_tps, 3),
            "config": (f"L{c.num_layers} d{c.d_model} ff{c.d_ff} "
                       f"V{c.vocab_size} f32, {n_requests} reqs x "
                       f"{max_new} new tokens through 8 slots; swaps "
                       f"staged every {swap_every_s}s from a "
                       "pre-converted device pytree (the subscriber "
                       "does conversion off-loop); no registered "
                       "prefixes (each pinned prefix adds its "
                       "re-prefill to the pause)")}


def measure_prefix_cache(smoke=False):
    """Automatic prefix caching row: a shared-prefix serving workload
    (the system-prompt pattern, UNREGISTERED — nobody curates prefixes
    at fleet scale) through one paged engine, cache on vs off.
    Admission cost = the flight recorder's per-request ``prefill``
    duration (the queue-to-admitted prefill work a hit turns into a
    pointer install + suffix extend); both engines drain identical
    traffic twice (pass 1 compiles AND warms the cache — pass 2 is the
    steady state measured) and per-request outputs are asserted
    token-identical both ways. The acceptance scalar is
    ``admission_p50_reduction`` (>= 2x on the dev box)."""
    import jax

    import jax.numpy as jnp

    from elephas_tpu.models.transformer import (TransformerConfig,
                                                init_params)
    from elephas_tpu.obs import percentile
    from elephas_tpu.serving_engine import DecodeEngine

    if smoke:
        layers, d_model, d_ff, vocab = 2, 64, 128, 500
        n_groups, n_requests = 2, 6
        prefix_len, suffix_len, max_new = 48, 8, 8
    else:
        layers, d_model, d_ff, vocab = 4, 256, 1024, 8000
        n_groups, n_requests = 4, 24
        prefix_len, suffix_len, max_new = 160, 8, 16
    block = 16
    max_slots = 4
    prompt_len = prefix_len + suffix_len
    # f32 compute: the token-identical assertion is the row's whole
    # point, and under bf16 the hit path's extend program vs the full
    # prefill program round differently (~5e-4 on logits — the module-
    # docstring cross-program caveat), flipping argmax near-ties
    c = TransformerConfig(vocab_size=vocab, num_layers=layers,
                          num_heads=8, d_model=d_model, d_ff=d_ff,
                          max_seq_len=prompt_len + max_new,
                          dtype=jnp.float32)
    params = init_params(c, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    heads = [list(rng.integers(0, vocab, prefix_len))
             for _ in range(n_groups)]
    prompts = [np.asarray(heads[i % n_groups]
                          + list(rng.integers(0, vocab, suffix_len)))
               for i in range(n_requests)]
    rng.shuffle(prompts)
    per_req = -(-(prompt_len + max_new) // block)
    # pool: full slot concurrency plus cache headroom for every group's
    # head (the sizing rule the serving-operations runbook documents)
    n_blocks = 1 + max_slots * per_req + n_groups * (prefix_len // block)

    def drain(eng):
        start = time.perf_counter()
        rids = [eng.submit(p, max_new) for p in prompts]
        while eng.pending:
            eng.step()
        outs = [eng.result(r) for r in rids]
        elapsed = time.perf_counter() - start
        prefills = [e["duration_s"]
                    for t in eng.recorder.recent(limit=n_requests)
                    for e in t["events"] if e["event"] == "prefill"]
        return outs, n_requests * max_new / elapsed, prefills

    results = {}
    for label, cache_on in (("off", False), ("on", True)):
        eng = DecodeEngine(params, c, max_slots=max_slots,
                           paged=(n_blocks, block),
                           prefix_cache=cache_on)
        drain(eng)                    # compile + (on) warm the cache
        outs, tps, prefills = drain(eng)
        results[label] = {"outs": outs, "tps": tps,
                          "adm_p50": percentile(prefills, 0.5),
                          "adm_p99": percentile(prefills, 0.99),
                          "stats": eng.stats}
    assert results["on"]["outs"] == results["off"]["outs"], \
        "cache-on outputs diverged from cache-off"
    on, off = results["on"], results["off"]
    ks = on["stats"]["kv_cache"]
    return {"metric": "prefix_cache_admission_p50_ms",
            "value": round(on["adm_p50"] * 1000, 3),
            "unit": "ms (admission prefill work, cache on, steady)",
            "admission_p50_ms_off": round(off["adm_p50"] * 1000, 3),
            "admission_p99_ms": round(on["adm_p99"] * 1000, 3),
            "admission_p99_ms_off": round(off["adm_p99"] * 1000, 3),
            "admission_p50_reduction": round(
                off["adm_p50"] / max(on["adm_p50"], 1e-9), 2),
            "tokens_per_sec": round(on["tps"], 1),
            "tokens_per_sec_off": round(off["tps"], 1),
            "tokens_per_sec_ratio": round(on["tps"] / off["tps"], 3),
            "cache_hits": ks["hits"], "cache_misses": ks["misses"],
            "prefix_tokens_reused": on["stats"]["prefix_tokens_reused"],
            "outputs_token_identical": True,
            "config": f"L{layers} d{d_model} ff{d_ff} V{vocab} f32 paged "
                      f"({n_blocks}x{block}), {n_requests} reqs = "
                      f"{n_groups} shared {prefix_len}-tok heads + "
                      f"{suffix_len}-tok suffixes, {max_new} new toks, "
                      f"{max_slots} slots, automatic (unregistered) "
                      "block cache, steady-state pass measured"}


def measure_kv_tiered(smoke=False):
    """Tiered KV row: multi-turn chat sessions whose combined trailing
    KV working set is a multiple of the device pool, spill+sessions on
    vs off on the same paged engine. With spill OFF, eviction discards
    a parked chain and every turn-2 admission re-prefills its whole
    conversation (cold TTFT); with spill+sessions ON, retirement
    persists the trailing chain and the next turn promotes it back
    (warm TTFT = remainder-only prefill + host->device copies). Both
    configurations drain identical traffic with outputs asserted
    token-identical, and neither sheds a request. The acceptance
    scalar is ``warm_ttft_speedup`` (>= 3x on the dev box at the full
    sizing, where the working set is ~10x the pool)."""
    import jax

    import jax.numpy as jnp

    from elephas_tpu.models.transformer import (TransformerConfig,
                                                init_params)
    from elephas_tpu.obs import percentile
    from elephas_tpu.serving_engine import DecodeEngine

    if smoke:
        layers, d_model, d_ff, vocab = 2, 64, 128, 500
        n_sessions, turn1_len, follow_len = 8, 48, 8
    else:
        layers, d_model, d_ff, vocab = 4, 768, 1536, 2000
        n_sessions, turn1_len, follow_len = 21, 448, 16
    block, max_slots, max_new = 16, 2, 8
    # the resumable-session shape: a LONG first turn (the document /
    # conversation history) and a short follow-up — the trailing chain
    # covers ~90% of turn 2's prompt, which is what sessions buy
    t2_len = turn1_len + max_new + follow_len
    per_req = -(-(t2_len + max_new) // block)
    # pool sized for slot concurrency ONLY — the parked working set
    # (every session's trailing chain) is deliberately a multiple of
    # it (~10x at the full sizing), so spill-off eviction MUST discard
    # conversation KV
    n_blocks = 1 + max_slots * per_req
    working = n_sessions * ((turn1_len + max_new) // block)
    c = TransformerConfig(vocab_size=vocab, num_layers=layers,
                          num_heads=8, d_model=d_model, d_ff=d_ff,
                          max_seq_len=t2_len + max_new,
                          dtype=jnp.float32)
    params = init_params(c, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    turn1 = [list(rng.integers(0, vocab, turn1_len))
             for _ in range(n_sessions)]
    turn2_user = [list(rng.integers(0, vocab, follow_len))
                  for _ in range(n_sessions)]

    def ttft(eng, rids):
        return [e["duration_s"]
                for r in rids
                for e in (eng.request_trace(r) or {"events": []})[
                    "events"] if e["event"] == "prefill"]

    def run(spill_on):
        eng = DecodeEngine(params, c, max_slots=max_slots,
                           paged=(n_blocks, block))
        if spill_on:
            eng.enable_kv_spill(host_capacity_blocks=4 * working)
            eng.enable_session_store()
        r1 = [eng.submit(np.asarray(t), max_new, session=f"s{i}")
              for i, t in enumerate(turn1)]
        while eng.pending:
            eng.step()
        outs1 = [eng.result(r) for r in r1]
        prompts2 = [np.asarray(turn1[i] + outs1[i] + turn2_user[i])
                    for i in range(n_sessions)]
        start = time.perf_counter()
        r2 = [eng.submit(p, max_new, session=f"s{i}")
              for i, p in enumerate(prompts2)]
        while eng.pending:
            eng.step()
        elapsed = time.perf_counter() - start
        outs2 = [eng.result(r) for r in r2]
        st = eng.stats
        assert st.get("requests_shed", 0) == 0, "a request was shed"
        return {"outs": outs1 + outs2, "ttft2": ttft(eng, r2),
                "tps2": n_sessions * max_new / elapsed, "stats": st}

    off = run(False)
    on = run(True)
    assert on["outs"] == off["outs"], \
        "spill-on outputs diverged from spill-off"
    kt = on["stats"]["kv_tiers"]
    assert kt["session"]["hits"] == n_sessions, \
        f"every turn-2 should resume its session: {kt['session']}"
    warm = percentile(on["ttft2"], 0.5)
    cold = percentile(off["ttft2"], 0.5)
    return {"metric": "kv_tiered_warm_ttft_ms",
            "value": round(warm * 1000, 3),
            "unit": "ms (turn-2 admission prefill, spill+sessions on)",
            "cold_ttft_ms": round(cold * 1000, 3),
            "warm_ttft_speedup": round(cold / max(warm, 1e-9), 2),
            "turn2_tokens_per_sec": round(on["tps2"], 1),
            "turn2_tokens_per_sec_off": round(off["tps2"], 1),
            "demotions_host": kt["host"]["demotions"],
            "promotions": kt.get("promotions", {}),
            "session_hits": kt["session"]["hits"],
            "session_blocks": kt["session"]["blocks"],
            "working_set_blocks": working,
            "pool_blocks": n_blocks - 1,
            "working_set_ratio": round(working / (n_blocks - 1), 2),
            "outputs_token_identical": True,
            "requests_shed": 0,
            "config": f"L{layers} d{d_model} ff{d_ff} V{vocab} f32 "
                      f"paged ({n_blocks}x{block}), {n_sessions} "
                      f"2-turn sessions: {turn1_len}-tok history + "
                      f"{follow_len}-tok follow-up, {max_new} new "
                      f"toks, {max_slots} slots, host spill + "
                      "in-process session store"}


def measure_speculative(smoke=False):
    """Speculative serving row: a decode-bound workload (short prompts,
    long generations) through one paged engine, speculative on vs off
    at EQUAL traffic. The draft is a 1-layer model sharing the target's
    trunk — the target's extra layers are down-scaled so the shared
    trunk dominates its behavior, a deterministic stand-in for a
    distilled draft (high-but-sub-1.0 acceptance without in-bench
    training; models/distill.py + its test own the "distillation
    raises acceptance" claim). Both engines drain identical traffic
    twice (pass 1 compiles and warms the cache, pass 2 is measured);
    outputs are asserted token-identical (greedy f32) across ALL THREE
    configurations — speculative off, speculative + prefix cache on,
    speculative + prefix cache off — which is simultaneously the
    speculative-exactness A/B and the cache on/off A/B the acceptance
    criteria name. The acceptance scalar is ``tokens_per_sec_ratio``
    (>= 1.5x on the dev box) with the measured acceptance rate
    reported alongside."""
    import jax

    import jax.numpy as jnp

    from elephas_tpu.models.transformer import (TransformerConfig,
                                                init_params)
    from elephas_tpu.serving_engine import DecodeEngine

    if smoke:
        # prompt_len > block: the chain walk has a full block to hit,
        # so the smoke also exercises the cached speculative admission
        layers, d_model, d_ff, vocab = 2, 64, 128, 500
        n_requests, prompt_len, max_new = 4, 20, 12
        heads = 4
    else:
        layers, d_model, d_ff, vocab = 4, 256, 1024, 8000
        n_requests, prompt_len, max_new = 12, 32, 48
        heads = 8
    gamma, block, max_slots, n_groups = 4, 16, 4, 2
    max_len = prompt_len + max_new + gamma
    c = TransformerConfig(vocab_size=vocab, num_layers=layers,
                          num_heads=heads, d_model=d_model, d_ff=d_ff,
                          max_seq_len=max_len, dtype=jnp.float32)
    dc = TransformerConfig(vocab_size=vocab, num_layers=1,
                           num_heads=heads, d_model=d_model, d_ff=d_ff,
                           max_seq_len=max_len, dtype=jnp.float32)
    params = init_params(c, jax.random.PRNGKey(0))
    # damp layers >= 1 where they re-enter the residual stream so the
    # shared first layer dominates: the 1-layer draft then agrees with
    # the target's argmax most of the time, like a distilled draft
    # would, while the target still pays all `layers` of compute
    for i in range(1, layers):
        layer = params[f"layer_{i}"]
        layer["attn"]["wo"] = layer["attn"]["wo"] * 0.02
        layer["mlp"]["w2"] = layer["mlp"]["w2"] * 0.02
        layer["mlp"]["b2"] = layer["mlp"]["b2"] * 0.02
    draft = {"embed": params["embed"], "layer_0": params["layer_0"],
             "final_ln": params["final_ln"]}
    # damping factor note: 0.02 keeps the extra layers' residual
    # contribution below the trunk's argmax margins for most positions
    # (~0.76 acceptance measured on the dev box) — the operating point
    # a distilled production draft sits at; the speedup model is
    # (1 + gamma*acc) tokens per (draft gamma+1 steps + one verify)
    rng = np.random.default_rng(0)
    group_heads = [list(rng.integers(0, vocab, prompt_len - 4))
                   for _ in range(n_groups)]
    prompts = [np.asarray(group_heads[i % n_groups]
                          + list(rng.integers(0, vocab, 4)))
               for i in range(n_requests)]
    per_req = -(-(prompt_len + max_new + gamma) // block)
    n_blocks = 1 + max_slots * per_req + n_groups * (prompt_len // block)

    def drain(eng):
        start = time.perf_counter()
        rids = [eng.submit(p, max_new) for p in prompts]
        while eng.pending:
            eng.step()
        outs = [eng.result(r) for r in rids]
        return outs, n_requests * max_new / (time.perf_counter() - start)

    results = {}
    configs = (
        ("off", dict()),
        ("spec", dict(draft_params=draft, draft_config=dc, gamma=gamma)),
        ("spec_nocache", dict(draft_params=draft, draft_config=dc,
                              gamma=gamma, prefix_cache=False)),
    )
    for label, kw in configs:
        eng = DecodeEngine(params, c, max_slots=max_slots,
                           paged=(n_blocks, block), **kw)
        drain(eng)                 # compile + warm the cache
        outs, tps = drain(eng)
        results[label] = {"outs": outs, "tps": tps, "stats": eng.stats}
    assert results["spec"]["outs"] == results["off"]["outs"], \
        "speculative outputs diverged from plain decoding"
    assert results["spec"]["outs"] == results["spec_nocache"]["outs"], \
        "prefix-cache-on speculative outputs diverged from cache-off"
    # --- adaptive-vs-fixed gamma under a draft-staleness sweep: the
    # draft's trunk is crushed to near-noise mid-run (the deterministic
    # stand-in for "re-distilled against a target several swaps ago"),
    # collapsing acceptance. The fixed engine keeps proposing gamma
    # tokens per round and throwing most away; the adaptive engine's
    # controller walks gamma to the floor within a few rounds and stops
    # paying for rejected drafts. A verify pass is exact at ANY depth,
    # so both must stay token-identical with the plain-decode outputs.
    stale_draft = jax.tree_util.tree_map(lambda a: a * 0.05, draft)

    def staleness_run(adaptive):
        eng = DecodeEngine(params, c, max_slots=max_slots,
                           paged=(n_blocks, block), draft_params=draft,
                           draft_config=dc, gamma=gamma,
                           adaptive_gamma=adaptive)
        drain(eng)                       # compile + warm, fresh draft
        eng.stage_draft_params(stale_draft, version=2)
        drain(eng)                       # adaptive: walk down + compile
        #                                  the visited depths' programs
        eng.stage_draft_params(stale_draft, version=3)
        #                                  ^ resets adaptive gamma to the
        #                                  ceiling: the measured pass
        #                                  includes the walk-down
        outs, tps = drain(eng)
        return {"outs": outs, "tps": tps, "stats": eng.stats}

    stale_fixed = staleness_run(False)
    stale_adaptive = staleness_run(True)
    assert stale_fixed["outs"] == results["off"]["outs"], \
        "stale-draft fixed-gamma outputs diverged"
    assert stale_adaptive["outs"] == results["off"]["outs"], \
        "stale-draft adaptive-gamma outputs diverged"
    assert stale_adaptive["stats"]["gamma"] < gamma, \
        "adaptive gamma did not move off the ceiling under staleness"
    on, off = results["spec"], results["off"]
    ks = on["stats"]["kv_cache"]
    return {"metric": "speculative_tokens_per_sec_ratio",
            "value": round(on["tps"] / off["tps"], 3),
            "unit": "x (speculative on / off, equal decode-bound "
                    "traffic, steady-state pass)",
            "tokens_per_sec": round(on["tps"], 1),
            "tokens_per_sec_off": round(off["tps"], 1),
            "tokens_per_sec_nocache": round(
                results["spec_nocache"]["tps"], 1),
            "draft_acceptance": round(on["stats"]["draft_acceptance"],
                                      3),
            "speculative_rounds": on["stats"]["speculative_rounds"],
            "tokens_per_step": round(on["stats"]["tokens_per_step"], 2),
            "tokens_per_step_off": round(
                off["stats"]["tokens_per_step"], 2),
            "cache_hits": ks["hits"],
            "outputs_token_identical": True,
            "stale_adaptive_vs_fixed": round(
                stale_adaptive["tps"] / stale_fixed["tps"], 3),
            "stale_tokens_per_sec_adaptive": round(
                stale_adaptive["tps"], 1),
            "stale_tokens_per_sec_fixed": round(stale_fixed["tps"], 1),
            "stale_gamma_end": stale_adaptive["stats"]["gamma"],
            "stale_acceptance": (
                None if stale_adaptive["stats"]["draft_acceptance"]
                is None
                else round(stale_adaptive["stats"]["draft_acceptance"],
                           3)),
            "config": f"target L{layers} d{d_model} ff{d_ff} V{vocab} "
                      f"f32 paged ({n_blocks}x{block}), draft L1 "
                      f"(shared trunk, extra layers x0.02), gamma "
                      f"{gamma}, {n_requests} reqs x {prompt_len}-tok "
                      f"prompts / {max_new} new toks, {max_slots} "
                      "slots, prefix cache on (A/B'd vs off), "
                      "steady-state pass measured; staleness sweep: "
                      "draft trunk x0.05 staged mid-run, adaptive "
                      "(floor 1) vs fixed gamma at equal traffic"}


def measure_adaptive_sched(smoke=False):
    """Adaptive-scheduling row: a long-prompt burst admitted OVER live
    decodes, chunked-prefill interleaving on vs off at equal traffic.
    Run-to-completion admission stalls every in-flight decode for the
    whole chunk loop — the stall lands squarely in the live requests'
    inter-token p99. Interleaving feeds the same chunks between decode
    steps under the profiler-derived budget, so live inter-token
    latency stays ~flat and the burst's TTFT degrades gracefully
    instead. Both runs drain identical traffic twice (pass 1 compiles,
    pass 2 measured) and outputs are asserted token-identical — the
    scheduler moves WHEN chunks run, never what they compute. The
    acceptance scalar is the live-decode inter-token p99 ratio
    (off/on, >= 3x on the dev box)."""
    import jax

    import jax.numpy as jnp

    from elephas_tpu.models.transformer import (TransformerConfig,
                                                init_params)
    from elephas_tpu.serving_engine import DecodeEngine

    if smoke:
        layers, d_model, d_ff, vocab, heads = 2, 64, 128, 500, 4
        live_n, live_prompt, live_new = 2, 8, 24
        burst_n, burst_prompt, burst_new, chunk = 1, 64, 4, 8
    else:
        layers, d_model, d_ff, vocab, heads = 4, 256, 1024, 8000, 8
        live_n, live_prompt, live_new = 4, 16, 64
        burst_n, burst_prompt, burst_new, chunk = 2, 384, 16, 32
    block = 16
    slots = live_n + burst_n
    max_len = burst_prompt + burst_new + block
    per_req = -(-max_len // block)
    n_blocks = 1 + slots * per_req
    c = TransformerConfig(vocab_size=vocab, num_layers=layers,
                          num_heads=heads, d_model=d_model, d_ff=d_ff,
                          max_seq_len=max_len, dtype=jnp.float32)
    params = init_params(c, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    live_prompts = [rng.integers(0, vocab, live_prompt)
                    for _ in range(live_n)]
    burst_prompts = [rng.integers(0, vocab, burst_prompt)
                     for _ in range(burst_n)]

    def run(eng):
        """One traffic pass: live decodes reach steady state, the long
        burst lands on top, per-step host stamps collect the live
        requests' inter-token gaps and the burst's TTFT."""
        live = [eng.submit(p, live_new) for p in live_prompts]
        last: dict = {}
        for _ in range(4):
            out = eng.step()
            now = time.perf_counter()
            # stamp (don't measure) the pre-burst steps: the FIRST
            # post-burst gap — the one the admission stall lands in —
            # must have a predecessor stamp to measure against
            for r in live:
                if out.get(r):
                    last[r] = now
        t_burst = time.perf_counter()
        burst = [eng.submit(p, burst_new) for p in burst_prompts]
        gaps: list = []
        ttfts: list = []
        while eng.pending:
            out = eng.step()
            now = time.perf_counter()
            for r in live:
                if out.get(r):
                    if r in last:
                        gaps.append(now - last[r])
                    last[r] = now
            for r in burst:
                if out.get(r) and r not in last:
                    ttfts.append(now - t_burst)
                    last[r] = now
        outs = [list(eng.result(r)) for r in live + burst]
        return outs, gaps, ttfts

    results = {}
    for label, interleave in (("off", False), ("on", True)):
        eng = DecodeEngine(params, c, max_slots=slots,
                           paged=(n_blocks, block), prefill_chunk=chunk,
                           prefix_cache=False,
                           interleave_prefill=interleave)
        run(eng)                              # compile pass
        outs, gaps, ttfts = run(eng)          # measured pass
        results[label] = {
            "outs": outs,
            "p99": float(np.quantile(gaps, 0.99)),
            "ttft": float(np.mean(ttfts)),
            "decode_util": eng.profiler.utilization()["decode"],
            "chunks": eng.stats.get("prefill_chunks_interleaved", 0)}
    on, off = results["on"], results["off"]
    assert on["outs"] == off["outs"], \
        "interleaved outputs diverged from run-to-completion"
    assert on["chunks"] > 0, "interleaving scheduler never engaged"
    return {"metric": "adaptive_sched_inter_token_p99_ratio",
            "value": round(off["p99"] / on["p99"], 2),
            "unit": "x (live-decode inter-token p99, interleave "
                    "off / on, equal traffic)",
            "inter_token_p99_ms": round(on["p99"] * 1e3, 3),
            "inter_token_p99_ms_off": round(off["p99"] * 1e3, 3),
            "burst_ttft_ms": round(on["ttft"] * 1e3, 1),
            "burst_ttft_ms_off": round(off["ttft"] * 1e3, 1),
            "decode_utilization": round(on["decode_util"], 3),
            "decode_utilization_off": round(off["decode_util"], 3),
            "chunks_interleaved": int(on["chunks"]),
            "outputs_token_identical": True,
            "config": f"L{layers} d{d_model} ff{d_ff} V{vocab} f32 "
                      f"paged ({n_blocks}x{block}), {live_n} live reqs "
                      f"x {live_prompt}-tok prompts / {live_new} new "
                      f"toks + {burst_n} burst reqs x {burst_prompt}-"
                      f"tok prompts, prefill_chunk {chunk}, "
                      "profiler-budgeted interleave vs "
                      "run-to-completion, steady-state pass measured"}


def measure_tenant_qos(smoke=False):
    """Multi-tenant QoS row: a flooding heavy tenant (long prompts,
    long decodes, backlog kept topped up past its quota) vs a light
    interactive tenant (short prompts, one request every few steps)
    through ONE paged engine, QoS on vs off, plus the light tenant's
    solo baseline. The isolation claim measured: with QoS on (weights
    + per-tenant quota + priority preemption) the light tenant's p99
    stays within 2x of its solo baseline and it sheds NOTHING while
    under quota — with QoS off (plain FIFO + global bounds only) the
    same flood starves it. Both workload passes run twice per engine
    (pass 1 compiles prefill/gather/extend shapes; pass 2 is the
    steady state measured — the prefix_cache row's pattern)."""
    import jax
    import jax.numpy as jnp

    from elephas_tpu.models.transformer import (TransformerConfig,
                                                init_params)
    from elephas_tpu.obs import percentile
    from elephas_tpu.serving_engine import DecodeEngine, QueueFullError
    from elephas_tpu.serving_qos import TenantQoS

    if smoke:
        dims = dict(vocab_size=300, num_layers=2, num_heads=4,
                    d_model=32, d_ff=64)
        n_light, light_every = 5, 4
        heavy_len, heavy_new, light_len, light_new = 24, 12, 6, 4
        block, slots, heavy_extra = 8, 2, 3
    else:
        dims = dict(vocab_size=2000, num_layers=2, num_heads=8,
                    d_model=128, d_ff=512)
        n_light, light_every = 16, 8
        heavy_len, heavy_new, light_len, light_new = 48, 32, 8, 8
        block, slots, heavy_extra = 16, 4, 6
    max_seq = heavy_len + heavy_new
    # f32: preempt-and-resume must stay token-identical (the engine's
    # cross-program rounding caveat) — and the row's latency claim
    # must not ride on outputs quietly diverging
    c = TransformerConfig(**dims, max_seq_len=max_seq,
                          dtype=jnp.float32)
    params = init_params(c, jax.random.PRNGKey(0))
    per_req = -(-max_seq // block)
    # pool exactly covers full slot occupancy: a light admission under
    # heavy flood MUST preempt (slot + block pressure) — the scenario
    # this row exists to measure
    n_blocks = 1 + slots * per_req
    heavy_quota = 4 * heavy_len           # ~4 queued heavy requests
    heavy_target = slots + heavy_extra    # flood pressure past quota
    qos = TenantQoS(tenants={
        "heavy": {"weight": 1.0, "priority": "low",
                  "max_queued_tokens": heavy_quota},
        "light": {"weight": 4.0, "priority": "high"}})
    rng = np.random.default_rng(0)

    def run_pass(eng, include_heavy):
        lat, submit_t = [], {}
        sheds = {"heavy": 0, "light": 0}
        hv_rids, issued, steps = [], 0, 0
        max_steps = n_light * light_every * 24
        # ramp: let the heavy flood reach steady state (slots full,
        # backlog at quota) before the first light request — each pass
        # starts with freed slots, and a light arriving behind that
        # cold burst of FULL heavy prefills measures pass startup, not
        # the steady-state isolation this row claims
        ramp = 2 * light_every
        while len(submit_t) + len(lat) + sheds["light"] < n_light \
                or submit_t:
            if steps >= max_steps + ramp:
                break
            # light FIRST: in the FIFO baseline it competes for queue
            # space on equal terms instead of always finding the queue
            # freshly topped up
            if (steps >= ramp and (steps - ramp) % light_every == 0
                    and issued < n_light):
                issued += 1
                t0 = time.perf_counter()
                try:
                    r = eng.submit(rng.integers(0, c.vocab_size,
                                                light_len),
                                   light_new, tenant="light",
                                   admit=False)
                    submit_t[r] = t0
                except QueueFullError:
                    sheds["light"] += 1
            if include_heavy:
                done = [r for r in hv_rids
                        if eng.result(r) is not None]
                for r in done:
                    hv_rids.remove(r)
                while len(hv_rids) < heavy_target:
                    try:
                        hv_rids.append(eng.submit(
                            rng.integers(0, c.vocab_size, heavy_len),
                            heavy_new, tenant="heavy", admit=False))
                    except QueueFullError:
                        sheds["heavy"] += 1
                        break
            eng.step()
            steps += 1
            for r in list(submit_t):
                if eng.result(r) is not None:
                    lat.append(time.perf_counter() - submit_t.pop(r))
        for r in hv_rids:
            eng.cancel(r)
        while eng.pending:
            eng.step()
        return lat, sheds

    def measure(qos_cfg, include_heavy):
        from elephas_tpu.obs import percentile as pct

        eng = DecodeEngine(params, c, max_slots=slots,
                           paged=(n_blocks, block),
                           prefill_chunk=block, max_queue=12,
                           qos=qos_cfg)
        run_pass(eng, include_heavy)        # compile + warm
        # median-of-3 steady passes (the disagg row's pattern): with
        # ~n_light samples per pass the p99 IS the worst sample, so
        # one GC/compile straggler must not define the row
        rounds = 1 if smoke else 3
        passes = [run_pass(eng, include_heavy) for _ in range(rounds)]
        p99s = sorted(pct(lat, 0.99) if lat else float("inf")
                      for lat, _ in passes)
        lat = [x for la, _ in passes for x in la]
        sheds = {k: sum(s[k] for _, s in passes)
                 for k in ("heavy", "light")}
        stats = eng.stats
        return {"lat": lat, "p99": p99s[len(p99s) // 2],
                "sheds": sheds,
                "preemptions": stats.get("preemptions", 0)}

    solo = measure(qos, include_heavy=False)
    on = measure(qos, include_heavy=True)
    off = measure(None, include_heavy=True)

    def p(lat, q):
        return round(percentile(lat, q) * 1000, 2) if lat else None

    def med_p99(res):
        v = res["p99"]
        return None if v == float("inf") else round(v * 1000, 2)

    solo_p99, on_p99, off_p99 = (med_p99(solo), med_p99(on),
                                 med_p99(off))
    within_2x = (on_p99 is not None and solo_p99 is not None
                 and on_p99 <= 2.0 * solo_p99)
    return {"metric": "tenant_qos_light_p99_ms",
            "value": on_p99,
            "unit": "ms (light-tenant p99, heavy flood, QoS on)",
            "light_p99_ms_solo": solo_p99,
            "light_p99_ms_qos_off": off_p99,
            "light_p50_ms_qos_on": p(on["lat"], 0.5),
            "light_p50_ms_solo": p(solo["lat"], 0.5),
            "light_p99_vs_solo": (None if not (on_p99 and solo_p99)
                                  else round(on_p99 / solo_p99, 2)),
            "light_p99_off_vs_solo": (
                None if not (off_p99 and solo_p99)
                else round(off_p99 / solo_p99, 2)),
            "light_completed_qos_on": len(on["lat"]),
            "light_completed_qos_off": len(off["lat"]),
            "light_sheds_qos_on": on["sheds"]["light"],
            "light_sheds_qos_off": off["sheds"]["light"],
            "heavy_sheds_qos_on": on["sheds"]["heavy"],
            "preemptions_qos_on": on["preemptions"],
            "light_p99_within_2x_solo": within_2x,
            "config": (f"L{c.num_layers} d{c.d_model} ff{c.d_ff} "
                       f"V{c.vocab_size} f32 paged ({n_blocks}x{block})"
                       f", {slots} slots, heavy={heavy_len}tok/"
                       f"{heavy_new}new flood topped to {heavy_target} "
                       f"(quota {heavy_quota} queued tokens), light="
                       f"{light_len}tok/{light_new}new every "
                       f"{light_every} steps x{n_light}; QoS = "
                       "weights 1:4, heavy low / light high priority, "
                       "preemption on; p99 = median of 3 steady "
                       "passes (warm pass compiles first)")}


def measure_slo_plane(smoke=False):
    """SLO-plane row: the observability layer's own cost and efficacy.
    Three claims measured: (1) the engine-loop continuous profiler
    costs <=2% tokens/s — verdict from the DETERMINISTIC form
    (per-iteration instrumentation cost, micro-timed, over this run's
    median step latency; ~10-20us vs a >=1ms step), with the
    interleaved on/off tokens/s A/B reported as corroboration (CPU
    step jitter is +-3-5% over seconds, wider than the effect, so the
    wall-clock ratio alone cannot carry the verdict); (2) the TTFT /
    inter-token decomposition is populated (p50/p95 reported, plus the
    loop-utilization split and jit-compile count off the same run);
    (3) a forced latency regression drives the TTFT burn rate over
    threshold — exactly one ``slo.burn_rate_exceeded`` fires — and the
    alert recovers once the regression clears (the tracker's clock is
    injected, so the window arithmetic is deterministic; the TTFT
    samples are real)."""
    import jax

    from elephas_tpu.models.transformer import (TransformerConfig,
                                                init_params)
    from elephas_tpu.obs import SLOObjective, SLOTracker, default_event_log
    from elephas_tpu.serving_engine import DecodeEngine

    if smoke:
        dims = dict(vocab_size=300, num_layers=2, num_heads=4,
                    d_model=32, d_ff=64)
        n_requests, prompt_len, max_new, slots = 24, 8, 32, 2
    else:
        dims = dict(vocab_size=2000, num_layers=2, num_heads=8,
                    d_model=128, d_ff=512)
        n_requests, prompt_len, max_new, slots = 24, 16, 48, 4
    c = TransformerConfig(**dims, max_seq_len=prompt_len + max_new)
    params = init_params(c, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [np.asarray(rng.integers(0, c.vocab_size, prompt_len))
               for _ in range(n_requests)]
    total = n_requests * max_new

    def drain_tps(eng):
        start = time.perf_counter()
        eng.run(prompts, max_new)
        return total / (time.perf_counter() - start)

    off = DecodeEngine(params, c, max_slots=slots, profiler=False)
    on = DecodeEngine(params, c, max_slots=slots)
    for eng in (off, on):
        # warmup() first: admission compiles must land neither in the
        # measured drains nor in the TTFT quantile window this row
        # reports (a compile storm is the JIT series' story, not the
        # steady-state decomposition's)
        eng.warmup(prompt_lengths=[prompt_len])
        drain_tps(eng)                      # shape warm
    # INTERLEAVED rounds (off, on, off, on, ...): each round's pair
    # runs back to back so the per-round ratio cancels process-level
    # drift, and the median rejects scheduler-noise rounds. Even so,
    # CPU step time wanders ±3-5% over seconds (XLA/scheduler jitter —
    # an off-vs-off null shows the same spread), which SWAMPS a ~1%
    # effect: the ratio is reported as corroboration, while the
    # overhead VERDICT uses the deterministic form below — the
    # per-iteration instrumentation sequence micro-timed in isolation,
    # as a fraction of the run's own median step latency.
    rounds = 9
    samples = {id(off): [], id(on): []}
    for _ in range(rounds):
        for eng in (off, on):
            samples[id(eng)].append(drain_tps(eng))
    per_round = sorted(b / a for a, b in zip(samples[id(off)],
                                             samples[id(on)]))
    ratio = per_round[rounds // 2]
    off_tps = sorted(samples[id(off)])[rounds // 2]
    on_tps = sorted(samples[id(on)])[rounds // 2]
    stats = on.stats
    loop = stats["loop"]

    # deterministic overhead: cost of one iteration's worth of
    # instrumentation (tick + the steady-state decode/emit sections)
    # over the median engine step this very run measured
    from elephas_tpu.obs import LoopProfiler, MetricsRegistry

    mprof = LoopProfiler(MetricsRegistry(), track_jit=False)
    mprof.tick()
    m = 2000
    t0 = time.perf_counter()
    for _ in range(m):
        mprof.tick()
        with mprof.section("decode"):
            pass
        with mprof.section("emit"):
            pass
    cost_s = (time.perf_counter() - t0) / m
    step_p50 = on.registry.get(
        "serving_step_latency_seconds").labels().quantile(0.5)
    overhead_frac = cost_s / step_p50 if step_p50 else 0.0

    # forced burn-rate alert on the profiled engine's own registry:
    # clean baseline -> a slow-step regression breaches the TTFT bound
    # -> fires once -> clearing the regression recovers it
    clk = [0.0]
    tracker = SLOTracker(
        [SLOObjective.latency("ttft_p95", "serving_ttft_seconds",
                              bound_s=max(0.05, 4 * stats["ttft_p95_s"]),
                              target=0.5)],
        on.registry, fast_window_s=10.0, slow_window_s=30.0,
        burn_threshold=1.5, clock=lambda: clk[0], name="slo_bench")
    tracker.evaluate()                       # baseline sample

    class _SlowStep:                         # the regression injector
        def __init__(self, eng, delay_s):
            self.eng, self.delay_s = eng, delay_s

        def run(self, reqs, new):
            # admit=False: admission (and the first token) happens in
            # step(), AFTER the injected stall — TTFT breaches
            rids = [self.eng.submit(p, new, admit=False) for p in reqs]
            while self.eng.pending:
                time.sleep(self.delay_s)
                self.eng.step()
            return [self.eng.result(r) for r in rids]

    bound = tracker.objectives[0].detail["bound_s"]
    _SlowStep(on, 2 * bound).run(prompts[:slots], 2)
    clk[0] += 11.0
    fired = tracker.evaluate()["objectives"]["ttft_p95"]["state"]
    on.run(prompts, max_new)                 # regression cleared: fast,
    clk[0] += 11.0                           # breaching samples age out
    recovered = tracker.evaluate()["objectives"]["ttft_p95"]["state"]
    alerts = [e for e in default_event_log().recent(
        "slo.burn_rate_exceeded") if e.get("source") == "slo_bench"]
    # the deterministic invariants HARD-ASSERT (the speculative row's
    # token-identity convention): the CI smoke step exists so this row
    # cannot rot, which requires a broken alert pipeline or a blown
    # overhead budget to FAIL the step, not print a sad JSON field
    assert fired == "firing", \
        f"forced TTFT regression did not fire the alert (state={fired})"
    assert recovered == "ok", \
        f"alert did not recover after the regression cleared " \
        f"(state={recovered})"
    assert len(alerts) == 1, \
        f"expected exactly one slo.burn_rate_exceeded, got {len(alerts)}"
    assert overhead_frac <= 0.02, \
        f"profiler instrumentation cost {cost_s * 1e6:.1f}us/iter is " \
        f"{overhead_frac:.1%} of the {step_p50 * 1e3:.2f}ms median " \
        f"step (budget 2%)"
    return {"metric": "slo_plane_profiler_overhead_frac",
            "value": round(overhead_frac, 5),
            "unit": ("instrumentation cost per iteration / median "
                     "step wall time (claim <= 0.02)"),
            "profiler_overhead_ok": overhead_frac <= 0.02,
            "profiler_cost_us_per_iter": round(cost_s * 1e6, 2),
            "step_p50_ms": round(step_p50 * 1e3, 3),
            "tps_ratio_on_off": round(ratio, 4),
            "tokens_per_sec_profiler_off": round(off_tps, 1),
            "tokens_per_sec_profiler_on": round(on_tps, 1),
            "ttft_p50_s": stats.get("ttft_p50_s"),
            "ttft_p95_s": stats.get("ttft_p95_s"),
            "inter_token_p50_s": stats.get("inter_token_p50_s"),
            "loop_utilization": loop["utilization"],
            "jit_compiles": loop["jit_compiles"],
            "alert_fired": fired == "firing",
            "alert_recovered": recovered == "ok",
            "alerts_emitted": len(alerts),
            "slo_plane_ok": (fired == "firing" and recovered == "ok"
                             and len(alerts) == 1),
            "config": (f"L{c.num_layers} d{c.d_model} ff{c.d_ff} "
                       f"V{c.vocab_size} {slots} slots, {n_requests} "
                       f"reqs x {prompt_len}tok/{max_new}new, greedy; "
                       "tps ratio = median of 9 per-round paired drains; tps per "
                       "engine; alert = TTFT-p95 objective, injected "
                       "slow-step regression, fake-clock windows "
                       "(fast 10s / slow 30s, threshold 1.5)")}


def measure_trace_plane(smoke=False):
    """Span-tree tracing row: the distributed tracing plane's own cost.
    Two claims measured: (1) full span recording — per-request root
    context, hierarchical spans through admission/prefill/decode, the
    retention decision at retirement — costs <=2% of a request's wall
    time. The VERDICT uses the deterministic form (the per-request
    span sequence micro-timed in isolation over this run's median
    request latency; a few tens of us vs multi-ms requests), with the
    interleaved on/off tokens/s A/B reported as corroboration (CPU
    step jitter swamps a sub-1% effect — same convention as the
    slo_plane row). (2) tail-based retention actually engages under
    the traced run: every finished trace reached a retention decision
    and the bounded store held on to at most its configured rings."""
    import jax

    from elephas_tpu.models.transformer import (TransformerConfig,
                                                init_params)
    from elephas_tpu.obs import (default_span_store, new_root,
                                 set_span_plane_enabled, use_context)
    from elephas_tpu.serving_engine import DecodeEngine

    if smoke:
        dims = dict(vocab_size=300, num_layers=2, num_heads=4,
                    d_model=32, d_ff=64)
        n_requests, prompt_len, max_new, slots = 16, 8, 24, 2
    else:
        dims = dict(vocab_size=2000, num_layers=2, num_heads=8,
                    d_model=128, d_ff=512)
        n_requests, prompt_len, max_new, slots = 24, 16, 48, 4
    c = TransformerConfig(**dims, max_seq_len=prompt_len + max_new)
    params = init_params(c, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [np.asarray(rng.integers(0, c.vocab_size, prompt_len))
               for _ in range(n_requests)]
    total = n_requests * max_new
    store = default_span_store()
    store.clear()

    def drive(eng, traced):
        set_span_plane_enabled(traced)
        start = time.perf_counter()
        rids = []
        for p in prompts:
            if traced:
                with use_context(new_root()):
                    rids.append(eng.submit(p, max_new))
            else:
                rids.append(eng.submit(p, max_new))
        while eng.pending:
            eng.step()
        dt = time.perf_counter() - start
        for r in rids:
            eng.result(r)
        return total / dt

    try:
        off = DecodeEngine(params, c, max_slots=slots)
        on = DecodeEngine(params, c, max_slots=slots)
        for eng, traced in ((off, False), (on, True)):
            eng.warmup(prompt_lengths=[prompt_len])
            drive(eng, traced)                   # shape warm
        # interleaved rounds, median per-round ratio (drift cancels)
        rounds = 9
        samples = {id(off): [], id(on): []}
        for _ in range(rounds):
            samples[id(off)].append(drive(off, False))
            samples[id(on)].append(drive(on, True))
        per_round = sorted(b / a for a, b in zip(samples[id(off)],
                                                 samples[id(on)]))
        ratio = per_round[rounds // 2]
        off_tps = sorted(samples[id(off)])[rounds // 2]
        on_tps = sorted(samples[id(on)])[rounds // 2]

        # deterministic overhead: one request's worth of span-plane
        # work micro-timed — root mint, the engine's live + retro
        # spans, and the retention decision at retirement
        from elephas_tpu.obs import SpanStore, add_span, start_span

        set_span_plane_enabled(True)
        mstore = SpanStore()
        m = 2000
        t0 = time.perf_counter()
        for i in range(m):
            ctx = new_root()
            with use_context(ctx):
                with start_span("bench.prefill", stage="prefill",
                                store=mstore):
                    pass
                add_span("bench.admission_wait", 0.0, 1e-4,
                         stage="admission_wait", store=mstore)
                add_span("bench.decode", 0.0, 1e-3, stage="decode",
                         store=mstore)
                add_span("bench.request", 0.0, 2e-3, ctx=ctx,
                         span_id=ctx.span_id, store=mstore)
            mstore.finish(ctx.trace_id, latency_s=2e-3, ttft_s=1e-3)
        cost_s = (time.perf_counter() - t0) / m
        req_s = (n_requests * max_new / on_tps) / n_requests
        overhead_frac = cost_s / req_s if req_s else 0.0

        st = store.stats()
        lat_on = on.registry.get(
            "serving_request_latency_seconds").labels()
        lat_off = off.registry.get(
            "serving_request_latency_seconds").labels()
        # the CI smoke step hard-asserts (slo_plane's convention): a
        # blown overhead budget or a dead retention pipeline must FAIL
        assert overhead_frac <= 0.02, \
            f"span-plane cost {cost_s * 1e6:.1f}us/request is " \
            f"{overhead_frac:.1%} of the {req_s * 1e3:.2f}ms median " \
            f"request (budget 2%)"
        traced_n = (rounds + 1) * n_requests
        assert st["finished_total"] >= traced_n, \
            f"retention decided {st['finished_total']} traces, " \
            f"expected >= {traced_n}"
        assert st["retained_traces"] <= store.retain_max
        return {"metric": "trace_plane_overhead_frac",
                "value": round(overhead_frac, 5),
                "unit": ("span-plane cost per request / median request "
                         "wall time (claim <= 0.02)"),
                "trace_plane_ok": overhead_frac <= 0.02,
                "span_cost_us_per_request": round(cost_s * 1e6, 2),
                "request_wall_ms": round(req_s * 1e3, 3),
                "tps_ratio_on_off": round(ratio, 4),
                "tokens_per_sec_tracing_off": round(off_tps, 1),
                "tokens_per_sec_tracing_on": round(on_tps, 1),
                "p99_request_latency_off_s": lat_off.quantile(0.99),
                "p99_request_latency_on_s": lat_on.quantile(0.99),
                "traces_finished": st["finished_total"],
                "traces_retained": st["retained_traces"],
                "traces_dropped": st["dropped_total"],
                "config": (f"L{c.num_layers} d{c.d_model} ff{c.d_ff} "
                           f"V{c.vocab_size} {slots} slots, "
                           f"{n_requests} reqs x {prompt_len}tok/"
                           f"{max_new}new, greedy; tps ratio = median "
                           "of 9 per-round paired drains; verdict = "
                           "micro-timed span sequence (root + 4 spans "
                           "+ retention decision) over the traced "
                           "run's median request wall time")}
    finally:
        set_span_plane_enabled(True)
        store.clear()


def _stage_percentiles(recorder, n: int) -> dict:
    """Queue-wait and prefill p50/p99 derived from the newest ``n``
    flight-recorder timelines — the BENCH record's per-stage latency
    companion to the end-to-end tokens/sec scalar."""
    from elephas_tpu.obs import percentile

    waits, prefills = [], []
    for t in recorder.recent(limit=n):
        for e in t["events"]:
            if (e["event"] == "admitted"
                    and e.get("queue_wait_s") is not None):
                waits.append(e["queue_wait_s"])
            elif (e["event"] == "prefill"
                    and e.get("duration_s") is not None):
                prefills.append(e["duration_s"])
    out = {}
    if waits:
        out["queue_wait_p50_s"] = round(percentile(waits, 0.5), 6)
        out["queue_wait_p99_s"] = round(percentile(waits, 0.99), 6)
    if prefills:
        out["prefill_p50_s"] = round(percentile(prefills, 0.5), 6)
        out["prefill_p99_s"] = round(percentile(prefills, 0.99), 6)
    return out


def measure_ssm(seqs=(1024, 4096, 8192), batch_tokens=8192,
                decode_batch=8, decode_new=128, vocab_size=32000,
                num_layers=8, d_model=1024, d_inner=2048):
    """Selective-SSM row: training-step time scales LINEARLY with
    sequence length (one associative scan per layer, no O(T^2) score
    matrix) — measured against the transformer flash row's configs —
    plus O(1)-state decode throughput. Parameter count per layer is
    comparable to the flagship transformer layer (10 D^2 vs 12 D^2)."""
    import jax
    import jax.numpy as jnp
    import optax

    from elephas_tpu.models.ssm import (SSMConfig, init_ssm_params,
                                        make_ssm_train_step, ssm_generate)

    c = SSMConfig(vocab_size=vocab_size, num_layers=num_layers,
                  d_model=d_model, d_inner=d_inner)
    params = init_ssm_params(c, jax.random.PRNGKey(0))
    tx = optax.adamw(1e-4)
    rows = []
    for seq in seqs:
        batch = max(1, batch_tokens // seq)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq),
                                    0, c.vocab_size)
        step = make_ssm_train_step(c, tx)
        p = jax.tree_util.tree_map(jnp.copy, params)
        p, opt, _ = step(p, tx.init(p), tokens)          # compile
        jax.block_until_ready(p)
        start = time.perf_counter()
        p, opt, loss = step(p, opt, tokens)
        jax.block_until_ready(p)
        dt = time.perf_counter() - start
        rows.append({"seq": seq, "batch": batch,
                     "train_ms": round(dt * 1000, 2),
                     "train_tokens_per_sec": round(batch * seq / dt, 1)})
    prompt = jax.random.randint(jax.random.PRNGKey(2), (decode_batch, 16),
                                0, c.vocab_size)
    np.asarray(ssm_generate(params, prompt, decode_new, c))  # compile
    start = time.perf_counter()
    np.asarray(ssm_generate(params, prompt, decode_new, c))
    decode_tps = decode_batch * decode_new / (time.perf_counter() - start)
    return {"metric": "ssm_train_tokens_per_sec",
            "value": rows[0]["train_tokens_per_sec"],
            "unit": "tokens/sec", "rows": rows,
            "decode_tokens_per_sec": round(decode_tps, 1),
            "config": "selective SSM L8 d1024 d_inner2048 V32000 adamw; "
                      "train = fwd+bwd+update, fixed ~8k tokens/step; "
                      "decode = batch 8 x 128 new tokens, O(1) state"}


def measure_mfu(steps: int = 10, batch: int = 8, seq: int = 1024,
                base_overrides=None):
    """MFU ceiling decomposition for the headline LM config (L8 d1024
    ff4096 h16 seq1024 batch8 bf16): where do the non-MXU cycles go, and
    what would close the 0.43 -> 0.48 gap?

    Components:
    - ``matmul_roofline``: the model's exact matmul chain (qkv/o, mlp,
      head) in bf16, nothing else — the achievable ceiling for THIS
      shape mix on THIS chip. If the end-to-end MFU is close to this,
      ~0.43 is the config ceiling, not framework overhead.
    - block-size sweep for the flash kernel at seq 1024
    - rmsnorm vs layernorm (the norm cost share)
    - sgd vs adamw (the optimizer update's HBM share)
    - forward-only vs train step (the backward share)
    """
    import jax
    import jax.numpy as jnp
    import optax

    from elephas_tpu.models.transformer import (TransformerConfig,
                                                init_params,
                                                make_train_step)

    base = dict(vocab_size=32000, num_layers=8, num_heads=16,
                d_model=1024, d_ff=4096, max_seq_len=seq,
                attention_impl="flash")
    base.update(base_overrides or {})  # tiny dims for the CPU smoke test
    peak = _peak_tflops()

    def flops_per_token(c):
        p_matmul = (c.num_layers * (4 * c.d_model * c.d_model
                                    + 2 * c.d_model * c.d_ff)
                    + c.d_model * c.vocab_size)
        attn = 2 * 2 * (seq / 2) * c.d_model
        return 3 * (2 * p_matmul + c.num_layers * attn)

    def time_train(c, tx):
        params = init_params(c, jax.random.PRNGKey(0))
        opt_state = tx.init(params)
        step = make_train_step(c, tx)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq),
                                    0, c.vocab_size)
        params, opt_state, loss = step(params, opt_state, tokens)
        float(loss)
        start = time.perf_counter()
        for _ in range(steps):
            params, opt_state, loss = step(params, opt_state, tokens)
        float(loss)
        return batch * seq * steps / (time.perf_counter() - start)

    # 1) matmul roofline: the model's own shape mix, pure chained matmuls
    c0 = TransformerConfig(**base)
    tok = batch * seq
    key = jax.random.PRNGKey(2)
    shapes = []
    for _ in range(c0.num_layers):
        shapes += [(c0.d_model, c0.d_model)] * 4
        shapes += [(c0.d_model, c0.d_ff), (c0.d_ff, c0.d_model)]
    shapes.append((c0.d_model, c0.vocab_size))
    ws = [jax.random.normal(jax.random.fold_in(key, i), s, jnp.bfloat16)
          * 0.01 for i, s in enumerate(shapes)]
    a0 = jax.random.normal(key, (tok, c0.d_model), jnp.bfloat16)

    @jax.jit
    def chain(a, ws):
        acc = jnp.zeros((), jnp.float32)
        h = a
        for i, w in enumerate(ws):
            y = h @ w
            if i == len(ws) - 1:
                # the head has no successor: a sliced read would let XLA
                # sink the slice into the dot and skip ~25% of the
                # counted FLOPs — sum the WHOLE product to keep it live
                acc = acc + jnp.sum(y.astype(jnp.float32))
            else:
                # successors consume y in full; a tiny read suffices
                acc = acc + jnp.sum(y[0, :8].astype(jnp.float32))
                h = y
        return acc

    float(chain(a0, ws))
    start = time.perf_counter()
    reps = 3 * steps
    for _ in range(reps):
        float_val = chain(a0, ws)
    jax.block_until_ready(float_val)
    elapsed = time.perf_counter() - start
    matmul_flops = 2 * tok * sum(m * n for m, n in shapes)
    roofline_tflops = matmul_flops * reps / elapsed / 1e12
    roofline_util = roofline_tflops / peak

    # 2) the headline step + levers
    adamw = optax.adamw(3e-4)
    tps_base = time_train(c0, adamw)
    mfu_base = flops_per_token(c0) * tps_base / (peak * 1e12)
    sweep = {}
    for bq, bk in ((512, 512), (512, 1024)):
        c = TransformerConfig(**base, flash_block_q=bq, flash_block_k=bk)
        sweep[f"{bq}x{bk}"] = round(time_train(c, adamw), 1)
    tps_rms = time_train(TransformerConfig(**base, norm="rmsnorm"), adamw)
    tps_sgd = time_train(c0, optax.sgd(3e-4))
    # bf16 first moment: halves one of the optimizer's param-sized
    # HBM streams (the lever AdamW(mu_dtype='bfloat16') exposes)
    tps_mu16 = time_train(c0, optax.adamw(3e-4, mu_dtype=jnp.bfloat16))

    # 3) forward-only share
    from elephas_tpu.models.transformer import forward, next_token_loss

    params = init_params(c0, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0,
                                c0.vocab_size)

    @jax.jit
    def fwd_loss(p, t):
        return next_token_loss(forward(p, t, c0), t)

    float(fwd_loss(params, tokens))
    start = time.perf_counter()
    for _ in range(steps):
        loss = fwd_loss(params, tokens)
    float(loss)
    tps_fwd = batch * seq * steps / (time.perf_counter() - start)

    best_tps = max([tps_base, tps_rms, tps_mu16] + list(sweep.values()))
    return {"metric": "transformer_mfu_ablation",
            "value": round(mfu_base, 4), "unit": "MFU (headline step)",
            "tokens_per_sec": round(tps_base, 1),
            "matmul_roofline_tflops": round(roofline_tflops, 1),
            "matmul_roofline_util": round(roofline_util, 4),
            "mfu_vs_roofline": round(mfu_base / max(roofline_util, 1e-9),
                                     4),
            "block_sweep_tokens_per_sec": sweep,
            "rmsnorm_tokens_per_sec": round(tps_rms, 1),
            "sgd_tokens_per_sec": round(tps_sgd, 1),
            "mu_bf16_tokens_per_sec": round(tps_mu16, 1),
            "optimizer_share": round(max(0.0, 1.0 - tps_base / tps_sgd), 4),
            "fwd_only_tokens_per_sec": round(tps_fwd, 1),
            "best_tokens_per_sec": round(best_tps, 1),
            "best_mfu": round(flops_per_token(c0) * best_tps
                              / (peak * 1e12), 4),
            "config": (f"L{c0.num_layers} d{c0.d_model} ff{c0.d_ff} "
                       f"h{c0.num_heads} seq{seq} batch{batch} bf16")}


def _peak_tflops():
    import jax

    from bench import _chip_peak_tflops  # repo root is on sys.path (top)

    return _chip_peak_tflops(jax.devices()[0])


def _emit(row):
    """Stamp measurement provenance (backend/device/time) onto a row so a
    CPU-fallback run can never be mistaken for a chip number downstream."""
    import jax

    dev = jax.devices()[0]
    row["backend"] = dev.platform
    row["device"] = getattr(dev, "device_kind", dev.platform)
    row["measured_at"] = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    print(json.dumps(row))


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--disagg-prefill-child":
        run_disagg_prefill_child(sys.argv[2:])
        sys.exit(0)
    args = list(sys.argv[1:])
    smoke = "--smoke" in args
    args = [a for a in args if a != "--smoke"]
    which = args[0] if args else "all"
    if which in ("otto", "all"):
        _emit(measure_otto())
    if which in ("resnet50", "all"):
        _emit(measure_resnet50())
    if which in ("async", "all"):
        _emit(measure_async())
    if which in ("ps_plane", "all"):
        _emit(measure_ps_plane())
    if which in ("ps_failover", "all"):
        _emit(measure_ps_failover(smoke=smoke))
    if which in ("decode", "all"):
        _emit(measure_decode())
    if which in ("flash", "all"):
        _emit(measure_flash_scaling())
    if which in ("engine", "all"):
        _emit(measure_engine())
    if which in ("fleet_router", "all"):
        _emit(measure_fleet_router(smoke=smoke))
    if which in ("prefix_cache", "all"):
        _emit(measure_prefix_cache(smoke=smoke))
    if which in ("kv_tiered", "all"):
        _emit(measure_kv_tiered(smoke=smoke))
    if which in ("disagg", "all"):
        _emit(measure_disagg(smoke=smoke))
    if which in ("weight_swap", "all"):
        _emit(measure_weight_swap(smoke=smoke))
    if which in ("speculative", "all"):
        _emit(measure_speculative(smoke=smoke))
    if which in ("adaptive_sched", "all"):
        _emit(measure_adaptive_sched(smoke=smoke))
    if which in ("tenant_qos", "all"):
        _emit(measure_tenant_qos(smoke=smoke))
    if which in ("autoscaler", "all"):
        _emit(measure_autoscaler(smoke=smoke))
    if which in ("slo_plane", "all"):
        _emit(measure_slo_plane(smoke=smoke))
    if which in ("trace_plane", "all"):
        _emit(measure_trace_plane(smoke=smoke))
    if which in ("crash_resume", "all"):
        _emit(measure_crash_resume(smoke=smoke))
    if which in ("resilience", "all"):
        _emit(measure_resilience(smoke=smoke))
    if which in ("ssm", "all"):
        _emit(measure_ssm())
    if which in ("mfu", "all"):
        _emit(measure_mfu())
