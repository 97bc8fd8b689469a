"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the main path once, through the entry points a user
calls, at the full width the repo benchmarks its flagship LM at
(``bench.py``: L8, d_model 1024, d_ff 4096, 16 heads, vocab 32000, seq
1024, bf16 compute / f32 params) with weights made from a seed:

1. **device** — what JAX found; anything but a TPU ends the run at once.
2. **train, one chip** — ``make_train_step`` (unmeshed, flash attention)
   for a few steps on a repeated batch, then the same init and batch
   through XLA attention: first-step loss and gradient norm must agree.
3. **serve** — the README quickstart engine behind ``ServingServer``,
   real HTTP traffic (shared prefix, a stream); outputs are held against
   solo ``generate`` and a float32 reference ``forward``, and one paged
   decode step's logits against that reference.
4. **train, four chips** — ``TPUModel(TransformerModel).fit`` on
   ``data=4`` and on ``data=2 x model=2``, when four devices are visible.
5. **elephas job** — the reference's MNIST-shaped MLP through
   ``TPUModel``: sync-step fit with the predict/evaluate parity oracle,
   and one asynchronous fit over the socket parameter server.

Every phase prints its wall time and compile time. These are set-up
observations for sizing later work, not speed numbers. The first failure
ends the run with its traceback: no phase result is recorded and carried
past. A run that passed ends with two JSON lines on standard output: the
summary (versions, per-phase seconds and facts, ``"claim": null``), and
last the verdict, ``{"ok": true, "device": {"platform", "kind",
"count"}}`` with the device as JAX reports it and no other key.

``--cpu-preflight`` runs every phase at toy size on four virtual CPU
devices, to debug the command before chip time is spent on it. It is
never chosen automatically, and its summary says ``"platform": "cpu"``.
"""
import contextlib
import dataclasses
import json
import socket
import sys
import threading
import time
import urllib.error
import urllib.request

PREFLIGHT_FLAG = "--cpu-preflight"

# Tolerances. Each "observed" is from the chip runs of PR 21 (TPU v5 lite,
# jax 0.9.0, libtpu 0.0.34, one chip and four; CHANGES.md quotes them).
# Seeds are fixed, so a run on the same installation repeats them; the
# limits leave room for another compiler version, not for a wrong kernel.
#: |loss_flash - loss_xla| / loss_xla at step 0 (observed 1.8e-6)
FLASH_VS_XLA_LOSS_RTOL = 1e-4
#: relative difference of the global gradient norm (observed 2.3e-5; 3.6e-4
#: in the CPU pre-flight at toy size)
FLASH_VS_XLA_GNORM_RTOL = 2e-3
#: first-step loss, mesh fit vs the one-chip flash step (observed 8.1e-7
#: on data=4, 3.7e-6 on data=2 x model=2)
MESH_VS_ONE_CHIP_LOSS_RTOL = 1e-4
#: max |logits_paged - logits_f32| over one paged decode step (8 rows x
#: 200 cached positions, bf16 pool): bf16 prefill, block install and
#: paged attention against the float32 "highest" forward (observed
#: 0.0185; 0.0064 in the CPU pre-flight at toy size)
PAGED_VS_F32_LOGITS_ATOL = 0.0625
#: how far below the position's maximum an emitted token's logit may sit
#: under the float32 "highest"-precision teacher-forced forward (observed
#: 0.0 on the chip — every emitted token was the f32
#: argmax, while solo bf16 ``generate`` matched 6 of 7 requests; 0.006
#: in the CPU pre-flight at toy size). At full width the seeded model
#: repeats one token per request, so this oracle is easy there; the
#: logits comparison above is the one with teeth.
ENGINE_VS_F32_LOGIT_MARGIN = 0.05
#: reference oracle: distributed evaluate vs master-network evaluate
EVALUATE_ABS_TOL = 0.01


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Everything that differs between the chip run and the pre-flight."""
    vocab: int
    layers: int
    heads: int
    d_model: int
    d_ff: int
    seq: int
    batch: int
    train_steps: int
    slots: int
    pool: tuple          # DecodeEngine(paged=(num_blocks, block_size))
    prefill_chunk: int
    new_tokens: int


# widths are the benchmark's (bench.py bench_transformer); nothing is cut
CHIP = Sizes(vocab=32000, layers=8, heads=16, d_model=1024, d_ff=4096,
             seq=1024, batch=8, train_steps=4, slots=8, pool=(512, 16),
             prefill_chunk=256, new_tokens=24)
TOY = Sizes(vocab=512, layers=2, heads=4, d_model=64, d_ff=128, seq=128,
            batch=4, train_steps=3, slots=4, pool=(96, 8),
            prefill_chunk=32, new_tokens=8)


class Phases:
    """Wall and compile seconds per phase, from JAX's own monitoring
    events (``backend_compile_duration`` covers the persistent-cache
    lookup too, so a warm cache shows up as small compile seconds)."""

    def __init__(self):
        import jax.monitoring

        self.results = {}
        self._compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self._compile_s += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    @contextlib.contextmanager
    def phase(self, name):
        print(f"[{name}] start", flush=True)
        t0, c0 = time.perf_counter(), self._compile_s
        facts = {}
        yield facts          # a failure propagates: nothing is recorded
        wall = time.perf_counter() - t0
        compile_s = self._compile_s - c0
        self.results[name] = {"ok": True, "seconds": round(wall, 2),
                              "compile_seconds": round(compile_s, 2),
                              **facts}
        print(f"[{name}] ok wall={wall:.1f}s compile={compile_s:.1f}s",
              flush=True)


def require(cond, message):
    if not cond:
        raise AssertionError(message)


def rel_diff(a, b):
    return abs(a - b) / max(abs(b), 1e-12)


def has_mosaic_kernel(lowered) -> bool:
    """True when a compiled Pallas kernel (a Mosaic ``tpu_custom_call``)
    is in the lowered program; the Pallas interpreter and plain XLA
    attention both lower to ordinary HLO."""
    return "tpu_custom_call" in lowered.as_text()


# ------------------------------------------------------------------ device
def phase_device(facts, on_chip, cache_dir):
    import jax
    import jaxlib
    import optax

    dev = jax.devices()[0]
    if dev.platform != "tpu" and on_chip:
        raise SystemExit(
            f"chip_smoke.py needs a TPU; JAX found platform "
            f"{dev.platform!r} ({len(jax.devices())} device(s)). Nothing "
            f"ran. ({PREFLIGHT_FLAG} debugs the command on the CPU.)")
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    versions = {"python": sys.version.split()[0], "jax": jax.__version__,
                "jaxlib": jaxlib.__version__, "libtpu": libtpu_version,
                "optax": optax.__version__}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"  device: {device}\n  versions: {versions}\n"
          f"  compile cache: {cache_dir}", flush=True)
    facts.update(compile_cache_dir=cache_dir)
    return device, versions


def build_native():
    """Build ``native/libetpu.so`` from its sources, never take one as
    found: the working tree may carry a left-over library that a checkout of
    the same commit does not. Returns which implementation the loader
    and the parameter-server codec will use."""
    from elephas_tpu.utils import native

    built = native.build(force=True)
    if not built and native.available():
        raise AssertionError(
            "native/libetpu.so exists but could not be rebuilt here; "
            "refusing to run on a library this run did not build")
    return "native (built here)" if built and native.available() else "python"


# --------------------------------------------------------- train, one chip
def lm_config(sz, attention_impl):
    from elephas_tpu.models.transformer import TransformerConfig

    return TransformerConfig(vocab_size=sz.vocab, num_layers=sz.layers,
                             num_heads=sz.heads, d_model=sz.d_model,
                             d_ff=sz.d_ff, max_seq_len=sz.seq,
                             attention_impl=attention_impl)


def train_tokens(sz):
    import jax
    import numpy as np

    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (sz.batch, sz.seq), 0, sz.vocab))


def phase_train_one_chip(facts, sz, on_chip):
    import jax
    import numpy as np
    import optax

    from elephas_tpu.models.transformer import (init_params, lm_loss,
                                                make_train_step,
                                                select_attention_impl)

    tokens = jax.numpy.asarray(train_tokens(sz))
    # attention_impl does not enter the init: one seeded tree serves both
    # gradient programs and is then donated to the train step
    params = init_params(lm_config(sz, "flash"), jax.random.PRNGKey(0))
    first = {}
    for impl in ("flash", "xla"):
        config = lm_config(sz, impl)
        resolved = select_attention_impl(config, None, None, None, None,
                                         sz.batch)
        require(resolved == impl, f"{impl!r} resolved to {resolved!r}")
        lowered = jax.jit(
            lambda p, t, c=config: jax.value_and_grad(lm_loss)(p, t, c)
        ).lower(params, tokens)
        mosaic = has_mosaic_kernel(lowered)
        loss, grads = lowered.compile()(params, tokens)
        first[impl] = {"loss": float(loss), "mosaic": mosaic,
                       "gnorm": float(optax.global_norm(grads))}
        del grads
        print(f"  {impl}: attention={resolved} mosaic_kernel={mosaic} "
              f"loss0={first[impl]['loss']:.6f} "
              f"gnorm0={first[impl]['gnorm']:.6f}", flush=True)
    if on_chip:
        require(first["flash"]["mosaic"],
                "flash attention did not lower to a compiled Mosaic kernel")
    require(not first["xla"]["mosaic"], "the xla path holds a Pallas kernel")
    loss_rel = rel_diff(first["flash"]["loss"], first["xla"]["loss"])
    gnorm_rel = rel_diff(first["flash"]["gnorm"], first["xla"]["gnorm"])
    require(loss_rel <= FLASH_VS_XLA_LOSS_RTOL,
            f"flash vs xla first-step loss differ by {loss_rel:.2e}")
    require(gnorm_rel <= FLASH_VS_XLA_GNORM_RTOL,
            f"flash vs xla gradient norm differ by {gnorm_rel:.2e}")

    config = lm_config(sz, "flash")
    tx = optax.adamw(3e-4)
    opt_state = tx.init(params)
    step = make_train_step(config, tx).lower(params, opt_state,
                                             tokens).compile()
    losses = []
    for _ in range(sz.train_steps):
        params, opt_state, loss = step(params, opt_state, tokens)
        losses.append(float(loss))
    print(f"  train losses: {[round(x, 4) for x in losses]}", flush=True)
    require(all(np.isfinite(losses)), f"non-finite loss in {losses}")
    require(losses[-1] < losses[0], f"loss did not fall: {losses}")
    require(rel_diff(losses[0], first["flash"]["loss"])
            <= FLASH_VS_XLA_LOSS_RTOL,
            "train step and lm_loss disagree on the first-step loss")
    auto = select_attention_impl(lm_config(sz, "auto"), None, None, None,
                                 None, sz.batch)
    print(f"  attention_impl='auto', unmeshed, resolves to {auto!r} here "
          f"({len(jax.devices())} device(s) visible)", flush=True)
    # (the chip run has required the compiled kernel by now)
    facts.update(attention={"flash_kernel": ("compiled" if on_chip
                                             else "interpreted"),
                            "auto_unmeshed_resolves_to": auto},
                 loss=[round(x, 5) for x in losses],
                 flash_vs_xla={"loss_rel": loss_rel, "gnorm_rel": gnorm_rel})
    return params, losses[0]


# ------------------------------------------------------------------- serve
def http_open(port, path, payload=None, timeout=600):
    """GET ``path``, or POST ``payload`` to it as JSON."""
    return urllib.request.urlopen(urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=None if payload is None else json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}), timeout=timeout)


def http_json(port, path, payload=None, timeout=600):
    with http_open(port, path, payload, timeout) as resp:
        return resp.status, json.loads(resp.read())


def http_stream(port, payload):
    """POST a ``"stream": true`` generate; returns (status, token list,
    terminal line)."""
    tokens, last = [], None
    with http_open(port, "/v1/generate", dict(payload, stream=True)) as resp:
        for raw in resp:
            last = json.loads(raw)
            tokens.extend(last.get("tokens", []))
        return resp.status, tokens, last


def serve_prompts(sz):
    """The traffic: a registered system prefix, three prompts that share
    it, two that do not, one longer than a prefill chunk, one streamed.
    Lengths are multiples of ``unit`` so admission compiles a handful of
    block shapes, all of them warmed."""
    import numpy as np

    unit = sz.prefill_chunk // 4
    rng = np.random.default_rng(7)

    def ids(n):
        return [int(t) for t in rng.integers(1, sz.vocab, n)]

    prefix = ids(unit)
    prompts = ([prefix + ids(unit) for _ in range(3)]
               + [ids(unit) for _ in range(2)]
               + [ids(5 * unit)]
               + [ids(unit)])                       # the streamed one
    budgets = [sz.new_tokens - (i % 3) * 2 for i in range(len(prompts))]
    return prefix, prompts, budgets, unit


def reference_outputs(params, config, prompts, budgets):
    """Solo greedy ``generate`` for every prompt — the repo's serving
    oracle — as one ragged batch (one compile)."""
    import numpy as np

    from elephas_tpu.models.transformer import generate

    width = max(len(p) for p in prompts)
    padded = np.zeros((len(prompts), width), np.int32)
    for i, p in enumerate(prompts):
        padded[i, :len(p)] = p
    out = np.asarray(generate(params, padded, max(budgets), config,
                              prompt_lengths=[len(p) for p in prompts]))
    return [[int(t) for t in out[i, :n]] for i, n in enumerate(budgets)]


def f32_reference(params, config):
    """The plain reference: returns ``logits(rows)``, one float32
    ``forward`` at "highest" matmul precision over right-padded token
    rows (causal, so the padding touches nothing before it)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from elephas_tpu.models.transformer import forward

    ref_config = dataclasses.replace(config, dtype=jnp.float32,
                                     attention_impl="xla")
    ref_forward = jax.jit(lambda p, t: forward(p, t, ref_config))

    def logits(rows):
        with jax.default_matmul_precision("highest"):
            out = np.asarray(ref_forward(params, jnp.asarray(rows)))
        require(np.isfinite(out).all(), "non-finite reference logits")
        return out

    return logits


def f32_logit_margin(ref_logits, prompts, outputs):
    """The fallback oracle for bf16 near-ties: teacher-force prompt +
    output through the reference and measure, for every emitted token,
    how far its logit sits below that position's maximum. 0 means the
    float32 model picks the same token."""
    import numpy as np

    width = max(len(p) + len(o) for p, o in zip(prompts, outputs))
    rows = np.zeros((len(prompts), width), np.int32)
    for i, (p, o) in enumerate(zip(prompts, outputs)):
        rows[i, :len(p) + len(o)] = p + o
    logits = ref_logits(rows)
    worst = 0.0
    for i, (p, o) in enumerate(zip(prompts, outputs)):
        for j, tok in enumerate(o):
            at = logits[i, len(p) + j - 1]       # predicts position len(p)+j
            worst = max(worst, float(at.max() - at[tok]))
    return worst


def check_outputs(name, outputs, budgets, oracle, ref_logits, prompts,
                  vocab):
    identical = sum(o == r for o, r in zip(outputs, oracle))
    for o, n in zip(outputs, budgets):
        require(len(o) == n, f"{name}: asked {n} tokens, got {len(o)}")
        require(all(0 <= t < vocab for t in o),
                f"{name}: token out of vocabulary")
    margin = f32_logit_margin(ref_logits, prompts, outputs)
    emitted = [t for o in outputs for t in o]
    print(f"  {name}: {identical}/{len(outputs)} requests token-identical "
          f"to solo generate; worst f32 logit margin {margin:.4f} over "
          f"{len(emitted)} tokens ({len(set(emitted))} distinct)",
          flush=True)
    require(margin <= ENGINE_VS_F32_LOGIT_MARGIN,
            f"{name}: an emitted token sits {margin:.4f} below the f32 "
            f"reference's best (limit {ENGINE_VS_F32_LOGIT_MARGIN})")
    return {"identical_to_generate": f"{identical}/{len(outputs)}",
            "f32_logit_margin": round(margin, 5),
            "distinct_tokens": len(set(emitted))}


def paged_step_logits(params, config, sz, ref_logits):
    """One paged decode step after a batched prefill, and the same
    position through the float32 reference, which holds the whole cache
    path (prefill, block install, table lookup, attention over the pool)
    to a number, where the seeded model's token choices are too easy to
    tell a good cache from a bad one. Returns max |paged - f32|."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from elephas_tpu.models.paged_decode import (decode_step_paged,
                                                 init_paged_pool,
                                                 install_row_paged)
    from elephas_tpu.models.transformer import prefill_cache

    num_blocks, bs = sz.pool
    length = 3 * sz.prefill_chunk // 4 + bs // 2     # mid-block position
    need = length // bs + 1
    rows = min(sz.slots, (num_blocks - 1) // need)
    max_blocks = -(-sz.seq // bs)
    prompts = jax.random.randint(jax.random.PRNGKey(3), (rows, length), 1,
                                 sz.vocab)
    logits, cache = jax.jit(
        lambda p, t: prefill_cache(p, t, config, sz.seq))(params, prompts)
    pool = init_paged_pool(config, num_blocks, bs)
    tables = np.zeros((rows, max_blocks), np.int32)
    for r in range(rows):
        tables[r, :need] = 1 + r * need + np.arange(need)
        row = jax.tree_util.tree_map(lambda a: a[r:r + 1], cache)
        pool = install_row_paged(pool, row, tables[r], need)
    last = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    step = jax.jit(lambda p, pl, tb, tk, ps: decode_step_paged(
        p, pl, tb, tk, ps, config)[0])
    got = np.asarray(step(params, pool, jnp.asarray(tables), last,
                          jnp.full((rows,), length, jnp.int32)), np.float32)
    ref = ref_logits(np.concatenate(
        [np.asarray(prompts), np.asarray(last)[:, None]], axis=1))[:, -1]
    return float(np.abs(got - ref).max())


def phase_serve(facts, sz, params):
    from elephas_tpu import DecodeEngine, ServingServer

    config = lm_config(sz, "flash")
    prefix, prompts, budgets, unit = serve_prompts(sz)
    oracle = reference_outputs(params, config, prompts, budgets)
    ref_logits = f32_reference(params, config)

    # the README quickstart engine
    engine = DecodeEngine(params, config, max_slots=sz.slots,
                          prefill_chunk=sz.prefill_chunk, paged=sz.pool)
    engine.register_prefix(prefix)
    engine.warmup(prompt_lengths=(unit, 5 * unit))
    # the stall watchdog's threshold sits above a cold compile, as its
    # docstring asks: the first prefix hit compiles its block shapes
    server = ServingServer(engine, watchdog_stall_s=300.0).start()
    try:
        port = server.port
        outputs = [None] * len(prompts)
        statuses = [None] * len(prompts)

        def call(i):
            body = {"prompt": prompts[i], "max_new_tokens": budgets[i]}
            if i == len(prompts) - 1:
                statuses[i], outputs[i], last = http_stream(port, body)
                require(last == {"status": "done"},
                        f"stream ended with {last}")
            else:
                statuses[i], reply = http_json(port, "/v1/generate", body)
                outputs[i] = reply["tokens"]

        call(0)              # the first prefix hit, alone
        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(1, len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=900)
        require(not any(t.is_alive() for t in threads),
                "an HTTP client is still waiting")
        require(all(s == 200 for s in statuses), f"statuses {statuses}")
        _, stats = http_json(port, "/stats")
        ready_status, ready = http_json(port, "/ready")
        health_status, _ = http_json(port, "/health")
    finally:
        server.stop()
    try:
        http_json(port, "/health", timeout=5)
    except (urllib.error.URLError, ConnectionError):
        pass
    else:
        raise AssertionError("the server still answers after stop()")
    print(f"  /stats: finished={stats['requests_finished']} "
          f"prefix_tokens_reused={stats.get('prefix_tokens_reused')} "
          f"tokens_per_step={stats['tokens_per_step']:.2f}", flush=True)
    require(ready_status == 200 and ready == {"status": "ready"},
            f"/ready said {ready}")
    require(health_status == 200, "/health failed")
    require(stats["requests_finished"] == len(prompts),
            f"{stats['requests_finished']} of {len(prompts)} finished")
    failed = {k: stats[k] for k in ("requests_shed", "requests_expired",
                                    "requests_timed_out")}
    require(not any(failed.values()), f"failed requests: {failed}")
    require(stats["prefix_tokens_reused"] >= 3 * unit,
            f"prefix reuse {stats['prefix_tokens_reused']} < {3 * unit}")
    engine_facts = check_outputs("engine over HTTP", outputs, budgets,
                                 oracle, ref_logits, prompts, sz.vocab)
    del engine, server

    vs_f32 = paged_step_logits(params, config, sz, ref_logits)
    print(f"  paged decode step: max |dlogit| vs f32 reference "
          f"{vs_f32:.5f}", flush=True)
    require(vs_f32 <= PAGED_VS_F32_LOGITS_ATOL,
            f"paged decode logits differ from the f32 reference by {vs_f32}")
    facts.update(engine=engine_facts,
                 paged_vs_f32_max_dlogit=round(vs_f32, 6))


# -------------------------------------------------------- train, four chips
def phase_train_four_chips(facts, sz, one_chip_loss0, on_chip):
    import jax
    import numpy as np
    from jax.sharding import NamedSharding

    from elephas_tpu import TPUModel
    from elephas_tpu.models import AdamW
    from elephas_tpu.models.transformer import (param_specs,
                                                select_attention_impl)
    from elephas_tpu.models.transformer_model import TransformerModel

    devices = jax.devices()
    if len(devices) < 4:
        print(f"  skipped: {len(devices)} device(s) visible", flush=True)
        facts.update(skipped=f"{len(devices)} device(s) visible")
        return
    tokens = train_tokens(sz)
    config = lm_config(sz, "flash")
    for tp in (1, 2):
        model = TransformerModel(config, tensor_parallel=tp)
        model.compile(AdamW(learning_rate=3e-4), seed=0)
        tpu_model = TPUModel(model, mode="synchronous")
        tpu_model.fit(tokens, epochs=sz.train_steps, batch_size=sz.batch,
                      validation_split=0.0)
        losses = tpu_model.training_histories[-1]["loss"]
        leaves = jax.tree_util.tree_leaves(model.params)
        mesh = leaves[0].sharding.mesh
        axes = dict(zip(mesh.axis_names, mesh.devices.shape))
        resolved = select_attention_impl(config, mesh, None, "data",
                                         "model", sz.batch)
        print(f"  mesh {axes}: attention={resolved} losses="
              f"{[round(x, 4) for x in losses]}", flush=True)
        require(axes == {"data": len(devices) // tp, "model": tp},
                f"unexpected training mesh {axes}")
        require(resolved == "flash_sharded", f"attention {resolved}")
        require(all(np.isfinite(losses)) and losses[-1] < losses[0],
                f"mesh {axes}: losses {losses}")
        loss_rel = rel_diff(losses[0], one_chip_loss0)
        require(loss_rel <= MESH_VS_ONE_CHIP_LOSS_RTOL,
                f"mesh {axes}: first-step loss {losses[0]} vs one chip "
                f"{one_chip_loss0} ({loss_rel:.2e})")
        specs = jax.tree_util.tree_leaves(
            param_specs(config, mesh=mesh),
            is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec))
        everywhere = set(mesh.devices.flat)
        require(everywhere == set(devices), "the mesh leaves devices out")
        for leaf, spec in zip(leaves, specs):
            if leaf.size < (1 << 16):
                continue
            want = NamedSharding(mesh, spec)
            require(leaf.sharding.is_equivalent_to(want, leaf.ndim),
                    f"{leaf.shape}: sharding {leaf.sharding} != {spec}")
            shards = leaf.addressable_shards
            require({s.device for s in shards} == everywhere
                    and all(s.data.shape == want.shard_shape(leaf.shape)
                            for s in shards),
                    f"{leaf.shape}: shards do not cover the mesh")
        in_use = [(d.memory_stats() or {}).get("bytes_in_use")
                  for d in devices]
        print(f"  bytes_in_use per device: {in_use}", flush=True)
        if on_chip:
            floor = sum(leaf.nbytes for leaf in leaves) // (2 * tp)
            require(all(b is not None and b >= floor for b in in_use),
                    f"a device holds less than {floor} bytes: {in_use}")
        facts[f"data{len(devices) // tp}_model{tp}"] = {
            "attention": resolved, "loss": [round(x, 5) for x in losses],
            "loss0_rel_vs_one_chip": loss_rel, "bytes_in_use": in_use}
        del model, tpu_model, leaves


# ------------------------------------------------------------- elephas job
def free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


#: training rows of the MLP job: enough that every asynchronous worker's
#: shard holds several batches (a shard of one batch does not train)
MLP_ROWS = 4096


def mlp_data(rows, seed, dim=784, classes=10):
    """A separable MNIST-shaped problem (class centres + noise)."""
    import numpy as np

    centers = np.random.default_rng(123).normal(0.0, 2.0, (classes, dim))
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, classes, rows)
    x = centers[labels] + rng.normal(0.0, 1.0, (rows, dim))
    x = (x - x.min()) / (x.max() - x.min())
    return x.astype("float32"), np.eye(classes, dtype="float32")[labels]


def mlp_model(learning_rate):
    from elephas_tpu.models import SGD, Activation, Dense, Sequential

    model = Sequential([Dense(128, input_dim=784), Activation("relu"),
                        Dense(128), Activation("relu"),
                        Dense(10), Activation("softmax")])
    model.compile(SGD(learning_rate=learning_rate),
                  "categorical_crossentropy", ["acc"], seed=0)
    return model


def check_parity(tpu_model, x_test, y_test):
    """The reference's oracle: distributed predict/evaluate against the
    master network's."""
    import numpy as np

    got = np.asarray(tpu_model.predict(x_test))
    want = np.asarray(tpu_model.master_network.predict(x_test))
    require(np.isfinite(got).all(), "non-finite predictions")
    require((got.argmax(-1) == want.argmax(-1)).all(),
            "distributed predict disagrees with the master network")
    evals = tpu_model.evaluate(x_test, y_test)
    master = tpu_model.master_network.evaluate(x_test, y_test)
    for g, w in zip(evals, master):
        require(abs(g - w) <= EVALUATE_ABS_TOL, f"evaluate {evals} vs {master}")
    return [float(v) for v in evals]


def phase_elephas_job(facts):
    import jax
    import numpy as np

    from elephas_tpu import TPUModel
    from elephas_tpu.utils.dataset_utils import to_dataset

    x, y = mlp_data(MLP_ROWS, seed=0)
    x_test, y_test = mlp_data(MLP_ROWS // 4, seed=1)

    # 0.05: the rate at which this synthetic problem trains steadily
    # (the reference example's 0.1 oscillates on it)
    sync = TPUModel(mlp_model(0.05), mode="synchronous", sync_mode="step",
                    batch_size=64)
    sync.fit(to_dataset(x, y), epochs=3, batch_size=64, verbose=0,
             validation_split=0.0)
    losses = sync.training_histories[-1]["loss"]
    require(all(np.isfinite(losses)) and losses[-1] < losses[0],
            f"sync-step losses {losses}")
    sync_eval = check_parity(sync, x_test, y_test)
    print(f"  sync-step on {len(jax.devices())} device(s): losses "
          f"{[round(v, 4) for v in losses]} evaluate {sync_eval}",
          flush=True)

    # workers that start together push deltas taken from the same
    # weights, which add up: the rate is cut so that their sum stays in
    # the steady range. At least two workers, so that one chip sees the
    # round-robin wrap and four chips get one worker each.
    master = mlp_model(0.02)
    before = master.evaluate(x_test, y_test)[0]
    workers = max(2, len(jax.local_devices()))
    async_model = TPUModel(master, mode="asynchronous", frequency="epoch",
                           parameter_server_mode="socket",
                           num_workers=workers, port=free_port())
    async_model.fit(to_dataset(x, y), epochs=2, batch_size=64, verbose=0,
                    validation_split=0.0)
    report = async_model.training_histories[-1]["supervisor"]
    async_eval = check_parity(async_model, x_test, y_test)
    print(f"  asynchronous/socket, {workers} workers: evaluate {before:.4f}"
          f" -> {async_eval[0]:.4f}; supervisor {report}", flush=True)
    require(np.isfinite(async_eval[0]) and async_eval[0] < before,
            f"async fit did not train: {before} -> {async_eval[0]}")
    facts.update(sync_step={"loss": [round(v, 5) for v in losses],
                            "evaluate": sync_eval},
                 asynchronous={"workers": workers, "evaluate": async_eval})


# -------------------------------------------------------------------- main
def main(argv):
    unknown = [a for a in argv if a != PREFLIGHT_FLAG]
    if unknown:
        raise SystemExit(f"usage: chip_smoke.py [{PREFLIGHT_FLAG}]")
    on_chip = PREFLIGHT_FLAG not in argv

    import jax

    if not on_chip:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", 4)

    from elephas_tpu.utils.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    phases = Phases()
    sz = CHIP if on_chip else TOY

    with phases.phase("device") as facts:
        device, versions = phase_device(facts, on_chip, cache_dir)
        native = build_native()
        print(f"  host loader and wire codec: {native}", flush=True)
    with phases.phase("train_one_chip") as facts:
        params, loss0 = phase_train_one_chip(facts, sz, on_chip)
    with phases.phase("serve") as facts:
        phase_serve(facts, sz, params)
    del params
    with phases.phase("train_four_chips") as facts:
        phase_train_four_chips(facts, sz, loss0, on_chip)
    with phases.phase("elephas_job") as facts:
        phase_elephas_job(facts)

    summary = {
        "ok": True, "device": device, "chips": device["count"],
        "preflight": not on_chip, "versions": versions, "native": native,
        "compile_cache": {"dir": cache_dir, "hits": phases.cache_hits,
                          "misses": phases.cache_misses},
        "phases": phases.results, "claim": None}
    print(json.dumps(summary), flush=True)
    # the verdict the driver reads: these keys and no others, last
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
