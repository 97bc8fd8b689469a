"""Introspection-based documentation generator.

Walks the public API and renders one markdown page per module from
docstrings and signatures (capability mirror of the reference's
``docs/autogen.py`` mkdocs generator). Output goes to ``docs/sources/``;
``docs/mkdocs.yml`` holds the nav.

Usage: ``python docs/autogen.py``
"""
import inspect
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

PAGES = [
    ("TPUModel", "elephas_tpu.tpu_model",
     ["TPUModel", "TPUMatrixModel", "load_tpu_model"]),
    ("Models", "elephas_tpu.models.core",
     ["Sequential", "Model", "BaseModel", "model_from_json"]),
    ("Layers", "elephas_tpu.models.layers",
     ["Dense", "Activation", "Dropout", "Flatten", "Reshape", "Conv2D",
      "MaxPooling2D", "AveragePooling2D", "GlobalAveragePooling2D",
      "Embedding", "LSTM", "GRU", "LayerNormalization",
      "BatchNormalization", "Add", "Multiply", "Concatenate", "Input"]),
    ("Optimizers", "elephas_tpu.models.optimizers",
     ["SGD", "Adam", "AdamW", "RMSprop", "Adagrad", "Adadelta", "Nadam",
      "Adafactor", "Lion", "LAMB"]),
    ("LR schedules", "elephas_tpu.models.schedules",
     ["ExponentialDecay", "CosineDecay", "PiecewiseConstantDecay",
      "WarmupCosine"]),
    ("Workers", "elephas_tpu.worker", ["SyncWorker", "AsyncWorker"]),
    ("Worker supervision", "elephas_tpu.parallel.supervisor",
     ["WorkerSupervisor", "SupervisorReport", "QuorumLostError"]),
    ("Fault injection", "elephas_tpu.utils.faults",
     ["FaultPlan", "FaultEvent", "fault_site", "install_plan",
      "clear_plan", "active_plan", "InjectedFault"]),
    ("Parameter servers", "elephas_tpu.parameter.server",
     ["BaseParameterServer", "HttpServer", "SocketServer"]),
    ("Parameter clients", "elephas_tpu.parameter.client",
     ["BaseParameterClient", "HttpClient", "SocketClient"]),
    ("Parameter-plane sharding", "elephas_tpu.parameter.sharding",
     ["ShardPlan", "ShardedServerGroup", "ShardedParameterClient",
      "TornPushError", "CommitAbortedError", "GenerationMismatchError"]),
    ("Parameter-plane replication", "elephas_tpu.parameter.replication",
     ["ShardReplicator", "ShardStandby"]),
    ("Parallel trainers", "elephas_tpu.parallel.sync_trainer",
     ["SyncAverageTrainer", "SyncStepTrainer", "build_sharded_predict",
      "build_sharded_evaluate"]),
    ("Mesh utilities", "elephas_tpu.parallel.mesh",
     ["worker_mesh", "data_mesh", "make_mesh", "hybrid_mesh",
      "shard_leading", "replicate"]),
    ("Multi-host", "elephas_tpu.parallel.multihost",
     ["initialize_multihost", "is_coordinator", "host_local_slice",
      "global_batch_from_host_data"]),
    ("ML pipeline", "elephas_tpu.ml.pipeline",
     ["Estimator", "Transformer", "load_ml_estimator", "load_ml_transformer"]),
    ("DataFrame adapters", "elephas_tpu.ml.adapter",
     ["to_data_frame", "from_data_frame", "df_to_dataset"]),
    ("Datasets", "elephas_tpu.data.dataset", ["Dataset"]),
    ("Out-of-core sources", "elephas_tpu.data.sources",
     ["ColumnSource", "ConcatSource", "NpySource", "ParquetSource",
      "SourceView"]),
    ("Dataset utilities", "elephas_tpu.utils.dataset_utils",
     ["to_dataset", "to_labeled_points", "from_labeled_points",
      "lp_to_dataset", "encode_label"]),
    ("Linalg", "elephas_tpu.mllib.linalg",
     ["DenseVector", "DenseMatrix", "LabeledPoint", "Vectors", "Matrices"]),
    ("Attention ops", "elephas_tpu.ops.attention",
     ["attention", "blockwise_attention"]),
    ("Flash attention (Pallas)", "elephas_tpu.ops.pallas_attention",
     ["flash_attention"]),
    ("Ring attention", "elephas_tpu.ops.ring_attention",
     ["ring_attention", "ring_flash_attention", "ring_attention_sharded"]),
    ("Transformer", "elephas_tpu.models.transformer",
     ["TransformerConfig", "init_params", "param_specs",
      "fsdp_param_specs", "zero_opt_specs", "abstract_params", "forward",
      "forward_with_aux", "lm_loss", "make_train_step", "shard_params",
      "select_moe_dispatch", "init_kv_cache", "decode_step", "generate",
      "beam_search"]),
    ("TransformerModel", "elephas_tpu.models.transformer_model",
     ["TransformerModel"]),
    ("LoRA fine-tuning", "elephas_tpu.models.lora",
     ["init_lora_params", "merge_lora", "make_lora_train_step",
      "lora_param_count"]),
    ("Encoder-decoder (seq2seq)", "elephas_tpu.models.encdec",
     ["EncDecConfig", "init_params", "param_specs", "encode",
      "decode_logits", "seq2seq_loss", "make_train_step", "greedy_decode",
      "shard_params"]),
    ("BERT encoder (MLM)", "elephas_tpu.models.bert",
     ["BertConfig", "init_params", "param_specs", "encode", "pool",
      "mask_tokens", "mlm_loss", "make_mlm_train_step", "shard_params"]),
    ("Vision Transformer", "elephas_tpu.models.vit",
     ["ViTConfig", "init_params", "param_specs", "forward", "vit_loss",
      "make_train_step", "shard_params"]),
    ("Pipeline parallelism", "elephas_tpu.parallel.pipeline",
     ["make_pipeline_fn", "stack_stage_params", "split_transformer_stages",
      "merge_transformer_stages", "shard_pipelined_params",
      "make_pipelined_lm_loss", "make_pipelined_train_step"]),
    ("Callbacks", "elephas_tpu.models.callbacks",
     ["Callback", "EarlyStopping", "ModelCheckpoint", "LambdaCallback"]),
    ("Quantized serving (int8)", "elephas_tpu.models.quantization",
     ["QTensor", "quantize_weight", "quantize_lm_params",
      "dequantize_lm_params"]),
    ("Speculative decoding", "elephas_tpu.models.speculative",
     ["speculative_generate", "speculative_round",
      "speculative_round_paged"]),
    ("Draft distillation", "elephas_tpu.models.distill",
     ["distill_loss", "make_distill_step"]),
    ("Continuous batching", "elephas_tpu.serving_engine",
     ["DecodeEngine", "QueueFullError", "DeadlineExceededError"]),
    ("Multi-tenant QoS", "elephas_tpu.serving_qos",
     ["TenantQoS", "FairQueue", "QueuedRequest"]),
    ("HTTP serving", "elephas_tpu.serving_http", ["ServingServer"]),
    ("Serving fleet API", "elephas_tpu.fleet",
     ["FleetRouter", "ReplicaMembership", "HashRing", "ReplicaPool",
      "ReplicaSupervisor", "RestartPolicy",
      "RetryPolicy", "RetryBudget", "CircuitBreaker",
      "FleetAutoscaler", "TierPolicy", "ReplicaPoolTier",
      "DisaggDecodeTier", "DisaggPrefillTier"]),
    ("Disaggregated serving API", "elephas_tpu.disagg",
     ["DisaggEngine", "DisaggPool", "PrefillWorker", "PrefillJob",
      "KVReceiver", "KVShipper", "encode_kv_frame", "decode_kv_frame"]),
    ("Live weight plane API", "elephas_tpu.weightsync",
     ["WeightSubscriber", "CanaryController"]),
    ("SSM serving", "elephas_tpu.ssm_engine", ["SSMEngine"]),
    ("Paged KV cache", "elephas_tpu.models.paged_decode",
     ["init_paged_pool", "decode_step_paged", "decode_block_paged",
      "install_row_paged", "gather_blocks_to_row", "export_kv_blocks",
      "import_kv_blocks"]),
    ("KV block cache", "elephas_tpu.models.block_cache",
     ["BlockCache", "BlockEntry", "chain_keys"]),
    ("Tiered KV API", "elephas_tpu.kvtier",
     ["TieredSpill", "HostTier", "StorageTier", "SpilledBlock",
      "SessionStore", "encode_payload", "decode_payload"]),
    ("SSMModel", "elephas_tpu.models.ssm_model", ["SSMModel"]),
    ("Selective SSM (Mamba-style)", "elephas_tpu.models.ssm",
     ["SSMConfig", "init_ssm_params", "ssm_forward", "ssm_lm_loss",
      "make_ssm_train_step", "init_ssm_state", "ssm_decode_step",
      "ssm_generate"]),
    ("Checkpointing", "elephas_tpu.utils.checkpoint", ["CheckpointManager"]),
    ("Object storage", "elephas_tpu.utils.storage",
     ["ObjectStore", "CliObjectStore", "LocalMirrorStore", "register_store",
      "get_store"]),
    ("Native acceleration", "elephas_tpu.utils.native",
     ["build", "available", "NativeBatchLoader", "batch_iterator"]),
    ("Text utilities", "elephas_tpu.utils.text", ["ByteTokenizer"]),
    ("Serving", "elephas_tpu.serving", ["TextGenerator"]),
    ("Step timing", "elephas_tpu.utils.tracing",
     ["StepTimer", "profiler_trace"]),
    ("Observability metrics API", "elephas_tpu.obs.metrics",
     ["MetricsRegistry", "Counter", "Gauge", "Histogram",
      "default_registry", "percentile"]),
    ("Trace spans API", "elephas_tpu.obs.trace",
     ["span", "span_if_counted", "record_span", "recent_slow_spans",
      "clear_slow_spans", "set_slow_span_threshold"]),
    ("Trace context API", "elephas_tpu.obs.context",
     ["TraceContext", "current_context", "current_trace_id", "new_root",
      "parse_traceparent", "set_context", "reset_context",
      "use_context"]),
    ("Event log API", "elephas_tpu.obs.events",
     ["EventLog", "FlightRecorder", "default_event_log", "emit",
      "recent_events", "clear_events"]),
    ("Loop profiler API", "elephas_tpu.obs.profiler",
     ["LoopProfiler"]),
    ("SLO plane API", "elephas_tpu.obs.slo",
     ["SLOObjective", "SLOTracker"]),
    ("Engine watchdog API", "elephas_tpu.obs.watchdog",
     ["EngineWatchdog"]),
    ("Wire codec", "elephas_tpu.utils.tensor_codec",
     ["encode_tensors", "decode_tensors", "encode", "decode"]),
    ("Delta compression", "elephas_tpu.utils.delta_compression",
     ["quantize_delta", "dequantize_delta", "ErrorFeedback"]),
    ("Input prefetch", "elephas_tpu.utils.prefetch",
     ["prefetch_to_device"]),
]


def _doc(obj) -> str:
    return inspect.getdoc(obj) or "*(no docstring)*"


def _signature(obj) -> str:
    try:
        return str(inspect.signature(obj))
    except (TypeError, ValueError):
        return "(...)"


def render_page(title: str, module_name: str, names) -> str:
    import importlib

    module = importlib.import_module(module_name)
    lines = [f"# {title}", "", f"`{module_name}`", ""]
    if module.__doc__:
        lines += [inspect.cleandoc(module.__doc__), ""]
    for name in names:
        obj = getattr(module, name)
        lines.append(f"## {name}")
        lines.append("")
        if inspect.isclass(obj):
            lines.append(f"```python\n{name}{_signature(obj.__init__)}\n```")
            lines += ["", _doc(obj), ""]
            for meth_name, meth in sorted(vars(obj).items()):
                if meth_name.startswith("_") or not callable(meth):
                    continue
                lines.append(f"### {name}.{meth_name}")
                lines.append(f"```python\n{meth_name}{_signature(meth)}\n```")
                lines += ["", _doc(meth), ""]
        elif callable(obj):
            lines.append(f"```python\n{name}{_signature(obj)}\n```")
            lines += ["", _doc(obj), ""]
        else:
            lines += [_doc(obj), ""]
    return "\n".join(lines)


def main(out_dir: str = None):
    out = Path(out_dir) if out_dir else ROOT / "docs" / "sources"
    out.mkdir(parents=True, exist_ok=True)
    nav = []
    import re

    for title, module_name, names in PAGES:
        slug = re.sub(r"[^a-z0-9]+", "-",
                      title.lower()).strip("-")
        (out / f"{slug}.md").write_text(render_page(title, module_name, names))
        nav.append((title, f"{slug}.md"))
        print(f"wrote {slug}.md")
    mkdocs = ["site_name: elephas_tpu", "nav:", "  - Home: index.md",
              "  - Scaling guide: scaling-guide.md",
              "  - Serving guide: serving-guide.md",
              "  - Serving operations: serving-operations.md",
              "  - Serving fleet: serving-fleet.md",
              "  - Disaggregated serving: disaggregated-serving.md",
              "  - Live weights: live-weights.md",
              "  - Speculative serving: speculative-serving.md",
              "  - Tiered KV: tiered-kv.md",
              "  - Fault tolerance: fault-tolerance.md",
              "  - Observability: observability.md",
              "  - Distributed tracing: tracing.md"]
    mkdocs += [f"  - {title}: {page}" for title, page in nav]
    (ROOT / "docs" / "mkdocs.yml").write_text("\n".join(mkdocs) + "\n")
    index = ROOT / "README.md"
    (out / "index.md").write_text(index.read_text())
    print("wrote mkdocs.yml and index.md")


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else None)
