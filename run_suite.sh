#!/usr/bin/env bash
# Run the full test suite as the CI shard matrix does: one pytest
# PROCESS per shard. Two reasons to prefer this over a single
# `pytest tests/`: (a) it is exactly what CI executes, and (b) a
# single long-lived process accumulates hundreds of tests' worth of
# jit executables, server threads, and spawned-subprocess residue —
# an XLA CPU compile deep into such a process has been observed to
# segfault (reproducibly at the same collection index, while every
# shard passes in isolation). Process-per-shard is the honest
# equivalence class.
#
#   bash run_suite.sh            # all shards, summary at the end
set -u
cd "$(dirname "$0")"
declare -a NAMES=(core ops models transformer serving engine distributed)
declare -a PATHS=(
  "tests/ml tests/mllib tests/utils tests/parameter tests/test_ps_sharding.py tests/test_ps_replication.py tests/test_matrix_model.py tests/test_model_serialization.py tests/test_tpu_callbacks.py tests/test_trainer_cache.py tests/test_ci_shards.py tests/test_chip_smoke.py tests/test_documented_options.py"
  "tests/ops"
  "tests/models --ignore=tests/models/test_transformer.py --ignore=tests/models/test_transformer_sharding.py --ignore=tests/models/test_transformer_generate.py --ignore=tests/models/test_transformer_training.py --ignore=tests/models/test_speculative.py --ignore=tests/models/test_distill.py"
  "tests/models/test_transformer.py tests/models/test_transformer_sharding.py tests/models/test_transformer_generate.py tests/models/test_transformer_training.py"
  "tests/models/test_speculative.py tests/models/test_distill.py tests/test_serving.py tests/test_serving_http.py tests/test_serving_overload.py tests/test_fleet_router.py tests/test_fleet_autoscaler.py tests/test_disagg.py tests/test_prefix_cache.py tests/test_speculative_serving.py tests/test_tenant_qos.py tests/test_weightsync.py tests/test_observability.py tests/test_slo_plane.py tests/test_tracing_propagation.py tests/test_crash_safe_serving.py tests/test_network_resilience.py tests/test_kv_tiered.py tests/test_trace_plane.py tests/test_adaptive_sched.py"
  "tests/test_serving_engine.py tests/test_paged_engine.py tests/test_ssm_engine.py tests/test_hybrid_engine.py tests/test_admission_dispatch.py"
  "tests/integration tests/parallel tests/data"
)
fail=0
for i in "${!NAMES[@]}"; do
    echo "=== shard ${NAMES[$i]} ==="
    # shellcheck disable=SC2086
    if ! python -m pytest ${PATHS[$i]} -q; then
        fail=1
        echo "shard ${NAMES[$i]} FAILED"
    fi
done
[ $fail -eq 0 ] && echo "ALL SHARDS GREEN" || echo "SOME SHARD FAILED"
exit $fail
