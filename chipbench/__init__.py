"""chipbench -- the on-chip benchmark of elephas_tpu (see PERF.md).

``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` and prints one JSON
line last. The yardstick lives here: traffic generation, the reduction
from traces to metrics, the table of peaks, operations and bytes, the
plain references and the comparisons that decide ``correct``.
"""
