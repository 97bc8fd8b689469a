"""The training driver end to end under ``--rehearse``: a subprocess on
four virtual CPU devices (data=2 x model=2), toy widths. It runs the
benchmark's own training cells; while ``BENCHMARK.json`` has none (a cell
is only committed once it is proved on the chip), it runs the driver on a
cell written here, added as data beside the benchmark."""
import json

import pytest

from ._util import REPO, last_line, run_cell
from .rehearse_serve_test import cells

SOURCE = "https://huggingface.co/mistralai/Mistral-7B-v0.1/blob/main/config.json"
#: Mistral-7B-v0.1's published sizes, depth cut to 6 (ISSUE 24's
#: ``mistral-7b-l6-train``); the rehearsal swaps in the family's toy sizes
CONFIG = {
    "hidden_act": "silu", "hidden_size": 4096, "intermediate_size": 14336,
    "num_attention_heads": 32, "num_hidden_layers": 6,
    "num_key_value_heads": 8, "rms_norm_eps": 1e-05, "rope_theta": 10000.0,
    "sliding_window": 4096, "tie_word_embeddings": False,
    "vocab_size": 32000, "family": "dense_decoder", "source": SOURCE,
    "param_dtype": "float32",
    "trainer": {"tensor_parallel": 2, "remat": True,
                "attention_impl": "flash", "learning_rate": 0.0003},
    "check": {"first_loss_rtol": 0.01}}
JOB = {"driver": "train", "seq_len": 4096, "global_batch": 8,
       "steps_per_epoch": 4, "max_epochs": 4000, "trace_s": 6,
       "rehearse": {"seq_len": 128, "global_batch": 4, "steps_per_epoch": 2,
                    "trace_s": 1}}
TRAIN_METRIC = {"reader": "train_rate"}


def written_cell(tmp_path):
    extra = tmp_path / "extra"
    for sub in ("configs", "traffic", "metrics"):
        (extra / sub).mkdir(parents=True)
    (extra / "configs" / "l6-train.json").write_text(json.dumps(CONFIG))
    (extra / "traffic" / "pretrain.json").write_text(json.dumps(JOB))
    (extra / "metrics" / "train_tokens_per_s.json").write_text(
        json.dumps(TRAIN_METRIC))
    with open(REPO / "BENCHMARK.json") as fh:
        bench = json.load(fh)
    bench.update(
        paths=["extra"],
        configs=[{"name": "l6-train", "source": SOURCE,
                  "file": "extra/configs/l6-train.json",
                  "reduced": ["num_hidden_layers"], "why": "a test"}],
        workloads=[{"name": "train-2x2", "config": "l6-train",
                    "traffic": "pretrain", "chips": 4, "why": "a test"}],
        end_to_end=[{"name": "train_tokens_per_s", "unit": "tokens/s",
                     "better": "higher", "bound": 0.02,
                     "source": "host_clock"},
                    {"name": "setup_s", "unit": "s", "better": "lower",
                     "bound": 0.1, "source": "host_clock"}],
        per_layer=[])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return ["--benchmark-json", str(tmp_path / "BENCHMARK.json"),
            "--workload", "train-2x2"]


@pytest.mark.parametrize("cell", cells("train") or [None])
def test_training_cell_rehearses(cell, tmp_path):
    which = ["--workload", cell] if cell else written_cell(tmp_path)
    proc = run_cell(*which, "--seed", "5", "--seconds", "3", "--trace", "0",
                    "--rehearse")
    line = last_line(proc)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"] == {"platform": "cpu", "kind": "cpu", "count": 4,
                              "memory_peak_bytes": 0}
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert "mesh {'data': 2, 'model': 2}" in proc.stdout
    assert "compiles inside the window: 0" in proc.stdout
