"""``costs.py`` against numbers worked by hand, one layer of each
configuration."""
import json

import pytest

from chipbench import costs

from ._util import REPO


def config(name):
    with open(REPO / "chipbench" / "configs" / f"{name}.json") as fh:
        return json.load(fh)


def test_one_layer_by_hand():
    cfg = config("mistral-7b-l16-serve")
    # q and o: 4096 x 4096 each; k and v: 4096 x 1024 each;
    # gate, up, down: 4096 x 14336 each
    by_hand = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert by_hand == 218_103_808                      # 218.1 M a layer
    assert costs.layer_params(cfg) == by_hand
    # K and V of one position in one layer: 2 x 8 heads x 128 x 2 bytes
    assert costs.kv_bytes_per_token(cfg, layers=1) == 4096     # 4 KiB
    assert costs.kv_bytes_per_token(cfg) == 16 * 4096
    assert costs.matmul_params(cfg) == 16 * by_hand + 4096 * 32000
    # 3.62 B matrix parameters in bf16 = 7.24 GB read by every step
    assert costs.weight_bytes(cfg, "bfloat16") == 2 * (
        16 * by_hand + 4096 * 32000)


def test_decode_step_bytes_add_the_cache_the_rows_hold():
    cfg = config("mistral-7b-l16-serve")
    base = costs.decode_step_bytes(cfg, "bfloat16", 0)
    assert base == costs.weight_bytes(cfg, "bfloat16")
    held = costs.decode_step_bytes(cfg, "bfloat16", 32 * 900)
    assert held - base == 32 * 900 * 65536


def test_train_flops_per_token_by_hand():
    path = REPO / "chipbench" / "configs" / "mistral-7b-l6-train.json"
    if path.exists():
        cfg = config("mistral-7b-l6-train")
    else:               # the cell is committed only once proved on the chip
        from .rehearse_train_test import CONFIG as cfg
    params = 6 * 218_103_808 + 4096 * 32000            # 1.44 B
    # attention: QK^T and PV, forward and backward = 12 x d_model x keys
    # a layer, keys = the causal mean (4096 + 1) / 2 at window 4096
    attention = 12 * 6 * 4096 * (4097 / 2)
    by_hand = 6 * params + attention
    assert costs.train_flops_per_token(cfg, 4096) == pytest.approx(by_hand)
    assert by_hand / 1e9 == pytest.approx(9.24, abs=0.01)   # 9.25 GFLOP


def test_a_window_shorter_than_the_sequence_cuts_attention():
    cfg = dict(config("mistral-7b-l16-serve"), sliding_window=1024)
    full = dict(cfg, sliding_window=None)
    assert costs.train_flops_per_token(cfg, 4096) < \
        costs.train_flops_per_token(full, 4096)


def test_an_unknown_device_kind_is_an_error():
    assert costs.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        costs.peaks("cpu")
