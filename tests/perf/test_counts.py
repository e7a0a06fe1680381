"""The FLOPs and bytes functions against hand-worked values."""

import pytest

import perf_presets  # noqa: F401  (puts the repo root on sys.path)
from perf import counts, harness

MLPERF_MACS = (13 * 512 + 512 * 256 + 256 * 128) + 27 * 27 * 128 + (
    479 * 1024 + 1024 * 1024 + 1024 * 512 + 512 * 256 + 256 * 1)
KAGGLE_MACS = (13 * 512 + 512 * 256 + 256 * 64 + 64 * 16) + 27 * 27 * 16 + (
    367 * 512 + 512 * 256 + 256 * 1)


# the DLRM reference's Criteo Kaggle widths (facebookresearch/dlrm
# bench/dlrm_s_criteo_kaggle.sh), to hold the functions at a narrow shape too
KAGGLE_WIDTHS = {"embedding_dim": 16, "bottom_mlp": [512, 256, 64, 16], "top_mlp": [512, 256, 1]}


@pytest.mark.parametrize("widths,macs,params", [
    ({}, MLPERF_MACS, 2_368_897),
    (KAGGLE_WIDTHS, KAGGLE_MACS, 475_985),
])
def test_counts_of_configuration(widths, macs, params):
    cfg = dict(harness.load_config("dlrm-mlperf-1tb"), **widths)
    assert len(cfg["table_rows"]) == 26 and sum(cfg["table_rows"]) == 187_767_399
    assert counts.forward_macs_per_sample(cfg) == macs
    assert counts.train_flops_per_sample(cfg) == 6.0 * macs
    assert counts.dense_param_count(cfg) == params


def test_mlperf_hand_values():
    assert MLPERF_MACS == 2_458_496  # about 2.4M multiply-adds forward
    cfg = harness.load_config("dlrm-mlperf-1tb")
    assert counts.train_flops_per_sample(cfg) == pytest.approx(14.75e6, rel=1e-3)
    # 106,496 rows x 512 B x 6 passes + 2,368,897 parameters x 4 B x 7
    assert counts.step_hbm_bytes(cfg, 4096) == 106_496 * 512 * 6 + 2_368_897 * 28


def test_floor_is_bound_by_hbm_on_the_v5e():
    cfg = harness.load_config("dlrm-mlperf-1tb")
    peaks = counts.load_peaks("TPU v5 lite")
    floor = counts.step_floor_seconds(cfg, 4096, peaks)
    assert floor["bound_by"] == "hbm_bytes"
    assert floor["seconds"] == pytest.approx(393_484_828 / 819e9)
    assert floor["flops_s"] == pytest.approx(14_750_976 * 4096 / 197e12)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(SystemExit):
        counts.load_peaks("TPU v99")
