"""Smoke tests for the BASELINE.json example harnesses: each runs a few
tiny steps end-to-end (real pipeline, synthetic streams) and must print a
finite loss/AUC without error."""

import importlib.util
import os
import sys

import numpy as np
import pytest

_EXAMPLES = os.path.join(os.path.dirname(__file__), "..", "examples")


def _load(rel):
    path = os.path.abspath(os.path.join(_EXAMPLES, rel))
    name = "example_" + rel.replace("/", "_").replace(".py", "")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def test_criteo_dlrm_smoke(capsys):
    mod = _load("criteo_dlrm/train.py")
    rc = mod.main(["--batch-size", "32", "--steps", "3", "--eval-steps", "1",
                   "--ps-replicas", "2"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "criteo-dlrm[kaggle]" in out and "test_auc=" in out


def test_criteo_dlrm_1tb_hashstack(capsys):
    mod = _load("criteo_dlrm/train.py")
    rc = mod.main(["--scale", "1tb", "--batch-size", "32", "--steps", "2",
                   "--eval-steps", "1", "--ps-replicas", "2"])
    assert rc == 0
    assert "criteo-dlrm[1tb]" in capsys.readouterr().out


@pytest.mark.parametrize("model", ["deepfm", "dcnv2"])
def test_avazu_smoke(capsys, model):
    mod = _load("avazu/train.py")
    rc = mod.main(["--model", model, "--batch-size", "32", "--steps", "3",
                   "--eval-steps", "1", "--ps-replicas", "1"])
    assert rc == 0
    assert f"avazu-{model}" in capsys.readouterr().out


def test_taobao_din_smoke(capsys):
    mod = _load("taobao_din/train.py")
    rc = mod.main(["--batch-size", "32", "--steps", "3", "--eval-steps", "1",
                   "--max-hist", "8", "--ps-replicas", "1"])
    assert rc == 0
    assert "taobao-din" in capsys.readouterr().out


def test_synthetic_100t_smoke(capsys, tmp_path):
    mod = _load("synthetic_100t/train.py")
    # --out to a tmp file: the default writes no file
    out_json = str(tmp_path / "bench_100t.json")
    rc = mod.main(["--batch-size", "32", "--steps", "2", "--num-slots", "4",
                   "--ids-per-sample", "2", "--ps-replicas", "8",
                   "--capacity-per-replica", "4096", "--out", out_json])
    assert rc == 0
    out = capsys.readouterr().out
    assert "synthetic-100t" in out and "100T params" in out
    import json

    artifact = json.load(open(out_json))
    assert artifact["capacity"]["bytes_per_row"] > 0
    assert artifact["throughput"]["ids_per_sec_through_router"] > 0


def test_datasets_deterministic():
    from persia_tpu.testing import CriteoSynthetic, TaobaoSynthetic

    a = list(CriteoSynthetic(num_samples=64, seed=5).batches(32))
    b = list(CriteoSynthetic(num_samples=64, seed=5).batches(32))
    np.testing.assert_array_equal(
        a[1].labels[0].data, b[1].labels[0].data
    )
    np.testing.assert_array_equal(
        a[1].id_type_features[3].data[0], b[1].id_type_features[3].data[0]
    )
    t = list(TaobaoSynthetic(num_samples=32, max_hist=8, seed=5).batches(32))
    # history slots are genuinely variable-length
    lens = {len(s) for s in t[0].id_type_features[2].data}
    assert len(lens) > 1


def test_datasets_auc_learnable():
    """The hidden ground truth must be learnable: ids repeated across
    batches carry consistent hashed weights."""
    from persia_tpu.testing.datasets import hash_to_unit

    ids = np.array([1, 2, 3, 2**63 - 1], dtype=np.uint64)
    w1 = hash_to_unit(ids, 7)
    w2 = hash_to_unit(ids, 7)
    np.testing.assert_array_equal(w1, w2)
    assert np.all(np.abs(w1) <= 1.0)
    assert len(np.unique(hash_to_unit(np.arange(1000, dtype=np.uint64), 7))) == 1000


def test_criteo_dlrm_cached_tier(capsys):
    """--tier cached: the capacity tier (HBM write-back cache + publish)
    drives the flagship example end to end; --scale 1tb additionally
    exercises the mixed-tier path (hash-stack slots on the worker/PS side)."""
    mod = _load("criteo_dlrm/train.py")
    rc = mod.main(["--batch-size", "32", "--steps", "3", "--eval-steps", "1",
                   "--ps-replicas", "2", "--tier", "cached"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "published" in out and "test_auc=" in out

    rc = mod.main(["--batch-size", "32", "--steps", "3", "--eval-steps", "1",
                   "--ps-replicas", "1", "--tier", "cached", "--scale", "1tb"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "criteo-dlrm[1tb]" in out and "test_auc=" in out


def test_criteo_dlrm_fused_tier(capsys):
    mod = _load("criteo_dlrm/train.py")
    rc = mod.main(["--tier", "fused", "--batch-size", "32", "--steps", "3",
                   "--eval-steps", "1", "--fused-vocab-cap", "512"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "criteo-dlrm[kaggle]" in out and "test_auc=" in out


def test_criteo_dlrm_fused_tier_file_data(capsys, tmp_path):
    fixture = os.path.join(os.path.dirname(__file__), "fixtures", "criteo_tiny.tsv")
    mod = _load("criteo_dlrm/train.py")
    rc = mod.main(["--tier", "fused", "--batch-size", "8", "--steps", "1",
                   "--eval-steps", "1", "--fused-vocab-cap", "256",
                   "--data-path", fixture])
    assert rc == 0
    assert "test_auc=" in capsys.readouterr().out


@pytest.mark.parametrize("model", ["deepfm", "dcnv2"])
def test_avazu_fused_tier(capsys, model):
    mod = _load("avazu/train.py")
    rc = mod.main(["--model", model, "--tier", "fused", "--batch-size", "32",
                   "--steps", "3", "--eval-steps", "1",
                   "--fused-vocab-cap", "512"])
    assert rc == 0
    assert f"avazu-{model}" in capsys.readouterr().out
