"""The cell ``sdar-ep8-bd4-seq4k`` on the CPU: rehearsed through ``run_cell``
at its tiny preset (sound, both planted faults, the control, a traced
rehearsal), its work counts against hand values, each of its readers on a
fixture, and its files against the catalog's row."""

import json
import os
import shutil
import time

import numpy as np
import pytest

import sdar_presets as presets
from perf import compare, harness, limits_one

ROOT = harness.ROOT
CELL = presets.CELL
SEED = 2 ** 31 + 29

# the catalog's row (model-configs guide): every number of its `config`
CATALOG = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144, "max_position_embeddings": 32768,
    "max_window_layers": 48, "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48, "num_key_value_heads": 4,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False, "vocab_size": 151936,
}


@pytest.fixture(autouse=True)
def _restore_matmul_precision():
    import jax

    before = jax.config.jax_default_matmul_precision
    yield
    jax.config.update("jax_default_matmul_precision", before)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A copy of the benchmark in which the cell is judged by the rehearsal's
    limits (``sdar_presets.REHEARSAL_LIMITS``); everything else is the cell's own."""
    root = str(tmp_path_factory.mktemp("sdar_cell"))
    shutil.copytree(os.path.join(ROOT, "perf"), os.path.join(root, "perf"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    with open(os.path.join(root, "perf", "limits", f"{CELL}.json"), "w") as f:
        json.dump({"workload": CELL, "limits": presets.REHEARSAL_LIMITS}, f)
    return root


def _rehearse(root, trace=False):
    return harness.run_cell(CELL, SEED, 0.3, trace, time.perf_counter(), rehearsal=presets.REHEARSAL, root=root)


@pytest.mark.parametrize("case", ["sound", "half_batch", "state_unchanged", "control", "traced"])
def test_cell_rehearsed_through_run_cell(root, case):
    if case == "sound":
        out = _rehearse(root)
        assert out["correct"] is True, out["compared"]
        assert set(out["compared"]) == set(compare.load_limits(CELL))  # the numbers the cell limits
        assert len(out["by_leaf"]["grad"]) == 2 * 12 + 2  # two layers' leaves and the top's
        assert "table" in out["by_leaf"]["change"]  # read after two steps: the rows by their change
        assert out["attempted"] > 0 and set(out["metrics"]) == {"samples_per_s_chip", "setup_s"}
    elif case in compare.FAULTS:  # each in a call of its own, as perf/limits_one.py runs them on the chip
        out = limits_one.fault(CELL, SEED, case, 0.3, rehearsal=presets.REHEARSAL, root=root)
        assert out["correct"] is False
        assert out["compared"]["grad_gap"][0] > 0.05  # half the sequences, or no gradient at all
    elif case == "control":  # float8 operands in the reference's place: not correct
        verdict = limits_one.control(CELL, SEED, rehearsal=presets.REHEARSAL, root=root)
        assert verdict["control"] == "operands_float8_e4m3"
        assert verdict["correct"] is False, verdict["compared"]
    else:
        out = _rehearse(root, trace=True)
        assert out["correct"] is True and out["rehearsal"] is True
        # counters are read off the chip too; nothing of the device trace is
        ratio = out["metrics"]["expert_load_max_over_mean"]["value"]
        assert 1.0 <= ratio <= 8.0
        assert out["metrics"]["h2d_bytes_per_sample"]["value"] == 2 * 32 * 4 + 32 * 4 + 32 * 4
        assert out["metrics"]["compiles_in_window"]["value"] == 0.0
        for name in ("attention_roofline", "attention_ms_per_step", "mfu", "train_step_roofline",
                     "device_ms_per_step"):
            assert name not in out["metrics"]
        # a traced slice of 4 s holds about six steps of 0.65 s, and the harness gives a
        # 95th percentile from 20 on: the metric lists the cells whose traces hold that many
        listed = {m["name"] for m in harness.cell_metrics(harness.load_benchmark(), CELL, "per_layer")}
        assert "step_ms_p95" not in listed and {"mfu", "train_step_roofline", "device_ms_per_step"} <= listed


def test_configuration_against_the_catalog_row():
    cfg = harness.load_config("sdar-30b-a3b-ep8")
    bench = harness.load_benchmark()
    (entry,) = [c for c in bench["configs"] if c["name"] == cfg["name"]]
    reduced = set(entry["reduced"])
    assert reduced == {"num_hidden_layers", "num_experts", "vocab_size"}
    assert [line.split(":")[0] for line in cfg["reduced"]] == ["num_hidden_layers", "num_experts", "vocab_size"]
    for key, value in CATALOG.items():
        if key in reduced:
            assert cfg["published"][key] == value and cfg[key] < value, key
        else:
            assert cfg[key] == value, key  # no width differs
    # the floors: four layers, eight experts held, an eighth of the vocabulary
    assert cfg["num_hidden_layers"] >= 4 and cfg["num_experts"] >= 8
    assert cfg["vocab_size"] * 8 == CATALOG["vocab_size"] and cfg["router_width"] == 128
    assert len(cfg["source"]) <= 200 and cfg["source"].startswith("https://huggingface.co/JetLM/SDAR-30B-A3B-Chat")
    assert {"block_length", "noise_law", "qk_norm", "initialisation", "optimizers"} <= set(cfg["assumed"])
    assert cfg["guarantees"]["math_dtype"] == "bfloat16" and cfg["deployment"]
    work = harness.model_module("work", cfg)
    assert cfg["bytes"]["dense_parameters"] == work.dense_param_count(cfg) == 606_727_680
    assert cfg["bytes"]["dense_state_bytes"] == 606_727_680 * 12
    assert cfg["bytes"]["token_table_and_accumulator_bytes"] == 18_992 * 2048 * 4 * 2
    traffic = harness.load_traffic("bd4-seq4k-b2")
    assert (traffic["batch"], traffic["seq_len"], traffic["warmup_steps"]) == (2, 4096, 5)
    limits = json.load(open(os.path.join(ROOT, "perf", "limits", f"{CELL}.json")))
    assert set(limits["limits"]) <= set(limits["readings"])  # every limit has its reason


@pytest.mark.parametrize("leaf,what", [
    ("L1.wq", "drawn"), ("L1.wo", "drawn"), ("L2.down", "drawn"), ("L0.q_norm", "one"),
    ("L3.norm2", "one"), ("norm_f", "one"), ("L4.router", "repeated")])
def test_the_weights_law(leaf, what):
    """``perf/sdar_weights.py``: what the seed draws, and the router's columns, which it does not."""
    from perf import sdar_weights

    cfg = dict(harness.load_config("sdar-30b-a3b-ep8"), hidden_size=256, moe_intermediate_size=64)
    x = sdar_weights.leaf(cfg, 2 ** 31 + 5, leaf)
    if what == "drawn":
        assert x.std() == pytest.approx(0.02, rel=0.02) and abs(x.mean()) < 1e-3
    elif what == "one":
        assert (x == 1.0).all()
    else:  # column e is column e mod held: a token's picks are one on every share
        assert x.shape == (256, 128) and len(np.unique(x[:, :16], axis=1).T) == 16
        np.testing.assert_array_equal(x, np.tile(x[:, :16], (1, 8)))


def test_work_counts_hand_values():
    cfg, tr = harness.load_config("sdar-30b-a3b-ep8"), harness.load_traffic("bd4-seq4k-b2")
    work = harness.model_module("work", cfg)
    products = 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048 + 2048 * 128  # q, k and v, o, router
    assert work.layer_product_macs(cfg) == products == 19_136_512
    assert work.expert_macs_per_pick(cfg) == 3 * 2048 * 768 == 4_718_592
    assert work.held_picks_per_position(cfg) == 1.0  # 8 picks x 16 held of 128
    # a noised query reads b + (i // b) b keys, a clean one (i // b + 1) b: L (L + b) a head
    assert work.live_pairs(cfg, tr) == 4096 * 4100 == sum(
        4 + (i // 4) * 4 + (i // 4 + 1) * 4 for i in range(4096))
    layer = 2 * (products + 4_718_592) * 8192 + 4 * 128 * 32 * 4096 * 4100
    head = 2 * 4096 * 2048 * 18992
    assert (layer, head) == (665_988_366_336, 318_632_886_272)
    assert work.train_flops_per_sample(cfg, tr) == 3 * (6 * layer + head) == 12_943_689_252_864
    dense, rows = 606_727_680 * 28, 2 * 8192 * 2048 * 4 * 6
    stream, logits = 6 * 2 * 8192 * 2048 * 4 * 2, 2 * 4096 * 18992 * 4 * 4
    assert work.step_hbm_bytes(cfg, tr) == dense + rows + stream + logits == 21_893_613_568
    attention = work.attention_kernel_work(cfg, tr)
    assert attention["flops"] == 3 * 4 * 128 * 32 * 4096 * 4100 * 2 * 6 == 9_905_268_326_400
    assert attention["bytes"] == 3 * 2 * 8192 * (2 * 32 + 2 * 4) * 128 * 2 * 6
    # the floor of a step: FLOPs bound it, 0.13 s at the bfloat16 peak
    from perf import counts

    floor = counts.step_floor_seconds(cfg, tr, counts.load_peaks("TPU v5 lite"))
    assert floor["bound_by"] == "flops" and floor["seconds"] == pytest.approx(0.1314, rel=1e-3)


# op labels as a traced run of the cell on the v5e printed them (my chip run, PR 33)
OP_S = {
    "block_diffusion_attention_fwd_bf16_2_8192_4096_": 0.40,
    "block_diffusion_attention_dq_bf16_2_8192_4096_": 0.20,
    "block_diffusion_attention_dkv_bf16_2_8192_512_": 0.30,
    "ragged_dot_none_f32_16384_768_": 0.06, "ragged_dot_none_f32_16_2048_768_": 0.03,
    "ragged_dot_metadata_s32_17_": 0.01, "fusion_f32_2_8192_2048_": 1.5,
}


def _facts(trace, counters=None):
    cell = harness.find_cell(harness.load_benchmark(), CELL)
    return {"cell": cell, "config": harness.load_config(cell["config"]),
            "traffic": harness.load_traffic(cell["traffic"]), "trace": trace,
            "counters": counters or {}, "root": ROOT, "chips": 1,
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


def _read(name, facts):
    return harness.load_module("readers", harness.load_metric(name)["reader"]).read(facts)


@pytest.mark.parametrize("name,want", [
    ("attention_ms_per_step", 0.90 / 4 * 1e3),
    ("attention_roofline", 100 * (9_905_268_326_400 / 197e12) / (0.90 / 4)),
])
def test_kernel_readers_on_a_fixture(name, want):
    trace = {"steps": 4, "op_s": OP_S}
    assert _read(name, _facts(trace)) == pytest.approx(want, rel=1e-9)
    # a program without these kernels (the parent), or an untraced run: nothing, and no error
    assert _read(name, _facts({"steps": 4, "op_s": {"fusion_f32_4096_128_": 1.0}})) is None
    assert _read(name, _facts(None)) is None


def test_expert_load_reader_on_a_fixture():
    picks = [[10, 10, 10, 10], [4, 28, 4, 4], [0, 0, 0, 0]]  # by layer and held expert
    assert _read("expert_load_max_over_mean", _facts(None, {"expert_picks": picks})) == 28 * 4 / 40
    assert _read("expert_load_max_over_mean", _facts(None, {"h2d_bytes": 1})) is None
