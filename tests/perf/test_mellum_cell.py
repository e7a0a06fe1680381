"""The cell ``mellum2-ep4-pack16k`` on the CPU: rehearsed through ``run_cell``
at its tiny preset (sound, both planted faults, the control, a traced
rehearsal), its traffic, its work counts against hand values, each of its
readers on a fixture, and its files against the catalog's row."""

import json
import os
import shutil
import time

import numpy as np
import pytest

import mellum_presets as presets
from perf import compare, harness, limits_one

ROOT = harness.ROOT
CELL = presets.CELL
CONFIG = "mellum2-12b-a2.5b-ep4"
SEED = 2 ** 31 + 31

# the catalog's row (model-configs guide): every number of its `config`
CATALOG = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu", "hidden_size": 2304,
    "intermediate_size": 7168,
    "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 7,
    "mlp_layer_types": ["sparse"] * 28,
    "max_position_embeddings": 131072, "max_window_layers": 0, "model_type": "mellum",
    "moe_intermediate_size": 896, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts": 64, "num_experts_per_tok": 8, "num_hidden_layers": 28, "num_key_value_heads": 4,
    "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                           "original_max_position_embeddings": 8192, "beta_fast": 32, "beta_slow": 1,
                           "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False, "vocab_size": 98304,
    "use_sliding_window": True,
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
    limits (``mellum_presets.REHEARSAL_LIMITS``); everything else is the cell's own."""
    root = str(tmp_path_factory.mktemp("mellum_cell"))
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
        assert len(out["by_leaf"]["grad"]) == 4 * 12 + 2  # one period's leaves and the top's
        assert "table" in out["by_leaf"]["change"]  # read after two steps: the rows by their change
        assert out["attempted"] > 0 and set(out["metrics"]) == {"samples_per_s_chip", "setup_s"}
    elif case in compare.FAULTS:  # each in a call of its own, as perf/limits_one.py runs them on the chip
        out = limits_one.fault(CELL, SEED, case, 0.3, rehearsal=presets.REHEARSAL, root=root)
        assert out["correct"] is False
        assert out["compared"]["grad_gap"][0] > 0.05  # half the positions, or no gradient at all
    elif case == "control":  # float8 operands in the reference's place: not correct
        verdict = limits_one.control(CELL, SEED, rehearsal=presets.REHEARSAL, root=root)
        assert verdict["control"] == "operands_float8_e4m3"
        assert verdict["correct"] is False, verdict["compared"]
    else:
        out = _rehearse(root, trace=True)
        assert out["correct"] is True and out["rehearsal"] is True
        # counters are read off the chip too; nothing of the device trace is
        assert 1.0 <= out["metrics"]["causal_expert_load_max_over_mean"]["value"] <= 4.0
        assert out["metrics"]["attention_tiles_visited_over_live"]["value"] == 1.0
        # ids, starts and labels int32, weights float32: 16 B a position
        assert out["metrics"]["h2d_bytes_per_sample"]["value"] == 64 * 16
        assert out["metrics"]["compiles_in_window"]["value"] == 0.0
        for name in ("interval_attention_roofline", "interval_attention_ms_per_step", "mfu",
                     "train_step_roofline", "device_ms_per_step", "attention_ms_per_step",
                     "expert_load_max_over_mean"):
            assert name not in out["metrics"]
        listed = {m["name"] for m in harness.cell_metrics(harness.load_benchmark(), CELL, "per_layer")}
        assert {"mfu", "train_step_roofline", "device_ms_per_step", "device_hbm_peak_gb",
                "interval_attention_roofline", "interval_attention_ms_per_step"} <= listed
        assert not {"step_ms_p95", "attention_roofline", "expert_load_max_over_mean"} & listed


def test_configuration_against_the_catalog_row():
    cfg = harness.load_config(CONFIG)
    bench = harness.load_benchmark()
    (entry,) = [c for c in bench["configs"] if c["name"] == cfg["name"]]
    reduced = set(entry["reduced"])
    assert reduced == {"num_hidden_layers", "num_experts", "vocab_size"}
    assert [line.split(":")[0] for line in cfg["reduced"]] == ["num_hidden_layers", "num_experts", "vocab_size"]
    for key, value in CATALOG.items():
        if key in reduced:
            assert cfg["published"][key] == value and cfg[key] < value, key
        else:
            assert cfg[key] == value, key  # no width, window, RoPE number or layer order differs
    # the floors: a whole period, eight experts held, an eighth of the vocabulary
    assert cfg["layer_types"][:cfg["num_hidden_layers"]] == ["sliding_attention"] * 3 + ["full_attention"]
    assert cfg["num_experts"] >= 8 and cfg["router_width"] == 64 and cfg["first_held_expert"] == 0
    assert cfg["vocab_size"] * 4 == CATALOG["vocab_size"]
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert 1 <= len(entry["why"]) <= 200 and entry["why"].isprintable()
    assert cfg["source"].startswith("https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/main/config.json")
    assert {"qk_norm", "routing", "packing", "multi_token_prediction", "intermediate_size",
            "initialisation", "optimizers", "router_initialisation", "auxiliary_loss"} <= set(cfg["assumed"])
    assert cfg["guarantees"]["math_dtype"] == "bfloat16" and cfg["deployment"] and cfg["model"] == "mellum_moe"
    # the bytes, reckoned again from the real leaf list
    from perf import mellum_weights

    shapes = dict(mellum_weights.layer_shapes(cfg))
    layer = sum(int(np.prod(shape)) for shape in shapes.values())
    top = sum(int(np.prod(shape)) for shape in mellum_weights.top_shapes(cfg).values())
    assert layer == 120_476_416 and cfg["num_hidden_layers"] * layer + top == 538_531_072
    work = harness.model_module("work", cfg)
    assert cfg["bytes"]["dense_parameters"] == work.dense_param_count(cfg) == 538_531_072
    assert cfg["bytes"]["dense_state_bytes"] == 538_531_072 * 12
    assert cfg["bytes"]["dense_gradient_bytes"] == 538_531_072 * 4
    assert cfg["bytes"]["token_table_and_accumulator_bytes"] == 24_576 * 2304 * 4 * 2
    traffic = harness.load_traffic("pack16k-docs7-b1")
    assert (traffic["batch"], traffic["seq_len"], traffic["warmup_steps"]) == (1, 16384, 5)
    assert traffic["doc_lengths"] == [8192, 4096, 2048, 1024, 512, 256, 256]
    limits = json.load(open(os.path.join(ROOT, "perf", "limits", f"{CELL}.json")))
    assert set(limits["limits"]) <= set(limits["readings"])  # every limit has its reason


@pytest.mark.parametrize("law,what", [("repeated_columns", "repeated"), ("plain", "drawn")])
def test_the_routers_law_is_the_configurations(law, what):
    from perf import mellum_weights

    cfg = dict(harness.load_config(CONFIG), hidden_size=256, moe_intermediate_size=64, router_law=law)
    x = mellum_weights.leaf(cfg, 2 ** 31 + 5, "L2.router")
    assert x.shape == (256, 64) and x.std() == pytest.approx(0.02, rel=0.05)
    if what == "repeated":  # column e is column e mod held: two of a token's eight picks on every share
        np.testing.assert_array_equal(x, np.tile(x[:, :16], (1, 4)))
        logits = np.random.default_rng(0).standard_normal((50, 256)) @ x
        picks = np.argsort(-logits, axis=1, kind="stable")[:, :8]
        assert ((picks < 16).sum(axis=1) == 2).all()
    else:
        assert len(np.unique(x, axis=1).T) == 64
    assert mellum_weights.leaf(cfg, 2 ** 31 + 5, "L2.wq").std() == pytest.approx(0.02, rel=0.02)
    with pytest.raises(ValueError, match="router_law"):
        mellum_weights.leaf(dict(cfg, router_law="other"), 1, "L0.router")


@pytest.mark.parametrize("what", ["documents", "labels", "seeded", "halved"])
def test_the_traffic(what):
    cfg, tr = harness.load_config(CONFIG), harness.load_traffic("pack16k-docs7-b1")
    gen = harness.load_module("generators", tr["generator"])
    first, second = (next(gen.make(cfg, tr, s)) for s in (SEED, SEED))
    b = first
    if what == "documents":  # the seven lengths in an order of the seed's, anew every step
        stream = gen.make(cfg, tr, SEED)
        orders = [tuple(next(stream)["doc_lengths"][0]) for _ in range(6)]
        assert all(sorted(o) == sorted(tr["doc_lengths"]) for o in orders) and len(set(orders)) > 1
        assert b["ids"].shape == (1, 16384) and 0 <= b["ids"].min() and b["ids"].max() < 24576
    elif what == "labels":  # the next token; weight 0 at each document's last position
        np.testing.assert_array_equal(b["labels"][0, :-1], b["ids"][0, 1:])
        ends = np.cumsum(b["doc_lengths"][0]) - 1
        assert b["labels"].dtype == np.int32 and ends[-1] == 16383
        assert (b["weights"][0, ends] == 0).all() and b["weights"].sum() == 16384 - 7
    elif what == "seeded":
        for k in b:
            np.testing.assert_array_equal(b[k], second[k])
        other = next(gen.make(cfg, tr, SEED + 1))
        assert (other["ids"] != b["ids"]).any()
    else:  # the planted fault: the first half of the positions, the straddling document cut
        h = gen.halve(b)
        assert h["ids"].shape == (1, 8192) and h["doc_lengths"].sum() == 8192
        assert h["weights"][0, -1] == 0 and (h["doc_lengths"] <= b["doc_lengths"]).all()
        np.testing.assert_array_equal(h["labels"][0, :-1], b["labels"][0, :8191])


def test_work_counts_hand_values():
    cfg, tr = harness.load_config(CONFIG), harness.load_traffic("pack16k-docs7-b1")
    work = harness.model_module("work", cfg)
    products = 2304 * 4096 + 2 * 2304 * 512 + 4096 * 2304 + 2304 * 64  # q, k and v, o, router
    assert work.layer_product_macs(cfg) == products == 21_381_120
    assert work.expert_macs_per_pick(cfg) == 3 * 2304 * 896 == 6_193_152
    assert work.held_picks_per_position(cfg) == 2.0  # 8 picks x 16 held of 64
    # position j of a document reads j + 1 keys, min(j + 1, 1024) of them under the window
    docs = tr["doc_lengths"]
    full = sum(j + 1 for n in docs for j in range(n))
    sliding = sum(min(j + 1, 1024) for n in docs for j in range(n))
    assert work.live_pairs(cfg, tr, "full_attention") == full == 44_769_280
    assert work.live_pairs(cfg, tr, "sliding_attention") == sliding == 13_830_656
    attention = 4 * 128 * 32 * (full + 3 * sliding)
    layers = 2 * (products + 2 * 6_193_152) * 16384 * 4
    head = 2 * 16384 * 2304 * 24576
    assert (attention, layers, head) == (1_413_304_287_232, 4_425_963_798_528, 1_855_425_871_872)
    assert work.train_flops_per_sample(cfg, tr) == 3 * (layers + attention + head) == 23_084_081_872_896
    dense, rows = 538_531_072 * 28, 16384 * 2304 * 4 * 6
    stream, logits = 4 * 16384 * 2304 * 4 * 2, 16384 * 24576 * 4 * 4
    assert work.step_hbm_bytes(cfg, tr) == dense + rows + stream + logits == 23_635_250_176
    kernels = work.attention_kernel_work(cfg, tr)
    assert kernels["flops"] == 3 * attention == 4_239_912_861_696
    assert kernels["bytes"] == 3 * 16384 * (2 * 32 + 2 * 4) * 128 * 2 * 4
    # the floor of a step: FLOPs bound it, 0.117 s at the bfloat16 peak
    from perf import counts

    floor = counts.step_floor_seconds(cfg, tr, counts.load_peaks("TPU v5 lite"))
    assert floor["bound_by"] == "flops" and floor["seconds"] == pytest.approx(0.1172, rel=1e-3)


# op labels as a traced run of the cell on the v5e prints them
OP_S = {
    "interval_attention_fwd_bf16_1_16384_4096_": 0.40,
    "interval_attention_dq_bf16_1_16384_4096_": 0.20,
    "interval_attention_dkv_bf16_1_16384_512_": 0.30,
    "block_diffusion_attention_fwd_bf16_2_8192_4096_": 7.0,  # another cell's kernels: not read here
    "ragged_dot_none_f32_36864_896_": 0.06, "fusion_f32_1_16384_2304_": 1.5,
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
    ("interval_attention_ms_per_step", 0.90 / 4 * 1e3),
    ("interval_attention_roofline", 100 * (4_239_912_861_696 / 197e12) / (0.90 / 4)),
])
def test_kernel_readers_on_a_fixture(name, want):
    trace = {"steps": 4, "op_s": OP_S}
    assert _read(name, _facts(trace)) == pytest.approx(want, rel=1e-9)
    # a program without these kernels (the parent), or an untraced run: nothing, and no error
    assert _read(name, _facts({"steps": 4, "op_s": {"fusion_f32_4096_128_": 1.0}})) is None
    assert _read(name, _facts(None)) is None


@pytest.mark.parametrize("name,counters,want", [
    ("attention_tiles_visited_over_live", {"attention_tiles": [[300, 270], [200, 180]]}, 500 / 450),
    ("attention_tiles_visited_over_live", {"attention_tiles": [[0, 0], [0, 0]]}, None),
    ("attention_tiles_visited_over_live", {"h2d_bytes": 1}, None),
    ("causal_expert_load_max_over_mean", {"expert_picks": [[10, 10, 10, 10], [4, 28, 4, 4]]}, 28 * 4 / 40),
    ("causal_expert_load_max_over_mean", {"h2d_bytes": 1}, None),
])
def test_counter_readers_on_a_fixture(name, counters, want):
    assert _read(name, _facts(None, counters)) == want
    assert harness.load_metric(name)["name"] == name
    (listed,) = [m for m in harness.load_benchmark()["per_layer"] if m["name"] == name]
    assert listed["workloads"] == [CELL] and listed["moves"] == "samples_per_s_chip"
