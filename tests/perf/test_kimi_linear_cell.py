"""The cell ``kimi-linear-ep32-pack16k`` on the CPU: rehearsed through
``run_cell`` at its tiny preset (sound, both planted faults, the control, a
traced rehearsal), its traffic, its work counts against hand values, each of
its readers on a fixture, and its files against the catalog's row."""

import itertools
import json
import os
import shutil
import time

import numpy as np
import pytest

import kimi_linear_presets as presets
from perf import compare, harness, kimi_linear_weights, limits_one

ROOT = harness.ROOT
CELL = presets.CELL
CONFIG = "kimi-linear-48b-a3b-ep32"
TRAFFIC = "pack16k-docs7-ragged-b1"
SEED = 2 ** 31 + 31

# the catalog's row (model-configs guide): every number of its `config`
CATALOG = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu", "hidden_size": 2304,
    "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {"full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
                           "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22, 23, 25, 26],
                           "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576, "model_type": "kimi_linear",
    "moe_intermediate_size": 1024, "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32, "num_expert_group": 1,
    "num_experts": 256, "num_experts_per_token": 8, "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1, "q_lora_rank": None, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False, "topk_group": 1,
    "use_grouped_topk": True, "v_head_dim": 128, "vocab_size": 163840,
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
    limits (``kimi_linear_presets.REHEARSAL_LIMITS``); everything else is the cell's own."""
    root = str(tmp_path_factory.mktemp("kimi_linear_cell"))
    shutil.copytree(os.path.join(ROOT, "perf"), os.path.join(root, "perf"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    with open(os.path.join(root, "perf", "limits", f"{CELL}.json"), "w") as f:
        json.dump({"workload": CELL, "limits": presets.REHEARSAL_LIMITS}, f)
    return root


def _rehearse(root, trace=False):
    return harness.run_cell(CELL, SEED, 0.3, trace, time.perf_counter(), rehearsal=presets.REHEARSAL, root=root)


# a rehearsal traces and compiles the tower and its reference anew, 40 to 60 s each on the CPU: the
# sound one stays in tier-1; the faults, the control and the traced one run with the slow tests
@pytest.mark.parametrize("case", ["sound"] + [pytest.param(c, marks=pytest.mark.slow) for c in (
    "half_batch", "state_unchanged", "control", "traced")])
def test_cell_rehearsed_through_run_cell(root, case):
    if case == "sound":
        out = _rehearse(root)
        assert out["correct"] is True, out["compared"]
        assert set(out["compared"]) == set(compare.load_limits(CELL))  # the numbers the cell limits
        # the leading layer's 20 leaves, three KDA expert layers' 24, the MLA one's 14, the top's 2
        assert len(out["by_leaf"]["grad"]) == 20 + 3 * 24 + 14 + 2
        assert "table" in out["by_leaf"]["change"]  # read after two steps: the rows by their change
        assert out["attempted"] > 0 and set(out["metrics"]) == {"samples_per_s_chip", "setup_s"}
    elif case in compare.FAULTS:  # each in a call of its own, as perf/limits_one.py runs them on the chip
        out = limits_one.fault(CELL, SEED, case, 0.3, rehearsal=presets.REHEARSAL, root=root)
        assert out["correct"] is False
        assert out["compared"]["grad_gap"][0] > 0.2  # half the positions, or no gradient at all
    elif case == "control":  # float8 operands in the reference's place: not correct
        verdict = limits_one.control(CELL, SEED, rehearsal=presets.REHEARSAL, root=root)
        assert verdict["control"] == "operands_float8_e4m3"
        assert verdict["correct"] is False, verdict["compared"]
    else:
        out = _rehearse(root, trace=True)
        assert out["correct"] is True and out["rehearsal"] is True
        # counters are read off the chip too; nothing of the device trace is
        assert 0.0 <= out["metrics"]["held_load_off_even"]["value"] < 1.0
        # ids, starts and labels int32, weights float32: 16 B a position
        assert out["metrics"]["h2d_bytes_per_sample"]["value"] == 64 * 16
        assert out["metrics"]["compiles_in_window"]["value"] == 0.0
        for name in ("kda_ms_per_step", "kda_roofline", "latent_attention_ms_per_step",
                     "latent_attention_roofline", "mfu", "train_step_roofline", "device_ms_per_step"):
            assert name not in out["metrics"]
        listed = {m["name"] for m in harness.cell_metrics(harness.load_benchmark(), CELL, "per_layer")}
        assert listed >= {"gen_wait_share", "h2d_bytes_per_sample", "compiles_in_window", "device_ms_per_step",
                          "train_step_roofline", "mfu", "device_idle_share", "device_hbm_peak_gb",
                          "kda_ms_per_step", "kda_roofline", "latent_attention_ms_per_step",
                          "latent_attention_roofline", "held_load_off_even"}


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
            assert cfg[key] == value, key  # no width, rank, pattern or routing number differs
    # the floors: the leading layer and a whole period, eight experts held, an eighth of the vocabulary
    assert kimi_linear_weights.layer_kinds(cfg) == [
        ("kda", "dense"), ("kda", "shared_experts"), ("kda", "shared_experts"), ("mla", "shared_experts"),
        ("kda", "shared_experts")]
    assert cfg["num_experts"] == 8 and cfg["router_width"] == 256 and cfg["first_held_expert"] == 0
    assert cfg["vocab_size"] * 8 == CATALOG["vocab_size"]
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert 1 <= len(entry["why"]) <= 200 and entry["why"].isprintable()
    assert cfg["source"] == "https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json"
    assert {"kda_low_rank", "kda_decay", "kda_norms", "kda_convolution", "mla", "routing", "packing",
            "initialisation", "optimizers", "router_initialisation", "matmul_precision"} <= set(cfg["assumed"])
    assert cfg["guarantees"]["math_dtype"] == "bfloat16" and "delta_rule" in cfg["guarantees"]
    assert cfg["deployment"] and cfg["model"] == "kimi_linear_moe"
    # the bytes, reckoned again from the real leaf list
    count = lambda kind, mlp: sum(int(np.prod(s)) for s in kimi_linear_weights.layer_shapes(cfg, kind, mlp).values())
    assert count("kda", "dense") == 39_514_272 + 63_700_992 + 4_608 == 103_219_872
    assert count("kda", "shared_experts") == 39_514_272 + 589_824 + 9 * 7_077_888 + 4_608 == 103_809_696
    assert count("mla", "shared_experts") == 93_410_304
    top = sum(int(np.prod(s)) for s in kimi_linear_weights.top_shapes(cfg).values())
    assert top == 47_185_920 + 2_304
    total = 103_219_872 + 3 * 103_809_696 + 93_410_304 + top
    work = harness.model_module("work", cfg)
    assert cfg["bytes"]["dense_parameters"] == work.dense_param_count(cfg) == total == 555_247_488
    assert cfg["bytes"]["dense_state_bytes"] == 555_247_488 * 12
    assert cfg["bytes"]["dense_gradient_bytes"] == 555_247_488 * 4
    assert cfg["bytes"]["token_table_and_accumulator_bytes"] == 20_480 * 2304 * 4 * 2
    traffic = harness.load_traffic(TRAFFIC)
    assert (traffic["batch"], traffic["seq_len"], traffic["warmup_steps"]) == (1, 16384, 5)
    assert traffic["doc_lengths"] == [8195, 4101, 2057, 1041, 499, 246, 245]
    limits = json.load(open(os.path.join(ROOT, "perf", "limits", f"{CELL}.json")))
    assert set(limits["limits"]) <= set(limits["readings"])  # every limit has its reason


def test_the_tower_holds_the_weights_files_leaves():
    """Shape for shape: what ``KimiLinearMoE.from_config`` holds is what the
    weights' law makes (no array is built: shapes only)."""
    import jax

    from persia_tpu.models import KimiLinearMoE

    cfg = harness.load_config(CONFIG)
    model = KimiLinearMoE.from_config(cfg, head_chunk=int(cfg["head_chunk"]))
    made = jax.eval_shape(lambda: kimi_linear_weights.dense_tree(cfg, jax.numpy.zeros((2,), jax.numpy.uint32),
                                                                 jax.numpy))
    is_shape = lambda x: isinstance(x, tuple) and (not x or isinstance(x[0], int))
    want = jax.tree.map(lambda s: tuple(s), model.param_shapes(), is_leaf=is_shape)
    assert jax.tree.map(lambda x: x.shape, made) == want
    assert model.pick_chunk(16384) == 8192 and model.counters()["expert_picks"].shape == (4, 8)
    assert sorted(kimi_linear_weights.leaves_by_name(jax.tree.map(lambda x: np.zeros(x.shape[-1:]), made), cfg)) \
        == sorted(kimi_linear_weights.leaf_names(cfg))


@pytest.mark.parametrize("law", ["mirrored_copies", "plain"])
def test_the_routers_law_is_the_configurations(law):
    cfg = dict(harness.load_config(CONFIG), hidden_size=256, moe_intermediate_size=64, router_law=law)
    x = kimi_linear_weights.leaf(cfg, 2 ** 31 + 5, "L2.router")
    assert x.shape == (256, 256) and len(np.unique(x, axis=1).T) == 256  # no two columns tie
    by_share = x.reshape(256, 32, 8)  # column e is share e // 8's slot e % 8
    if law == "mirrored_copies":  # slots j and j + 4: base j plus and minus the share's own draw
        base, own = (by_share[:, :, :4] + by_share[:, :, 4:]) / 2, (by_share[:, :, :4] - by_share[:, :, 4:]) / 2
        np.testing.assert_allclose(base, np.broadcast_to(base[:, :1], base.shape), atol=1e-7)
        assert base.std() == pytest.approx(0.02, rel=0.1) and own.std() == pytest.approx(0.005, rel=0.1)
        # of a share's mirrored pair a token takes the one on its side, never both
        logits = np.random.default_rng(0).standard_normal((40, 256)) @ x
        picks = np.argsort(-logits, axis=1)[:, :8]
        assert all(len({(e // 8, e % 4) for e in row}) == 8 for row in picks)
    else:
        assert x.std() == pytest.approx(0.02, rel=0.05) and abs(np.corrcoef(x[:, 0], x[:, 8])[0, 1]) < 0.3
    with pytest.raises(ValueError, match="router_law"):
        kimi_linear_weights.leaf(dict(cfg, router_law="other"), 1, "L1.router")


def test_the_familys_own_leaves_start_as_stated():
    cfg = harness.load_config(CONFIG)
    a = np.exp(kimi_linear_weights.leaf(cfg, SEED, "L0.a_log"))
    assert a.shape == (32,) and 1.0 <= a.min() and a.max() < 16.0
    dt = np.log1p(np.exp(kimi_linear_weights.leaf(cfg, SEED, "L2.dt_bias").astype(np.float64)))
    assert dt.shape == (4096,) and 0.001 * 0.999 <= dt.min() and dt.max() <= 0.1 * 1.001
    taps = kimi_linear_weights.leaf(cfg, SEED, "L4.conv_k")
    assert taps.shape == (4, 4096) and np.abs(taps).max() <= 0.5 and taps.std() == pytest.approx(0.5 / np.sqrt(3), rel=0.05)
    assert (kimi_linear_weights.leaf(cfg, SEED, "L3.kv_norm") == 1).all()
    assert kimi_linear_weights.leaf(cfg, SEED, "L3.wkv_b").std() == pytest.approx(0.02, rel=0.02)


@pytest.mark.parametrize("what", ["documents", "ragged", "labels", "halved"])
def test_the_traffic(what):
    cfg, tr = harness.load_config(CONFIG), harness.load_traffic(TRAFFIC)
    gen = harness.load_module("generators", tr["generator"])
    b = next(gen.make(cfg, tr, SEED))
    if what == "documents":  # the seven lengths in an order of the seed's, anew every step
        stream = gen.make(cfg, tr, SEED)
        orders = [tuple(next(stream)["doc_lengths"][0]) for _ in range(6)]
        assert all(sorted(o) == sorted(tr["doc_lengths"]) for o in orders) and len(set(orders)) > 1
        assert b["ids"].shape == (1, 16384) and 0 <= b["ids"].min() and b["ids"].max() < 20480
    elif what == "ragged":  # no proper subset sums to a multiple of 64: every later start is inside a chunk and a tile
        docs = tr["doc_lengths"]
        assert sum(docs) == 16384
        assert not [s for r in range(1, 7) for s in itertools.combinations(docs, r) if sum(s) % 64 == 0]
    elif what == "labels":
        np.testing.assert_array_equal(b["labels"][0, :-1], b["ids"][0, 1:])
        ends = np.cumsum(b["doc_lengths"][0]) - 1
        assert (b["weights"][0, ends] == 0).all() and b["weights"].sum() == 16384 - 7
    else:
        h = gen.halve(b)
        assert h["ids"].shape == (1, 8192) and h["doc_lengths"].sum() == 8192


def test_work_counts_hand_values():
    cfg, tr = harness.load_config(CONFIG), harness.load_traffic(TRAFFIC)
    work = harness.model_module("work", cfg)
    d = 2304
    kda = 3 * d * 4096 + 2 * (d * 128 + 128 * 4096) + d * 32 + 4096 * d  # q k v, decay and gate low rank, beta, o
    mla = d * 32 * 192 + d * 576 + 512 * 32 * 256 + 4096 * d
    assert work.attention_product_macs(cfg, "kda") == kda == 39_460_864
    assert work.attention_product_macs(cfg, "mla") == mla == 29_114_368
    assert work.state_macs_per_position(cfg) == 3 * 128 * 128 * 32 == 1_572_864
    assert work.mlp_macs(cfg, "dense") == 3 * d * 9216 == 63_700_992
    # the router at 256, the shared expert, and 8 x 8 / 256 = a quarter of a held pick a position
    assert work.mlp_macs(cfg, "shared_experts") == d * 256 + 1.25 * 3 * d * 1024 == 9_437_184
    pairs = sum(j + 1 for n in tr["doc_lengths"] for j in range(n))
    assert work.live_pairs(tr) == pairs == 44_838_541
    latent = 2 * (192 + 128) * 32 * pairs
    assert work.latent_attention_forward_flops(cfg, tr) == latent == 918_293_319_680
    t = 16384
    layers = 2 * t * (4 * (kda + 1_572_864) + mla + 63_700_992 + 4 * 9_437_184) + latent
    head = 2 * t * d * 20480
    assert work.train_flops_per_sample(cfg, tr) == 3 * (layers + head) == 36_363_535_921_152
    dense, rows = 555_247_488 * 28, t * d * 4 * 6
    stream, logits = 5 * t * d * 4 * 2, t * 20480 * 4 * 4
    assert work.step_hbm_bytes(cfg, tr) == dense + rows + stream + logits == 23_331_557_888
    k = work.kda_kernel_work(cfg, tr)
    assert k["flops"] == 3 * 2 * 1_572_864 * t * 4 == 618_475_290_624
    assert k["bytes"] == 2 * t * 32 * (5 * 128 + 1) * 4 * 4 == 10_754_195_456
    la = work.latent_attention_kernel_work(cfg, tr)
    assert la["flops"] == 3 * latent and la["bytes"] == 3 * t * (32 * 192 + 32 * 128 + 64 + 2 * 4096) * 2
    from perf import counts

    floor = counts.step_floor_seconds(cfg, tr, counts.load_peaks("TPU v5 lite"))
    assert floor["bound_by"] == "flops" and floor["seconds"] == pytest.approx(0.18459, rel=1e-3)


# op labels as a traced run of the cell on the v5e prints them
OP_S = {
    "kda_chunk_fwd_f32_1_32_256_64_128_": 0.50, "kda_chunk_bwd_bf16_1_32_256_64_128_": 0.70,
    "interval_attention_fwd_bf16_1_16384_4096_": 0.10, "interval_attention_dq_bf16_1_16384_4096_": 0.06,
    "interval_attention_dkv_bf16_1_16384_4096_": 0.08,
    "grouped_matmul_f32_4608_1024_": 0.06, "fusion_f32_1_16384_2304_": 1.5,
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
    ("kda_ms_per_step", 1.20 / 4 * 1e3),
    ("kda_roofline", 100 * (10_754_195_456 / 819e9) / (1.20 / 4)),  # the bytes bound it
    ("latent_attention_ms_per_step", 0.24 / 4 * 1e3),
    ("latent_attention_roofline", 100 * (3 * 918_293_319_680 / 197e12) / (0.24 / 4)),
])
def test_kernel_readers_on_a_fixture(name, want):
    trace = {"steps": 4, "op_s": OP_S}
    assert _read(name, _facts(trace)) == pytest.approx(want, rel=1e-9)
    # a program without these kernels (the parent), or an untraced run: nothing, and no error
    assert _read(name, _facts({"steps": 4, "op_s": {"fusion_f32_4096_128_": 1.0}})) is None
    assert _read(name, _facts(None)) is None


@pytest.mark.parametrize("counters,want", [
    ({"held_picks_over_even": [1.0, 1.04, 0.93, 1.0]}, pytest.approx(0.07)),
    ({"held_picks_over_even": [1.0, 1.0, 1.0, 1.0]}, 0.0),
    ({"h2d_bytes": 1}, None),
])
def test_the_load_reader_on_a_fixture(counters, want):
    assert _read("held_load_off_even", _facts(None, counters)) == want


@pytest.mark.parametrize("name", ["kda_ms_per_step", "kda_roofline", "latent_attention_ms_per_step",
                                  "latent_attention_roofline", "held_load_off_even"])
def test_the_new_metrics_list_this_cell_alone(name):
    (listed,) = [m for m in harness.load_benchmark()["per_layer"] if m["name"] == name]
    assert listed["workloads"] == [CELL] and listed["moves"] == "samples_per_s_chip"
    spec = harness.load_metric(name)
    assert {k: spec[k] for k in ("name", "unit", "better", "source", "layer")} == \
        {k: listed[k] for k in ("name", "unit", "better", "source", "layer")}
    if "roofline" in name:
        assert listed["unit"] == "%" and listed["better"] == "higher"


def test_benchmark_json_keeps_its_own_rules():
    """Names, lengths and the time rule of the whole file, as the driver holds it."""
    import re

    bench = harness.load_benchmark()
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in bench[group]]
        assert len(names) == len(set(names)) and all(name.match(n) for n in names), group
    for c in bench["configs"]:
        assert 1 <= len(c["why"]) <= 200 and 1 <= len(c["source"]) <= 200 and len(c["reduced"]) <= 16
        assert os.path.exists(os.path.join(ROOT, c["file"])) and all(name.match(k) for k in c["reduced"])
    for w in bench["workloads"]:
        assert 1 <= len(w["why"]) <= 200 and "\t" not in w["why"] and w["chips"] in (1, 4)
        assert name.match(w["traffic"]) and w["config"] in {c["name"] for c in bench["configs"]}
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert set(m.get("workloads", [])) <= cells and re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
    runs = 2 + 14 * len(cells)
    assert runs * (bench["run_seconds"] + 60) + 2 * 90 * len(cells) + 1200 <= 43200
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
