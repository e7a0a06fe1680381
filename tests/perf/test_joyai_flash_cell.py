"""The cell ``joyai-flash-ep32-pack16k-mtp1`` on the CPU: rehearsed through
``run_cell`` at its tiny preset (sound, the planted half batch, the control,
and the tower's own two faults: the module's term left out, the rotation left
out), its traffic, its work counts against hand values, its readers on
fixtures, and its files against the catalog's row."""

import itertools
import json
import os
import shutil
import time

import numpy as np
import pytest

import joyai_flash_presets as presets
from perf import compare, harness, joyai_flash_faults, joyai_flash_weights, limits_one

ROOT = harness.ROOT
CELL = presets.CELL
CATALOG = presets.CATALOG
CONFIG = "joyai-llm-flash-48b-a3b-ep32"
TRAFFIC = "pack16k-docs7-ragged-mtp1-b1"
SEED = 2 ** 31 + 97
NEW_METRICS = ("rope_latent_attention_ms_per_step", "rope_latent_attention_roofline", "mtp_label_share",
               "mtp_held_load_off_even")


@pytest.fixture(autouse=True)
def _restore_matmul_precision():
    import jax

    before = jax.config.jax_default_matmul_precision
    yield
    jax.config.update("jax_default_matmul_precision", before)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A copy of the benchmark in which the cell is judged by the rehearsal's
    limits (``joyai_flash_presets.REHEARSAL_LIMITS``); everything else is the cell's own."""
    root = str(tmp_path_factory.mktemp("joyai_flash_cell"))
    shutil.copytree(os.path.join(ROOT, "perf"), os.path.join(root, "perf"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    with open(os.path.join(root, "perf", "limits", f"{CELL}.json"), "w") as f:
        json.dump({"workload": CELL, "limits": presets.REHEARSAL_LIMITS}, f)
    return root


# a rehearsal traces and compiles the tower and its reference anew (20 to 30 s each on the CPU)
@pytest.mark.time_limit(600)
@pytest.mark.parametrize("case", ["sound", "half_batch", "control", "mtp_term_left_out", "rotation_left_out"])
def test_cell_rehearsed_through_run_cell(root, case):
    if case == "sound":  # traced, so that one rehearsal also shows what the cell reports off the chip
        out = harness.run_cell(CELL, SEED, 0.3, True, time.perf_counter(), rehearsal=presets.REHEARSAL, root=root)
        assert out["correct"] is True and out["rehearsal"] is True, out["compared"]
        assert set(out["compared"]) == set(compare.load_limits(CELL))  # the numbers the cell limits
        assert "mtp_loss_gap" in out["compared"] and "expert_pick_mismatch_share" in out["compared"]
        # the leading layer's 12 leaves, the expert layer's 16, the module's 4 + 16, the top's 2
        assert len(out["by_leaf"]["grad"]) == 12 + 16 + 20 + 2
        assert "table" in out["by_leaf"]["change"]  # read after two steps: the rows by their change
        assert out["attempted"] > 0
        # counters are read off the chip too; nothing of the device trace is
        assert out["metrics"]["mtp_label_share"]["value"] == pytest.approx((64 - 10) / (64 - 5))
        assert 0.0 <= out["metrics"]["mtp_held_load_off_even"]["value"] < 1.5
        # ids, starts and labels int32, weights float32: 16 B a position, the module's labels made on the device
        assert out["metrics"]["h2d_bytes_per_sample"]["value"] == 64 * 16
        assert out["metrics"]["compiles_in_window"]["value"] == 0.0
        for name in ("rope_latent_attention_ms_per_step", "rope_latent_attention_roofline", "mfu",
                     "train_step_roofline", "device_ms_per_step"):
            assert name not in out["metrics"]
        listed = {m["name"] for m in harness.cell_metrics(harness.load_benchmark(), CELL, "per_layer")}
        assert listed == {"gen_wait_share", "h2d_bytes_per_sample", "compiles_in_window", "device_ms_per_step",
                          "train_step_roofline", "mfu", "device_idle_share", "device_hbm_peak_gb", *NEW_METRICS}
    elif case == "half_batch":  # in a call of its own, as perf/limits_one.py runs it on the chip
        out = limits_one.fault(CELL, SEED, case, 0.3, rehearsal=presets.REHEARSAL, root=root)
        assert out["correct"] is False
        assert out["compared"]["grad_gap"][0] > 0.2  # half the positions
    elif case == "control":  # float8 operands in the reference's place: not correct
        verdict = limits_one.control(CELL, SEED, rehearsal=presets.REHEARSAL, root=root)
        assert verdict["control"] == "operands_float8_e4m3"
        assert verdict["correct"] is False, verdict["compared"]
        assert verdict["compared"]["mtp_loss_gap"][0] > presets.REHEARSAL_LIMITS["mtp_loss_gap"]
    elif case == "mtp_term_left_out":  # the module runs and nothing trains on it
        out = joyai_flash_faults.fault(CELL, SEED, case, 0.3, rehearsal=presets.REHEARSAL, root=root)
        assert out["correct"] is False
        # the loss lacks a tenth of a second cross-entropy; the module's leaves get no gradient at all
        assert out["compared"]["loss_gap"][0] == pytest.approx(0.1 / 1.1, rel=0.05)
        # (a leaf's gap is measured against the reference's norm of it or the median leaf's, whichever is larger)
        assert out["by_leaf"]["grad"]["mtp.merge"] > 0.9 and out["compared"]["change_gap"][0] >= 1.0
        # its own term is still computed, on leaves that no step has trained: one step's progress behind
        assert out["compared"]["mtp_loss_gap"][0] < 5e-3
    else:  # attention without positions: what reads the rotation is the query's and the latent's leaves
        out = joyai_flash_faults.fault(CELL, SEED, case, 0.3, rehearsal=presets.REHEARSAL, root=root)
        assert out["correct"] is False, out["compared"]
        worst = max(out["by_leaf"]["grad"], key=out["by_leaf"]["grad"].get)
        assert any(leaf in worst for leaf in ("wq_a", "wq_b", "q_norm", "wkv_a")), worst


def test_the_towers_faults_leave_no_trace():
    """``planted`` puts the tower's ``from_config`` back, whatever happened inside."""
    from persia_tpu.models import JoyAIFlashMoE

    sound = JoyAIFlashMoE.from_config(CATALOG)
    for name, field in (("mtp_term_left_out", "mtp_weight"), ("rotation_left_out", "rope_theta")):
        with pytest.raises(RuntimeError), joyai_flash_faults.planted(name):
            broken = JoyAIFlashMoE.from_config(CATALOG)
            assert getattr(broken, field) != getattr(sound, field)
            raise RuntimeError
        assert JoyAIFlashMoE.from_config(CATALOG) == sound


def test_configuration_against_the_catalog_row():
    cfg = harness.load_config(CONFIG)
    bench = harness.load_benchmark()
    (entry,) = [c for c in bench["configs"] if c["name"] == cfg["name"]]
    reduced = set(entry["reduced"])
    assert reduced == {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert [line.split(":")[0] for line in cfg["reduced"]] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    for key, value in CATALOG.items():
        if key in reduced:
            assert cfg["published"][key] == value and cfg[key] < value, key
        else:
            assert cfg[key] == value, key  # no width, rank, rotation or routing number differs
    # the cut: layer 0 and five of the layers that follow, eight experts held, an eighth of the vocabulary, the module whole
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"], cfg["num_nextn_predict_layers"]) == (6, 1, 1)
    assert [joyai_flash_weights.mlp_of(cfg, l) for l in range(6)] == ["dense"] + ["shared_experts"] * 5
    assert cfg["n_routed_experts"] == 8 and cfg["router_width"] == 256 and cfg["first_held_expert"] == 0
    assert cfg["vocab_size"] * 8 == CATALOG["vocab_size"]
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert 1 <= len(entry["why"]) <= 200 and entry["why"].isprintable()
    # the catalog's source_url, then the widths in words
    assert cfg["source"].startswith("https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/config.json hidden 2048, MLA ")
    assert {"mtp_inputs", "mtp_loss_weight", "mtp_document_ends", "packing", "routing", "auxiliary_loss",
            "softmax_scale", "rotation", "head_dim", "initialisation", "optimizers", "router_initialisation",
            "matmul_precision"} <= set(cfg["assumed"])
    assert cfg["guarantees"]["math_dtype"] == "bfloat16" and "rotation" in cfg["guarantees"]["float32"]
    assert "two uses" in cfg["guarantees"]["duplicates"] and cfg["mtp_loss_weight"] == 0.1
    assert cfg["deployment"] and cfg["model"] == "joyai_flash_moe" and cfg["router_law"] == "mirrored_copies"
    # the bytes, reckoned again from the real leaf list
    count = lambda shapes: sum(int(np.prod(s)) for s in shapes.values())
    attention = 3_145_728 + 9_437_184 + 1_179_648 + 4_194_304 + 8_388_608 + 1_536 + 512
    assert attention == 26_347_520
    assert count(joyai_flash_weights.layer_shapes(cfg, "dense")) == attention + 44_040_192 + 4_096 == 70_391_808
    assert count(joyai_flash_weights.layer_shapes(cfg, "shared_experts")) \
        == attention + 524_288 + 9 * 4_718_592 + 4_096 == 69_343_232
    assert count(joyai_flash_weights.module_shapes(cfg)) == 8_388_608 + 3 * 2_048 + 69_343_232 == 77_737_984
    assert count(joyai_flash_weights.top_shapes(cfg)) == 33_095_680 + 2_048
    total = 70_391_808 + 5 * 69_343_232 + 77_737_984 + 33_095_680 + 2_048
    work = harness.model_module("work", cfg)
    assert cfg["bytes"]["dense_parameters"] == work.dense_param_count(cfg) == total == 527_943_680
    assert cfg["bytes"]["dense_state_bytes"] == 527_943_680 * 12 == 6_335_324_160
    assert cfg["bytes"]["dense_gradient_bytes"] == 527_943_680 * 4 == 2_111_774_720
    assert cfg["bytes"]["token_table_and_accumulator_bytes"] == 16_160 * 2048 * 4 * 2 == 264_765_440
    traffic = harness.load_traffic(TRAFFIC)
    assert (traffic["batch"], traffic["seq_len"], traffic["warmup_steps"]) == (1, 16384, 5)
    assert traffic["doc_lengths"] == [8195, 4101, 2057, 1041, 499, 246, 245]
    assert (traffic["generator"], traffic["entry"]) == ("packed_documents", "fused_joyai_flash")
    limits = json.load(open(os.path.join(ROOT, "perf", "limits", f"{CELL}.json")))
    assert set(limits["limits"]) <= set(limits["readings"])  # every limit has its reason
    assert {"loss_gap", "grad_gap", "change_gap", "expert_pick_mismatch_share", "mtp_loss_gap"} <= set(limits["limits"])


def test_the_tower_holds_the_weights_files_leaves():
    """Shape for shape: what ``JoyAIFlashMoE.from_config`` holds is what the
    weights' law makes (no array is built: shapes only)."""
    import jax

    from persia_tpu.models import JoyAIFlashMoE

    cfg = harness.load_config(CONFIG)
    model = JoyAIFlashMoE.from_config(cfg, head_chunk=int(cfg["head_chunk"]))
    made = jax.eval_shape(lambda: joyai_flash_weights.dense_tree(cfg, jax.numpy.zeros((2,), jax.numpy.uint32),
                                                                 jax.numpy))
    is_shape = lambda x: isinstance(x, tuple) and (not x or isinstance(x[0], int))
    want = jax.tree.map(lambda s: tuple(s), model.param_shapes(), is_leaf=is_shape)
    assert jax.tree.map(lambda x: x.shape, made) == want
    assert model.pick_chunk(16384) == 16384 and model.counters()["expert_picks"].shape == (6, 8)
    assert model.counters()["router_bias"].shape == (6, 256) and model.counters()["objective"].shape == (4,)
    assert sorted(joyai_flash_weights.leaves_by_name(jax.tree.map(lambda x: np.zeros(x.shape[:1]), made), cfg)) \
        == sorted(joyai_flash_weights.leaf_names(cfg))
    assert len(joyai_flash_weights.leaf_names(cfg)) == 12 + 5 * 16 + 20 + 2


@pytest.mark.parametrize("law", ["mirrored_copies", "plain"])
def test_the_routers_law_is_the_configurations(law):
    cfg = dict(harness.load_config(CONFIG), hidden_size=256, moe_intermediate_size=64, router_law=law)
    for name in ("L2.router", "mtp.router"):  # a layer's and the module's, each a draw of its own
        x = joyai_flash_weights.leaf(cfg, 2 ** 31 + 5, name)
        assert x.shape == (256, 256) and len(np.unique(x, axis=1).T) == 256  # no two columns tie
        by_share = x.reshape(256, 32, 8)  # column e is share e // 8's slot e % 8
        if law == "mirrored_copies":  # slots j and j + 4: base j plus and minus the share's own draw
            base, own = (by_share[:, :, :4] + by_share[:, :, 4:]) / 2, (by_share[:, :, :4] - by_share[:, :, 4:]) / 2
            np.testing.assert_allclose(base, np.broadcast_to(base[:, :1], base.shape), atol=1e-7)
            assert base.std() == pytest.approx(0.02, rel=0.1) and own.std() == pytest.approx(0.005, rel=0.1)
        else:
            assert x.std() == pytest.approx(0.02, rel=0.05) and abs(np.corrcoef(x[:, 0], x[:, 8])[0, 1]) < 0.3
    assert not np.array_equal(joyai_flash_weights.leaf(cfg, 2 ** 31 + 5, "L2.router"), x)
    with pytest.raises(ValueError, match="router_law"):
        joyai_flash_weights.leaf(dict(cfg, router_law="other"), 1, "L1.router")


@pytest.mark.parametrize("name,shape,deviation", [
    ("L0.wq_a", (2048, 1536), 0.02), ("L3.wq_b", (1536, 6144), 0.02), ("L5.wkv_a", (2048, 576), 0.02),
    ("mtp.merge", (4096, 2048), 0.02), ("mtp.wkv_b", (512, 8192), 0.02), ("L0.q_norm", (1536,), 0.0),
    ("mtp.norm_e", (2048,), 0.0), ("mtp.norm_s", (2048,), 0.0), ("L4.kv_norm", (512,), 0.0),
])
def test_the_leaves_start_as_stated(name, shape, deviation):
    cfg = harness.load_config(CONFIG)
    x = joyai_flash_weights.leaf(cfg, SEED, name)
    assert x.shape == shape and x.dtype == np.float32
    if deviation:
        assert x.std() == pytest.approx(deviation, rel=0.02) and abs(x.mean()) < 1e-4
        other = joyai_flash_weights.leaf(cfg, SEED, "mtp.wq_a" if name == "L0.wq_a" else "L0.wq_a")
        assert x.shape != other.shape or not np.array_equal(x, other)  # a stream of its own
    else:
        assert (x == 1).all()


@pytest.mark.parametrize("what", ["documents", "ragged", "labels", "module_labels", "halved"])
def test_the_traffic(what):
    cfg, tr = harness.load_config(CONFIG), harness.load_traffic(TRAFFIC)
    gen = harness.load_module("generators", tr["generator"])
    b = next(gen.make(cfg, tr, SEED))
    if what == "documents":  # the seven lengths in an order of the seed's, anew every step
        stream = gen.make(cfg, tr, SEED)
        orders = [tuple(next(stream)["doc_lengths"][0]) for _ in range(6)]
        assert all(sorted(o) == sorted(tr["doc_lengths"]) for o in orders) and len(set(orders)) > 1
        assert b["ids"].shape == (1, 16384) and 0 <= b["ids"].min() and b["ids"].max() < 16160
    elif what == "ragged":  # no proper subset sums to a multiple of 64: every later start is inside a tile
        docs = tr["doc_lengths"]
        assert sum(docs) == 16384
        assert not [s for r in range(1, 7) for s in itertools.combinations(docs, r) if sum(s) % 64 == 0]
    elif what == "labels":
        np.testing.assert_array_equal(b["labels"][0, :-1], b["ids"][0, 1:])
        ends = np.cumsum(b["doc_lengths"][0]) - 1
        assert (b["weights"][0, ends] == 0).all() and b["weights"].sum() == 16384 - 7
    elif what == "module_labels":  # what the device makes of them: 16,370 of the main objective's 16,377
        w = b["weights"][0]
        w2 = w * np.concatenate([w[1:], [0.0]])
        assert w2.sum() == 16384 - 14 and w2.sum() / w.sum() == pytest.approx(0.99957, abs=1e-5)
    else:
        h = gen.halve(b)
        assert h["ids"].shape == (1, 8192) and h["doc_lengths"].sum() == 8192


def test_work_counts_hand_values():
    cfg, tr = harness.load_config(CONFIG), harness.load_traffic(TRAFFIC)
    work = harness.model_module("work", cfg)
    d = 2048
    mla = d * 1536 + 1536 * 32 * 192 + d * 576 + 512 * 32 * 256 + 4096 * d  # the query's two, the latent's two, o
    assert work.attention_product_macs(cfg) == mla == 26_345_472
    assert work.mlp_macs(cfg, "dense") == 3 * d * 7168 == 44_040_192
    # the router at 256, the shared expert, and 8 x 8 / 256 = a quarter of a held pick a position
    assert work.mlp_macs(cfg, "shared_experts") == d * 256 + 1.25 * 3 * d * 768 == 6_422_528
    pairs = sum(j + 1 for n in tr["doc_lengths"] for j in range(n))
    assert work.live_pairs(tr) == pairs == 44_838_541 and work.blocks(cfg) == 7
    latent = 2 * (192 + 128) * 32 * pairs
    assert work.latent_attention_forward_flops(cfg, tr) == latent == 918_293_319_680
    t = 16384
    # seven blocks' attention products, one dense MLP, six expert layers, the merge, two passes through the head
    macs = 7 * mla + 44_040_192 + 6 * 6_422_528 + 2 * d * d + 2 * d * 16160
    assert work.train_flops_per_sample(cfg, tr) == 3 * (2 * t * macs + 7 * latent) == 52_862_214_033_408
    assert 3 * 7 * latent / 52_862_214_033_408 == pytest.approx(0.365, abs=1e-3)  # the kernels' share of the step's work
    dense, rows = 527_943_680 * 28, t * d * 4 * 6
    stream, logits = 7 * t * d * 4 * 2, 2 * t * 16160 * 4 * 4
    assert work.step_hbm_bytes(cfg, tr) == dense + rows + stream + logits == 25_939_271_680
    la = work.latent_attention_kernel_work(cfg, tr)
    assert la["flops"] == 3 * 7 * latent and la["bytes"] == 7 * 3 * t * (32 * 192 + 32 * 128 + 64 + 2 * 4096) * 2
    from perf import counts

    floor = counts.step_floor_seconds(cfg, tr, counts.load_peaks("TPU v5 lite"))
    assert floor["bound_by"] == "flops" and floor["seconds"] == pytest.approx(0.26834, rel=1e-3)


# op labels as a traced run of a latent cell on the v5e prints them
OP_S = {
    "interval_attention_fwd_bf16_1_16384_4096_": 0.70, "interval_attention_dq_bf16_1_16384_4096_": 0.42,
    "interval_attention_dkv_bf16_1_16384_4096_": 0.56,
    "grouped_matmul_f32_16384_768_": 0.06, "fusion_f32_1_16384_2048_": 1.5,
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
    ("rope_latent_attention_ms_per_step", 1.68 / 4 * 1e3),
    ("rope_latent_attention_roofline", 100 * (3 * 7 * 918_293_319_680 / 197e12) / (1.68 / 4)),
])
def test_kernel_readers_on_a_fixture(name, want):
    trace = {"steps": 4, "op_s": OP_S}
    assert _read(name, _facts(trace)) == pytest.approx(want, rel=1e-9)
    # a program without these kernels (the parent), or an untraced run: nothing, and no error
    assert _read(name, _facts({"steps": 4, "op_s": {"fusion_f32_4096_128_": 1.0}})) is None
    assert _read(name, _facts(None)) is None


@pytest.mark.parametrize("name,counters,want", [
    ("mtp_label_share", {"objective": [16377.0 * 20, 16370.0 * 20, 3.1e6, 3.2e6]}, pytest.approx(16370 / 16377)),
    ("mtp_label_share", {"objective": [16377.0, 0.0, 1.6e5, 0.0]}, 0.0),  # the module's pass not in the step
    ("mtp_label_share", {"objective": [16377.0, 1.6e5]}, None),  # a tower that keeps one objective
    ("mtp_label_share", {"h2d_bytes": 1}, None),  # a program without the counter (the parent)
    ("mtp_held_load_off_even", {"held_picks_over_even": [1.0, 1.04, 0.93, 1.0, 1.0, 1.21]}, pytest.approx(0.21)),
    ("mtp_held_load_off_even", {"h2d_bytes": 1}, None),
])
def test_counter_readers_on_a_fixture(name, counters, want):
    assert _read(name, _facts(None, counters)) == want


@pytest.mark.parametrize("name", NEW_METRICS)
def test_the_new_metrics_list_this_cell_alone(name):
    (listed,) = [m for m in harness.load_benchmark()["per_layer"] if m["name"] == name]
    assert listed["workloads"] == [CELL] and listed["moves"] == "samples_per_s_chip"
    spec = harness.load_metric(name)
    assert {k: spec[k] for k in ("name", "unit", "better", "source", "layer")} == \
        {k: listed[k] for k in ("name", "unit", "better", "source", "layer")}
    if "roofline" in name:
        assert listed["unit"] == "%" and listed["better"] == "higher"
    # of the four, one brings a reader; the others name an accepted one in their data file
    assert (spec["reader"] == name) == (name == "mtp_label_share")


def test_the_benchmark_gained_entries_at_the_end_only():
    bench = harness.load_benchmark()
    assert [c["name"] for c in bench["configs"]][-1] == CONFIG and len(bench["configs"]) == 5
    assert [w["name"] for w in bench["workloads"]][-1] == CELL and len(bench["workloads"]) == 6
    assert tuple(m["name"] for m in bench["per_layer"][-4:]) == NEW_METRICS
    assert all(w["chips"] == 1 for w in bench["workloads"])
    cells = len(bench["workloads"])
    assert (2 + 14 * cells) * (bench["run_seconds"] + 60) + 2 * 90 * cells + 1200 <= 43200


@pytest.mark.parametrize("what", ["both_counted", "picks_moved", "module_not_in_the_step", "one_objective"])
def test_the_references_extra_readings(what):
    reference = harness.model_module("reference", harness.load_config(CONFIG))
    snap = lambda picks, sums: {"expert_picks": np.asarray(picks), "objective": np.asarray(sums, np.float64)}
    zero = snap([[0, 0]], [0, 0, 0, 0])
    theirs = {"snaps": {0: zero, 2: snap([[100, 60]], [200.0, 190.0, 900.0, 874.0])}}
    if what == "both_counted":
        mine = {"snaps": {0: zero, 2: snap([[100, 60]], [200.0, 190.0, 900.0, 874.0 * 1.001])}}
        got = reference.extra_readings(mine, theirs)
        assert got["expert_pick_mismatch_share"] == 0 and got["mtp_loss_gap"] == pytest.approx(1e-3)
    elif what == "picks_moved":  # one pick that goes from one held expert to the other counts twice
        mine = {"snaps": {0: zero, 2: snap([[99, 61]], [200.0, 190.0, 900.0, 874.0])}}
        got = reference.extra_readings(mine, theirs)
        assert got["expert_pick_mismatch_share"] == pytest.approx(2 / 160) and got["mtp_loss_gap"] == 0
    elif what == "module_not_in_the_step":  # its sums never moved: 1, not a division by zero
        mine = {"snaps": {0: zero, 2: snap([[100, 60]], [200.0, 0.0, 900.0, 0.0])}}
        assert reference.extra_readings(mine, theirs)["mtp_loss_gap"] == 1.0
    else:  # a configuration without the module keeps two sums and reads no such gap
        two = {"snaps": {0: snap([[0, 0]], [0, 0]), 2: snap([[100, 60]], [200.0, 900.0])}}
        assert set(reference.extra_readings(two, two)) == {"expert_pick_mismatch_share"}
