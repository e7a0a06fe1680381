"""The per-layer metric ``mla_rope_ms_per_step`` (PR 43): its reader on
recorded ``op_s`` fixtures of the cell ``joyai-flash-ep32-pack16k-mtp1``, and
its entry in ``BENCHMARK.json``."""

import pytest

import joyai_flash_presets as presets
from perf import harness

ROOT = harness.ROOT
CELL = presets.CELL
NAME = "mla_rope_ms_per_step"

# the interval kernels and a product, as the cell's traced step names them
OP_S = {
    "interval_attention_fwd_bf16_1_16384_4096_": 0.70, "interval_attention_dq_bf16_1_16384_4096_": 0.42,
    "interval_attention_dkv_bf16_1_16384_4096_": 0.56,
    "grouped_matmul_f32_16384_768_": 0.06, "fusion_f32_1_16384_2048_": 1.5,
}
# the rotation pass's labels as the step's compiled text names them: the query's 32 heads, the shared key's one
ROTATION_OP_S = {"mla_rope_fwd_bf16_1_32_16384_64_": 0.020, "mla_rope_bwd_f32_1_16384_2048_": 0.011,
                 "mla_rope_fwd_bf16_1_1_16384_64_": 0.001, "mla_rope_bwd_f32_1_16384_64_": 0.0008}


def _facts(trace):
    cell = harness.find_cell(harness.load_benchmark(), CELL)
    return {"cell": cell, "config": harness.load_config(cell["config"]),
            "traffic": harness.load_traffic(cell["traffic"]), "trace": trace,
            "counters": {}, "root": ROOT, "chips": 1,
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


def _read(facts):
    return harness.load_module("readers", harness.load_metric(NAME)["reader"]).read(facts)


@pytest.mark.parametrize("op_s,want", [
    pytest.param({**OP_S, **ROTATION_OP_S}, 0.0328 / 4 * 1e3, id="the_pass_beside_other_ops"),
    # XLA's rotation of the parent (``attention/rope``) is fusions, and no label of the pass
    pytest.param({**OP_S, "fusion_f32_1_16384_32_63_": 0.09, "fusion_bf16_1_32_16384_64_": 0.11}, None,
                 id="the_parent"),
])
def test_the_rotation_reader_sums_its_labels_alone(op_s, want):
    got = _read(_facts({"steps": 4, "op_s": op_s}))
    assert got == (None if want is None else pytest.approx(want, rel=1e-9))
    assert _read(_facts(None)) is None  # an untraced run


def test_the_rotation_metric_lists_this_cell_alone():
    (listed,) = [m for m in harness.load_benchmark()["per_layer"] if m["name"] == NAME]
    assert listed["workloads"] == [CELL] and listed["moves"] == "samples_per_s_chip"
    assert listed["layer"] == "q/k pass" and listed["source"] == "device_trace"
    spec = harness.load_metric(NAME)
    assert {k: spec[k] for k in ("name", "unit", "better", "source", "layer")} == \
        {k: listed[k] for k in ("name", "unit", "better", "source", "layer")}
    assert spec["reader"] == NAME


def test_the_rotation_metric_is_the_last_entry():
    """In ``per_layer`` once, wherever later entries put it."""
    bench = harness.load_benchmark()
    assert [m["name"] for m in bench["per_layer"]].count(NAME) == 1
