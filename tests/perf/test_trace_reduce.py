"""The trace reduction on a hand-made trace with worked answers and on a
small trace recorded on the v5e (three steps of tb-pinned-share16, PR 25)."""

import json
import os

import pytest

import perf_presets as presets
from perf import trace_reduce as tr

DEV, HOST = "/device:TPU:0", "/host:CPU"
OPS, MODS = tr.OPS_LINE, tr.MODULES_LINE
MS = 1e6


def _hand_made():
    ev = []
    # 21 steps, 10 ms apart, each 8 ms of module time
    for i in range(21):
        t = i * 10 * MS
        ev.append((DEV, MODS, f"jit_step({i})", t, 8 * MS))
        ev.append((DEV, OPS, "%fusion.1 = f32[64,8]{1,0} fusion(%p)", t, 5 * MS))
        ev.append((DEV, OPS, "%copy.2 = f32[8]{0} copy(%q)", t + 4 * MS, 4 * MS))  # overlaps 1 ms
    # a long gap before a 22nd, late step: the host was compiling
    ev.append((DEV, MODS, "jit_step(21)", 240 * MS, 8 * MS))
    ev.append((DEV, OPS, "%fusion.1 = f32[64,8]{1,0} fusion(%p)", 240 * MS, 8 * MS))
    ev.append((HOST, "main/1", "backend_compile", 209 * MS, 30 * MS))
    ev.append((HOST, "main/1", "DevicePut", 8.5 * MS, 1 * MS))
    ev.append((DEV, "Steps", "ignored line", 0.0, 999 * MS))
    return ev


def test_hand_made_trace_gives_the_worked_numbers():
    out = tr.reduce_events(_hand_made(), {"jit_step": 1}, chips=1)
    # each of 21 steps is busy 8 ms (5 + 4 - 1 overlap), the last one 8 ms
    assert out["busy_s"] == pytest.approx(22 * 8e-3)
    assert out["window_s"] == pytest.approx(0.248)
    assert out["idle_share"] == pytest.approx(1 - 0.176 / 0.248)
    assert out["steps"] == 22
    assert out["device_ms_per_step"] == pytest.approx(8.0)
    # 20 start-to-start times of 10 ms and one of 40 ms: the 95th percentile
    # of 21 values lies at rank 19 of 0..20: 10 ms
    assert out["step_ms_p95"] == pytest.approx(10.0)
    ops = dict(out["breakdown"]["device_ops"])
    assert ops["fusion_f32_64_8_"] == pytest.approx(21 * 5e-3 + 8e-3)
    assert ops["copy_f32_8_"] == pytest.approx(21 * 4e-3)
    gaps = out["breakdown"]["idle_gaps"]
    assert gaps[0][0] == "backend_compile" and gaps[0][1] == pytest.approx(32e-3)
    assert gaps[1][1] == pytest.approx(2e-3)
    assert {g[0] for g in gaps[1:]} <= {"DevicePut", "inside_the_program"}
    assert len(gaps) <= 10 and len(ops) <= 10


def test_busy_union_merges_overlaps_and_reports_gaps():
    busy, gaps = tr.busy_union([(0, 5), (4, 8), (10, 12), (11, 11.5)])
    assert busy == 10 and gaps == [(8, 10)]


def test_k_step_program_counts_its_steps():
    ev = [(DEV, MODS, "jit_run(1)", 0.0, 70 * MS), (DEV, MODS, "jit_step(2)", 80 * MS, 9 * MS),
          (DEV, OPS, "%a = f32[1]{0} add(%x)", 0.0, 70 * MS), (DEV, OPS, "%a = f32[1]{0} add(%x)", 80 * MS, 9 * MS)]
    out = tr.reduce_events(ev, {"jit_step": 1, "jit_run": 8}, chips=1)
    assert out["steps"] == 9 and out["device_ms_per_step"] == pytest.approx(79 / 9)


def test_a_pack_cut_by_the_edge_of_the_trace_counts_for_its_share():
    """The profiler cuts a program's event at the trace's edges (recorded on
    the v5e, PR 25: a first pack of 162 ms among packs of 232 ms): half a
    pack of 8 is 4 steps, and the time a step takes comes out the same."""
    packs = [(0.0, 40.0)] + [(40.0 + 80.0 * i, 80.0) for i in range(4)]
    ev = [(DEV, line, name, s0 * MS, d * MS) for s0, d in packs
          for line, name in ((MODS, "jit_run(1)"), (OPS, "%a = f32[1]{0} add(%x)"))]
    out = tr.reduce_events(ev, {"jit_step": 1, "jit_run": 8}, chips=1)
    assert out["steps"] == pytest.approx(36.0)
    assert out["device_ms_per_step"] == pytest.approx(10.0)


def test_no_device_operation_gives_nothing():
    assert tr.reduce_events([(HOST, "main/1", "x", 0.0, 1e6)], {"jit_step": 1}, 1) is None


def test_recorded_trace_of_three_pinned_steps():
    with open(os.path.join(presets.ROOT, "perf", "fixtures", "trace_pinned_3steps.json")) as f:
        events = [tuple(e) for e in json.load(f)]
    out = tr.reduce_events(events, {"jit_step": 1}, chips=1)
    assert out["steps"] == 3
    assert out["device_ms_per_step"] == pytest.approx(28.62, abs=0.01)
    assert 0.0 <= out["idle_share"] < 0.001  # one program a step: the device never waits
    assert out["busy_s"] <= out["window_s"]
    assert out["step_ms_p95"] is None  # too few steps for a tail
    top = out["breakdown"]["device_ops"]
    # the passes over the whole 11.7M-row table lead: over half of the step
    assert top[0][0] == "fusion_f32_11735473_128_"
    assert top[0][1] / out["busy_s"] > 0.5
    assert top[1][0] == "fusion_f32_106496_128_"
    assert sum(v for _k, v in top) <= out["busy_s"] * 1.0001
