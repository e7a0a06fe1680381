"""The trace reduction on hand-made traces with worked answers and on a small
trace recorded on the v5e (three steps of tb-pinned-share16): device ops cut
to the time the host tracer covered, whole steps only, loops out of the op
labels, and the traced slice's length from the warm-up's time a step."""

import json
import os

import pytest

import perf_presets as presets
from perf import harness
from perf import trace_reduce as tr

DEV, HOST = "/device:TPU:0", "/host:CPU"
OPS, MODS = tr.OPS_LINE, tr.MODULES_LINE
MS = 1e6


def _read(metric, trace):
    """What ``metric``'s reader makes of a reduced trace, as a traced run of a
    one-chip cell hands it over."""
    facts = {"trace": trace, "root": presets.ROOT, "chips": 1,
             "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
             "config": harness.load_config("dlrm-mlperf-1tb"),
             "traffic": harness.load_traffic("zipf105-pinned-share16")}
    return harness.load_module("readers", harness.load_metric(metric)["reader"]).read(facts)


def _hand_made():
    # an op before the first step: the device's record starts before it
    ev = [(DEV, OPS, "%infeed.3 = f32[8]{0} infeed(%t)", -2 * MS, 1 * MS)]
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
    # the host tracer's first and last events
    ev.append((HOST, "main/1", "PjitFunction(step)", -2 * MS, 0.1 * MS))
    ev.append((HOST, "main/1", "PjitFunction(step)", 248 * MS, 0.1 * MS))
    # a step the device ran after the host tracer had stopped
    ev.append((DEV, MODS, "jit_step(22)", 252 * MS, 8 * MS))
    ev.append((DEV, OPS, "%fusion.1 = f32[64,8]{1,0} fusion(%p)", 252 * MS, 8 * MS))
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
    assert out["op_s"] == ops  # two ops in all: the breakdown holds every one; the infeed ran before
    gaps = out["breakdown"]["idle_gaps"]
    assert gaps[0][0] == "backend_compile" and gaps[0][1] == pytest.approx(32e-3)
    assert gaps[1][1] == pytest.approx(2e-3)
    assert {g[0] for g in gaps[1:]} <= {"DevicePut", "inside_the_program"}
    assert len(gaps) <= 10 and len(ops) <= 10


def test_busy_union_merges_overlaps_and_reports_gaps():
    busy, gaps = tr.busy_union([(0, 5), (4, 8), (10, 12), (11, 11.5)])
    assert busy == 10 and gaps == [(8, 10)]


def test_k_step_program_counts_its_steps():
    ev = [(DEV, OPS, "%b = f32[1]{0} copy(%y)", -5 * MS, 1 * MS),
          (DEV, MODS, "jit_run(1)", 0.0, 70 * MS), (DEV, MODS, "jit_step(2)", 80 * MS, 9 * MS),
          (DEV, OPS, "%a = f32[1]{0} add(%x)", 0.0, 70 * MS), (DEV, OPS, "%a = f32[1]{0} add(%x)", 80 * MS, 9 * MS)]
    out = tr.reduce_events(ev, {"jit_step": 1, "jit_run": 8}, chips=1)
    assert out["steps"] == 9 and out["device_ms_per_step"] == pytest.approx(79 / 9)


def _packs(starts_durations, program="jit_run(1)"):
    return [(DEV, line, name, s0 * MS, d * MS) for s0, d in starts_durations
            for line, name in ((MODS, program), (OPS, "%a = f32[1]{0} add(%x)"))]


WHOLE = {
    # The profiler cuts a program's event at the start of its record (a
    # first pack of 162 ms among packs of 232 ms, recorded on the v5e): the
    # half pack counts for nothing, and the time a step takes comes out the same.
    "pack_cut_by_the_record_start": (
        _packs([(0.0, 40.0)] + [(40.0 + 80.0 * i, 80.0) for i in range(4)])
        + [(HOST, "main/1", "PjitFunction(run)", 0.0, 360 * MS)],
        32, 0.320, 10.0),
    # the fifth pack ends after the host tracer's last event: its ops are cut off with it
    "pack_cut_by_the_host_tracers_end": (
        _packs([(10.0 + 80.0 * i, 80.0) for i in range(5)])
        + [(DEV, OPS, "%b = f32[1]{0} copy(%y)", 0.0, 1 * MS),
           (HOST, "main/1", "PjitFunction(run)", 5 * MS, 365 * MS)],
        32, 0.320, 10.0),
    # an aux program between two packs is not a step, and its ops count in the steps' time
    "aux_program_between_steps": (
        _packs([(10.0, 80.0), (95.0, 80.0)]) + _packs([(90.0, 5.0)], "jit_apply_aux(3)")
        + [(DEV, OPS, "%b = f32[1]{0} copy(%y)", 0.0, 1 * MS),
           (HOST, "main/1", "PjitFunction(run)", 5 * MS, 195 * MS)],
        16, 0.165, 165 / 16),
}


@pytest.mark.parametrize("case", sorted(WHOLE))
def test_whole_steps_only(case):
    events, steps, window_s, ms = WHOLE[case]
    out = tr.reduce_events(events, {"jit_step": 1, "jit_run": 8}, chips=1)
    assert out["steps"] == steps
    assert out["window_s"] == pytest.approx(window_s)
    assert out["device_ms_per_step"] == pytest.approx(ms)
    assert out["busy_s"] <= out["window_s"] * (1 + 1e-12)


def test_a_device_tail_after_the_last_host_event_is_cut_off():
    """The device runs on while ``stop_trace`` collects, slower and with the
    host tracer off: those steps, their ops and their gaps are not the
    program's, and the step's numbers read what ran before."""
    ev = [(DEV, MODS, "jit_step(0)", 0.0, 0.9 * MS),  # the step the record's start cut
          (DEV, OPS, "%fusion.4 = f32[4096,128]{1,0} fusion(%p)", 0.0, 0.9 * MS),
          (HOST, "main/1", "main_loop", 0.5 * MS, 284.5 * MS)]
    for i in range(40):  # 7.0 ms of ops every 7.1 ms while the host tracer is on
        t = (1.0 + 7.1 * i) * MS
        ev += [(DEV, MODS, f"jit_step({i + 1})", t, 7 * MS),
               (DEV, OPS, "%fusion.4 = f32[4096,128]{1,0} fusion(%p)", t, 7 * MS)]
    for j in range(10):  # and every 12 ms after it
        t = (290.0 + 12.0 * j) * MS
        ev += [(DEV, MODS, f"jit_step({j + 41})", t, 7 * MS),
               (DEV, OPS, "%fusion.4 = f32[4096,128]{1,0} fusion(%p)", t, 7 * MS)]
    out = tr.reduce_events(ev, {"jit_step": 1}, chips=1)
    assert out["steps"] == 40
    assert out["window_s"] == pytest.approx(283.9e-3)
    assert out["busy_s"] == pytest.approx(280e-3)
    assert out["idle_share"] == pytest.approx(1 - 280 / 283.9)
    assert out["step_ms_p95"] == pytest.approx(7.1)
    assert out["device_ms_per_step"] == pytest.approx(7.0)
    assert out["op_s"] == {"fusion_f32_4096_128_": pytest.approx(0.280)}
    gaps = out["breakdown"]["idle_gaps"]
    assert len(gaps) == 10
    assert all(name == "main_loop" and s == pytest.approx(0.1e-3) for name, s in gaps)


@pytest.mark.parametrize("start_ms", [50.0, 250.0, 500.0, 750.0, 950.0])
def test_a_label_in_one_place_of_each_step_reads_the_same_wherever_the_slice_starts(start_ms):
    """Steps of 1 s, the delta rule's kernel 200 ms of each at one place: a
    slice of 6 s reads 200 ms a step whatever part of a step its edges cut."""
    ev = []
    for i in range(12):
        t = 1000.0 * i * MS
        ev += [(DEV, MODS, f"jit_step({i})", t, 990 * MS),
               (DEV, OPS, "%fusion.7 = f32[16384,2048]{1,0} fusion(%p)", t, 100 * MS),
               (DEV, OPS, "%kda_chunk_fwd.2 = f32[1,16384,128]{2,1,0} custom-call(%q)", t + 100 * MS, 200 * MS),
               (DEV, OPS, "%fusion.9 = f32[16384,2048]{1,0} fusion(%r)", t + 300 * MS, 690 * MS)]
    ev.append((HOST, "main/1", "PjitFunction(step)", start_ms * MS, 6000 * MS))
    out = tr.reduce_events(ev, {"jit_step": 1}, chips=1)
    assert out["steps"] == 5
    assert _read("kda_ms_per_step", out) == pytest.approx(200.0)
    assert _read("device_ms_per_step", out) == pytest.approx(990.0)
    # 5 steps over the 4.99 s from the first one's start to the last one's end
    assert out["window_s"] == pytest.approx(4.99)


@pytest.mark.parametrize("container", [
    "%while.3 = (s32[], f32[64]{0}) while(%t), condition=%c, body=%b",
    "%conditional.1 = (f32[64]{0}) conditional(%p, %x, %y)",
    "%call.2 = f32[64]{0} call(%x), to_apply=%f",
], ids=["while", "conditional", "call"])
def test_a_loop_that_holds_its_body_leaves_the_op_labels(container):
    ev = [(DEV, OPS, "%b = f32[1]{0} copy(%y)", 0.0, 1 * MS),
          (HOST, "main/1", "PjitFunction(step)", 0.0, 50 * MS)]
    for t in (10.0, 30.0):
        ev += [(DEV, MODS, "jit_step(1)", t * MS, 10 * MS),
               (DEV, OPS, container, t * MS, 8 * MS),
               (DEV, OPS, "%custom-call.5 = f32[64]{0} custom-call(%x)", (t + 1) * MS, 0.0),  # a marker
               (DEV, OPS, "%fusion.2 = f32[64]{0} fusion(%x)", (t + 1) * MS, 3 * MS),
               (DEV, OPS, "%dot.1 = f32[64]{0} dot(%x, %w)", (t + 4) * MS, 3 * MS),
               (DEV, OPS, "%copy.1 = f32[64]{0} copy(%x)", (t + 8) * MS, 2 * MS)]
    out = tr.reduce_events(ev, {"jit_step": 1}, chips=1)
    assert out["op_s"] == {"fusion_f32_64_": pytest.approx(6e-3), "dot_f32_64_": pytest.approx(6e-3),
                           "copy_f32_64_": pytest.approx(4e-3)}
    assert dict(out["breakdown"]["device_ops"]) == out["op_s"]
    # the loop's own event still makes the device busy: 10 ms a step, 1 ms of 8 not its body's
    assert out["busy_s"] == pytest.approx(20e-3) and out["window_s"] == pytest.approx(30e-3)
    assert sum(s for _k, s in out["breakdown"]["device_ops"]) <= out["busy_s"]


LEAD = (DEV, OPS, "%b = f32[1]{0} copy(%y)", 0.0, 0.2 * MS)  # the device's record starts before the steps
FEW = {
    "one_whole_step": [LEAD, (DEV, MODS, "jit_step(1)", 1 * MS, 10 * MS),
                       (DEV, MODS, "jit_step(2)", 12 * MS, 10 * MS),
                       (HOST, "main/1", "PjitFunction(step)", 0.5 * MS, 14.5 * MS)],
    "no_whole_step": [LEAD, (DEV, MODS, "jit_step(1)", 1 * MS, 10 * MS),
                      (DEV, MODS, "jit_step(2)", 12 * MS, 10 * MS),
                      (HOST, "main/1", "PjitFunction(step)", 2 * MS, 7 * MS)],
    "a_step_cut_by_the_record_start": [(DEV, MODS, "jit_step(1)", 0.0, 10 * MS),
                                       (DEV, MODS, "jit_step(2)", 11 * MS, 10 * MS),
                                       (HOST, "main/1", "PjitFunction(step)", 0.0, 30 * MS)],
}


@pytest.mark.parametrize("case", sorted(FEW))
def test_fewer_than_two_whole_steps_give_nothing(case):
    ev = list(FEW[case])
    ev += [(DEV, OPS, "%kda_chunk_fwd.2 = f32[1,16384,128]{2,1,0} custom-call(%q)", e[3], e[4])
           for e in FEW[case] if e[1] == MODS]
    out = tr.reduce_events(ev, {"jit_step": 1}, chips=1)
    # the device line still says what ran in the time the host tracer covered
    assert out["busy_s"] > 0.0 and out["window_s"] >= out["busy_s"]
    assert out["steps"] is None and out["device_ms_per_step"] is None and out["idle_share"] is None
    for metric in ("device_ms_per_step", "device_idle_share", "mfu", "train_step_roofline",
                   "step_ms_p95", "kda_ms_per_step", "kda_roofline"):
        assert _read(metric, out) is None, metric


@pytest.mark.parametrize("step_s,seconds,want", [
    (0.00716, 20.0, 4.0),  # the pinned and cached cells' steps: 4 s
    (0.47526, 20.0, 4.0),  # the SDAR cell's
    (0.99376, 20.0, 6 * 0.99376),  # the delta-rule cell's: 4 whole steps and one cut at each edge
    (0.85544, 20.0, 6 * 0.85544),
    (5.0, 20.0, 18.5),  # never past the window, a second in
    (1.0, 5.0, 3.5),
    (0.0, 20.0, 4.0),  # a warm-up that read nothing
])
def test_the_slice_follows_the_warm_ups_time_a_step(step_s, seconds, want, tmp_path):
    assert tr.slice_length(seconds, step_s) == pytest.approx(want)
    assert tr.WindowTracer(str(tmp_path), seconds, step_s).length == pytest.approx(want)


def test_no_device_operation_gives_nothing():
    assert tr.reduce_events([(HOST, "main/1", "x", 0.0, 1e6)], {"jit_step": 1}, 1) is None


def _recorded():
    with open(os.path.join(presets.ROOT, "perf", "fixtures", "trace_pinned_3steps.json")) as f:
        return [tuple(e) for e in json.load(f)]


def test_recorded_trace_of_three_pinned_steps():
    """The recorded device events under a host tracer that covered them all:
    the first step begins at the record's first instant, where the profiler
    may have cut it, so two of the three are whole; a step still reads 28.62 ms."""
    out = tr.reduce_events(presets.host_tracer_over(_recorded()), {"jit_step": 1}, chips=1)
    assert out["steps"] == 2
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
    # every op by label for a reader of one kernel's time; the breakdown is its ten largest
    assert "sort_s32_106496_" in out["op_s"] and "sort_s32_106496_" not in dict(top)
    assert sorted(out["op_s"].items(), key=lambda kv: -kv[1])[:10] == [tuple(t) for t in top]


def test_recorded_trace_as_it_was_kept_holds_one_whole_step():
    """As recorded, the host tracer's events run from 1.67 to 65.52 ms and the
    device's to 85.91 ms: the third step ran in the profiler's stop phase and
    only the second is whole, so nothing is read a step; the ops and busy
    time of the host tracer's 63.85 ms still are."""
    out = tr.reduce_events(_recorded(), {"jit_step": 1}, chips=1)
    assert out["steps"] is None and out["device_ms_per_step"] is None and out["idle_share"] is None
    assert out["window_s"] == pytest.approx(65.520206e-3 - 1.671682e-3)
    assert 0.99 * out["window_s"] < out["busy_s"] <= out["window_s"]
    top = out["breakdown"]["device_ops"]
    assert top[0][0] == "fusion_f32_11735473_128_" and top[0][1] / out["busy_s"] > 0.5
    assert sum(v for _k, v in top) <= out["busy_s"] * 1.0001
