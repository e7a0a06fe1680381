"""The four per-layer metrics that read the stream's span accounting
(``stream_stats()["stages"]`` / ``["waits"]``): worked answers on a recorded
dict, nothing on a program that has no such accounting, and in a rehearsal
the cached cell prints all four and the pinned cell none. And the contract
the program's spans lean on in the trace reduction: host events of 50 us or
more on the ``/host:CPU`` plane are kept and name the idle gaps they cover."""

import json
import os
import re
import time

import pytest

import perf_presets as presets
from perf import harness
from perf import trace_reduce as tr

NEW = ("stage_ms_per_step", "dispatch_ms_per_step", "dispatch_ms_max", "dispatch_starved_share")

# the window's stream of one traced tb-cached-resident run on the v5e (my chip
# run, PR 26, seed 2147483869), cut to the keys the readers take
RECORDED = {
    "packs": 119, "packed_steps": 952, "single_steps": 5, "wall_s": 20.420668193,
    "feeder_busy_s": 3.460094698,
    "stages": {
        "stream.prep": {"n": 957, "busy_s": 3.460094698, "max_s": 0.049910967},
        "stream.stage": {"n": 957, "busy_s": 1.57692857, "max_s": 0.00333461},
        "stream.dispatch_pack": {"n": 119, "busy_s": 17.992114131, "max_s": 0.227194248},
        "stream.dispatch": {"n": 5, "busy_s": 0.00867609, "max_s": 0.00377683},
    },
    "waits": {
        "stream.dispatch_get_wait": {"n": 676, "wait_s": 2.250667232, "max_s": 0.050485287},
        "stream.stage_put_wait": {"n": 81, "wait_s": 15.886687614, "max_s": 0.206513271},
        "stream.prep_put_wait": {"n": 80, "wait_s": 14.62455768, "max_s": 0.194273084},
        "stream.drain": {"n": 2, "wait_s": 7.433631262, "max_s": 7.432045592},
    },
}
WORKED = {  # what that run's result line printed
    "stage_ms_per_step": 1.6477832497,
    "dispatch_ms_per_step": 18.8096031567,
    "dispatch_ms_max": 227.194248,
    "dispatch_starved_share": 11.0215160970,
}


def _read(name, stream_stats):
    spec = harness.load_metric(name)
    reader = harness.load_module("readers", spec["reader"])
    return reader.read({"counters": {"h2d_bytes": 1, "stream_stats": stream_stats}})


@pytest.mark.parametrize("name", NEW)
def test_reader_gives_the_worked_number(name):
    assert _read(name, RECORDED) == pytest.approx(WORKED[name])
    spec = harness.load_metric(name)
    assert spec["source"] == "program_span" and spec["moves"] == "samples_per_s_chip"


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_where_the_program_keeps_no_accounting(name):
    parent = {k: v for k, v in RECORDED.items() if k not in ("stages", "waits")}
    assert _read(name, parent) is None  # the parent commit's stream_stats()
    assert _read(name, {}) is None  # an entry with no stream at all
    spec = harness.load_metric(name)
    reader = harness.load_module("readers", spec["reader"])
    assert reader.read({"counters": {"h2d_bytes": 1}}) is None  # the pinned entry's counters


def test_only_the_cached_cell_lists_them():
    bench = harness.load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert set(NEW) <= set(by_name)
    for name in NEW:
        assert by_name[name]["workloads"] == ["tb-cached-resident"]
    pinned = {m["name"] for m in harness.cell_metrics(bench, "tb-pinned-share16", "per_layer")}
    assert not pinned & set(NEW)


def test_rehearsal_of_the_cached_cell_prints_all_four():
    preset = dict(presets.REHEARSAL["cached_stream"])
    out = harness.run_cell("tb-cached-resident", 2 ** 31 + 29, 0.6, True, time.perf_counter(),
                           rehearsal=preset)
    assert set(NEW) <= set(out["metrics"]), sorted(out["metrics"])
    for name in NEW:
        assert out["metrics"][name]["value"] >= 0.0
    assert out["metrics"]["dispatch_starved_share"]["value"] <= 100.0
    assert out["metrics"]["dispatch_ms_max"]["value"] >= out["metrics"]["dispatch_ms_per_step"]["value"]


# ------------------------------------------------- the recorded traced packs

def _recorded_events():
    path = os.path.join(presets.ROOT, "perf", "fixtures", "trace_cached_spans.json")
    with open(path) as f:
        return [tuple(e) for e in json.load(f)]


def test_recorded_cached_trace_keeps_the_programs_spans():
    events = _recorded_events()
    host = {e[2] for e in events if e[0].startswith("/host:CPU")}
    assert {"stream.prep", "stream.stage", "stream.dispatch_pack"} <= host
    # what the program leans on: its spans are host events of 50 us or more
    for e in events:
        if e[0].startswith("/host:CPU"):
            assert e[4] >= tr.HOST_MIN_NS
    # as kept, the host's events end at 282 ms and the device's at 465: neither
    # pack is whole, and nothing is read a step
    out = tr.reduce_events(events, {"jit_step": 1, "jit_run": 8}, chips=1)
    assert out["steps"] is None and out["device_ms_per_step"] is None
    assert out["breakdown"]["device_ops"][0][0] == "fusion_f32_6291457_128_"
    # under a host tracer that covered the device's record: the first pack
    # begins at its first instant, where the profiler may have cut it, so the
    # second is the one whole pack of eight
    out = tr.reduce_events(presets.host_tracer_over(events), {"jit_step": 1, "jit_run": 8}, chips=1)
    assert out["steps"] == 8
    assert out["device_ms_per_step"] == pytest.approx(29.04, abs=0.01)
    assert out["breakdown"]["device_ops"][0][0] == "fusion_f32_6291457_128_"


def test_a_gap_inside_a_dispatch_span_is_named_by_a_host_event():
    """A device idle gap planted inside a recorded ``stream.dispatch_pack``
    span: the reduction names it by a host event that covers it, not
    ``inside_the_program``."""
    events = _recorded_events()
    packs = sorted((e for e in events if e[2] == "stream.dispatch_pack"), key=lambda e: -e[4])
    _p, _l, _n, s0, d0 = packs[0]
    dev = [e for e in events if e[0].startswith("/device:")]
    gap0, gap1 = s0 + 0.25 * d0, s0 + 0.75 * d0
    # cut the device's operations out of the middle of the span
    kept = [e for e in dev if e[3] + e[4] <= gap0 or e[3] >= gap1]
    assert len(kept) < len(dev)
    rest = [e for e in events if not e[0].startswith("/device:")]
    out = tr.reduce_events(kept + rest, {"jit_step": 1, "jit_run": 8}, chips=1)
    name, secs = out["breakdown"]["idle_gaps"][0]
    assert secs >= 0.4 * d0 * 1e-9
    covering = {e[2] for e in rest if e[3] <= gap0 and e[3] + e[4] >= gap1}
    assert "stream.dispatch_pack" in covering
    # nested host events tie on overlap and the first found wins (PERF.md 7)
    assert name != "inside_the_program" and name in {re.sub(r"[^\w.]+", "_", n)[:64] for n in covering}
