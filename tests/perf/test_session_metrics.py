"""The four per-layer metrics of the host side that the benchmark's accounting
had no word for: three read ``FusedTrainCtx.train_step``'s spans off the
totals the program keeps while a profiler session is live
(``tracing.session_totals()``), one reads the feeder's pull of the caller's
iterator off ``stream_stats()["waits"]``. Worked answers on recorded dicts,
nothing where the process saw no session or the program keeps no such totals,
and the real spans under a real session."""

import time

import pytest

import perf_presets as presets
from perf import harness
from persia_tpu import tracing

FUSED = ("fused_stage_ms_per_step", "fused_dispatch_ms_per_step", "caller_wait_share")
NEW = FUSED + ("source_wait_ms_per_step",)
FUSED_CELLS = ["tb-pinned-share16", "sdar-ep8-bd4-seq4k", "mellum2-ep4-pack16k"]

# session_totals() after the traced slice of one tb-pinned-share16 run on the
# v5e (my chip run, PR 38, seed 3800000101), whole
SESSION = {
    "wall_s": 3.993562644000008,
    "stages": {
        "fused.stage": {"n": 552, "busy_s": 0.448939825999787, "max_s": 0.0020804310000244186},
        # one fewer: the session's end fell inside a dispatch, which counts for nothing
        "fused.dispatch": {"n": 551, "busy_s": 2.8759316300001387, "max_s": 0.006874418999984755},
    },
    "waits": {},  # the window fetches nothing: no fused.fetch
}
# the window's stream of one traced tb-cached-resident run on the v5e (my chip
# run, PR 38, seed 3800000103), cut to the keys the reader takes
STREAM = {
    "packs": 351, "packed_steps": 2808, "single_steps": 5, "wall_s": 20.012546875999988,
    "feeder_busy_s": 10.105836804999967,
    "stages": {
        "stream.prep": {"n": 2813, "busy_s": 10.105836804999967, "max_s": 0.011978279000004477},
        "stream.stage": {"n": 2813, "busy_s": 4.5082954990015764, "max_s": 0.0031215789999805565},
    },
    "waits": {
        # one more than the steps: the pull that found the window's end
        "stream.source_wait": {"n": 2814, "wait_s": 8.629477718999397, "max_s": 0.05636948400001529},
        "stream.prep_put_wait": {"n": 87, "wait_s": 0.9407276850002404, "max_s": 0.021224307000011322},
        "stream.dispatch_get_wait": {"n": 2441, "wait_s": 13.812191642997902, "max_s": 0.013497799000049326},
    },
}
WORKED = {  # what those runs' result lines printed
    "fused_stage_ms_per_step": 0.8147728239560562,
    "fused_dispatch_ms_per_step": 5.219476642468491,
    "caller_wait_share": 16.7442268372761,
    "source_wait_ms_per_step": 3.06771337326676,
}


@pytest.fixture(autouse=True)
def _restore_matmul_precision():
    """A run sets the configuration's matmul precision for its process; a test
    worker goes on to other files, so put it back."""
    import jax

    before = jax.config.jax_default_matmul_precision
    yield
    jax.config.update("jax_default_matmul_precision", before)


def _read(name, counters, session, monkeypatch):
    monkeypatch.setattr(tracing, "session_totals", lambda: session)
    spec = harness.load_metric(name)
    return harness.load_module("readers", spec["reader"]).read({"counters": counters})


@pytest.mark.parametrize("name", NEW)
def test_reader_gives_the_worked_number(name, monkeypatch):
    counters = {"h2d_bytes": 1, "stream_stats": STREAM} if name not in FUSED else {"h2d_bytes": 1}
    assert _read(name, counters, SESSION, monkeypatch) == pytest.approx(WORKED[name])
    spec = harness.load_metric(name)
    assert spec["source"] == "program_span" and spec["moves"] == "samples_per_s_chip"


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_where_there_is_nothing_to_read(name, monkeypatch):
    pinned = {"h2d_bytes": 1}  # the pinned entry's counters
    assert _read(name, pinned, None, monkeypatch) is None  # no session seen: an untraced run
    # the stream's accounting of a program without the span, and an entry with no stream
    before = dict(STREAM, waits={k: v for k, v in STREAM["waits"].items()
                                 if k != "stream.source_wait"})
    for counters in (dict(pinned, stream_stats=before), dict(pinned, stream_stats={})):
        assert _read(name, counters, None, monkeypatch) is None
    # a session in which the span never closed: n 0, or not there at all
    idle = dict(SESSION, stages={"fused.stage": {"n": 0, "busy_s": 0.0, "max_s": 0.0},
                                 "fused.dispatch": {"n": 0, "busy_s": 0.0, "max_s": 0.0}}, waits={})
    never = dict(STREAM, waits=dict(STREAM["waits"],
                                    **{"stream.source_wait": {"n": 0, "wait_s": 0.0, "max_s": 0.0}}))
    assert _read(name, dict(pinned, stream_stats=never), idle, monkeypatch) is None
    assert _read(name, pinned, {"wall_s": 4.0, "stages": {}, "waits": {}}, monkeypatch) is None
    # the parent commit's program: no session_totals at all
    monkeypatch.delattr(tracing, "session_totals")
    spec = harness.load_metric(name)
    assert harness.load_module("readers", spec["reader"]).read({"counters": pinned}) is None


def test_the_four_are_listed_for_their_cells():
    bench = harness.load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in FUSED:
        assert by_name[name]["workloads"] == FUSED_CELLS
    assert by_name["source_wait_ms_per_step"]["workloads"] == ["tb-cached-resident"]
    for name in NEW:
        assert by_name[name]["moves"] == "samples_per_s_chip"
    cached = {m["name"] for m in harness.cell_metrics(bench, "tb-cached-resident", "per_layer")}
    assert "source_wait_ms_per_step" in cached and not cached & set(FUSED)
    layers = {by_name[n]["layer"] for n in NEW}
    assert layers == {"h2d staging", "step dispatch", "traffic generator", "host feeder"}


def test_pinned_entry_under_a_profiler_session_prints_the_three(tmp_path):
    import jax

    c = harness.find_cell(harness.load_benchmark(), "tb-pinned-share16")
    preset = presets.REHEARSAL["fused_pinned"]
    config = dict(harness.load_config(c["config"]), **preset["config"])
    traffic = dict(harness.load_traffic(c["traffic"]), **preset["traffic"])
    entry = harness.load_module("entries", traffic["entry"]).Entry(config, traffic, 7)
    entry.build()
    gen = iter(harness.load_module("generators", traffic["generator"]).make(config, traffic, 7))
    entry.ctx.train_step(entry.to_program_batch(next(gen)), fetch_metrics=False)  # compiles
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    steps = 5
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for i in range(steps):
            batch = entry.to_program_batch(next(gen))  # the caller's own time
            entry.ctx.train_step(batch, fetch_metrics=(i == steps - 1))
    finally:
        jax.profiler.stop_trace()
    entry.ctx.train_step(entry.to_program_batch(next(gen)), fetch_metrics=False)  # after: not counted
    t = tracing.session_totals()
    assert t["stages"]["fused.stage"]["n"] == t["stages"]["fused.dispatch"]["n"] == steps
    assert t["waits"]["fused.fetch"]["n"] == 1
    inside = (t["stages"]["fused.stage"]["busy_s"] + t["stages"]["fused.dispatch"]["busy_s"]
              + t["waits"]["fused.fetch"]["wait_s"])
    assert 0.0 < inside < t["wall_s"]
    facts = {"counters": entry.counters()}
    got = {n: harness.load_module("readers", harness.load_metric(n)["reader"]).read(facts)
           for n in FUSED}
    assert got["fused_stage_ms_per_step"] == pytest.approx(
        1e3 * t["stages"]["fused.stage"]["busy_s"] / steps)
    assert got["fused_dispatch_ms_per_step"] == pytest.approx(
        1e3 * t["stages"]["fused.dispatch"]["busy_s"] / steps)
    assert got["caller_wait_share"] == pytest.approx(100.0 * (1.0 - inside / t["wall_s"]))
    assert 0.0 < got["caller_wait_share"] < 100.0
    entry.free()


def test_rehearsal_of_the_cached_cell_prints_the_feeders_pull():
    preset = dict(presets.REHEARSAL["cached_stream"])
    out = harness.run_cell("tb-cached-resident", 2 ** 31 + 31, 0.6, True, time.perf_counter(),
                           rehearsal=preset)
    assert out["metrics"]["source_wait_ms_per_step"]["value"] >= 0.0
    assert out["metrics"]["source_wait_ms_per_step"]["unit"] == "ms/step"
    assert not set(FUSED) & set(out["metrics"])  # not the cached cell's
