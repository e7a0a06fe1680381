"""The one span primitive and what rests on it: a span lands in a
``jax.profiler`` trace under its name, costs next to nothing with no session,
feeds one per-stream accounting of busy and wait time that leaves no hole in
the dispatcher's thread or the feeder's, and the device steps carry the named
scopes a device trace attributes time by."""

import glob
import os
import re
import sys
import time

import numpy as np
import pytest

from persia_tpu import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tests", "perf"))

import perf_presets as presets  # noqa: E402  (puts the checkout on sys.path)
from perf import harness  # noqa: E402


@pytest.fixture(autouse=True)
def _ring_off():
    tracing.enable(False)
    tracing.clear()
    yield
    tracing.enable(False)
    tracing.clear()


# ------------------------------------------------- (a) the profiler's trace

def _host_events(trace_dir):
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    assert files, f"the profiler wrote no trace under {trace_dir}"
    out = {}
    for plane in ProfileData.from_file(files[0]).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    out.setdefault(e.name, []).append((e, dict(e.stats)))
    return out


def test_spans_land_in_the_profilers_host_plane(tmp_path):
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with tracing.span("spans_test.plain", seq=7, group="g"):
            with tracing.stage_span("spans_test.stage", seq=7):
                with tracing.wait_span("spans_test.wait") as w:
                    time.sleep(0.002)
                    w.set(rows=5)
    finally:
        jax.profiler.stop_trace()
    host = _host_events(str(tmp_path))
    if not host:
        pytest.skip("the CPU profiler wrote no /host:CPU plane")
    for name in ("spans_test.plain", "spans_test.stage", "spans_test.wait"):
        assert name in host, f"{name} is not in the trace: {sorted(host)[:20]}"
    event, stats = host["spans_test.plain"][0]
    assert stats["seq"] == 7 and stats["group"] == "g"  # attributes ride as stats
    assert event.duration_ns >= 2_000_000
    assert host["spans_test.wait"][0][1]["rows"] == 5  # set before the span closed
    assert tracing.spans_snapshot() == []  # the ring stayed off: no switch was thrown


# ------------------------------------------------------ (b) what a span costs

@pytest.mark.parametrize("kind", ["span", "stage_span", "wait_span"])
def test_span_with_no_session_stays_cheap(kind):
    import jax  # noqa: F401  (with JAX imported every span opens an annotation)

    assert not tracing.enabled()
    opener = getattr(tracing, kind)
    n = 20_000
    t0 = time.perf_counter()
    for i in range(n):
        with opener("noop", seq=i):
            pass
    per_call_us = (time.perf_counter() - t0) / n * 1e6
    assert tracing.spans_snapshot() == []
    # the bound tests/test_telemetry.py sets for a disabled span
    assert per_call_us < 25.0, f"{kind} with no session costs {per_call_us:.1f}us"


def test_accumulator_keeps_wait_out_of_busy():
    now = [0.0]
    acc = tracing.StageAccumulator(clock=lambda: now[0])
    with tracing.accumulate(acc):
        for dt_work, dt_wait in ((2.0, 1.0), (4.0, 0.0)):
            with tracing.stage_span("work"):
                now[0] += dt_work
                if dt_wait:
                    with tracing.wait_span("blocked"):
                        now[0] += dt_wait
    with tracing.stage_span("work"):  # no accumulator bound: counted nowhere
        now[0] += 100.0
    assert acc.stages == {"work": {"n": 2, "busy_s": 6.0, "max_s": 4.0}}
    assert acc.waits == {"blocked": {"n": 1, "wait_s": 1.0, "max_s": 1.0}}
    assert acc.busy_s("work", "absent") == 6.0


def test_compiles_become_flight_events():
    import jax
    import jax.numpy as jnp

    from persia_tpu.compile_cache import enable_compile_cache

    enable_compile_cache()
    tracing.flight_clear()

    def spans_test_program(x):
        return x * 3 + 1

    jax.jit(spans_test_program)(jnp.arange(5.0)).block_until_ready()
    found = [e for e in tracing.flight_snapshot()
             if e["kind"] == "compile" and "spans_test_program" in e["attrs"]["program"]]
    assert len(found) == 1, tracing.flight_snapshot()
    assert float(found[0]["attrs"]["secs"]) > 0 and found[0]["attrs"]["cached"] == "False"


def test_sharded_walk_has_one_live_span_around_the_native_call():
    """The native pool reports its shards' walks after the fact (``feed.shard``,
    ring only); ``feed.walk`` is the live span they lie inside."""
    from persia_tpu.embedding.hbm_cache.directory import CacheDirectory

    tracing.enable(True)
    d = CacheDirectory(256, shards=4, feed_threads=2)
    d.feed_batch(np.arange(100, dtype=np.uint64), None)
    walks = [s for s in tracing.spans_snapshot() if s["name"] == "feed.walk"]
    assert len(walks) == 1
    assert walks[0]["args"]["shards"] == "4"
    assert int(walks[0]["args"]["slowest_ns"]) == int(d.shard_busy_ns().max()) > 0


# ------------------------------- (c), (d) the stream's one time accounting

def _stream_stats(k, slow_s, uniform=False, source_s=0.0):
    from test_hbm_cache import UNIFORM_STREAM, _block_batches, _one_slot_ctx

    cfg, batches = _block_batches(36, **(UNIFORM_STREAM if uniform else {}))
    ctx, _store = _one_slot_ctx(cfg, cache_rows=136)
    orig = ctx._step

    def slow_step(*a):  # a step the host can see: the queues fill behind it
        time.sleep(slow_s)
        return orig(*a)

    ctx._step = slow_step

    def late_start():  # the dispatcher finds nothing staged at first
        time.sleep(0.05)
        for b in batches:
            time.sleep(source_s)  # a source the feeder has to wait for
            yield b

    with ctx:
        ctx.train_stream(late_start(), dispatch_k=k, wb_flush_steps=2, prefetch=2)
        st = ctx.stream_stats()
    return st


WORK_ALWAYS = {"stream.prep", "stream.stage", "stream.wb_flush", "stream.wb_fetch",
               "stream.wb_store", "stage.feed", "stage.dense", "stage.psgrad"}
CASES = {
    # in order, step by step: the aux programs go out one by one
    "in_order": (1, False, WORK_ALWAYS | {"stream.dispatch", "ctx.apply_aux"},
                 {"stream.dispatch_get_wait", "stream.prep_put_wait", "stream.stage_put_wait",
                  "stream.drain"}),
    # K-step packs: the aux rides inside the pack's program
    "packed": (4, False, WORK_ALWAYS | {"stream.dispatch_pack"},
               {"stream.dispatch_get_wait", "stream.drain"}),
    # the width the benchmark's cached cell runs, over test_hbm_cache.UNIFORM_STREAM
    # (test_stream_kstep_packing_bitwise_parity says why)
    "packed_k8": (8, True,
                  WORK_ALWAYS | {"stream.dispatch_pack"},
                  {"stream.dispatch_get_wait", "stream.drain"}),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_stream_accounts_for_every_stage_and_wait(case):
    k, uniform, work, waits = CASES[case]
    st = _stream_stats(k, slow_s=0.02, uniform=uniform)
    stages, wts = st["stages"], st["waits"]
    assert work <= set(stages), f"missing work spans: {work - set(stages)}"
    assert waits <= set(wts), f"missing wait spans: {waits - set(wts)}"
    for row in stages.values():
        assert row["n"] >= 1 and 0.0 <= row["max_s"] <= row["busy_s"] + 1e-9
    for row in wts.values():
        assert row["n"] >= 1 and 0.0 <= row["max_s"] <= row["wait_s"] + 1e-9
    n_dispatch = sum(stages.get(n, {"n": 0})["n"] for n in ("stream.dispatch", "stream.dispatch_pack"))
    assert n_dispatch == st["packs"] + st["single_steps"]
    assert stages["stream.prep"]["n"] == stages["stream.stage"]["n"] == 36
    # (d) the keys the stream had before are read off the same accounting
    assert st["feeder_busy_s"] == stages["stream.prep"]["busy_s"]
    for lane in ("feed", "dense"):
        assert st["stage_wall_s"][lane] == round(stages[f"stage.{lane}"]["busy_s"], 6)
    # read as the dispatcher closed, before the write-back thread's last flush
    assert st["stage_wall_s"]["psgrad"] <= round(stages["stage.psgrad"]["busy_s"], 6)
    # the write-back's two halves lie inside the flush
    assert (stages["stream.wb_fetch"]["busy_s"] + stages["stream.wb_store"]["busy_s"]
            <= stages["stream.wb_flush"]["busy_s"])


def test_dispatcher_thread_has_no_hole():
    """Busy in the dispatch calls plus blocked on the empty staged queue is
    the dispatcher's whole stream, give or take its bookkeeping."""
    st = _stream_stats(1, slow_s=0.03)
    busy = st["stages"]["stream.dispatch"]["busy_s"]
    wait = st["waits"]["stream.dispatch_get_wait"]["wait_s"]
    assert 0.85 * st["wall_s"] <= busy + wait <= st["wall_s"], (busy, wait, st["wall_s"])
    # the closing drain is a wait of its own, after wall_s is taken
    assert st["waits"]["stream.drain"]["n"] >= 1


def test_feeder_thread_has_no_hole():
    """Pulling the caller's iterator, busy in ``stream.prep`` and blocked on
    the full queue downstream or on a ring is the feeder's whole stream."""
    st = _stream_stats(1, slow_s=0.0, source_s=0.03)
    waits = st["waits"]
    assert waits["stream.source_wait"]["n"] == 36 + 1  # every batch, and the pull that found the end
    assert waits["stream.source_wait"]["wait_s"] >= 0.05 + 36 * 0.03
    covered = st["stages"]["stream.prep"]["busy_s"] + sum(
        waits.get(n, {"wait_s": 0.0})["wait_s"]
        for n in ("stream.source_wait", "stream.prep_put_wait", "stream.ring_wait"))
    assert 0.85 * st["wall_s"] <= covered <= st["wall_s"], (covered, st["wall_s"], waits)
    # feeder_busy_s keeps its meaning: stream.prep alone
    assert st["feeder_busy_s"] == st["stages"]["stream.prep"]["busy_s"]


# ------------------------------------------- (e) named scopes in the steps

MODEL_SCOPES = {"gather", "pool", "bottom_mlp", "interaction", "top_mlp", "loss", "dense_opt",
                "sparse_prep", "sparse_update", "dedup", "row_update", "gather_rows",
                "scatter_table", "scatter_acc"}
_LOC_DEF = re.compile(r'^#loc(\d+) = loc\("([^"]*)"', re.M)


def _entry(kind):
    cell = presets.CELL_OF_ENTRY[kind]
    c = harness.find_cell(harness.load_benchmark(), cell)
    config = dict(harness.load_config(c["config"]), **presets.REHEARSAL[kind]["config"])
    traffic = dict(harness.load_traffic(c["traffic"]), **presets.REHEARSAL[kind]["traffic"])
    entry = harness.load_module("entries", traffic["entry"]).Entry(config, traffic, 5)
    entry.build()
    gen = iter(harness.load_module("generators", traffic["generator"]).make(config, traffic, 5))
    return entry, gen


def _staged(entry, gen):
    ctx = entry.ctx
    di, layout, miss, cold, _restore, evict, _meta = ctx.tier.prepare_batch(
        entry.to_program_batch(next(gen)))
    di, miss, cold, evict = ctx._stage(di, miss, cold, evict)
    return di, layout


def _lower_cached_step():
    from persia_tpu.embedding.hbm_cache.step import build_cached_train_step

    entry, gen = _entry("cached_stream")
    ctx = entry.ctx
    di, layout = _staged(entry, gen)
    step = build_cached_train_step(  # the guard is built only where the probe is on
        ctx.model, ctx.dense_optimizer, ctx.sparse_cfg, ctx.tier.groups,
        sentinel_probe=True, guard_clip_norm=1.0)
    rows = ctx.state.tables[entry.group.name].shape
    return step, step.lower(ctx.state, di, layout), rows, MODEL_SCOPES | {"grad_guard"}


def _lower_kstep_pack():
    entry, gen = _entry("cached_stream")
    ctx = entry.ctx
    steps, layout = [], None
    for _ in range(entry.dispatch_k):
        di, layout = _staged(entry, gen)
        steps.append((di, {}))
    run = ctx._kstep_fn()
    rows = ctx.state.tables[entry.group.name].shape
    return run, run.lower(ctx.state, {}, tuple(steps), layout), rows, MODEL_SCOPES


def _lower_fused_step():
    from persia_tpu.parallel.fused_ctx import batch_to_fused

    entry, gen = _entry("fused_pinned")
    ctx = entry.ctx
    fb = batch_to_fused(entry.to_program_batch(next(gen)), ctx.specs)
    rows = ctx.state.tables[entry.group.name].shape
    return ctx._step, ctx._step.lower(ctx.state, fb), rows, MODEL_SCOPES


def _lower_aux_programs():
    import jax.numpy as jnp

    from persia_tpu.embedding.hbm_cache import groups

    table, acc = jnp.zeros((65, 8)), {"acc": jnp.ones((65, 8))}
    idx = jnp.arange(4, dtype=jnp.int32)
    lowered = groups._apply_aux_ring.lower(
        table, acc, jnp.zeros((16, 16)), jnp.int32(0), idx, idx, jnp.zeros((4, 16)),
        idx, jnp.zeros((4, 8)), (("acc", 0.1),), False)
    return groups._apply_aux_ring, lowered, table.shape, {"aux_scatter", "evict_gather"}


def _lower_restore():
    import jax.numpy as jnp

    from persia_tpu.embedding.hbm_cache import groups

    table, acc = jnp.zeros((65, 8)), {"acc": jnp.ones((65, 8))}
    idx = jnp.arange(4, dtype=jnp.int32)
    lowered = groups._restore_rows.lower(table, acc, jnp.zeros((16, 16)), idx, idx)
    return groups._restore_rows, lowered, table.shape, {"restore"}


LOWERINGS = {
    "cached_step": (_lower_cached_step, "step"),
    "kstep_pack": (_lower_kstep_pack, "run"),
    "fused_step": (_lower_fused_step, "step"),
    "aux_programs": (_lower_aux_programs, "_apply_aux_ring"),
    "restore": (_lower_restore, "_restore_rows"),
}


@pytest.mark.parametrize("which", sorted(LOWERINGS))
def test_device_programs_carry_their_named_scopes(which):
    build, fn_name = LOWERINGS[which]
    jitted, lowered, table_shape, scopes = build()
    # the benchmark finds the step programs by these names (jit_step, jit_run)
    assert jitted.__name__ == fn_name
    text = lowered.as_text(debug_info=True)
    assert f"module @jit_{fn_name} " in text
    names = dict(_LOC_DEF.findall(text))
    in_scopes = set()
    for name in names.values():
        in_scopes.update(re.split(r"[/()]+", name))
    assert scopes <= in_scopes, f"scopes not in the lowered op names: {scopes - in_scopes}"
    # every operation that yields a whole table lies under one of the scopes
    table_ty = "tensor<" + "x".join(str(d) for d in table_shape) + "xf32>"
    whole = re.findall(r"-> \(?[^\n]*" + re.escape(table_ty) + r"[^\n]*loc\(#loc(\d+)\)", text)
    assert whole, f"no operation yields {table_ty}"
    known = MODEL_SCOPES | {"grad_guard", "aux_scatter", "evict_gather", "restore"}
    for loc in whole:
        if names.get(loc, "").rsplit("/", 1)[-1].startswith("jit("):
            continue  # the call of a jitted function inside this one, not an operation
        parts = set(re.split(r"[/()]+", names.get(loc, "")))
        assert parts & known, f"an op over the whole table lies under no scope: {names.get(loc)!r}"
