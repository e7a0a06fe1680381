"""Stage lanes (parallel/stage_graph.py), the one dispatch order of the
cached stream, and the staged driver of the fused tier (``FusedPipeline``).

- ``test_unit_*``: lane accounting, rebuild hooks and ``FusedPipeline``'s
  staging bound in isolation — fast, no XLA dispatch; these ride the
  preflight's step-1 subset (scripts/round_preflight.sh).
- the stream runs: ``pipeline_depth`` is a vestige that accepts 1 only, the
  stream carries nothing of the feed-hoisting regime PR 30 removed, and the
  rebuild hook fires once, at the migration fence, without changing a bit.
- the fused runs: bit parity with the ``train_step`` loop, and a dispatch or
  a stage that raises leaves no feeder thread behind.
"""

import threading
import time

import numpy as np
import pytest

from persia_tpu.parallel.stage_graph import StageGraph

# ------------------------------------------------------------ unit: lanes


def test_unit_rebuild_hooks_fire_with_step():
    from persia_tpu import tracing

    g = StageGraph()
    got = []
    g.on_rebuild(got.append)
    g.on_rebuild(lambda s: got.append(s * 10))
    tracing.flight_clear()
    g.rebuild(7)
    assert got == [7, 70]
    assert any(
        e["kind"] == "pipeline.rebuild" and e["attrs"]["step"] == "7"
        for e in tracing.flight_snapshot()
    )


def test_unit_lane_overlap_stats():
    now = [0.0]
    g = StageGraph(clock=lambda: now[0])

    def spend(stage, dt):
        with g.lane(stage):
            now[0] += dt

    spend("feed", 2.0)
    spend("dense", 6.0)
    st = g.stats(wall_s=6.0)  # 2s of feed hidden under the 6s of dense
    assert set(st) == {"stage_wall_s", "stage_overlap_frac"}
    assert st["stage_wall_s"] == {"feed": 2.0, "dense": 6.0, "psgrad": 0.0}
    assert st["stage_overlap_frac"] == pytest.approx(2.0 / 8.0)
    serial = StageGraph(clock=lambda: now[0]).stats(wall_s=0.0)
    assert serial["stage_overlap_frac"] == 0.0


# ------------------------------------- unit: FusedPipeline's staging bound


def _fake_pipeline(depth, step_s=0.0, fail_at=None):
    """A FusedPipeline over plain Python callables (no XLA): the state
    counts dispatched batches, ``ahead`` those staged and not dispatched."""
    from persia_tpu.parallel.fused_step import FusedPipeline

    lock = threading.Lock()
    seen = {"ahead": 0, "peak": 0, "staged": 0}

    def stage(b):
        with lock:
            seen["staged"] += 1
            seen["ahead"] += 1
            seen["peak"] = max(seen["peak"], seen["ahead"])
        return b

    def step(state, b):
        if fail_at is not None and state + 1 == fail_at:
            raise RuntimeError("dispatch died")
        time.sleep(step_s)  # the feeder runs ahead as far as it is let
        with lock:
            seen["ahead"] -= 1
        return state + 1, (b, None)

    return FusedPipeline(step, depth=depth), stage, seen


def _feeder_threads():
    return [t for t in threading.enumerate() if t.name == "fused-pipe-feeder"]


@pytest.mark.parametrize("depth", [1, 2, 4])
def test_unit_window_capacity_is_the_depth(depth):
    """Never more than ``depth`` batches between ``stage`` and their
    dispatch, and a slow dispatch lets the feeder get that far."""
    pipe, stage, seen = _fake_pipeline(depth, step_s=0.01)
    state, losses = pipe.run(0, range(12), stage=stage)
    assert state == 12 and losses == list(range(12))  # stream order
    assert seen["peak"] == depth
    assert not _feeder_threads()


def test_unit_dispatch_error_leaves_no_feeder_thread():
    """The caller's dispatch raises at its third call with the feeder
    parked on a full window over an endless stream: ``run`` re-raises
    and the feeder thread is gone (ROADMAP D12's leak)."""
    import itertools

    pipe, stage, seen = _fake_pipeline(2, fail_at=3)
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="dispatch died"):
        pipe.run(0, itertools.count(), stage=stage)
    assert time.monotonic() - t0 < 5.0
    assert not _feeder_threads()
    # the window held to the end: two dispatched, at most two more staged
    assert seen["staged"] <= 4


def test_unit_stage_error_is_the_one_raised():
    from persia_tpu.parallel.fused_step import FusedPipeline

    def stage(b):
        if b == 2:
            raise KeyError("stage died")
        return b

    pipe = FusedPipeline(lambda s, b: (s + 1, (b, None)), depth=2)
    with pytest.raises(KeyError, match="stage died"):
        pipe.run(0, range(6), stage=stage)
    assert not _feeder_threads()


# ------------------------------------------ cached stream: one dispatch order


@pytest.mark.parametrize("depth", [1, 0, 2, 3])
def test_pipeline_depth_validation(depth):
    """The keyword stays for the benchmark's entry, with the one value 1."""
    from test_hbm_cache import _block_batches, _one_slot_ctx

    cfg, batches = _block_batches(2)
    ctx, _ = _one_slot_ctx(cfg, cache_rows=64)
    with ctx:
        if depth == 1:
            m = ctx.train_stream(batches, pipeline_depth=depth)
            assert np.isfinite(m["loss"])
        else:
            with pytest.raises(ValueError, match="pipeline_depth must be 1.*PR 30"):
                ctx.train_stream(batches, pipeline_depth=depth)


def test_stream_carries_nothing_of_the_removed_regime(monkeypatch):
    """A stream with misses and evictions starts its three threads and no
    other, and its stats hold no key of the feed-hoisting regime."""
    from test_hbm_cache import _block_batches, _one_slot_ctx

    started = []
    real_start = threading.Thread.start

    def start(self):
        started.append(self.name)
        real_start(self)

    monkeypatch.setattr(threading.Thread, "start", start)
    cfg, batches = _block_batches(12)
    ctx, _ = _one_slot_ctx(cfg, cache_rows=40)
    with ctx:
        ctx.train_stream(batches, dispatch_k=4, wb_flush_steps=2)
        st = ctx.stream_stats()
    monkeypatch.undo()
    # the write-back's concurrent d2h fetches come from a pool of the ctx
    own = [n for n in started if not n.startswith("cache-fetch")]
    assert own == ["cache-feeder", "cache-stager", "cache-writeback"]
    assert st["stages"]["ctx.apply_aux"]["n"] >= 1  # it did miss
    assert st["packed_steps"] + st["single_steps"] == 12
    gone = [k for k in st if k.startswith("pipelin")]
    assert not gone, gone
    # every span of the stream is one of PERF.md 3b's table
    assert set(st["stages"]) <= {
        "stream.prep", "stream.stage", "stream.dispatch", "stream.dispatch_pack",
        "ctx.apply_aux", "stream.wb_flush", "stream.wb_fetch", "stream.wb_store",
        "stage.feed", "stage.dense", "stage.psgrad",
    }, set(st["stages"])
    assert set(st["waits"]) <= {
        "stream.ring_wait", "stream.prep_put_wait", "stream.stage_get_wait",
        "stream.stage_put_wait", "stream.dispatch_get_wait", "stream.drain",
        "stream.source_wait",
    }, set(st["waits"])
    assert set(st["stage_wall_s"]) == {"feed", "dense", "psgrad"}


def test_stream_migration_fence_fires_rebuild_hook_once(tmp_path):
    """A stream with a snapshot fence and a live tier migration mid-stream
    fires the registered rebuild hook exactly once, at the migration fence,
    and lands the bits of the same stream without a hook."""
    from test_tiering import (
        _assert_entries_equal,
        _assert_params_equal,
        _batches,
        _cfg,
        _make_ctx,
        _ps_entries,
        _stores,
    )

    cfg = _cfg()
    batches = _batches(8)

    # dispatch_k pinned to 1 in both runs: packs form timing-dependently
    # and K-step packing's bitwise parity is config-dependent (XLA
    # compiles the step subgraph differently inside a K program on this
    # two-slot adam config); pinning leaves the hook as the only variable
    stores_a = _stores()
    ctx_a = _make_ctx(stores_a)
    ctx_a.request_migration(to_ps=["cat_1"])
    ctx_a.train_stream(
        batches, snapshot_every=4, job_state=str(tmp_path / "js_a"),
        dispatch_k=1,
    )
    assert ctx_a.stream_stats()["migrations"] == 1
    ctx_a.flush()

    stores_b = _stores()
    ctx_b = _make_ctx(stores_b)
    ctx_b.request_migration(to_ps=["cat_1"])
    rebuilt = []
    ctx_b.register_stage_rebuild(rebuilt.append)
    ctx_b.train_stream(
        batches, snapshot_every=4, job_state=str(tmp_path / "js_b"),
        dispatch_k=1,
    )
    st = ctx_b.stream_stats()
    ctx_b.flush()

    assert st["migrations"] == 1 and st["fences"] >= 1
    assert rebuilt == [4], "rebuild hook must fire once, at the migration fence"
    _assert_params_equal(ctx_a.state.params, ctx_b.state.params)
    _assert_entries_equal(
        _ps_entries(cfg, stores_a), _ps_entries(cfg, stores_b)
    )


# --------------------------------------------------- fused-tier pipeline


def _fused_leaves(ctx):
    import jax

    return [np.asarray(x) for x in jax.tree_util.tree_leaves(ctx.state)]


def test_fused_pipeline_bit_parity_and_drain():
    """FusedTrainCtx.train_pipelined (depth 3, k=1): h2d staging overlaps
    the jitted step, every staged batch is dispatched before return, and
    every state leaf matches the sequential train_step loop bit for bit."""
    from test_fused_ctx import _batch, _ctx

    batches = [_batch(i) for i in range(16)]
    seq = _ctx()
    for b in batches:
        seq.train_step(b, fetch_metrics=False)

    pipe = _ctx()
    m = pipe.train_pipelined(batches, pipeline_depth=3, dispatch_k=1)
    st = pipe.pipeline_stats()
    assert st["stage_wall_s"]["feed"] > 0 and st["stage_wall_s"]["dense"] > 0
    assert st["stage_wall_s"]["psgrad"] == 0  # no d2h lane in this tier
    assert st["wall_s"] > 0
    assert len(m["losses"]) == 16
    assert not _feeder_threads()
    for i, (x, y) in enumerate(zip(_fused_leaves(seq), _fused_leaves(pipe))):
        np.testing.assert_array_equal(x, y, err_msg=f"leaf {i}")


def test_fused_pipeline_kstep_numerical_parity():
    """k > 1 packs the dense stage via build_fused_multi_step, whose
    parity with the single-step program is numerical, not bitwise (XLA
    compiles the step subgraph differently in the K context — see its
    docstring). Pin the ~1 ulp envelope so a real math divergence fails."""
    from test_fused_ctx import _batch, _ctx

    batches = [_batch(i) for i in range(16)]
    seq = _ctx()
    for b in batches:
        seq.train_step(b, fetch_metrics=False)

    pipe = _ctx()
    pipe.train_pipelined(batches, pipeline_depth=4, dispatch_k=2)
    for i, (x, y) in enumerate(zip(_fused_leaves(seq), _fused_leaves(pipe))):
        np.testing.assert_allclose(
            x, y, rtol=5e-3, atol=5e-5, err_msg=f"leaf {i}"
        )


def test_fused_pipeline_feed_error_propagates():
    """An exception inside the feed thread (mid-conversion) must surface
    from train_pipelined, not hang the dense loop."""
    from test_fused_ctx import _batch, _ctx

    def bad_stream():
        yield _batch(0)
        yield _batch(1)
        raise RuntimeError("loader died")

    pipe = _ctx()
    with pytest.raises(RuntimeError, match="loader died"):
        pipe.train_pipelined(bad_stream(), pipeline_depth=2)
