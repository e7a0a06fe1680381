"""Stage lanes (parallel/stage_graph.py), the one dispatch order of the
cached stream, and the one way the fused tier drives its step.

- ``test_unit_*``: lane accounting and rebuild hooks in isolation — fast,
  no XLA dispatch; these ride the preflight's step-1 subset
  (scripts/round_preflight.sh).
- the stream runs: ``pipeline_depth`` is a vestige that accepts 1 only, the
  stream carries nothing of the feed-hoisting regime PR 30 removed, and the
  rebuild hook fires once, at the migration fence, without changing a bit.
- the fused tier: ``FusedTrainCtx.train_step`` on the caller's thread, and
  no staged twin beside it.
"""

import threading

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


# ----------------------------------------- fused tier: one way to its step


def test_fused_tier_drives_its_step_one_way(monkeypatch):
    """The fused tier drives its step one way, ``train_step`` on the
    caller's thread: no staged twin on the context or in the step module, and a few
    steps start no thread."""
    from persia_tpu.parallel import fused_step
    from persia_tpu.parallel.fused_ctx import FusedTrainCtx
    from test_fused_ctx import _batch, _ctx

    for gone in ("train_pipelined", "pipeline_stats"):
        assert not hasattr(FusedTrainCtx, gone), gone
    for gone in ("FusedPipeline", "build_fused_pipeline", "build_fused_multi_step"):
        assert not hasattr(fused_step, gone), gone

    started = []
    real_start = threading.Thread.start

    def start(self):
        started.append(self.name)
        real_start(self)

    ctx = _ctx()
    monkeypatch.setattr(threading.Thread, "start", start)
    for i in range(3):
        m = ctx.train_step(_batch(i))
        assert np.isfinite(m["loss"])
    monkeypatch.undo()
    assert started == [], started
