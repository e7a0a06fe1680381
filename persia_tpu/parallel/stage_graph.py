"""Stage lanes and their time accounting for the two pipelined drivers
(the cached tier's ``train_stream`` and the fused tier's ``FusedPipeline``).

A step runs through three lanes: ``feed`` (host conversion + h2d staging),
``dense`` (the dispatch of the step program, single or K-step packed) and
``psgrad`` (the d2h return lane: eviction write-backs and PS-tier gradient
returns). :class:`StageGraph` owns the driver's one
:class:`~persia_tpu.tracing.StageAccumulator`, opens a ``stage.<lane>``
span per lane occupancy, derives ``stage_wall_s`` / ``stage_overlap_frac``
from them, and carries the fence-point rebuild hooks of
``CachedTrainCtx.register_stage_rebuild``. It schedules nothing: both
drivers dispatch in stream order.
"""

from __future__ import annotations

import time
from contextlib import contextmanager
from typing import Callable, Dict, List

from persia_tpu.tracing import StageAccumulator, accumulate, record_event, stage_span

#: stage lanes of the hybrid step, in dataflow order
STAGES = ("feed", "dense", "psgrad")


class StageGraph:
    """Lane spans, their busy seconds (``acc``, which also takes every stage
    and wait span opened on a thread bound to it) and the rebuild hooks."""

    def __init__(self, clock=time.perf_counter):
        self.acc = StageAccumulator(clock)
        self._rebuild_hooks: List[Callable[[int], None]] = []

    def on_rebuild(self, fn: Callable[[int], None]) -> None:
        self._rebuild_hooks.append(fn)

    def rebuild(self, step: int) -> None:
        """Fence-point rebuild: fired by the cached stream at a drained
        fence (feeder parked, write-back landed), right after a tier
        migration re-registered the groups (the step programs' shapes
        changed underneath the stages). Registered hooks run here — the
        extension point for promoting a migrated group into
        ``FusedTrainCtx`` proper, per ROADMAP direction 1."""
        record_event("pipeline.rebuild", step=step)
        for fn in list(self._rebuild_hooks):
            fn(step)

    @contextmanager
    def lane(self, stage: str, **attrs):
        """Time a stage-lane occupancy: a ``stage.*`` span (tracing.
        stage_span) accounted to this graph's accumulator, which is what
        ``stage_wall_s`` and ``stage_overlap_frac`` are read from."""
        with accumulate(self.acc), stage_span(f"stage.{stage}", **attrs):
            yield

    def stats(self, wall_s: float) -> Dict:
        """``stage_wall_s``: busy seconds a lane. ``stage_overlap_frac``:
        the fraction of lane-busy time hidden under other lanes,
        ``max(0, (sum(busy) - wall) / sum(busy))`` — 0 when the lanes ran
        strictly serially, approaching 1 - 1/n_lanes at perfect overlap."""
        busy = {s: self.acc.busy_s(f"stage.{s}") for s in STAGES}
        total = sum(busy.values())
        overlap = max(0.0, (total - wall_s) / total) if total > 0.0 else 0.0
        return {
            "stage_wall_s": {k: round(v, 6) for k, v in busy.items()},
            "stage_overlap_frac": round(overlap, 6),
        }
