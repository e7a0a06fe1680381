"""Explicit MPMD stage graph for the pipelined hybrid step.

The hybrid step decomposes into three device-program stages — ``feed``
(embedding lookup/feed: the fused aux scatters that admit missed rows and
read eviction payloads), ``dense`` (model fwd/bwd + dense/sparse updates;
a packed K-step window is ONE dense stage), and ``psgrad`` (the gradient
return + eviction write-back d2h lane). The source paper's core win is
bounded-staleness *overlap* between the sparse plane and the dense tower;
this module expresses that overlap as MPMD pipeline stages in the dispatch
layer (PAPERS.md: "Scaling Deep Learning Training with MPMD Pipeline
Parallelism", arxiv 2412.14374) instead of host threads alone: batch
N+k's feed dispatches from the stream's stager thread and rides under
batch N's dense compute, with the pipeline depth as the staleness knob.

Bit-parity contract (the reason the overlap is SOUND, not just fast):
feed(t)'s program touches exactly the cache rows newly assigned at
prepare(t) (evict-payload reads + warm/cold scatter targets); dense(j)'s
program touches exactly the rows step j trains (gathers + gradient
scatters). Scatter/gather chains over DISJOINT rows of the same pool
commute bitwise — each row's final value depends only on the ops that
touch that row — so hoisting feed(t) above dense(j < t) changes no bit
as long as the row sets are disjoint. :func:`feed_hazard_info` computes
both sets host-side at prepare time; :meth:`StageGraph.reserve_feed`
stalls the feed (``pipeline.stall`` flight event +
``persia_tpu_pipeline_stalls``) until the conflicting dense stages
retire. Everything the hazard ledger already forbids (in-flight-eviction
restores, PS-tier forwards) enters the window as a *barrier* entry that
no later feed may hoist across.

Fences drain the window (``pipeline.drain`` + the drains counter): the
feeder parks first, so by the time the dispatcher reaches the fence
marker every feed AND dense has dispatched and
:meth:`StageGraph.drain_for_fence` merely asserts the invariant — jobstate
bit-parity needs no new machinery. :meth:`StageGraph.rebuild` is the
fence-point hook that fires after a tier migration re-registers groups:
the clean place for the tiering follow-on of promoting a migrated group
into ``FusedTrainCtx`` proper (a step-graph rebuild at the fence).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from persia_tpu.metrics import get_metrics
from persia_tpu.tracing import StageAccumulator, accumulate, record_event, stage_span

#: stage lanes of the hybrid step, in dataflow order
STAGES = ("feed", "dense", "psgrad")


def _rows_intersect(sorted_rows: np.ndarray, probe: np.ndarray) -> bool:
    """True when any value of ``probe`` occurs in ``sorted_rows``."""
    if sorted_rows.size == 0 or probe.size == 0:
        return False
    idx = np.searchsorted(sorted_rows, probe)
    np.minimum(idx, sorted_rows.size - 1, out=idx)
    return bool(np.any(sorted_rows[idx] == probe))


def feed_hazard_info(
    device_inputs: Dict,
    miss_aux: Dict,
    cold_aux: Dict,
    evict_aux: Dict,
    slot_group: Dict[str, str],
) -> Tuple[Dict[str, np.ndarray], Dict[str, np.ndarray]]:
    """Host-side hazard sets of one prepared step, computed BEFORE the h2d
    staging turns the arrays into device buffers.

    Returns ``(feed_rows, trained_rows)`` keyed by group name: the cache
    rows the step's FEED stage writes/reads (evict-payload reads + warm
    miss scatters + cold scatters) and the *sorted* rows its DENSE stage
    gathers and gradient-scatters (stacked + raw lookup rows; the pad row
    rides along harmlessly — a feed never targets it). Disjointness of a
    later step's ``feed_rows`` against every in-flight step's
    ``trained_rows`` is the bit-parity license for hoisting the feed
    (module docstring); ``slot_group`` maps raw-slot names to their group.
    """
    feed: Dict[str, np.ndarray] = {}
    for gname in set(miss_aux) | set(cold_aux) | set(evict_aux):
        parts: List[np.ndarray] = []
        ev = evict_aux.get(gname)
        if ev is not None and np.size(ev):
            parts.append(np.asarray(ev, dtype=np.int64).ravel())
        m = miss_aux.get(gname)
        if m is not None and np.size(m[0]):
            parts.append(np.asarray(m[0], dtype=np.int64).ravel())
        c = cold_aux.get(gname)
        if c is not None and np.size(c[0]):
            parts.append(np.asarray(c[0], dtype=np.int64).ravel())
        if parts:
            feed[gname] = np.concatenate(parts)
    by_group: Dict[str, List[np.ndarray]] = {}
    for gname, rows in device_inputs["stacked_rows"].items():
        by_group.setdefault(gname, []).append(
            np.asarray(rows, dtype=np.int64).ravel()
        )
    for slot, rows in device_inputs.get("raw_rows", {}).items():
        by_group.setdefault(slot_group[slot], []).append(
            np.asarray(rows, dtype=np.int64).ravel()
        )
    trained = {
        gname: np.sort(np.concatenate(parts) if len(parts) > 1 else parts[0])
        for gname, parts in by_group.items()
    }
    return feed, trained


class StageGraph:
    """In-flight window + hazard accounting of the pipelined stream.

    The window holds one entry per step whose FEED stage has dispatched
    (or, for barrier steps, been forwarded) but whose DENSE stage has not;
    its length is bounded by ``depth``, which is therefore the staleness
    knob — a feed dispatches at most ``depth - 1`` steps ahead of its own
    dense stage, and ``depth == 1`` degenerates to the fully in-order
    pipeline. The stager thread appends via :meth:`reserve_feed` /
    barrier entries; the dispatch thread pops via :meth:`note_dense` after
    each dense dispatch. Lanes (:meth:`lane`) are ``stage.*`` spans; their
    busy seconds, and those of every stage span opened inside one, are kept
    by ``acc``, which the ``stage_overlap_frac`` stat the bench artifact
    records is derived from.
    """

    def __init__(self, depth: int, clock=time.perf_counter):
        self.depth = max(1, int(depth))
        self.acc = StageAccumulator(clock)
        # guards the window and the abort flag; a
        # leaf-ish condition — nothing ranked is ever taken under it
        # (analysis/lock_order.py rank 1)
        self._pipe_cv = threading.Condition()
        self._window: "deque[Tuple[int, Optional[Dict[str, np.ndarray]]]]" = deque()
        self._aborted = False
        self.stalls = 0
        self.drains = 0
        self._rebuild_hooks: List[Callable[[int], None]] = []
        m = get_metrics()
        self._m_stalls = m.counter(
            "persia_tpu_pipeline_stalls",
            "feed stages stalled on a row hazard against an in-flight dense stage",
        )
        self._m_drains = m.counter(
            "persia_tpu_pipeline_drains",
            "pipeline windows drained at a fence or stream end",
        )
        m.gauge(
            "persia_tpu_pipeline_depth",
            "stage-pipeline depth of the most recent stream",
        ).set(self.depth)

    # ----------------------------------------------------------- window

    def reserve_feed(
        self,
        seq: int,
        feed_rows: Optional[Dict[str, np.ndarray]],
        trained_rows: Optional[Dict[str, np.ndarray]],
        should_abort: Optional[Callable[[], bool]] = None,
        barrier: bool = False,
    ) -> bool:
        """Block until step ``seq`` may enter the in-flight window, then
        append it. Feed entries (``barrier=False``) additionally wait
        until ``feed_rows`` is disjoint from every in-flight entry's
        trained rows; barrier entries (restore / PS-forward / pre-init
        steps, which dispatch through the full in-order path) only wait
        for window capacity and then conflict with EVERY later feed, so
        nothing hoists across them. Returns False when aborted — the
        caller unwinds without dispatching."""
        stalled = False
        with self._pipe_cv:
            while True:
                if self._aborted or (should_abort is not None and should_abort()):
                    return False
                if len(self._window) < self.depth:
                    conflict = None if barrier else self._conflict(feed_rows)
                    if conflict is None:
                        self._window.append(
                            (seq, None if barrier else trained_rows)
                        )
                        return True
                    if not stalled:
                        # counted once per stalled feed, not per retry
                        stalled = True
                        self.stalls += 1
                        self._m_stalls.inc()
                        record_event("pipeline.stall", step=seq, group=conflict)
                self._pipe_cv.wait(timeout=0.05)

    def _conflict(self, feed_rows) -> Optional[str]:
        for _seq, trained in self._window:
            if trained is None:
                return "barrier"
            if not feed_rows:
                continue
            for gname, probe in feed_rows.items():
                srt = trained.get(gname)
                if srt is not None and _rows_intersect(srt, probe):
                    return gname
        return None

    def note_dense(self, seq: int) -> None:
        """Retire every window entry up to and including ``seq`` — its
        dense stage (single or packed) has dispatched."""
        with self._pipe_cv:
            while self._window and self._window[0][0] <= seq:
                self._window.popleft()
            self._pipe_cv.notify_all()

    def abort(self) -> None:
        with self._pipe_cv:
            self._aborted = True
            self._pipe_cv.notify_all()

    # ----------------------------------------------------- fences/rebuild

    def drain_for_fence(self, step: int, reason: str = "fence") -> None:
        """Assert the window empty (feeder parked + FIFO ordering make it
        so by the time the dispatcher reaches a fence marker) and record
        the drain. Raises when a feed is still in flight — that would
        break the fence's jobstate bit-parity."""
        with self._pipe_cv:
            n = len(self._window)
        if n:
            raise RuntimeError(
                f"pipeline drain at step {step}: {n} feed stage(s) still "
                "in flight ahead of their dense stages"
            )
        self.drains += 1
        self._m_drains.inc()
        record_event("pipeline.drain", step=step, reason=reason)

    def on_rebuild(self, fn: Callable[[int], None]) -> None:
        self._rebuild_hooks.append(fn)

    def rebuild(self, step: int) -> None:
        """Fence-point stage-graph rebuild: fired with the window drained
        and the feeder parked, right after a tier migration re-registered
        the groups (the step programs' shapes changed underneath the
        stages). Registered hooks run here — the extension point for
        promoting a migrated group into ``FusedTrainCtx`` proper, per
        ROADMAP direction 1."""
        record_event("pipeline.rebuild", step=step)
        for fn in list(self._rebuild_hooks):
            fn(step)

    # ------------------------------------------------------------- lanes

    @contextmanager
    def lane(self, stage: str, **attrs):
        """Time a stage-lane occupancy: a ``stage.*`` span (tracing.
        stage_span) accounted to this graph's accumulator, which is what
        ``stage_wall_s`` and ``stage_overlap_frac`` are read from."""
        with accumulate(self.acc), stage_span(f"stage.{stage}", **attrs):
            yield

    def stats(self, wall_s: float) -> Dict:
        """Pipeline stats for the stream's stats dict / bench record.
        ``stage_overlap_frac`` is the fraction of lane-busy time hidden
        under other lanes: ``max(0, (sum(busy) - wall) / sum(busy))`` —
        0 when the lanes ran strictly serially, approaching 1 - 1/n_lanes
        at perfect overlap."""
        busy = {s: self.acc.busy_s(f"stage.{s}") for s in STAGES}
        total = sum(busy.values())
        overlap = max(0.0, (total - wall_s) / total) if total > 0.0 else 0.0
        return {
            "pipeline_depth": self.depth,
            "pipeline_stalls": self.stalls,
            "pipeline_drains": self.drains,
            "stage_wall_s": {k: round(v, 6) for k, v in busy.items()},
            "stage_overlap_frac": round(overlap, 6),
        }
