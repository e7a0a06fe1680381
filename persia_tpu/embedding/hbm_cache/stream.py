"""The asynchronous streaming train loop of the cached tier (feeder ->
stager -> dispatch -> write-back pipeline), split out of CachedTrainCtx
-- ``CachedTrainCtx.train_stream`` delegates here."""


from __future__ import annotations

import ctypes
import os
import threading
from dataclasses import dataclass
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence, Set, Tuple

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np

from persia_tpu.config import EmbeddingConfig
from persia_tpu.data import PersiaBatch
from persia_tpu.embedding.optim import OPTIMIZER_ADAM, OptimizerConfig
from persia_tpu.embedding.worker import (
    ProcessedBatch,
    ProcessedSlot,
    ShardedLookup,
    preprocess_batch,
)
from persia_tpu.logger import get_default_logger
from persia_tpu.utils import round_up_pow2 as _round_up_pow2
from persia_tpu.metrics import get_metrics
from persia_tpu.ops.sparse_update import sparse_update
from persia_tpu.tracing import accumulate, record_event, span, stage_span, wait_span

logger = get_default_logger("persia_tpu.hbm_cache")

# ------------------------------------------------------------------ ctypes


from persia_tpu.embedding.hbm_cache.groups import (  # noqa: F401
    CacheLayout,
    _bucket,
)
from persia_tpu.embedding.hbm_cache.directory import (  # noqa: F401
    PendingSignMap,
    _BufRing,
)

class _Staged(NamedTuple):
    """One prepared step with its inputs on the device: what the stager
    hands the dispatcher through ``staged_q``."""

    seq: int
    di: Dict
    layout: CacheLayout
    miss_aux: Dict
    cold_aux: Dict
    restore_aux: Dict
    evict_aux: Dict
    evict_meta: Dict
    ps_item: Optional[Tuple]


def run_train_stream(
    self,
    batches,
    prefetch: int = 3,
    on_metrics: Optional[Callable[[Dict], None]] = None,
    wb_flush_steps: int = 8,
    fetch_final: bool = True,
    psgrad_batch: int = 8,
    dispatch_k: int = 4,
    pipeline_depth: int = 1,
    snapshot_every: Optional[int] = None,
    job_state=None,
    start_step: int = 0,
    sentinel=None,
    skip_steps=None,
    fence_callback: Optional[Callable[[int], None]] = None,
) -> Optional[Dict]:
    """Fully-pipelined training over an iterable of ``PersiaBatch``.

    Three concurrent stages (the TPU analogue of the reference's
    latency-hiding forward/backward engines, forward.rs:640-779 /
    backward.rs:304-354):

    - a **feeder thread** runs host preprocessing, the directory admit,
      the PS checkout, and kicks off the async host→device staging for
      batch N+k while the device executes batch N;
    - the **caller's thread** only dispatches the (tiny) device programs
      in order;
    - a **write-back thread** materializes each step's eviction payload
      (the device→host transfer) and persists it to the PS.

    Correctness across threads: the directory is only touched by the
    feeder (serial admits), and the feeder's hazard gate blocks a PS
    checkout while an overlapping eviction write-back is in flight.
    Returns the final step's metrics; ``on_metrics`` (if given) receives
    every step's metrics at the cost of a per-step device sync.

    Mixed-tier configs stream too: PS-tier slots forward in the feeder
    thread and their gradients return through the write-back thread, so
    they train under BOUNDED staleness (a forward may read entries
    whose previous-step gradients are in flight, the window set by the
    prefetch depth) — the reference's async mode; cached slots stay
    fully synchronous.

    ``psgrad_batch``: PS-tier gradient returns are device→host fetches;
    on a high-latency link a serial per-step fetch caps the whole
    pipeline at 1/latency. The write-back thread therefore accumulates
    up to ``psgrad_batch`` consecutive steps' gradient outputs and
    fetches them CONCURRENTLY (parallel transfers share the latency),
    then applies them to the worker in step order — the staleness
    window grows to ``prefetch + psgrad_batch`` steps, the same
    throughput/staleness trade the reference's lookup-worker count
    sets (forward.rs:640-779).

    ``fetch_final=False`` keeps the loop COMPLETELY free of
    device→host transfers: the final header is only
    ``block_until_ready``-synced (completion without a fetch) and
    stashed device-side; ``last_metrics()`` materializes it on demand.
    A d2h fetch makes the host wait for every step dispatched so far, so
    throughput-critical loops should defer every fetch past the region
    they care about.

    ``dispatch_k``: multi-step fused dispatch. Up to ``dispatch_k``
    consecutive HAZARD-FREE staged steps (no in-flight-eviction restore,
    no PS-tier forward — exactly the windows where the hazard ledger
    shows no overlap) are packed and run as ONE jitted K-step program
    (``ctx._dispatch_packed``), cutting Python dispatch and header
    traffic by K×. A step that restores from the standing ring, carries a
    PS-tier forward, or changes shape signature flushes the pack first,
    so packing NEVER reorders a restore against the eviction write that
    produced its ring rows, and the write-back FIFO keeps step order.
    Packing adds NO staleness to cached slots (every packed step still
    sees its predecessor's updates inside the program); it only defers
    the per-step header materialization by < K steps. ``on_metrics``
    forces ``dispatch_k=1`` (it needs a header sync per step). Partial
    packs (stream tail, or a 50 ms idle wait while the feeder is parked
    on ring back-pressure) dispatch through the already-compiled
    single-step path — only exactly-K uniform windows pay a (one-time)
    K-step compile.

    ``snapshot_every`` + ``job_state``: step-fenced consistent snapshots
    (persia_tpu.jobstate). Every ``snapshot_every`` global steps the
    FEEDER pauses before preparing the next batch and a fence marker
    rides the pipeline's own FIFO: by the time the dispatcher sees it,
    every earlier step has dispatched; a drain marker then flushes the
    write-back thread (eviction landings + PS-tier gradient applies), the
    hazard ledger and eviction rings are verified empty (tails caught up
    to heads — the same accounting the in-flight gate uses), and
    ``ctx._fence_capture`` flushes the resident cache to the PS and
    commits one manifest epoch: PS shards, dense params + optimizer
    state + (now cold) cache pools, directory/ring occupancy, the loader
    cursor, and the RNG streams. ``start_step`` offsets the fence cadence
    and journal ids for a resumed stream
    (``train_stream(batches_from_F, start_step=F, ...)``).

    ``pipeline_depth``: accepted with the one value 1 (the benchmark's
    entry still passes it); the feed-hoisting regime it selected went in
    PR 30 and any other value raises ``ValueError``.

    ``fence_callback``: a hook invoked at EVERY fence with the global
    step, after the manifest commit (when ``job_state`` is armed) and the
    migration point, while the feeder is still parked and the write-back
    drained — the one window where topology may change under the stream
    (the autopilot controller's reshard/replication actuation point;
    persia_tpu/autopilot). Park → callback → resume: the drained-fence
    invariants are identical to snapshot fences, and a no-op callback is
    bit-transparent to the stream (tests/test_autopilot.py pins this).
    With ``fence_callback`` set the fence cadence runs even without
    ``job_state`` (no manifest is committed then). A callback exception is
    ISOLATED: the fence's own invariants already held before the callback
    ran, so the error is counted
    (``persia_tpu_stream_fence_callback_errors``), recorded as a
    ``stream.fence_callback_error`` flight event, and training continues —
    the callback's own journal (e.g. the autopilot's planned manifest)
    keeps its interrupted work resumable. Fence-internal failures (drain,
    ledger, manifest commit) still abort the stream.

    ``sentinel`` + ``skip_steps`` (persia_tpu/health): an armed
    :class:`~persia_tpu.health.sentinel.StreamSentinel` digests each
    step's header one dispatch behind the newest in-flight step (the
    probe tail rides the header the step already emits; disabled cost is
    one ``is None`` check) and raises ``SentinelRollback`` through the
    caller's thread for the fence auto-rollback driver
    (``health.run_guarded_stream``). ``skip_steps`` is the quarantined
    global-step set: the feeder consumes those batches WITHOUT preparing
    or training them — seq/fence cadence and journal ids stay aligned
    with the unquarantined run.
    """
    import queue as _queue
    import time as _time

    if prefetch < 1:
        raise ValueError(f"prefetch must be >= 1, got {prefetch}")
    if pipeline_depth != 1:
        raise ValueError(
            f"pipeline_depth must be 1, got {pipeline_depth}: the stream "
            "dispatches in one order since PR 30 (feed hoisting was removed)"
        )
    from persia_tpu.parallel.stage_graph import StageGraph

    graph = StageGraph()
    self._stage_graph = graph
    # the stream's one time accounting: every stage and wait span closed on
    # a thread of this stream adds to it (ctx.stream_stats()["stages"/"waits"])
    timing = graph.acc
    for _hook in self._stage_rebuild_hooks:
        graph.on_rebuild(_hook)
    job_mgr = None
    if job_state is not None:
        from persia_tpu.jobstate import coerce_manager

        job_mgr = coerce_manager(job_state)
        if self._job_epoch is None:
            self._job_epoch = 0  # journal from the first step; see jobstate
    fence_done = threading.Event()
    # Host staging buffers are FRESH per step (_BufRing hands out new
    # arrays; its docstring records the reuse-race history), so nothing
    # needs sizing against the prefetch depth here.
    self._land_pending()  # do not mix with a sync-path deferred step
    cv = threading.Condition()
    stop = threading.Event()
    staged_q: "_queue.Queue" = _queue.Queue(maxsize=prefetch)
    # bounds device-memory retention: at most ~(queue + one flush batch)
    # steps of eviction payloads (+ one psgrad batch) stay pinned in HBM
    # while the PS lags
    wb_q: "_queue.Queue" = _queue.Queue(
        maxsize=max(1, wb_flush_steps) + prefetch + max(1, psgrad_batch)
    )
    SENTINEL = object()
    errors: List[BaseException] = []

    # Standing-ring accounting. Eviction payloads land in each group's
    # DEVICE ring (ctx._ev_rings, written inside _apply_aux_ring); the
    # allocator below reserves PADDED row spans at prepare time and
    # back-pressures when the in-flight window would overrun the ring. The
    # write-back thread advances the tail after landing a span in the PS.
    # All shared state (heads/tails/alloc_q/sign_map) is guarded by `cv`.
    heads: Dict[str, int] = {}  # monotonic, unwrapped
    tails: Dict[str, int] = {}
    # per-group FIFO of reserved span sizes (skip + kp) — allocations and
    # flushes are both in seq order per group, so tail advance is a pop
    alloc_q: Dict[str, List[int]] = {}
    flush_now = threading.Event()  # feeder → wb: ring full, flush early

    def ring_alloc(gname: str, kp: int) -> int:
        W = self.ring_rows(gname)
        if kp > W:
            raise RuntimeError(
                f"one step evicts {kp} (padded) rows > the {W}-row "
                f"eviction ring of group {gname!r}; raise wb_ring_rows or "
                "lower the eviction volume (admit_touches / cache_rows)"
            )
        with cv:
            while not (stop.is_set() or errors):
                head = heads.get(gname, 0)
                tail = tails.get(gname, 0)
                # a span never wraps mid-region: skip to 0 if it would
                skip = (W - head % W) if (head % W) + kp > W else 0
                if head + skip + kp - tail <= W:
                    heads[gname] = head + skip + kp
                    alloc_q.setdefault(gname, []).append(skip + kp)
                    return (head + skip) % W
                if tail == head and head % W:
                    # ring fully drained, only the wrap waste doesn't fit
                    # the circular invariant (waste counts as allocated
                    # until a flush passes it, but there is nothing left
                    # to flush) — jump both pointers to the next ring
                    # boundary; no live span exists to overlap
                    heads[gname] = tails[gname] = -(-head // W) * W
                    continue
                # ring full: ask the write-back thread to flush early and
                # wait for the tail to advance
                flush_now.set()
                with wait_span("stream.ring_wait", group=gname):
                    cv.wait(timeout=0.5)
            return -1  # unwinding — the step never dispatches

    # sign → (token=seq, ring row) for every in-flight eviction: ONE native
    # query per gate call (native/cache.cpp pending_map_*), ONE restore
    # program per group per step (all hits gather from the standing ring,
    # regardless of how many producing steps are referenced). Keys are
    # namespaced per group (directory.group_salt): with
    # feature_index_prefix_bit=0 the same raw sign can live in two groups,
    # and an unsalted probe would restore the OTHER group's ring rows.
    sign_map = PendingSignMap()
    # a COPY, refreshed in place after a fence-point tier migration (the
    # migration replaces self.tier, and the feeder/gate closures hold this
    # dict): group names usually survive a move (cache_d{dim}) but a dim
    # appearing/disappearing changes the key set
    salts = dict(self.tier._group_salt)

    def gate(gname: str, miss_signs: np.ndarray):
        """Resolve re-missed pending-evicted signs against the in-flight
        DEVICE ring: returns at most one restore descriptor, whose payload
        is ``None`` (= the group's standing ring, resolved by the main
        thread at dispatch). Correctness is dispatch ordering: the steps
        that wrote the referenced ring rows dispatch before this one, and
        a span is only reallocated after its write-back lands (tail
        advance), which also removes its map entries."""
        with cv:
            if stop.is_set() or errors:
                return None
            hits, _tokens, srcs = sign_map.query(miss_signs, salt=salts[gname])
            if not hits:
                return None
            pos = np.nonzero(srcs >= 0)[0]
            return [(None, srcs[pos], pos)]

    prep_q: "_queue.Queue" = _queue.Queue(maxsize=prefetch)

    def _put(q, item, wait_name: str) -> bool:
        if stop.is_set() or errors:
            return False
        try:
            q.put_nowait(item)
            return True
        except _queue.Full:
            pass
        with wait_span(wait_name):  # blocked on the stage downstream
            while not (stop.is_set() or errors):
                try:
                    q.put(item, timeout=0.5)
                    return True
                except _queue.Full:
                    continue
        return False

    def _take(q, wait_name: str, timeout=None):
        """``_put``'s mirror, every stage's blocking take: the next item;
        SENTINEL once the stream unwinds (``stop``/``errors``), whatever is
        queued; None when ``timeout`` passed with nothing queued. No stage
        needs to be handed an end mark in order to stop."""
        if stop.is_set() or errors:
            return SENTINEL
        try:
            return q.get_nowait()
        except _queue.Empty:
            pass
        with wait_span(wait_name):  # blocked on the stage upstream
            while not (stop.is_set() or errors):
                try:
                    return q.get(timeout=timeout or 0.5)
                except _queue.Empty:
                    if timeout:
                        return None
        return SENTINEL

    # dispatch/feeder accounting for the bench artifact (ctx.stream_stats):
    # regressions in the hot loop must be visible from the JSON alone
    stats = {
        "dispatch_k": max(1, int(dispatch_k)) if on_metrics is None else 1,
        "packs": 0, "packed_steps": 0, "single_steps": 0,
        "feeder_busy_s": 0.0, "wall_s": 0.0,
        "stages": timing.stages, "waits": timing.waits,
        "degraded_steps": 0, "degraded_lookup_frac_max": 0.0,
        "fences": 0, "quarantine_skips": 0,
    }
    # health sentinel: headers queued at dispatch, digested one window
    # behind (sentinel.py); both hooks are no-ops when sentinel is None
    from persia_tpu.health.sentinel import sentinel_drain, sentinel_note

    sent_pending: List = []
    t_start = _time.perf_counter()
    # per-seq degraded-lookup fraction (written by the feeder BEFORE the
    # item enters prep_q, popped by the dispatcher — queue ordering is the
    # happens-before edge); the router's window counters are exclusive to
    # the feeder thread inside one stream
    deg_fracs: Dict[int, float] = {}
    _router = self.tier.router
    _deg_tracking = (
        hasattr(_router, "take_degraded_window")
        and getattr(_router, "policy", None) is not None
    )
    _m_step_deg = get_metrics().gauge(
        "persia_tpu_stream_degraded_lookup_frac",
        "per-step degraded lookup fraction of the cached stream",
    )
    def _note_degraded(seq: int) -> None:
        """Per-step degraded accounting + the configurable abort: a step
        that had to synthesize more than ``max_degraded_frac`` of its
        lookups kills the stream instead of silently training on mostly-
        degraded embeddings."""
        if not _deg_tracking:
            return
        d, t = _router.take_degraded_window()
        frac = (d / t) if t else 0.0
        deg_fracs[seq] = frac
        _m_step_deg.set(frac)
        if frac > 0.0:
            stats["degraded_steps"] += 1
            stats["degraded_lookup_frac_max"] = max(
                stats["degraded_lookup_frac_max"], frac
            )
        if frac > _router.policy.max_degraded_frac:
            raise RuntimeError(
                f"step {seq}: degraded_lookup_frac {frac:.3f} exceeds the "
                f"abort threshold {_router.policy.max_degraded_frac:.3f}"
            )

    def feeder_prep():
        """Stage 1: host preprocessing + directory admit (fused with the
        native hazard-ledger probe) + PS probe."""
        seq = 0
        try:
            source = iter(batches)
            while True:
                # the caller's iterator: the thread's time outside stream.prep
                with wait_span("stream.source_wait", seq=seq):
                    batch = next(source, SENTINEL)
                if batch is SENTINEL or stop.is_set() or errors:
                    break
                if (
                    (job_mgr is not None or fence_callback is not None)
                    and snapshot_every
                    and seq > 0 and (start_step + seq) % snapshot_every == 0
                ):
                    # snapshot fence: pause BEFORE this step's prepare — a
                    # prepare would touch the directory and the PS (admits,
                    # checkout LRU) and the capture must see exactly the
                    # post-step-(seq-1) state. The marker rides the FIFO so
                    # the dispatcher reaches it only after every earlier
                    # step dispatched; fence_done unparks us post-capture.
                    fence_done.clear()
                    if not _put(prep_q, ("fence", start_step + seq),
                                "stream.prep_put_wait"):
                        return
                    while not fence_done.wait(0.25):
                        if stop.is_set() or errors:
                            return
                if skip_steps and (start_step + seq) in skip_steps:
                    # quarantined step: consume the batch but never touch
                    # the directory/PS/device with it — seq still advances
                    # so fence cadence + journal ids match a run where the
                    # step never existed
                    record_event(
                        "health.quarantine_skip", step=start_step + seq
                    )
                    stats["quarantine_skips"] += 1
                    seq += 1
                    continue
                with stage_span("stream.prep", seq=seq):
                    item = self.tier.prepare_batch(
                        batch, hazard_gate=gate, ring_alloc=ring_alloc,
                        pending_map=sign_map,
                    )
                    with span("stream.ps_forward"):
                        ps_item = self._ps_forward(batch)
                    try:
                        _note_degraded(seq)
                    except BaseException:
                        # abort threshold tripped with a PS forward in hand:
                        # release its staleness slot before unwinding
                        if ps_item is not None:
                            self.worker.abort_gradient(ps_item[0])
                        raise
                    if ps_item is not None:
                        _ref, embs, _counts, entries = ps_item
                        di0 = item[0]
                        di0["ps_emb"] = entries
                        layout0 = CacheLayout(
                            stacked=item[1].stacked,
                            ps=tuple(eb.name for eb in embs),
                        )
                        item = (di0, layout0) + item[2:]
                    evict_meta = item[6]
                    # evicted signs become hazard-gated HERE (admit time): a
                    # later batch's probe must not trust the PS for them
                    # until the write-back lands their rows. Map srcs are the
                    # STANDING-RING rows reserved by ring_alloc above.
                    if evict_meta:
                        with cv:
                            for gn, (ev, k, ring_pos) in evict_meta.items():
                                if ring_pos < 0:  # unwinding ring_alloc
                                    continue
                                sign_map.insert_range(
                                    ev[:k], ring_pos, seq, salt=salts[gn]
                                )
                if not _put(prep_q, (seq, item, ps_item), "stream.prep_put_wait"):
                    if ps_item is not None:
                        self.worker.abort_gradient(ps_item[0])
                    return
                seq += 1
        except BaseException as e:  # noqa: BLE001 — propagate to caller
            errors.append(e)
            with cv:
                cv.notify_all()
        finally:
            _put(prep_q, SENTINEL, "stream.prep_put_wait")  # the clean end

    def feeder_dp():
        """Stage 2 — the FEED lane: async host→device staging."""
        try:
            while True:
                got = _take(prep_q, "stream.stage_get_wait")
                if got is SENTINEL:
                    break
                if isinstance(got, tuple) and got[0] == "fence":
                    # FIFO keeps fence ordering
                    if not _put(staged_q, got, "stream.stage_put_wait"):
                        return
                    continue
                seq, item, ps_item = got
                (di, layout, miss_aux, cold_aux, restore_aux, evict_aux,
                 evict_meta) = item
                with graph.lane("feed"):
                    with stage_span("stream.stage", seq=seq):
                        di, miss_aux, cold_aux, evict_aux = self._stage(
                            di, miss_aux, cold_aux, evict_aux
                        )
                    # restore index arrays must commit like every other aux
                    # input: on a mesh an uncommitted put lands on one
                    # device and _restore_rows would see incompatible
                    # devices against the replicated tables. Payloads stay
                    # untouched — None means "the group's standing eviction
                    # ring", resolved by the main thread at dispatch.
                    rep = self._replicated()
                    put = (
                        jax.device_put if rep is None
                        else (lambda a: jax.device_put(a, rep))
                    )
                    restore_aux = {
                        gn: [(p, put(src), put(dst)) for (p, src, dst) in lst]
                        for gn, lst in restore_aux.items()
                    }
                if not _put(
                    staged_q,
                    _Staged(seq, di, layout, miss_aux, cold_aux, restore_aux,
                            evict_aux, evict_meta, ps_item),
                    "stream.stage_put_wait",
                ):
                    if ps_item is not None:
                        self.worker.abort_gradient(ps_item[0])
                    return
        except BaseException as e:  # noqa: BLE001
            errors.append(e)
            with cv:
                cv.notify_all()
        finally:
            _put(staged_q, SENTINEL, "stream.stage_put_wait")  # the clean end

    # every device→host transfer pays a fixed round-trip whatever its size
    # (a small fetch measured 1.6 ms on the v5e, PR 21 chip_smoke), so the
    # write-back batches many steps' payloads and fetches them
    # CONCURRENTLY (parallel transfers share the latency), then persists
    # to the PS. The gate never needs host data (device-side restore).
    FLUSH_STEPS = max(1, wb_flush_steps)

    def _flush_acc(acc) -> None:
        if not acc:
            return
        # the d2h return lane is the stage graph's third stage: eviction
        # write-backs and PS gradient returns ride it
        with graph.lane("psgrad", steps=len(acc)):
            with stage_span("stream.wb_flush", seq=acc[0][0], steps=len(acc)):
                _flush_acc_inner(acc)

    def _release_acc(acc) -> None:
        """ONE owner for the write-back accumulator's bookkeeping — used by
        the success path after the rows land AND by every failure path
        (round-5 finding: the queue-timeout early-flush failure leaked all
        three): token-conditionally remove the steps' hazard-ledger
        entries (a later re-evict of the same sign under a newer seq
        survives an older flush), advance the ring tails so the reserved
        spans free for reallocation, clear the accumulator, and wake the
        feeder (which may be parked on ring back-pressure)."""
        with cv:
            for seq, evict_meta, _p in acc:
                for gn, (ev, k, _ring_pos) in evict_meta.items():
                    sign_map.remove(ev[:k], seq, salt=salts[gn])
                    q = alloc_q.get(gn)
                    if q:  # tail advance frees the span for reallocation
                        tails[gn] = tails.get(gn, 0) + q.pop(0)
            cv.notify_all()
        acc.clear()

    def _flush_acc_inner(acc) -> None:
        pool = self._fetch_pool()
        fetches = []  # (seq, gname, k, device payload)
        for seq, evict_meta, evict_payload in acc:
            for gn, (ev, k, _ring_pos) in evict_meta.items():
                fetches.append((seq, gn, ev, k, evict_payload[gn]))

        def fetch(f):
            return np.asarray(f[4])[:f[3]].astype(np.float32)

        with stage_span("stream.wb_fetch", n=len(fetches)):
            hosts = list(pool.map(fetch, fetches)) if pool else [fetch(f) for f in fetches]
        with stage_span("stream.wb_store", rows=sum(f[3] for f in fetches)):
            for (seq, gn, ev, k, _p), host in zip(fetches, hosts):
                g = next(gr for gr in self.tier.groups if gr.name == gn)
                self.tier._set_embedding(ev[:k], host[:k], dim=g.dim)
        _release_acc(acc)

    PS_BATCH = max(1, psgrad_batch)

    def _abort_ps_refs(items) -> None:
        """Best-effort staleness-slot release for queued psgrad items
        (shutdown paths): one place owns which tuple element holds the
        ref and the swallow-exceptions policy."""
        for it in items:
            try:
                self.worker.abort_gradient(it[1][0])
            except Exception:  # noqa: BLE001 — shutdown best-effort
                pass
        if isinstance(items, list):
            items.clear()

    def _flush_ps(ps_acc) -> None:
        if not ps_acc:
            return
        with graph.lane("psgrad", steps=len(ps_acc)):
            _flush_ps_inner(ps_acc)

    def _flush_ps_inner(ps_acc) -> None:
        """Fetch the accumulated steps' packed ps-grad outputs
        CONCURRENTLY (d2h latency is shared), then apply to the worker
        in step order. On an apply failure, not-yet-applied refs are
        aborted (the failing apply aborts its own ref itself).

        Ordering vs eviction write-backs: NONE needed — the constructor
        rejects configs where a feature group spans both tiers, so a PS
        gradient can never touch a sign an eviction wrote back; psgrad
        batches and eviction flushes proceed independently, each keeping
        its own concurrent-fetch batching."""
        pool = self._fetch_pool()

        def fetch(it):
            g = it[2]
            if isinstance(g, tuple):  # int8 wire: (q, scales)
                return tuple(np.asarray(x) for x in g)
            return np.asarray(g)

        hosts = (
            list(pool.map(fetch, ps_acc)) if pool
            else [fetch(it) for it in ps_acc]
        )
        k = 0
        try:
            for k, ((_tag, ps_item, _g, gstep), host) in enumerate(
                zip(ps_acc, hosts)
            ):
                self._apply_ps_grads(ps_item, host, journal_step=gstep)
        except BaseException:
            _abort_ps_refs(ps_acc[k + 1:])
            ps_acc.clear()
            raise
        ps_acc.clear()

    def writeback():
        acc: List = []
        ps_acc: List = []
        # the sink outlives `stop` and `errors` on purpose: it lands what was
        # dispatched and keeps wb_q moving for its one producer, the caller,
        # whose `finally` always hands it the end mark (no second taker)
        while True:
            try:
                item = wb_q.get(timeout=0.25)
            except _queue.Empty:
                # ring-full back-pressure: the feeder is parked waiting for
                # tail advance, and no new wb items can arrive until it
                # resumes — flush whatever is accumulated, however small
                if flush_now.is_set() and acc:
                    try:
                        flush_now.clear()
                        _flush_acc(acc)
                    except BaseException as e:  # noqa: BLE001
                        errors.append(e)
                        # same cleanup contract as the main-loop failure:
                        # ledger entries out, ring spans released, acc
                        # cleared — or the parked feeder deadlocks on
                        # spans nobody will ever free
                        _release_acc(acc)
                continue
            try:
                if item is SENTINEL:
                    _flush_acc(acc)
                    _flush_ps(ps_acc)
                    return
                if isinstance(item, tuple) and item[0] == "fence":
                    # drain marker: everything queued before it (FIFO) must
                    # land — eviction write-backs AND PS-tier gradient
                    # applies — before the capture reads the PS. The event
                    # is set even on failure (the error unwinds the main
                    # loop; an unset event would deadlock it instead).
                    try:
                        _flush_acc(acc)
                        _flush_ps(ps_acc)
                    finally:
                        item[1].set()
                    continue
                if isinstance(item, tuple) and item[0] == "psgrad":
                    ps_acc.append(item)
                    if len(ps_acc) >= PS_BATCH:
                        _flush_ps(ps_acc)
                    continue
                acc.append(item)
                if len(acc) >= FLUSH_STEPS or flush_now.is_set():
                    flush_now.clear()
                    _flush_acc(acc)
            except BaseException as e:  # noqa: BLE001
                errors.append(e)
                _abort_ps_refs(ps_acc)
                _release_acc(acc)
                if item is SENTINEL:
                    return

    def _bound(stage_fn):
        def run():
            with accumulate(timing):
                stage_fn()
        return run

    feeder_t = threading.Thread(target=_bound(feeder_prep), daemon=True, name="cache-feeder")
    dp_t = threading.Thread(target=_bound(feeder_dp), daemon=True, name="cache-stager")
    wb_t = threading.Thread(target=_bound(writeback), daemon=True, name="cache-writeback")
    feeder_t.start()
    dp_t.start()
    wb_t.start()
    header = None
    label_shape = None

    def _abort_drained(got) -> None:
        # a drained-but-never-applied item may carry a PS-tier forward
        # ref: release its staleness slot + stashed layout. prep_q items
        # are (seq, item, ps_item) 3-tuples
        if not (isinstance(got, tuple) and len(got) >= 3):
            return
        ps_item = got.ps_item if isinstance(got, _Staged) else got[-1]
        if (
            ps_item is not None
            and isinstance(ps_item, tuple) and len(ps_item) == 4
        ):
            try:
                self.worker.abort_gradient(ps_item[0])
            except Exception:  # noqa: BLE001 — shutdown best-effort
                pass

    K = stats["dispatch_k"]
    pack: List = []  # staged hazard-free items awaiting a K-step dispatch
    pack_sig: List = [None]

    def _run_fence(gstep: int) -> None:
        """Snapshot fence, main-thread side: every step < gstep has
        dispatched (the marker rode the FIFO); drain the write-back
        thread, verify the hazard accounting empty, capture, unpark the
        feeder."""
        ev = threading.Event()
        wb_q.put(("fence", ev))
        while not ev.wait(0.25):
            if errors:
                break
        if not errors:
            with cv:
                undrained = {
                    gn: (heads.get(gn, 0), tails.get(gn, 0))
                    for gn in set(heads) | set(tails)
                    if heads.get(gn, 0) != tails.get(gn, 0)
                }
                occupancy = {
                    "resident_rows": {
                        g.name: len(self.tier.dirs[g.name])
                        for g in self.tier.groups
                    },
                    "ring": {
                        gn: {
                            "head": heads.get(gn, 0),
                            "tail": tails.get(gn, 0),
                            "rows": self.ring_rows(gn),
                        }
                        for gn in set(heads) | set(tails)
                    },
                    "pending_ledger_entries": len(sign_map),
                }
                if self.tier.feed_shards is not None:
                    # per-shard directory occupancy + cumulative walk time:
                    # a skewed shard here means the partition salt is
                    # fighting the key distribution
                    occupancy["feeder_shards"] = self.tier.feeder_shard_stats()
            if undrained:
                errors.append(RuntimeError(
                    f"fence at step {gstep}: eviction ring spans still in "
                    f"flight after the write-back drain: {undrained}"
                ))
            else:
                try:
                    if job_mgr is not None:
                        with stage_span("stream.fence", step=gstep):
                            self._fence_capture(job_mgr, gstep, occupancy)
                    stats["fences"] = stats.get("fences", 0) + 1
                    record_event("stream.fence_commit", step=gstep)
                    n_mig = stats.get("migrations", 0)
                    _fence_migrate(gstep)
                    if stats.get("migrations", 0) != n_mig:
                        # the tier swap re-registered groups under the
                        # stage programs: fire the fence-point stage-graph
                        # rebuild hooks (write-back drained, feeder parked)
                        graph.rebuild(gstep)
                    if fence_callback is not None:
                        # topology-change window: feeder parked, write-back
                        # drained, rings verified empty, manifest (if any)
                        # committed — the callback may reshard the PS tier
                        # or swap routing before the stream resumes
                        try:
                            with span("stream.fence_callback", step=gstep):
                                fence_callback(gstep)
                        except Exception as cb_err:  # noqa: BLE001
                            # a control-plane failure must not take the
                            # training plane down with it: the fence's own
                            # invariants (drain, ledger, manifest) already
                            # held above, the callback's two-phase journal
                            # keeps ITS work resumable, and nothing here
                            # holds cv or leaves the ledger dirty — count
                            # loudly and resume the stream. BaseException
                            # (SimulatedCrash) still aborts like a kill.
                            stats["fence_callback_errors"] = (
                                stats.get("fence_callback_errors", 0) + 1
                            )
                            get_metrics().counter(
                                "persia_tpu_stream_fence_callback_errors",
                                "fence callbacks that raised (stream "
                                "continued; callback journal holds the "
                                "resume token)",
                            ).inc()
                            record_event(
                                "stream.fence_callback_error", step=gstep,
                                error=repr(cb_err),
                            )
                            logger.warning(
                                "fence callback failed at step %d (stream "
                                "continues): %s", gstep, cb_err,
                            )
                except BaseException as e:  # noqa: BLE001
                    errors.append(e)
        fence_done.set()

    def _fence_migrate(gstep: int) -> None:
        """Tier migration point: runs right after the fence's manifest
        commit, with the feeder parked and the write-back drained — the PS
        holds the only copy of every cached row, so a re-registration moves
        pure metadata. The hazard ledger (PendingSignMap) SURVIVES the
        re-registration (same native map; the ring-drain check above
        already proved heads == tails) — it must read empty here or an
        in-flight eviction would dangle across the tier swap."""
        if self._pending_migration is None and self._auto_tier is None:
            return
        with cv:
            n_pending = len(sign_map)
        if n_pending:
            raise RuntimeError(
                f"migration fence at step {gstep}: hazard ledger still "
                f"holds {n_pending} entries after the write-back drain"
            )
        if not self._maybe_migrate_at_fence(gstep):
            return
        with cv:
            # re-registration sanity: the drained ledger survived the tier
            # swap untouched
            if len(sign_map):
                raise RuntimeError(
                    "hazard ledger grew during a parked-feeder migration"
                )
            # fresh device rings were installed (ctx._ev_rings cleared):
            # restart the ring accounting so spans allocate against the
            # NEW ring heights from position 0
            heads.clear()
            tails.clear()
            alloc_q.clear()
            salts.clear()
            salts.update(self.tier._group_salt)
        stats["migrations"] = stats.get("migrations", 0) + 1

    def _post_step(seq, di, evict_meta, evict_payload):
        """Per-step bookkeeping shared by the single and packed paths."""
        nonlocal label_shape
        label_shape = di["labels"][0].shape
        self._global_step = start_step + seq + 1  # fences/journal continue here
        if evict_meta:
            # the ring rows were written device-side inside this step's
            # _apply_aux_ring; the wb thread only needs the per-step
            # payload array for its bounded d2h fetch
            wb_q.put((seq, evict_meta, evict_payload))
        if self.sparse_cfg.kind == OPTIMIZER_ADAM:
            # mirror the device's beta-power advance on the PS every
            # gradient batch (same contract as the sync train_step)
            for grp in self._cached_groups:
                self.tier.router.advance_batch_state(grp)

    def _dispatch_one(item):
        nonlocal header
        seq, di, ps_item = item.seq, item.di, item.ps_item
        try:
            with graph.lane("dense"), stage_span("stream.dispatch", seq=seq):
                if self.state is None:
                    self.init_state(jax.random.PRNGKey(0), di, item.layout)
                header, evict_payload, ps_gpacked = self._dispatch(
                    di, item.layout, item.miss_aux, item.cold_aux,
                    item.restore_aux, item.evict_aux, item.evict_meta,
                )
        except BaseException:
            # the in-hand item is already off the queue: the shutdown
            # sweep in finally can't see it, so its staleness ref must
            # be released HERE or it leaks
            if ps_item is not None:
                try:
                    self.worker.abort_gradient(ps_item[0])
                except Exception:  # noqa: BLE001 — shutdown best-effort
                    pass
            raise
        stats["single_steps"] += 1
        if ps_item is not None:
            # gradient return for PS-tier slots rides the write-back
            # thread (its d2h is off the dispatch path); FIFO order
            # keeps the worker's per-batch Adam advance in step order.
            # The global step rides along as the apply-journal step id.
            wb_q.put(("psgrad", ps_item, ps_gpacked, start_step + seq))
        _post_step(seq, di, item.evict_meta, evict_payload)
        sentinel_note(
            sentinel, sent_pending, start_step + seq, header,
            int(np.prod(di["labels"][0].shape)),
        )
        if on_metrics is not None:
            self._last_metrics = self._parse_header(
                np.asarray(header), label_shape
            )
            if _deg_tracking:
                # per-step degraded fraction rides the metrics dict (the
                # chaos suite asserts it is reported every step)
                self._last_metrics["degraded_lookup_frac"] = deg_fracs.pop(
                    seq, 0.0
                )
            on_metrics(self._last_metrics)

    def _item_sig(item):
        """Shape signature of a staged step. Packs are UNIFORM (every
        member shares one signature) so the K-step jit cache is keyed on
        a single step's shapes × K — the same cardinality as the
        single-step cache, not its K-th power."""
        di, evict_meta = item.di, item.evict_meta

        def aux_sig(d):
            return tuple(sorted(
                (k, tuple(np.shape(x) for x in (v if isinstance(v, tuple) else (v,))))
                for k, v in d.items()
            ))

        return (
            item.layout,
            tuple(sorted((k, tuple(np.shape(v))) for k, v in di["stacked_rows"].items())),
            tuple(np.shape(x) for x in di["labels"]),
            aux_sig(item.miss_aux), aux_sig(item.cold_aux), aux_sig(item.evict_aux),
            tuple(sorted((gn, evict_meta[gn][2] >= 0) for gn in evict_meta)),
        )

    def _packable(item) -> bool:
        # hazard-free: no in-flight-eviction restore, no PS-tier forward
        # (its gradient return is per-step), and the state must exist
        return (
            self.state is not None
            and not item.restore_aux
            and item.ps_item is None
        )

    def _flush_pack_single():
        """Dispatch buffered items through the single-step path (partial
        pack, signature change, or shutdown): reuses already-compiled
        programs and preserves seq order."""
        for it in pack:
            _dispatch_one(it)
        pack.clear()

    def _dispatch_pack():
        nonlocal header
        with graph.lane("dense"):
            with stage_span("stream.dispatch_pack", seq=pack[0].seq, k=len(pack)):
                headers, payloads = self._dispatch_packed(
                    [(it.di, it.layout, it.miss_aux, it.cold_aux,
                      it.evict_aux, it.evict_meta) for it in pack]
                )
        header = headers[-1]
        stats["packs"] += 1
        stats["packed_steps"] += len(pack)
        for it, payload in zip(pack, payloads):
            _post_step(it.seq, it.di, it.evict_meta, payload)
        for it, h in zip(pack, headers):
            sentinel_note(
                sentinel, sent_pending, start_step + it.seq, h,
                int(np.prod(it.di["labels"][0].shape)),
            )
        pack.clear()

    try:
        with accumulate(timing):
            while True:
                if pack:
                    # never hold a partial pack while the pipeline idles: the
                    # feeder may be parked on ring back-pressure waiting for
                    # write-backs that only exist once these steps dispatch
                    item = _take(staged_q, "stream.dispatch_get_wait", timeout=0.05)
                    if item is None:
                        _flush_pack_single()
                        continue
                else:
                    item = _take(staged_q, "stream.dispatch_get_wait")
                if errors:
                    # buffered pack items carry no PS refs (_packable) — drop
                    pack.clear()
                    _abort_drained(item)
                    break
                if item is SENTINEL:
                    _flush_pack_single()
                    sentinel_drain(sentinel, sent_pending)
                    break
                if isinstance(item, tuple) and len(item) == 2 and item[0] == "fence":
                    _flush_pack_single()
                    # the sentinel must digest every pre-fence header BEFORE
                    # the capture: a poisoned step must never become LAST_GOOD
                    sentinel_drain(sentinel, sent_pending)
                    _run_fence(item[1])
                    continue
                if K > 1 and _packable(item):
                    sig = _item_sig(item)
                    if pack and sig != pack_sig[0]:
                        _flush_pack_single()
                    if not pack:
                        pack_sig[0] = sig
                    pack.append(item)
                    if len(pack) == K:
                        _dispatch_pack()
                    continue
                _flush_pack_single()
                _dispatch_one(item)
    finally:
        stats["wall_s"] = _time.perf_counter() - t_start
        stats["feeder_busy_s"] = timing.busy_s("stream.prep")
        # per-tier layout + occupancy ride the stats dict so bench stream
        # records report EVERY tier, not just the active one's cache stats
        try:
            stats["tiers"] = {
                "cached_slots": sorted(
                    s for g in self.tier.groups for s in g.slots
                ),
                "ps_slots": sorted(self.tier.ps_slots),
                "resident_rows": {
                    g.name: len(self.tier.dirs[g.name])
                    for g in self.tier.groups
                },
                "capacity_rows": {
                    g.name: g.rows for g in self.tier.groups
                },
            }
            if self.tier.feed_shards is not None:
                stats["feeder"] = {
                    "feed_threads": self.tier.feed_threads,
                    "feed_shards": self.tier.feed_shards,
                    "shards": self.tier.feeder_shard_stats(),
                }
        except Exception:  # noqa: BLE001 — stats are best-effort at teardown
            pass
        # dense-plane sync accounting (grad_sync.dense_sync_wire_bytes):
        # the cached tier's dense half rides XLA's implicit psum, so the
        # record carries the modeled f32-allreduce cost — the honest
        # baseline the explicit block-int8 ring modes are priced against
        stats["sync_mode"] = self.sync_mode
        stats["dense_wire_bytes_per_step"] = self.dense_wire_bytes_per_step()
        stats.update(graph.stats(stats["wall_s"]))
        self._stream_stats = stats
        stop.set()
        with cv:
            cv.notify_all()

        # every stage gives up on `stop` by itself (_put/_take/ring_alloc
        # poll it); the write-back drains wb_q up to its end mark first
        wb_q.put(SENTINEL)
        threads = (feeder_t, dp_t, wb_t)
        for t in threads:
            t.join(timeout=300)
        # sweep AFTER the feeders ended: what they left queued may hold PS
        # forward refs, which would otherwise leak staleness slots
        for q in (prep_q, staged_q):
            while True:
                try:
                    _abort_drained(q.get_nowait())
                except _queue.Empty:
                    break
        alive = [t.name for t in threads if t.is_alive()]
        if alive:
            err = RuntimeError(f"cached train pipeline: {alive} outlived join(300) after stop")
            if errors:
                raise err from errors[0]
            raise err  # the caller's own failure, if one is in flight, stays its context
    if errors:
        raise RuntimeError("cached train pipeline failed") from errors[0]
    if header is not None:
        # the device runs behind the dispatcher: what is left of its queue
        # drains here
        with accumulate(timing), wait_span("stream.drain"):
            if on_metrics is not None or fetch_final:
                if on_metrics is None:
                    self._last_metrics = self._parse_header(
                        np.asarray(header), label_shape
                    )
                self._last_header_dev = None  # this stream is the freshest
            else:
                jax.block_until_ready(header)  # completion, no transfer
                self._last_header_dev = (header, label_shape)
                return None
    return self._last_metrics
