"""Per-slot access-stats profiler over the native sketch.

The feeder's admit walk (``CachedEmbeddingTier.prepare_batch`` /
``_prepare_batch_single_id``) already materializes every sign of every
batch; the profiler taps that stream in place: one ``sketch_observe``
per group per step on the single-id fast path (the flattened (S, B)
matrix attributes positions to slots by stride), one per slot on the
general path. The walk is DRAM-latency-bound like the admit walk it
rides. Everything downstream — the skew/working-set stats the placement
planner scores, the snapshot/resume persistence — reads the same sketch.

Round 14: the profiler can run **sharded** (``shards=S``): one
sub-sketch per feed-directory shard, partitioned by the same
``shard_route(sign ^ part_salt)`` the directory uses. The fused feed
walk then observes each shard's signs into its own sub-sketch with no
cross-shard locking, while the unfused paths (ServiceCtx, PS slots) go
through ``sketch_observe_routed`` and land in the same sub-sketch the
fused walk would. Each sub-sketch sees ~1/S of the distinct signs, so
its count-min width and working-set bitmap scale down by S — same
per-sketch load factor (same error), same total footprint as the
unsharded profiler. Stats aggregate across the family: totals and
working-set uniques sum (the partition makes per-shard sign sets
disjoint), heavy-hitter fractions mass-weight, top-K lists merge
deterministically (estimate desc, shard asc, rank asc).
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from persia_tpu.embedding.tiering.native import (
    NativeSketch,
    observe_routed,
    shard_route,
)


def sketch_sample_k(env: Optional[str] = None) -> int:
    """Parse ``PERSIA_SKETCH_SAMPLE`` into the integer k of the 1/k
    observe sampling rate. Accepts ``1/k`` (the documented form) or a
    bare integer k; unset/invalid/<=1 means no sampling (k=1)."""
    if env is None:
        env = os.environ.get("PERSIA_SKETCH_SAMPLE", "")
    env = env.strip()
    if not env:
        return 1
    try:
        if "/" in env:
            num, den = env.split("/", 1)
            if int(num) != 1:
                return 1
            k = int(den)
        else:
            k = int(env)
    except ValueError:
        return 1
    return max(1, k)


@dataclass(frozen=True)
class SlotStats:
    """Decayed access statistics for one slot.

    ``total``     access mass (position count) under exponential decay;
    ``unique``    working-set estimate (distinct signs, two-window
                  linear counting);
    ``hot_frac``  fraction of the mass carried by the top-K signs;
    ``top1_frac`` fraction carried by the single hottest sign.

    ``reuse`` = total/unique is the planner's primary score: expected
    hits per distinct sign, i.e. how much a cached row earns its HBM.
    A slot whose working-set windows are EMPTY (no traffic for two decay
    rounds) scores 0, not total/1 — residual decayed mass with no recent
    distinct signs is a slot going cold, and inflating its reuse would
    promote exactly the slots that should drain to the PS.
    """

    total: float
    unique: float
    hot_frac: float
    top1_frac: float

    @property
    def reuse(self) -> float:
        if self.unique <= 0.0:
            return 0.0
        return self.total / max(self.unique, 1.0)


class AccessProfiler:
    """Slot-name-addressed wrapper over a :class:`NativeSketch` family.

    ``slot_order`` fixes the name -> sketch-index mapping for the life of
    the profiler (and of every exported blob): keep it stable across
    migrations — a slot keeps its index no matter which tier currently
    serves it, so its history survives the move.

    ``shards``/``part_salt`` (see module docstring) must match the feed
    directory's sharding for the fused observe to ride the admit walk;
    ``shards=None`` is the classic single-sketch profiler, bit-identical
    to every previous round. ``sample`` > 1 turns on 1/k observe sampling
    on every sub-sketch (default: ``PERSIA_SKETCH_SAMPLE``).
    """

    def __init__(
        self,
        slot_order: Sequence[str],
        width_log2: int = 16,
        depth: int = 4,
        bitmap_bits: int = 1 << 15,
        topk: int = 8,
        shards: Optional[int] = None,
        part_salt: int = 0,
        sample: Optional[int] = None,
        slot_salts: Optional[Dict[str, int]] = None,
    ):
        self.slot_order: List[str] = list(slot_order)
        if len(set(self.slot_order)) != len(self.slot_order):
            raise ValueError("duplicate slot names in slot_order")
        self._index: Dict[str, int] = {
            n: i for i, n in enumerate(self.slot_order)
        }
        self._cfg = dict(
            width_log2=width_log2, depth=depth,
            bitmap_bits=bitmap_bits, topk=topk,
        )
        self.shards = None if shards is None else max(1, int(shards))
        self.part_salt = int(part_salt) & (2**64 - 1)
        # per-slot partition salt: each cached group's sharded directory
        # partitions by ITS OWN group salt, so a cached slot's unfused
        # observes must route with that salt to land in the sub-sketch the
        # fused walk uses. Slots without an entry (PS-tier) route by
        # part_salt; any fixed salt is consistent for them because their
        # signs never cross a directory.
        self.slot_salts: Dict[str, int] = {}
        if slot_salts:
            self.set_slot_salts(slot_salts)
        self.sample = sketch_sample_k() if sample is None else max(1, int(sample))
        if self.shards is None:
            self._sks = [NativeSketch(len(self.slot_order), **self._cfg)]
        else:
            # per-sub-sketch geometry: each shard sees ~1/S of the signs,
            # so width and bitmap scale down by S — same load factor per
            # sketch (same count-min / linear-counting error) and the
            # family's total footprint matches the unsharded sketch. This
            # is also the fused walk's cache-footprint contract: a family
            # of full-width sketches would not fit the cache the walk
            # shares with the admit walk.
            lg = (self.shards - 1).bit_length()
            sub = dict(self._cfg)
            sub["width_log2"] = max(4, width_log2 - lg)
            sub["bitmap_bits"] = max(64, bitmap_bits >> lg)
            self._sks = [
                NativeSketch(len(self.slot_order), **sub)
                for _ in range(self.shards)
            ]
        if self.sample > 1:
            for sk in self._sks:
                sk.set_sample(self.sample)
        self._sk = self._sks[0]  # back-compat alias (single-sketch callers)

    @property
    def sketches(self) -> List[NativeSketch]:
        """The sub-sketch family in shard order — what the fused feed walk
        passes to ``CacheDirectory.feed_batch(sketches=...)``."""
        return self._sks

    # ---------------------------------------------------------- observe

    def observe_group(
        self, names: Sequence[str], flat_signs: np.ndarray, batch: int
    ) -> None:
        """Feed one group's flattened (S, B) sign matrix (the single-id
        fast path): position i belongs to ``names[i // batch]``. One
        native call when the group's slots are index-contiguous (they are
        by construction when the profiler is built in group order),
        otherwise one call per slot."""
        if batch <= 0 or flat_signs.size == 0:
            return
        idx = [self._index[n] for n in names]
        if idx == list(range(idx[0], idx[0] + len(idx))):
            self._observe(flat_signs, batch, idx[0])
            return
        for j, i in enumerate(idx):
            self._observe(flat_signs[j * batch:(j + 1) * batch], 0, i)

    def observe_slot(self, name: str, signs: np.ndarray) -> None:
        """Feed one slot's raw (duplicated) sign stream (general path)."""
        if signs.size:
            self._observe(signs, 0, self._index[name])

    def set_slot_salts(self, slot_salts: Dict[str, int]) -> None:
        """Update the routing salts for the named slots (e.g. after a tier
        migration regroups them). Unknown names are rejected; unnamed slots
        keep their current salt."""
        for n in slot_salts:
            if n not in self._index:
                raise KeyError(f"unknown slot {n!r} in slot_salts")
        for n, s in slot_salts.items():
            self.slot_salts[n] = int(s) & (2**64 - 1)

    def _salt_of(self, slot_idx: int) -> int:
        return self.slot_salts.get(self.slot_order[slot_idx], self.part_salt)

    def _observe(
        self, signs: np.ndarray, samples_per_slot: int, slot_base: int
    ) -> None:
        if self.shards is None:
            self._sk.observe(signs, samples_per_slot, slot_base)
        else:
            # a multi-slot (contiguous-group) observe spans ONE group, so
            # the base slot's salt covers every position in the call
            observe_routed(
                self._sks, self._salt_of(slot_base), signs,
                samples_per_slot, slot_base,
            )

    def group_contiguous_base(self, names: Sequence[str]) -> Optional[int]:
        """The base slot index when ``names`` maps to a contiguous index
        run (the precondition for fusing the observe into the sharded feed
        walk), else None."""
        idx = [self._index[n] for n in names]
        if idx == list(range(idx[0], idx[0] + len(idx))):
            return idx[0]
        return None

    # ------------------------------------------------------------ stats

    def decay(self, factor: float = 0.5) -> None:
        """Exponential decay + working-set window slide; call once per
        planning round (fence) so stats track the recent stream."""
        for sk in self._sks:
            sk.decay(factor)

    def stats(self) -> Dict[str, SlotStats]:
        out = {}
        for name, i in self._index.items():
            if self.shards is None:
                total, unique, hot, top1 = self._sk.slot_stats(i)
            else:
                # shard partition makes per-sub sign sets disjoint:
                # totals and working-set uniques SUM exactly; hot_frac
                # mass-weights (union of per-shard top-Ks); top1 is the
                # heaviest single sign across the family.
                total = unique = hot_mass = top1_mass = 0.0
                for sk in self._sks:
                    t, u, h, t1 = sk.slot_stats(i)
                    total += t
                    unique += u
                    hot_mass += t * h
                    top1_mass = max(top1_mass, t * t1)
                hot = hot_mass / total if total > 0 else 0.0
                top1 = top1_mass / total if total > 0 else 0.0
            out[name] = SlotStats(total, unique, hot, top1)
        return out

    def estimate(self, name: str, sign: int) -> float:
        i = self._index[name]
        if self.shards is None:
            return self._sk.estimate(i, sign)
        s = shard_route(sign, self._salt_of(i), self.shards)
        return self._sks[s].estimate(i, sign)

    def slot_tops(self, name: str) -> List[Tuple[int, float]]:
        """Merged heavy-hitter list for one slot: (sign, est) pairs,
        estimate desc; ties broken by shard index then per-shard rank so
        the merge is deterministic at any thread count."""
        i = self._index[name]
        cand = []
        for s, sk in enumerate(self._sks):
            signs, ests = sk.slot_tops(i)
            for k in range(sk.topk):
                if ests[k] > 0.0:
                    cand.append((-float(ests[k]), s, k, int(signs[k])))
        cand.sort()
        topk = self._cfg["topk"]
        return [(sign, -negest) for negest, _, _, sign in cand[:topk]]

    # ------------------------------------------------- snapshot / resume

    def export_bytes(self) -> bytes:
        if self.shards is not None:
            raise RuntimeError(
                "sharded profiler has one blob per sub-sketch — use "
                "export_state()")
        return self._sk.export_bytes()

    def import_bytes(self, blob: bytes) -> None:
        if self.shards is not None:
            raise RuntimeError(
                "sharded profiler has one blob per sub-sketch — use "
                "load_state()")
        self._sk.import_bytes(blob)

    def export_state(self) -> Dict:
        """JSON-safe form for a jobstate component (the blob rides as hex;
        sketches are ~1-2 MB at default geometry, and the manifest epoch
        already carries multi-MB PS shards). Sharded profilers export one
        blob per sub-sketch plus the partition key — a resumed job must
        rebuild the same family shape (pinned by the parity tests)."""
        state = {
            "slot_order": self.slot_order,
            "cfg": dict(self._cfg),
        }
        if self.shards is None:
            state["blob_hex"] = self.export_bytes().hex()
        else:
            state["shards"] = self.shards
            state["part_salt"] = self.part_salt
            state["slot_salts"] = dict(self.slot_salts)
            state["blobs_hex"] = [sk.export_bytes().hex() for sk in self._sks]
        return state

    @classmethod
    def from_state(cls, state: Dict) -> "AccessProfiler":
        prof = cls(
            state["slot_order"], **state["cfg"],
            shards=state.get("shards"),
            part_salt=state.get("part_salt", 0),
            slot_salts=state.get("slot_salts"),
        )
        prof.load_state(state)
        return prof

    def load_state(self, state: Dict) -> None:
        """Import into THIS profiler (geometry and slot order must match)."""
        if list(state["slot_order"]) != self.slot_order:
            raise ValueError(
                "profiler slot_order changed across the snapshot: "
                f"{state['slot_order']} != {self.slot_order}"
            )
        if self.shards is None:
            if "blob_hex" not in state:
                raise ValueError(
                    "sharded profiler snapshot loaded into an unsharded "
                    "profiler — pass shards= to match the snapshot"
                )
            self.import_bytes(bytes.fromhex(state["blob_hex"]))
            return
        blobs = state.get("blobs_hex")
        if blobs is None or len(blobs) != self.shards:
            raise ValueError(
                f"profiler shard count changed across the snapshot: "
                f"{len(blobs) if blobs else None} != {self.shards}"
            )
        if state.get("part_salt", 0) != self.part_salt:
            raise ValueError(
                "profiler part_salt changed across the snapshot — the "
                "sub-sketch partition would no longer match the blobs"
            )
        for sk, blob in zip(self._sks, blobs):
            sk.import_bytes(bytes.fromhex(blob))


# ----------------------------------------------------- /metrics publication


def publish_sketch_metrics(profiler: "AccessProfiler",
                           splits=None) -> Dict[str, float]:
    """Publish the access sketch's view onto the process /metrics endpoint
    (persia_tpu.metrics.serve_http) so the autopilot controller and a human
    operator read the SAME signal — until now the sketch was only readable
    in-process through ``stats()``/``slot_tops()``.

    Exports, all in the ``persia_tpu_`` namespace:

    - ``persia_tpu_ps_shard_load{shard=i}``  modeled load fraction per PS
      shard under ``splits`` (the live ring, or hash-uniform when None) —
      the ShardPlanner's own load model (heavy-hitter point masses +
      uniform residual), i.e. what the reshard decision is made FROM;
    - ``persia_tpu_ps_shard_load_skew``      max/mean of those fractions;
    - ``persia_tpu_sketch_heavy_hitter_mass{slot=...}``  fraction of the
      slot's decayed mass carried by its tracked top-K (hot_frac);
    - ``persia_tpu_sketch_working_set{slot=...}``        distinct-sign
      working-set estimate per slot.

    Returns ``{"skew": ..., "total_mass": ...}`` for the caller's own
    decision path. ``splits`` defaults to hash-uniform for the CURRENT
    modeled shard count only when given explicitly as an int via
    ``uniform_splits`` by the caller; passing None publishes a single
    whole-ring shard (n=1)."""
    from persia_tpu.embedding.tiering.shard_planner import ShardPlanner
    from persia_tpu.metrics import get_metrics

    m = get_metrics()
    g_load = m.gauge(
        "persia_tpu_ps_shard_load",
        "modeled PS shard load fraction from the access sketch",
    )
    g_skew = m.gauge(
        "persia_tpu_ps_shard_load_skew",
        "modeled PS load skew (max/mean) under the current ring",
    )
    g_hh = m.gauge(
        "persia_tpu_sketch_heavy_hitter_mass",
        "fraction of a slot's decayed access mass in its top-K heavy hitters",
    )
    g_ws = m.gauge(
        "persia_tpu_sketch_working_set",
        "distinct-sign working-set estimate per slot",
    )
    pos, w, residual = ShardPlanner.mass_from_profiler(profiler)
    ring = (np.empty(0, np.uint64) if splits is None
            else np.asarray(splits, np.uint64))
    loads = ShardPlanner.shard_loads(ring, pos, w, residual)
    for i, frac in enumerate(loads):
        g_load.set(float(frac), shard=str(i))
    skew = ShardPlanner.skew_of(loads)
    g_skew.set(skew)
    total = 0.0
    for name, st in profiler.stats().items():
        g_hh.set(float(st.hot_frac), slot=name)
        g_ws.set(float(st.unique), slot=name)
        total += float(st.total)
    return {"skew": skew, "total_mass": total}
