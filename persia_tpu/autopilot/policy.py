"""Autopilot decision policy: sensors in, guarded decisions out.

The policy layer is deliberately PURE — it never touches a store, a
socket, or a thread. Each ``decide_*`` method maps one sensor snapshot to
at most one :class:`Decision`, and every path that could flap is gated by
the same two-token discipline the tiering planners use (persia-lint
CTRL001 enforces it repo-wide):

- **hysteresis margin** — a change is proposed only when the modeled
  improvement clears a multiplicative band, not on any epsilon delta;
- **min-dwell** — even a clearing change waits until the incumbent has
  been stable for ``min_dwell`` rounds, so two states cannot trade places
  every round. A clearing-but-dwelling round is counted as a *suppressed
  flap* (the controller exports it — a silent guard is indistinguishable
  from a dead sensor).

PS-reshard hysteresis/dwell live inside the reused
:class:`~persia_tpu.embedding.tiering.shard_planner.ShardPlanner`; the
serving-scale and hot-replication guards are implemented here with the
same shape.
"""

from __future__ import annotations

import math
from dataclasses import asdict, dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from persia_tpu.embedding.tiering.shard_planner import ShardPlanner

# decision kinds — also the jobstate manifest / metrics label vocabulary
KIND_RESHARD = "reshard"
KIND_REPLICATE = "replicate"
KIND_SCALE = "scale"
KIND_HEAL = "heal"  # decided by autopilot.heal.HealPolicy, not PolicyEngine


@dataclass
class PolicyConfig:
    """Knobs for all three actuators. Production tuning goes through the
    launcher env (see ``--autopilot``)."""

    # --- PS resharding (ring re-split at a drained fence) ---
    skew_target: float = 1.10  # act only when measured skew exceeds this
    reshard_hysteresis: float = 0.10
    reshard_min_dwell: int = 2
    # --- hot-sign read replication ---
    hot_fanout: int = 2  # owner + (fanout-1) read replicas per hot sign
    hot_max_signs: int = 32  # journal op-index namespace holds 127
    hot_mass_frac: float = 0.01  # sign must carry >= this of total mass
    hot_min_dwell: int = 1
    # --- serving replica scaling ---
    qps_per_replica: float = 200.0
    scale_min_replicas: int = 1
    scale_max_replicas: int = 8
    scale_hysteresis: float = 0.25
    scale_min_dwell: int = 2

    def to_dict(self) -> Dict:
        return asdict(self)


@dataclass
class Decision:
    """One actuation the controller should perform. ``params`` is
    JSON-serializable verbatim — it IS the planned-manifest payload, so a
    resumed controller re-drives from exactly these numbers."""

    kind: str
    reason: str
    params: Dict = field(default_factory=dict)

    def to_meta(self) -> Dict:
        return {"kind": self.kind, "reason": self.reason,
                "params": self.params}

    @classmethod
    def from_meta(cls, meta: Dict) -> "Decision":
        return cls(meta["kind"], meta.get("reason", ""),
                   dict(meta.get("params", {})))


class PolicyEngine:
    """Stateful guard counters + the pure decision functions.

    State here is SOFT: dwell counters and the last hot set reset on a
    controller restart, which can only DELAY the next actuation by
    ``min_dwell`` rounds — it can never double-apply one. Anything whose
    replay must be exactly-once rides the decision manifest instead
    (controller.py)."""

    def __init__(self, cfg: Optional[PolicyConfig] = None):
        self.cfg = cfg or PolicyConfig()
        c = self.cfg
        self.shard_planner = ShardPlanner(
            hysteresis=c.reshard_hysteresis, min_dwell=c.reshard_min_dwell,
        )
        self.suppressed = 0  # flaps suppressed across all decision kinds
        self._scale_dwell = 0
        self._scale_target: Optional[int] = None
        self._hot_dwell = 0
        self._hot_signs: Tuple[int, ...] = ()
        self._hot_salt = 0

    # ------------------------------------------------------------- reshard

    def decide_reshard(
        self, profiler, n_shards: int, current_splits,
    ) -> Optional[Decision]:
        """Propose a ring re-split when the sketch-modeled skew of the
        CURRENT ring exceeds ``skew_target`` and the reused ShardPlanner's
        hysteresis + dwell adopt the candidate. Returns None (and counts a
        suppressed flap when the margin cleared but dwell held) otherwise."""
        from persia_tpu.embedding.hashing import splitmix64, uniform_splits

        pos, w, residual = ShardPlanner.mass_from_profiler(profiler)
        if self._hot_signs and self.cfg.hot_fanout > 1 and len(w):
            # the installed read fan-out round-robins each hot sign's
            # reads over ``fanout`` replicas — model the owner's share as
            # 1/fanout so the ring balances the POST-replication load
            # (the neighbour smear is near-uniform and cancels in skew)
            hot_pos = splitmix64(
                np.asarray(self._hot_signs, dtype=np.uint64)
            )
            m = np.isin(pos, hot_pos)
            if m.any():
                w = np.asarray(w, dtype=np.float64).copy()
                w[m] /= float(min(self.cfg.hot_fanout, max(n_shards, 1)))
        if current_splits is None:
            # modulo routing has no ring; it is hash-uniform to first
            # order, so the uniform ring is the right skew model for it
            cur = (uniform_splits(n_shards) if n_shards > 1
                   else np.empty(0, np.uint64))
        else:
            cur = np.asarray(current_splits, dtype=np.uint64)
        cur_loads = ShardPlanner.shard_loads(cur, pos, w, residual)
        cur_skew = ShardPlanner.skew_of(cur_loads)
        if cur_skew <= self.cfg.skew_target:
            # balanced enough — keep the planner's dwell clock ticking so a
            # later breach does not ALSO have to wait out a stale counter
            self.shard_planner._current = cur
            self.shard_planner._dwell += 1
            return None
        self.shard_planner._current = cur
        before = self.shard_planner.suppressed
        plan = self.shard_planner.plan(n_shards, pos=pos, w=w,
                                       residual=residual)
        self.suppressed += self.shard_planner.suppressed - before
        if not plan.adopted:
            return None
        return Decision(
            KIND_RESHARD,
            f"skew {cur_skew:.3f} > target {self.cfg.skew_target:.3f}, "
            f"candidate {plan.skew:.3f}",
            {
                "n_shards": int(n_shards),
                "splits": [int(x) for x in plan.splits],
                "skew_before": float(cur_skew),
                "skew_after": float(plan.skew),
            },
        )

    def notify_topology_changed(self) -> None:
        """A ring swap cleared the router's hot-read map (the copies were
        placed relative to the OLD owner layout): forget the installed set
        so the next ``decide_replicate`` re-fires immediately and re-copies
        onto the new owners' neighbours."""
        self._hot_signs = ()
        self._hot_dwell = 0

    # ----------------------------------------------------------- replicate

    def decide_replicate(self, profiler) -> Optional[Decision]:
        """Propose a hot-sign read-replica refresh: the signs carrying at
        least ``hot_mass_frac`` of total sketch mass, capped at
        ``hot_max_signs``. A refresh is proposed when the set CHANGES (or
        to rotate the salt over an existing set); an unchanged set within
        dwell is suppressed."""
        c = self.cfg
        if c.hot_fanout <= 1 or c.hot_max_signs <= 0:
            return None
        total = sum(float(st.total) for st in profiler.stats().values())
        if total <= 0:
            return None
        cand: List[Tuple[float, int]] = []
        for name in profiler.stats():
            for sign, est in profiler.slot_tops(name):
                if float(est) >= c.hot_mass_frac * total:
                    cand.append((float(est), int(sign)))
        cand.sort(reverse=True)
        signs = tuple(sorted({s for _, s in cand[: c.hot_max_signs]}))
        if not signs and not self._hot_signs:
            return None
        changed = signs != self._hot_signs
        if not changed:
            self._hot_dwell += 1
            return None
        if self._hot_dwell < c.hot_min_dwell and self._hot_signs:
            # hysteresis dwell: the installed set keeps serving until the
            # new one has been the candidate long enough to trust
            self.suppressed += 1
            self._hot_dwell += 1
            return None
        self._hot_dwell = 0
        self._hot_signs = signs
        self._hot_salt += 1
        return Decision(
            KIND_REPLICATE,
            f"hot set changed: {len(signs)} signs >= "
            f"{c.hot_mass_frac:.3f} of mass",
            {"signs": list(signs), "fanout": int(c.hot_fanout),
             "salt": int(self._hot_salt)},
        )

    # --------------------------------------------------------------- scale

    def decide_scale(
        self, qps: float, n_replicas: int, quarantined: int = 0,
    ) -> Optional[Decision]:
        """Propose a serving fleet size from the gateway's request rate.
        Desired = ceil(qps / qps_per_replica) clamped to
        [min, max]; quarantined replicas are lag-drained capacity, so the
        live target grows by their count (the quarantine/heal plumbing
        already knows how to fold them back in). A change must hold for
        ``scale_min_dwell`` consecutive rounds (hysteresis band
        ``scale_hysteresis`` keeps a borderline qps from oscillating the
        desired count itself)."""
        c = self.cfg
        raw = qps / c.qps_per_replica if c.qps_per_replica > 0 else 0.0
        desired = max(1, math.ceil(raw))
        # hysteresis: within the band around the current size, keep it
        if n_replicas >= 1 and desired != n_replicas:
            lo = (n_replicas - 1) * c.qps_per_replica * (1 - c.scale_hysteresis)
            hi = n_replicas * c.qps_per_replica * (1 + c.scale_hysteresis)
            if lo <= qps <= hi:
                desired = n_replicas
        desired += max(int(quarantined), 0)
        desired = min(max(desired, c.scale_min_replicas), c.scale_max_replicas)
        if desired == n_replicas:
            self._scale_target = None
            self._scale_dwell = 0
            return None
        if self._scale_target != desired:
            # new target — start its dwell clock; acting now would flap
            self._scale_target = desired
            self._scale_dwell = 1
            self.suppressed += 1
            return None
        self._scale_dwell += 1
        if self._scale_dwell <= c.scale_min_dwell:
            self.suppressed += 1
            return None
        self._scale_dwell = 0
        self._scale_target = None
        return Decision(
            KIND_SCALE,
            f"qps {qps:.1f} wants {desired} replicas (have {n_replicas}, "
            f"{quarantined} quarantined)",
            {"target": int(desired), "from": int(n_replicas),
             "qps": float(qps), "quarantined": int(quarantined)},
        )

    # --------------------------------------------------------------- state

    def export_state(self) -> Dict:
        """Soft guard state — rides the decision manifests so a resumed
        controller restarts its dwell clocks close to where they were."""
        return {
            "suppressed": int(self.suppressed),
            "scale_dwell": int(self._scale_dwell),
            "scale_target": self._scale_target,
            "hot_dwell": int(self._hot_dwell),
            "hot_signs": [int(s) for s in self._hot_signs],
            "hot_salt": int(self._hot_salt),
            "reshard_dwell": int(self.shard_planner._dwell),
            "reshard_suppressed": int(self.shard_planner.suppressed),
        }

    def load_state(self, state: Dict) -> None:
        self.suppressed = int(state.get("suppressed", 0))
        self._scale_dwell = int(state.get("scale_dwell", 0))
        st = state.get("scale_target")
        self._scale_target = None if st is None else int(st)
        self._hot_dwell = int(state.get("hot_dwell", 0))
        self._hot_signs = tuple(int(s) for s in state.get("hot_signs", ()))
        self._hot_salt = int(state.get("hot_salt", 0))
        self.shard_planner._dwell = int(state.get("reshard_dwell", 0))
        self.shard_planner.suppressed = int(
            state.get("reshard_suppressed", 0)
        )
