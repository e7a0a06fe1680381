"""Autopilot: a closed-loop fleet controller (ROADMAP direction 2).

Senses live telemetry the fleet already produces — the tiering access
sketch's per-shard load model, the serving gateway's request rate and
quarantine pressure — and reshapes the fleet through three actuators
behind one hysteresis/dwell-guarded policy engine:

- :mod:`policy` — pure decisions (PS ring re-split, hot-sign read
  replication, serving replica count) with flap suppression accounted;
- :mod:`replicate` — journaled exactly-once hot-sign copies + the
  ``ShardedLookup`` read fan-out swap;
- :mod:`controller` — the :class:`Autopilot` loop: fence-driven on the
  training plane (``train_stream(fence_callback=pilot.on_fence)``),
  timer-driven on the serving plane, every decision two-phase-journaled
  to jobstate so a SIGKILLed controller resumes its plan exactly-once;
- :mod:`heal` — the self-healing arc: the
  :class:`~persia_tpu.service.failure_detector.FailureDetector`'s
  lease/probe verdicts drive autonomous standby promotion for dead PS
  shards, gray-replica drains, and fleet grow/shrink, under the same
  two-phase journal (a SIGKILLed healer resumes its heal exactly-once).
"""

from persia_tpu.autopilot.controller import (  # noqa: F401
    AUTOPILOT_ENV,
    Autopilot,
    autopilot_enabled,
    enable_autopilot,
    gateway_sensors,
)
from persia_tpu.autopilot.heal import (  # noqa: F401
    ACTION_DRAIN_GRAY,
    ACTION_PROMOTE,
    ACTION_RESIZE,
    HealConfig,
    Healer,
    HealPolicy,
    enable_self_heal,
)
from persia_tpu.autopilot.policy import (  # noqa: F401
    KIND_HEAL,
    KIND_REPLICATE,
    KIND_RESHARD,
    KIND_SCALE,
    Decision,
    PolicyConfig,
    PolicyEngine,
)
from persia_tpu.autopilot.replicate import (  # noqa: F401
    MAX_REPLICATED_SIGNS,
    replicate_hot_signs,
)
