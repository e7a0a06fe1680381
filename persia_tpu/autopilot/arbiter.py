"""Control-plane arbiter: ONE lease over every fleet topology mutation.

Four independent loops drive the control plane — the Autopilot
(``autopilot/controller.py``), the Healer (``autopilot/heal.py``), the
AutoTierController (``embedding/tiering/controller.py``) and the serving
rollover (``serving/rollover.py``) — each firing at fences or on timers
with no mutual awareness. Until this module, a HEAL landing mid-reshard or
a tier move racing a ring re-split was only not-a-disaster by schedule
luck. The arbiter closes that hole: loops submit :class:`Intent`\\ s
instead of calling actuators directly, and the single topology-actuation
lease serializes them under a fixed priority order:

=============  ========  ====================================================
intent kind    priority  meaning
=============  ========  ====================================================
heal_dead      0         promote a standby over a DEAD replica
heal_gray      1         drain a gray (slow-but-answering) replica
scrub          2         integrity scrub of a quarantined range
reshard        3         ring re-split / resize (autopilot or healer RESIZE)
tier           4         HBM<->PS placement migration at a fence
replicate      5         hot-sign read replication
rollover       5         serving model version swap
scale          5         serving replica set resize
=============  ========  ====================================================

Three mechanisms ride the lease:

- **Serialization**: ``run(intent)`` blocks until the lease is free and no
  higher-priority intent is queued, executes, releases. At most one
  topology mutation is ever in flight — ``max_concurrent`` stays 1 by
  construction.
- **Journaled preemption**: a waiting intent of strictly higher priority
  sets the holder's preemption flag when the holder declared itself
  ``preemptable``. The holder's ``execute(abort_check)`` threads that flag
  into the two-phase engine (``elastic.execute_reshard(abort_check=...)``),
  which honors it at the next phase boundary by rolling back through the
  journaled ABORT arm (exactly-once; SIGKILL mid-abort resumes
  bit-identical — see persia_tpu/elastic.py).
- **Cross-loop flap suppression**: an intent that would UNDO another
  loop's actuation inside its dwell window (same ``key``, opposite
  ``direction``, different ``source``) is suppressed, counted, and
  exported — e.g. an autopilot ring shrink right after a healer resize
  grew the fleet.

Every grant/release/preempt/suppress is a flight-recorder event
(``arbiter.*``) and a metric, so the arbitration itself is observable.
"""

from __future__ import annotations

import heapq
import inspect
import itertools
import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

from persia_tpu.logger import get_default_logger
from persia_tpu.metrics import get_metrics
from persia_tpu.tracing import record_event, span

logger = get_default_logger("persia_tpu.autopilot.arbiter")

INTENT_HEAL_DEAD = "heal_dead"
INTENT_HEAL_GRAY = "heal_gray"
INTENT_SCRUB = "scrub"
INTENT_RESHARD = "reshard"
INTENT_TIER = "tier"
INTENT_REPLICATE = "replicate"
INTENT_ROLLOVER = "rollover"
INTENT_SCALE = "scale"

PRIORITY: Dict[str, int] = {
    INTENT_HEAL_DEAD: 0,
    INTENT_HEAL_GRAY: 1,
    INTENT_SCRUB: 2,
    INTENT_RESHARD: 3,
    INTENT_TIER: 4,
    INTENT_REPLICATE: 5,
    INTENT_ROLLOVER: 5,
    INTENT_SCALE: 5,
}

# the only direction pair that means "undo": a grow right after a shrink
# (or vice versa) is a flap; a resplit/rollover carries no direction and
# is never suppressed
_OPPOSITE = {("grow", "shrink"), ("shrink", "grow")}

# HEAL intents are never flap-suppressed: a dead replica outranks any
# dwell bookkeeping
_NEVER_SUPPRESSED = 1


@dataclass
class Intent:
    """One unit of control-plane work submitted to the arbiter.

    ``execute(abort_check)`` performs the actuation; ``abort_check`` is a
    zero-arg callable returning True once a higher-priority intent has
    requested preemption — thread it into the engine's phase boundaries
    (or ignore it for non-preemptable work). ``key``/``direction`` feed
    flap suppression (e.g. ``key="ps_topology"``, ``direction="grow"``);
    ``preemptable`` declares the execute body abortable at phase
    boundaries."""

    kind: str
    source: str
    execute: Callable[[Callable[[], bool]], Any]
    key: str = ""
    direction: Optional[str] = None
    preemptable: bool = False
    label: str = ""

    @property
    def priority(self) -> int:
        return PRIORITY[self.kind]


def accepts_abort(fn: Callable) -> bool:
    """Whether an injected actuator takes ``abort_check`` — legacy test
    actuators are plain positional lambdas and must keep working, so the
    loops only thread the preemption flag into actuators that declare the
    parameter (or take ``**kwargs``)."""
    try:
        sig = inspect.signature(fn)
    except (TypeError, ValueError):
        return False
    return any(
        p.kind == inspect.Parameter.VAR_KEYWORD or p.name == "abort_check"
        for p in sig.parameters.values()
    )


@dataclass
class _Actuation:
    key: str
    direction: Optional[str]
    source: str
    ts: float


class Arbiter:
    """Holder of the single topology-actuation lease (see module doc).

    ``dwell_s`` is the flap-suppression window: an actuation's
    (key, direction, source) record stays live that long, and an intent
    from ANOTHER loop with the same key and the opposite direction inside
    the window is suppressed. ``clock`` is injectable for tests."""

    def __init__(self, *, dwell_s: float = 30.0,
                 clock: Callable[[], float] = time.monotonic):
        self.dwell_s = float(dwell_s)
        self.clock = clock
        self._cv = threading.Condition()
        self._queue: List[Tuple[int, int, Intent]] = []
        self._seq = itertools.count()
        # (priority, seq, intent, preempt_event) of the lease holder
        self._holder: Optional[Tuple[int, int, Intent, threading.Event]] = None
        self._recent: List[_Actuation] = []
        self._active = 0
        self.max_concurrent = 0
        self.grants = 0
        self.preemptions = 0
        self.preempted_rollbacks = 0
        self.suppressed_flaps = 0
        m = get_metrics()
        self._m_grants = m.counter(
            "persia_tpu_arbiter_grants", "topology-lease grants, by kind",
        )
        self._m_preempts = m.counter(
            "persia_tpu_arbiter_preemptions",
            "preemption requests issued against a lower-priority holder",
        )
        self._m_suppressed = m.counter(
            "persia_tpu_arbiter_suppressed_flaps",
            "intents suppressed for undoing another loop inside its dwell",
        )
        self._m_queue = m.gauge(
            "persia_tpu_arbiter_queue_depth", "intents waiting on the lease",
        )

    # ----------------------------------------------------------- suppression

    def _suppressor(self, intent: Intent) -> Optional[_Actuation]:
        if not intent.key or intent.direction is None:
            return None
        if intent.priority <= _NEVER_SUPPRESSED:
            return None
        now = self.clock()
        self._recent = [a for a in self._recent
                        if now - a.ts < self.dwell_s]
        for a in reversed(self._recent):
            if (a.key == intent.key and a.source != intent.source
                    and (a.direction, intent.direction) in _OPPOSITE):
                return a
        return None

    # ----------------------------------------------------------------- lease

    def run(self, intent: Intent) -> Dict:
        """Submit ``intent`` and block until it executed (or was
        suppressed). Returns the execute result coerced to a dict, or
        ``{"suppressed": True, ...}`` when flap suppression held it.
        Exceptions from ``execute`` propagate after the lease releases —
        including ``elastic.ReshardAborted`` when the intent itself was
        preempted mid-flight (the loop commits its ``aborted`` phase)."""
        with self._cv:
            sup = self._suppressor(intent)
            if sup is not None:
                self.suppressed_flaps += 1
                self._m_suppressed.inc(kind=intent.kind)
                record_event(
                    "arbiter.suppress", intent=intent.kind, source=intent.source,
                    key=intent.key, direction=intent.direction,
                    undoes_source=sup.source, undoes_direction=sup.direction,
                )
                logger.info(
                    "arbiter: suppressed %s/%s (%s %s would undo %s's %s "
                    "inside dwell)", intent.source, intent.kind, intent.key,
                    intent.direction, sup.source, sup.direction,
                )
                return {"suppressed": True, "kind": intent.kind,
                        "undoes": sup.source}
            prio, seq = intent.priority, next(self._seq)
            heapq.heappush(self._queue, (prio, seq, intent))
            self._m_queue.set(float(len(self._queue)))
            preempt_asked = False
            while not (self._holder is None and self._queue[0][1] == seq):
                h = self._holder
                if (h is not None and not preempt_asked and prio < h[0]
                        and h[2].preemptable and not h[3].is_set()):
                    h[3].set()
                    preempt_asked = True
                    self.preemptions += 1
                    self._m_preempts.inc()
                    record_event(
                        "arbiter.preempt", holder_kind=h[2].kind,
                        holder_source=h[2].source, by_kind=intent.kind,
                        by_source=intent.source,
                    )
                    logger.info(
                        "arbiter: %s/%s preempting in-flight %s/%s",
                        intent.source, intent.kind, h[2].source, h[2].kind,
                    )
                self._cv.wait(0.05)
            heapq.heappop(self._queue)
            self._m_queue.set(float(len(self._queue)))
            ev = threading.Event()
            self._holder = (prio, seq, intent, ev)
            self.grants += 1
            self._m_grants.inc(kind=intent.kind)
            self._active += 1
            self.max_concurrent = max(self.max_concurrent, self._active)
        record_event("arbiter.grant", intent=intent.kind, source=intent.source,
                     label=intent.label)
        # "aborted" = the preemption was honored and rolled back — either
        # the engine's ReshardAborted escaped, or the loop swallowed it and
        # returned its aborted-phase stats. Either way the actuation did
        # NOT land, so it must not enter the flap ledger.
        aborted = False
        try:
            with span("arbiter.actuate", kind=intent.kind,
                      source=intent.source):
                result = intent.execute(ev.is_set)
            out = dict(result or {})
            aborted = bool(out.get("aborted"))
            return out
        except BaseException as e:  # noqa: BLE001 — release, then re-raise
            aborted = type(e).__name__ == "ReshardAborted"
            raise
        finally:
            if aborted:
                self.preempted_rollbacks += 1
            with self._cv:
                self._active -= 1
                self._holder = None
                if intent.key and not aborted:
                    self._recent.append(_Actuation(
                        intent.key, intent.direction, intent.source,
                        self.clock()))
                self._cv.notify_all()
            record_event("arbiter.release", intent=intent.kind,
                         source=intent.source, preempted=aborted)

    # ------------------------------------------------------------- observers

    def export_state(self) -> Dict:
        with self._cv:
            return {
                "grants": self.grants,
                "preemptions": self.preemptions,
                "preempted_rollbacks": self.preempted_rollbacks,
                "suppressed_flaps": self.suppressed_flaps,
                "max_concurrent": self.max_concurrent,
                "active": self._active,
                "queued": len(self._queue),
            }
