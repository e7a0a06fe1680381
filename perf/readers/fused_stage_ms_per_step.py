"""Host ms a step inside ``fused.stage`` over the traced slice: busy of ``fused.stage`` over ``n`` of ``fused.dispatch``, of the
totals the program's spans added up while the run's profiler session was live (``persia_tpu.tracing.session_totals()``)."""

import sys


def step_totals():
    """``FusedTrainCtx.train_step``'s spans over the last profiler session: seconds inside ``fused.stage``, ``fused.dispatch`` and
    ``fused.fetch``, the steps dispatched and the session's ``wall_s``. None where the process saw no session (an untraced run, a
    rehearsal), where the program keeps no such totals (a parent commit's) or where no step was dispatched inside the session."""
    totals = getattr(sys.modules.get("persia_tpu.tracing"), "session_totals", None)
    t = totals() if totals else None
    if not t:
        return None
    stages, waits = t.get("stages") or {}, t.get("waits") or {}
    steps = stages.get("fused.dispatch", {}).get("n", 0)
    if not steps or not t.get("wall_s"):
        return None
    return {"stage_s": stages.get("fused.stage", {}).get("busy_s", 0.0),
            "dispatch_s": stages["fused.dispatch"]["busy_s"],
            "fetch_s": waits.get("fused.fetch", {}).get("wait_s", 0.0),
            "steps": steps, "wall_s": t["wall_s"]}


def read(facts):
    t = step_totals()
    return 1e3 * t["stage_s"] / t["steps"] if t else None
