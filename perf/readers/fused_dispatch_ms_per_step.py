"""Host ms a step inside ``fused.dispatch`` over the traced slice (``persia_tpu.tracing.session_totals()``): the arguments'
transfer and the launch, and the runtime's wait where the device paces."""

from perf.readers.fused_stage_ms_per_step import step_totals


def read(facts):
    t = step_totals()
    return 1e3 * t["dispatch_s"] / t["steps"] if t else None
