"""Share of the traced slice the driving thread spent outside ``train_step``: what ``fused.stage``, ``fused.dispatch`` and
``fused.fetch`` leave of the profiler session's ``wall_s`` (``persia_tpu.tracing.session_totals()``)."""

from perf.readers.fused_stage_ms_per_step import step_totals


def read(facts):
    t = step_totals()
    return 100.0 * (1.0 - (t["stage_s"] + t["dispatch_s"] + t["fetch_s"]) / t["wall_s"]) if t else None
