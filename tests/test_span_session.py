"""A profiler session is the window: while one is live, and only then, a
stage or wait span that opens and closes inside it adds to one process-wide
accumulator that ``tracing.session_totals()`` reads; with no session the span
path keeps no totals, and the ring, the bound accumulator and the stage
histogram read as they did."""

import time
from contextlib import contextmanager

import pytest

from persia_tpu import tracing
from persia_tpu.metrics import get_metrics

OPENERS = {"span": tracing.span, "stage_span": tracing.stage_span, "wait_span": tracing.wait_span}


@pytest.fixture(autouse=True)
def _ring_off():
    tracing.enable(False)
    tracing.clear()
    yield
    tracing.enable(False)
    tracing.clear()


@contextmanager
def _Session(directory):
    """A real profiler session under the harness's options
    (``perf/trace_reduce.py::WindowTracer``)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(directory), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _names(totals):
    return set(totals["stages"]) | set(totals["waits"])


@pytest.mark.parametrize("kind", sorted(OPENERS))
def test_with_no_session_a_span_leaves_the_totals_as_they_were(kind):
    import jax  # noqa: F401  (with JAX imported every span opens an annotation)

    before = tracing.session_totals()  # None in a fresh process
    with OPENERS[kind](f"session_test.off.{kind}", seq=1):
        time.sleep(0.001)
    assert tracing.session_totals() == before
    assert before is None or f"session_test.off.{kind}" not in _names(before)
    assert tracing.spans_snapshot() == [] and not tracing.enabled()  # the ring stayed off


def test_a_fresh_process_has_seen_no_session():
    import os
    import subprocess
    import sys

    code = ("import jax\nfrom persia_tpu import tracing\n"
            "with tracing.stage_span('s'):\n    pass\n"
            "with tracing.wait_span('w'):\n    pass\n"
            "assert tracing.session_totals() is None and tracing._session is None\n")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=240,
                   env=dict(os.environ, JAX_PLATFORMS="cpu"))


def test_work_span_leaves_its_nested_wait_out(tmp_path):
    with _Session(tmp_path):
        t0 = time.perf_counter()
        with tracing.span("session_test.plain"):  # a plain span counts nowhere
            with tracing.stage_span("session_test.work", seq=0):
                time.sleep(0.02)
                with tracing.wait_span("session_test.blocked"):
                    time.sleep(0.03)
        whole = time.perf_counter() - t0
        live = tracing.session_totals()  # readable while the session is live
    t = tracing.session_totals()
    assert t == live
    assert set(t["stages"]) == {"session_test.work"} and set(t["waits"]) == {"session_test.blocked"}
    work, wait = t["stages"]["session_test.work"], t["waits"]["session_test.blocked"]
    assert work["n"] == 1 and wait["n"] == 1
    assert 0.03 <= wait["wait_s"] == wait["max_s"]
    assert 0.02 <= work["busy_s"] <= whole - 0.03  # whole - wait
    assert work["busy_s"] + wait["wait_s"] == pytest.approx(t["wall_s"], abs=1e-3)
    assert t["wall_s"] <= whole


def test_a_span_that_straddles_either_end_counts_for_nothing(tmp_path):
    early = tracing.stage_span("session_test.early")
    early.__enter__()  # opened before start_trace
    with _Session(tmp_path):
        early.__exit__(None, None, None)  # closed inside
        with tracing.stage_span("session_test.inside"):
            time.sleep(0.002)
        late = tracing.wait_span("session_test.late")
        late.__enter__()  # opened inside
    late.__exit__(None, None, None)  # closed after stop_trace
    t = tracing.session_totals()
    assert _names(t) == {"session_test.inside"}
    with tracing.stage_span("session_test.after"):  # the session is over
        pass
    assert tracing.session_totals() == t  # the ended session's totals stay for the reader


def test_a_second_session_starts_from_zero(tmp_path):
    with _Session(tmp_path / "one"):
        with tracing.stage_span("session_test.first"):
            time.sleep(0.002)
    assert _names(tracing.session_totals()) == {"session_test.first"}
    with _Session(tmp_path / "two"):
        with tracing.wait_span("session_test.second"):
            time.sleep(0.002)
        with tracing.wait_span("session_test.second"):
            time.sleep(0.002)
    t = tracing.session_totals()
    assert t["stages"] == {} and set(t["waits"]) == {"session_test.second"}
    assert t["waits"]["session_test.second"]["n"] == 2


def test_counts_longest_and_wall_of_three_known_sleeps(tmp_path):
    sleeps = (0.01, 0.03, 0.02)
    with _Session(tmp_path):
        t0 = time.perf_counter()
        for i, s in enumerate(sleeps):
            with tracing.stage_span("session_test.three", seq=i):
                time.sleep(s)
            time.sleep(0.005)  # the caller's own time: no span, inside the wall
        t1 = time.perf_counter()
    t = tracing.session_totals()
    row = t["stages"]["session_test.three"]
    assert row["n"] == 3
    assert sum(sleeps) <= row["busy_s"] <= t["wall_s"] - 2 * 0.005  # the gaps are the wall's alone
    assert max(sleeps) <= row["max_s"] <= row["busy_s"] - (sum(sleeps) - max(sleeps))
    # first span's start to the last counted span's end
    assert sum(sleeps) + 2 * 0.005 <= t["wall_s"] <= t1 - t0


def _bound_and_histogram(tag, tmp_path=None):
    """The bound accumulator's tables and the stage histogram's counts of one
    fixed run of spans, with or without a session around it."""
    hist = get_metrics().histogram("persia_stage_duration_seconds", "per-stage latency")
    names = (f"session_test.same.work.{tag}", f"session_test.same.wait.{tag}")
    acc = tracing.StageAccumulator()

    def run():
        with tracing.accumulate(acc):
            for _ in range(3):
                with tracing.stage_span(names[0]):
                    with tracing.wait_span(names[1]):
                        time.sleep(0.001)

    if tmp_path is None:
        run()
    else:
        with _Session(tmp_path):
            run()
    counts = {n.rsplit(".", 1)[0]: hist.get_count(stage=n) for n in names}
    shape = {kind: {n.rsplit(".", 1)[0]: row["n"] for n, row in table.items()}
             for kind, table in (("stages", acc.stages), ("waits", acc.waits))}
    busy = acc.stages[names[0]]["busy_s"]
    return counts, shape, busy, acc.waits[names[1]]["wait_s"]


def test_bound_accumulator_and_histogram_read_the_same_with_a_session(tmp_path):
    counts0, shape0, busy0, wait0 = _bound_and_histogram("off")
    counts1, shape1, busy1, wait1 = _bound_and_histogram("on", tmp_path)
    assert counts0 == counts1 == {"session_test.same.work": 3, "session_test.same.wait": 3}
    assert shape0 == shape1
    for busy, wait in ((busy0, wait0), (busy1, wait1)):
        assert 0.003 <= wait and 0.0 <= busy < wait  # busy leaves the wait out
    t = tracing.session_totals()  # the session saw the same three and three
    assert t["stages"]["session_test.same.work.on"]["n"] == 3
    assert t["waits"]["session_test.same.wait.on"]["wait_s"] == pytest.approx(wait1)
    assert "session_test.same.work.off" not in t["stages"]


def test_threads_lose_no_span_of_one_session(tmp_path):
    """More threads than cores closing spans at once, under a short switch
    interval: the session's one accumulator counts every one, and every
    thread's bound accumulator its own."""
    import os
    import sys
    import threading

    workers, each = 2 * (os.cpu_count() or 4), 300
    bound = [tracing.StageAccumulator() for _ in range(workers)]
    go = threading.Event()

    def work(acc):
        go.wait(10)
        with tracing.accumulate(acc):
            for i in range(each):
                with tracing.stage_span("session_test.many", seq=i):
                    with tracing.wait_span("session_test.many_wait"):
                        pass

    threads = [threading.Thread(target=work, args=(acc,), daemon=True) for acc in bound]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with _Session(tmp_path):
            for t in threads:
                t.start()
            go.set()
            for t in threads:
                t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    t = tracing.session_totals()
    assert t["stages"]["session_test.many"]["n"] == workers * each
    assert t["waits"]["session_test.many_wait"]["n"] == workers * each
    for acc in bound:
        assert acc.stages["session_test.many"]["n"] == each
    assert t["stages"]["session_test.many"]["busy_s"] == pytest.approx(
        sum(acc.stages["session_test.many"]["busy_s"] for acc in bound))
