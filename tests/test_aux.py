"""Aux subsystems: tracing spans, message queue, stall detector."""

import threading
import time

import pytest

from persia_tpu import tracing
from persia_tpu.diagnostics import (
    StallDetector,
    dump_all_stacks,
    heartbeat,
    inflight,
    unregister,
)
from persia_tpu.mq import MessageQueueClient, MessageQueueServer


# ------------------------------------------------------------------ tracing

@pytest.fixture(autouse=True)
def _tracing_on():
    tracing.enable(True)
    yield
    tracing.enable(False)


def test_span_records_and_exports(tmp_path):
    tracing.clear()
    with tracing.span("outer", key="v"):
        with tracing.span("inner"):
            pass
    spans = tracing.spans_snapshot()
    names = [s["name"] for s in spans]
    assert names == ["inner", "outer"]  # completion order
    # args carry the user attrs plus the span's trace identity
    assert spans[1]["args"]["key"] == "v"
    assert spans[1]["args"]["trace_id"] == spans[0]["args"]["trace_id"]
    assert spans[0]["args"]["parent_id"] == spans[1]["args"]["span_id"]
    assert spans[1]["dur"] >= spans[0]["dur"]

    p = tmp_path / "trace.json"
    n = tracing.trace_export(str(p))
    assert n == 2
    import json

    data = json.loads(p.read_text())
    assert len(data["traceEvents"]) == 2
    assert data["traceEvents"][0]["ph"] == "X"


def test_span_survives_exception():
    tracing.clear()
    with pytest.raises(RuntimeError):
        with tracing.span("boom"):
            raise RuntimeError("x")
    assert tracing.spans_snapshot()[0]["name"] == "boom"


def test_disable_enable():
    tracing.clear()
    tracing.enable(False)
    try:
        with tracing.span("hidden"):
            pass
        assert not tracing.spans_snapshot()
    finally:
        tracing.enable(True)


# ------------------------------------------------------------------- queue

@pytest.fixture()
def mq():
    srv = MessageQueueServer(capacity=4).start()
    cli = MessageQueueClient(f"127.0.0.1:{srv.port}")
    yield srv, cli
    cli.close()
    srv.stop()


def test_mq_fifo_roundtrip(mq):
    _, cli = mq
    cli.put(b"a")
    cli.put(b"b" * 100_000)
    assert cli.size() == 2
    assert cli.get(timeout_ms=1000) == b"a"
    assert cli.get(timeout_ms=1000) == b"b" * 100_000
    assert cli.size() == 0


def test_mq_get_timeout(mq):
    _, cli = mq
    t0 = time.time()
    assert cli.get(timeout_ms=200) is None
    assert 0.1 < time.time() - t0 < 5


def test_mq_blocking_get_wakes_on_put(mq):
    srv, cli = mq
    got = []
    cli2 = MessageQueueClient(f"127.0.0.1:{srv.port}")
    t = threading.Thread(target=lambda: got.append(cli2.get(timeout_ms=5000)))
    t.start()
    time.sleep(0.1)
    cli.put(b"wake")
    t.join(timeout=10)
    cli2.close()
    assert got == [b"wake"]


def test_mq_put_full_times_out():
    srv = MessageQueueServer(capacity=1).start()
    cli = MessageQueueClient(f"127.0.0.1:{srv.port}")
    try:
        cli.put(b"x")
        t0 = time.time()
        with pytest.raises(TimeoutError):
            cli.put(b"y", timeout_s=0.3)
        # client timeout is honored server-side, not rounded up to 10s
        assert time.time() - t0 < 3
    finally:
        cli.close()
        srv.stop()


# ---------------------------------------------------------------- detector

@pytest.fixture(autouse=True)
def _own_heartbeats():
    """The detector's registry is the process's: a component an earlier test
    file of this worker left beating (the gateway's health loop) must not
    read as a stall here."""
    from persia_tpu import diagnostics

    with diagnostics._lock:
        diagnostics._beats.clear()
        diagnostics._inflight.clear()
    yield


def test_stall_detector_flags_silent_component():
    det = StallDetector(stall_after_s=0.1)
    heartbeat("comp_a")
    assert det.check_once() == []
    time.sleep(0.15)
    assert det.check_once() == ["comp_a"]
    heartbeat("comp_a")
    assert det.check_once() == []
    unregister("comp_a")
    time.sleep(0.15)
    assert det.check_once() == []


def test_dump_all_stacks_contains_this_test():
    text = dump_all_stacks("unit test")
    assert "test_dump_all_stacks_contains_this_test" in text
    assert "thread dump" in text


def test_inflight_flags_long_running_op():
    det = StallDetector(stall_after_s=0.1)
    with inflight("rpc:lookup"):
        assert det.check_once() == []
        time.sleep(0.15)
        assert det.check_once() == ["inflight:rpc:lookup"]
    assert det.check_once() == []  # cleared on exit


def test_inflight_override_threshold():
    det = StallDetector(stall_after_s=0.05)
    with inflight("rpc:dump", stall_after_s=60.0):
        time.sleep(0.1)
        assert det.check_once() == []  # slow-op threshold suppresses alarm
