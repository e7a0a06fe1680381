"""Fleet telemetry plane: trace-context propagation on every wire, the
span ring + Perfetto export, the flight recorder, the per-role
``/spans``/``/flight`` endpoints, and the merged fleet timeline.

The acceptance pins live here:

- one client request's ``trace_id`` is visible across the gateway span,
  the replica's ``serving.request``/``serving.batch_forward`` spans, and
  the engine forward span (``test_gateway_request_trace_spans_all_hops``);
- the flight recorder correlates an injected delta-channel fault with the
  quarantine/heal events it caused
  (``test_flight_recorder_correlates_chaos_with_quarantine``);
- a ``LocalTopology`` run with ``trace_dir`` merges every role's ring into
  ONE Perfetto timeline (``test_local_topology_merged_trace``).
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time
import types
import urllib.request

import numpy as np
import pytest

from persia_tpu import tracing
from persia_tpu.data import (
    IDTypeFeatureWithSingleID,
    NonIDTypeFeature,
    PersiaBatch,
)
from persia_tpu.metrics import MetricsRegistry

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_tracing():
    tracing.enable(False)
    tracing.clear()
    tracing.flight_clear()
    yield
    tracing.enable(False)
    tracing.clear()
    tracing.flight_clear()


def _spans_by_name():
    out = {}
    for ev in tracing.spans_snapshot():
        out.setdefault(ev["name"], []).append(ev)
    return out


def _req_batch(rows: int) -> PersiaBatch:
    return PersiaBatch(
        [IDTypeFeatureWithSingleID(
            "s", (np.arange(rows) % 16).astype(np.uint64))],
        non_id_type_features=[NonIDTypeFeature(
            np.zeros((rows, 2), dtype=np.float32))],
        requires_grad=False,
    )


def _wait(pred, timeout_s=30.0, every=0.05, what="condition"):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(every)
    raise AssertionError(f"timed out waiting for {what}")


# ------------------------------------------------------------ span mechanics


def test_span_nesting_and_parent_links():
    tracing.enable(True)
    with tracing.span("outer", k=1):
        with tracing.span("inner"):
            pass
    by = _spans_by_name()
    outer, inner = by["outer"][0], by["inner"][0]
    assert outer["args"]["trace_id"] == inner["args"]["trace_id"]
    assert inner["args"]["parent_id"] == outer["args"]["span_id"]
    assert "parent_id" not in outer["args"]  # outer IS the edge
    assert outer["ph"] == "X" and outer["dur"] >= 0
    assert outer["args"]["k"] == "1"


def test_trace_context_adoption_and_wire_headers():
    tracing.enable(True)
    assert tracing.wire_headers() == {}  # no ambient context
    with tracing.trace_context("ab" * 16, "cd" * 8) as frame:
        assert frame == ("ab" * 16, "cd" * 8)
        h = tracing.wire_headers()
        assert h == {"X-Trace-Id": "ab" * 16, "X-Parent-Span": "cd" * 8}
        with tracing.span("adopted"):
            pass
    ev = _spans_by_name()["adopted"][0]
    assert ev["args"]["trace_id"] == "ab" * 16
    assert ev["args"]["parent_id"] == "cd" * 8


def test_span_ring_is_bounded():
    tracing.enable(True)
    cap = tracing._MAX_SPANS
    for i in range(cap + 50):
        with tracing.span("s"):
            pass
    assert len(tracing.spans_snapshot()) == cap


def test_spans_drain_empties_ring():
    tracing.enable(True)
    with tracing.span("once"):
        pass
    drained = tracing.spans_drain()
    assert [e["name"] for e in drained] == ["once"]
    assert tracing.spans_snapshot() == []


def test_disabled_tracer_records_nothing_and_stays_cheap():
    assert not tracing.enabled()
    n = 50_000
    t0 = time.perf_counter()
    for _ in range(n):
        with tracing.span("noop"):
            pass
    per_call_us = (time.perf_counter() - t0) / n * 1e6
    assert tracing.spans_snapshot() == []
    # a disabled span must stay a no-op: generous bound, catches an
    # accidental id-generation or dict-build on the disabled path
    assert per_call_us < 25.0, f"disabled span costs {per_call_us:.1f}us"


def test_stage_span_feeds_histogram_even_when_disabled():
    from persia_tpu.metrics import get_metrics

    assert not tracing.enabled()
    with tracing.stage_span("telemetry_test_stage"):
        pass
    assert tracing.spans_snapshot() == []  # no span while disabled...
    counts = get_metrics().snapshot().get(
        "persia_stage_duration_seconds_count", {})
    assert any("telemetry_test_stage" in lbl for lbl in counts), \
        "stage histogram did not observe the disabled-mode stage"


def test_export_round_trip_is_atomic(tmp_path):
    tracing.enable(True)
    with tracing.span("exported", tag="v"):
        pass
    path = str(tmp_path / "role.trace.json")
    n = tracing.trace_export(path)
    assert n == 1
    doc = json.loads(open(path).read())
    names = [e["name"] for e in doc["traceEvents"]]
    assert names == ["exported"]
    assert doc["metadata"]["pid"] == os.getpid()
    assert not [f for f in os.listdir(tmp_path) if ".tmp" in f]


# ---------------------------------------------------------- flight recorder


def test_flight_recorder_records_and_dumps(tmp_path):
    with tracing.trace_context("ee" * 16):
        evt = tracing.record_event("breaker.trip", endpoint="x:1", cause="t")
    assert evt["trace_id"] == "ee" * 16  # stamped even with tracing OFF
    tracing.record_event("resync", replica="0")
    events = tracing.flight_snapshot()
    assert [e["kind"] for e in events] == ["breaker.trip", "resync"]
    assert [e["seq"] for e in events] == [0, 1]
    assert events[0]["attrs"] == {"endpoint": "x:1", "cause": "t"}
    path = str(tmp_path / "flight.json")
    assert tracing.flight_dump(path) == path
    doc = json.loads(open(path).read())
    assert [e["kind"] for e in doc["events"]] == ["breaker.trip", "resync"]
    tracing.flight_clear()
    assert tracing.flight_snapshot() == []


_CHILD_PRELUDE = """
import os, sys
from persia_tpu import tracing
tracing.install_flight_recorder(sys.argv[1])
tracing.record_event("boot", pid=os.getpid())
"""


def _run_child(body: str, dump: str, expect_rc_zero: bool = False):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-c", _CHILD_PRELUDE + body, dump],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True, timeout=120,
    )
    if expect_rc_zero:
        assert p.returncode == 0, p.stderr
    return p


def test_flight_recorder_dumps_on_sigterm(tmp_path):
    dump = str(tmp_path / "f.json")
    p = _run_child(
        "import signal\n"
        "os.kill(os.getpid(), signal.SIGTERM)\n",
        dump,
    )
    assert p.returncode != 0
    kinds = [e["kind"] for e in json.loads(open(dump).read())["events"]]
    assert kinds == ["boot", "sigterm"]


def test_flight_recorder_dumps_on_fatal_exception(tmp_path):
    dump = str(tmp_path / "f.json")
    p = _run_child("raise RuntimeError('boom')\n", dump)
    assert p.returncode != 0 and "boom" in p.stderr
    events = json.loads(open(dump).read())["events"]
    fatal = [e for e in events if e["kind"] == "fatal"]
    assert fatal and "boom" in fatal[0]["attrs"]["exc"]


def test_flight_recorder_dumps_at_exit_with_armed_export(tmp_path):
    dump = str(tmp_path / "f.json")
    trace = str(tmp_path / "t.json")
    _run_child(
        f"tracing.arm_trace_export({trace!r})\n"
        "tracing.enable(True)\n"
        "with tracing.span('child.work'):\n"
        "    pass\n",
        dump, expect_rc_zero=True,
    )
    assert [e["kind"] for e in json.loads(open(dump).read())["events"]] \
        == ["boot"]
    names = [e["name"]
             for e in json.loads(open(trace).read())["traceEvents"]]
    assert names == ["child.work"]


# -------------------------------------------------------- per-role endpoints


def test_metrics_endpoints_serve_spans_and_flight(tmp_path):
    tracing.enable(True)
    with tracing.span("served"):
        pass
    tracing.record_event("served.event")
    reg = MetricsRegistry(job="t")
    reg.counter("persia_tpu_test_scraped").inc()
    port = reg.serve_http(0)
    try:
        # loopback binding is the default (OBS hardening): the socket must
        # not listen on every interface
        assert reg._server.server_address[0] == "127.0.0.1"

        def get(path):
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=5
            ) as r:
                return json.loads(r.read())

        doc = get("/spans")
        assert doc["pid"] == os.getpid() and doc["now_us"] > 0
        assert [s["name"] for s in doc["spans"]] == ["served"]
        fl = get("/flight")
        assert [e["kind"] for e in fl["events"]] == ["served.event"]
        # drain semantics: the collector never double-counts
        assert [s["name"] for s in get("/spans?drain=1")["spans"]] \
            == ["served"]
        assert get("/spans")["spans"] == []
    finally:
        reg.shutdown()


# -------------------------------------------------- cross-process: RPC wire


def test_rpc_trace_context_crosses_the_wire():
    from persia_tpu.service.rpc import RpcClient, RpcServer

    tracing.enable(True)
    srv = RpcServer(port=0)
    srv.register("echo", lambda p: p)
    srv.start()
    try:
        cli = RpcClient(f"127.0.0.1:{srv.port}")
        with tracing.trace_context() as frame:
            assert cli.call("echo", b"hi") == b"hi"
        by = _spans_by_name()
        client_span = by["rpc.client.echo"][0]
        server_span = by["rpc.server.echo"][0]
        # one id across the wire: the frame's trace_id reaches the server
        assert client_span["args"]["trace_id"] == frame[0]
        assert server_span["args"]["trace_id"] == frame[0]
        # and the server's span is a CHILD of the client's call span
        assert server_span["args"]["parent_id"] \
            == client_span["args"]["span_id"]
    finally:
        srv.stop()


# --------------------------------------------- gateway HTTP path (acceptance)


class _ServeCtx:
    """Minimal InferCtx stand-in (same shape test_serving_chaos uses)."""

    def __init__(self, value=1.0, store=None):
        self.model = None
        self.state = None
        self.value = value
        self.worker = types.SimpleNamespace(
            lookup_router=types.SimpleNamespace(
                replicas=[store] if store is not None else [])
        )

    def predict(self, batch):
        return np.full((batch.batch_size,), self.value, dtype=np.float32)


def test_gateway_request_trace_spans_all_hops():
    """ACCEPTANCE PIN: one client request's trace_id is visible across the
    gateway span, the replica's request + batch spans, and the engine
    forward span — the full serving wire."""
    from persia_tpu.serving import ReplicaGateway, ServingServer

    tracing.enable(True)
    srv = ServingServer(_ServeCtx(), port=0, cache_rows=0,
                        max_wait_ms=0.5).start()
    gw = ReplicaGateway(replicas=[f"127.0.0.1:{srv.port}"],
                        health_interval_s=0.1).start()
    try:
        scores, info = gw.predict_bytes_ex(_req_batch(3).to_bytes())
        assert scores.shape == (3,)
        tid = info["trace_id"]
        assert tid
        by = _spans_by_name()
        for hop in ("gateway.predict", "gateway.attempt", "serving.request",
                    "serving.batch_forward", "serving.engine_forward"):
            hits = [e for e in by.get(hop, ())
                    if e["args"]["trace_id"] == tid]
            assert hits, f"hop {hop} missing from trace {tid}: " \
                         f"{sorted(by)}"
        # per-hop attribution: the replica reported its server-side time
        # and the gateway recorded queue/server/wire splits
        from persia_tpu.metrics import get_metrics

        snap = get_metrics().snapshot()
        for series in ("persia_tpu_gateway_queue_wait_seconds",
                       "persia_tpu_gateway_replica_server_seconds",
                       "persia_tpu_gateway_wire_seconds",
                       "persia_tpu_serving_queue_wait_seconds"):
            assert snap.get(f"{series}_count"), series
    finally:
        gw.stop()
        srv.stop()


def test_gateway_edge_generates_and_propagates_fresh_id():
    """Two requests get two distinct trace ids; a caller-provided ambient
    context is adopted instead of replaced."""
    from persia_tpu.serving import ReplicaGateway, ServingServer

    tracing.enable(True)
    srv = ServingServer(_ServeCtx(), port=0, cache_rows=0,
                        max_wait_ms=0.5).start()
    gw = ReplicaGateway(replicas=[f"127.0.0.1:{srv.port}"],
                        health_interval_s=0.1).start()
    try:
        _, a = gw.predict_bytes_ex(_req_batch(1).to_bytes())
        _, b = gw.predict_bytes_ex(_req_batch(1).to_bytes())
        assert a["trace_id"] != b["trace_id"]
        with tracing.trace_context("fe" * 16):
            _, c = gw.predict_bytes_ex(_req_batch(1).to_bytes())
        assert c["trace_id"] == "fe" * 16
    finally:
        gw.stop()
        srv.stop()


def test_sharded_feeder_gauge_and_spans(monkeypatch):
    """OBS PIN for the round-14 sharded feeder: a sharded cached run must
    land (a) one ``persia_tpu_feeder_shard_busy`` gauge series per
    (group, shard) and (b) one ``feed.shard`` span per shard per feed —
    the native walker's self-measured walk time, surfaced via
    ``record_span`` (a Python-side ``span()`` would time the whole
    dispatch, not the shard)."""
    import sys as _sys

    _sys.path.insert(0, os.path.join(REPO_ROOT, "tests"))
    from test_hbm_cache import _block_batches, _one_slot_ctx

    from persia_tpu.metrics import get_metrics

    monkeypatch.setenv("PERSIA_FEED_SHARDS", "4")
    monkeypatch.setenv("PERSIA_FEED_THREADS", "2")
    tracing.enable(True)
    cfg, batches = _block_batches(4)
    ctx, _store = _one_slot_ctx(cfg, cache_rows=64)
    with ctx:
        assert ctx.tier.feed_shards == 4
        assert ctx.tier.feed_threads == 2
        gname = ctx.tier.groups[0].name
        ctx.train_stream(batches)
        ctx.flush()

    shard_spans = _spans_by_name().get("feed.shard", [])
    assert len(shard_spans) == 4 * len(batches), len(shard_spans)
    assert {ev["args"]["shard"] for ev in shard_spans} == {"0", "1", "2", "3"}
    assert all(ev["args"]["group"] == gname for ev in shard_spans)
    assert all(ev["dur"] >= 0 for ev in shard_spans)

    busy = get_metrics().snapshot("persia_tpu_feeder")[
        "persia_tpu_feeder_shard_busy"
    ]
    want = {f"group={gname},shard={s}" for s in range(4)}
    assert want <= set(busy), busy
    assert all(busy[k] >= 0.0 for k in want)


# ----------------------------------- flight recorder × chaos (acceptance)


def test_flight_recorder_correlates_chaos_with_quarantine(tmp_path):
    """ACCEPTANCE PIN: an injected delta-channel fault (blackhole) and the
    staleness quarantine + heal it causes land in ONE flight ledger, in
    causal order, carrying enough attrs to correlate them."""
    from persia_tpu.chaos import ChaosConfig, DeltaChannelChaos
    from persia_tpu.embedding.optim import Adagrad
    from persia_tpu.embedding.store import EmbeddingStore
    from persia_tpu.incremental import IncrementalUpdateManager, read_head
    from persia_tpu.serving import ReplicaGateway, ServingServer

    src_dir = str(tmp_path / "inc")
    src = EmbeddingStore(capacity=4096, num_internal_shards=4,
                         optimizer=Adagrad(lr=0.1).config, seed=3)
    mgr = IncrementalUpdateManager(src, src_dir)
    relay = DeltaChannelChaos(src_dir, str(tmp_path / "delta"), n_replicas=1,
                              cfg=ChaosConfig(), seed=1)
    store = EmbeddingStore(capacity=4096, num_internal_shards=2)
    srv = ServingServer(_ServeCtx(store=store), port=0, cache_rows=0,
                        inc_dir=relay.inc_dir(0),
                        rollover_poll_s=0.05).start()
    addr = f"127.0.0.1:{srv.port}"
    gw = ReplicaGateway(replicas=[addr], health_interval_s=0.1,
                        max_staleness_steps=2,
                        head_source=lambda: read_head(src_dir)).start()
    relay.start(interval_s=0.05)

    def publish(rounds, start):
        for r in range(rounds):
            signs = np.arange(start + r * 3, start + (r + 1) * 3,
                              dtype=np.uint64)
            src.lookup(signs, 8, train=True)
            src.update_gradients(signs,
                                 np.ones((len(signs), 8), dtype=np.float32))
            mgr.commit(signs)
            mgr.note_step(mgr.train_step + 1)
            mgr.flush()

    try:
        publish(2, 1)
        _wait(lambda: gw.stats()["live"] == [addr], what="replica live")
        relay.set_blackhole(0, True)          # the injected fault
        publish(4, 100)                       # head advances; replica frozen
        _wait(lambda: addr in gw.stats()["quarantined"], what="quarantine")
        relay.set_blackhole(0, False)         # heal the channel
        publish(1, 200)
        _wait(lambda: gw.stats()["quarantined"] == [], what="heal")

        events = tracing.flight_snapshot()
        kinds = [e["kind"] for e in events]
        for k in ("chaos.blackhole", "gateway.quarantine", "chaos.heal",
                  "gateway.heal"):
            assert k in kinds, f"{k} missing from {kinds}"
        # causal order by seq: fault -> quarantine -> heal -> gateway.heal
        seq = {k: next(e["seq"] for e in events if e["kind"] == k)
               for k in ("chaos.blackhole", "gateway.quarantine",
                         "chaos.heal", "gateway.heal")}
        assert seq["chaos.blackhole"] < seq["gateway.quarantine"] \
            < seq["chaos.heal"] < seq["gateway.heal"]
        # correlation attrs: the chaos event names the replica index, the
        # gateway event the replica address it quarantined
        black = next(e for e in events if e["kind"] == "chaos.blackhole")
        quar = next(e for e in events if e["kind"] == "gateway.quarantine")
        assert black["attrs"]["replica"] == "0"
        assert quar["attrs"]["replica"] == addr
        assert int(quar["attrs"]["lag_steps"]) > 2
        # and the dump is one artifact carrying the whole story
        dump = str(tmp_path / "flight.json")
        tracing.flight_dump(dump)
        doc = json.loads(open(dump).read())
        assert {"chaos.blackhole", "gateway.quarantine"} \
            <= {e["kind"] for e in doc["events"]}
    finally:
        relay.stop()
        gw.stop()
        srv.stop()
        mgr.stop()


# ------------------------------------------- training plane trace propagation


def test_breaker_trip_lands_in_flight_ring():
    from persia_tpu.service.resilience import CircuitBreaker

    b = CircuitBreaker("127.0.0.1:9", failure_threshold=2,
                       reset_timeout_s=60.0)
    b.on_failure()
    assert not [e for e in tracing.flight_snapshot()
                if e["kind"] == "breaker.trip"]
    with tracing.trace_context("aa" * 16):
        b.on_failure()  # second consecutive failure trips
    trips = [e for e in tracing.flight_snapshot()
             if e["kind"] == "breaker.trip"]
    assert len(trips) == 1
    assert trips[0]["attrs"]["endpoint"] == "127.0.0.1:9"
    assert trips[0]["attrs"]["cause"] == "failure"
    assert trips[0]["trace_id"] == "aa" * 16  # stamped with the culprit


# ------------------------------------------------- merged fleet (acceptance)


def test_local_topology_merged_trace(tmp_path):
    """ACCEPTANCE PIN: one ``LocalTopology`` run (what
    ``persia-tpu-launcher local --trace-dir`` wraps) produces ONE merged
    Perfetto timeline in which a client request's trace_id appears in BOTH
    the gateway process's spans and the replica subprocess's spans, with
    per-role process_name metadata and clock offsets recorded."""
    from persia_tpu.topology import LocalTopology

    trace_dir = str(tmp_path / "traces")
    # snapshot_every>0 so the trainer hits fence points: the armed
    # sentinel (PERSIA_HEALTH=1, LocalTopology default) scrubs the PS
    # there and its health.* events must land in the merged flight ledger
    topo = LocalTopology(
        trainers=1, replicas=1, steps=25, step_ms=0.0, rows=8,
        vocab=1000, flush_every=5, ckpt_every=0, snapshot_every=10,
        base_dir=str(tmp_path / "work"), trace_dir=trace_dir,
        auto_resume=False, startup_timeout_s=180.0,
    )
    with topo:
        # the replica advertised its telemetry endpoint on boot
        _wait(lambda: "replica0" in topo.telemetry_endpoints(),
              timeout_s=60.0, what="replica endpoint file")
        from persia_tpu.topology import demo_batch

        raw = demo_batch(step=0, rows=2, vocab=1000,
                         requires_grad=False).to_bytes()
        scores, info = topo.gateway.predict_bytes_ex(raw)
        assert scores.shape[0] == 2
        tid = info["trace_id"]

        def replica_has_span():
            eps = topo.telemetry_endpoints()
            doc, _ = LocalTopology._scrape(eps["replica0"]["port"], "/spans")
            return any(s["args"].get("trace_id") == tid
                       for s in doc["spans"])

        _wait(replica_has_span, timeout_s=30.0,
              what="replica span with the client trace id")

        def trainer_scrubbed():
            # live ring while the trainer runs; atexit dump once it exits
            try:
                eps = topo.telemetry_endpoints()
                doc, _ = LocalTopology._scrape(
                    eps["trainer0"]["port"], "/flight")
                evs = doc.get("events", [])
            except Exception:
                try:
                    evs = json.loads(open(os.path.join(
                        trace_dir, "trainer0.flight.json")).read())["events"]
                except (OSError, ValueError):
                    return False
            return any(e["kind"] == "health.scrub" for e in evs)

        _wait(trainer_scrubbed, timeout_s=120.0,
              what="trainer fence-point health.scrub event")
        merged = topo.merge_traces()
        assert merged and os.path.exists(merged)
        doc = json.loads(open(merged).read())
        assert set(doc["metadata"]["roles"]) >= {"gateway", "replica0"}
        meta = [e for e in doc["traceEvents"] if e.get("ph") == "M"]
        assert {m["args"]["name"] for m in meta} >= {"gateway", "replica0"}
        spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        pids_with_tid = {
            s["pid"] for s in spans if s["args"].get("trace_id") == tid
        }
        # the SAME request id crosses the process boundary: parent
        # (gateway) pid AND the replica subprocess pid both carry it
        assert len(pids_with_tid) >= 2, pids_with_tid
        names_with_tid = {
            s["name"] for s in spans if s["args"].get("trace_id") == tid
        }
        assert "gateway.predict" in names_with_tid
        assert "serving.request" in names_with_tid
        assert "serving.engine_forward" in names_with_tid
        # the armed trainer's fence-point health scrubs crossed the
        # process boundary into the merged flight ledger
        fl = json.loads(open(
            os.path.join(trace_dir, "merged_flight.json")).read())
        health_kinds = {e["kind"] for e in fl["events"]
                        if e["kind"].startswith("health.")}
        assert "health.scrub" in health_kinds, health_kinds
