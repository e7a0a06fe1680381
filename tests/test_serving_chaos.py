"""Chaos-hardened serving plane: staleness-bounded quarantine + auto-heal,
all-replicas-stale degraded serving with the ``X-Staleness-Steps`` label,
the server-side staleness header contract, and delta-channel damage repair
through the rollover watcher.

These are the FAST serving-chaos schedules (preflight step 1 runs them).
"""

import json
import threading
import time
import types

import numpy as np
import pytest

from persia_tpu.chaos import ChaosConfig, DeltaChannelChaos
from persia_tpu.embedding.optim import Adagrad
from persia_tpu.embedding.store import EmbeddingStore
from persia_tpu.data import IDTypeFeatureWithSingleID, NonIDTypeFeature, PersiaBatch
from persia_tpu.incremental import (
    IncrementalUpdateManager,
    read_head,
)
from persia_tpu.serving import InferenceClient, ReplicaGateway, ServingServer
from persia_tpu.storage import storage_path


def _train_store():
    return EmbeddingStore(capacity=4096, num_internal_shards=4,
                          optimizer=Adagrad(lr=0.1).config, seed=3)


def _touch(store, signs, dim=8):
    signs = np.asarray(signs, dtype=np.uint64)
    store.lookup(signs, dim, train=True)
    store.update_gradients(signs, np.ones((len(signs), dim), dtype=np.float32))


def _publish(src, mgr, rounds, start_sign=1, per=3):
    """``rounds`` packets of ``per`` fresh signs; one train step per packet.
    Returns the touched signs."""
    touched = []
    for r in range(rounds):
        signs = np.arange(start_sign + r * per, start_sign + (r + 1) * per,
                          dtype=np.uint64)
        _touch(src, signs)
        mgr.commit(signs)
        mgr.note_step(mgr.train_step + 1)
        assert mgr.flush() == per
        touched.extend(signs.tolist())
    return np.asarray(touched, dtype=np.uint64)


class _DeltaServeCtx:
    """Minimal InferCtx stand-in for delta-only replicas: constant scores,
    and the worker surface the rollover loader needs (one store behind a
    lookup router)."""

    def __init__(self, store, value):
        self.model = None
        self.state = None
        self.value = value
        self.worker = types.SimpleNamespace(
            lookup_router=types.SimpleNamespace(replicas=[store])
        )

    def predict(self, batch):
        return np.full((batch.batch_size,), self.value, dtype=np.float32)


def _req_batch(rows: int) -> PersiaBatch:
    return PersiaBatch(
        [IDTypeFeatureWithSingleID(
            "s", (np.arange(rows) % 16).astype(np.uint64))],
        non_id_type_features=[NonIDTypeFeature(
            np.zeros((rows, 2), dtype=np.float32))],
        requires_grad=False,
    )


def _entries_of(store, signs):
    return np.stack([store.get_embedding_entry(int(s)) for s in signs])


def _wait(pred, timeout_s=20.0, every=0.05, what="condition"):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(every)
    raise AssertionError(f"timed out waiting for {what}")


def test_quarantine_heal_and_bitwise_rejoin(tmp_path):
    """The acceptance pin: a replica fed a black-holed delta channel
    exceeds the staleness bound, leaves the balance set WITHOUT dropping
    in-flight requests, resyncs from the retained stream after the channel
    heals, rejoins serving, and its embeddings are bitwise identical to a
    never-faulted replica's."""
    src_dir = str(tmp_path / "inc")
    src = _train_store()
    mgr = IncrementalUpdateManager(src, src_dir)
    relay = DeltaChannelChaos(src_dir, str(tmp_path / "delta"), n_replicas=2,
                              cfg=ChaosConfig(), seed=1)
    store_a, store_b = (EmbeddingStore(capacity=4096, num_internal_shards=2)
                        for _ in range(2))
    srv_a = ServingServer(_DeltaServeCtx(store_a, 1.0), port=0, cache_rows=0,
                          inc_dir=relay.inc_dir(0), rollover_poll_s=0.05).start()
    srv_b = ServingServer(_DeltaServeCtx(store_b, 2.0), port=0, cache_rows=0,
                          inc_dir=relay.inc_dir(1), rollover_poll_s=0.05).start()
    addr_a, addr_b = (f"127.0.0.1:{s.port}" for s in (srv_a, srv_b))
    gw = ReplicaGateway(
        replicas=[addr_a, addr_b],
        health_interval_s=0.1, hedge_after_ms=500.0, request_timeout_s=5.0,
        max_staleness_steps=3,
        head_source=lambda: read_head(src_dir),
    ).start()
    relay.start(interval_s=0.05)
    failures = []
    stop_load = threading.Event()

    def hammer():
        while not stop_load.is_set():
            try:
                gw.predict(_req_batch(2))
            except Exception as e:  # noqa: BLE001 — every failure fails the test
                failures.append(repr(e))
                return

    load = threading.Thread(target=hammer)
    try:
        # phase 1: both replicas consume the live stream
        signs = _publish(src, mgr, rounds=2)
        _wait(lambda: all(
            (InferenceClient(a).health().get("freshness") or {})
            .get("applied_step", -1) == 2 for a in (addr_a, addr_b)
        ), what="both replicas caught up")
        assert sorted(gw.live_replicas()) == sorted([addr_a, addr_b])

        # phase 2: blackhole B's channel while requests are in flight; the
        # trainer keeps publishing and B's lag blows the 3-step bound
        load.start()
        relay.set_blackhole(1, True)
        for r in range(6):
            signs = np.concatenate([
                signs, _publish(src, mgr, rounds=1, start_sign=100 + 3 * r)
            ])
            # A applies each packet before the next goes out, so its lag
            # never passes one step however slowly its poll loop is scheduled
            _wait(lambda: (srv_a.freshness() or {}).get("applied_step")
                  == mgr.train_step, what="replica A caught up")
        _wait(lambda: gw.quarantined_replicas() == [addr_b],
              what="replica B quarantined")
        assert gw.live_replicas() == [addr_a]
        # quarantine only changes routing: the load thread never saw an error
        assert not failures
        # served by A only, still answering
        out = gw.predict(_req_batch(2))
        np.testing.assert_allclose(out, 1.0)

        # phase 3: heal the channel; the relay catches the replica up and
        # the gateway heals it back into the balance set on lag alone
        relay.set_blackhole(1, False)
        _wait(lambda: not gw.quarantined_replicas(), what="replica B healed")
        assert sorted(gw.live_replicas()) == sorted([addr_a, addr_b])
    finally:
        stop_load.set()
        load.join(timeout=10)
    assert not failures, f"requests failed across quarantine: {failures[:3]}"
    # the healed replica serves bitwise-identical embeddings to the
    # never-faulted one (and to the trainer source)
    # against the trainer's own head: B's `lag_steps` is measured from the
    # head its relay has shown it so far, and reads 0 a packet or two early
    _wait(lambda: (srv_b.freshness() or {}).get("applied_step") == mgr.train_step,
          what="replica B fully caught up")
    np.testing.assert_array_equal(_entries_of(store_b, signs),
                                  _entries_of(store_a, signs))
    np.testing.assert_array_equal(_entries_of(store_b, signs),
                                  _entries_of(src, signs))
    # the replica that was held back left the balance set once and came back once
    assert [(e["action"], e["replica"]) for e in gw.quarantine_log] == [
        ("quarantine", addr_b), ("heal", addr_b)]
    gw.stop()
    relay.stop()
    srv_a.stop()
    srv_b.stop()
    mgr.stop(final_flush=False)


def test_all_replicas_stale_serves_with_staleness_label(tmp_path):
    """When EVERY replica is quarantined the gateway degrades instead of
    failing: it serves from the least-stale replica and labels the answer
    with an over-bound staleness estimate."""
    src_dir = str(tmp_path / "inc")
    src = _train_store()
    mgr = IncrementalUpdateManager(src, src_dir)
    relay = DeltaChannelChaos(src_dir, str(tmp_path / "delta"), n_replicas=1,
                              cfg=ChaosConfig(), seed=2)
    store = EmbeddingStore(capacity=4096, num_internal_shards=1)
    srv = ServingServer(_DeltaServeCtx(store, 5.0), port=0, cache_rows=0,
                        inc_dir=relay.inc_dir(0), rollover_poll_s=0.05).start()
    addr = f"127.0.0.1:{srv.port}"
    gw = ReplicaGateway(
        replicas=[addr], health_interval_s=0.1, request_timeout_s=5.0,
        max_staleness_steps=2, head_source=lambda: read_head(src_dir),
    ).start()
    relay.start(interval_s=0.05)
    try:
        _publish(src, mgr, rounds=1)
        _wait(lambda: (InferenceClient(addr).health().get("freshness") or {})
              .get("applied_step", -1) == 1, what="replica caught up")
        relay.set_blackhole(0, True)
        _publish(src, mgr, rounds=6, start_sign=50)
        _wait(lambda: gw.quarantined_replicas() == [addr],
              what="sole replica quarantined")
        assert gw.live_replicas() == []
        scores, info = gw.predict_bytes_ex(_req_batch(2).to_bytes())
        np.testing.assert_allclose(scores, 5.0)
        assert info["stale_fallback"] is True
        assert info["staleness_steps"] > 2  # over the bound, explicitly labelled
        assert gw.stats()["stale_served"] >= 1
    finally:
        gw.stop()
        relay.stop()
        srv.stop()
        mgr.stop(final_flush=False)


def test_server_staleness_header_contract(tmp_path):
    """Every /predict answer carries X-Staleness-Steps: the replica's own
    lag between the newest applied packet and the trainer head it can see;
    /healthz carries the full freshness block."""
    src_dir = str(tmp_path / "inc")
    src = _train_store()
    mgr = IncrementalUpdateManager(src, src_dir)
    _touch(src, [1, 2, 3])
    mgr.commit(np.array([1, 2, 3], dtype=np.uint64))
    mgr.note_step(10)
    mgr.flush()
    store = EmbeddingStore(capacity=4096, num_internal_shards=1)
    srv = ServingServer(_DeltaServeCtx(store, 1.0), port=0, cache_rows=0,
                        inc_dir=src_dir, rollover_poll_s=0.05).start()
    cli = InferenceClient(f"127.0.0.1:{srv.port}")
    try:
        _wait(lambda: (cli.health().get("freshness") or {})
              .get("applied_step", -1) == 10, what="packet applied")
        # the trainer head races ahead without new packets landing
        storage_path(src_dir).join("inc_update_done.0").write_text(
            json.dumps({"replica": 0, "last_seq": 0, "time_us": 2 ** 62,
                        "train_step": 25})
        )
        srv.rollover._inc_loader.poll_once()
        f = cli.health()["freshness"]
        assert f["head_step"] == 25 and f["lag_steps"] == 15
        _scores, headers = cli.predict_bytes_ex(_req_batch(2).to_bytes())
        assert headers.get("x-staleness-steps") == "15"
    finally:
        srv.stop()
        mgr.stop(final_flush=False)


def test_rollover_resync_repairs_gap_via_retained_tail(tmp_path):
    """Delta-only rollover: a seq gap (lost packet) flags needs_resync and
    the watcher repairs it by replaying the retained tail — serving keeps
    answering throughout and the store converges to the newest values the
    stream still carries."""
    src_dir = str(tmp_path / "inc")
    src = _train_store()
    mgr = IncrementalUpdateManager(src, src_dir)
    # packets 0/1/2; packet 1's signs are RE-covered by packet 2 (the
    # retained tail can fully repair the gap)
    _touch(src, [1, 2, 3])
    mgr.commit(np.array([1, 2, 3], dtype=np.uint64))
    mgr.note_step(1)
    mgr.flush()
    _touch(src, [4, 5])
    mgr.commit(np.array([4, 5], dtype=np.uint64))
    mgr.note_step(2)
    mgr.flush()
    _touch(src, [4, 5, 6])
    mgr.commit(np.array([4, 5, 6], dtype=np.uint64))
    mgr.note_step(3)
    mgr.flush()

    store = EmbeddingStore(capacity=4096, num_internal_shards=1)
    srv = ServingServer(_DeltaServeCtx(store, 1.0), port=0, cache_rows=0,
                        inc_dir=src_dir, rollover_poll_s=0.05).start()
    cli = InferenceClient(f"127.0.0.1:{srv.port}")
    loader = srv.rollover._inc_loader
    try:
        _wait(lambda: (cli.health().get("freshness") or {})
              .get("applied_step", -1) == 3, what="stream applied")
        # lose a NEW packet in flight: 3 never lands, 4 does. The watcher
        # does not look between the write and the loss (its poll is 50 ms
        # and this thread may be held up for longer than that)
        no_poll = threading.Lock()
        poll = loader.poll_once

        def poll_outside_the_loss():
            with no_poll:
                return poll()

        loader.poll_once = poll_outside_the_loss
        with no_poll:
            _touch(src, [7, 8])
            mgr.commit(np.array([7, 8], dtype=np.uint64))
            mgr.note_step(4)
            mgr.flush()
            storage_path(src_dir).join("0_3.inc").remove()
        _touch(src, [7, 8, 9])
        mgr.commit(np.array([7, 8, 9], dtype=np.uint64))
        mgr.note_step(5)
        mgr.flush()
        _wait(lambda: loader.stats["gaps"] >= 1, what="gap observed")
        _wait(lambda: loader.stats["resyncs"] >= 1 and not loader.needs_resync,
              what="rollover-driven resync")
        # the server kept answering and converged to the source values
        probe = np.array([1, 2, 3, 4, 5, 6, 7, 8, 9], dtype=np.uint64)
        np.testing.assert_array_equal(_entries_of(store, probe),
                                      _entries_of(src, probe))
        assert cli.predict_bytes(_req_batch(2).to_bytes()).shape == (2,)
    finally:
        srv.stop()
        mgr.stop(final_flush=False)
