"""RPC data-plane concurrency + failure recovery.

Parity targets: the reference runs 8-10 concurrent RPCs per connection pool
(`rust/persia-core/src/forward.rs:640-779`); forward workers catch lookup
errors, block on wait_for_serving, then continue (forward.rs:708-716);
the embedding worker rebuilds its PS state on error
(embedding_worker_service/mod.rs:1320-1333).
"""

import threading
import time

import numpy as np
import pytest

from persia_tpu.service.resilience import (
    CircuitBreaker,
    Deadline,
    ResiliencePolicy,
    RetryPolicy,
)
from persia_tpu.service.rpc import RpcClient, RpcError, RpcServer


# ----------------------------------------------------------- connection pool


def _slow_server(delay_s: float = 0.05) -> RpcServer:
    srv = RpcServer(port=0)

    def handler(payload: bytes) -> bytes:
        time.sleep(delay_s)
        return b"done"

    srv.register("slow", handler)
    return srv.start()


def test_pool_parallel_in_flight_scaling():
    """N threads over one pooled client must drive N concurrent calls (the
    round-1 single-socket client serialized them). Each handler call holds
    until all 8 are in the server at once: a client that serializes never
    gets the second one there. No clock is compared, so a loaded machine
    cannot fail it."""
    n = 8
    all_in = threading.Barrier(n)
    srv = RpcServer(port=0)

    def handler(payload: bytes) -> bytes:
        all_in.wait(timeout=30)  # BrokenBarrierError -> an RpcError at the caller
        return b"done"

    srv.register("meet", handler)
    srv.start()
    try:
        client = RpcClient(f"127.0.0.1:{srv.port}", pool_size=n)
        client.call("ping")  # warm one connection
        replies, errors = [], []

        def call():
            try:
                replies.append(client.call("meet", timeout_s=60.0))
            except Exception as e:  # noqa: BLE001 — reported below
                errors.append(e)

        threads = [threading.Thread(target=call) for _ in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=90)
        assert not any(t.is_alive() for t in threads)
        assert not errors, f"pool did not parallelize: {errors[:2]}"
        assert replies == [b"done"] * n
    finally:
        srv.stop()


def test_pool_bounds_connections_and_recovers_broken():
    srv = _slow_server(0.01)
    try:
        client = RpcClient(f"127.0.0.1:{srv.port}", pool_size=2)
        threads = [
            threading.Thread(target=lambda: client.call("slow")) for _ in range(6)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
        assert client._total <= 2
        # break every pooled socket; next call must transparently reconnect
        with client._cond:
            for s in client._idle:
                s.close()
        assert client.call("ping", idempotent=True) == b"pong"
    finally:
        srv.stop()


def _hard_stop(srv, *clients):
    """Simulate a process death, not a graceful drain: stop the accept
    loop, close the listener (so new connects are refused), and drop the
    clients' pooled connections (their handler threads die with them)."""
    srv.stop()
    srv._server.server_close()
    for c in clients:
        rpc = getattr(c, "_rpc", c)
        rpc.close()
    time.sleep(0.05)


# ------------------------------------------------- breaker trip / half-open


def test_breaker_unit_trip_half_open_reclose():
    """State machine: threshold consecutive failures open the breaker; the
    reset window grants exactly ONE half-open probe; probe success
    re-closes, probe failure re-opens."""
    b = CircuitBreaker("ep", failure_threshold=3, reset_timeout_s=0.1)
    assert b.state == "closed" and b.allow()
    b.on_failure()
    b.on_failure()
    assert b.state == "closed"  # under threshold
    b.on_failure()
    assert b.state == "open" and b.trips == 1
    assert not b.allow()  # open: fail fast
    time.sleep(0.12)
    assert b.state == "half_open"
    assert b.allow()       # the one probe slot
    assert not b.allow()   # second caller in the window is rejected
    b.on_failure()         # probe failed → re-open (counts a trip)
    assert b.state == "open" and b.trips == 2
    time.sleep(0.12)
    assert b.allow()
    b.on_success()         # probe succeeded → closed, counters reset
    assert b.state == "closed" and b.allow()


def test_client_breaker_trip_then_recovery_recloses():
    """RPC-level breaker lifecycle: a dead endpoint trips the breaker
    (subsequent calls fail FAST, no connect timeout), and the endpoint
    coming back re-closes it through the ping probe path."""
    srv = RpcServer(port=0).start()
    port = srv.port
    policy = ResiliencePolicy(
        retry=RetryPolicy(max_attempts=2, base_s=0.01, max_s=0.02),
        breaker_failure_threshold=2, breaker_reset_s=0.15,
    )
    client = RpcClient(f"127.0.0.1:{port}", timeout_s=2.0, policy=policy)
    assert client.call("ping") == b"pong"
    _hard_stop(srv, client)
    breaker = policy.breaker(client.endpoint)
    for _ in range(3):
        with pytest.raises(RpcError):
            client.call("ping2", idempotent=True)  # not ping: breaker applies
    assert breaker.state in ("open", "half_open")
    assert breaker.trips >= 1
    # open breaker = fail fast (no 2s connect timeout per call)
    t0 = time.perf_counter()
    with pytest.raises(RpcError):
        client.call("ping2")
    assert time.perf_counter() - t0 < 1.0
    # endpoint returns on the SAME port: ping (breaker-exempt) succeeds and
    # re-closes the breaker
    srv2 = RpcServer(port=port).start()
    try:
        client.wait_ready(timeout_s=10)
        assert breaker.state == "closed"
    finally:
        srv2.stop()


def test_deadline_budget_bounds_call():
    """A per-call Deadline caps the attempt's socket timeout: a wedged
    handler costs the caller its budget, not the full client timeout."""
    srv = _slow_server(5.0)  # handler far slower than the budget
    try:
        client = RpcClient(f"127.0.0.1:{srv.port}", timeout_s=30.0)
        t0 = time.perf_counter()
        with pytest.raises(RpcError):
            client.call("slow", deadline=Deadline.after(0.2))
        assert time.perf_counter() - t0 < 2.0
    finally:
        srv.stop()


# --------------------------------------------- degraded lookup + reconcile


def _ps_service(store, port=0):
    from persia_tpu.service.ps_server import ParameterServerService

    return ParameterServerService(store, native_server=False, port=port).start()


def test_degraded_lookup_then_reconcile():
    """Shard dies past the degrade budget → lookups serve DETERMINISTIC
    init vectors and the signs' gradients are dropped; shard returns →
    the next live lookup reconciles the record and gradients apply
    again."""
    from persia_tpu.config import HyperParameters
    from persia_tpu.embedding.hashing import init_for_signs
    from persia_tpu.embedding.optim import Adagrad
    from persia_tpu.embedding.store import EmbeddingStore
    from persia_tpu.embedding.worker import ShardedLookup
    from persia_tpu.service.clients import StoreClient

    seed, dim = 11, 8
    method = HyperParameters().resolved_init_method()
    store = EmbeddingStore(
        capacity=1 << 12, num_internal_shards=2, seed=seed,
        optimizer=Adagrad(lr=0.5).config,
    )
    svc = _ps_service(store)
    port = svc.port
    policy = ResiliencePolicy(
        retry=RetryPolicy(max_attempts=2, base_s=0.01, max_s=0.02),
        breaker_failure_threshold=2, breaker_reset_s=0.1,
        degrade_after_s=0.3, max_degraded_frac=1.0,
    )
    client = StoreClient(f"127.0.0.1:{port}", timeout_s=2.0, policy=policy)
    router = ShardedLookup(
        [client], policy=policy,
        degraded_init=lambda s, d: init_for_signs(s, seed, d, method),
    )
    signs = np.array([3, 9, 17], dtype=np.uint64)
    init_vals = init_for_signs(signs, seed, dim, method)
    # admit + train so the REAL rows differ from the init vectors
    first = router.lookup(signs, dim, train=True)
    np.testing.assert_array_equal(first, init_vals)
    router.update(signs, np.ones((3, dim), np.float32), 0)
    trained = router.lookup(signs, dim, train=True)
    assert np.abs(trained - init_vals).max() > 1e-3

    _hard_stop(svc.server, client)
    # degraded: deterministic init vectors, NOT zeros, NOT an exception
    degraded = router.lookup(signs, dim, train=True)
    np.testing.assert_array_equal(degraded, init_vals)
    assert router.degraded_intersection(signs).all()
    d, t = router.take_degraded_window()
    assert d == len(signs) and t >= len(signs)

    # shard returns (same store object, same port: state intact)
    svc2 = _ps_service(store, port=port)
    try:
        client.wait_ready(timeout_s=10)
        # gradients computed against the degraded forward are DROPPED
        before = router._m_deg_grad_dropped.get()
        snapshot = store.lookup(signs, dim, train=False).copy()
        router.update(signs, np.ones((3, dim), np.float32), 0)
        assert router._m_deg_grad_dropped.get() - before == len(signs)
        np.testing.assert_array_equal(
            store.lookup(signs, dim, train=False), snapshot
        )
        # a live lookup reconciles; the NEXT gradient applies again
        live = router.lookup(signs, dim, train=True)
        np.testing.assert_array_equal(live, trained)
        assert not router.degraded_intersection(signs).any()
        router.update(signs, np.ones((3, dim), np.float32), 0)
        assert np.abs(
            store.lookup(signs, dim, train=False) - snapshot
        ).max() > 1e-4
    finally:
        svc2.server.stop()


def test_degraded_abort_threshold():
    """A call whose degraded fraction exceeds max_degraded_frac raises
    instead of silently training on synthetic embeddings."""
    from persia_tpu.embedding.store import EmbeddingStore
    from persia_tpu.embedding.worker import ShardedLookup
    from persia_tpu.service.clients import StoreClient

    store = EmbeddingStore(capacity=1 << 10, num_internal_shards=2, seed=0)
    svc = _ps_service(store)
    policy = ResiliencePolicy(
        retry=RetryPolicy(max_attempts=1, base_s=0.01, max_s=0.02),
        breaker_failure_threshold=1, breaker_reset_s=0.05,
        degrade_after_s=0.1, max_degraded_frac=0.5,
    )
    client = StoreClient(f"127.0.0.1:{svc.port}", timeout_s=1.0, policy=policy)
    router = ShardedLookup([client], policy=policy)
    signs = np.arange(1, 9, dtype=np.uint64)
    router.lookup(signs, 4, train=True)
    _hard_stop(svc.server, client)
    with pytest.raises(RuntimeError, match="degraded_lookup_frac"):
        router.lookup(signs, 4, train=True)


# --------------------------------------------------------- PS kill + restart


def test_trainer_kill_resume_over_rpc_bit_identical(tmp_path):
    """Trainer-crash recovery over the REAL RPC wire (in-process PS
    services, StoreClient transport — the journaled update frame included):
    the trainer is abandoned mid-window with post-fence gradients already
    applied; a fresh trainer resumes from the manifest (PS rewind + journal
    clear over RPC) and finishes bit-identical to an uninterrupted run."""
    import optax

    from persia_tpu.config import EmbeddingConfig, SlotConfig
    from persia_tpu.ctx import TrainCtx
    from persia_tpu.embedding.hashing import add_index_prefix
    from persia_tpu.embedding.optim import Adagrad
    from persia_tpu.embedding.store import EmbeddingStore
    from persia_tpu.embedding.worker import EmbeddingWorker
    from persia_tpu.jobstate import JobStateManager
    from persia_tpu.models import DNN
    from persia_tpu.service.clients import StoreClient
    from persia_tpu.testing import SyntheticClickDataset

    VOCABS = (64, 32)
    cfg = EmbeddingConfig(
        slots_config={"cat_0": SlotConfig(dim=8), "cat_1": SlotConfig(dim=8)},
        feature_index_prefix_bit=8,
    )
    STEPS, K, KILL_AT = 10, 4, 7
    batches = list(
        SyntheticClickDataset(num_samples=STEPS * 32, vocab_sizes=VOCABS, seed=9)
        .batches(32)
    )[:STEPS]

    def make_stores():
        return [
            EmbeddingStore(capacity=1 << 16, num_internal_shards=4, seed=7)
            for _ in range(2)
        ]

    def make_ctx(worker):
        return TrainCtx(
            model=DNN(dense_mlp_size=8, sparse_mlp_size=16, hidden_sizes=(32,)),
            dense_optimizer=optax.adam(3e-3),
            embedding_optimizer=Adagrad(lr=0.1),
            worker=worker, embedding_config=cfg,
        ).__enter__()

    def entries_of(stores):
        out = {}
        for slot, vocab in zip(("cat_0", "cat_1"), VOCABS):
            pre = cfg.slot(slot).index_prefix
            for s in range(vocab):
                sign = int(add_index_prefix(np.array([s], np.uint64), pre, 8)[0])
                e = next(
                    (st.get_embedding_entry(sign) for st in stores
                     if st.get_embedding_entry(sign) is not None), None,
                )
                if e is not None:
                    out[(slot, s)] = e
        return out

    # baseline: in-process stores, uninterrupted
    base_stores = make_stores()
    base = make_ctx(EmbeddingWorker(cfg, base_stores))
    for b in batches:
        base.train_step(b)
    import jax

    base_params = jax.tree.map(np.asarray, base.state.params)

    # chaos run: PS behind real RPC servers; trainer dies mid-window
    stores = make_stores()
    services = [_ps_service(s) for s in stores]
    try:
        clients = [StoreClient(f"127.0.0.1:{svc.port}") for svc in services]
        for c in clients:
            c.wait_ready()
        mgr = JobStateManager(str(tmp_path / "js"))
        ctx1 = make_ctx(EmbeddingWorker(cfg, clients))
        ctx1.resume(mgr)  # cold start arms journaling
        for i, b in enumerate(batches[:KILL_AT]):
            ctx1.train_step(b)
            if (i + 1) % K == 0:
                ctx1.snapshot_job(mgr)
        del ctx1  # trainer "dies"; PS processes keep serving

        ctx2 = make_ctx(EmbeddingWorker(
            cfg, [StoreClient(f"127.0.0.1:{svc.port}") for svc in services]
        ))
        m = ctx2.resume(mgr)  # PS rewind + journal clear over RPC
        assert m is not None and m.step == 4
        for b in batches[m.step:]:
            ctx2.train_step(b)
        res_params = jax.tree.map(np.asarray, ctx2.state.params)
        for (kp, a), (_, b_) in zip(
            jax.tree_util.tree_leaves_with_path(base_params),
            jax.tree_util.tree_leaves_with_path(res_params),
        ):
            np.testing.assert_array_equal(a, b_, err_msg=str(kp))
    finally:
        for svc in services:
            try:
                svc.server.stop()
            except Exception:
                pass
    base_e, chaos_e = entries_of(base_stores), entries_of(stores)
    assert set(base_e) == set(chaos_e) and len(base_e) > 50
    for k in base_e:
        np.testing.assert_array_equal(base_e[k], chaos_e[k], err_msg=str(k))


@pytest.mark.slow
def test_training_survives_ps_kill_and_restart(tmp_path):
    """SIGKILL one PS replica mid-training, restart it on the same port:
    the DataLoader's lookup workers wait for serving and resume, the
    backward engine tolerates the window, and training completes with
    staleness drained (ref: forward.rs:708-716, emb_worker mod.rs:1320-1333)."""
    import optax
    import yaml

    from persia_tpu.ctx import TrainCtx
    from persia_tpu.data import IDTypeFeatureWithSingleID, Label, NonIDTypeFeature, PersiaBatch
    from persia_tpu.data_loader import DataLoader
    from persia_tpu.embedding.optim import Adagrad
    from persia_tpu.helper import ServiceCtx
    from persia_tpu.models import DNN
    from persia_tpu.config import EmbeddingConfig, SlotConfig

    cfg_path = tmp_path / "emb.yml"
    cfg_path.write_text(yaml.safe_dump({
        "feature_index_prefix_bit": 4,
        "slots_config": {"cat": {"dim": 8}},
    }))
    cfg = EmbeddingConfig(
        slots_config={"cat": SlotConfig(dim=8)}, feature_index_prefix_bit=4
    )

    with ServiceCtx(
        num_parameter_servers=2, num_embedding_workers=1,
        embedding_config_path=str(cfg_path),
    ) as svc:
        worker = svc.worker_clients()[0]
        worker.wait_ready()
        ctx = TrainCtx(
            model=DNN(dense_mlp_size=8, sparse_mlp_size=16, hidden_sizes=(16,)),
            dense_optimizer=optax.sgd(1e-2),
            embedding_optimizer=Adagrad(lr=0.1),
            worker=worker,
            embedding_config=cfg,
        ).__enter__()

        rng = np.random.default_rng(0)
        total_batches = 14
        killed = {"done": False}

        def stream():
            for i in range(total_batches):
                if i == 5 and not killed["done"]:
                    killed["done"] = True
                    svc.kill_ps(0)
                    # restart on the ORIGINAL port: clients reconnect
                    svc.restart_ps(0)
                yield PersiaBatch(
                    [IDTypeFeatureWithSingleID(
                        "cat", rng.integers(0, 500, 16, dtype=np.uint64))],
                    non_id_type_features=[NonIDTypeFeature(
                        rng.normal(size=(16, 4)).astype(np.float32))],
                    labels=[Label(rng.integers(0, 2, (16, 1)).astype(np.float32))],
                    requires_grad=True,
                )

        loader = DataLoader(
            stream(), ctx, num_workers=2, staleness=2, recovery_retries=6,
            timeout_s=120.0,
        )
        steps = 0
        for tb in loader:
            ctx.train_step_prepared(tb, loader)
            steps += 1
        loader.flush()
        assert steps == total_batches
        assert killed["done"]
        assert worker.staleness == 0
        svc.check_healthy()  # the (intentional) kill must not trip the watchdog
