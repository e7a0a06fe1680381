"""Chaos suite: scripted fault schedules against live local topologies.

Fast tests (tier-1): the frame-aware fault proxy (resets / corruption /
slow-reads / refusals, deterministic by seed), crc32 end-to-end integrity,
the chaos spec parser, and the two-group pending-ledger collision
regression. Slow tests: the flagship train_stream run that kills a PS
shard mid-stream under ≥1% frame resets and must finish BIT-IDENTICAL to
a fault-free replay (plus breaker re-close and per-step
degraded_lookup_frac reporting), and standby promotion with snapshot
replay."""

import time

import numpy as np
import pytest

from persia_tpu.chaos import (
    ChaosAction,
    ChaosConfig,
    ChaosPlane,
    ChaosProxy,
    parse_chaos_spec,
)
from persia_tpu.service.resilience import ResiliencePolicy, RetryPolicy
from persia_tpu.service.rpc import RpcClient, RpcError, RpcServer


# ----------------------------------------------------------------- spec


def test_chaos_spec_parse():
    cfg = parse_chaos_spec("seed=7,reset=0.02,slow=0.01,slow_ms=40,corrupt=0.005")
    assert cfg.seed == 7
    assert cfg.reset_prob == 0.02
    assert cfg.slow_prob == 0.01
    assert cfg.slow_ms == 40.0
    assert cfg.corrupt_prob == 0.005
    assert parse_chaos_spec("").to_dict() == ChaosConfig().to_dict()
    with pytest.raises(ValueError):
        parse_chaos_spec("warp=0.5")


# --------------------------------------------- resilience primitives


def test_retry_jitter_replays_deterministically_across_threads():
    """The seeded-jitter contract under concurrency: two policies with the
    same seed sleep the same sequence, and when N threads share ONE
    policy the interleaving may permute which caller gets which draw but
    the multiset of sleeps is the seeded sequence exactly — no draw
    lost, duplicated, or torn by a race on the shared RNG."""
    import threading

    mk = lambda: RetryPolicy(  # noqa: E731 - four identical policies
        max_attempts=4, base_s=0.01, multiplier=2.0, max_s=0.08,
        jitter=0.5, seed=7)
    n_threads, per_thread = 8, 8
    n = n_threads * per_thread
    ref_pol, replay_pol = mk(), mk()
    ref = [ref_pol.backoff(1) for _ in range(n)]
    assert [replay_pol.backoff(1) for _ in range(n)] == ref

    pol = mk()
    out: list = []
    lock = threading.Lock()

    def worker():
        mine = [pol.backoff(1) for _ in range(per_thread)]
        with lock:
            out.extend(mine)

    threads = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)
    assert len(out) == n
    assert sorted(out) == sorted(ref)
    # and every sleep respects the jitter envelope [d/2, d]
    d = min(0.01 * 2.0, 0.08)
    assert all(d * 0.5 <= s <= d for s in out)


def test_open_breaker_fails_fast_without_consuming_deadline_budget():
    """Deadline.cap composed with an OPEN breaker: the fail-fast path
    must not burn the caller's time budget — no socket, no backoff
    sleep. A later attempt (healthy peer) still gets nearly the whole
    budget from cap()."""
    from persia_tpu.service.resilience import CircuitBreaker, Deadline

    b = CircuitBreaker("dead:1", failure_threshold=1, reset_timeout_s=30.0)
    b.on_failure()
    assert b.state == "open" and b.trips == 1

    d = Deadline(0.5)
    t0 = time.monotonic()
    for _ in range(200):
        assert not b.allow()  # fail-fast: no probe slot while open
    assert time.monotonic() - t0 < 0.1
    # the budget survived the open-circuit storm
    assert d.cap(None) > 0.3
    assert d.cap(10.0) > 0.3
    assert d.cap(0.05) == pytest.approx(0.05)
    assert not d.expired
    d.check("healthy attempt")  # must not raise


def test_breaker_transitions_land_in_flight_recorder():
    """Satellite: trips, half-open probe grants, and re-closes are all
    record_event spans the flight recorder captures."""
    from persia_tpu import tracing
    from persia_tpu.service.resilience import CircuitBreaker

    tracing.flight_clear()
    b = CircuitBreaker("ep:9", failure_threshold=2, reset_timeout_s=0.05)
    b.on_failure()
    b.on_failure()  # second consecutive failure trips closed->open
    assert b.state == "open"
    time.sleep(0.06)  # reset window elapses -> half-open
    assert b.allow()  # consumes (and records) the one half-open probe
    assert not b.allow()  # probe slot taken
    b.on_success()  # probe succeeded: half_open -> closed
    assert b.state == "closed"
    kinds = [e["kind"] for e in tracing.flight_snapshot()
             if e["kind"].startswith("breaker.")]
    assert kinds == ["breaker.trip", "breaker.probe", "breaker.close"]
    events = {e["kind"]: e["attrs"] for e in tracing.flight_snapshot()
              if e["kind"].startswith("breaker.")}
    assert events["breaker.trip"]["endpoint"] == "ep:9"
    assert events["breaker.trip"]["cause"] == "failure"
    assert events["breaker.probe"]["trips"] == "1"
    assert events["breaker.close"]["prior_state"] == "half_open"
    tracing.flight_clear()


# ---------------------------------------------------------------- proxy


def _echo_server() -> RpcServer:
    srv = RpcServer(port=0)
    srv.register("echo", lambda p: bytes(p))
    return srv.start()


def test_proxy_transparent_when_faultless():
    srv = _echo_server()
    proxy = ChaosProxy(f"127.0.0.1:{srv.port}")
    try:
        client = RpcClient(proxy.addr, timeout_s=5.0)
        payload = bytes(range(256)) * 8
        assert client.call("echo", payload) == payload
        assert proxy.counts["frames"] >= 2  # request + reply
    finally:
        proxy.stop()
        srv.stop()


def test_proxy_resets_recovered_by_idempotent_retry():
    """Mid-frame resets on ~10%% of frames: every idempotent call still
    returns the exact payload (retry + reconnect), and the proxy proves
    the faults actually fired. Same seed ⇒ same injected-fault count."""
    counts = []
    for _run in range(2):
        srv = _echo_server()
        proxy = ChaosProxy(
            f"127.0.0.1:{srv.port}", ChaosConfig(seed=5, reset_prob=0.1)
        )
        try:
            policy = ResiliencePolicy(
                retry=RetryPolicy(max_attempts=8, base_s=0.005, max_s=0.02),
                breaker_failure_threshold=100,  # resets must not trip here
            )
            client = RpcClient(
                proxy.addr, timeout_s=5.0, retries=8, pool_size=1,
                policy=policy,
            )
            rng = np.random.default_rng(0)
            for i in range(40):
                payload = rng.integers(0, 256, 512, dtype=np.uint8).tobytes()
                assert client.call("echo", payload, idempotent=True) == payload
            assert proxy.counts["reset"] >= 1
            counts.append(dict(proxy.counts))
        finally:
            proxy.stop()
            srv.stop()
    # deterministic by seed: the sequential single-connection workload
    # draws the identical fault stream both runs
    assert counts[0] == counts[1]


def test_corrupt_frames_detected_by_crc():
    """Byte flips inside frames: with the negotiated crc32 trailer on,
    every corrupted frame is DETECTED (retryable error), so all idempotent
    calls return bit-exact payloads — never silent garbage."""
    srv = _echo_server()
    proxy = ChaosProxy(
        f"127.0.0.1:{srv.port}", ChaosConfig(seed=3, corrupt_prob=0.25)
    )
    try:
        client = RpcClient(
            proxy.addr, timeout_s=5.0, retries=10, pool_size=1,
            integrity=True,
            policy=ResiliencePolicy(
                retry=RetryPolicy(max_attempts=10, base_s=0.002, max_s=0.01),
                breaker_failure_threshold=1000,
            ),
        )
        rng = np.random.default_rng(1)
        ok = 0
        for i in range(40):
            payload = rng.integers(0, 256, 1024, dtype=np.uint8).tobytes()
            try:
                reply = client.call("echo", payload, idempotent=True)
            except RpcError:
                continue  # every retry hit a corrupt frame — fine, DETECTED
            assert reply == payload  # bit-exact or error, nothing in between
            ok += 1
        assert ok >= 20
        assert proxy.counts["corrupt"] >= 3
    finally:
        proxy.stop()
        srv.stop()


def test_proxy_slow_and_refuse_and_blackhole():
    srv = _echo_server()
    proxy = ChaosProxy(
        f"127.0.0.1:{srv.port}", ChaosConfig(seed=2, slow_prob=1.0, slow_ms=30)
    )
    try:
        client = RpcClient(proxy.addr, timeout_s=5.0, pool_size=1)
        t0 = time.perf_counter()
        assert client.call("echo", b"x", idempotent=True) == b"x"
        assert time.perf_counter() - t0 >= 0.03  # both directions delayed
        assert proxy.counts["slow"] >= 1
        # blackhole: existing + new connections die, calls fail
        proxy.set_blackhole(True)
        with pytest.raises(RpcError):
            client.call("echo", b"y")
        # heal: service resumes
        proxy.set_blackhole(False)
        assert client.call("echo", b"z", idempotent=True) == b"z"
    finally:
        proxy.stop()
        srv.stop()


# ------------------------------------- pending-ledger group-salt collision


def test_two_group_pending_collision_regression():
    """Round-5 medium finding: PendingSignMap is global but gate() runs per
    group — with feature_index_prefix_bit=0 the SAME raw sign exists in
    two groups, and an unsalted probe in group B would restore group A's
    in-flight ring rows (silent corruption). The per-group salt must keep
    the namespaces apart through the REAL fused-feed prepare path."""
    from persia_tpu.config import EmbeddingConfig, SlotConfig
    from persia_tpu.data import IDTypeFeatureWithSingleID, Label, PersiaBatch
    from persia_tpu.embedding.hbm_cache.directory import PendingSignMap
    from persia_tpu.embedding.hbm_cache.tier import CachedEmbeddingTier
    from persia_tpu.embedding.optim import Adagrad
    from persia_tpu.embedding.store import EmbeddingStore
    from persia_tpu.embedding.worker import EmbeddingWorker

    cfg = EmbeddingConfig(
        slots_config={"a": SlotConfig(dim=8), "b": SlotConfig(dim=16)},
        feature_index_prefix_bit=0,  # raw signs collide across groups
    )
    worker = EmbeddingWorker(
        cfg,
        [EmbeddingStore(
            capacity=1 << 12, num_internal_shards=2, seed=3,
            optimizer=Adagrad(lr=0.1).config,
        )],
    )
    tier = CachedEmbeddingTier(
        worker, Adagrad(lr=0.1).config, rows=64, embedding_config=cfg,
        init_seed=3,
    )
    ga = next(g for g in tier.groups if g.dim == 8)
    gb = next(g for g in tier.groups if g.dim == 16)
    assert tier._group_salt[ga.name] != tier._group_salt[gb.name]

    pm = PendingSignMap()
    x = np.array([42], dtype=np.uint64)
    # group A has sign 42 riding an in-flight eviction (ring row 7)
    pm.insert_range(x, base_src=7, token=1, salt=tier._group_salt[ga.name])

    n = 4
    batch = PersiaBatch(
        [
            IDTypeFeatureWithSingleID(
                "a", np.full(n, 42, dtype=np.uint64)),
            IDTypeFeatureWithSingleID(
                "b", np.full(n, 42, dtype=np.uint64)),
        ],
        labels=[Label(np.zeros((n, 1), dtype=np.float32))],
        requires_grad=True,
    )
    out = tier.prepare_batch(batch, pending_map=pm)
    restore_aux = out[4]
    # group A's miss resolves against ITS pending entry (positive control)
    assert ga.name in restore_aux
    payload, src, pos = restore_aux[ga.name][0]
    assert payload is None and 7 in np.asarray(src)
    # group B misses the same raw sign but must NOT see A's entry
    assert gb.name not in restore_aux


def test_pending_map_salt_namespaces_queries():
    from persia_tpu.embedding.hbm_cache.directory import (
        PendingSignMap,
        group_salt,
    )

    pm = PendingSignMap()
    signs = np.arange(10, 20, dtype=np.uint64)
    sa, sb = group_salt("cache_d8"), group_salt("cache_d16")
    assert sa != sb
    pm.insert_range(signs, base_src=100, token=1, salt=sa)
    hits_a, _t, srcs_a = pm.query(signs, salt=sa)
    hits_b, _t, srcs_b = pm.query(signs, salt=sb)
    assert hits_a == len(signs) and (srcs_a >= 100).all()
    assert hits_b == 0 and (srcs_b == -1).all()
    # token-conditional remove honors the namespace too
    pm.remove(signs, token=1, salt=sb)
    assert pm.query(signs, salt=sa)[0] == len(signs)
    pm.remove(signs, token=1, salt=sa)
    assert pm.query(signs, salt=sa)[0] == 0


# ----------------------------------------------------- flagship (slow)


def _two_slot_cfg():
    from persia_tpu.config import EmbeddingConfig, SlotConfig

    return EmbeddingConfig(
        slots_config={"cat_0": SlotConfig(dim=8), "cat_1": SlotConfig(dim=8)},
        feature_index_prefix_bit=8,
    )


@pytest.mark.slow
def test_chaos_stream_kill_and_resets_bitwise(monkeypatch):
    """THE acceptance run: CachedTrainCtx.train_stream against real
    subprocess PS shards behind fault proxies injecting ≥1% mid-frame
    resets, with PS shard 0 SIGKILLed mid-stream and restarted (snapshot
    replay). Must hold: the stream completes; per-step metrics report
    degraded_lookup_frac; the killed shard's breaker tripped and
    RE-CLOSED; and the run is BIT-IDENTICAL to a fault-free in-process
    replay of the same seed for all non-degraded signs (here: every sign —
    the failover budget rides out the restart, so nothing degrades and
    nothing is allowed to be wrong)."""
    import optax

    from persia_tpu.embedding import hbm_cache as hbm
    from persia_tpu.embedding.hashing import add_index_prefix
    from persia_tpu.embedding.optim import Adagrad
    from persia_tpu.embedding.store import EmbeddingStore
    from persia_tpu.embedding.worker import EmbeddingWorker
    from persia_tpu.helper import ServiceCtx
    from persia_tpu.models import DNN
    from persia_tpu.testing import SyntheticClickDataset

    monkeypatch.setenv("PERSIA_RPC_CRC", "1")  # resets + integrity together
    VOCABS = (64, 32)
    cfg = _two_slot_cfg()
    ds = SyntheticClickDataset(num_samples=768, vocab_sizes=VOCABS, seed=9)

    def make_ctx(worker):
        return hbm.CachedTrainCtx(
            model=DNN(dense_mlp_size=8, sparse_mlp_size=16, hidden_sizes=(32,)),
            dense_optimizer=optax.adam(3e-3),
            embedding_optimizer=Adagrad(lr=0.1),
            worker=worker, embedding_config=cfg,
            cache_rows=256,  # > the 96-sign space: eviction-free segments,
            init_seed=7,     # so the kill loses no in-flight write-backs
        ).__enter__()

    def run(worker, plane=None, metrics=None):
        ctx = make_ctx(worker)
        cb = (lambda m: metrics.append(m)) if metrics is not None else None
        seg1 = list(ds.batches(32))[:12]
        seg2 = list(ds.batches(32))[12:24]
        ctx.train_stream(seg1, on_metrics=cb)
        ctx.flush()  # all rows land on the PS tier (both runs)
        if plane is not None:
            seg2 = plane.wrap_batches(seg2)
        ctx.train_stream(seg2, on_metrics=cb)
        ctx.flush()
        return ctx

    # ---- chaos run: remote PS behind reset-injecting proxies ----
    policy = ResiliencePolicy(
        retry=RetryPolicy(max_attempts=4, base_s=0.02, max_s=0.3, seed=1),
        breaker_failure_threshold=3, breaker_reset_s=0.3,
        degrade_after_s=60.0,  # ride out the restart; degrade only if stuck
        max_degraded_frac=1.0,
    )
    chaos_metrics = []
    with ServiceCtx(
        num_parameter_servers=2, num_embedding_workers=0,
        backend="numpy", seed=7,
    ) as svc:
        plane = ChaosPlane(
            svc, ChaosConfig(seed=11, reset_prob=0.15),  # ≥1% resets (15%:
            # the stream carries ~70-100 frames, so a low rate can draw
            # zero faults on an unlucky connection layout)
            schedule=[
                # snapshot + kill inline at step 4, restart 1.5 s later:
                # a REAL dead window the stream must ride out (failing
                # lookups trip the breaker; the replay restores state)
                ChaosAction(step=4, op="snapshot", idx=0),
                ChaosAction(step=4, op="kill_ps", idx=0),
                ChaosAction(step=4, op="restart_ps", idx=0, restore=True,
                            after_s=1.5),
            ],
        )
        try:
            ps = plane.ps_clients(policy=policy, timeout_s=10.0)
            for c in ps:
                c.wait_ready()
            worker = EmbeddingWorker(cfg, ps, policy=policy)
            run(worker, plane=plane, metrics=chaos_metrics)

            # the schedule actually fired and the wire actually hurt
            assert all(a.fired for a in plane.schedule)
            assert plane.fault_counts()["reset"] >= 1
            # degraded_lookup_frac reported per step, and nothing degraded
            assert all("degraded_lookup_frac" in m for m in chaos_metrics)
            assert all(m["degraded_lookup_frac"] == 0.0 for m in chaos_metrics)
            assert not worker.lookup_router._degraded_signs
            # the killed shard's breaker tripped and re-closed
            trips = policy.breaker_trips()
            assert any(v >= 1 for v in trips.values()), trips
            for c in ps:
                c.wait_ready()
            assert all(
                s == "closed" for s in policy.breaker_states().values()
            ), policy.breaker_states()

            # read the final PS state through CLEAN direct clients
            remote_entries = {}
            direct = [
                __import__("persia_tpu.service.clients",
                           fromlist=["StoreClient"]).StoreClient(a)
                for a in svc.ps_addrs()
            ]
            for si, (slot, vocab) in enumerate(zip(("cat_0", "cat_1"), VOCABS)):
                pre = cfg.slot(slot).index_prefix
                for s in range(vocab):
                    sign = int(add_index_prefix(
                        np.array([s], np.uint64), pre, 8)[0])
                    for c in direct:
                        e = c.get_embedding_entry(sign)
                        if e is not None:
                            remote_entries[(slot, s)] = e
                            break
        finally:
            plane.stop()

    # ---- fault-free replay: identical seeds, in-process stores ----
    clean_stores = [
        EmbeddingStore(capacity=1 << 18, num_internal_shards=4, seed=7)
        for _ in range(2)
    ]
    clean_metrics = []
    run(EmbeddingWorker(cfg, clean_stores), metrics=clean_metrics)

    # losses agree step for step…
    np.testing.assert_allclose(
        [m["loss"] for m in chaos_metrics],
        [m["loss"] for m in clean_metrics], rtol=1e-6,
    )
    # …and the final PS entries are BIT-identical for every sign: zero
    # wrong-row lookups anywhere in the chaos run (a single mis-routed or
    # corrupted row would diverge the training trajectory)
    checked = 0
    for si, (slot, vocab) in enumerate(zip(("cat_0", "cat_1"), VOCABS)):
        pre = cfg.slot(slot).index_prefix
        for s in range(vocab):
            sign = int(add_index_prefix(np.array([s], np.uint64), pre, 8)[0])
            clean = None
            for st in clean_stores:
                clean = st.get_embedding_entry(sign)
                if clean is not None:
                    break
            chaos_e = remote_entries.get((slot, s))
            assert (clean is None) == (chaos_e is None), (slot, s)
            if clean is not None:
                np.testing.assert_array_equal(chaos_e, clean, err_msg=str((slot, s)))
                checked += 1
    assert checked > 50


@pytest.mark.slow
def test_trainer_sigkill_auto_resume_bitwise(tmp_path):
    """THE trainer-crash acceptance run (ISSUE 5): a REAL ``SIGKILL`` of
    the trainer subprocess at a seeded-RANDOM mid-stream step (landed via
    the progress beacon, i.e. between "gradient applied" and "next
    manifest committed"), an auto-resume relaunch from the newest
    manifest, and final PS entries + dense params BIT-IDENTICAL to an
    uninterrupted run of the same seeds — no lost and no double-applied
    gradients anywhere."""
    import os as _os
    import random
    import subprocess
    import sys

    from persia_tpu.chaos import TrainerKiller
    from persia_tpu.embedding.hashing import add_index_prefix
    from persia_tpu.helper import ServiceCtx
    from persia_tpu.service.clients import StoreClient

    STEPS, K = 22, 5
    VOCABS = (64, 32)
    kill_at = random.Random(1234).randint(6, 16)  # randomized, reproducible
    trainer_main = _os.path.join(_os.path.dirname(__file__), "jobstate_trainer_main.py")

    def run_topology(workdir, kill: bool):
        workdir.mkdir()
        out_path = str(workdir / "final.state")
        progress = str(workdir / "progress")
        with ServiceCtx(
            num_parameter_servers=2, num_embedding_workers=0,
            backend="numpy", seed=7,
        ) as svc:
            env = dict(_os.environ)
            repo_root = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
            env.update({
                "PYTHONPATH": repo_root + _os.pathsep + env.get("PYTHONPATH", ""),
                "JAX_PLATFORMS": "cpu",
                "JS_PS_ADDRS": ",".join(svc.ps_addrs()),
                "JS_DIR": str(workdir / "js"),
                "JS_PROGRESS": progress,
                "JS_OUT": out_path,
                "JS_STEPS": str(STEPS),
                "JS_SNAPSHOT_EVERY": str(K),
                "JS_SEED": "9",
            })
            cmd = [sys.executable, trainer_main]
            proc = subprocess.Popen(cmd, env=env)
            if kill:
                killer = TrainerKiller(proc, progress, kill_at).start()
                assert killer.wait(timeout_s=300)
                assert killer.killed_at is not None, "trainer finished before the kill"
                assert proc.wait(timeout=30) != 0  # SIGKILL, not clean exit
                # auto-resume relaunch (what the launcher's loop does)
                proc = subprocess.Popen(cmd, env=env)
            assert proc.wait(timeout=600) == 0
            state_bytes = open(out_path, "rb").read()
            entries = {}
            direct = [StoreClient(a) for a in svc.ps_addrs()]
            from persia_tpu.config import EmbeddingConfig, SlotConfig

            cfg = EmbeddingConfig(
                slots_config={"cat_0": SlotConfig(dim=8), "cat_1": SlotConfig(dim=8)},
                feature_index_prefix_bit=8,
            )
            for slot, vocab in zip(("cat_0", "cat_1"), VOCABS):
                pre = cfg.slot(slot).index_prefix
                for s in range(vocab):
                    sign = int(add_index_prefix(
                        np.array([s], np.uint64), pre, 8)[0])
                    for c in direct:
                        e = c.get_embedding_entry(sign)
                        if e is not None:
                            entries[(slot, s)] = e
                            break
            return state_bytes, entries

    chaos_state, chaos_entries = run_topology(tmp_path / "chaos", kill=True)
    clean_state, clean_entries = run_topology(tmp_path / "clean", kill=False)

    # dense params + optimizer state: BYTE-identical serialized trees
    assert chaos_state == clean_state
    # every PS entry bitwise (values AND optimizer state)
    assert set(chaos_entries) == set(clean_entries)
    checked = 0
    for k in clean_entries:
        np.testing.assert_array_equal(
            chaos_entries[k], clean_entries[k], err_msg=str(k)
        )
        checked += 1
    assert checked > 50


@pytest.mark.slow
def test_standby_promotion_with_snapshot_replay():
    """A spare PS is promoted into a dead shard's slot: the snapshot
    replays through dump_shard/load_shard_bytes, the coordinator entry is
    upserted, and a router that swaps the replica handle serves the
    restored rows bitwise."""
    from persia_tpu.embedding.worker import ShardedLookup
    from persia_tpu.helper import ServiceCtx
    from persia_tpu.service.clients import StoreClient

    with ServiceCtx(
        num_parameter_servers=2, num_embedding_workers=0,
        backend="numpy", seed=7,
    ) as svc:
        ps = svc.ps_clients()
        for c in ps:
            c.wait_ready()
        router = ShardedLookup(ps)
        rng = np.random.default_rng(0)
        signs = np.arange(1, 200, dtype=np.uint64)
        vals = rng.normal(size=(len(signs), 8)).astype(np.float32)
        router.set_embedding(signs, vals, dim=8)
        svc.snapshot_ps(0)
        standby = svc.spawn_standby_ps()
        svc.kill_ps(0)
        promoted = svc.promote_standby(0, standby)
        assert promoted == standby
        assert svc.ps_addrs()[0] == promoted  # coordinator upserted
        router.replace_replica(0, StoreClient(promoted))
        got = router.lookup(signs, 8, train=False)
        np.testing.assert_array_equal(got, vals)
