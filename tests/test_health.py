"""Numerical-health sentinel (persia_tpu.health): batch validator +
quarantine, on-device probe decode, sentinel escalation ladder, PS row
scrubber exactly-once journaling, non-finite delta rejection, NUM001
lint, and the flagship poisoned-stream parity run.

Flagship shape: a finite gradient spike injected mid-stream must be
detected within one dispatch window by the host z-score, trigger an
auto-rollback to the LAST_GOOD jobstate fence, and leave the final PS
entries + dense state BIT-IDENTICAL to a clean run that simply skipped
the poisoned step — rollback is exact, not approximate.
"""

import os
import time

import numpy as np
import pytest

from persia_tpu.config import EmbeddingConfig, SlotConfig
from persia_tpu.data import (
    IDTypeFeature,
    Label,
    NonIDTypeFeature,
    PersiaBatch,
)
from persia_tpu.embedding.hashing import add_index_prefix
from persia_tpu.embedding.optim import Adagrad, Adam
from persia_tpu.embedding.store import EmbeddingStore
from persia_tpu.embedding.worker import EmbeddingWorker
from persia_tpu.health import (
    BatchValidator,
    Quarantine,
    SentinelAbort,
    SentinelConfig,
    SentinelRollback,
    StreamSentinel,
    ValidatorConfig,
    run_guarded_stream,
    scrub_journal_id,
    scrub_router,
    scrub_store,
    sentinel_drain,
    sentinel_note,
)

VOCABS = (64, 32)


def _cfg():
    return EmbeddingConfig(
        slots_config={"cat_0": SlotConfig(dim=8), "cat_1": SlotConfig(dim=8)},
        feature_index_prefix_bit=8,
    )


def _stores(n=2, seed=7):
    return [
        EmbeddingStore(capacity=1 << 16, num_internal_shards=4, seed=seed)
        for _ in range(n)
    ]


def _ps_entries(cfg, stores):
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


def _assert_entries_equal(a, b):
    assert set(a) == set(b)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=str(k))


def _assert_params_equal(pa, pb):
    import jax

    for (kp, x), (_, y) in zip(
        jax.tree_util.tree_leaves_with_path(pa),
        jax.tree_util.tree_leaves_with_path(pb),
    ):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y), err_msg=str(kp))


def _batch(seed=0, rows=4, nan_dense=False, bad_label=None, bad_sign=False):
    rng = np.random.default_rng(seed)
    ids = IDTypeFeature.from_flat(
        "cat_0",
        rng.integers(0, 1 << 40, rows, dtype=np.uint64),
        np.ones(rows, np.int64),
    )
    if bad_sign:
        flat, counts = ids.flat_counts()
        flat = flat.copy()
        flat[0] |= np.uint64(1) << np.uint64(63)
        ids = IDTypeFeature.from_flat("cat_0", flat, counts)
    dense = rng.normal(size=(rows, 3)).astype(np.float32)
    if nan_dense:
        dense[0, 0] = np.nan
    labels = rng.integers(0, 2, (rows, 1)).astype(np.float32)
    if bad_label is not None:
        labels[0, 0] = bad_label
    return PersiaBatch(
        [ids], [NonIDTypeFeature(dense, name="d")],
        [Label(labels, name="y")], requires_grad=True,
    )


# ---------------------------------------------------- validator/quarantine


def test_validator_rules_fire_and_clean_batch_admits(tmp_path):
    v = BatchValidator(
        ValidatorConfig(sign_prefix_bit=8),
        Quarantine(str(tmp_path / "q")),
    )
    assert v.check(_batch()) is None
    assert v.check(_batch(nan_dense=True))[0] == "nonfinite"
    assert v.check(_batch(bad_label=7.0))[0] == "label_range"
    assert v.check(_batch(bad_sign=True))[0] == "sign_domain"
    # requires_grad without labels = schema violation
    ids = IDTypeFeature.from_flat(
        "cat_0", np.arange(2, dtype=np.uint64), np.ones(2, np.int64))
    schema_bad = PersiaBatch([ids], requires_grad=False)
    schema_bad.requires_grad = True  # bypass ctor guard: simulates decode bug
    assert v.check(schema_bad)[0] == "schema"


def test_quarantine_roundtrip_and_rejected_never_admitted(tmp_path):
    q = Quarantine(str(tmp_path / "q"))
    v = BatchValidator(ValidatorConfig(sign_prefix_bit=8), q)
    batches = [_batch(seed=i) for i in range(4)]
    batches[2] = _batch(seed=2, nan_dense=True)
    admitted = list(v.wrap(batches))
    assert len(admitted) == 3
    assert len(q) == 1
    assert v.rejected_by_rule == {"nonfinite": 1}
    name = q.names()[0]
    back, sidecar = q.load(name)
    # the poisoned payload survives byte-exact for offline triage
    np.testing.assert_array_equal(
        back.non_id_type_features[0].data,
        batches[2].non_id_type_features[0].data,
    )
    assert sidecar["rule"] == "nonfinite"
    assert sidecar["step"] == 2
    assert "trace_id" in sidecar


def test_data_loader_feed_quarantines(tmp_path):
    """The DataLoader feed stage drops rejected batches before they get a
    batch_id, so survivors stay contiguous."""
    from persia_tpu.data_loader import DataLoader

    class _NullCtx:
        worker = None

    q = Quarantine(str(tmp_path / "q"))
    v = BatchValidator(ValidatorConfig(sign_prefix_bit=8), q)
    dl = DataLoader.__new__(DataLoader)  # feed stage only: no pipeline
    dl.dataset = [
        _batch(0), _batch(1, nan_dense=True), _batch(2),
    ]
    dl.validator = v
    import queue

    out = queue.Queue()
    dl._feed(out)
    ids = []
    while True:
        item = out.get(timeout=60)
        if not isinstance(item, PersiaBatch):
            break
        ids.append(item.batch_id)
    assert ids == [0, 1]  # contiguous despite the quarantined middle batch
    assert len(q) == 1


# ------------------------------------------------------------ probe decode


def test_probe_tail_decode_roundtrip():
    from persia_tpu.parallel.train_step import probe_tail_len, unpack_step_probe

    n_labels, n_groups = 4, 2
    tail = np.array([1.5, 2.0, 3.0, 0.5, 1.0, 0.0], np.float32)
    assert probe_tail_len(n_groups) == len(tail)
    header = np.concatenate([
        np.array([0.7], np.float32), np.zeros(n_labels, np.float32), tail,
    ])
    p = unpack_step_probe(header, n_labels, n_groups)
    assert p["dense_gnorm"] == pytest.approx(1.5)
    assert list(p["group_gnorms"]) == [pytest.approx(2.0), pytest.approx(3.0)]
    assert p["ps_gnorm"] == pytest.approx(0.5)
    assert p["total_gnorm"] == pytest.approx(
        np.sqrt(1.5 ** 2 + 2.0 ** 2 + 3.0 ** 2 + 0.5 ** 2))
    assert p["finite"] == 1.0 and p["clipped"] == 0.0
    with pytest.raises(ValueError):
        unpack_step_probe(header[:-1], n_labels, n_groups)


def _probe_header(gnorm, finite=1.0, clipped=0.0, n_labels=1):
    return np.array(
        [0.5] + [0.0] * n_labels + [gnorm, 0.0, float(finite), float(clipped)],
        np.float32,
    )


# -------------------------------------------------------- sentinel ladder


def test_sentinel_detects_within_one_dispatch_window():
    s = StreamSentinel(SentinelConfig(z_threshold=4.0, warmup_steps=3))
    pending = []
    for g in range(4):
        sentinel_note(s, pending, g, _probe_header(1.0), 1)
    # the newest dispatch is never materialized: detection trails by <= 1
    assert s.stats["observed"] == 3 and len(pending) == 1
    with pytest.raises(SentinelRollback) as ei:
        # poisoned step 4 queues; digested the moment step 5 dispatches
        sentinel_note(s, pending, 4, _probe_header(100.0), 1)
        sentinel_note(s, pending, 5, _probe_header(1.0), 1)
    assert ei.value.step == 4


def test_sentinel_replay_dedupe_and_rungs():
    s = StreamSentinel(SentinelConfig(z_threshold=4.0, warmup_steps=2))
    for g in range(4):
        s.observe(g, _probe_header(1.0), 1)
    # rung 1: device already skipped — counted, EMA untouched
    s.observe(4, _probe_header(0.0, finite=0.0), 1)
    assert s.stats["nonfinite_skips"] == 1
    # rung 2: clipped on device — counted, still folded
    s.observe(5, _probe_header(1.1, clipped=1.0), 1)
    assert s.stats["clips"] == 1
    # replayed history is counted but never re-folded / re-tripped
    s.observe(3, _probe_header(100.0), 1)
    assert s.stats["replayed"] == 1 and s.stats["z_anomalies"] == 0
    with pytest.raises(SentinelRollback):
        s.observe(6, _probe_header(100.0), 1)
    assert s.stats["z_anomalies"] == 1


def test_sentinel_abort_paths():
    # anomaly-fraction abort
    s = StreamSentinel(SentinelConfig(
        z_threshold=1e9, warmup_steps=1000,
        max_anomaly_frac=0.3, min_anomaly_steps=4,
    ))
    with pytest.raises(SentinelAbort):
        for g in range(10):
            s.observe(g, _probe_header(0.0, finite=0.0), 1)
    # rollback-budget abort
    s2 = StreamSentinel(SentinelConfig(max_rollbacks=1))
    s2.note_rollback(5, 4)
    with pytest.raises(SentinelAbort):
        s2.note_rollback(9, 8)


def test_disabled_sentinel_noop_overhead():
    """Sentinel off = one ``is None`` check per step on the stream hot
    path (same contract as the disabled tracer, tests/test_telemetry.py)."""
    pending = []
    header = _probe_header(1.0)
    n = 200_000
    t0 = time.perf_counter()
    for g in range(n):
        sentinel_note(None, pending, g, header, 1)
    sentinel_drain(None, pending)
    per_us = (time.perf_counter() - t0) / n * 1e6
    assert pending == []
    assert per_us < 25.0, f"disabled sentinel_note costs {per_us:.2f}us"


# ------------------------------------------------------------- PS scrubber


def _poison_store(store, signs):
    # poison through set_embedding with the FULL [emb | state] row — the
    # native store hands out entry copies, in-place writes would be lost
    for i, sign in enumerate(signs):
        sign = int(sign)
        entry = store.get_embedding_entry(sign).copy()
        entry[0] = np.nan if i % 2 else np.inf
        store.set_embedding(
            np.array([sign], np.uint64), entry[None, :],
            store.get_entry_dim(sign),
        )


def test_scrub_repairs_to_seeded_init_exactly_once():
    opt = Adam(lr=1e-3).config
    store = EmbeddingStore(capacity=2048, num_internal_shards=4, seed=9,
                           optimizer=opt)
    fresh = EmbeddingStore(capacity=2048, num_internal_shards=4, seed=9,
                           optimizer=opt)
    signs = np.arange(1, 17, dtype=np.uint64)
    store.lookup(signs, 8, train=True)
    _poison_store(store, [3, 8, 12])
    jid = scrub_journal_id(0, 40, 0)
    res = scrub_store(store, journal_id=jid)
    assert res["repaired"] == 3 and sorted(res["signs"]) == [3, 8, 12]
    # repaired rows == a fresh same-seed store's rows (degraded contract)
    fresh.lookup(signs, 8, train=True)
    for s in (3, 8, 12):
        np.testing.assert_array_equal(
            store.get_embedding_entry(int(s)),
            fresh.get_embedding_entry(int(s)),
        )
    # retry of the same fence = journaled no-op, even if rows re-poisoned
    _poison_store(store, [5])
    res2 = scrub_store(store, journal_id=jid)
    assert res2["skipped"] and res2["repaired"] == 0
    # a NEW fence id scans again
    res3 = scrub_store(store, journal_id=scrub_journal_id(0, 44, 0))
    assert res3["repaired"] == 1 and list(res3["signs"]) == [5]


def test_scrub_router_fans_out_and_emits(tmp_path):
    stores = _stores()
    stores[0].lookup(np.arange(1, 9, dtype=np.uint64), 8, train=True)
    _poison_store(stores[0], [2, 4])
    worker = EmbeddingWorker(_cfg(), stores)
    res = scrub_router(worker.lookup_router, 0, 8)
    assert res["repaired"] == 2
    assert len(res["replicas"]) == len(stores)
    # journaled per replica: retry is a fleet-wide no-op
    res2 = scrub_router(worker.lookup_router, 0, 8)
    assert res2["repaired"] == 0
    assert all(r["skipped"] for r in res2["replicas"])


def test_native_scan_nonfinite_matches_golden():
    native = pytest.importorskip("persia_tpu.embedding.native_store")
    opt = Adam(lr=1e-3).config
    gold = EmbeddingStore(capacity=2048, num_internal_shards=4, seed=9,
                          optimizer=opt)
    nat = native.NativeEmbeddingStore(capacity=2048, num_internal_shards=4,
                                      seed=9, optimizer=opt)
    signs = np.arange(1, 33, dtype=np.uint64)
    for st in (gold, nat):
        st.lookup(signs, 8, train=True)
        _poison_store(st, [3, 8, 12])
    ng, sg = gold.scan_nonfinite()
    nn, sn = nat.scan_nonfinite()
    assert ng == nn == 3
    assert sorted(sg) == sorted(sn) == [3, 8, 12]
    for s in (3, 8, 12):
        np.testing.assert_array_equal(
            gold.get_embedding_entry(int(s)), nat.get_embedding_entry(int(s)))
    assert gold.scan_nonfinite()[0] == nat.scan_nonfinite()[0] == 0


# -------------------------------------------------- delta packet rejection


def test_incremental_loader_rejects_nonfinite_packet(tmp_path):
    from persia_tpu.incremental import (
        IncrementalLoader, _pack_packet, packet_body_nonfinite,
    )

    dim = 4
    good_vec = np.arange(2 * dim, dtype=np.float32)
    bad_vec = good_vec.copy()
    bad_vec[1] = np.nan
    root = tmp_path / "inc"
    root.mkdir()
    (root / "0_0.inc").write_bytes(
        _pack_packet([(1, dim, good_vec)], 1000, train_step=1, seq=0))
    (root / "0_1.inc").write_bytes(
        _pack_packet([(2, dim, bad_vec)], 2000, train_step=2, seq=1))

    store = EmbeddingStore(capacity=256, num_internal_shards=2, seed=3)
    loader = IncrementalLoader(store, str(root))
    loader.poll_once()
    # the finite packet applied; the poisoned one is refused and HELD
    assert store.get_embedding_entry(1) is not None
    assert store.get_embedding_entry(2) is None
    assert loader.stats["nonfinite_rejected"] >= 1
    assert loader.needs_resync
    # retries exhaust, the stream skips past — damage never applies
    for _ in range(loader.max_bad_retries + 1):
        loader.poll_once()
    assert store.get_embedding_entry(2) is None
    assert packet_body_nonfinite(
        _pack_packet([(2, dim, bad_vec)], 0)[36:]) == 1


def test_incremental_loader_nonfinite_check_can_be_disabled(tmp_path):
    from persia_tpu.incremental import IncrementalLoader, _pack_packet

    dim = 4
    bad_vec = np.full(2 * dim, np.inf, np.float32)
    root = tmp_path / "inc"
    root.mkdir()
    (root / "0_0.inc").write_bytes(
        _pack_packet([(9, dim, bad_vec)], 1000, train_step=1, seq=0))
    store = EmbeddingStore(capacity=256, num_internal_shards=2, seed=3)
    loader = IncrementalLoader(store, str(root), reject_nonfinite=False)
    loader.poll_once()
    assert store.get_embedding_entry(9) is not None  # legacy behavior


# ------------------------------------------------------------- NUM001 lint


FIXDIR = os.path.join(os.path.dirname(__file__), "fixtures", "analysis")


def test_num001_fixture_fires():
    from persia_tpu.analysis import numeric_lint
    from persia_tpu.analysis.common import read_text

    findings = numeric_lint.check_source(
        read_text(os.path.join(FIXDIR, "num_unguarded_scalar.py")),
        "num_unguarded_scalar.py",
    )
    assert len(findings) == 3
    assert {f.rule for f in findings} == {"NUM001"}


def test_num001_guarded_fixture_clean():
    from persia_tpu.analysis import numeric_lint
    from persia_tpu.analysis.common import read_text

    assert numeric_lint.check_source(
        read_text(os.path.join(FIXDIR, "num_guarded_clean.py")),
        "num_guarded_clean.py",
    ) == []


def test_num001_repo_tree_clean():
    from persia_tpu.analysis import run_all

    findings, _cov = run_all(rules=["NUM"])
    assert findings == [], [f.format() for f in findings]


# -------------------------------------------------------- data-plane chaos


def test_data_plane_chaos_deterministic_and_copy_safe():
    from persia_tpu.chaos import DataPlaneChaos, DataPlaneChaosConfig

    cfg = DataPlaneChaosConfig(seed=7, nan_prob=0.1, label_flip_prob=0.1,
                               sign_corrupt_prob=0.1, spike_prob=0.1)
    runs = []
    for _ in range(2):
        c = DataPlaneChaos(cfg)
        out = list(c.wrap(_batch(seed=i) for i in range(40)))
        runs.append((c.counts, out))
    assert runs[0][0] == runs[1][0]
    assert sum(v for k, v in runs[0][0].items() if k != "batches") > 0
    for b1, b2 in zip(runs[0][1], runs[1][1]):
        np.testing.assert_array_equal(
            b1.non_id_type_features[0].data, b2.non_id_type_features[0].data)
        np.testing.assert_array_equal(b1.labels[0].data, b2.labels[0].data)
    # poisoning copies: the source batch stays clean
    src = _batch(0)
    c = DataPlaneChaos(DataPlaneChaosConfig(seed=0, nan_prob=1.0))
    [pois] = list(c.wrap([src]))
    assert np.isfinite(src.non_id_type_features[0].data).all()
    assert not np.isfinite(pois.non_id_type_features[0].data).all()


def test_data_chaos_spec_parse():
    from persia_tpu.chaos import parse_data_chaos_spec

    cfg = parse_data_chaos_spec("seed=3,nan=0.01,label_flip=0.02,spike=0.5")
    assert cfg.seed == 3 and cfg.nan_prob == 0.01
    assert cfg.label_flip_prob == 0.02 and cfg.spike_prob == 0.5
    with pytest.raises(ValueError):
        parse_data_chaos_spec("bogus=1")


# --------------------------------------------------------------- flagship


def _spike(batch, scale):
    # corrupted labels: finite, schema-valid, and invisible to the dense
    # path's per-batch normalization — exactly the poison only the grad
    # z-score can catch (a dense-feature scale spike is erased by the
    # model's BatchNorm before it ever reaches a gradient)
    labels = [
        Label(f.data * np.float32(scale), name=f.name)
        for f in batch.labels
    ]
    return PersiaBatch(batch.id_type_features, batch.non_id_type_features,
                       labels, requires_grad=batch.requires_grad,
                       batch_id=batch.batch_id)


def _make_cached_ctx(cfg, stores):
    import optax

    from persia_tpu.embedding import hbm_cache as hbm
    from persia_tpu.models import DNN

    return hbm.CachedTrainCtx(
        model=DNN(dense_mlp_size=8, sparse_mlp_size=16, hidden_sizes=(32,)),
        dense_optimizer=optax.adam(3e-3),
        embedding_optimizer=Adagrad(lr=0.1),
        worker=EmbeddingWorker(cfg, stores), embedding_config=cfg,
        cache_rows=256, init_seed=7, health_probe=True,
    ).__enter__()


def test_poisoned_stream_rollback_bit_parity(tmp_path):
    """A finite gradient spike at step 6 must be caught by the host
    z-score within one dispatch window, roll the stream back to the
    LAST_GOOD fence (step 4), replay minus the quarantined step, and land
    BIT-IDENTICAL — PS entries and dense params — to a clean run that
    skipped step 6 from the start."""
    from persia_tpu.testing import SyntheticClickDataset

    cfg = _cfg()
    STEPS, K, POISON = 12, 4, 6
    clean = list(
        SyntheticClickDataset(num_samples=STEPS * 32, vocab_sizes=VOCABS,
                              seed=9).batches(32)
    )[:STEPS]
    poisoned = list(clean)
    poisoned[POISON] = _spike(clean[POISON], 50.0)

    # --- run A: poisoned stream under guard ---------------------------
    stores_a = _stores()
    spec_ctx = _make_cached_ctx(cfg, _stores())  # throwaway: probe shape
    sentinel = StreamSentinel.from_ctx(
        spec_ctx,
        SentinelConfig(z_threshold=4.0, warmup_steps=4, decay=0.9),
    )
    metrics, ctx_a, skipped = run_guarded_stream(
        lambda: _make_cached_ctx(cfg, stores_a),
        lambda start: poisoned[start:],
        str(tmp_path / "a"),
        sentinel,
        snapshot_every=K,
    )
    assert skipped == {POISON}
    assert sentinel.stats["rollbacks"] == 1
    assert sentinel.stats["z_anomalies"] == 1
    # detection within one dispatch window: the anomaly at 6 tripped while
    # step 7 was the newest dispatch, so the replay from fence 4 re-sees
    # exactly {4, 5} (deduped) — a later detection would replay more
    assert sentinel.stats["replayed"] == 2
    ctx_a.flush()

    # --- run B: clean stream, poisoned step skipped from the start ----
    stores_b = _stores()
    ctx_b = _make_cached_ctx(cfg, stores_b)
    ctx_b.train_stream(
        clean, snapshot_every=K, job_state=str(tmp_path / "b"),
        skip_steps={POISON},
    )
    ctx_b.flush()
    assert ctx_b.stream_stats()["quarantine_skips"] == 1

    # --- bit parity ---------------------------------------------------
    _assert_params_equal(ctx_a.state.params, ctx_b.state.params)
    _assert_entries_equal(
        _ps_entries(cfg, stores_a), _ps_entries(cfg, stores_b))


def test_on_device_nonfinite_skip_rung(tmp_path):
    """A NaN batch under the armed probe is skipped ON DEVICE (finite
    gate): the sentinel counts it, the stream survives, and the final
    state is unpoisoned (all-finite)."""
    from persia_tpu.testing import SyntheticClickDataset

    cfg = _cfg()
    batches = list(
        SyntheticClickDataset(num_samples=6 * 32, vocab_sizes=VOCABS,
                              seed=11).batches(32)
    )[:6]
    dense = batches[3].non_id_type_features[0]
    bad = dense.data.copy()
    bad[0, 0] = np.nan
    batches[3] = PersiaBatch(
        batches[3].id_type_features,
        [NonIDTypeFeature(bad, name=dense.name)],
        batches[3].labels, requires_grad=True,
    )
    stores = _stores()
    ctx = _make_cached_ctx(cfg, stores)
    sentinel = StreamSentinel.from_ctx(
        ctx, SentinelConfig(z_threshold=1e9, warmup_steps=1000))
    ctx.train_stream(batches, sentinel=sentinel)
    ctx.flush()
    assert sentinel.stats["nonfinite_skips"] == 1
    import jax

    for leaf in jax.tree_util.tree_leaves(ctx.state.params):
        assert np.isfinite(np.asarray(leaf)).all()
    for e in _ps_entries(cfg, stores).values():
        assert np.isfinite(e).all()
