"""Seeded multi-thread stress harness for the mutex-protected native cores.

The bit-parity suites drive every extern "C" entry point single-threaded;
the production stream does not: cache_feed_batch probes the hazard ledger
on the feeder thread while the write-back thread removes landed entries,
sketch_observe runs on the feeder while decay/stats/export run at fences,
and the PS shards take concurrent update/lookup/scrub/journal traffic from
RPC worker threads. A race there is a *silent quality* bug (PAPER.md's
async-update argument cuts both ways), so this harness exists to give
ThreadSanitizer real interleavings to judge:

    bash scripts/race_native.sh          # TSan variant .so's + this file

Under ``PERSIA_NATIVE_SANITIZE=tsan`` (libtsan preloaded by the script,
``TSAN_OPTIONS=halt_on_error=1``) the FIRST data race aborts the test
process — suite green means zero reports. Without the variant it still
runs in tier-1 as a functional concurrency smoke: every invariant below
must hold under 8-thread hammering either way.

Deliberately jax-free: the harness binds ctypes directly over
``_native_build.build_so`` so the TSan run instruments only the native
cores plus the interpreter's own pthread traffic — no flax/jax import
noise, and the whole file stays fast enough for every preflight.

Thread-discipline note: the Cache directory itself is single-writer by
contract (only the feeder thread calls cache_feed_batch); the harness
honors that and hammers the SHARED structures (PendingMap, AccessSketch,
PS shards, journal ring) from the sibling threads, exactly like the
production thread plane.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from persia_tpu.embedding import _native_build

logger = logging.getLogger("test_race_stress")

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_NATIVE = os.path.join(_REPO, "native")

# per-call batch sizes are deliberately large: on a small host the GIL is
# released for the whole ctypes call, and long native sections are what
# make the 8 threads actually overlap inside the mutexes under test
N_THREADS = 8
ITERS = int(os.environ.get("RACE_STRESS_ITERS", "40"))
BATCH = int(os.environ.get("RACE_STRESS_BATCH", "4096"))
SEED = int(os.environ.get("RACE_STRESS_SEED", "1234"))

_i64 = ctypes.c_int64
_u64 = ctypes.c_uint64
_u32 = ctypes.c_uint32
_i32 = ctypes.c_int32
_p = ctypes.c_void_p
_i64p = ctypes.POINTER(_i64)
_u64p = ctypes.POINTER(_u64)
_u32p = ctypes.POINTER(_u32)
_i32p = ctypes.POINTER(_i32)
_f32p = ctypes.POINTER(ctypes.c_float)
_f64p = ctypes.POINTER(ctypes.c_double)
_u8p = ctypes.POINTER(ctypes.c_uint8)


def _build(src: str, so: str, extra=()) -> str:
    # same base flag vector as the owning binding modules; build_so appends
    # the PERSIA_NATIVE_SANITIZE variant flags and returns the variant path
    flags = ["-O3", "-std=c++17", "-fPIC", "-shared", "-Wall", *extra]
    return _native_build.build_so(
        os.path.join(_NATIVE, src), os.path.join(_NATIVE, so), flags, logger
    )


def _sig(lib, name, restype, argtypes):
    fn = getattr(lib, name)
    fn.restype = restype
    fn.argtypes = argtypes
    return fn


@pytest.fixture(scope="module")
def cache_lib():
    lib = ctypes.CDLL(_build("cache.cpp", "libpersia_cache.so"))
    _sig(lib, "cache_create", _p, [_i64])
    _sig(lib, "cache_destroy", None, [_p])
    _sig(lib, "cache_len", _i64, [_p])
    _sig(lib, "cache_feed_batch", _i64, [
        _p, _p, _u64p, _i64, _i32p, _u64p, _i64p, _u64p, _i64p,
        _i64p, _i64p, _i64p, _i64p, _i64p, _u64,
    ])
    _sig(lib, "pending_map_create", _p, [])
    _sig(lib, "pending_map_destroy", None, [_p])
    _sig(lib, "pending_map_size", _i64, [_p])
    _sig(lib, "pending_map_insert", None, [_p, _u64p, _i64p, _i64, _u32])
    _sig(lib, "pending_map_insert_range", None, [_p, _u64p, _i64, _i64, _u32])
    _sig(lib, "pending_map_query", _i64, [_p, _u64p, _i64, _u32p, _i64p])
    _sig(lib, "pending_map_remove", None, [_p, _u64p, _i64, _u32])
    _sig(lib, "sketch_create", _p, [_i64, _i64, _i64, _i64, _i64])
    _sig(lib, "sketch_destroy", None, [_p])
    _sig(lib, "sketch_observe", _i64, [_p, _u64p, _i64, _i64, _i64])
    _sig(lib, "sketch_decay", None, [_p, ctypes.c_double])
    _sig(lib, "sketch_slot_stats", _i64, [_p, _i64, _f64p])
    _sig(lib, "sketch_export_size", _i64, [_p])
    _sig(lib, "sketch_export", _i64, [_p, _u8p, _i64])
    _sig(lib, "sketch_import", _i64, [_p, _u8p, _i64])
    _sig(lib, "sketch_set_sample", None, [_p, _i64])
    # round 14: the sharded feeder surface
    _sig(lib, "cache_create_sharded", _p, [_i64, _i64, _u64, _i64])
    _sig(lib, "cache_sharded_destroy", None, [_p])
    _sig(lib, "cache_sharded_len", _i64, [_p])
    _sig(lib, "cache_sharded_threads", _i64, [_p])
    _sig(lib, "cache_sharded_set_threads", None, [_p, _i64])
    _sig(lib, "cache_sharded_probe", None, [_p, _u64p, _i64, _i64p])
    _sig(lib, "cache_sharded_shard_sizes", None, [_p, _i64p])
    _sig(lib, "cache_sharded_shard_busy_ns", None, [_p, _i64p])
    # round 17: SIMD probe layout + affinity/stall surfaces
    _sig(lib, "cache_sharded_shard_stall_ns", None, [_p, _i64p])
    _sig(lib, "cache_sharded_set_probe_mode", None, [_p, _i64])
    _sig(lib, "cache_sharded_probe_mode", _i64, [_p])
    _sig(lib, "cache_sharded_set_affinity", None, [_p, _i64])
    _sig(lib, "cache_sharded_affinity", _i64, [_p])
    _sig(lib, "cache_sharded_drain", _i64, [_p, _u64p, _i64p])
    _sig(lib, "cache_feed_batch_sharded", _i64, [
        _p, _p, _u64p, _i64, _i32p, _u64p, _i64p, _u64p, _i64p,
        _i64p, _i64p, _i64p, _i64p, _i64p, _u64,
        ctypes.POINTER(_p), _i64, _i64, _i64,
    ])
    return lib


@pytest.fixture(scope="module")
def ps_lib():
    lib = ctypes.CDLL(_build(
        "ps.cpp", "libpersia_ps.so", extra=["-mavx2", "-mfma"]
    ))
    _sig(lib, "ps_create", _p, [_u64, _u32, _u64])
    _sig(lib, "ps_destroy", None, [_p])
    _sig(lib, "ps_configure", None, [
        _p, ctypes.c_double, ctypes.c_double, ctypes.c_double, ctypes.c_float,
    ])
    _sig(lib, "ps_register_optimizer", None, [
        _p, ctypes.c_int, ctypes.c_float, ctypes.c_float, ctypes.c_float,
        ctypes.c_float, ctypes.c_float, ctypes.c_int, ctypes.c_float,
        ctypes.c_float,
    ])
    _sig(lib, "ps_lookup", None, [_p, _u64p, _i64, _u32, ctypes.c_int, _f32p])
    _sig(lib, "ps_update_gradients", ctypes.c_int,
         [_p, _u64p, _i64, _u32, _f32p, ctypes.c_int])
    _sig(lib, "ps_advance_batch_state", None, [_p, ctypes.c_int])
    _sig(lib, "ps_size", _i64, [_p])
    _sig(lib, "ps_journal_record", None, [_p, _u64, _u32])
    _sig(lib, "ps_journal_probe", _i32, [_p, _u64, _u32])
    _sig(lib, "ps_journal_len", _i64, [_p])
    _sig(lib, "ps_journal_clear", None, [_p])
    _sig(lib, "ps_scan_nonfinite", _i64, [_p, _u64p, _i64])
    _sig(lib, "ps_dump_shard_size", _i64, [_p, _u32])
    _sig(lib, "ps_dump_shard", _i64, [_p, _u32, _u8p, _i64])
    return lib


def _u64arr(a):
    return np.ascontiguousarray(a, dtype=np.uint64)


def _run_threads(workers):
    """Start all workers behind a barrier, join, re-raise the first error
    (an assertion inside a thread must fail the TEST, not vanish)."""
    barrier = threading.Barrier(len(workers))
    errors = []

    def wrap(fn):
        def run():
            try:
                barrier.wait(timeout=60)
                fn()
            except BaseException as e:  # noqa: BLE001 - reported below
                errors.append(e)
        return run

    threads = [threading.Thread(target=wrap(fn), daemon=True) for fn in workers]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
        assert not t.is_alive(), "stress worker wedged (deadlock?)"
    if errors:
        raise errors[0]


# --------------------------------------------------- feeder vs write-back


def test_feed_batch_vs_writeback_hammers_pending_map(cache_lib):
    """The production discipline, concentrated: ONE feeder thread runs the
    fused admit (cache_feed_batch probes the ledger under the PendingMap
    mutex) and records each step's eviction span, while 7 write-back
    threads concurrently flush (token-conditional remove), re-probe
    (query), and watch occupancy (size). TSan judges the PendingMap mutex;
    the functional assertions pin the token-conditional remove contract."""
    lib = cache_lib
    cap = 1 << 12
    cache = lib.cache_create(cap)
    pending = lib.pending_map_create()
    assert cache and pending
    salt = 0x9E3779B97F4A7C15
    stop = threading.Event()
    spans = []  # (signs, token) published by the feeder, flushed by wb
    spans_lock = threading.Lock()

    def feeder():
        rng = np.random.default_rng(SEED)
        rows = np.empty(BATCH, np.int32)
        miss_s = np.empty(BATCH, np.uint64)
        miss_r = np.empty(BATCH, np.int64)
        ev_s = np.empty(cap, np.uint64)
        ev_r = np.empty(cap, np.int64)
        rest_src = np.empty(BATCH, np.int64)
        rest_pos = np.empty(BATCH, np.int64)
        n_unique = _i64(0)
        n_evict = _i64(0)
        n_restore = _i64(0)
        try:
            for it in range(ITERS * 4):
                # zipf-ish skew: a hot head plus a moving cold tail forces
                # steady eviction traffic (the ledger is never quiet)
                hot = rng.integers(0, 512, BATCH // 2, dtype=np.uint64)
                cold = rng.integers(it * 64, it * 64 + (1 << 14),
                                    BATCH // 2, dtype=np.uint64)
                signs = _u64arr(np.concatenate([hot, cold]))
                n_miss = lib.cache_feed_batch(
                    cache, pending, signs.ctypes.data_as(_u64p), BATCH,
                    rows.ctypes.data_as(_i32p),
                    miss_s.ctypes.data_as(_u64p), miss_r.ctypes.data_as(_i64p),
                    ev_s.ctypes.data_as(_u64p), ev_r.ctypes.data_as(_i64p),
                    ctypes.byref(n_unique), ctypes.byref(n_evict),
                    rest_src.ctypes.data_as(_i64p),
                    rest_pos.ctypes.data_as(_i64p),
                    ctypes.byref(n_restore), _u64(salt),
                )
                assert 0 <= n_miss <= BATCH
                assert 0 <= n_restore.value <= n_miss
                ne = n_evict.value
                if ne:
                    evicted = _u64arr(ev_s[:ne] ^ np.uint64(salt))
                    token = _u32(it & 0xFFFFFFFF)
                    lib.pending_map_insert_range(
                        pending, evicted.ctypes.data_as(_u64p), ne,
                        it * cap, token,
                    )
                    with spans_lock:
                        spans.append((evicted, token))
        finally:
            stop.set()

    def writeback(tid):
        def run():
            rng = np.random.default_rng(SEED + 100 + tid)
            tokens = np.empty(BATCH, np.uint32)
            srcs = np.empty(BATCH, np.int64)
            while not stop.is_set() or spans:
                with spans_lock:
                    span = spans.pop() if spans else None
                if span is None:
                    probe = _u64arr(rng.integers(0, 1 << 14, 64, dtype=np.uint64))
                    lib.pending_map_query(
                        pending, probe.ctypes.data_as(_u64p), 64,
                        tokens.ctypes.data_as(_u32p),
                        srcs.ctypes.data_as(_i64p),
                    )
                    continue
                signs, token = span
                n = len(signs)
                hits = lib.pending_map_query(
                    pending, signs.ctypes.data_as(_u64p), n,
                    tokens.ctypes.data_as(_u32p), srcs.ctypes.data_as(_i64p),
                )
                assert 0 <= hits <= n
                # flush: remove is token-conditional, so a sign re-evicted
                # under a newer token must survive this older flush
                lib.pending_map_remove(
                    pending, signs.ctypes.data_as(_u64p), n, token
                )
                assert lib.pending_map_size(pending) >= 0
        return run

    _run_threads([feeder] + [writeback(t) for t in range(N_THREADS - 1)])
    # every span flushed; survivors can only be signs re-evicted under a
    # NEWER token whose span a wb thread already popped (remove skipped
    # them by design) — bounded by the map's own accounting, never negative
    assert lib.pending_map_size(pending) >= 0
    assert lib.cache_len(cache) <= cap
    lib.pending_map_destroy(pending)
    lib.cache_destroy(cache)


# ------------------------------------------------ sketch observe vs fence


def test_sketch_observe_vs_decay_stats_export(cache_lib):
    """Feeder-plane sketch_observe from 5 threads against concurrent
    fence-plane decay/slot_stats and export/import snapshots. The sketch
    holds ONE mutex over count-min + totals + window bitmaps + top-K; a
    forgotten guard on any of the five estimator arrays is exactly what
    TSan sees here."""
    lib = cache_lib
    n_slots = 16
    sk = lib.sketch_create(n_slots, 12, 4, 2048, 8)
    sk2 = lib.sketch_create(n_slots, 12, 4, 2048, 8)
    assert sk and sk2
    stop = threading.Event()

    def observer(tid):
        def run():
            rng = np.random.default_rng(SEED + tid)
            base = tid % n_slots
            for _ in range(ITERS * 6):
                signs = _u64arr(rng.zipf(1.3, BATCH).astype(np.uint64))
                seen = lib.sketch_observe(
                    sk, signs.ctypes.data_as(_u64p), BATCH, BATCH // 4, base
                )
                assert 0 <= seen <= BATCH
        return run

    def fencer():
        out = np.empty(4, np.float64)
        while not stop.is_set():
            lib.sketch_decay(sk, 0.5)
            for slot in range(n_slots):
                rc = lib.sketch_slot_stats(
                    sk, slot, out.ctypes.data_as(_f64p)
                )
                assert rc == 0 and out[0] >= 0.0
            assert lib.sketch_slot_stats(sk, n_slots, out.ctypes.data_as(_f64p)) == -1

    def exporter():
        while not stop.is_set():
            size = lib.sketch_export_size(sk)
            assert size > 0
            buf = np.empty(size, np.uint8)
            n = lib.sketch_export(sk, buf.ctypes.data_as(_u8p), size)
            # a concurrent decay cannot tear the blob: export holds the
            # sketch mutex for the whole copy
            assert n == size
            assert lib.sketch_import(sk2, buf.ctypes.data_as(_u8p), n) == 0

    observers = [observer(t) for t in range(5)]
    # observers drive the duration; fencer/exporter spin until they finish
    obs_done = threading.Barrier(5 + 1)

    def obs_group(fn):
        def run():
            try:
                fn()
            finally:
                obs_done.wait(timeout=120)
        return run

    def closer():
        try:
            obs_done.wait(timeout=120)
        finally:  # a broken barrier must still end the fencer and exporter
            stop.set()

    _run_threads(
        [obs_group(o) for o in observers] + [closer, fencer, exporter]
    )
    lib.sketch_destroy(sk)
    lib.sketch_destroy(sk2)


# ------------------------------------------------------- ps journal ring


def test_ps_journal_concurrent_record_probe(ps_lib):
    """8 threads record/probe/len over overlapping id ranges. The journal
    is a bounded FIFO ring under its own mutex; the contract under
    concurrency: probe returns 1 only for a (id, crc) pair actually
    recorded, -1 only for a recorded id with a different payload, and the
    ring never wedges or miscounts."""
    lib = ps_lib
    store = lib.ps_create(1 << 12, 4, SEED)
    assert store

    def worker(tid):
        def run():
            rng = np.random.default_rng(SEED + tid)
            for it in range(ITERS * 30):
                jid = int(rng.integers(0, 512))
                crc = (jid * 2654435761) & 0xFFFFFFFF
                op = it % 3
                if op == 0:
                    lib.ps_journal_record(store, _u64(jid), _u32(crc))
                elif op == 1:
                    rc = lib.ps_journal_probe(store, _u64(jid), _u32(crc))
                    assert rc in (0, 1)
                else:
                    # same id, different payload: skip-with-warning signal
                    rc = lib.ps_journal_probe(store, _u64(jid), _u32(crc ^ 1))
                    assert rc in (0, -1)
                assert lib.ps_journal_len(store) >= 0
        return run

    _run_threads([worker(t) for t in range(N_THREADS)])
    # a recorded id survives (single-threaded tail): the ring still works
    lib.ps_journal_clear(store)
    assert lib.ps_journal_len(store) == 0
    lib.ps_journal_record(store, _u64(7), _u32(9))
    assert lib.ps_journal_probe(store, _u64(7), _u32(9)) == 1
    lib.ps_destroy(store)


# --------------------------------------- ps update / lookup / scrub plane


def test_ps_update_lookup_scrub_concurrent(ps_lib):
    """The RPC-worker view of one PS replica: concurrent training lookups
    (admit + LRU touch), gradient updates, inference lookups, fence-plane
    nonfinite scrubs, and shard dumps, all on overlapping sign sets.
    Per-shard mutexes + batch_mu + journal_mu are the claim under test;
    functionally, no lookup may ever return a non-finite float (we inject
    none, and the scrubber repairs-to-init rather than zeroing)."""
    lib = ps_lib
    dim = 8
    store = lib.ps_create(1 << 12, 4, SEED)
    assert store
    lib.ps_configure(store, -0.01, 0.01, 1.0, 10.0)
    # SGD keeps entry_len == dim: every thread agrees on row width
    lib.ps_register_optimizer(store, 0, 0.05, 0.0, 0.01, 0.95, 1e-8, 0, 0.9, 0.999)
    n = 256

    def trainer(tid):
        def run():
            rng = np.random.default_rng(SEED + tid)
            out = np.empty((n, dim), np.float32)
            for _ in range(ITERS * 4):
                signs = _u64arr(rng.integers(0, 2048, n, dtype=np.uint64))
                lib.ps_lookup(store, signs.ctypes.data_as(_u64p), n, dim, 1,
                              out.ctypes.data_as(_f32p))
                assert np.isfinite(out).all()
                g = rng.normal(0, 0.1, (n, dim)).astype(np.float32)
                lib.ps_advance_batch_state(store, 0)
                rc = lib.ps_update_gradients(
                    store, signs.ctypes.data_as(_u64p), n, dim,
                    g.ctypes.data_as(_f32p), 0,
                )
                assert rc == 0
        return run

    def reader(tid):
        def run():
            rng = np.random.default_rng(SEED + 50 + tid)
            out = np.empty((n, dim), np.float32)
            for _ in range(ITERS * 6):
                signs = _u64arr(rng.integers(0, 4096, n, dtype=np.uint64))
                lib.ps_lookup(store, signs.ctypes.data_as(_u64p), n, dim, 0,
                              out.ctypes.data_as(_f32p))
                assert np.isfinite(out).all()
                assert 0 <= lib.ps_size(store) <= (1 << 12)
        return run

    def scrubber():
        repaired_signs = np.empty(64, np.uint64)
        for _ in range(ITERS * 2):
            repaired = lib.ps_scan_nonfinite(
                store, repaired_signs.ctypes.data_as(_u64p), 64
            )
            assert repaired == 0  # nothing non-finite was ever written

    def dumper():
        for _ in range(ITERS):
            for shard in range(4):
                size = lib.ps_dump_shard_size(store, _u32(shard))
                assert size >= 4
                buf = np.empty(size, np.uint8)
                got = lib.ps_dump_shard(
                    store, _u32(shard), buf.ctypes.data_as(_u8p), size
                )
                # entries admitted after the size call don't fit — a short
                # read is the documented retry signal, never a tear
                assert got == -1 or got <= size

    _run_threads(
        [trainer(t) for t in range(3)] + [reader(t) for t in range(3)]
        + [scrubber, dumper]
    )
    lib.ps_destroy(store)


# ------------------------------------------------------------ TSan canary


_RACY_SRC = """
#include <cstdint>
extern "C" {
static int64_t counter = 0;
void canary_bump(int64_t n) { for (int64_t i = 0; i < n; ++i) counter++; }
int64_t canary_get() { return counter; }
}
"""

_CANARY_DRIVER = """
import ctypes, sys, threading
lib = ctypes.CDLL(sys.argv[1])
lib.canary_bump.restype = None
lib.canary_bump.argtypes = [ctypes.c_int64]
ts = [threading.Thread(target=lib.canary_bump, args=(3_000_000,))
      for _ in range(4)]
[t.start() for t in ts]
[t.join(120) for t in ts]
assert not any(t.is_alive() for t in ts)
print("canary done")
"""


@pytest.mark.skipif(
    os.environ.get("PERSIA_NATIVE_SANITIZE", "").lower() != "tsan",
    reason="TSan canary only meaningful under scripts/race_native.sh",
)
def test_tsan_canary_detects_seeded_race(tmp_path):
    """Zero reports from the suites above is only evidence if the detector
    is alive in THIS configuration (preload + options + variant flags):
    build a deliberately racy library the same way and require TSan to
    kill the subprocess that drives it."""
    src = tmp_path / "canary.cpp"
    src.write_text(_RACY_SRC)
    # -O0 is load-bearing: at -O2 gcc collapses the loop into a single
    # ``counter += n`` (one instrumented load/store per call), the call
    # finishes inside one GIL timeslice, and the GIL mutex hands TSan a
    # happens-before edge that serializes every access — no race visible.
    # Unoptimized, the 3M-iteration loop runs long enough to be preempted
    # mid-call so the threads genuinely overlap.
    so = _native_build.build_so(
        str(src), str(tmp_path / "libcanary.so"),
        ["-O0", "-std=c++17", "-fPIC", "-shared"], logger,
    )
    assert so.endswith(".tsan.so")
    env = dict(os.environ)
    env["TSAN_OPTIONS"] = "halt_on_error=1:abort_on_error=1"
    proc = subprocess.run(
        [sys.executable, "-c", _CANARY_DRIVER, so],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0, (
        "TSan did not fire on a seeded data race — the zero-report claim "
        f"of this run is void. stdout={proc.stdout!r} stderr={proc.stderr!r}"
    )
    assert "ThreadSanitizer" in proc.stderr


# ----------------------------------- round 14: sharded feeder vs the world


def test_sharded_feed_vs_probe_evict_sketch_decay(cache_lib):
    """The round-14 thread plane, concentrated: ONE feeder thread drives
    ``cache_feed_batch_sharded`` (4 shards, its OWN native walker pool,
    the sketch observe FUSED into the walk across 4 sub-sketches, the
    hazard ledger probed under the PendingMap mutex) while sibling threads
    hammer every reader the production stream runs concurrently —
    ``cache_sharded_probe`` + per-shard occupancy/busy gauges (stats
    plane), ledger query/remove (write-back plane), and sub-sketch
    decay/slot_stats/export (fence plane). The feeder also resizes its
    walker pool mid-run (the ``set_feed_threads`` path, legal only from
    the feed caller) — pool teardown/rebuild must be invisible to the
    concurrent readers. TSan judges the shard mutexes, the pool handshake
    and the sketch mutexes; the functional assertions pin occupancy and
    estimator sanity."""
    lib = cache_lib
    cap = 1 << 12
    S = 4
    n_slots = 4
    salt = 0xD00DFEEDFACE1234
    sc = lib.cache_create_sharded(cap, S, _u64(salt), 2)
    pending = lib.pending_map_create()
    sks = [lib.sketch_create(n_slots, 12, 4, 1 << 11, 8) for _ in range(S)]
    assert sc and pending and all(sks)
    lib.sketch_set_sample(sks[0], 4)  # one sampled sub-sketch in the mix
    sk_arr = (_p * S)(*sks)
    stop = threading.Event()
    spans = []
    spans_lock = threading.Lock()

    def feeder():
        rng = np.random.default_rng(SEED)
        rows = np.empty(BATCH, np.int32)
        miss_s = np.empty(BATCH, np.uint64)
        miss_r = np.empty(BATCH, np.int64)
        ev_s = np.empty(cap, np.uint64)
        ev_r = np.empty(cap, np.int64)
        rest_src = np.empty(BATCH, np.int64)
        rest_pos = np.empty(BATCH, np.int64)
        n_unique = _i64(0)
        n_evict = _i64(0)
        n_restore = _i64(0)
        drain_s = np.empty(cap, np.uint64)
        drain_r = np.empty(cap, np.int64)
        try:
            for it in range(ITERS * 4):
                if it % 16 == 8:
                    # single-writer contract: only the feed caller may
                    # resize the pool (joins the walker threads)
                    lib.cache_sharded_set_threads(sc, 1 + (it // 16) % S)
                hot = rng.integers(0, 512, BATCH // 2, dtype=np.uint64)
                cold = rng.integers(it * 64, it * 64 + (1 << 14),
                                    BATCH // 2, dtype=np.uint64)
                signs = _u64arr(np.concatenate([hot, cold]))
                n_miss = lib.cache_feed_batch_sharded(
                    sc, pending, signs.ctypes.data_as(_u64p), BATCH,
                    rows.ctypes.data_as(_i32p),
                    miss_s.ctypes.data_as(_u64p), miss_r.ctypes.data_as(_i64p),
                    ev_s.ctypes.data_as(_u64p), ev_r.ctypes.data_as(_i64p),
                    ctypes.byref(n_unique), ctypes.byref(n_evict),
                    rest_src.ctypes.data_as(_i64p),
                    rest_pos.ctypes.data_as(_i64p),
                    ctypes.byref(n_restore), _u64(salt),
                    sk_arr, S, BATCH // n_slots, 0,
                )
                assert 0 <= n_miss <= BATCH
                assert 0 <= n_restore.value <= n_miss
                assert 0 < n_unique.value <= BATCH
                ne = n_evict.value
                if ne:
                    evicted = _u64arr(ev_s[:ne] ^ np.uint64(salt))
                    token = _u32(it & 0xFFFFFFFF)
                    lib.pending_map_insert_range(
                        pending, evicted.ctypes.data_as(_u64p), ne,
                        it * cap, token,
                    )
                    with spans_lock:
                        spans.append((evicted, token))
                if it % 64 == 63:
                    # eviction-heavy churn: cold-restart the directory
                    # (drain is feed-caller-only, like the stream fence)
                    nd = lib.cache_sharded_drain(
                        sc, drain_s.ctypes.data_as(_u64p),
                        drain_r.ctypes.data_as(_i64p),
                    )
                    assert 0 <= nd <= cap
        finally:
            stop.set()

    def prober(tid):
        def run():
            rng = np.random.default_rng(SEED + 200 + tid)
            rows = np.empty(256, np.int64)
            sizes = np.empty(S, np.int64)
            busy = np.empty(S, np.int64)
            while not stop.is_set():
                probe = _u64arr(
                    rng.integers(0, 1 << 14, 256, dtype=np.uint64)
                )
                lib.cache_sharded_probe(
                    sc, probe.ctypes.data_as(_u64p), 256,
                    rows.ctypes.data_as(_i64p),
                )
                assert ((rows >= -1) & (rows < cap)).all()
                lib.cache_sharded_shard_sizes(sc, sizes.ctypes.data_as(_i64p))
                assert 0 <= sizes.sum() <= cap
                lib.cache_sharded_shard_busy_ns(sc, busy.ctypes.data_as(_i64p))
                assert (busy >= 0).all()
                assert 1 <= lib.cache_sharded_threads(sc) <= S
                assert 0 <= lib.cache_sharded_len(sc) <= cap
        return run

    def fencer(tid):
        def run():
            stats = np.empty(4, np.float64)
            buf = np.empty(1 << 20, np.uint8)
            i = 0
            while not stop.is_set():
                i += 1
                sk = sks[(tid + i) % S]
                if i % 3 == 0:
                    lib.sketch_decay(sk, 0.7)
                for slot in range(n_slots):
                    assert lib.sketch_slot_stats(
                        sk, slot, stats.ctypes.data_as(_f64p)
                    ) == 0
                    assert stats[0] >= 0.0 and stats[1] >= 0.0
                size = lib.sketch_export_size(sk)
                assert 0 < size <= buf.size
                assert lib.sketch_export(
                    sk, buf.ctypes.data_as(_u8p), buf.size
                ) == size
        return run

    def writeback(tid):
        def run():
            rng = np.random.default_rng(SEED + 100 + tid)
            tokens = np.empty(BATCH, np.uint32)
            srcs = np.empty(BATCH, np.int64)
            while not stop.is_set() or spans:
                with spans_lock:
                    span = spans.pop() if spans else None
                if span is None:
                    probe = _u64arr(
                        rng.integers(0, 1 << 14, 64, dtype=np.uint64)
                    )
                    lib.pending_map_query(
                        pending, probe.ctypes.data_as(_u64p), 64,
                        tokens.ctypes.data_as(_u32p),
                        srcs.ctypes.data_as(_i64p),
                    )
                    continue
                signs, token = span
                n = len(signs)
                hits = lib.pending_map_query(
                    pending, signs.ctypes.data_as(_u64p), n,
                    tokens.ctypes.data_as(_u32p), srcs.ctypes.data_as(_i64p),
                )
                assert 0 <= hits <= n
                lib.pending_map_remove(
                    pending, signs.ctypes.data_as(_u64p), n, token
                )
        return run

    _run_threads(
        [feeder]
        + [writeback(t) for t in range(3)]
        + [prober(t) for t in range(2)]
        + [fencer(t) for t in range(2)]
    )
    assert lib.pending_map_size(pending) >= 0
    assert lib.cache_sharded_len(sc) <= cap
    for sk in sks:
        lib.sketch_destroy(sk)
    lib.pending_map_destroy(pending)
    lib.cache_sharded_destroy(sc)


def test_probe_wave_feed_vs_mode_toggles_and_stall_readers(cache_lib):
    """Round 17: the SIMD probe-wave walk under concurrent knob traffic.
    One feeder drives ``cache_feed_batch_sharded`` in wave mode while a
    TUNER thread flips ``cache_sharded_set_probe_mode`` scalar<->simd (per
    shard under its mu — legal from any thread, unlike the pool-resizing
    setters) and stats threads hammer the new per-shard STALL gauge plus
    the probe/affinity getters alongside the round-14 reader set. The
    feeder itself exercises the pool single-writer surfaces mid-run —
    ``set_threads`` AND the round-17 ``set_affinity`` (both join/respawn
    walkers, so only the feed caller may touch them). TSan judges that the
    tag-array walk, the mode flag and the stall atomics never race; the
    functional assertions pin occupancy and gauge sanity. No new mutexes
    this round — everything above rides the existing FeedShard::mu /
    pool_mu ranks (see scripts/lock_order.py)."""
    lib = cache_lib
    cap = 1 << 12
    S = 4
    salt = 0x17C0FFEE17C0FFEE
    sc = lib.cache_create_sharded(cap, S, _u64(salt), 2)
    pending = lib.pending_map_create()
    assert sc and pending
    lib.cache_sharded_set_probe_mode(sc, 1)
    stop = threading.Event()
    spans = []
    spans_lock = threading.Lock()

    def feeder():
        rng = np.random.default_rng(SEED + 17)
        rows = np.empty(BATCH, np.int32)
        miss_s = np.empty(BATCH, np.uint64)
        miss_r = np.empty(BATCH, np.int64)
        ev_s = np.empty(cap, np.uint64)
        ev_r = np.empty(cap, np.int64)
        rest_src = np.empty(BATCH, np.int64)
        rest_pos = np.empty(BATCH, np.int64)
        n_unique = _i64(0)
        n_evict = _i64(0)
        n_restore = _i64(0)
        drain_s = np.empty(cap, np.uint64)
        drain_r = np.empty(cap, np.int64)
        try:
            for it in range(ITERS * 4):
                if it % 16 == 8:
                    # pool single-writer surfaces: resize AND re-pin the
                    # walkers (set_affinity joins/respawns like
                    # set_threads, so only the feed caller may call it)
                    lib.cache_sharded_set_threads(sc, 1 + (it // 16) % S)
                    lib.cache_sharded_set_affinity(sc, (it // 16) % 3)
                hot = rng.integers(0, 512, BATCH // 2, dtype=np.uint64)
                cold = rng.integers(it * 64, it * 64 + (1 << 14),
                                    BATCH // 2, dtype=np.uint64)
                signs = _u64arr(np.concatenate([hot, cold]))
                n_miss = lib.cache_feed_batch_sharded(
                    sc, pending, signs.ctypes.data_as(_u64p), BATCH,
                    rows.ctypes.data_as(_i32p),
                    miss_s.ctypes.data_as(_u64p), miss_r.ctypes.data_as(_i64p),
                    ev_s.ctypes.data_as(_u64p), ev_r.ctypes.data_as(_i64p),
                    ctypes.byref(n_unique), ctypes.byref(n_evict),
                    rest_src.ctypes.data_as(_i64p),
                    rest_pos.ctypes.data_as(_i64p),
                    ctypes.byref(n_restore), _u64(salt),
                    None, 0, 0, 0,
                )
                assert 0 <= n_miss <= BATCH
                assert 0 <= n_restore.value <= n_miss
                assert 0 < n_unique.value <= BATCH
                ne = n_evict.value
                if ne:
                    evicted = _u64arr(ev_s[:ne] ^ np.uint64(salt))
                    token = _u32(it & 0xFFFFFFFF)
                    lib.pending_map_insert_range(
                        pending, evicted.ctypes.data_as(_u64p), ne,
                        it * cap, token,
                    )
                    with spans_lock:
                        spans.append((evicted, token))
                if it % 64 == 63:
                    nd = lib.cache_sharded_drain(
                        sc, drain_s.ctypes.data_as(_u64p),
                        drain_r.ctypes.data_as(_i64p),
                    )
                    assert 0 <= nd <= cap
        finally:
            stop.set()

    def tuner():
        # probe-mode flips serialize with pass 1 on each shard's mu, so
        # they are legal from OUTSIDE the feed caller — every walk sees a
        # coherent mode and the tag array is maintained under both
        i = 0
        while not stop.is_set():
            i += 1
            lib.cache_sharded_set_probe_mode(sc, i & 1)
            assert lib.cache_sharded_probe_mode(sc) in (0, 1)

    def prober(tid):
        def run():
            rng = np.random.default_rng(SEED + 300 + tid)
            rows = np.empty(256, np.int64)
            sizes = np.empty(S, np.int64)
            busy = np.empty(S, np.int64)
            stall = np.empty(S, np.int64)
            while not stop.is_set():
                probe = _u64arr(
                    rng.integers(0, 1 << 14, 256, dtype=np.uint64)
                )
                lib.cache_sharded_probe(
                    sc, probe.ctypes.data_as(_u64p), 256,
                    rows.ctypes.data_as(_i64p),
                )
                assert ((rows >= -1) & (rows < cap)).all()
                lib.cache_sharded_shard_sizes(sc, sizes.ctypes.data_as(_i64p))
                assert 0 <= sizes.sum() <= cap
                lib.cache_sharded_shard_busy_ns(sc, busy.ctypes.data_as(_i64p))
                assert (busy >= 0).all()
                lib.cache_sharded_shard_stall_ns(
                    sc, stall.ctypes.data_as(_i64p))
                assert (stall >= 0).all()
                assert 0 <= lib.cache_sharded_affinity(sc) <= 2
                assert 1 <= lib.cache_sharded_threads(sc) <= S

        return run

    def writeback(tid):
        def run():
            rng = np.random.default_rng(SEED + 400 + tid)
            tokens = np.empty(BATCH, np.uint32)
            srcs = np.empty(BATCH, np.int64)
            while not stop.is_set() or spans:
                with spans_lock:
                    span = spans.pop() if spans else None
                if span is None:
                    probe = _u64arr(
                        rng.integers(0, 1 << 14, 64, dtype=np.uint64)
                    )
                    lib.pending_map_query(
                        pending, probe.ctypes.data_as(_u64p), 64,
                        tokens.ctypes.data_as(_u32p),
                        srcs.ctypes.data_as(_i64p),
                    )
                    continue
                signs, token = span
                n = len(signs)
                hits = lib.pending_map_query(
                    pending, signs.ctypes.data_as(_u64p), n,
                    tokens.ctypes.data_as(_u32p), srcs.ctypes.data_as(_i64p),
                )
                assert 0 <= hits <= n
                lib.pending_map_remove(
                    pending, signs.ctypes.data_as(_u64p), n, token
                )

        return run

    _run_threads(
        [feeder, tuner]
        + [writeback(t) for t in range(2)]
        + [prober(t) for t in range(2)]
    )
    assert lib.pending_map_size(pending) >= 0
    assert lib.cache_sharded_len(sc) <= cap
    lib.pending_map_destroy(pending)
    lib.cache_sharded_destroy(sc)
