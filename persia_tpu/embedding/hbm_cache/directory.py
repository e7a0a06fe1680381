"""Native cache-directory bindings + host staging rings (split from the
round-3 monolith; see package __init__ for the design overview)."""


from __future__ import annotations

import ctypes
import os
import threading
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Set, Tuple

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np

from persia_tpu.config import EmbeddingConfig
from persia_tpu.data import PersiaBatch
from persia_tpu.embedding.optim import OPTIMIZER_ADAM, OptimizerConfig
from persia_tpu.embedding.worker import (
    ProcessedBatch,
    ProcessedSlot,
    ShardedLookup,
    preprocess_batch,
)
from persia_tpu.logger import get_default_logger
from persia_tpu.utils import round_up_pow2 as _round_up_pow2
from persia_tpu.embedding.hbm_cache.common import _bucket  # noqa: F401
from persia_tpu.metrics import get_metrics
from persia_tpu.ops.sparse_update import sparse_update
from persia_tpu.tracing import span

logger = get_default_logger("persia_tpu.hbm_cache")

# ------------------------------------------------------------------ ctypes


# one extra level: this file lives in the hbm_cache PACKAGE
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
_SRC = os.path.join(_REPO_ROOT, "native", "cache.cpp")
_SO = os.path.join(_REPO_ROOT, "native", "libpersia_cache.so")
_LIB: Optional[ctypes.CDLL] = None

_i64p = ctypes.POINTER(ctypes.c_int64)
_u64p = ctypes.POINTER(ctypes.c_uint64)


def build_native(force: bool = False) -> str:
    from persia_tpu.embedding._native_build import build_so

    return build_so(
        # -pthread: the sharded feeder runs its shard walks on a native pool
        _SRC, _SO, ["-O3", "-std=c++17", "-fPIC", "-shared", "-Wall",
                    "-pthread"],
        logger, force=force,
    )


def _load_lib() -> ctypes.CDLL:
    global _LIB
    if _LIB is None:
        # CDLL the path build_native RETURNS (sanitizer-variant aware)
        so_path = build_native()
        lib = ctypes.CDLL(so_path)
        i64, p = ctypes.c_int64, ctypes.c_void_p
        # every binding declares BOTH restype and argtypes (restype = None
        # for void) — persia-lint ABI003/ABI007 enforce it mechanically
        lib.cache_create.restype = p
        lib.cache_create.argtypes = [i64]
        lib.cache_destroy.restype = None
        lib.cache_destroy.argtypes = [p]
        lib.cache_len.restype = i64
        lib.cache_len.argtypes = [p]
        lib.cache_capacity.restype = i64
        lib.cache_capacity.argtypes = [p]
        lib.cache_admit.restype = i64
        lib.cache_admit.argtypes = [p, _u64p, i64, _i64p, _i64p, _u64p, _i64p, _i64p]
        lib.cache_probe.restype = None
        lib.cache_probe.argtypes = [p, _u64p, i64, _i64p]
        lib.cache_drain.restype = i64
        lib.cache_drain.argtypes = [p, _u64p, _i64p]
        lib.cache_snapshot.restype = i64
        lib.cache_snapshot.argtypes = [p, _u64p, _i64p]
        lib.cache_set_admit_touches.restype = None
        lib.cache_set_admit_touches.argtypes = [p, i64]
        # probe layout selector (round 17): 1 = SIMD tag probe, 0 = scalar
        lib.cache_set_probe_mode.restype = None
        lib.cache_set_probe_mode.argtypes = [p, i64]
        lib.cache_probe_mode.restype = i64
        lib.cache_probe_mode.argtypes = [p]
        _i32p = ctypes.POINTER(ctypes.c_int32)
        lib.cache_admit_positions.restype = i64
        lib.cache_admit_positions.argtypes = [
            p, _u64p, i64, _i32p, _u64p, _i64p, _u64p, _i64p,
            ctypes.POINTER(i64), ctypes.POINTER(i64),
        ]
        lib.cache_uniform_init.restype = None
        lib.cache_uniform_init.argtypes = [
            _u64p, i64, i64, ctypes.c_uint64, ctypes.c_double,
            ctypes.c_double, ctypes.POINTER(ctypes.c_float),
        ]
        lib.cache_init_rows.restype = None
        lib.cache_init_rows.argtypes = [
            _u64p, i64, i64, ctypes.c_uint64, ctypes.c_int,
            ctypes.c_double, ctypes.c_double, ctypes.POINTER(ctypes.c_float),
        ]
        u32 = ctypes.c_uint32
        u32p = ctypes.POINTER(u32)
        lib.pending_map_create.restype = p
        lib.pending_map_create.argtypes = []
        lib.pending_map_destroy.restype = None
        lib.pending_map_destroy.argtypes = [p]
        lib.pending_map_size.restype = i64
        lib.pending_map_size.argtypes = [p]
        lib.pending_map_insert.restype = None
        lib.pending_map_insert.argtypes = [p, _u64p, _i64p, i64, u32]
        lib.pending_map_insert_range.restype = None
        lib.pending_map_insert_range.argtypes = [p, _u64p, i64, i64, u32]
        lib.pending_map_query.restype = i64
        lib.pending_map_query.argtypes = [p, _u64p, i64, u32p, _i64p]
        lib.pending_map_remove.restype = None
        lib.pending_map_remove.argtypes = [p, _u64p, i64, u32]
        lib.cache_feed_batch.restype = i64
        lib.cache_feed_batch.argtypes = [
            p, p, _u64p, i64, _i32p, _u64p, _i64p, _u64p, _i64p,
            ctypes.POINTER(i64), ctypes.POINTER(i64),
            _i64p, _i64p, ctypes.POINTER(i64), ctypes.c_uint64,
        ]
        # ---- sharded feeder directory (round 14) ----
        pp = ctypes.POINTER(p)  # void** — the per-shard sketch array
        lib.cache_create_sharded.restype = p
        lib.cache_create_sharded.argtypes = [i64, i64, ctypes.c_uint64, i64]
        lib.cache_sharded_destroy.restype = None
        lib.cache_sharded_destroy.argtypes = [p]
        lib.cache_sharded_len.restype = i64
        lib.cache_sharded_len.argtypes = [p]
        lib.cache_sharded_capacity.restype = i64
        lib.cache_sharded_capacity.argtypes = [p]
        lib.cache_sharded_n_shards.restype = i64
        lib.cache_sharded_n_shards.argtypes = [p]
        lib.cache_sharded_threads.restype = i64
        lib.cache_sharded_threads.argtypes = [p]
        lib.cache_sharded_set_threads.restype = None
        lib.cache_sharded_set_threads.argtypes = [p, i64]
        lib.cache_sharded_set_admit_touches.restype = None
        lib.cache_sharded_set_admit_touches.argtypes = [p, i64]
        lib.cache_sharded_shard_sizes.restype = None
        lib.cache_sharded_shard_sizes.argtypes = [p, _i64p]
        lib.cache_sharded_shard_busy_ns.restype = None
        lib.cache_sharded_shard_busy_ns.argtypes = [p, _i64p]
        # ---- probe layout + walker affinity (round 17) ----
        lib.cache_sharded_shard_stall_ns.restype = None
        lib.cache_sharded_shard_stall_ns.argtypes = [p, _i64p]
        lib.cache_sharded_set_probe_mode.restype = None
        lib.cache_sharded_set_probe_mode.argtypes = [p, i64]
        lib.cache_sharded_probe_mode.restype = i64
        lib.cache_sharded_probe_mode.argtypes = [p]
        lib.cache_sharded_set_affinity.restype = None
        lib.cache_sharded_set_affinity.argtypes = [p, i64]
        lib.cache_sharded_affinity.restype = i64
        lib.cache_sharded_affinity.argtypes = [p]
        lib.cache_sharded_probe.restype = None
        lib.cache_sharded_probe.argtypes = [p, _u64p, i64, _i64p]
        lib.cache_sharded_admit.restype = i64
        lib.cache_sharded_admit.argtypes = [
            p, _u64p, i64, _i64p, _i64p, _u64p, _i64p, ctypes.POINTER(i64),
        ]
        lib.cache_sharded_snapshot.restype = i64
        lib.cache_sharded_snapshot.argtypes = [p, _u64p, _i64p]
        lib.cache_sharded_drain.restype = i64
        lib.cache_sharded_drain.argtypes = [p, _u64p, _i64p]
        lib.cache_feed_batch_sharded.restype = i64
        lib.cache_feed_batch_sharded.argtypes = [
            p, p, _u64p, i64, _i32p, _u64p, _i64p, _u64p, _i64p,
            ctypes.POINTER(i64), ctypes.POINTER(i64),
            _i64p, _i64p, ctypes.POINTER(i64), ctypes.c_uint64,
            pp, i64, i64, i64,
        ]
        _LIB = lib
    return _LIB


#: PERSIA_FEED_AFFINITY policy names → native mode codes. ``none`` leaves
#: walkers unpinned; ``compact`` packs worker i onto cpu ``i % ncpu``
#: (shared-LLC locality); ``spread`` stripes workers across the cpu range
#: (one walker per NUMA node's worth of cores on big hosts).
AFFINITY_MODES = {"none": 0, "compact": 1, "spread": 2}


def feed_affinity_from_env() -> int:
    """Resolve PERSIA_FEED_AFFINITY to a native pinning mode (default 0 =
    none). Unknown values fall back to none — placement is best-effort."""
    return AFFINITY_MODES.get(
        os.environ.get("PERSIA_FEED_AFFINITY", "none").strip().lower(), 0)


def feed_probe_from_env() -> int:
    """Resolve PERSIA_FEED_PROBE to a probe-layout mode: ``scalar`` → 0,
    anything else (including unset) → 1, the SIMD tag probe. Mirrors the
    native ``default_probe_mode`` so Python-side introspection agrees with
    directories created before the first setter call."""
    return 0 if os.environ.get("PERSIA_FEED_PROBE", "").strip() == "scalar" else 1


def native_uniform_init(
    signs: np.ndarray, seed: int, dim: int, lo: float, hi: float,
    out: Optional[np.ndarray] = None,
) -> np.ndarray:
    """Seeded cold-miss embedding init in C++ — bit-identical to
    ``hashing.uniform_init_for_signs`` (tested). ``out`` (M, dim) f32
    C-contiguous is filled in place when given."""
    lib = _load_lib()
    signs = np.ascontiguousarray(signs, dtype=np.uint64)
    m = len(signs)
    if out is None:
        out = np.empty((m, dim), dtype=np.float32)
    assert out.flags["C_CONTIGUOUS"] and out.dtype == np.float32
    lib.cache_uniform_init(
        signs.ctypes.data_as(_u64p), m, dim, ctypes.c_uint64(seed),
        lo, hi, out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
    )
    return out


def native_init_rows(
    signs: np.ndarray, seed: int, dim: int, method,
    out: Optional[np.ndarray] = None,
) -> np.ndarray:
    """Seeded cold-miss init for any ``config.InitializationMethod`` —
    bit-identical to ``hashing.init_for_signs`` and to the PS cores
    (tests/test_init_methods.py), so a row born in the cache matches one
    born on the PS (ref: emb_entry.rs:28-60 seeded init)."""
    lib = _load_lib()
    signs = np.ascontiguousarray(signs, dtype=np.uint64)
    m = len(signs)
    if out is None:
        out = np.empty((m, dim), dtype=np.float32)
    assert out.flags["C_CONTIGUOUS"] and out.dtype == np.float32
    lib.cache_init_rows(
        signs.ctypes.data_as(_u64p), m, dim, ctypes.c_uint64(seed),
        method.code, method.p0, method.p1,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
    )
    return out


def _retain_allocator_pages() -> None:
    """Tell glibc to satisfy MB-scale allocations from retained heap pages.

    The per-step staging buffers (~0.5-1 MB each) historically crossed
    malloc's default mmap threshold, so every step paid mmap +
    first-touch page faults + munmap TLB churn — profiled at ~20 ms/step
    of pure allocator cost on a single-core host. The old answer was a
    fixed-depth buffer-reuse ring, which turned out to hand a
    still-in-flight buffer back to the feeder whenever the pipeline ran
    deeper than the depth — measured as run-to-run NONDETERMINISTIC
    training (torn staging bytes). Raising M_MMAP_THRESHOLD keeps fresh
    allocations cheap (glibc free-lists, no page churn) so every step can
    own brand-new buffers: correctness by construction, same speed.
    Called once, lazily, when the first cache tier is constructed — a
    process that merely imports this package (fused-tier users, test
    collection) keeps its default allocator behavior. Opt out with
    PERSIA_NO_MALLOPT=1. No-op where mallopt is unavailable (non-glibc)."""
    global _MALLOPT_DONE
    if _MALLOPT_DONE or os.environ.get("PERSIA_NO_MALLOPT") == "1":
        return
    _MALLOPT_DONE = True
    try:
        libc = ctypes.CDLL(None)
        libc.mallopt.restype = ctypes.c_int
        libc.mallopt.argtypes = [ctypes.c_int, ctypes.c_int]
        M_MMAP_THRESHOLD = -3
        libc.mallopt(M_MMAP_THRESHOLD, 64 * 1024 * 1024)
    except Exception:  # noqa: BLE001 — allocator tuning is best-effort
        pass


_MALLOPT_DONE = False


class _BufRing:
    """Per-step host staging buffer source.

    Every ``get`` returns a FRESH array: the per-step buffers escape into
    an asynchronously consumed pipeline (device_put serialization, jit
    argument lifetimes), and no rotation depth or release protocol proved
    robust against every consumer — a reused buffer whose bytes change
    while any in-flight reader still needs them silently corrupts
    training (observed as bimodal per-step losses at deep prefetch).
    Allocation stays cheap because ``_retain_allocator_pages`` keeps
    glibc from mmap-ing these MB-scale buffers. The class keeps its
    pooling-era ``key`` argument so call sites stay unchanged."""

    def get(self, key, shape, dtype) -> np.ndarray:
        return np.empty(shape, dtype)

    def full(self, key, shape, dtype, fill) -> np.ndarray:
        arr = np.empty(shape, dtype)
        arr.fill(fill)
        return arr


class CacheDirectory:
    """LRU map sign → device cache row (native C++, O(1) per op).

    ``admit_touches`` — touch-gated admission (the reference's
    ``admit_probability`` analogue, reference
    `persia-embedding-config/src/lib.rs` HyperParameters): a non-resident
    sign is admitted only on its Nth distinct-batch touch; earlier touches
    map to the pad row ``capacity`` (zero forward contribution, gradient
    dropped — the reference's non-admitted-sign semantics). Default 1 =
    admit on first touch (exact parity with the ungated tier).

    ``shards`` — when set, the directory is partitioned into that many
    independent shards (own mutex + LRU chain + row range) keyed by
    ``shard_route(sign ^ part_salt)``; the feed walk can then run on the
    native thread pool (``feed_threads``) and fuse the tiering sketch
    observe into the same pass (``feed_batch(..., sketches=)``). Outputs
    are merged in shard order, so they are bit-identical at ANY thread
    count (but differ from the unsharded directory's LRU order for
    ``shards > 1`` — ``shards`` must therefore be a jobstate-stable
    choice, not derived from the host). ``shards=1`` is bit-identical to
    the legacy directory. ``part_salt`` is the per-group ledger salt
    (:func:`group_salt`) so partitioning rides the same namespace the
    hazard ledger already uses."""

    def __init__(self, capacity: int, admit_touches: int = 1,
                 shards: Optional[int] = None, feed_threads: int = 1,
                 part_salt: int = 0, probe: Optional[int] = None,
                 affinity: Optional[int] = None):
        self._lib = _load_lib()
        self.part_salt = int(part_salt) & (2**64 - 1)
        self._sharded = shards is not None
        if self._sharded:
            self._h = self._lib.cache_create_sharded(
                capacity, max(1, int(shards)), self.part_salt,
                max(1, int(feed_threads)))
            # the native side clamps shards to [1, min(64, capacity)]
            self.shards: Optional[int] = int(
                self._lib.cache_sharded_n_shards(self._h))
        else:
            self._h = self._lib.cache_create(capacity)
            self.shards = None
        # probe layout (round 17): the native side already defaulted from
        # PERSIA_FEED_PROBE at load; an explicit arg overrides per handle.
        # Bit-identical either way — a profiling/parity knob, never a
        # jobstate-stable choice.
        if probe is not None:
            self.set_probe_mode(probe)
        aff = feed_affinity_from_env() if affinity is None else int(affinity)
        if self._sharded and aff:
            self._lib.cache_sharded_set_affinity(self._h, aff)
        self.capacity = capacity
        self.admit_touches = int(admit_touches)
        if self.admit_touches > 1:
            if self._sharded:
                self._lib.cache_sharded_set_admit_touches(
                    self._h, self.admit_touches)
            else:
                self._lib.cache_set_admit_touches(self._h, self.admit_touches)
        # reusable admit_positions outputs: 5 scratch arrays (miss/evict
        # results are .copy()'d out, so a single reused buffer each is safe)
        # plus a ring for the per-position rows (which ESCAPE to the async
        # device staging path as views)
        self._scratch_n = 0
        self._rows_ring = _BufRing()

    @property
    def feed_threads(self) -> int:
        return (int(self._lib.cache_sharded_threads(self._h))
                if self._sharded else 1)

    def set_feed_threads(self, threads: int) -> None:
        """Resize the native walker pool (sharded mode only; clamped to
        [1, shards]). Output bits never depend on this — it is purely a
        throughput knob, safe to change between feeds."""
        if self._sharded:
            self._lib.cache_sharded_set_threads(self._h, max(1, int(threads)))

    def shard_sizes(self) -> np.ndarray:
        """Resident count per shard (sharded mode; (shards,) i64) — the
        per-shard occupancy surfaced in stream stats and fence logs."""
        if not self._sharded:
            return np.array([len(self)], dtype=np.int64)
        out = np.empty(self.shards, dtype=np.int64)
        self._lib.cache_sharded_shard_sizes(
            self._h, out.ctypes.data_as(_i64p))
        return out

    def shard_busy_ns(self) -> np.ndarray:
        """Per-shard walk time of the LAST feed in ns (sharded mode) —
        feeds the ``persia_tpu_feeder_shard_busy`` gauges + ``feed.shard``
        spans."""
        if not self._sharded:
            return np.zeros(1, dtype=np.int64)
        out = np.empty(self.shards, dtype=np.int64)
        self._lib.cache_sharded_shard_busy_ns(
            self._h, out.ctypes.data_as(_i64p))
        return out

    def shard_stall_ns(self) -> np.ndarray:
        """Per-shard pool-queue wait of the LAST feed in ns (sharded mode):
        dispatch-to-walk-start, summed over both walk phases. Busy says how
        long a shard's walk ran; stall says how long it waited for a core
        first — together they separate shard imbalance from core starvation
        on the ``persia_tpu_feeder_shard_stall`` gauge."""
        if not self._sharded:
            return np.zeros(1, dtype=np.int64)
        out = np.empty(self.shards, dtype=np.int64)
        self._lib.cache_sharded_shard_stall_ns(
            self._h, out.ctypes.data_as(_i64p))
        return out

    @property
    def probe_mode(self) -> int:
        """Active probe layout: 1 = SIMD tag probe, 0 = scalar slot walk."""
        if self._sharded:
            return int(self._lib.cache_sharded_probe_mode(self._h))
        return int(self._lib.cache_probe_mode(self._h))

    def set_probe_mode(self, mode: int) -> None:
        """Select the probe layout (1 = SIMD tag probe, 0 = scalar).
        Output bits never depend on this — it exists for the golden parity
        suite and A/B profiling; safe to flip between feeds."""
        mode = 1 if int(mode) else 0
        if self._sharded:
            self._lib.cache_sharded_set_probe_mode(self._h, mode)
        else:
            self._lib.cache_set_probe_mode(self._h, mode)

    @property
    def feed_affinity(self) -> int:
        """Walker pinning policy (sharded mode): 0 none, 1 compact,
        2 spread — see ``PERSIA_FEED_AFFINITY``."""
        if not self._sharded:
            return 0
        return int(self._lib.cache_sharded_affinity(self._h))

    def set_feed_affinity(self, mode: int) -> None:
        """Re-pin the walker pool (sharded mode only; best-effort, Linux
        only). Purely a placement knob — output bits never depend on it."""
        if self._sharded:
            self._lib.cache_sharded_set_affinity(self._h, int(mode))

    def _ensure_scratch(self, n: int) -> None:
        if n <= self._scratch_n:
            return
        self._scratch_n = n
        self._s_miss_signs = np.empty(n, dtype=np.uint64)
        self._s_miss_rows = np.empty(n, dtype=np.int64)
        self._s_ev_signs = np.empty(n, dtype=np.uint64)
        self._s_ev_rows = np.empty(n, dtype=np.int64)
        self._s_miss_idx = np.empty(n, dtype=np.int64)
        self._s_rst_src = np.empty(n, dtype=np.int64)
        self._s_rst_pos = np.empty(n, dtype=np.int64)

    def __del__(self):
        if getattr(self, "_h", None) is not None:
            if self._sharded:
                self._lib.cache_sharded_destroy(self._h)
            else:
                self._lib.cache_destroy(self._h)
            self._h = None

    def __len__(self) -> int:
        if self._sharded:
            return self._lib.cache_sharded_len(self._h)
        return self._lib.cache_len(self._h)

    def admit(self, signs: np.ndarray):
        """signs must be deduplicated. Returns (rows (n,), miss_idx (M,),
        evict_signs (K,), evict_rows (K,)). Raises if the batch's distinct
        count exceeds capacity (the C call returns -1 *before* writing
        rows_out, so the outputs are uninitialized in that case)."""
        signs = np.ascontiguousarray(signs, dtype=np.uint64)
        n = len(signs)
        self._ensure_scratch(n)
        # bucketed ring shape (n varies per batch; exact shapes would
        # reallocate every call), result is the [:n] slice
        rows = self._rows_ring.get("rows64", (_bucket(max(n, 1)),), np.int64)[:n]
        miss_idx = self._s_miss_idx
        ev_signs = self._s_ev_signs
        ev_rows = self._s_ev_rows
        n_evict = ctypes.c_int64(0)
        admit_fn = (self._lib.cache_sharded_admit if self._sharded
                    else self._lib.cache_admit)
        n_miss = admit_fn(
            self._h, signs.ctypes.data_as(_u64p), n,
            rows.ctypes.data_as(_i64p), miss_idx.ctypes.data_as(_i64p),
            ev_signs.ctypes.data_as(_u64p), ev_rows.ctypes.data_as(_i64p),
            ctypes.byref(n_evict),
        )
        if n_miss < 0:
            raise RuntimeError(
                f"batch distinct-sign count {n} exceeds cache capacity "
                f"{self.capacity} — raise cache rows or shrink the batch"
            )
        k = n_evict.value
        return rows, miss_idx[:n_miss].copy(), ev_signs[:k].copy(), ev_rows[:k].copy()

    def admit_positions(self, signs: np.ndarray):
        """Admit a RAW (duplicated) position-level sign stream — the dedup
        happens natively. Returns (rows (n,) int32 per position,
        miss_signs (M,), miss_rows (M,), evict_signs (K,), evict_rows (K,),
        n_unique). One call replaces per-slot dedup + cross-slot dedup +
        admit + row LUT for the single-id fast path."""
        if self._sharded:
            out = self.feed_batch(signs, None)
            return out[:6]
        signs = np.ascontiguousarray(signs, dtype=np.uint64)
        n = signs.size
        self._ensure_scratch(n)
        rows = self._rows_ring.get("rows", (_bucket(max(n, 1)),), np.int32)[:n]
        miss_signs = self._s_miss_signs
        miss_rows = self._s_miss_rows
        ev_signs = self._s_ev_signs
        ev_rows = self._s_ev_rows
        n_unique = ctypes.c_int64(0)
        n_evict = ctypes.c_int64(0)
        i32p = ctypes.POINTER(ctypes.c_int32)
        n_miss = self._lib.cache_admit_positions(
            self._h, signs.ctypes.data_as(_u64p), n,
            rows.ctypes.data_as(i32p),
            miss_signs.ctypes.data_as(_u64p), miss_rows.ctypes.data_as(_i64p),
            ev_signs.ctypes.data_as(_u64p), ev_rows.ctypes.data_as(_i64p),
            ctypes.byref(n_unique), ctypes.byref(n_evict),
        )
        if n_miss < 0:
            raise RuntimeError(
                f"batch distinct-sign count exceeds cache capacity "
                f"{self.capacity} — raise cache rows or shrink the batch"
            )
        k = n_evict.value
        return (
            rows, miss_signs[:n_miss].copy(), miss_rows[:n_miss].copy(),
            ev_signs[:k].copy(), ev_rows[:k].copy(), n_unique.value,
        )

    def feed_batch(
        self, signs: np.ndarray, pending_map: "PendingSignMap | None",
        salt: int = 0,
        sketches: Optional[Sequence] = None,
        samples_per_slot: int = 0, slot_base: int = 0,
    ):
        """The feeder hot-loop fused call (``native/cache.cpp``
        ``cache_feed_batch``): everything ``admit_positions`` does PLUS the
        write-back hazard-ledger probe of the resulting misses, in ONE
        native round-trip. Returns ``admit_positions``'s 6-tuple extended
        with ``(restore_src (R,), restore_pos (R,))`` — the in-flight ring
        row and miss ordinal of every miss whose freshest entry is still
        riding an un-landed eviction write-back. The probe runs before the
        caller's ring-span reservation, so restore hits must be
        REVALIDATED against the map after reserving (see the C comment);
        a hit that died in between is safe to route through the PS.

        ``salt`` namespaces the ledger probe per cache group (the native
        side applies the SAME ``sign ^ salt`` the Python map methods do —
        see :func:`group_salt`).

        Sharded mode only: ``sketches`` (one per shard — native sketch
        handles or objects carrying ``_h``) fuses the tiering observe into
        the admit walk itself, one traversal of the sign matrix instead of
        two. ``samples_per_slot``/``slot_base`` give the position → slot
        map (position ``i`` → ``slot_base + i // samples_per_slot``). The
        fused observe attributes a sign to the slot of its FIRST position
        in the batch — callers must only fuse when sign → slot is
        injective (``feature_index_prefix_bit > 0``) and keep the routed
        unfused observe otherwise."""
        signs = np.ascontiguousarray(signs, dtype=np.uint64)
        n = signs.size
        self._ensure_scratch(n)
        rows = self._rows_ring.get("rows", (_bucket(max(n, 1)),), np.int32)[:n]
        n_unique = ctypes.c_int64(0)
        n_evict = ctypes.c_int64(0)
        n_restore = ctypes.c_int64(0)
        i32p = ctypes.POINTER(ctypes.c_int32)
        pending_h = pending_map._h if pending_map is not None else None
        common = (
            signs.ctypes.data_as(_u64p), n,
            rows.ctypes.data_as(i32p),
            self._s_miss_signs.ctypes.data_as(_u64p),
            self._s_miss_rows.ctypes.data_as(_i64p),
            self._s_ev_signs.ctypes.data_as(_u64p),
            self._s_ev_rows.ctypes.data_as(_i64p),
            ctypes.byref(n_unique), ctypes.byref(n_evict),
            self._s_rst_src.ctypes.data_as(_i64p),
            self._s_rst_pos.ctypes.data_as(_i64p),
            ctypes.byref(n_restore), ctypes.c_uint64(salt & (2**64 - 1)),
        )
        if self._sharded:
            sk_arr, n_sk = None, 0
            if sketches is not None:
                handles = [getattr(s, "_h", s) for s in sketches]
                if len(handles) != self.shards:
                    raise ValueError(
                        f"fused observe needs one sketch per shard "
                        f"({self.shards}), got {len(handles)}")
                sk_arr = (ctypes.c_void_p * len(handles))(*handles)
                n_sk = len(handles)
            # the per-shard walks time themselves natively and are reported
            # after the fact (tier._note_shard_walk: ``feed.shard``, ring
            # only); this is the live span they lie inside
            with span("feed.walk", shards=self.shards) as walk:
                n_miss = self._lib.cache_feed_batch_sharded(
                    self._h, pending_h, *common,
                    sk_arr, n_sk, int(samples_per_slot), int(slot_base),
                )
                walk.set(slowest_ns=int(self.shard_busy_ns().max()))
        else:
            if sketches is not None:
                raise ValueError("fused sketch observe needs shards= set")
            n_miss = self._lib.cache_feed_batch(self._h, pending_h, *common)
        if n_miss < 0:
            raise RuntimeError(
                f"batch distinct-sign count exceeds cache capacity "
                f"{self.capacity} — raise cache rows or shrink the batch"
            )
        k = n_evict.value
        r = n_restore.value
        return (
            rows,
            self._s_miss_signs[:n_miss].copy(),
            self._s_miss_rows[:n_miss].copy(),
            self._s_ev_signs[:k].copy(), self._s_ev_rows[:k].copy(),
            n_unique.value,
            self._s_rst_src[:r].copy(), self._s_rst_pos[:r].copy(),
        )

    def probe(self, signs: np.ndarray) -> np.ndarray:
        """Read-only residency check: row per sign, -1 on miss. No admit, no
        LRU touch — safe for eval/infer batches."""
        signs = np.ascontiguousarray(signs, dtype=np.uint64)
        rows = np.empty(len(signs), dtype=np.int64)
        probe_fn = (self._lib.cache_sharded_probe if self._sharded
                    else self._lib.cache_probe)
        probe_fn(self._h, signs.ctypes.data_as(_u64p), len(signs),
                 rows.ctypes.data_as(_i64p))
        return rows

    def drain(self) -> Tuple[np.ndarray, np.ndarray]:
        """Empty the directory; returns (signs, rows) of everything resident."""
        cap = self.capacity
        signs = np.empty(cap, dtype=np.uint64)
        rows = np.empty(cap, dtype=np.int64)
        drain_fn = (self._lib.cache_sharded_drain if self._sharded
                    else self._lib.cache_drain)
        k = drain_fn(self._h, signs.ctypes.data_as(_u64p),
                     rows.ctypes.data_as(_i64p))
        return signs[:k].copy(), rows[:k].copy()

    def snapshot(self) -> Tuple[np.ndarray, np.ndarray]:
        """Non-destructive (signs, rows) of everything resident — no LRU
        churn, no eviction, directory unchanged."""
        cap = self.capacity
        signs = np.empty(cap, dtype=np.uint64)
        rows = np.empty(cap, dtype=np.int64)
        snap_fn = (self._lib.cache_sharded_snapshot if self._sharded
                   else self._lib.cache_snapshot)
        k = snap_fn(self._h, signs.ctypes.data_as(_u64p),
                    rows.ctypes.data_as(_i64p))
        return signs[:k].copy(), rows[:k].copy()


# ------------------------------------------------------------ device state


def group_salt(name: str) -> int:
    """64-bit namespace salt for a cache group's pending-ledger keys.

    The ``PendingSignMap`` is GLOBAL to the stream but its entries are
    per-group ring rows, while the gate runs per group — with
    ``feature_index_prefix_bit=0`` two groups can carry the SAME raw sign,
    and an unsalted probe in group B would resolve group A's in-flight
    eviction (restoring A's ring rows into B's cache: silent corruption;
    round-5 advisor finding). Both the Python map methods and the native
    fused probe (``cache_feed_batch``) key on ``sign ^ group_salt(name)``,
    so the namespaces cannot collide. Deterministic by group name."""
    import hashlib

    h = hashlib.blake2b(name.encode(), digest_size=8).digest()
    return int.from_bytes(h, "little") or 1


class PendingSignMap:
    """Native sign → (token, src) map for the stream's write-back hazard
    gate (`native/cache.cpp` pending_map_*): one query call per step
    replaces a per-pending-record searchsorted scan. Internally
    mutex-protected, so the fused feeder probe (``cache_feed_batch``) and
    the write-back thread's removals need no shared Python lock; the
    stream's condvar still orders removals against ring-tail advances.

    ``salt`` (see :func:`group_salt`) namespaces keys per cache group:
    every method XORs it into the signs before they touch the native map,
    and the fused native probe applies the SAME xor (``cache_feed_batch``'s
    ``salt`` argument) — the two sides must agree or the fused path would
    silently probe the wrong namespace."""

    def __init__(self):
        self._lib = _load_lib()
        self._h = self._lib.pending_map_create()
        if not self._h:
            raise MemoryError("pending_map_create failed")

    def __del__(self):
        h = getattr(self, "_h", None)
        if h:
            self._lib.pending_map_destroy(h)
            self._h = None

    def __len__(self) -> int:
        return int(self._lib.pending_map_size(self._h))

    @staticmethod
    def _salted(signs: np.ndarray, salt: int) -> np.ndarray:
        signs = np.ascontiguousarray(signs, dtype=np.uint64)
        if salt:
            signs = signs ^ np.uint64(salt)
        return signs

    def insert(
        self, signs: np.ndarray, srcs: np.ndarray, token: int, salt: int = 0
    ) -> None:
        signs = self._salted(signs, salt)
        srcs = np.ascontiguousarray(srcs, dtype=np.int64)
        assert len(signs) == len(srcs)
        self._lib.pending_map_insert(
            self._h, signs.ctypes.data_as(_u64p),
            srcs.ctypes.data_as(_i64p), len(signs),
            ctypes.c_uint32(token & 0xFFFFFFFF),
        )

    def insert_range(
        self, signs: np.ndarray, base_src: int, token: int, salt: int = 0
    ) -> None:
        """Insert ``signs[i] -> (base_src + i, token)`` — the contiguous
        ring-span form every eviction record takes, without the host-side
        arange temporary."""
        signs = self._salted(signs, salt)
        self._lib.pending_map_insert_range(
            self._h, signs.ctypes.data_as(_u64p), len(signs),
            int(base_src), ctypes.c_uint32(token & 0xFFFFFFFF),
        )

    def query(self, signs: np.ndarray, salt: int = 0):
        """(hits, tokens (n,) u32, srcs (n,) i64 with -1 = not pending)."""
        signs = self._salted(signs, salt)
        n = len(signs)
        tokens = np.empty(n, dtype=np.uint32)
        srcs = np.empty(n, dtype=np.int64)
        hits = self._lib.pending_map_query(
            self._h, signs.ctypes.data_as(_u64p), n,
            tokens.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
            srcs.ctypes.data_as(_i64p),
        )
        return int(hits), tokens, srcs

    def remove(self, signs: np.ndarray, token: int, salt: int = 0) -> None:
        signs = self._salted(signs, salt)
        self._lib.pending_map_remove(
            self._h, signs.ctypes.data_as(_u64p), len(signs),
            ctypes.c_uint32(token & 0xFFFFFFFF),
        )
