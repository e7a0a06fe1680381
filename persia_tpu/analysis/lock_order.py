"""Declared lock-order registry for the feeder / write-back / stream threads.

The stream pipeline (hbm_cache/stream.py) runs three cooperating threads —
feeder prep, host→device staging, and the write-back flusher — plus the
RPC client threads underneath them. Deadlock-freedom rests on every thread
acquiring locks in ONE global order; this registry makes that order a
checkable artifact instead of tribal knowledge. CONC004 flags any lexically
nested ``with``-acquisition whose inner lock ranks ABOVE (outer-than) the
outer lock.

Ranks are matched by attribute-name suffix (the lock's field name), which
is how the code names them everywhere; a lock field not listed here simply
does not participate in the check — add it when it starts nesting.

Order (outermost first):

1. ``cv``            — the stream pipeline condition (hbm_cache/stream.py);
                       guards heads/tails/alloc queue/sign map. Nothing may
                       be held when taking it.
2. ``_cv``           — data-loader prefetch pipeline condition; same
                       contract as ``cv`` for the loader threads
3. ``_cond``         — RPC response-waiter / serving-batcher queue
                       conditions; taken first by their worker threads
4. ``_buf_lock``     — embedding worker forward-buffer table
5. ``_grad_lock``    — embedding worker gradient-state table
6. ``_deg_lock``     — degraded-lookup bookkeeping (worker + cache tier)
7. ``_ring_lock``    — ShardedLookup versioned-topology swap latch
                       (embedding/worker.py): guards the atomic publish of
                       the (replicas, ring, version) tuple during an
                       elastic reshard / replica replacement. Held for the
                       tuple swap only — every side effect (gauge, breaker
                       reset, degraded purge, flight event) runs after
                       release, so nothing is ever nested under it
8. ``_swap_lock``    — serving engine model-swap latch
9. ``_lock``/``lock``— generic leaf locks (breakers, caches, registries,
                       checkpoint shard fan-out); must never wrap a
                       ranked-above lock
10. ``_flight_lock``  — tracing flight-recorder ring (leaf; appends only)
11. ``_rng_lock``    — RetryPolicy jitter RNG (innermost; held for one
                       random() call only)
12. ``_DEFAULT_LOCK``— resilience default-policy registry (leaf)
13. ``_PROC_LOCK``   — native-build serializer (_native_build.py): a LAZY
                       first-use build can trigger under any lock above,
                       and nothing ranked is ever taken under it (only the
                       compile subprocess + flock), so it is a leaf despite
                       being held the longest
14. ``_REGISTRY_LOCK``— metrics registry (innermost leaf)

Native mutexes (native/cache.cpp) live below every Python lock: a ctypes
call can run under any ``with`` above (CONC005 audits which ones), and the
native side never calls back into Python. ``NATIVE_LOCK_RANKS`` records
the round-14 sharded-feeder order so the TSan harness and reviewers have
one artifact to check the C++ against. The discipline is deliberately
**never-nested**: a feed walker releases each mutex before taking the
next — FeedShard::mu for the admit passes, then AccessSketch::mu for the
fused observe apply, then PendingMap::mu for the ledger probe — and
ShardedCache::pool_mu is only ever held around the dispatch/teardown
handshake, never across a shard walk. The ranks therefore encode the
SEQUENCE of a walker's acquisitions, not a nesting tree; any future change
that nests two of them must follow this order (and will face TSan's
deadlock detector in scripts/race_native.sh either way). Stats-plane
readers (probe/len/snapshot/shard_sizes) take one FeedShard::mu at a time.

Round 17 (SIMD probe layout + walker affinity) adds NO new mutexes: the
tag array and probe_mode flag mutate only under the owning shard's
FeedShard::mu (so scalar<->simd flips are legal from any thread), the
stall gauge is a relaxed atomic beside busy_ns, and affinity_mode rides
pool_mu with the same join-outside-the-lock respawn shape as set_threads.
"""

from __future__ import annotations

from typing import Dict, Optional

# native/cache.cpp mutex order (outermost / first-acquired first). These
# are C++ fields, invisible to the AST lints above — the registry is the
# documented contract the TSan gate exercises.
NATIVE_LOCK_RANKS: Dict[str, int] = {
    "pool_mu": 0,   # ShardedCache walker-pool handshake (dispatch only)
    "mu@FeedShard": 10,    # per-shard directory + LRU + result buffers
    "mu@AccessSketch": 20,  # count-min/bitmap/top-K (observe vs fence)
    "mu@PendingMap": 30,   # hazard ledger (feeder probe vs write-back)
}

# attribute-name suffix -> rank (lower = must be taken first / outermost)
LOCK_RANKS: Dict[str, int] = {
    "cv": 0,
    "_cv": 2,
    "_cond": 6,
    "_buf_lock": 10,
    "_grad_lock": 20,
    "_deg_lock": 30,
    "_ring_lock": 35,
    "_swap_lock": 40,
    "_lock": 50,
    "lock": 50,
    "_flight_lock": 55,
    "_rng_lock": 60,
    "_DEFAULT_LOCK": 65,
    "_PROC_LOCK": 68,
    "_REGISTRY_LOCK": 70,
}


def rank_of(name: str) -> Optional[int]:
    """Rank for a lock-ish expression's terminal attribute/variable name,
    or None when the name is not registered."""
    if name in LOCK_RANKS:
        return LOCK_RANKS[name]
    return None
