"""Where a process keeps JAX's persistent compilation cache.

The cached tier compiles well over a hundred programs per process (single
step, K-step pack, restore, eval, one aux scatter per shape bucket); on the
v5e one 96-step stream spent 54 s compiling 127 programs against 5 s of
training, and the next process read all of them back in 2.7 s (PR 21
chip_smoke). Its directory must not move between processes, so it is either what
the operator placed in ``JAX_COMPILATION_CACHE_DIR`` (JAX reads that
variable itself; nothing is set here) or ``<checkout>/.jax_cache``, derived
from this package's own location. This is the only place in the tree that
names a cache directory. ``CompileMeter`` counts what a process compiled
and what the cache gave it, from JAX's own monitoring events; from the same
events every backend compile becomes a flight event ``compile`` (seconds,
whether the persistent cache served it, the program's name), whose trigger
is the span around it (``stream.dispatch*``, ``ctx.apply_aux``,
``fused.dispatch``).
"""

from __future__ import annotations

import os
import threading
from typing import Dict, Tuple

from persia_tpu.tracing import record_event

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BACKEND = "/jax/core/compile/backend_compile_duration"
_HIT = "/jax/compilation_cache/cache_hits"
_WRITE = "/jax/compilation_cache/cache_misses"
# a persistent-cache hit is announced inside the compile call it serves, on
# the thread that makes the call
_hit = threading.local()
_recording = False


def _note_hit(event, **_kw) -> None:
    if event == _HIT:
        _hit.seen = True


def _note_compile(event, secs, **kw) -> None:
    if event == _BACKEND:
        record_event("compile", secs=round(secs, 6),
                     cached=getattr(_hit, "seen", False),
                     program=kw.get("fun_name", ""))
        _hit.seen = False


def _record_compiles() -> None:
    """Every backend compile of this process as a flight event; once."""
    global _recording
    if _recording:
        return
    _recording = True
    import jax.monitoring as mon

    mon.register_event_listener(_note_hit)
    mon.register_event_duration_secs_listener(_note_compile)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process and
    return its directory. Call before the first compile; calling again is
    free.

    On the CPU backend nothing is turned on: XLA:CPU compiles these
    programs in seconds, and a reloaded CPU entry logs a machine-feature
    mismatch ("could lead to SIGILL") per program."""
    import jax

    _record_compiles()
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    path = placed or os.path.join(_CHECKOUT, ".jax_cache")
    if jax.default_backend() == "cpu":
        return path
    if not placed and jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
    # JAX's default skips programs that compiled in under a second, which
    # nearly all of this system's programs do
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


class CompileMeter:
    """Sums what JAX's own monitoring events say about compilation:
    seconds inside the backend compile call (a persistent-cache hit spends
    its retrieval time there instead), how many programs, how many came
    from the persistent cache and how many were written to it."""

    BACKEND, HIT, WRITE = _BACKEND, _HIT, _WRITE

    def __init__(self):
        import jax.monitoring as mon

        self.secs = 0.0
        self.programs = 0
        self.hits = 0
        self.writes = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_kw):
        if event == self.BACKEND:
            self.secs += secs
            self.programs += 1

    def _on_event(self, event, **_kw):
        if event == self.HIT:
            self.hits += 1
        elif event == self.WRITE:
            self.writes += 1

    def mark(self) -> Tuple[float, int, int, int]:
        return (self.secs, self.programs, self.hits, self.writes)

    def since(self, mark) -> Dict:
        s, p, h, w = mark
        return {
            "compile_s": round(self.secs - s, 2),
            "programs": self.programs - p,
            "persistent_cache_hits": self.hits - h,
            "persistent_cache_writes": self.writes - w,
        }
