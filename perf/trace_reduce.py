"""From the profiler's trace to numbers: device busy time (union of the
intervals in which an operation ran), idle share, time per operation, the
step program's start-to-start times, and the longest idle gaps named by what
the host was doing in them.

The reduction works on a compact list of events
``(plane, line, name, start_ns, duration_ns)`` so that it can be checked on a
small recorded trace (``perf/fixtures/``) without the profiler.
"""

from __future__ import annotations

import glob
import os
import re
import shutil
import threading
import time
from typing import Dict, Iterable, List, Optional, Tuple

Event = Tuple[str, str, str, float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_MIN_NS = 50_000  # host events shorter than this are not kept


def read_events(xplane_path: str) -> List[Event]:
    """Device-plane events in full, host events of 50 us or more."""
    from jax.profiler import ProfileData

    out: List[Event] = []
    for plane in ProfileData.from_file(xplane_path).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for e in line.events:
                if device or e.duration_ns >= HOST_MIN_NS:
                    out.append((plane.name, line.name, e.name, float(e.start_ns),
                                float(e.duration_ns)))
    return out


def busy_union(intervals: Iterable[Tuple[float, float]]) -> Tuple[float, List[Tuple[float, float]]]:
    """(busy ns, gaps) of a set of (start, end) intervals."""
    busy, gaps, cur_s, cur_e = 0.0, [], None, None
    for s, e in sorted(intervals):
        if cur_e is None:
            cur_s, cur_e = s, e
        elif s <= cur_e:
            cur_e = max(cur_e, e)
        else:
            busy += cur_e - cur_s
            gaps.append((cur_e, s))
            cur_s, cur_e = s, e
    if cur_e is not None:
        busy += cur_e - cur_s
    return busy, gaps


def op_label(name: str) -> str:
    """A short stable label of a device operation: its kind and result shape.
    The compiler's counter (``fusion.10``) is dropped: it differs between the
    copies of one operation in a K-step program and moves with any refactor."""
    lhs, _, rhs = name.partition(" = ")
    label = re.sub(r"(\.\d+)+$", "", lhs.strip().lstrip("%").split("(")[0])
    shape = re.search(r"\w+\[[\d,]*\]", rhs)
    if shape:
        label += "_" + shape.group(0)
    return re.sub(r"[^\w.]+", "_", label)[:64]


def _percentile(values: List[float], q: float) -> float:
    v = sorted(values)
    pos = (len(v) - 1) * q
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def _steps_in(found: List[Tuple[float, float, str]], step_programs: Dict[str, int]) -> float:
    """Steps that ran inside the trace. The profiler cuts a program's event
    at the trace's edges, so an event shorter than nine tenths of its
    program's median counts for its share of that median: a K-step pack that
    the trace's start cut in half is K/2 steps, not K."""
    total = 0.0
    for p, k in step_programs.items():
        durs = sorted(d for _s, d, q in found if q == p)
        if not durs:
            continue
        median = durs[len(durs) // 2]
        total += sum(k * (1.0 if d >= 0.9 * median else d / median) for d in durs)
    return total


def reduce_events(events: List[Event], step_programs: Dict[str, int], chips: int) -> Optional[dict]:
    """The trace's numbers; None if no operation ran on a device."""
    planes = sorted({e[0] for e in events if DEVICE_PLANE.match(e[0])})[:chips]
    if not planes:
        return None
    busy_s, window_s, per_op, all_gaps, step_dts, n_steps = 0.0, 0.0, {}, [], [], 0
    for plane in planes:
        ops = [e for e in events if e[0] == plane and e[1] == OPS_LINE]
        if not ops:
            continue
        t0 = min(e[3] for e in ops)
        t1 = max(e[3] + e[4] for e in ops)
        busy, gaps = busy_union((e[3], e[3] + e[4]) for e in ops)
        busy_s += busy * 1e-9
        window_s += (t1 - t0) * 1e-9
        for e in ops:
            label = op_label(e[2])
            per_op[label] = per_op.get(label, 0.0) + e[4] * 1e-9
        if plane == planes[0]:
            all_gaps = gaps
            found = sorted((e[3], e[4], p) for e in events
                           if e[0] == plane and e[1] == MODULES_LINE
                           for p in step_programs if e[2].startswith(p))
            mods = [(s0, step_programs[p]) for s0, _d, p in found]
            n_steps = _steps_in(found, step_programs)
            for (s0, k), (s1, _k1) in zip(mods, mods[1:]):
                step_dts += [(s1 - s0) * 1e-6 / k] * k
    if window_s <= 0.0:
        return None
    n = len(planes)
    busy_s, window_s = busy_s / n, window_s / n
    host = [e for e in events if not DEVICE_PLANE.match(e[0])]
    named_gaps = []
    for gs, ge in sorted(all_gaps, key=lambda g: g[0] - g[1])[:10]:
        best, best_ov = "inside_the_program", 0.0
        for _p, _l, name, s, d in host:
            ov = min(ge, s + d) - max(gs, s)
            if ov > best_ov:
                best, best_ov = re.sub(r"[^\w.]+", "_", name)[:64], ov
        named_gaps.append([best, (ge - gs) * 1e-9])
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": busy_s, "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s,
        "steps": n_steps,
        "step_ms_p95": _percentile(step_dts, 0.95) if len(step_dts) >= 20 else None,
        "device_ms_per_step": busy_s * 1e3 / n_steps if n_steps else None,
        "breakdown": {"device_ops": [[k, v / n] for k, v in top_ops], "idle_gaps": named_gaps},
    }


class WindowTracer:
    """Traces a slice of the measured window from a thread of its own: starts
    a second into the window, stops after ``length`` seconds."""

    DELAY_S = 1.0

    def __init__(self, directory: str, seconds: float, length: float = 4.0):
        self.dir = directory
        self.length = max(0.5, min(length, seconds - self.DELAY_S - 0.5))
        self._thread = threading.Thread(target=self._run, name="perf-tracer", daemon=True)
        self._stopped = threading.Event()
        self.error: Optional[BaseException] = None

    def start(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)
        self._thread.start()

    def _run(self) -> None:
        import jax

        try:
            time.sleep(self.DELAY_S)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            time.sleep(self.length)
            jax.profiler.stop_trace()
        except BaseException as e:  # reported by finish(), in the caller's thread
            self.error = e
        finally:
            self._stopped.set()

    def finish(self) -> None:
        self._thread.join(timeout=300)
        if self.error is not None:
            raise RuntimeError("the profiler failed") from self.error
        if not self._stopped.is_set():
            raise RuntimeError("the profiler did not stop")

    def reduce(self, step_programs: Dict[str, int], chips: int,
               dump: Optional[str] = None) -> dict:
        files = glob.glob(os.path.join(self.dir, "plugins", "profile", "*", "*.xplane.pb"))
        if not files:
            raise RuntimeError(f"no trace under {self.dir}")
        events = read_events(max(files, key=os.path.getmtime))
        if dump:
            import gzip
            import json

            with gzip.open(dump, "wt") as f:
                json.dump(events, f)
        out = reduce_events(events, step_programs, chips)
        shutil.rmtree(self.dir, ignore_errors=True)  # little is left on disk
        if out is None:
            raise RuntimeError("the trace holds no device operation")
        return out
