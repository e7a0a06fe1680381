"""From the profiler's trace to numbers: device busy time (union of the
intervals in which an operation ran), idle share, time per operation, the
step program's start-to-start times, and the longest idle gaps named by what
the host was doing in them. All of them are read over whole steps inside the
time the host tracer covered (``reduce_events``).

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


def host_interval(events: List[Event]) -> Optional[Tuple[float, float]]:
    """(first host event's start, last host event's end): the time the host
    tracer covered, None where it kept no event. Device ops go on after it
    while ``stop_trace`` collects, with the host tracer off; what they do then
    is the profiler's, not the program's."""
    host = [(e[3], e[3] + e[4]) for e in events if not DEVICE_PLANE.match(e[0])]
    if not host:
        return None
    return min(s for s, _e in host), max(e for _s, e in host)


def leaf_ops(ops: List[Event]) -> List[Event]:
    """The ops that hold no other op of their line: a ``while``, a
    ``conditional`` or a ``call`` runs its body's ops inside its own event,
    and its time is theirs. An op of no duration (a custom call's marker at
    a fusion's start) is held by nothing."""
    order = sorted(ops, key=lambda e: (e[3], -e[4]))
    out = []
    for i, e in enumerate(order):
        end, j = e[3] + e[4], i + 1
        while j < len(order) and order[j][3] < end:
            if order[j][4] > 0 and order[j][3] + order[j][4] <= end:
                break  # e holds order[j]
            j += 1
        else:
            out.append(e)
    return out


def whole_steps(events: List[Event], plane: str, step_programs: Dict[str, int],
                lo: float, hi: float) -> List[Tuple[float, float, int]]:
    """(start, end, steps) of each step program's event on the plane's
    modules line that lies entirely inside [lo, hi], by start. One that
    starts at the first instant the plane recorded is left out: the profiler
    cuts an event that began before its record (a first pack of 162 ms among
    packs of 232 ms in a trace of the v5e). A K-step pack holds K steps; one
    that is not whole holds none."""
    mine = [e for e in events if e[0] == plane and e[1] in (OPS_LINE, MODULES_LINE)]
    first = min(e[3] for e in mine)
    return sorted((e[3], e[3] + e[4], k) for e in mine
                  if e[1] == MODULES_LINE and first < e[3] and lo <= e[3] and e[3] + e[4] <= hi
                  for p, k in step_programs.items() if e[2].startswith(p))


def reduce_events(events: List[Event], step_programs: Dict[str, int], chips: int) -> Optional[dict]:
    """The trace's numbers; None if no operation ran on a device inside the
    time the host tracer covered.

    Every device op is cut to that time first. Where it holds two whole steps
    or more, everything is taken from the start of the first whole step to
    the end of the last: ``steps`` counts those steps, and ``window_s``,
    ``busy_s``, ``op_s`` and the gaps cover the same interval, ops of other
    programs between the steps included. Where it holds fewer, they cover the
    host tracer's time, and ``steps`` and every number taken a step or over
    the steps is None."""
    planes = sorted({e[0] for e in events if DEVICE_PLANE.match(e[0])})[:chips]
    if not planes:
        return None
    covered = host_interval(events)
    busy_s, window_s, per_op, all_gaps, step_dts, n_steps = 0.0, 0.0, {}, [], [], None
    for plane in planes:
        ops = [e for e in events if e[0] == plane and e[1] == OPS_LINE]
        if not ops:
            continue
        lo, hi = covered or (min(e[3] for e in ops), max(e[3] + e[4] for e in ops))
        steps = whole_steps(events, plane, step_programs, lo, hi)
        n_whole = sum(k for _s, _e, k in steps)
        if n_whole >= 2:
            lo, hi = steps[0][0], steps[-1][1]
        busy, gaps = busy_union((max(e[3], lo), min(e[3] + e[4], hi))
                                for e in ops if e[3] < hi and e[3] + e[4] > lo)
        busy_s += busy * 1e-9
        window_s += (hi - lo) * 1e-9
        for e in leaf_ops(ops):
            s, t = max(e[3], lo), min(e[3] + e[4], hi)
            if t > s:
                label = op_label(e[2])
                per_op[label] = per_op.get(label, 0.0) + (t - s) * 1e-9
        if plane == planes[0]:
            all_gaps = gaps
            if n_whole >= 2:
                n_steps = n_whole
                for (s0, _e0, k), (s1, _e1, _k1) in zip(steps, steps[1:]):
                    step_dts += [(s1 - s0) * 1e-6 / k] * k
    if busy_s <= 0.0:
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
        "idle_share": 1.0 - busy_s / window_s if n_steps else None,
        "steps": n_steps,
        "step_ms_p95": _percentile(step_dts, 0.95) if len(step_dts) >= 20 else None,
        "device_ms_per_step": busy_s * 1e3 / n_steps if n_steps else None,
        "breakdown": {"device_ops": [[k, v / n] for k, v in top_ops], "idle_gaps": named_gaps},
        # every leaf op's busy seconds a chip by label, for a reader of one kernel's time
        "op_s": {k: v / n for k, v in per_op.items()},
    }


WHOLE_STEPS = 4  # whole steps a traced slice holds at the least, at the warm-up's time a step
MIN_SLICE_S = 4.0


def slice_length(seconds: float, step_s: float) -> float:
    """Seconds to trace of a window of ``seconds``: 4, or ``WHOLE_STEPS``
    steps of ``step_s`` and one more cut at each edge where that is longer,
    and never past the window's end."""
    want = max(MIN_SLICE_S, (WHOLE_STEPS + 2) * step_s)
    return max(0.5, min(want, seconds - WindowTracer.DELAY_S - 0.5))


class WindowTracer:
    """Traces a slice of the measured window from a thread of its own: starts
    a second into the window, stops after ``slice_length`` seconds, which
    follows ``step_s``, the time a step took in the warm-up."""

    DELAY_S = 1.0

    def __init__(self, directory: str, seconds: float, step_s: float = 0.0):
        self.dir = directory
        self.length = slice_length(seconds, step_s)
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
            raise RuntimeError("the trace holds no device operation inside the host tracer's time")
        return out
