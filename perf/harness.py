"""The data-driven harness: one cell, one process, one run.

Everything that belongs to one configuration, one model, one traffic mix or
one per-layer metric is a file of its own, found by a name that a data file
gives. A later PR adds a cell or a metric by adding files and ``BENCHMARK.json``
entries; nothing that is here is edited, and nothing here, in ``compare.py``,
``counts.py``, ``trace_reduce.py``, ``limits_study.py`` or a reader names a
model or a key of one model's configuration.

A cell of a configuration the benchmark has brings ``perf/traffic/<traffic>.json``
(which names its generator in ``perf/generators/`` and its entry in
``perf/entries/``), ``perf/limits/<workload>.json`` and the ``BENCHMARK.json``
entries; a metric brings ``perf/metrics/<metric>.json`` (which names its
reader in ``perf/readers/``).

A configuration of another model brings besides, all new files:

- ``perf/configs/<config>.json`` with ``"model": "<model>"`` (a configuration
  that names no model is an error), ``"reference"``, ``sparse_optimizer``,
  ``dense_optimizer`` and ``guarantees`` with ``matmul_precision``;
- ``perf/reference/<model>.py``, the plain reference (contract:
  ``perf/compare.py``'s docstring), importing nothing of the program;
- ``perf/work/<model>.py``: ``train_flops_per_sample(config, traffic)`` and
  ``step_hbm_bytes(config, traffic)``, the operations and bytes the step's
  algorithm needs, for ``mfu`` and ``train_step_roofline`` (``perf/counts.py``);
- a generator with ``make(config, traffic, seed)`` and ``halve(batch)``, and an
  entry whose ``snapshot`` names its dense leaves as the reference does;
- its tests under ``tests/perf/`` (the control at a size a test run can hold,
  the planted faults), as files of their own.

It may not touch a file that is there: a need to do so is a fault of the
harness, for a ``benchmark`` PR (``tests/perf/test_harness.py`` rehearses a
throwaway tower of another architecture through ``run_cell`` to hold this).
"""

from __future__ import annotations

import importlib.util
import json
import os
import queue
import sys
import threading
import time
from typing import Dict, Iterator, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
MAX_STEPS_PER_S = 48  # batches made ahead of a window: more than any cell trains


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def load_config(name: str, root: str = ROOT) -> dict:
    config = _load_json(os.path.join(root, "perf", "configs", f"{name}.json"))
    if "model" not in config:
        raise SystemExit(f"configuration {name!r} names no model: its file needs a \"model\" key")
    return config


def load_traffic(name: str, root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "perf", "traffic", f"{name}.json"))


def load_metric(name: str, root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "perf", "metrics", f"{name}.json"))


def load_module(kind: str, name: str, root: str = ROOT):
    """``perf/<kind>/<name>.py`` of the checkout at ``root``: a generator, an
    entry, a reader, or a model's reference or work counts, found by the name
    a data file gives."""
    path = os.path.join(root, "perf", kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"perf_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def model_module(kind: str, config: dict, root: str = ROOT):
    """``perf/<kind>/<model>.py`` of the model the configuration names:
    ``reference`` or ``work``."""
    return load_module(kind, config["model"], root)


def find_cell(bench: dict, workload: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == workload:
            return cell
    raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")


def load_config_for_traffic(traffic: str, root: str = ROOT) -> dict:
    for cell in load_benchmark(root)["workloads"]:
        if cell["traffic"] == traffic:
            return load_config(cell["config"], root)
    raise SystemExit(f"no workload uses traffic {traffic!r}")


def cell_metrics(bench: dict, workload: str, kind: str) -> List[dict]:
    """The metrics of ``kind`` (``end_to_end`` / ``per_layer``) this cell reports."""
    return [m for m in bench[kind] if workload in m.get("workloads", [workload])]


# ------------------------------------------------------------------ batches

class BatchStream:
    """Runs a generator in one producer thread, a bounded queue ahead of the
    program, and counts how long the program waited on an empty queue."""

    _END = object()

    def __init__(self, batches: Iterator[dict], depth: int = 64, tap=None):
        self._tap = tap  # sees every batch, in order, in the producer thread
        self._q: "queue.Queue" = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self.wait_s = 0.0
        self.taken = 0
        self._error: Optional[BaseException] = None
        self._thread = threading.Thread(target=self._produce, args=(batches,),
                                        name="perf-generator", daemon=True)
        self._thread.start()

    def _produce(self, batches):
        try:
            for b in batches:
                if self._tap is not None:
                    self._tap(b)
                while not self._stop.is_set():
                    try:
                        self._q.put(b, timeout=0.1)
                        break
                    except queue.Full:
                        continue
                if self._stop.is_set():
                    return
        except BaseException as e:  # surfaced to the consumer, then re-raised there
            self._error = e
            self._q.put(self._END)

    def __iter__(self):
        return self

    def __next__(self) -> dict:
        try:
            b = self._q.get_nowait()
        except queue.Empty:
            t = time.perf_counter()
            b = self._q.get()
            self.wait_s += time.perf_counter() - t
        if b is self._END:
            raise RuntimeError("the traffic generator failed") from self._error
        self.taken += 1
        return b

    def close(self) -> None:
        self._stop.set()
        self._thread.join(timeout=10)


# ------------------------------------------------------------------- device

def require_chips(chips: int) -> dict:
    """The accelerator as JAX reports it; anything but ``chips`` TPU chips
    ends the run with no result."""
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(
            f"perf/run.py measures a TPU and JAX found platform {d.platform!r} "
            f"({d.device_kind}): no result is printed from it")
    if len(devs) < chips:
        raise SystemExit(f"the cell asks for {chips} chips and JAX found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def memory_peak_bytes(chips: int) -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()[:chips]]
    return int(max(peaks)) if peaks else 0


# ---------------------------------------------------------------------- run

def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_process_start: float, rehearsal: Optional[dict] = None,
             root: str = ROOT, dump_trace: Optional[str] = None,
             overrides: Optional[dict] = None) -> dict:
    """One run of one cell. ``rehearsal`` (tests only) replaces the chip check
    and cuts the sizes; its result is marked and carries no device metric.
    ``overrides`` (``perf/limits_study.py`` only) changes keys of the
    configuration or plants a fault on the chip, and may bring the seed's
    reference run (``reference``) so that it is not made again."""
    bench = load_benchmark(root)
    cell = find_cell(bench, workload)
    config = load_config(cell["config"], root)
    traffic = load_traffic(cell["traffic"], root)
    changed = rehearsal if rehearsal is not None else (overrides or {})
    config = dict(config, **changed.get("config", {}))
    traffic = dict(traffic, **changed.get("traffic", {}))
    if rehearsal is not None:
        device = {"platform": "rehearsal", "kind": "rehearsal", "count": cell["chips"]}
    else:
        device = require_chips(cell["chips"])

    import jax

    # the configuration's stated arithmetic
    jax.config.update("jax_default_matmul_precision", config["guarantees"]["matmul_precision"])
    from persia_tpu.compile_cache import CompileMeter, enable_compile_cache

    enable_compile_cache()  # JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache
    meter = CompileMeter()

    gen_mod = load_module("generators", traffic["generator"], root)
    entry_mod = load_module("entries", traffic["entry"], root)
    from perf import compare

    entry = entry_mod.Entry(config, traffic, seed)
    fault = changed.get("fault")
    if fault:
        compare.plant_fault(entry, fault, gen_mod)
    # rows trained up to the last compared step and never again are read back
    # after the window, where the entry has a cache and a parameter server to read
    n_first = entry.snapshot_after[-1]
    watcher = (compare.RowWatcher(entry.keys, seed, n_first)
               if hasattr(entry, "held_rows") else None)
    # the producer runs through set-up, so that the window's batches are made
    # before the window opens and the generator takes no core from the program
    ahead = n_first + int(traffic["warmup_steps"]) + int(MAX_STEPS_PER_S * seconds) + 96
    stream = BatchStream(gen_mod.make(config, traffic, seed), depth=ahead,
                         tap=watcher.tap if watcher else None)
    try:
        entry.build()
        # the compared steps: the same object, through the window's own call
        observed = compare.observe_program(entry, stream)
        mark, t_warm, taken = meter.mark(), time.perf_counter(), stream.taken
        entry.warm_up(stream)
        # the warm-up's time a step, its compiles left out: how long the traced slice runs
        step_s = max(0.0, time.perf_counter() - t_warm - meter.since(mark)["compile_s"]) / max(
            1, stream.taken - taken)
        if trace:
            entry.install_probes()
        mark = meter.mark()
        setup_s = time.perf_counter() - t_process_start
        tracer = None
        if trace and rehearsal is None:
            from perf import trace_reduce

            tracer = trace_reduce.WindowTracer(os.path.join(root, ".perf_trace"), seconds, step_s)
            print(f"trace slice {tracer.length:.3f} s, warm-up {step_s * 1e3:.2f} ms a step",
                  file=sys.stderr)
            tracer.start()
        wait0, taken0 = stream.wait_s, stream.taken
        window = entry.run_window(stream, seconds)
        if tracer is not None:
            tracer.finish()
        compiled = meter.since(mark)
        window["gen_wait_s"] = stream.wait_s - wait0
        window["seconds"] = window["t1"] - window["t0"]
        after = watcher.read(entry) if watcher else {}
    finally:
        stream.close()
    peak = memory_peak_bytes(cell["chips"]) if rehearsal is None else 0
    counters = entry.counters()
    step_programs = entry.step_programs()
    entry.free()

    # the reference runs once the window has closed, the peak has been read
    # and the program's state is freed
    t_ref = time.perf_counter()
    verdict = compare.judge(config, entry, observed, after, seed, workload=workload,
                            root=root, reference=changed.get("reference"))
    reference_s = time.perf_counter() - t_ref

    chips = cell["chips"]
    facts = {
        "cell": cell, "config": config, "traffic": traffic, "window": window,
        "setup_s": setup_s, "compiled_in_window": compiled, "counters": counters,
        "device": device, "chips": chips,
        "memory_peak_bytes": peak, "trace": None, "root": root,
    }
    dev_out = dict(device, memory_peak_bytes=peak)
    breakdown = None
    if tracer is not None:
        from perf import trace_reduce

        facts["trace"] = tracer.reduce(step_programs, chips, dump_trace)
        print(f"trace whole steps {facts['trace']['steps']} over {facts['trace']['window_s']:.4f} s",
              file=sys.stderr)
        dev_out["busy_s"] = facts["trace"]["busy_s"]
        dev_out["window_s"] = facts["trace"]["window_s"]
        breakdown = facts["trace"]["breakdown"]

    metrics: Dict[str, dict] = {}
    if not trace:
        values = {
            "samples_per_s_chip": window["samples"] / window["seconds"] / chips,
            "setup_s": setup_s,
        }
        for m in cell_metrics(bench, workload, "end_to_end"):
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        for m in cell_metrics(bench, workload, "per_layer"):
            spec = load_metric(m["name"], root)
            reader = load_module("readers", spec["reader"], root)
            value = reader.read(facts)
            if value is not None:  # a reader that finds nothing returns nothing
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    result = {
        "correct": bool(verdict["correct"]),
        "attempted": window["steps"], "failed": 0,
        "metrics": metrics, "device": dev_out,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
        result["op_s"] = facts["trace"]["op_s"]  # every device op, where the breakdown keeps ten
    if rehearsal is not None:
        result["rehearsal"] = True
    result["steps"] = window["steps"]
    result["window_s"] = window["seconds"]
    result["reference_s"] = reference_s
    result["compiles_in_window"] = compiled["programs"]
    result["by_leaf"] = verdict["by_leaf"]
    if "keep" in changed:  # limits_study: the batches, readings and reference of this run
        changed["keep"].update(observed=observed, after=after, reference=verdict["reference"])
    result["compared"] = verdict["compared"]  # each number beside its limit: last key
    for name, (value, limit) in verdict["compared"].items():
        print(f"compared {name} {value:.6g} limit {limit:.6g}", file=sys.stderr)
    print(f"correct {result['correct']}", file=sys.stderr, flush=True)
    return result
