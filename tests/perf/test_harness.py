"""The harness is driven by data: every file loads by name, the metrics and
cells agree, a run without a TPU prints no result, a rehearsal at a tiny
preset drives load, generate, train and compare end to end, a planted fault
comes out as not correct, and a new cell, traffic mix and metric, and a cell
of a model of another architecture with its reference, work counts, generator
and entry, are taken as new files without an edit to an existing one."""

import json
import os
import re
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

import perf_presets as presets
from perf import compare, harness

ROOT = presets.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(autouse=True)
def _restore_matmul_precision():
    """A run sets the configuration's matmul precision for its process; a test
    worker goes on to other files, so put it back."""
    import jax

    before = jax.config.jax_default_matmul_precision
    yield
    jax.config.update("jax_default_matmul_precision", before)


def _files(kind):
    return sorted(f[:-5] for f in os.listdir(os.path.join(ROOT, "perf", kind)) if f.endswith(".json"))


@pytest.mark.parametrize("name", _files("configs"))
def test_config_file_loads(name):
    """What the harness asks of any model's configuration: it names its model,
    whose reference and work counts load by that name; the sizes are the
    model's own business (``test_counts.py`` holds DLRM's)."""
    cfg = harness.load_config(name)
    assert cfg["name"] == name and NAME.match(name) and NAME.match(cfg["model"])
    reference = harness.model_module("reference", cfg)
    assert callable(reference.make) and reference.CONTROLS
    assert cfg["reference"] == f"perf/reference/{cfg['model']}.py"
    work = harness.model_module("work", cfg)
    assert callable(work.train_flops_per_sample) and callable(work.step_hbm_bytes)
    assert {"math_dtype", "matmul_precision"} <= set(cfg["guarantees"])
    assert {"sparse_optimizer", "dense_optimizer"} <= set(cfg)


def test_a_configuration_that_names_no_model_is_an_error(tmp_path):
    os.makedirs(tmp_path / "perf" / "configs")
    cfg = {k: v for k, v in harness.load_config("dlrm-mlperf-1tb").items() if k != "model"}
    with open(tmp_path / "perf" / "configs" / "nameless.json", "w") as f:
        json.dump(cfg, f)
    with pytest.raises(SystemExit, match="names no model"):
        harness.load_config("nameless", str(tmp_path))


@pytest.mark.parametrize("name", _files("traffic"))
def test_traffic_file_loads(name):
    tr = harness.load_traffic(name)
    assert tr["name"] == name and NAME.match(name)
    generator = harness.load_module("generators", tr["generator"])
    assert callable(generator.make) and callable(generator.halve)
    assert hasattr(harness.load_module("entries", tr["entry"]), "Entry")


@pytest.mark.parametrize("name", _files("metrics"))
def test_metric_file_loads(name):
    m = harness.load_metric(name)
    assert m["name"] == name and NAME.match(name) and UNIT.match(m["unit"])
    assert m["source"] in SOURCES and m["better"] in ("lower", "higher")
    assert callable(harness.load_module("readers", m["reader"]).read)


def test_benchmark_json_agrees_with_the_files():
    bench = harness.load_benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    cells = {w["name"]: w for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for c in bench["configs"]:
        assert c["file"] == f"perf/configs/{c['name']}.json"
        assert harness.load_config(c["name"])["source"] == c["source"]
        assert any(w["config"] == c["name"] for w in cells.values())
    # a cell takes four chips only for what exists only across chips: at most
    # a quarter of the cells, and one always may
    four = [w for w in cells.values() if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)
    for w in cells.values():
        assert NAME.match(w["name"]) and w["chips"] in (1, 4) and len(w["why"]) <= 200
        harness.load_config(w["config"]), harness.load_traffic(w["traffic"])
        assert compare.load_limits(w["name"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["source"] in SOURCES
    for m in bench["per_layer"]:
        spec = harness.load_metric(m["name"])
        for k in ("unit", "better", "source", "layer", "moves"):
            assert spec[k] == m[k], (m["name"], k)
        assert m["moves"] in e2e
        # each cell a metric lists (none listed: every cell) reports the
        # end-to-end metric it moves
        for w in m.get("workloads", list(cells)):
            assert w in cells
            assert w in e2e[m["moves"]].get("workloads", list(cells))
    for w in cells:
        assert harness.cell_metrics(bench, w, "per_layer")


def test_without_a_tpu_no_result_is_printed():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "perf/run.py", "--workload", "tb-pinned-share16", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "metrics" not in p.stdout and "TPU" in p.stderr


def _rehearse(entry_name, trace=False, fault=None, root=ROOT, workload=None, seed=2 ** 31 + 23):
    preset = dict(presets.REHEARSAL[entry_name])
    if fault:
        preset["fault"] = fault
    return harness.run_cell(workload or presets.CELL_OF_ENTRY[entry_name], seed, 0.6,
                            trace, time.perf_counter(), rehearsal=preset, root=root)


@pytest.mark.parametrize("entry_name", ["fused_pinned", "cached_stream"])
def test_rehearsal_end_to_end(entry_name):
    out = _rehearse(entry_name)
    assert out["rehearsal"] is True and out["device"]["platform"] == "rehearsal"
    assert out["correct"] is True, out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"samples_per_s_chip", "setup_s"}
    assert list(out)[-1] == "compared"  # each number beside its limit, last
    for value, limit in out["compared"].values():
        assert value <= limit
    json.dumps(out)


def test_rehearsal_traced_reports_counters_but_no_device_metric():
    out = _rehearse("cached_stream", trace=True)
    got = set(out["metrics"])
    assert {"gen_wait_share", "feeder_util", "h2d_bytes_per_sample", "compiles_in_window",
            "packed_step_frac"} <= got
    # nothing read from a device trace or the device's memory on a CPU
    assert not got & {"mfu", "train_step_roofline", "device_idle_share", "device_ms_per_step",
                      "step_ms_p95", "device_hbm_peak_gb"}
    assert "busy_s" not in out["device"] and "breakdown" not in out
    assert out["metrics"]["packed_step_frac"]["value"] > 0.5


@pytest.mark.parametrize("entry_name", ["fused_pinned", "cached_stream"])
@pytest.mark.parametrize("fault", compare.FAULTS)
def test_a_broken_timed_path_is_not_correct(entry_name, fault):
    """The rest of a run with the timed path broken underneath: a step that
    returns its state unchanged; half of the batch left out, the mean taken
    over the rest. (One chip: no exchange to leave out; a training cell
    produces no token or answer to alter.)"""
    out = _rehearse(entry_name, fault=fault)
    assert out["correct"] is False
    assert any(v > lim for v, lim in out["compared"].values())


def test_new_cell_traffic_and_metric_are_new_files_only(tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "perf"), os.path.join(root, "perf"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {}
    for d, _s, fs in os.walk(os.path.join(root, "perf")):
        for f in fs:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                before[p] = fh.read()
    bench = harness.load_benchmark()
    cfg = dict(harness.load_config("dlrm-mlperf-1tb"), name="dlrm-throwaway", embedding_dim=64)
    with open(os.path.join(root, "perf", "configs", "dlrm-throwaway.json"), "w") as f:
        json.dump(cfg, f)
    traffic = dict(harness.load_traffic("zipf105-pinned-share16"), name="zipf120-throwaway",
                   zipf_a=1.2)
    with open(os.path.join(root, "perf", "traffic", "zipf120-throwaway.json"), "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(root, "perf", "limits", "throwaway-pinned.json"), "w") as f:
        json.dump({"limits": compare.load_limits("tb-pinned-share16")}, f)
    metric = {"name": "steps_in_window", "unit": "steps", "better": "higher",
              "source": "program_counter", "layer": "device step",
              "moves": "samples_per_s_chip", "workloads": ["throwaway-pinned"],
              "reader": "steps_in_window"}
    with open(os.path.join(root, "perf", "metrics", "steps_in_window.json"), "w") as f:
        json.dump(metric, f)
    with open(os.path.join(root, "perf", "readers", "steps_in_window.py"), "w") as f:
        f.write("def read(facts):\n    return float(facts['window']['steps'])\n")
    bench["configs"].append({"name": cfg["name"], "source": cfg["source"], "reduced": [],
                             "file": "perf/configs/dlrm-throwaway.json", "why": "a test"})
    bench["workloads"].append({"name": "throwaway-pinned", "config": "dlrm-throwaway",
                               "traffic": "zipf120-throwaway", "chips": 1, "why": "a test"})
    bench["per_layer"].append({k: v for k, v in metric.items() if k != "reader"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    out = _rehearse("fused_pinned", trace=True, root=root, workload="throwaway-pinned")
    assert out["correct"] is True
    assert out["metrics"]["steps_in_window"]["value"] == out["attempted"]
    for p, data in before.items():
        with open(p, "rb") as fh:
            assert fh.read() == data, f"{p} was edited"


# ------------------------------------------- a cell of another architecture
# A tower that is no DLRM: one raw (B, L, d) slot, one matrix, no MLP keys; a
# sample's work grows with the traffic's length. Every file below is new.

THROWAWAY = {
    "perf/configs/seq-throwaway.json": json.dumps({
        "name": "seq-throwaway", "model": "throwaway", "source": "a test", "dim": 8, "rows": 997,
        "sparse_optimizer": {"kind": "adagrad", "lr": 0.05, "initial_accumulator": 0.01, "eps": 1e-10},
        "dense_optimizer": {"kind": "adam", "lr": 0.001, "b1": 0.9, "b2": 0.999, "eps": 1e-08},
        "guarantees": {"math_dtype": "float32", "matmul_precision": "highest"},
        "reference": "perf/reference/throwaway.py"}),
    "perf/traffic/seq12-throwaway.json": json.dumps({
        "name": "seq12-throwaway", "generator": "uniform_seq", "entry": "plain_seq",
        "batch": 64, "seq_len": 12, "warmup_steps": 2}),
    "perf/traffic/seq20-throwaway.json": json.dumps({
        "name": "seq20-throwaway", "generator": "uniform_seq", "entry": "plain_seq",
        "batch": 64, "seq_len": 20, "warmup_steps": 2}),
    "perf/limits/throwaway-seq12.json": json.dumps({"limits": {
        "loss_gap": 1e-05, "grad_gap": 3e-05, "change_gap": 5e-05, "acc_gap": 1e-04}}),
    # the same cell at another length, with a limit no reading can meet on the
    # number that only the model's reference knows
    "perf/limits/throwaway-seq20.json": json.dumps({"limits": {"loss_gap": 1e-05, "acc_gap": -1.0}}),
    "perf/generators/uniform_seq.py": '''
import numpy as np


def make(config, traffic, seed):
    rng = np.random.Generator(np.random.PCG64([int(seed), 7]))
    shape = (int(traffic["batch"]), int(traffic["seq_len"]))
    while True:
        yield {"tokens": rng.integers(0, config["rows"], shape),
               "y": (rng.random((shape[0], 1)) < 0.5).astype(np.float32)}


def halve(b):
    h = len(b["y"]) // 2
    return {"tokens": b["tokens"][:h], "y": b["y"][:h]}
''',
    "perf/entries/plain_seq.py": '''
import time

import numpy as np

from perf import weights


class Entry:
    snapshot_after = (1, 3)

    def __init__(self, config, traffic, seed):
        self.config, self.traffic, self.seed = config, traffic, int(seed)

    def build(self):
        import jax
        import jax.numpy as jnp

        c, so, do = self.config, self.config["sparse_optimizer"], self.config["dense_optimizer"]
        lo, hi = weights.seed_words(self.seed)
        w = jnp.asarray(weights.hashed_uniform(lo, hi, 1, np.arange(c["dim"]), 1, 0.5))
        self.state = {
            "table": jnp.asarray(weights.hashed_uniform(lo, hi, 0, np.arange(c["rows"]), c["dim"], 0.5)),
            "acc": jnp.full((c["rows"], c["dim"]), so["initial_accumulator"], jnp.float32),
            "w": w, "mu": jnp.zeros_like(w), "nu": jnp.zeros_like(w), "t": jnp.zeros(())}

        def loss_fn(table, w, tokens, y):
            z = (table[tokens] @ w).mean(axis=1)  # the raw (B, L, d) slot through one matrix
            return jnp.mean(y * jax.nn.softplus(-z) + (1.0 - y) * jax.nn.softplus(z))

        @jax.jit
        def step(s, tokens, y):
            loss, (g_t, g_w) = jax.value_and_grad(loss_fn, (0, 1))(s["table"], s["w"], tokens, y)
            acc = s["acc"] + g_t * g_t
            t = s["t"] + 1.0
            mu = do["b1"] * s["mu"] + (1.0 - do["b1"]) * g_w
            nu = do["b2"] * s["nu"] + (1.0 - do["b2"]) * g_w * g_w
            w = s["w"] - do["lr"] * (mu / (1.0 - do["b1"] ** t)) / (
                jnp.sqrt(nu / (1.0 - do["b2"] ** t)) + do["eps"])
            return loss, {"table": s["table"] - so["lr"] * g_t / jnp.sqrt(acc + so["eps"]),
                          "acc": acc, "w": w, "mu": mu, "nu": nu, "t": t}

        self._step = step

    def keys(self, b):
        return b["tokens"].astype(np.uint64)

    def to_program_batch(self, b):
        return b

    def _train(self, b):
        b = self.to_program_batch(b)
        loss, self.state = self._step(self.state, b["tokens"], b["y"])
        return loss

    def compared_run(self, batches):
        return [float(self._train(b)) for b in batches]

    def snapshot(self, keys):
        s, at = self.state, np.asarray(keys, np.int64)
        return {"dense": {"proj": np.asarray(s["w"])}, "adam_mu": {"proj": np.asarray(s["mu"])},
                "rows": np.asarray(s["table"])[at], "acc": np.asarray(s["acc"])[at]}

    def warm_up(self, stream):
        for _ in range(int(self.traffic["warmup_steps"])):
            self._train(next(stream))

    def run_window(self, stream, seconds):
        t0, steps = time.perf_counter(), 0
        while time.perf_counter() - t0 < seconds:
            loss = self._train(next(stream))
            steps += 1
        loss.block_until_ready()
        return {"steps": steps, "samples": steps * int(self.traffic["batch"]),
                "t0": t0, "t1": time.perf_counter()}

    def install_probes(self):
        pass

    def counters(self):
        return {"h2d_bytes": 0}

    def step_programs(self):
        return {"jit_step": 1}

    def free(self):
        self.state = None
''',
    "perf/reference/throwaway.py": '''
"""numpy, float64, the gradients written out; the control rounds the
product's operands to bfloat16."""
import numpy as np

from perf import weights

CONTROLS = ("bf16_operands",)


def _bf16(x):
    u = np.asarray(x, np.float32).view(np.uint32)
    u = (u + np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1))) & np.uint32(0xFFFF0000)
    return u.view(np.float32).astype(np.float64)


class Reference:
    def __init__(self, config, seed, rounded):
        lo, hi = weights.seed_words(seed)
        c = self.c = config
        self.table = weights.hashed_uniform(lo, hi, 0, np.arange(c["rows"]), c["dim"], 0.5).astype(np.float64)
        self.acc = np.full_like(self.table, c["sparse_optimizer"]["initial_accumulator"])
        self.w = weights.hashed_uniform(lo, hi, 1, np.arange(c["dim"]), 1, 0.5).astype(np.float64)
        self.mu, self.nu, self.t = np.zeros_like(self.w), np.zeros_like(self.w), 0
        self.cast = _bf16 if rounded else (lambda x: x)

    def step(self, batch, keys):
        so, do = self.c["sparse_optimizer"], self.c["dense_optimizer"]
        tokens, y = np.asarray(keys, np.int64), batch["y"].astype(np.float64)
        n, length = tokens.shape
        e, w = self.cast(self.table[tokens]), self.cast(self.w)
        z = (e @ w).mean(axis=1)
        loss = np.mean(y * np.logaddexp(0.0, -z) + (1.0 - y) * np.logaddexp(0.0, z))
        dz = self.cast((1.0 / (1.0 + np.exp(-z)) - y) / n)  # (B, 1)
        g_w = np.einsum("bld,bo->do", e, dz) / length
        g_t = np.zeros_like(self.table)
        np.add.at(g_t, tokens, (dz / length)[:, None, :] * w[:, 0])
        self.acc += g_t * g_t
        self.table -= so["lr"] * g_t / np.sqrt(self.acc + so["eps"])
        self.t += 1
        self.mu = do["b1"] * self.mu + (1.0 - do["b1"]) * g_w
        self.nu = do["b2"] * self.nu + (1.0 - do["b2"]) * g_w * g_w
        self.w = self.w - do["lr"] * (self.mu / (1.0 - do["b1"] ** self.t)) / (
            np.sqrt(self.nu / (1.0 - do["b2"] ** self.t)) + do["eps"])
        return float(loss)

    def lookup(self, keys):
        at = np.asarray(keys, np.int64)
        return self.table[at], self.acc[at]

    def snapshot(self, keys):
        rows, acc = self.lookup(keys)
        return {"dense": {"proj": self.w.copy()}, "adam_mu": {"proj": self.mu.copy()},
                "rows": rows, "acc": acc}


def make(config, seed, entry, control=None):
    if control not in (None,) + CONTROLS:
        raise ValueError(control)
    return Reference(config, seed, rounded=control is not None)


def extra_readings(program, reference):
    """A number of this model's own: the accumulators' norm after the last step."""
    last = max(program["snaps"])
    p = np.linalg.norm(np.asarray(program["snaps"][last]["acc"], np.float64))
    r = np.linalg.norm(np.asarray(reference["snaps"][last]["acc"], np.float64))
    return {"acc_gap": abs(p - r) / r}
''',
    "perf/work/throwaway.py": '''
def train_flops_per_sample(config, traffic):
    return 6.0 * int(traffic["seq_len"]) * config["dim"]  # one (d, 1) product a token


def step_hbm_bytes(config, traffic):
    rows = int(traffic["batch"]) * int(traffic["seq_len"])
    return float(rows * config["dim"] * 4 * 6 + config["dim"] * 4 * 7)
''',
}
THROWAWAY_CELLS = {"throwaway-seq12": "seq12-throwaway", "throwaway-seq20": "seq20-throwaway"}
DLRM_MODULES = ("perf.reference.dlrm", "perf.work.dlrm")


def _all_bytes(root):
    out = {}
    for d, _s, fs in os.walk(os.path.join(root, "perf")):
        if "__pycache__" in d:
            continue
        for f in fs:
            with open(os.path.join(d, f), "rb") as fh:
                out[os.path.join(d, f)] = fh.read()
    return out


@pytest.fixture(scope="module")
def other_model(tmp_path_factory):
    """A copy of ``perf/`` to which the throwaway model's cells come as new
    files and ``BENCHMARK.json`` entries; gives the root and the bytes of
    every file that was there."""
    root = str(tmp_path_factory.mktemp("other_model"))
    shutil.copytree(os.path.join(ROOT, "perf"), os.path.join(root, "perf"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _all_bytes(root)
    for rel, text in THROWAWAY.items():
        path = os.path.join(root, rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        assert not os.path.exists(path), f"{rel} is no new file"
        with open(path, "w") as f:
            f.write(text)
    bench = harness.load_benchmark()
    bench["configs"].append({"name": "seq-throwaway", "source": "a test", "reduced": [],
                             "file": "perf/configs/seq-throwaway.json", "why": "a test"})
    for cell, traffic in THROWAWAY_CELLS.items():
        bench["workloads"].append({"name": cell, "config": "seq-throwaway", "traffic": traffic,
                                   "chips": 1, "why": "a test"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root, before


def _control_verdict(root, workload, seed):
    """The throwaway's control in the program's place, through ``judge``."""
    cell = harness.find_cell(harness.load_benchmark(root), workload)
    cfg, tr = harness.load_config(cell["config"], root), harness.load_traffic(cell["traffic"], root)
    entry = harness.load_module("entries", tr["entry"], root).Entry(cfg, tr, seed)
    gen = harness.load_module("generators", tr["generator"], root).make(cfg, tr, seed)
    first = [next(gen) for _ in range(entry.snapshot_after[-1])]
    keys = np.unique(np.concatenate([entry.keys(b).reshape(-1) for b in first]))
    controls = harness.model_module("reference", cfg, root).CONTROLS
    control = compare.run_reference(cfg, entry, [], first, seed, keys, control=controls[0], root=root)
    control.update(lead=[], first=first)
    return compare.judge(cfg, entry, control, None, seed, workload, root=root)


def _device_metrics_from_the_recorded_trace(root, workload):
    """``mfu`` and ``train_step_roofline`` as a traced run of the cell reads
    them, from the facts ``run_cell`` hands a reader and the three steps
    recorded on the v5e under a host tracer that covered them (the first
    begins at the record's first instant: two are whole)."""
    from perf import trace_reduce

    bench = harness.load_benchmark(root)
    cell = harness.find_cell(bench, workload)
    with open(os.path.join(root, "perf", "fixtures", "trace_pinned_3steps.json")) as f:
        events = presets.host_tracer_over([tuple(e) for e in json.load(f)])
    trace = trace_reduce.reduce_events(events, {"jit_step": 1}, 1)
    facts = {"cell": cell, "config": harness.load_config(cell["config"], root),
             "traffic": harness.load_traffic(cell["traffic"], root), "trace": trace,
             "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}, "chips": 1,
             "root": root}
    names = {m["name"] for m in harness.cell_metrics(bench, workload, "per_layer")}
    assert {"mfu", "train_step_roofline"} <= names  # no workloads list: every cell reports them
    read = {n: harness.load_module("readers", harness.load_metric(n, root)["reader"], root).read(facts)
            for n in ("mfu", "train_step_roofline")}
    return read, trace


@pytest.mark.parametrize("case", ["sound", "half_batch", "control", "limit_on_a_number_of_its_own",
                                  "work_counts"])
def test_new_cell_of_another_model_is_new_files_only(other_model, case, monkeypatch):
    root, before = other_model
    seed = 2 ** 31 + 101
    loaded = []
    load = harness.load_module
    monkeypatch.setattr(harness, "load_module",
                        lambda kind, name, root=ROOT: loaded.append((kind, name)) or load(kind, name, root))
    for mod in DLRM_MODULES:  # another test of this worker may have imported them
        monkeypatch.delitem(sys.modules, mod, raising=False)

    def run(workload, **preset):
        return harness.run_cell(workload, seed, 0.3, False, time.perf_counter(),
                                rehearsal=preset, root=root)

    if case == "sound":
        out = run("throwaway-seq12")
        assert out["correct"] is True, out["compared"]
        assert set(out["compared"]) == {"loss_gap", "grad_gap", "change_gap", "acc_gap"}
        assert set(out["by_leaf"]["grad"]) == {"proj", "table"}  # the leaves the model names
        assert out["attempted"] > 0 and set(out["metrics"]) == {"samples_per_s_chip", "setup_s"}
    elif case == "half_batch":
        out = run("throwaway-seq12", fault="half_batch")  # cut by the generator's own ``halve``
        assert out["correct"] is False
        assert out["compared"]["grad_gap"][0] > 0.01
    elif case == "control":
        verdict = _control_verdict(root, "throwaway-seq12", seed)
        assert verdict["correct"] is False, verdict["compared"]
    elif case == "limit_on_a_number_of_its_own":
        out = run("throwaway-seq20")
        assert out["compared"]["acc_gap"][1] == -1.0 and out["compared"]["loss_gap"][0] <= 1e-05
        assert out["correct"] is False  # on ``extra_readings``'s number alone
        assert "grad_gap" not in out["compared"]  # no limit, not judged
    else:
        for workload, length in (("throwaway-seq12", 12), ("throwaway-seq20", 20)):
            read, trace = _device_metrics_from_the_recorded_trace(root, workload)
            flops, hbm = 6.0 * length * 8, 64 * length * 8 * 4 * 6 + 8 * 4 * 7
            assert trace["steps"] == 2
            assert read["mfu"] == pytest.approx(
                100.0 * flops * (2 * 64 / trace["window_s"]) / 197e12, rel=1e-12)
            assert read["train_step_roofline"] == pytest.approx(
                100.0 * max(flops * 64 / 197e12, hbm / 819e9) * 1e3 / trace["device_ms_per_step"],
                rel=1e-12)
    # nothing of DLRM's was asked for, and every file that was there is what it was
    assert ("reference", "throwaway") in loaded or ("work", "throwaway") in loaded
    assert not {("reference", "dlrm"), ("work", "dlrm")} & set(loaded)
    assert not set(DLRM_MODULES) & set(sys.modules)
    assert _all_bytes(root).items() >= before.items()
