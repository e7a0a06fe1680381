"""The harness is driven by data: every file loads by name, the metrics and
cells agree, a run without a TPU prints no result, a rehearsal at a tiny
preset drives load, generate, train and compare end to end, a planted fault
comes out as not correct, and a new cell, traffic mix and metric are taken as
new files without an edit to an existing one."""

import json
import os
import re
import shutil
import subprocess
import sys
import time

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
    cfg = harness.load_config(name)
    assert cfg["name"] == name and NAME.match(name)
    assert len(cfg["table_rows"]) == 26 and cfg["bottom_mlp"][-1] == cfg["embedding_dim"]
    assert os.path.exists(os.path.join(ROOT, cfg["reference"]))
    assert {"math_dtype", "matmul_precision", "admission", "row_birth"} <= set(cfg["guarantees"])


@pytest.mark.parametrize("name", _files("traffic"))
def test_traffic_file_loads(name):
    tr = harness.load_traffic(name)
    assert tr["name"] == name and NAME.match(name)
    assert hasattr(harness.load_module("generators", tr["generator"]), "make")
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
    for w in cells.values():
        assert NAME.match(w["name"]) and w["chips"] == 1 and len(w["why"]) <= 200
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
