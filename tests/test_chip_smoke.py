"""chip_smoke.py and the no-fallback rules, as far as a CPU can check them.

The smoke itself only means something on the chip; here its phase
functions run at a tiny size (kernel interpreted), and the entry points
that must refuse a CPU are shown to refuse it.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)  # chip_smoke.py and perf/ live at the repo root

import chip_smoke as cs  # noqa: E402

TINY = cs.Shape(
    batch=64, n_slots=4, vocab=1000, bottom_mlp=(32, 16), top_mlp=(32,),
    cache_rows=320, store_capacity=1 << 16, dispatch_k=2,
)


def _run(script, **env):
    """``python <script>`` from the repo root on the CPU backend."""
    return subprocess.run(
        [sys.executable, os.path.join(REPO, script)], cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **env},
        capture_output=True, text=True, timeout=120,
    )


def test_chip_smoke_refuses_cpu():
    r = _run("chip_smoke.py")
    assert r.returncode != 0
    assert "not 'tpu'" in r.stderr and "'cpu'" in r.stderr, r.stderr[-500:]
    assert '"ok"' not in r.stdout  # no result line


def test_batches_come_from_the_benchmarks_generator():
    a = list(cs._batches(TINY, seed=3, steps=2, learnable=True))
    b = list(cs._batches(TINY, seed=3, steps=2, learnable=True))
    for x, y in zip(a, b):  # one seed, one stream
        for fx, fy in zip(x.id_type_features, y.id_type_features):
            assert fx.name == fy.name
            np.testing.assert_array_equal(fx.flat_counts()[0], fy.flat_counts()[0])
        np.testing.assert_array_equal(
            x.non_id_type_features[0].data, y.non_id_type_features[0].data
        )
        np.testing.assert_array_equal(x.labels[0].data, y.labels[0].data)
    for batch in a:
        assert [f.name for f in batch.id_type_features] == [
            f"cat_{i}" for i in range(TINY.n_slots)
        ]
        for f in batch.id_type_features:
            ids, counts = f.flat_counts()
            assert ids.shape == (TINY.batch,) and (counts == 1).all()
            assert ids.min() >= 0 and ids.max() < TINY.vocab
        dense = batch.non_id_type_features[0].data
        assert dense.shape == (TINY.batch, 13)
        np.testing.assert_array_equal(
            batch.labels[0].data,
            (dense.sum(1, keepdims=True) > 0).astype(np.float32),
        )


def test_cached_phase_tiny_evicts_and_packs():
    a = cs.phase_cached(TINY, steps=16)
    assert a["rows_evicted"] > 0 and a["ps_rows"] > 0
    assert a["packs"] > 0 and a["packed_steps"] > 0
    assert a["degraded_steps"] == 0
    # what main() prints for a phase is one JSON object
    json.dumps({k: v for k, v in a.items() if k != "params"})


@pytest.mark.slow  # two more cached streams; the bit-identity itself is
# pinned on CPU by test_stream_deterministic_under_flush_timing
def test_cached_phase_tiny_is_deterministic():
    a = cs.phase_cached(TINY, steps=16)
    b = cs.phase_cached(TINY, steps=16)
    assert a["loss"] == b["loss"] and a["params_digest"] == b["params_digest"]


def test_cached_phase_tiny_mixed_tier_returns_ps_gradients():
    m = cs.phase_cached(TINY, steps=6, n_ps_slots=1)
    assert m["ps_grad_updates"] > 0
    assert m["packed_steps"] == 0  # a PS-tier forward is never packed


def test_other_trainer_phases_tiny():
    h = cs.phase_hybrid(TINY, steps=5)
    assert np.isfinite(h["loss"]) and h["ps_rows"] > 0
    p = cs.phase_pinned(TINY, steps=3)
    assert np.isfinite(p["loss"])
    assert p["table_rows"] == TINY.n_slots * TINY.vocab
    assert p["fit"]["fits"] == "not measured"  # CPU reports no allocator stats


def test_kernel_phase_interpreted():
    k = cs.phase_kernel(shapes=((40, 16),), interpret=True, block_q=16, block_k=32)
    assert k["cases"] == 4 and k["max_abs_err"]["float32"] < 1e-5


def test_sequence_kernels_phase_interpreted():
    k = cs.phase_sequence_kernels(length=64, heads=2, chunk=16, tile=16, interpret=True)
    assert set(k["delta_rule"]) == {"forward", "d0", "d1", "d2", "d3", "d4"}
    assert set(k["latent_attention"]) == {"forward", "d0", "d1", "d2", "d3"}
    assert max(k["latent_attention"].values()) < 1e-4  # float32 operands here: exact but for the sums' order
    # the layer with a low-rank query and rotated columns: bfloat16 operands in its projections
    assert set(k["rotated_latent_attention"]) == {"forward"} | {f"d{i}" for i in range(8)}
    assert max(k["rotated_latent_attention"].values()) < 3e-2


@pytest.mark.slow  # a second and third cached stream: ~7 s of CPU compiles
def test_multichip_phase_on_virtual_devices():
    r = cs.phase_multichip(TINY, steps=12, n_devices=4)
    assert len(r["batch_shards"]) == 4
    assert r["loss_diff"] <= cs.DP_LOSS_ATOL


def test_link_phase_reports_both_sides_of_the_first_d2h():
    r = cs.phase_link()
    assert set(r) == {"link", "dispatch_before_first_d2h", "dispatch_after_d2h"}
    assert r["link"]["d2h_MBps"] > 0


def test_compile_cache_helper_path_is_fixed_and_yields_to_the_env(monkeypatch):
    from persia_tpu.compile_cache import enable_compile_cache

    fixed = os.path.join(REPO, ".jax_cache")
    existed = os.path.exists(fixed)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert enable_compile_cache() == fixed
    # a second process, started elsewhere, derives the same path
    r = subprocess.run(
        [sys.executable, "-c",
         "from persia_tpu.compile_cache import enable_compile_cache as f; "
         "print(f())"],
        cwd="/", capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO},
    )
    assert r.returncode == 0, r.stderr[-500:]
    assert r.stdout.strip().splitlines()[-1] == fixed
    # where the operator placed the cache, the helper names that and sets
    # nothing (JAX reads the variable itself)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert enable_compile_cache() == "/some/dir"
    assert os.path.exists(fixed) == existed  # naming it creates nothing


def test_compile_meter_counts_a_compile():
    import jax
    import jax.numpy as jnp

    from persia_tpu.compile_cache import CompileMeter

    meter = CompileMeter()
    mark = meter.mark()
    jax.jit(lambda x: x * 3 + 7)(jnp.ones((5, 3))).block_until_ready()
    seen = meter.since(mark)
    assert seen["programs"] >= 1 and seen["compile_s"] >= 0
    assert seen["persistent_cache_hits"] == 0  # nothing is cached on CPU
