"""``perf/reference/dlrm.py`` against the program at a small size on the CPU:
forward, loss and gradients against ``persia_tpu/models/dlrm.py``, a cached
run of a few dozen evicting steps against the reference's dictionary of rows,
and the control (the reference one precision down) against the limits."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import perf_presets as presets
from perf import compare, harness, weights
from perf.generators.zipf import ZipfBatches
from perf.reference import dlrm as ref



@pytest.fixture(autouse=True, scope="module")
def _stated_precision():
    """The configuration's arithmetic for this file, put back afterwards."""
    before = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    yield
    jax.config.update("jax_default_matmul_precision", before)


def _small(entry_name):
    cell = presets.CELL_OF_ENTRY[entry_name]
    bench = harness.load_benchmark()
    c = harness.find_cell(bench, cell)
    cfg = dict(harness.load_config(c["config"]), **presets.REHEARSAL[entry_name]["config"])
    tr = dict(harness.load_traffic(c["traffic"]), **presets.REHEARSAL[entry_name]["traffic"])
    return cfg, tr


def test_forward_loss_and_gradients_match_the_program_model():
    import optax

    from persia_tpu.models import DLRM

    cfg, _ = _small("fused_pinned")
    dense = weights.dense_params(cfg, 11)
    rng = np.random.default_rng(0)
    b, s, d = 64, len(cfg["table_rows"]), cfg["embedding_dim"]
    emb = rng.normal(size=(b, s, d)).astype(np.float32) * 0.1
    x = rng.normal(size=(b, cfg["num_dense"])).astype(np.float32)
    y = (rng.random((b, 1)) < 0.5).astype(np.float32)
    model = DLRM(embedding_dim=d, bottom_mlp=tuple(cfg["bottom_mlp"]),
                 top_mlp=tuple(cfg["top_mlp"][:-1]), compute_dtype=jnp.float32)
    params = {f"Dense_{i}": {"kernel": jnp.asarray(k), "bias": jnp.asarray(bb)}
              for i, (k, bb) in enumerate(dense)}

    def prog_loss(p, e):
        logits = model.apply({"params": p}, [x], [e[:, i] for i in range(s)])
        return optax.sigmoid_binary_cross_entropy(logits, y).mean()

    def ref_loss(dp, e):
        return ref.bce_mean(ref.forward(dp, e, x, len(cfg["bottom_mlp"])), y)

    lp, (gp, gep) = jax.value_and_grad(prog_loss, argnums=(0, 1))(params, jnp.asarray(emb))
    lr, (gr, ger) = jax.value_and_grad(ref_loss, argnums=(0, 1))(
        [(jnp.asarray(k), jnp.asarray(bb)) for k, bb in dense], jnp.asarray(emb))
    assert float(lp) == pytest.approx(float(lr), rel=1e-6)
    np.testing.assert_allclose(np.asarray(gep), np.asarray(ger), rtol=1e-4, atol=1e-9)
    for i, (gk, gb) in enumerate(gr):
        np.testing.assert_allclose(np.asarray(gp[f"Dense_{i}"]["kernel"]), np.asarray(gk),
                                   rtol=1e-4, atol=1e-9)
        np.testing.assert_allclose(np.asarray(gp[f"Dense_{i}"]["bias"]), np.asarray(gb),
                                   rtol=1e-4, atol=1e-9)


def test_row_birth_rule_matches_the_parameter_server():
    from persia_tpu.embedding.hashing import uniform_init_for_signs

    signs = np.random.default_rng(1).integers(1, 1 << 62, 500).astype(np.uint64)
    np.testing.assert_array_equal(
        ref.splitmix_uniform_rows(signs, 12345, 16),
        uniform_init_for_signs(signs, 12345, 16, -0.01, 0.01))


def test_cached_tier_follows_the_reference_through_evictions():
    """Three dozen steps through ``train_stream`` with a pool a seventh of the
    vocabulary, so that it evicts and re-admits, the admission rule on: every
    loss, and what cache and PS together hold for every sign trained, against
    the reference's dictionary of rows."""
    from perf.entries.cached_stream import Entry

    cfg, tr = _small("cached_stream")
    tr = dict(tr, cache_rows=1024, resident_from_start=False)
    seed = 2 ** 31 + 5
    entry = Entry(cfg, tr, seed)
    entry.build()
    gen = iter(ZipfBatches(cfg, tr, seed))
    reference = ref.ReferenceDLRM(cfg, weights.dense_params(cfg, seed), entry.row_birth,
                                  adam_start=entry.adam_start)
    keys = []
    for step in range(36):
        b = next(gen)
        keys.append(entry.keys(b).reshape(-1))
        entry._stream([entry.to_program_batch(b)], 1)
        lp = float(entry.ctx.last_metrics()["loss"])
        lr = reference.step(entry.keys(b), b["dense"], b["labels"])
        assert lp == pytest.approx(lr, rel=2e-6), step
    keys = np.unique(np.concatenate(keys))
    assert len(keys) > 2 * tr["cache_rows"], "the pool never had to evict"
    resident = np.asarray(entry.ctx.tier.dirs[entry.group.name].probe(keys)) >= 0
    assert resident.any() and (~resident).any()
    rows, acc, found = entry.held_rows(keys)
    assert found.all()
    rr, ra = reference.lookup(keys)
    np.testing.assert_allclose(rows, rr, rtol=0, atol=2e-7)
    np.testing.assert_allclose(acc, ra, rtol=1e-5, atol=0)
    entry.free()


@pytest.mark.parametrize("workload", [w["name"] for w in harness.load_benchmark()["workloads"]])
@pytest.mark.parametrize("seed", [3, 2 ** 31 + 17, 3_000_000_019])
def test_control_one_precision_down_is_not_correct(workload, seed):
    """The control at a size a test run can hold (the configuration's own MLP
    widths, embedding width and batch, tables cut to 1/4096): the
    reference in the program's place,
    computed in three bfloat16 passes (``high``) where the configuration
    states ``highest``, fails at least one of the cell's limits through the
    harness's own ``judge``; the reference itself in the program's place
    passes them all."""
    cell = harness.find_cell(harness.load_benchmark(), workload)
    cfg = harness.load_config(cell["config"])
    cfg = dict(cfg, table_rows=[max(3, n // 4096) for n in cfg["table_rows"]])
    tr = dict(harness.load_traffic(cell["traffic"]), rows_divisor=1)
    entry = harness.load_module("entries", tr["entry"]).Entry(cfg, tr, seed)
    gen = iter(ZipfBatches(cfg, tr, seed))
    lead = []
    first = [next(gen) for _ in range(entry.snapshot_after[-1])]
    keys = np.unique(np.concatenate([entry.keys(b).reshape(-1) for b in first]))
    sound = compare.run_reference(cfg, entry, lead, first, seed, keys)
    control = compare.run_reference(cfg, entry, lead, first, seed, keys, passes=3)
    for run in (sound, control):
        run.update(lead=lead, first=first)
    verdicts = {}
    for name, run in (("sound", sound), ("control", control)):
        after = compare.held_by(run, keys[::7]) if hasattr(entry, "held_rows") else None
        verdicts[name] = compare.judge(cfg, entry, run, after, seed, workload, reference=sound)
    assert verdicts["sound"]["correct"] is True, verdicts["sound"]["compared"]
    assert verdicts["control"]["correct"] is False, verdicts["control"]["compared"]
