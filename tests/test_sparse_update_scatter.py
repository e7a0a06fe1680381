"""The sparse update's row write-back: which rows it may touch, the indices
it writes at, what it declares to XLA (ISSUE 27), and the row-write kernel
that takes the scatter's place where the layout allows (ISSUE 29).

(a) property tests over masked, unmasked and adversarial ids for every
    optimizer: the scatter index is strictly ascending; table and state equal
    a NumPy per-row loop to the parity tests' tolerance; rows no live id
    names are bit-identical. Not bit for bit on the CPU: its compiler
    contracts multiply-adds by the program's shape (this function at 8 and at
    27 rows a trip differs in Adam's first moment by 9e-10). On the chip the
    loop and the whole-N form it replaced are bit-equal (PERF.md, PR 27).
(b) structural tests on the StableHLO of a jitted ``sparse_update``: the
    flags on every scatter, that the row scatters sit in the loop over
    live rows, and that ``dedup`` moves its ids by two sorts and no gather.
(c) the row-write kernel (``_write_rows_dma``) through the Pallas TPU
    interpreter, which this file asks for itself: alone against a NumPy row
    assignment, and inside ``sparse_update`` with the DMA path forced, over
    (a)'s grid: equal to the NumPy row loop and bit-equal to the scatter path
    on the same inputs.
(d) which path each array gets (``_row_write_path``) and the flight event
    ``sparse_update.row_write`` that says so.
(e) ``dedup_gradients`` with its ids riding two sorts (ISSUE 31) against the
    form it replaced (an N-wide index gather and a ``uid`` scatter), kept
    here frozen: bit for bit, since no float sum changed its order.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from persia_tpu import tracing
from persia_tpu.embedding.optim import SGD, Adagrad, Adam
from persia_tpu.ops import sparse_update as su

INT32_MAX = np.iinfo(np.int32).max
V, D = 37, 16

OPTS = {
    "sgd_wd": SGD(lr=0.1, weight_decay=0.01),
    "adagrad": Adagrad(lr=0.05, g_square_momentum=0.95, weight_decay=0.01),
    "adagrad_vw": Adagrad(lr=0.05, vectorwise_shared=True),
    "adam": Adam(lr=0.01),
}


def _ids_cases():
    """name -> (ids, mask or None). n is 1, below, at and past a chunk of
    the row loop (the tests run it at 8 rows a trip), and not a multiple."""
    rng = np.random.default_rng(11)
    n = 27
    plain = rng.integers(0, V, n)
    adversarial = plain.copy()
    adversarial[[0, 5, 9, 13, 20, 26]] = [-1, V, V + n, INT32_MAX, -V, V + 3]
    some = rng.random(n) < 0.6
    return {
        "plain": (plain, None),
        "masked": (plain, some),
        "adversarial_unmasked": (adversarial, None),
        "adversarial_masked": (adversarial, some),
        "negative_masked_by_caller": (np.where(some, plain, -1), some),
        "all_duplicate": (np.full(n, 7), None),
        "all_masked": (plain, np.zeros(n, bool)),
        "all_out_of_range": (np.full(n, V), None),
        "all_distinct": (rng.permutation(V)[:n], None),
        "n_1": (np.array([V - 1]), None),
        "n_1_dead": (np.array([-1]), None),
        "one_chunk": (plain[:8], None),
        "two_chunks": (plain[:16], None),
    }


IDS_CASES = _ids_cases()


@pytest.fixture(autouse=True)
def small_chunks(monkeypatch):
    # the loop's partial-chunk and several-trip paths at a test's size
    monkeypatch.setattr(su, "_CHUNK_ROWS", 8)


def _live(ids, mask):
    live = (ids >= 0) & (ids < V)
    return live if mask is None else live & mask


def _inputs(cfg, ids, seed=3, dim=D):
    rng = np.random.default_rng(seed)
    table = rng.normal(size=(V, dim)).astype(np.float32)
    state = {k: np.asarray(v) + rng.random(v.shape).astype(np.float32)
             for k, v in su.init_sparse_state(cfg, V, dim).items()}
    grads = rng.normal(size=(len(ids), dim)).astype(np.float32)
    return table, state, grads


def _numpy_row_loop(cfg, table, state, ids, grads, mask, batch_state):
    """Per distinct live row: sum its gradients in input order, take the
    row's new value from the optimizer's row math, add the difference."""
    live = _live(ids, mask)
    rows = sorted(set(ids[live].tolist()))
    table = table.copy()
    state = {k: v.copy() for k, v in state.items()}
    for row in rows:
        gsum = np.zeros(grads.shape[1], np.float32)
        for i, g, ok in zip(ids, grads, live):
            if ok and i == row:
                gsum += g
        w = table[row:row + 1]
        st = {k: v[row:row + 1] for k, v in state.items()}
        new_w, new_st = su._apply_rows(
            cfg, jnp.asarray(w), {k: jnp.asarray(v) for k, v in st.items()},
            jnp.asarray(gsum[None]), jnp.asarray(batch_state, jnp.float32))
        table[row] += (np.asarray(new_w) - w)[0]
        for k in state:
            state[k][row] += (np.asarray(new_st[k]) - st[k])[0]
    return table, state, rows


def _run(cfg, table, state, ids, grads, mask, batch_state):
    t, s = jax.jit(lambda t, s, i, g, b, m: su.sparse_update(cfg, t, s, i, g, b, mask=m))(
        jnp.asarray(table), {k: jnp.asarray(v) for k, v in state.items()},
        jnp.asarray(ids, jnp.int32), jnp.asarray(grads), jnp.asarray(batch_state, jnp.float32),
        None if mask is None else jnp.asarray(mask))
    return np.asarray(t), {k: np.asarray(v) for k, v in s.items()}


@pytest.mark.parametrize("case", sorted(IDS_CASES))
def test_scatter_index_strictly_ascending(case):
    ids, mask = IDS_CASES[case]
    n = len(ids)
    live = _live(ids, mask)
    uid, _gsum, valid = su.dedup_gradients(
        jnp.asarray(ids, jnp.int32), jnp.zeros((n, D)), jnp.asarray(live))
    sidx = np.asarray(su.scatter_indices(uid, valid, V)).astype(np.int64)
    assert (np.diff(sidx) > 0).all(), sidx
    rows = sorted(set(ids[live].tolist()))
    np.testing.assert_array_equal(sidx[:len(rows)], rows)  # the live prefix: the rows, ascending
    assert (sidx[len(rows):] >= V).all()  # sentinel and tail: out of range, dropped
    assert int(np.asarray(valid).sum()) == len(rows)


def test_scatter_index_must_fit_int32():
    uid = jnp.zeros((4,), jnp.int32)
    with pytest.raises(ValueError, match="overflows the int32"):
        su.scatter_indices(uid, uid == 0, INT32_MAX - 3)
    su.scatter_indices(uid, uid == 0, INT32_MAX - 4)


def _check(cfg, ids, mask, batch_state, dim=D):
    """Returns what the run was given and what it gave, for a second look."""
    table, state, grads = _inputs(cfg, ids, dim=dim)
    got_t, got_s = _run(cfg, table, state, ids, grads, mask, batch_state)
    ref_t, ref_s, rows = _numpy_row_loop(cfg, table, state, ids, grads, mask, batch_state)
    np.testing.assert_allclose(got_t, ref_t, rtol=2e-5, atol=2e-6)
    assert sorted(got_s) == sorted(ref_s)
    for k in ref_s:
        np.testing.assert_allclose(got_s[k], ref_s[k], rtol=2e-5, atol=2e-6)
    # rows no live id names (masked, negative, out of range, never named)
    # are bit-identical: table and state
    untouched = [r for r in range(V) if r not in rows]
    np.testing.assert_array_equal(got_t[untouched], table[untouched])
    for k in state:
        np.testing.assert_array_equal(got_s[k][untouched], state[k][untouched])
    if rows:
        assert np.abs(got_t[rows] - table[rows]).sum() > 0
    return (table, state, grads), (got_t, got_s)


@pytest.mark.parametrize("case", sorted(IDS_CASES))
@pytest.mark.parametrize("opt", sorted(OPTS))
def test_matches_numpy_row_loop(opt, case):
    cfg = OPTS[opt].config
    _check(cfg, *IDS_CASES[case], (cfg.beta1 ** 3, cfg.beta2 ** 3))


@pytest.mark.parametrize("chunk", [1, 5, 27, 1024])
def test_any_chunk_size_writes_each_live_row_once(monkeypatch, chunk):
    """One trip or twenty-seven, a last chunk that overlaps the one before
    (27 rows at 5 a trip) or not."""
    monkeypatch.setattr(su, "_CHUNK_ROWS", chunk)
    cfg = OPTS["adam"].config
    _check(cfg, *IDS_CASES["all_distinct"], (cfg.beta1, cfg.beta2))


def test_bf16_table_keeps_its_dtype_and_untouched_rows():
    cfg = OPTS["adagrad"].config
    ids, mask = IDS_CASES["adversarial_masked"]
    table, state, grads = _inputs(cfg, ids)
    table16 = jnp.asarray(table, jnp.bfloat16)
    got_t, _ = jax.jit(lambda t, s, i, g, m: su.sparse_update(cfg, t, s, i, g, mask=m))(
        table16, {k: jnp.asarray(v) for k, v in state.items()},
        jnp.asarray(ids, jnp.int32), jnp.asarray(grads), jnp.asarray(mask))
    assert got_t.dtype == jnp.bfloat16
    rows = sorted(set(ids[_live(ids, mask)].tolist()))
    untouched = [r for r in range(V) if r not in rows]
    np.testing.assert_array_equal(np.asarray(got_t)[untouched], np.asarray(table16)[untouched])


# ------------------------------------------------- (b) what XLA is told

def _ops(cfg, kind, n=27):
    """Every ``kind`` operation in the StableHLO of a jitted sparse_update, in
    program order, as plain values (the module does not outlive this call):
    its name with its scopes, whether it sits inside a while, its attributes
    as text, its operand count and its first result's type."""
    state = su.init_sparse_state(cfg, V, D)
    lowered = jax.jit(lambda t, s, i, g: su.sparse_update(cfg, t, s, i, g)).lower(
        jnp.zeros((V, D)), state, jnp.zeros((n,), jnp.int32), jnp.zeros((n, D)))
    found = []

    def walk(op, in_while):
        for region in op.regions:
            for block in region.blocks:
                for o in block.operations:
                    name = o.operation.name
                    if name == kind:
                        found.append({
                            "name": str(o.location).split('"')[1], "in_while": in_while,
                            "attrs": {a: str(o.attributes[a]) for a in o.attributes},
                            "operands": len(o.operands), "result": str(o.results[0].type)})
                    walk(o.operation, in_while or name == "stablehlo.while")

    walk(lowered.compiler_ir("stablehlo").operation, False)
    return found


def _scatters(cfg, n=27):
    """(op name with its scopes, unique_indices, indices_are_sorted, inside a
    while) of every stablehlo.scatter of a jitted sparse_update."""
    return [(o["name"], o["attrs"]["unique_indices"] == "true",
             o["attrs"]["indices_are_sorted"] == "true", o["in_while"])
            for o in _ops(cfg, "stablehlo.scatter", n)]


@pytest.mark.parametrize("opt,scopes", [
    ("sgd_wd", ["scatter_table"]),
    ("adagrad", ["scatter_table", "scatter_acc"]),
    ("adagrad_vw", ["scatter_table", "scatter_acc"]),
    ("adam", ["scatter_table", "scatter_m", "scatter_v"]),
])
def test_row_scatters_declare_unique_indices_inside_the_live_row_loop(opt, scopes):
    found = _scatters(OPTS[opt].config)
    rows = [f for f in found if "sparse_update/row_update/" in f[0]]
    assert sorted(f[0].split("/")[-2] for f in rows) == sorted(scopes)
    for name, unique, is_sorted, in_while in rows:
        assert unique, f"{name}: unique_indices dropped"
        # true, and not said: the v5e's scatter copies its whole operand
        # every trip of the loop when it is (PERF.md, PR 27)
        assert not is_sorted, f"{name}: indices_are_sorted costs 8 ms a trip on the v5e"
        assert in_while, f"{name}: outside the loop over live rows, the dead tail runs again"


def test_dedup_scatters_declare_sorted_segments():
    found = _scatters(OPTS["adagrad"].config)
    dedup = [f for f in found if "/dedup/" in f[0]]
    assert len(dedup) == 1  # the segment sum; the uid scatter went into a sort (ISSUE 31)
    for name, unique, is_sorted, in_while in dedup:
        assert is_sorted and not unique and not in_while, name
    assert len(found) == len(dedup) + 2


def test_dedup_moves_its_ids_by_two_sorts_and_no_gather():
    """The sorted ids are the first sort's keys and the distinct ids a second
    sort's: no gather of int32 elements under ``dedup`` (the one gather left
    there takes the gradient rows). The first sort is stable: it fixes the
    order of the float sums."""
    cfg = OPTS["adagrad"].config
    gathers = [o for o in _ops(cfg, "stablehlo.gather") if "/dedup/" in o["name"]]
    assert [o["result"] for o in gathers] == [f"tensor<27x{D}xf32>"], gathers
    sorts = _ops(cfg, "stablehlo.sort")
    assert len(sorts) == 2 and all("/dedup/" in o["name"] and not o["in_while"] for o in sorts), sorts
    first, second = sorts
    assert first["operands"] == 2 and first["attrs"]["is_stable"] == "true"  # ids and positions
    assert second["operands"] == 1  # keys alone


# ------------------------------------------------- (c) the row-write kernel

D_DMA = 128  # a row the kernel takes: one 128-lane vector of float32


# the interpreter is this file's choice, as in tests/test_flash_attention.py
_write_rows_interpreted = functools.partial(su._write_rows_dma, interpret=True)


@pytest.fixture
def dma_path(monkeypatch):
    """What a one-chip TPU process sees, with the kernel interpreted: every
    float32 array whose row is a multiple of 128 lanes goes through
    ``_write_rows_dma``."""
    monkeypatch.setattr(su, "_backend", lambda: ("tpu", 1))
    monkeypatch.setattr(su, "_write_rows_dma", _write_rows_interpreted)
    tracing.flight_clear()


def _row_write_events():
    return [e["attrs"] for e in tracing.flight_snapshot() if e["kind"] == "sparse_update.row_write"]


@pytest.mark.parametrize("chunk,vocab", [(1, 2048), (5, 2048), (8, 2048), (27, 2048), (1024, 2048), (64, 40)])
@pytest.mark.parametrize("live", ["none", "some", "all"])
def test_kernel_writes_the_live_rows_and_no_other(chunk, vocab, live):
    """Positions whose index is >= V are skipped wherever they sit in the
    chunk; every other row of ``full`` keeps its bits. 1, 5 and 27 rows take
    the kernel's plain loop, 8, 64 and 1024 the unrolled one; the last case
    is a table shorter than the chunk."""
    rng = np.random.default_rng(chunk)
    full = rng.normal(size=(vocab, D_DMA)).astype(np.float32)
    rows = rng.normal(size=(chunk, D_DMA)).astype(np.float32)
    share = {"none": 0.0, "some": 0.6, "all": 1.0}[live]
    at = np.sort(rng.permutation(chunk)[:int(share * min(chunk, vocab) + 0.5)])  # the live positions
    idx = vocab + np.arange(chunk, dtype=np.int32)
    idx[at] = np.sort(rng.permutation(vocab)[:len(at)])
    got = jax.jit(_write_rows_interpreted)(jnp.asarray(full), jnp.asarray(idx), jnp.asarray(rows))
    want = full.copy()
    want[idx[at]] = rows[at]
    np.testing.assert_array_equal(np.asarray(got), want)


def test_kernel_compiles_unless_asked_to_interpret():
    """No caller gets the interpreter without asking: off the TPU the
    default (compile with Mosaic) refuses."""
    with pytest.raises(ValueError, match="interpret mode"):
        jax.jit(su._write_rows_dma)(jnp.zeros((16, D_DMA)), jnp.arange(8, dtype=jnp.int32),
                                    jnp.ones((8, D_DMA)))


@pytest.mark.parametrize("case", sorted(IDS_CASES))
@pytest.mark.parametrize("opt", sorted(OPTS))
def test_dma_path_matches_numpy_row_loop_and_the_scatter_path_bit_for_bit(opt, case, dma_path):
    """(a)'s grid with the kernel writing the rows: a partly live last chunk
    (``plain``: 27 positions at 8 a trip), zero live rows (``all_masked``,
    ``all_out_of_range``, ``n_1_dead``), all ids distinct."""
    cfg = OPTS[opt].config
    ids, mask = IDS_CASES[case]
    batch_state = (cfg.beta1 ** 3, cfg.beta2 ** 3)
    (table, state, grads), (dma_t, dma_s) = _check(cfg, ids, mask, batch_state, dim=D_DMA)
    events = _row_write_events()
    # vectorwise_shared Adagrad keeps the scatter for its (V, 1) accumulator
    want = {"table": "dma", **{k: "dma" if v.shape[1] == D_DMA else "scatter" for k, v in state.items()}}
    assert {e["array"]: e["path"] for e in events} == want
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(su, "_backend", lambda: ("cpu", 1))
        sc_t, sc_s = _run(cfg, table, state, ids, grads, mask, batch_state)
    assert {e["path"] for e in _row_write_events()[len(events):]} == {"scatter"}
    np.testing.assert_array_equal(dma_t, sc_t)
    for k in sc_s:
        np.testing.assert_array_equal(dma_s[k], sc_s[k])


@pytest.mark.parametrize("chunk", [1, 5, 27, 1024])
def test_dma_path_any_chunk_size_writes_each_live_row_once(monkeypatch, dma_path, chunk):
    monkeypatch.setattr(su, "_CHUNK_ROWS", chunk)
    cfg = OPTS["adam"].config
    _check(cfg, *IDS_CASES["all_distinct"], (cfg.beta1, cfg.beta2), dim=D_DMA)
    assert [(e["path"], e["chunk"]) for e in _row_write_events()] == [("dma", str(min(chunk, 27)))] * 3


# ------------------------------------------------- (d) which path, and the event that says so

def _traced_paths(opt, dtype, dim):
    """Trace one sparse_update (nothing is lowered, so the compiled kernel
    can be chosen on the CPU) and return its flight events and how many
    kernels the trace holds."""
    cfg = OPTS[opt].config
    tracing.flight_clear()
    jaxpr = jax.make_jaxpr(lambda t, s, i, g: su.sparse_update(cfg, t, s, i, g))(
        jnp.zeros((V, dim), dtype), su.init_sparse_state(cfg, V, dim),
        jnp.zeros((27,), jnp.int32), jnp.zeros((27, dim)))
    return _row_write_events(), str(jaxpr).count("pallas_call")


@pytest.mark.parametrize("platform,devices,dtype,dim,opt,want", [
    ("tpu", 1, "float32", 128, "adagrad", {"table": "dma", "acc": "dma"}),
    # per array: one row of a wider array is no contiguous piece (Mosaic refuses the slice)
    ("tpu", 1, "float32", 256, "adam", {"table": "scatter", "m": "scatter", "v": "scatter"}),
    ("tpu", 1, "float32", 2048, "adagrad", {"table": "scatter", "acc": "scatter"}),
    ("tpu", 1, "float32", 128, "sgd_wd", {"table": "dma"}),
    # per array: the (V, 1) accumulator is a sliver of a tile
    ("tpu", 1, "float32", 128, "adagrad_vw", {"table": "dma", "acc": "scatter"}),
    # per array: a bfloat16 row is not contiguous, its float32 state is
    ("tpu", 1, "bfloat16", 128, "adagrad", {"table": "scatter", "acc": "dma"}),
    ("tpu", 1, "float32", 16, "adagrad", {"table": "scatter", "acc": "scatter"}),
    ("tpu", 1, "float32", 192, "adam", {"table": "scatter", "m": "scatter", "v": "scatter"}),
    # several devices: GSPMD cannot partition the custom call
    ("tpu", 4, "float32", 128, "adagrad", {"table": "scatter", "acc": "scatter"}),
    ("tpu", 16, "float32", 128, "sgd_wd", {"table": "scatter"}),
    ("cpu", 1, "float32", 128, "adagrad", {"table": "scatter", "acc": "scatter"}),
    ("gpu", 1, "float32", 128, "adam", {"table": "scatter", "m": "scatter", "v": "scatter"}),
])
def test_row_write_path_follows_backend_dtype_row_width_and_device_count(
        monkeypatch, platform, devices, dtype, dim, opt, want):
    monkeypatch.setattr(su, "_backend", lambda: (platform, devices))
    events, kernels = _traced_paths(opt, jnp.dtype(dtype), dim)
    assert [e["array"] for e in events] == list(want)  # one event an array, the table first
    assert {e["array"]: e["path"] for e in events} == want
    assert kernels == sum(p == "dma" for p in want.values())
    table_event = events[0]
    assert (table_event["rows"], table_event["dim"], table_event["dtype"], table_event["chunk"]) == (
        str(V), str(dim), dtype, "8")
    # a second trace records its own
    events_again, _ = _traced_paths(opt, jnp.dtype(dtype), dim)
    assert events_again == events


def test_this_process_takes_the_scatter_for_every_array():
    """Nothing patched: a CPU run (all of tier-1) never picks the kernel."""
    assert su._backend()[0] == "cpu"
    events, kernels = _traced_paths("adam", jnp.float32, 128)
    assert [e["path"] for e in events] == ["scatter"] * 3 and kernels == 0


# ------------------------------------------------- (e) dedup against the form it replaced

def _dedup_gradients_frozen(ids, grads, mask=None):
    """``dedup_gradients`` as it stood until ISSUE 31, kept as the reference:
    the sorted ids gathered back through the permutation, the distinct ids
    compacted by a scatter, a tail of uid 0."""
    n = ids.shape[0]
    if mask is not None:
        ids = jnp.where(mask, ids, su._PAD_SENTINEL)
        grads = grads * mask[..., None].astype(grads.dtype)
    order = jnp.argsort(ids)
    sids = ids[order]
    sg = grads[order]
    is_new = jnp.concatenate(
        [jnp.ones((1,), dtype=bool), sids[1:] != sids[:-1]]
    )
    seg = jnp.cumsum(is_new) - 1
    gsum = jax.ops.segment_sum(sg, seg, num_segments=n, indices_are_sorted=True)
    uid = jnp.zeros((n,), dtype=ids.dtype).at[seg].set(sids, indices_are_sorted=True)
    valid = (jnp.arange(n) <= seg[-1]) & (uid != su._PAD_SENTINEL)
    return uid, gsum, valid


def _big_ids_case():
    """The cells' N (26 slots x 4,096 samples) of skewed ids: 8% below 0,
    40% at or past the vocabulary, a tenth masked out; 47% live, naming
    22,510 distinct rows (the cells' steps name 19-21.5k)."""
    rng = np.random.default_rng(31)
    n, vocab = 106_496, 6_291_457
    ids = np.minimum(rng.zipf(1.05, n), vocab + 5) - 3
    return ids, (ids >= 0) & (ids < vocab) & (rng.random(n) < 0.9), vocab, 8


# name -> (ids, live or None, vocab, dim). ``masked`` hands dedup_gradients
# what sparse_update does (the case's mask and the ids that name a row),
# ``unmasked`` the raw ids and no mask, negative and sentinel-valued ones
# among them.
DEDUP_CASES = {
    **{f"{case}-masked": (ids, _live(ids, mask), V, D) for case, (ids, mask) in IDS_CASES.items()},
    **{f"{case}-unmasked": (ids, None, V, D) for case, (ids, _) in IDS_CASES.items()},
    "n_106496-masked": _big_ids_case(),
}


@pytest.mark.parametrize("case", sorted(DEDUP_CASES))
def test_dedup_is_bit_for_bit_the_form_it_replaced(case):
    ids, live, vocab, dim = DEDUP_CASES[case]
    n = len(ids)
    ids = jnp.asarray(ids, jnp.int32)
    grads = jnp.asarray(np.random.default_rng(n).normal(size=(n, dim)).astype(np.float32))

    def outputs(dedup):
        def f(i, g, m):
            uid, gsum, valid = dedup(i, g, m)
            return uid, gsum, valid, su.scatter_indices(uid, valid, vocab), jnp.sum(valid, dtype=jnp.int32)
        return [np.asarray(x) for x in jax.jit(f)(ids, grads, None if live is None else jnp.asarray(live))]

    uid, gsum, valid, sidx, n_live = outputs(su.dedup_gradients)
    ref_uid, ref_gsum, ref_valid, ref_sidx, ref_n_live = outputs(_dedup_gradients_frozen)
    np.testing.assert_array_equal(sidx, ref_sidx)  # all N positions
    np.testing.assert_array_equal(gsum.view(np.uint32), ref_gsum.view(np.uint32))  # all N rows, by their bits
    np.testing.assert_array_equal(valid, ref_valid)
    assert n_live == ref_n_live
    # the distinct ids, ascending, then the sentinel to the end (the frozen form: zeros)
    distinct = len(np.unique(ids if live is None else np.where(live, ids, INT32_MAX)))
    np.testing.assert_array_equal(uid[:distinct], ref_uid[:distinct])
    assert (np.diff(uid[:distinct].astype(np.int64)) > 0).all()
    assert (uid[distinct:] == INT32_MAX).all() and (ref_uid[distinct:] == 0).all()
