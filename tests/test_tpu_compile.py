"""Compiles for a described TPU v5e (no chip attached) at the benchmark
cells' own widths: what the chip's compiler refuses, copies or runs one
element at a time shows here, at no chip time (ISSUES 29, 31).

One file and a module fixture on purpose: only one process may hold the
TPU's library, so the topology is described after a test of this file has
started, never at import, and every such test lives here.
"""

import functools
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from persia_tpu import tracing
from persia_tpu.embedding.optim import Adagrad, Adam
from persia_tpu.ops import sparse_update as su

N_IDS = 106_496  # 26 slots x 4,096 samples
CELL_ROWS = {"tb-cached-resident": 6_291_457, "tb-pinned-share16": 11_735_473}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    # a compile for a described device is written to the persistent cache
    # and cannot be read back without a chip: keep it out
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@functools.lru_cache(maxsize=None)  # two tests read each Adagrad cell's program: 20 s a compile
def _compile_sparse_update(cfg, vocab, dim, sharding):
    """(the compiled ``sparse_update``, its ``row_write`` flight events), built
    as a one-chip TPU process builds it: ``jax.default_backend()`` still says
    cpu here."""
    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    state = {k: shaped(v.shape, v.dtype)
             for k, v in jax.eval_shape(lambda: su.init_sparse_state(cfg, vocab, dim)).items()}
    tracing.flight_clear()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(su, "_backend", lambda: ("tpu", 1))
        compiled = jax.jit(
            lambda t, s, i, g: su.sparse_update(cfg, t, s, i, g, mask=i >= 0), donate_argnums=(0, 1),
        ).lower(shaped((vocab, dim), jnp.float32), state, shaped((N_IDS,), jnp.int32),
                shaped((N_IDS, dim), jnp.float32)).compile()
    events = [e["attrs"] for e in tracing.flight_snapshot() if e["kind"] == "sparse_update.row_write"]
    return compiled, events


@pytest.mark.parametrize("cell,opt", [
    pytest.param("tb-cached-resident", Adagrad(lr=0.05), id="cached-adagrad"),
    pytest.param("tb-pinned-share16", Adagrad(lr=0.05), id="pinned-adagrad"),
    pytest.param("tb-cached-resident", Adam(lr=0.01), id="cached-adam"),
])
def test_sparse_update_writes_rows_by_dma_in_place_at_the_cells_widths(
        one_chip, no_compile_cache, cell, opt):
    """The kernel compiles for the v5e, one call an array inside the loop
    over live rows, and the donated table and state are updated where they
    lie: no whole-array copy, a megabyte of scratch."""
    vocab, dim = CELL_ROWS[cell], 128
    compiled, events = _compile_sparse_update(opt.config, vocab, dim, one_chip)
    assert [e["path"] for e in events] == ["dma"] * len(events) and events
    text = compiled.as_text()
    whole = re.escape(f"f32[{vocab},{dim}]")
    calls = [line for line in text.splitlines()
             if re.search(rf"= {whole}\S* custom-call\(.*tpu_custom_call", line)]
    assert len(calls) == len(events)
    for call in calls:
        assert "output_to_operand_aliasing={{}: (2, {})}" in call
        assert re.search(r"sparse_update/row_update/while/body/[^\"]*write_\w+/", call), call[:300]
    assert not re.search(rf"= {whole}\S* (copy|scatter)\(", text)
    mem = compiled.memory_analysis()
    # every array aliased to its output (rows padded to whole tiles of 8)
    assert mem.alias_size_in_bytes >= len(events) * vocab * dim * 4
    assert mem.temp_size_in_bytes < 16 * 2**20


@pytest.mark.parametrize("cell", sorted(CELL_ROWS))
def test_dedup_runs_one_serial_fusion_and_sorts_its_ids(one_chip, no_compile_cache, cell):
    """On the v5e a gather or scatter is a ``kCustom`` fusion that takes its
    elements one after another (4.6-7.1 ns an int32, PERF.md section 5).
    ``dedup`` keeps one, the segment sum with the gradient rows' gather fused
    into its operand; the ids go through two sorts and loop fusions."""
    compiled, _ = _compile_sparse_update(Adagrad(lr=0.05).config, CELL_ROWS[cell], 128, one_chip)
    entry = re.search(r"^ENTRY .*?^}", compiled.as_text(), re.S | re.M).group(0)
    dedup = [line for line in entry.splitlines() if re.search(r'op_name="[^"]*/dedup/', line)]
    serial = [line for line in dedup if "kind=kCustom" in line]
    assert len(serial) == 1, [line[:200] for line in serial]
    assert re.search(rf'= f32\[{N_IDS},128\]\S* fusion\(.*op_name="[^"]*/dedup/scatter-add"', serial[0]), serial[0][:300]
    sorts = [line for line in dedup if re.search(r"\bsort\(", line)]
    assert len(sorts) == 2 and "is_stable=true" in sorts[0], [line[:200] for line in sorts]
    for line in dedup:  # every other fusion of N int32: a loop fusion
        if re.search(rf"= \(?s32\[{N_IDS}\].* fusion\(", line):
            assert "kind=kLoop" in line, line[:300]


# ---- the sequence cell's kernels and rows (sdar-ep8-bd4-seq4k, ISSUE 33) ----

@pytest.fixture
def highest_by_default():
    """What ``perf/harness.py`` sets for a configuration that states
    ``highest``: the kernels have to compile under it (the first chip run of
    the cell did not: Mosaic refuses a bfloat16 operand at fp32 precision)."""
    before = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    yield
    jax.config.update("jax_default_matmul_precision", before)


@pytest.mark.parametrize("what", ["forward", "backward"])
def test_block_diffusion_attention_compiles_at_the_cells_widths(
        one_chip, no_compile_cache, highest_by_default, what):
    """32 query heads over 4 K/V heads of 128, 2 x 8,192 positions, blocks of
    4, tiles of 512: three Mosaic kernels, nothing L^2 in HBM."""
    from persia_tpu.ops.flash_attention import block_diffusion_attention

    length = 4096
    q = jax.ShapeDtypeStruct((2, 2 * length, 32, 128), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((2, 2 * length, 4, 128), jnp.bfloat16, sharding=one_chip)

    def forward(q, k, v):
        return block_diffusion_attention(q, k, v, length, 4)

    def backward(q, k, v):
        return jax.grad(lambda *a: forward(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    compiled = jax.jit(forward if what == "forward" else backward).lower(q, kv, kv).compile()
    text = compiled.as_text()
    kernels = sorted(set(re.findall(r"block_diffusion_attention_\w+", text)))
    want = ["block_diffusion_attention_fwd"] if what == "forward" else [
        "block_diffusion_attention_dkv", "block_diffusion_attention_dq", "block_diffusion_attention_fwd"]
    assert [k for k in want if any(k in name for name in kernels)] == want, kernels
    # the scores of one (batch, head) alone would be 256 MB in float32
    assert compiled.memory_analysis().temp_size_in_bytes < 600 * 2**20


# ---- the packed-documents cell's kernels (mellum2-ep4-pack16k, ISSUE 35) ----

@pytest.mark.parametrize("what,window", [("forward", None), ("backward", None), ("backward", 1024)],
                         ids=["forward-full", "backward-full", "backward-sliding"])
def test_interval_attention_compiles_at_the_cells_widths(
        one_chip, no_compile_cache, highest_by_default, what, window):
    """32 query heads over 4 K/V heads of 128, one sequence of 16,384
    positions, ``lo`` an argument, tiles of 512: three Mosaic kernels whose
    visit lists are made on the device, nothing T x T in HBM."""
    from persia_tpu.ops.flash_attention import interval_attention, interval_visits

    length = 16384
    q = jax.ShapeDtypeStruct((1, length, 32, 128), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, length, 4, 128), jnp.bfloat16, sharding=one_chip)
    lo = jax.ShapeDtypeStruct((1, length), jnp.int32, sharding=one_chip)

    def forward(q, k, v, lo):
        return interval_attention(q, k, v, lo, window=window)

    def backward(q, k, v, lo):
        return jax.grad(lambda *a: forward(*a, lo).astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    compiled = jax.jit(forward if what == "forward" else backward).lower(q, kv, kv, lo).compile()
    text = compiled.as_text()
    kernels = sorted(set(re.findall(r"interval_attention_\w+", text)))
    want = ["interval_attention_fwd"] if what == "forward" else [
        "interval_attention_dkv", "interval_attention_dq", "interval_attention_fwd"]
    assert [k for k in want if any(k in name for name in kernels)] == want, kernels
    # the scores of one head alone would be 1 GB in float32
    assert compiled.memory_analysis().temp_size_in_bytes < 600 * 2**20
    assert interval_visits(32, 512, window) == (528 if window is None else 93)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_causal_flash_attention_compiles_through_the_interval_kernels(one_chip, no_compile_cache, dtype):
    """``flash_attention(causal=True)`` as ``chip_smoke.py`` calls it (L 1000, two
    heads of 64, either dtype): padded to tiles of 256 and lanes of 128."""
    from persia_tpu.ops import flash_attention

    x = jax.ShapeDtypeStruct((1, 1000, 2, 64), dtype, sharding=one_chip)
    compiled = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True)).lower(x, x, x).compile()
    assert "interval_attention_fwd" in compiled.as_text()


def test_rows_wider_than_one_tile_column_keep_the_scatter(one_chip, no_compile_cache):
    """An 8 KB row is no contiguous piece of the (8, 128) tiling: Mosaic
    refuses the one-row slice the row-write kernel copies, at 256 lanes as at
    2,048, so ``_row_write_path`` sends such rows to the compiler's scatter,
    and the token table of the sequence cell is still updated where it lies."""
    shaped = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    with pytest.raises(Exception, match="aligned to tiling"):
        jax.jit(su._write_rows_dma, donate_argnums=(0,)).lower(
            shaped((4096, 256), jnp.float32), shaped((1024,), jnp.int32),
            shaped((1024, 256), jnp.float32)).compile()
    cfg, vocab, dim, n = Adagrad(lr=0.01).config, 18_992, 2048, 16_384
    tracing.flight_clear()
    state = {k: shaped(v.shape, v.dtype)
             for k, v in jax.eval_shape(lambda: su.init_sparse_state(cfg, vocab, dim)).items()}
    import unittest.mock

    with unittest.mock.patch.object(su, "_backend", lambda: ("tpu", 1)):
        compiled = jax.jit(functools.partial(su.sparse_update, cfg), donate_argnums=(0, 1)).lower(
            shaped((vocab, dim), jnp.float32), state, shaped((n,), jnp.int32),
            shaped((n, dim), jnp.float32)).compile()
    events = [e["attrs"] for e in tracing.flight_snapshot() if e["kind"] == "sparse_update.row_write"]
    assert [(e["array"], e["path"]) for e in events] == [("table", "scatter"), ("acc", "scatter")]
    assert compiled.memory_analysis().alias_size_in_bytes >= 2 * vocab * dim * 4


# ---- the sequence towers' q/k pass (ISSUE 36) ----

def _writes_under(text, scope):
    """(bytes written, name, opcode) of every instruction outside a fused
    computation whose ``op_name`` holds ``scope``: what the program writes to
    HBM there, a fusion counted once by its result."""
    sizes = {"f32": 4, "bf16": 2, "s32": 4, "u32": 4, "pred": 1}
    free = {"bitcast", "get-tuple-element", "parameter", "tuple", "constant", "while", "call"}
    out, computation = [], ""
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if head:
            computation = head.group(1)
            continue
        at = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([\w\-]+)\(", line)
        if not at or "fused" in computation or at.group(3) in free:
            continue
        name, shape, opcode = at.groups()
        if scope not in (re.search(r'op_name="([^"]*)"', line) or [""])[0]:
            continue
        n = sum(sizes[dtype] * math.prod(int(x) for x in dims.split(",") if x)
                for dtype, dims in re.findall(r"(\w+)\[([\d,]*)\]", shape) if dtype in sizes)
        out.append((n, name, opcode))
    return out


@pytest.mark.time_limit(600)
def test_an_sdar_layer_keeps_q_and_k_in_the_projections_layout(one_chip, no_compile_cache, highest_by_default):
    """One layer of the SDAR tower, forward and backward, at the cell's widths
    (rows (2, 8192, 2048), 32 / 4 heads of 128, 16 held experts): the q/k pass
    compiles as two Mosaic kernels, and under the ``attention`` scope nothing
    copies, broadcasts, pads or slices an array of 100 MB or more. With q and
    k reshaped to (B, T, H, D) before their norm and RoPE the recomputed
    forward alone held 3 such copies (every reshape between the (T, 4096) and
    (32, 128) tilings moves the array), the tables broadcast over the heads
    (3), rotate-half's pad and its slices: 37 instructions of 100 MB or more
    and 7.5 GB written a layer, 17 and 3.0 GB now."""
    from persia_tpu.models.sdar_moe import SDARMoE

    tower = SDARMoE(vocab=128, n_layers=1, block_len=4, n_held=16)
    shaped = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    params = jax.tree.map(shaped, tower.param_shapes(), is_leaf=lambda s: isinstance(s, tuple))

    def loss(params, rows):
        return jnp.sum(tower.apply({"params": params}, [], [(rows, None)]))

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(params, shaped((2, 8192, 2048))).compile().as_text()
    assert {"qk_norm_rope_fwd", "qk_norm_rope_bwd"} <= set(re.findall(r"qk_norm_rope_[a-z]+", text))
    large = [w for w in _writes_under(text, "/attention") if w[0] >= 100e6]
    moved = [w for w in large if re.search(r"copy|broadcast|pad|slice", w[1] + " " + w[2])]
    assert not moved, moved
    # the forward q pass once and once recomputed, the backward's once; the k
    # passes are an eighth of the size
    assert sorted(w[1].split(".")[0] for w in large if "qk_norm_rope" in w[1]) == [
        "qk_norm_rope_bwd", "qk_norm_rope_fwd", "qk_norm_rope_fwd"]
    assert len(large) <= 20 and sum(w[0] for w in large) < 3.5e9, (len(large), large)


# ---- the held experts' grouped products (ISSUE 39) ----

# cell: (rows of a chunk, hidden, an expert's width)
EXPERT_SHAPES = {"mellum2-ep4-pack16k": (36_864, 2304, 896), "sdar-ep8-bd4-seq4k": (18_432, 2048, 768)}


@pytest.mark.parametrize("form", ["product", "transposed_weights", "outer"])
@pytest.mark.parametrize("leaf", ["gate_or_up", "down"])
@pytest.mark.parametrize("cell", sorted(EXPERT_SHAPES))
def test_the_grouped_products_compile_at_the_cells_widths(
        one_chip, no_compile_cache, highest_by_default, cell, leaf, form):
    """16 held experts, a chunk's rows in tiles of 512 with the whole K and N
    in a block, under the harness's ``highest``: one Mosaic kernel each, which
    needs more VMEM than the scoped default (the call asks for it), no
    transposed copy of the weights and nothing else of the rows' size."""
    from persia_tpu.ops import grouped_matmul as gm

    m, d, f = EXPERT_SHAPES[cell]
    k, n = (d, f) if leaf == "gate_or_up" else (f, d)
    assert gm.grouped_tiles(m, k, n) == (512, k, n)
    shaped = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    sizes = shaped((16,), jnp.int32)
    if form == "product":
        fn, args, name = gm.grouped_matmul, (shaped((m, k)), shaped((16, k, n)), sizes), "grouped_matmul"
    elif form == "transposed_weights":
        fn = functools.partial(gm.grouped_matmul, transposed=True)
        args, name = (shaped((m, k)), shaped((16, n, k)), sizes), "grouped_matmul_t"
    else:
        fn, args, name = gm.grouped_outer, (shaped((m, k)), shaped((m, n)), sizes), "grouped_outer"
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"", text)) == 1 and name in text
    assert not re.search(r"= bf16\[[\d,]+\]\S* (transpose|copy)\(", text) and "ragged" not in text
    # beside the result: the walk's four small arrays
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 2**20


# ---- the delta-rule and latent-attention cell's kernels (kimi-linear-ep32-pack16k, ISSUE 40) ----

@pytest.mark.parametrize("what", ["forward", "backward"])
def test_the_delta_rule_compiles_at_the_cells_widths(one_chip, no_compile_cache, highest_by_default, what):
    """A pass of a KDA layer: 8 heads of 128 x 128, one sequence of 16,384
    positions in chunks of 64, ``lo`` an argument: the four Mosaic kernels
    (a chunk's operands and the scan over chunks, each way), the chunk states
    the largest array between them."""
    from persia_tpu.ops.delta_rule import kda

    length, heads = 16384, 8
    x = jax.ShapeDtypeStruct((1, length, heads, 128), jnp.float32, sharding=one_chip)
    beta = jax.ShapeDtypeStruct((1, length, heads), jnp.float32, sharding=one_chip)
    lo = jax.ShapeDtypeStruct((1, length), jnp.int32, sharding=one_chip)

    def backward(q, k, v, g, beta, lo):
        return jax.grad(lambda *a: kda(*a, lo).sum(), argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)

    compiled = jax.jit(kda if what == "forward" else backward).lower(x, x, x, x, beta, lo).compile()
    kernels = sorted(set(re.findall(r"kda_(?:chunk|prepare)_\w+", compiled.as_text())))
    want = (["kda_chunk_fwd", "kda_prepare_fwd"] if what == "forward"
            else ["kda_chunk_bwd", "kda_chunk_fwd", "kda_prepare_bwd", "kda_prepare_fwd"])
    assert [k for k in want if any(k in name for name in kernels)] == want, kernels
    # the chunk states of 8 heads are 128 MiB; nothing stands a position at 128 x 128
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2**30


@pytest.mark.parametrize("what", ["forward", "backward"])
def test_interval_attention_compiles_at_two_widths(one_chip, no_compile_cache, highest_by_default, what):
    """Latent attention at the cell's widths: 32 heads, scores 192 wide (a
    head's 128 columns and 64 that all share), values 128, one sequence of
    16,384 positions: the same three kernels, a second product a tile, and no
    array 256 lanes a head in HBM."""
    from persia_tpu.ops.flash_attention import interval_attention

    length = 16384
    q = jax.ShapeDtypeStruct((1, length, 32, 192), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, length, 32, 128), jnp.bfloat16, sharding=one_chip)
    shared = jax.ShapeDtypeStruct((1, length, 64), jnp.bfloat16, sharding=one_chip)
    lo = jax.ShapeDtypeStruct((1, length), jnp.int32, sharding=one_chip)

    def forward(q, k, v, s, lo):
        return interval_attention(q, k, v, lo, k_shared=s)

    def backward(q, k, v, s, lo):
        return jax.grad(lambda *a: forward(*a, lo).astype(jnp.float32).sum(), argnums=(0, 1, 2, 3))(q, k, v, s)

    compiled = jax.jit(forward if what == "forward" else backward).lower(q, kv, kv, shared, lo).compile()
    text = compiled.as_text()
    kernels = sorted(set(re.findall(r"interval_attention_\w+", text)))
    want = ["interval_attention_fwd"] if what == "forward" else [
        "interval_attention_dkv", "interval_attention_dq", "interval_attention_fwd"]
    assert [k for k in want if any(k in name for name in kernels)] == want, kernels
    assert "16384,8192]" not in text and "32,16384,256]" not in text  # no head padded to 256 lanes
    assert compiled.memory_analysis().temp_size_in_bytes < 1200 * 2**20


# ---- the rotated latent-attention cell's layer (joyai-flash-ep32-pack16k-mtp1, ISSUE 42) ----

@pytest.mark.parametrize("what", ["forward", "backward"])
def test_rotated_latent_attention_compiles_at_the_cells_widths(one_chip, no_compile_cache, highest_by_default, what):
    """One block's attention as the ``joyai_llm_flash`` family states it, at the
    cell's shape: hidden 2,048, a query low rank of 1,536, 32 heads of 128 + 64,
    one sequence of 16,384 positions, the rotation's tables made from ``lo`` in
    the program: the three interval kernels at two widths, the rotation XLA's
    with no array a pair wide (minor dimension 2) and nothing the size of the
    scores."""
    from persia_tpu.models.joyai_flash_moe import rope_tables
    from persia_tpu.models.moe_tower import latent_attention

    length, d, h = 16384, 2048, 32
    shapes = {"wq_a": (d, 1536), "q_norm": (1536,), "wq_b": (1536, h * 192), "wkv_a": (d, 576), "kv_norm": (512,),
              "wkv_b": (512, h * 256), "wo": (h * 128, d)}
    p = {k: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip) for k, s in shapes.items()}
    a = jax.ShapeDtypeStruct((1, length, d), jnp.float32, sharding=one_chip)
    lo = jax.ShapeDtypeStruct((1, length), jnp.int32, sharding=one_chip)

    def forward(p, a, lo):
        return latent_attention(p, a, lo, n_heads=h, head_dim=128, rope_head_dim=64, kv_lora_rank=512, eps=1e-6,
                                tile=512, interpret=False, rope=rope_tables(lo, 64, 32e6))

    def backward(p, a, lo):
        return jax.grad(lambda p, a: forward(p, a, lo).sum(), argnums=(0, 1))(p, a)

    compiled = jax.jit(forward if what == "forward" else backward).lower(p, a, lo).compile()
    text = compiled.as_text()
    kernels = sorted(set(re.findall(r"interval_attention_\w+", text)))
    want = ["interval_attention_fwd"] if what == "forward" else [
        "interval_attention_dkv", "interval_attention_dq", "interval_attention_fwd"]
    assert [k for k in want if any(k in name for name in kernels)] == want, kernels
    assert not re.search(r"f32\[[\d,]*,2\]\{", text) and "16384,16384]" not in text
    # q in float32 and bfloat16, its gradient, the latent's: 0.4 GB arrays, a handful of them
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * 2**30
