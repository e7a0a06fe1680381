"""The dry run of ``zipf105-cached-resident`` through the real
``CachedTrainCtx`` with the widths cut to almost nothing and pool, vocabulary
and batch scaled down together (1/64 of the rows, 1/8 of the batch): with
every row resident from the first step no step misses, evicts or restores, so
no aux program is keyed at all, and every full run of ``dispatch_k`` steps
goes out as one pack. Counts only; runs on the CPU."""

import pytest

import perf_presets  # noqa: F401
from perf import harness
from perf.entries.cached_stream import Entry
from perf.generators.zipf import ZipfBatches, table_rows

TINY = {"embedding_dim": 4, "bottom_mlp": [8, 4], "top_mlp": [8, 1]}
SCALED = {"batch": 512, "cache_rows": 98304, "ps_capacity": 1 << 18}


def _cache_counts():
    from persia_tpu.metrics import get_metrics

    snap = get_metrics().snapshot(prefix="persia_tpu_cache_")
    return {k: sum((snap.get(f"persia_tpu_cache_{k}_count") or {}).values())
            for k in ("hit", "miss", "evict")}


@pytest.mark.parametrize("seed", [5, 2 ** 31 + 41, 3_000_000_019])
def test_no_miss_and_full_packs_from_the_first_step(seed):
    config = harness.load_config_for_traffic("zipf105-cached-resident")
    config = dict(config, **TINY, table_rows=[max(3, n // 64) for n in config["table_rows"]])
    traffic = dict(harness.load_traffic("zipf105-cached-resident"), **SCALED)
    entry = Entry(config, traffic, seed)
    entry.build()
    assert len(entry.ctx.tier.dirs[entry.group.name]) == sum(table_rows(config, traffic))
    gen = iter(ZipfBatches(config, traffic, seed))
    start = _cache_counts()  # the counters are the process's, not the context's
    assert entry.compared_run([next(gen) for _ in range(entry.dispatch_k)]) is not None
    entry.warm_up(gen)
    steps = 20 * entry.dispatch_k
    entry._stream((entry.to_program_batch(next(gen)) for _ in range(steps)), entry.dispatch_k)
    after = _cache_counts()
    assert after["miss"] == start["miss"] and after["evict"] == start["evict"]
    assert after["hit"] > start["hit"]
    stats = entry.ctx.stream_stats()
    assert stats["packed_steps"] == steps and stats["single_steps"] == 0
    entry.free()


def test_full_size_pool_holds_every_row_of_the_slice():
    tr = harness.load_traffic("zipf105-cached-resident")
    rows = table_rows(harness.load_config_for_traffic("zipf105-cached-resident"), tr)
    # the directory has 8 shards of cache_rows / 8 rows each: room for the
    # slice's rows however the signs fall among them (sd about 800 a shard)
    assert sum(rows) == 5_867_745 and sum(rows) / 8 + 40_000 < tr["cache_rows"] / 8
    assert tr["resident_from_start"] is True and tr["warmup_steps"] % tr["dispatch_k"] == 0
