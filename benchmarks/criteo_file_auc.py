"""File-borne Criteo AUC artifact: same held-out AUC across tiers.

BASELINE.md's north star is samples/sec AT matched model quality. The
synthetic-stream quality gate (bench.py BENCH_MODE=quality) prices the
tiers in-process; this script closes the remaining gap to real data by
driving the EXAMPLE CLI (`examples/criteo_dlrm/train.py`) end-to-end over
an on-disk Criteo-FORMAT file — the byte-identical schema of
Criteo-Kaggle's train.txt (label \t 13 ints \t 26 hex cats), through the
real `persia_tpu.datasets.CriteoTSV` ingestion path — for the fused,
cached, and hybrid tiers, and asserts they reach the same held-out AUC.

This environment has zero egress, so the slice is GENERATED (seeded,
documented below) from the CriteoSynthetic hidden-ground-truth model and
round-tripped through the TSV text format exactly as real data would be;
a user with the actual Criteo-Kaggle file gets the identical measurement
via `--data-path /path/to/train.txt` per tier. Writes
BENCH_CRITEO_REAL.json {file sha256, rows, per-tier auc + samples/sec}.

Run from the repo root: python benchmarks/criteo_file_auc.py
Knobs: CRITEO_FILE_STEPS (train batches, default 40), CRITEO_FILE_EVAL
(held-out batches, default 8), CRITEO_FILE_BS (default 4096).
"""

import gzip
import hashlib
import json
import os
import re
import subprocess
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

STEPS = int(os.environ.get("CRITEO_FILE_STEPS", "40"))
EVAL = int(os.environ.get("CRITEO_FILE_EVAL", "8"))
BS = int(os.environ.get("CRITEO_FILE_BS", "4096"))
SEED = 42


def generate_slice(path: str) -> str:
    """Seeded Criteo-format TSV.gz; returns its sha256. Deterministic in
    (SEED, STEPS, EVAL, BS) — anyone can regenerate and verify the hash."""
    from persia_tpu.testing import CRITEO_KAGGLE_VOCABS, CriteoSynthetic

    ds = CriteoSynthetic(
        num_samples=(STEPS + EVAL) * BS, vocab_sizes=CRITEO_KAGGLE_VOCABS,
        seed=SEED,
    )
    h = hashlib.sha256()
    with gzip.open(path, "wt") as f:
        for b in ds.batches(batch_size=BS):
            dense = np.asarray(b.non_id_type_features[0].data)
            labels = np.asarray(b.labels[0].data).reshape(-1)
            # the parser applies log1p(int); the synthetic stream is already
            # log1p-space, so emit round(expm1(d)) to round-trip
            ints = np.rint(np.expm1(np.maximum(dense, 0.0))).astype(np.int64)
            cats = [np.asarray(fi.data) for fi in b.id_type_features]
            for r in range(len(labels)):
                row = [str(int(labels[r]))]
                row += [str(int(v)) for v in ints[r]]
                row += [format(int(c[r]), "x") for c in cats]
                line = "\t".join(row) + "\n"
                f.write(line)
                h.update(line.encode())
    return h.hexdigest()


def run_tier(tier: str, data_path: str) -> dict:
    """One tier through the example CLI in its own subprocess (one process
    per chip: this parent imports JAX only after its children are done)."""
    cmd = [
        sys.executable, os.path.join(REPO, "examples", "criteo_dlrm", "train.py"),
        "--tier", tier, "--data-path", data_path,
        "--steps", str(STEPS), "--eval-steps", str(EVAL),
        "--batch-size", str(BS),
    ]
    if tier == "cached":
        cmd += ["--wire", "bfloat16"]
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(cmd, capture_output=True, text=True, cwd=REPO, env=env)
    if out.returncode != 0:
        raise RuntimeError(
            f"tier {tier} failed (rc={out.returncode}):\n"
            + "\n".join(out.stderr.strip().splitlines()[-12:])
        )
    m = re.search(
        r"test_auc=([\d.]+) throughput=([\d,]+) samples/sec", out.stdout
    )
    if not m:
        raise RuntimeError(f"tier {tier}: no result line in:\n{out.stdout[-2000:]}")
    return {
        "auc": float(m.group(1)),
        "samples_per_sec": float(m.group(2).replace(",", "")),
    }


def main():
    data_path = os.environ.get(
        "CRITEO_FILE_PATH", "/tmp/criteo_slice_%d_%d_%d.tsv.gz" % (STEPS, EVAL, BS)
    )
    if not os.path.exists(data_path):
        print(f"generating {data_path} ...", flush=True)
        sha = generate_slice(data_path)
        rows = (STEPS + EVAL) * BS
    else:
        opener = gzip.open if data_path.endswith(".gz") else open
        h = hashlib.sha256()
        rows = 0
        with opener(data_path, "rt") as f:
            for line in f:
                h.update(line.encode())
                rows += 1
        sha = h.hexdigest()
    out = {
        "file": os.path.basename(data_path),
        "file_sha256": sha,
        "rows": rows,
        "train_steps": STEPS,
        "eval_steps": EVAL,
        "batch_size": BS,
        "format": "criteo-kaggle train.txt schema (label, 13 ints, 26 hex cats)",
        "source": "seeded CriteoSynthetic ground-truth model (zero-egress env); "
                  "swap --data-path for the real file to reproduce on Criteo",
    }
    for tier in ("fused", "cached", "hybrid"):
        print(f"running tier {tier} ...", flush=True)
        out[tier] = run_tier(tier, data_path)
        print(tier, out[tier], flush=True)
    import jax

    out["platform"] = jax.default_backend()
    aucs = [out[t]["auc"] for t in ("fused", "cached", "hybrid")]
    out["auc_spread"] = round(max(aucs) - min(aucs), 6)
    # Looser than BENCH_QUALITY's 0.02: that gate compares tiers on an
    # IDENTICAL seeded stream with shared embedding init; here the fused
    # tier's dense-table init is jax.random while the PS tiers seed by
    # sign, so short budgets legitimately land a few AUC points apart.
    # This artifact certifies the end-to-end FILE path trains every tier
    # to comparable quality; raise CRITEO_FILE_STEPS to tighten.
    gate = float(os.environ.get("CRITEO_FILE_SPREAD_GATE", "0.05"))
    assert out["auc_spread"] < gate, f"tier AUC spread too wide: {out}"
    with open(os.path.join(REPO, "BENCH_CRITEO_REAL.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
