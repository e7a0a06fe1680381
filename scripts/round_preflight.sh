#!/usr/bin/env bash
# End-of-round preflight: a snapshot is only DONE when all three proofs
# pass. Round 4 shipped its final commit with 44 red tests and a broken
# bench because none of these ran; this script is the institutional
# answer — run it before any end-of-round (or otherwise milestone) commit:
#
#   bash scripts/round_preflight.sh
#
# 0. persia-verify (ABI drift + lexical AND interprocedural concurrency
#    + JAX trace-discipline + resilience rules + the PROTO protocol pass:
#    journal-id namespace prover, two-phase/resume shape rules, and the
#    PROTO_COVERAGE.json crash-matrix completeness contract; fails on any
#    finding not in scripts/lint_baseline.json when that file exists)
#    + the fast protocol crash matrices (fence / scrub / heal promotion,
#    every reach() transition killed once + resumed) + native cores
#    compile from source + the fused-feed ABI parity tests pass
#    (a broken ctypes signature loads fine and silently corrupts — the
#    lint catches the declaration drift, the golden parity tests catch
#    the rest) + the native parity suites under UBSan (zero reports or
#    the run aborts). ASan is opt-in (PREFLIGHT_ASAN=1) — preloading
#    libasan instruments all of python and costs ~100s. The TSan race
#    gate (scripts/race_native.sh: seeded multithread stress over all
#    four native cores, zero-report-or-abort) is opt-in the same way
#    via PREFLIGHT_TSAN=1 — it rebuilds every core at -O1 with
#    -fsanitize=thread and costs ~2min.
# 1. chaos suite, fast schedules (fault proxies, breakers, degraded mode)
# 2. full test suite green
# 3. dryrun_multichip(8) on a virtual CPU mesh
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== 0/4 persia-verify + native build + ABI parity smoke =="
# static pass first: it needs no toolchain and fails fast on drift.
# With a committed baseline only NEW findings fail the round — exit
# contract documented in persia_tpu/analysis/__main__.py
if [ -f scripts/lint_baseline.json ]; then
    python -m persia_tpu.analysis --baseline scripts/lint_baseline.json
else
    python -m persia_tpu.analysis
fi
# protocol layer (ISSUE 19): static extraction + prover units + the fast
# crash matrices — jobstate fence, scrub record, healer promotion — every
# extracted reach() transition killed once and the resumed end state
# compared bit-for-bit against an uninterrupted run. The ~35-point
# reshard and autopilot matrices ride the full suite in step 2; the
# committed PROTO_COVERAGE.json (validated here via PROTO006 above and
# test_committed_coverage_is_complete) proves ALL of them ran.
JAX_PLATFORMS=cpu python -m pytest tests/test_protocol.py -q -m 'not slow'
# control-plane lease lint (ISSUE 20): CTRL002 pinned fixtures — the
# unleased fixture must fire on every direct actuator call, the leased /
# suppressed fixture must stay clean, and the mechanism layer (files
# DEFINING an actuator) stays exempt. Keeps the arbiter's single
# topology-actuation lease enforceable as a static contract.
JAX_PLATFORMS=cpu python -m pytest tests/test_analysis.py -q -k "ctrl002 or ctrl_"
# force=True recompile of every core: the stamp cache must not mask a
# toolchain or source breakage
JAX_PLATFORMS=cpu python - <<'PY'
from persia_tpu.embedding import hbm_cache, native_store, native_worker
for name, builder in (("ps", native_store.build_native),
                      ("worker", native_worker.build_native),
                      ("cache", hbm_cache.build_native)):
    print(name, builder(force=True))
PY
JAX_PLATFORMS=cpu python -m pytest tests/test_native_feed.py -q
# sharded-feeder parity goldens against the cores just force-rebuilt:
# shard-route Python/C++ mirror, S=1 bitwise-vs-legacy, thread-count
# bit-invariance, fused-observe equivalence, sampling convergence
# (~1s; the ctx-level reshard/kill-resume parity runs ride step 2)
JAX_PLATFORMS=cpu python -m pytest tests/test_sharded_feeder.py -q
# probe-layout goldens (ISSUE 17): SIMD tag walk bitwise-vs-scalar across
# shard/thread counts and admit paths, mid-stream probe-mode flips,
# fused-observe state parity across modes, affinity re-pin invariance
# (~13s; the native-handoff subset rides step 1, the subprocess
# native-fleet reshard run rides step 2)
JAX_PLATFORMS=cpu python -m pytest tests/test_probe_layout.py -q \
    -k "probe or affinity or env_knob or fused"
# UBSan variant of the full parity surface (~10s incl. variant builds);
# SANITIZE_ASAN rides the same script when PREFLIGHT_ASAN=1
SANITIZE_ASAN="${PREFLIGHT_ASAN:-0}" bash scripts/sanitize_native.sh
# TSan race gate: seeded multithread stress over the four native cores
# under -fsanitize=thread, zero TSan reports or the run aborts
if [ "${PREFLIGHT_TSAN:-0}" = "1" ]; then
    bash scripts/race_native.sh
fi

echo "== 1/4 chaos suite (fast schedules + resume-chaos + serving-chaos) =="
# deterministic fault injection against live local services: proxies,
# breakers, crc integrity, degraded-mode router, pending-ledger salts —
# plus the fast resume-chaos runs (trainer-kill/resume bit-parity for the
# hybrid ctx, the cached stream fence, and the RPC journal wire) and the
# fast serving-chaos subset (staleness quarantine/heal + delta-packet
# integrity/resync); the full kill+resets, trainer-SIGKILL bitwise runs,
# and the zipfian online soak ride slow.
# tests/test_tiering.py rides here too — the fast subset (sketch accuracy,
# planner hysteresis/lockstep, controller rounds, snapshot roundtrip, the
# sharded-feeder env knobs); the multi-second stream/e2e/bit-parity runs —
# incl. the round-14 fused-observe invariance, reshard-at-fence and
# sharded kill/resume parity ctx runs — stay in the full suite
# tests/test_health.py rides here too — the fast subset (validator +
# quarantine, sentinel ladder/dedupe, scrubber exactly-once, delta
# rejection, NUM001, data-plane chaos determinism); the two multi-second
# cached-stream runs (poisoned-stream bit-parity, on-device skip rung)
# stay in the full suite
JAX_PLATFORMS=cpu python -m pytest tests/test_chaos.py tests/test_failure_recovery.py tests/test_jobstate.py tests/test_serving_chaos.py tests/test_incremental.py tests/test_tiering.py tests/test_health.py -q -m 'not slow' \
    --deselect tests/test_tiering.py::test_stream_migration_at_fence_and_ledger_drained \
    --deselect tests/test_tiering.py::test_auto_tier_demotes_cold_slot_and_survives_resume \
    --deselect tests/test_tiering.py::test_migration_bit_parity_with_fresh_placement_resume \
    --deselect tests/test_tiering.py::test_fence_manifest_carries_tiering_component \
    --deselect tests/test_tiering.py::test_sharded_feeder_fused_observe_and_thread_invariance \
    --deselect tests/test_tiering.py::test_reshard_at_fence_parity_with_fresh_resume \
    --deselect tests/test_tiering.py::test_sharded_feeder_kill_resume_parity \
    --deselect tests/test_health.py::test_poisoned_stream_rollback_bit_parity \
    --deselect tests/test_health.py::test_on_device_nonfinite_skip_rung
# stage-graph fast subset: the pipeline's hazard/window/drain/rebuild unit
# tests (test_unit_*; sub-second, no jit). The multi-second pipelined-stream
# bit-parity runs (depth A/B, fence+migration, kill/resume) ride the full
# suite in step 2.
JAX_PLATFORMS=cpu python -m pytest tests/test_stage_graph.py -q -m 'not slow' -k "unit"
# dense-plane sync fast subset (ISSUE 13): quantizer edge cases, the
# block-int8 ring's exact-mean/EF/replica-parity gates, sharded-update
# parity + ~1/n memory, the mode registry/wire model, and the TrainCtx
# mode plumbing incl. the sharded jobstate kill/resume bit-parity run.
# The n=32/64 forced-device-count dp-invariance subprocesses ride slow.
JAX_PLATFORMS=cpu python -m pytest tests/test_dense_sync.py -q -m 'not slow'
JAX_PLATFORMS=cpu python -m pytest tests/test_grad_sync.py -q -m 'not slow' \
    -k "block_int8 or sharded or quantize or sync_mode"
# elastic PS tier fast subset (ISSUE 15): reshard planning + journal-id
# namespace units, the sparsity-aware ShardPlanner, router ring-swap /
# replace_replica breaker-reset regression, range handoff dedupe, and the
# in-proc engine crash/resume matrix; the multi-process ServiceCtx
# grow/shrink chaos parity runs (test_ctx_*) ride the full suite in step 2
JAX_PLATFORMS=cpu python -m pytest tests/test_elastic.py -q -m 'not slow' \
    -k "not ctx_"
# autopilot fast subset (ISSUE 16): policy hysteresis/dwell guards,
# journaled hot-sign replication exactly-once + read fan-out, two-phase
# decision SIGKILL resume, gateway sensors/actuators, LoadSchedule
# parsing/determinism; the multi-second fence_callback bit-transparency
# stream runs ride the full suite in step 2
JAX_PLATFORMS=cpu python -m pytest tests/test_autopilot.py -q -m 'not slow'
# native-handoff fast subset (ISSUE 17): ps_export_range bytes
# native-vs-numpy and the mixed-backend reshard journal-crc dedupe, both
# in-proc; the subprocess native-fleet grow 2->4 rides the full suite
JAX_PLATFORMS=cpu python -m pytest tests/test_probe_layout.py -q \
    -k "export_range or mixed_backend"
# self-healing failover fast subset (ISSUE 18): the lease+probe
# FailureDetector verdict matrix (one miss never evicts, partition
# witness rule), HealPolicy dwell/cooldown, the Healer's exactly-once
# journal resume, and the in-flight lookup migration across
# replace_replica; the flagship SIGKILL-mid-stream autonomous-heal
# bit-parity runs ride the full suite in step 2
JAX_PLATFORMS=cpu python -m pytest tests/test_selfheal.py -q -m 'not slow'

echo "== 1.5/4 telemetry plane (trace propagation + flight recorder) =="
# the fast tracing/telemetry subset: span mechanics, RPC + gateway HTTP
# trace propagation, the flight-recorder dump paths, and the per-role
# /spans endpoints (the merged-fleet topology pin rides the full suite)
JAX_PLATFORMS=cpu python -m pytest tests/test_telemetry.py -q -m 'not slow' \
    --deselect tests/test_telemetry.py::test_local_topology_merged_trace
# tracing-disabled overhead guard: a span on a disabled tracer must stay
# a no-op — no id generation, no record, no ring append
JAX_PLATFORMS=cpu python - <<'PY'
import time
from persia_tpu import tracing
assert not tracing.enabled()
n = 200_000
t0 = time.perf_counter()
for _ in range(n):
    with tracing.span("preflight.noop"):
        pass
per_us = (time.perf_counter() - t0) / n * 1e6
assert tracing.spans_snapshot() == [], "disabled tracer recorded spans"
assert per_us < 25.0, f"disabled span costs {per_us:.2f}us (no-op bound 25us)"
print(f"disabled-span overhead {per_us:.2f}us/call OK")
PY
# sentinel-disabled overhead guard: same contract on the stream hot path —
# sentinel off must cost exactly one ``is None`` check per step
JAX_PLATFORMS=cpu python - <<'PY'
import time
import numpy as np
from persia_tpu.health import sentinel_drain, sentinel_note
pending, header = [], np.zeros(6, np.float32)
n = 200_000
t0 = time.perf_counter()
for g in range(n):
    sentinel_note(None, pending, g, header, 1)
sentinel_drain(None, pending)
per_us = (time.perf_counter() - t0) / n * 1e6
assert pending == [], "disabled sentinel queued headers"
assert per_us < 25.0, f"disabled sentinel_note costs {per_us:.2f}us (no-op bound 25us)"
print(f"disabled-sentinel overhead {per_us:.2f}us/call OK")
PY

echo "== 2/4 test suite =="
python -m pytest tests/ -q

echo "== 3/4 multichip dryrun =="
XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
    python -c "import __graft_entry__ as g; g.dryrun_multichip(8); print('dryrun OK')"

echo "PREFLIGHT PASSED"
