"""One-command local train-to-serve topology.

The pieces of the online continuous-learning loop — trainer, incremental
delta channel, checkpoint rollover, serving replicas, gateway — each run
standalone, but bringing them up together used to take a page of glue.
This module is that glue, in three layers:

- **role entries** (``python -m persia_tpu.topology trainer|replica ...``):
  a demo trainer (synthetic zipf-skewed click stream, in-process embedding
  store, jobstate fences for crash-consistent auto-resume, incremental
  packets + periodic checkpoints published on a cadence) and a demo
  serving replica (ServingServer: micro-batcher, hot cache, rollover
  watcher + live delta consumption, freshness export). Both build the
  SAME deterministic model spec, so a replica can deserialize any
  trainer checkpoint;
- :class:`LocalTopology` — spawns K trainers + R replica subprocesses
  (optionally a ServiceCtx PS/worker tier as the discovery fabric),
  fronts the replicas with a staleness-aware :class:`ReplicaGateway`,
  auto-restarts crashed trainers (the jobstate resume path), and exposes
  the fault hooks the chaos soak drives (kill/restart any component,
  per-replica delta-channel faults via ``chaos.DeltaChannelChaos``);
- the ``persia-tpu-launcher local`` subcommand (persia_tpu/launcher.py)
  wraps :class:`LocalTopology` for the README quickstart.

Everything is CPU-host friendly (``JAX_PLATFORMS=cpu`` is forced into
children) — the point is the topology, not the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from persia_tpu.logger import get_default_logger

logger = get_default_logger("persia_tpu.topology")

# demo model spec — shared by every role so checkpoints deserialize anywhere
N_SLOTS = 4
EMB_DIM = 8
N_DENSE = 4
READY_LINE = "TOPOLOGY_REPLICA_READY"


def build_demo_ctx(seed: int = 7, capacity: int = 1 << 16):
    """Deterministic (TrainCtx, EmbeddingConfig) every topology role shares."""
    import optax

    from persia_tpu.config import EmbeddingConfig, SlotConfig
    from persia_tpu.ctx import TrainCtx
    from persia_tpu.embedding.optim import Adagrad
    from persia_tpu.embedding.store import EmbeddingStore
    from persia_tpu.embedding.worker import EmbeddingWorker
    from persia_tpu.models import DNN

    cfg = EmbeddingConfig(
        slots_config={f"cat_{i}": SlotConfig(dim=EMB_DIM) for i in range(N_SLOTS)},
        feature_index_prefix_bit=8,
    )
    store = EmbeddingStore(capacity=capacity, num_internal_shards=4,
                           optimizer=Adagrad(lr=0.1).config, seed=seed)
    worker = EmbeddingWorker(cfg, [store])
    ctx = TrainCtx(
        model=DNN(dense_mlp_size=16, sparse_mlp_size=32, hidden_sizes=(32,)),
        dense_optimizer=optax.adam(3e-3),
        embedding_optimizer=Adagrad(lr=0.1),
        worker=worker,
        embedding_config=cfg,
    )
    return ctx, cfg


def demo_batch(step: int, rows: int, vocab: int, seed: int = 0,
               publisher: int = 0, requires_grad: bool = True):
    """One deterministic zipf-skewed training batch: the stream regenerates
    identically after a trainer crash-resume (batch N is a pure function of
    N), and publisher ``k`` owns the id range ``[k*vocab, (k+1)*vocab)`` so
    multiple trainers partition the user space instead of fighting over it."""
    from persia_tpu.data import (
        IDTypeFeatureWithSingleID,
        Label,
        NonIDTypeFeature,
        PersiaBatch,
    )

    rng = np.random.default_rng(seed * 1_000_003 + step * 2 + publisher * 977)
    base = np.uint64(publisher * vocab)
    ids = [
        IDTypeFeatureWithSingleID(
            f"cat_{i}",
            base + ((rng.zipf(1.2, rows).astype(np.uint64)
                     + np.uint64(i * 1000)) % vocab),
        )
        for i in range(N_SLOTS)
    ]
    return PersiaBatch(
        ids,
        non_id_type_features=[NonIDTypeFeature(
            rng.normal(size=(rows, N_DENSE)).astype(np.float32))],
        labels=[Label(rng.integers(0, 2, (rows, 1)).astype(np.float32))],
        requires_grad=requires_grad,
    )


def _arm_telemetry(role: str, trace_dir: Optional[str] = None) -> Optional[int]:
    """Arm a role's telemetry plane when the parent asked for one
    (``PERSIA_TRACE_DIR`` in the child env, or an explicit ``trace_dir``):
    enable tracing tagged with ``role``, serve ``/metrics`` + ``/spans`` +
    ``/flight`` on a loopback port advertised through an atomic
    ``<role>.endpoint`` file in the trace dir, arm the flight recorder,
    and export the span ring on exit. Returns the bound port (None when
    telemetry is off)."""
    trace_dir = trace_dir or os.environ.get("PERSIA_TRACE_DIR")
    if not trace_dir:
        return None
    from persia_tpu import tracing
    from persia_tpu.metrics import get_metrics

    os.makedirs(trace_dir, exist_ok=True)
    tracing.enable(True)
    tracing.set_role(role)
    tracing.install_flight_recorder(
        os.path.join(trace_dir, f"{role}.flight.json")
    )
    port = get_metrics().serve_http(0, host="127.0.0.1")
    ep = os.path.join(trace_dir, f"{role}.endpoint")
    tmp = f"{ep}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump({"role": role, "pid": os.getpid(), "port": port}, f)
    os.replace(tmp, ep)  # atomic: the collector never reads a torn file
    tracing.arm_trace_export(os.path.join(trace_dir, f"{role}.trace.json"))
    logger.info("telemetry armed for %s on 127.0.0.1:%d", role, port)
    return port


def _annotate_checkpoint_step(ckpt_dir: str, step: int) -> None:
    """Stamp the trainer's committed step onto the checkpoint done-marker:
    a replica resyncing from this checkpoint reports the step as its
    freshness floor (serving/rollover.py reads ``train_step``)."""
    from persia_tpu.checkpoint import DONE_MARKER as CKPT_DONE
    from persia_tpu.storage import StorageError, storage_path

    try:
        p = storage_path(ckpt_dir).join(CKPT_DONE)
        info = json.loads(p.read_text())
        info["train_step"] = int(step)
        p.write_text(json.dumps(info))
    except (StorageError, OSError, ValueError) as e:
        logger.warning("could not annotate checkpoint step: %s", e)


# ------------------------------------------------------------ trainer role


def trainer_main(argv: Optional[List[str]] = None) -> int:
    """Demo online trainer: train the synthetic stream, publish incremental
    packets every ``--flush-every`` steps, a full checkpoint every
    ``--ckpt-every``, a jobstate fence every ``--snapshot-every`` — and
    resume all three exactly where a crash left them."""
    ap = argparse.ArgumentParser("persia-topology-trainer")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--inc-dir", required=True)
    ap.add_argument("--job-state-dir", default=None)
    ap.add_argument("--progress-file", default=None,
                    help="per-step beacon for external killers (chaos.py)")
    ap.add_argument("--steps", type=int, default=1000)
    ap.add_argument("--rows", type=int, default=32)
    ap.add_argument("--vocab", type=int, default=100_000)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--publisher-index", type=int, default=0)
    ap.add_argument("--flush-every", type=int, default=5)
    ap.add_argument("--ckpt-every", type=int, default=200,
                    help="0 = this trainer never dumps full checkpoints")
    ap.add_argument("--snapshot-every", type=int, default=50)
    ap.add_argument("--step-ms", type=float, default=0.0,
                    help="pace the loop (an online trainer is rate-driven)")
    args = ap.parse_args(argv)

    from persia_tpu.chaos import write_progress
    from persia_tpu.health import health_enabled
    from persia_tpu.health.scrub import scrub_router
    from persia_tpu.incremental import attach_incremental
    from persia_tpu.parallel.train_step import _note_nonfinite_loss

    _arm_telemetry(f"trainer{args.publisher_index}")
    ctx, _cfg = build_demo_ctx(seed=args.seed)
    store = ctx.worker.lookup_router.replicas[0]
    with ctx:
        if args.job_state_dir:
            manifest = ctx.resume(args.job_state_dir, restore_ps=True)
            if manifest is not None:
                logger.info("trainer resumed at step %d", ctx._global_step)
        mgr = attach_incremental(
            store, args.inc_dir, replica_index=args.publisher_index,
            flush_interval_sec=3600.0,  # cadence is step-driven below
        )
        mgr.note_step(ctx._global_step)
        start = ctx._global_step
        sentinel_armed = health_enabled()
        for step in range(start, args.steps):
            out = ctx.train_step(demo_batch(step, args.rows, args.vocab,
                                            seed=args.seed,
                                            publisher=args.publisher_index))
            if sentinel_armed and isinstance(out, dict) and "loss" in out:
                _note_nonfinite_loss(float(out["loss"]))
            done = step + 1
            mgr.note_step(done)
            if args.progress_file:
                write_progress(args.progress_file, done)
            if args.flush_every and done % args.flush_every == 0:
                mgr.flush()
            if args.snapshot_every and args.job_state_dir and \
                    done % args.snapshot_every == 0:
                if sentinel_armed:
                    # fence-point scrub: repair any non-finite PS row
                    # BEFORE it can be captured into LAST_GOOD
                    scrub_router(ctx.worker.lookup_router,
                                 getattr(ctx, "_job_epoch", 0) or 0, done)
                ctx.snapshot_job(args.job_state_dir)
            if args.ckpt_every and args.ckpt_dir and done % args.ckpt_every == 0:
                ctx.dump_checkpoint(args.ckpt_dir)
                _annotate_checkpoint_step(args.ckpt_dir, done)
                mgr.flush()
            if args.step_ms > 0:
                time.sleep(args.step_ms / 1e3)
        mgr.stop(final_flush=True)
        if args.ckpt_dir:
            ctx.dump_checkpoint(args.ckpt_dir)
            _annotate_checkpoint_step(args.ckpt_dir, ctx._global_step)
        if args.job_state_dir:
            ctx.snapshot_job(args.job_state_dir)
    return 0


# ------------------------------------------------------------ replica role


def replica_main(argv: Optional[List[str]] = None) -> int:
    """Demo serving replica: ServingServer with the hot cache, the rollover
    watcher, and the live delta channel armed; registers with a coordinator
    when one is given and prints a READY line with its port."""
    ap = argparse.ArgumentParser("persia-topology-replica")
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--inc-dir", default=None)
    ap.add_argument("--replica-index", type=int, default=0)
    ap.add_argument("--coordinator", default=None)
    ap.add_argument("--cache-rows", type=int, default=1 << 15)
    ap.add_argument("--poll-s", type=float, default=0.2)
    ap.add_argument("--vocab", type=int, default=100_000)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)

    import jax

    from persia_tpu.ctx import InferCtx
    from persia_tpu.serving import ServingServer

    _arm_telemetry(f"replica{args.replica_index}")
    train_ctx, cfg = build_demo_ctx(seed=args.seed)
    # initialize dense shapes off one sample batch; the rollover watcher
    # overlays real weights the moment a checkpoint marker lands
    sample = demo_batch(0, 8, args.vocab, seed=args.seed, requires_grad=False)
    emb = train_ctx.worker.forward_directly(sample, train=False)
    device_batch, _ = train_ctx.prepare_features(sample, emb)
    train_ctx.init_state(jax.random.PRNGKey(0), device_batch)

    ctx = InferCtx(model=train_ctx.model, state=train_ctx.state,
                   worker=train_ctx.worker, embedding_config=cfg)
    srv = ServingServer(
        ctx,
        port=args.port,
        max_batch=256,
        max_wait_ms=2.0,
        cache_rows=args.cache_rows,
        ckpt_dir=args.ckpt_dir,
        inc_dir=args.inc_dir,
        rollover_poll_s=args.poll_s,
        coordinator=args.coordinator,
        replica_index=args.replica_index,
    ).start()
    print(f"{READY_LINE} port={srv.port}", flush=True)
    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *_: stop.set())
    try:
        while not stop.wait(0.5):
            pass
    except KeyboardInterrupt:
        pass
    srv.stop()
    return 0


# ------------------------------------------------------------- the topology


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


class LocalTopology:
    """One-command local cluster: K trainers + R serving replicas + a
    staleness-aware gateway (+ an optional ServiceCtx PS/worker tier as the
    discovery fabric). Every component is a real subprocess, so the chaos
    hooks (:meth:`kill_trainer` / :meth:`kill_replica` / the delta relay)
    inject the same faults production sees.

    ``delta_chaos`` (a ``chaos.ChaosConfig`` or True) routes each replica's
    delta channel through a :class:`~persia_tpu.chaos.DeltaChannelChaos`
    relay — per-replica corrupt/torn/drop faults and blackhole windows;
    without it all replicas scan the trainer's packet dir directly.
    """

    def __init__(
        self,
        ps: int = 0,
        workers: int = 0,
        trainers: int = 1,
        replicas: int = 2,
        base_dir: Optional[str] = None,
        steps: int = 2000,
        rows: int = 32,
        vocab: int = 100_000,
        step_ms: float = 5.0,
        flush_every: int = 5,
        ckpt_every: int = 200,
        snapshot_every: int = 50,
        cache_rows: int = 1 << 15,
        replica_poll_s: float = 0.2,
        max_staleness_steps: Optional[int] = None,
        max_staleness_s: Optional[float] = None,
        health_interval_s: float = 0.5,
        auto_resume: bool = True,
        max_restarts: int = 10,
        delta_chaos=None,
        seed: int = 7,
        startup_timeout_s: float = 120.0,
        trace_dir: Optional[str] = None,
    ):
        import tempfile

        self.n_ps, self.n_workers = ps, workers
        self.n_trainers, self.n_replicas = max(1, trainers), max(1, replicas)
        self.base_dir = base_dir or tempfile.mkdtemp(prefix="persia_local_")
        self.ckpt_dir = os.path.join(self.base_dir, "ckpt")
        self.inc_dir = os.path.join(self.base_dir, "inc")
        self.jobstate_dir = os.path.join(self.base_dir, "jobstate")
        for d in (self.ckpt_dir, self.inc_dir, self.jobstate_dir):
            os.makedirs(d, exist_ok=True)
        self.steps, self.rows, self.vocab = steps, rows, vocab
        self.step_ms = step_ms
        self.flush_every, self.ckpt_every = flush_every, ckpt_every
        self.snapshot_every = snapshot_every
        self.cache_rows, self.replica_poll_s = cache_rows, replica_poll_s
        self.max_staleness_steps = max_staleness_steps
        self.max_staleness_s = max_staleness_s
        self.health_interval_s = health_interval_s
        self.auto_resume, self.max_restarts = auto_resume, max_restarts
        self.seed = seed
        self.startup_timeout_s = startup_timeout_s
        self.svc = None
        self.gateway = None
        self.delta_chaos = None
        self._delta_cfg = delta_chaos
        self._trainer_procs: List[subprocess.Popen] = []
        self._replica_procs: List[Optional[subprocess.Popen]] = []
        self.replica_ports: List[int] = []
        self.trainer_restarts = 0
        self._expected_dead: set = set()
        self._watch_stop = threading.Event()
        self._watch_thread: Optional[threading.Thread] = None
        self.autopilot = None
        self._ap_stop = threading.Event()
        self._ap_thread: Optional[threading.Thread] = None
        self.healer = None
        self._env = dict(os.environ, JAX_PLATFORMS="cpu")
        self._env["PYTHONPATH"] = (
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            + os.pathsep + self._env.get("PYTHONPATH", "")
        )
        self.trace_dir = trace_dir
        if self.trace_dir:
            os.makedirs(self.trace_dir, exist_ok=True)
            # children inherit the telemetry contract through the env:
            # every role arms tracing + its /spans endpoint on boot
            self._env["PERSIA_TRACE"] = "1"
            self._env["PERSIA_TRACE_DIR"] = self.trace_dir
        # data-plane health sentinel armed by default in the demo fleet:
        # health.* events (scrub at fences, anomalies) land in the merged
        # trace alongside the fence/rollover events they correlate with
        self._env.setdefault("PERSIA_HEALTH", "1")

    # -------------------------------------------------------------- lifecycle

    def __enter__(self) -> "LocalTopology":
        try:
            return self._enter_impl()
        except BaseException:
            self.stop()
            raise

    def _enter_impl(self) -> "LocalTopology":
        from persia_tpu.serving import InferenceClient, ReplicaGateway
        from persia_tpu.service.resilience import poll_until

        if self.trace_dir:
            # the parent process hosts the gateway (and the delta relay
            # under chaos): its spans and flight events join the fleet too
            _arm_telemetry("gateway", self.trace_dir)
        coordinator = None
        if self.n_ps > 0:
            from persia_tpu.helper import ServiceCtx

            self.svc = ServiceCtx(
                num_parameter_servers=self.n_ps,
                num_embedding_workers=self.n_workers,
                startup_timeout_s=self.startup_timeout_s,
            ).__enter__()
            coordinator = f"127.0.0.1:{self.svc.coordinator.port}"
        if self._delta_cfg:
            from persia_tpu.chaos import ChaosConfig, DeltaChannelChaos

            cfg = (self._delta_cfg if not isinstance(self._delta_cfg, bool)
                   else ChaosConfig())
            self.delta_chaos = DeltaChannelChaos(
                self.inc_dir, os.path.join(self.base_dir, "delta"),
                self.n_replicas, cfg=cfg, seed=self.seed,
            ).start(interval_s=min(0.2, self.replica_poll_s))
        for k in range(self.n_trainers):
            self._trainer_procs.append(self._spawn_trainer(k))
        for i in range(self.n_replicas):
            self.replica_ports.append(_free_port())
            self._replica_procs.append(
                self._spawn_replica(i, coordinator=coordinator)
            )
        # wait for every replica's health endpoint before fronting them
        for i, port in enumerate(self.replica_ports):
            cli = InferenceClient(f"127.0.0.1:{port}", timeout_s=5.0)
            poll_until(
                lambda c=cli: c.health().get("status") == "ok",
                timeout_s=self.startup_timeout_s,
                what=f"replica {i} health",
            )
        from persia_tpu.incremental import read_head

        self.gateway = ReplicaGateway(
            replicas=[f"127.0.0.1:{p}" for p in self.replica_ports],
            health_interval_s=self.health_interval_s,
            max_staleness_steps=self.max_staleness_steps,
            max_staleness_s=self.max_staleness_s,
            # the durable source dir is the head oracle: a partition that
            # freezes every replica's delta channel cannot also freeze the
            # staleness measurement
            head_source=lambda: read_head(self.inc_dir),
        ).start()
        if self.auto_resume:
            self._watch_thread = threading.Thread(
                target=self._watch, daemon=True, name="topology-watch"
            )
            self._watch_thread.start()
        return self

    def _spawn_trainer(self, k: int) -> subprocess.Popen:
        cmd = [
            sys.executable, "-m", "persia_tpu.topology", "trainer",
            "--inc-dir", self.inc_dir,
            "--job-state-dir", os.path.join(self.jobstate_dir, f"t{k}"),
            "--progress-file", self.progress_file(k),
            "--steps", str(self.steps), "--rows", str(self.rows),
            "--vocab", str(self.vocab), "--seed", str(self.seed),
            "--publisher-index", str(k),
            "--flush-every", str(self.flush_every),
            "--snapshot-every", str(self.snapshot_every),
            "--step-ms", str(self.step_ms),
            # only publisher 0 dumps full checkpoints: one writer per dir
            "--ckpt-every", str(self.ckpt_every if k == 0 else 0),
        ]
        if k == 0:
            cmd += ["--ckpt-dir", self.ckpt_dir]
        return subprocess.Popen(cmd, env=self._env)

    def _spawn_replica(self, i: int, coordinator=None) -> subprocess.Popen:
        inc = (self.delta_chaos.inc_dir(i) if self.delta_chaos is not None
               else self.inc_dir)
        cmd = [
            sys.executable, "-m", "persia_tpu.topology", "replica",
            "--port", str(self.replica_ports[i]),
            "--ckpt-dir", self.ckpt_dir, "--inc-dir", inc,
            "--replica-index", str(i),
            "--cache-rows", str(self.cache_rows),
            "--poll-s", str(self.replica_poll_s),
            "--vocab", str(self.vocab), "--seed", str(self.seed),
        ]
        if coordinator:
            cmd += ["--coordinator", coordinator]
        return subprocess.Popen(cmd, env=self._env)

    def progress_file(self, k: int = 0) -> str:
        return os.path.join(self.base_dir, f"progress_{k}")

    # ----------------------------------------------------------- chaos hooks

    def kill_trainer(self, k: int = 0) -> None:
        """SIGKILL trainer ``k`` mid-step; the watcher (auto_resume) brings
        it back through the jobstate resume path."""
        p = self._trainer_procs[k]
        p.kill()
        p.wait(timeout=30)

    def kill_replica(self, i: int) -> None:
        """SIGKILL replica ``i`` (possibly mid-packet-apply). Marked
        expected so the watcher leaves it down until restart_replica."""
        p = self._replica_procs[i]
        if p is not None:
            self._expected_dead.add(p.pid)
            p.kill()
            p.wait(timeout=30)

    def restart_replica(self, i: int) -> None:
        """Respawn replica ``i`` on its ORIGINAL port: it boots from the
        newest checkpoint, replays the retained delta tail, and the gateway
        heals it back into rotation when its breaker re-closes."""
        self._replica_procs[i] = self._spawn_replica(i)

    # ------------------------------------------------------------- autopilot

    def live_serving(self) -> List[int]:
        """Indices of serving replicas whose process is currently alive."""
        return [i for i, p in enumerate(self._replica_procs)
                if p is not None and p.poll() is None]

    def scale_serving(self, target: int) -> int:
        """Grow/shrink the live serving replica set to ``target`` — the
        autopilot's scale actuator. Shrink drains from the highest live
        index (kill + ``gateway.remove_replica``, so no new requests route
        there); grow reuses dead slots' original ports first (the healed
        replica boots from the newest checkpoint + delta tail) before
        allocating fresh ones, waits for ``/healthz``, then folds the
        address into the gateway's balance set. Idempotent: re-driving the
        same target converges without churn. This is a pure ACTUATOR —
        the flap guards (hysteresis margin + min-dwell, CTRL001) live
        upstream in ``autopilot.PolicyEngine.decide_scale``, which decides
        ``target``; nothing here re-decides. Returns the live count."""
        from persia_tpu.serving import InferenceClient
        from persia_tpu.service.resilience import poll_until

        target = max(1, int(target))
        coordinator = (f"127.0.0.1:{self.svc.coordinator.port}"
                       if self.svc is not None else None)
        live = self.live_serving()
        while len(live) > target:
            i = live.pop()
            addr = f"127.0.0.1:{self.replica_ports[i]}"
            logger.info("autopilot scale: draining serving replica %d", i)
            self.kill_replica(i)
            self._replica_procs[i] = None
            if self.gateway is not None:
                self.gateway.remove_replica(addr)
        while len(live) < target:
            dead = [i for i in range(len(self._replica_procs))
                    if i not in live]
            if dead:
                i = dead[0]
            else:
                i = len(self._replica_procs)
                self._replica_procs.append(None)
                self.replica_ports.append(_free_port())
            logger.info("autopilot scale: spawning serving replica %d", i)
            self._replica_procs[i] = self._spawn_replica(
                i, coordinator=coordinator
            )
            addr = f"127.0.0.1:{self.replica_ports[i]}"
            cli = InferenceClient(addr, timeout_s=5.0)
            poll_until(
                lambda c=cli: c.health().get("status") == "ok",
                timeout_s=self.startup_timeout_s,
                what=f"replica {i} health",
            )
            if self.gateway is not None:
                self.gateway.add_replica(addr)
            live.append(i)
        return len(live)

    def start_autopilot(self, interval_s: float = 2.0, config=None):
        """Arm the parent-side serving autopilot: a timer thread sensing
        the gateway (QPS, quarantine pressure) and actuating
        :meth:`scale_serving`, every decision two-phase-journaled under
        ``base_dir/autopilot`` and resumed on re-arm. The PS-reshard and
        hot-replication actuators are fence-driven and live INSIDE the
        trainer (``train_stream(fence_callback=pilot.on_fence)``, see
        persia_tpu/autopilot) — this thread covers the serving plane,
        whose control loop has no fence to ride. All flap suppression
        (hysteresis margin + min-dwell) happens in the shared
        :class:`~persia_tpu.autopilot.PolicyEngine` on the decision path,
        never here."""
        from persia_tpu.autopilot import (
            Autopilot, PolicyConfig, PolicyEngine, gateway_sensors,
        )

        self.autopilot = Autopilot(
            os.path.join(self.base_dir, "autopilot", "decisions"),
            policy=PolicyEngine(config or PolicyConfig()),
            scale_to=self.scale_serving,
            serving_sensors=gateway_sensors(self.gateway),
        )
        self.autopilot.resume()

        def _loop() -> None:
            tick = 0
            while not self._ap_stop.wait(interval_s):
                tick += 1
                try:
                    self.autopilot.on_tick(tick)
                except Exception:
                    logger.exception("autopilot tick %d failed", tick)

        self._ap_thread = threading.Thread(
            target=_loop, daemon=True, name="autopilot"
        )
        self._ap_thread.start()
        return self.autopilot

    def start_self_heal(self, interval_s: float = 0.5, **kw):
        """Arm the self-healing control plane over the PS tier (needs
        ``ps > 0``): a lease+probe :class:`FailureDetector` feeding a
        :class:`~persia_tpu.autopilot.Healer` whose decisions journal
        under ``base_dir/selfheal`` — a SIGKILLed PS is detected, a warm
        standby promoted from the last fence snapshot, and the fleet
        registration re-pointed, with no operator in the loop. Any heal
        interrupted by a parent crash is re-driven by ``resume()`` on
        re-arm. Extra ``**kw`` forwards to
        :func:`~persia_tpu.autopilot.enable_self_heal` (router, configs,
        sensors...)."""
        from persia_tpu.autopilot import enable_self_heal

        if self.svc is None:
            raise RuntimeError("start_self_heal needs a PS tier (ps > 0)")
        if self.healer is None:
            state = os.path.join(self.base_dir, "selfheal")
            os.makedirs(state, exist_ok=True)
            self.healer = enable_self_heal(self.svc, state, **kw)
            self.healer.resume()
            self.healer.start(interval_s)
        return self.healer

    def reshard_ps(self, n_new: int, **kw) -> Dict:
        """Live-reshard the PS tier to ``n_new`` replicas (needs ``ps > 0``):
        delegates to :meth:`ServiceCtx.reshard_ps` with a journal dir under
        this topology's base_dir, so an interrupted reshard resumes through
        ``self.svc.resume_reshard`` against the same manifests. Accepts the
        same keyword knobs (``planner``/``profiler``/``router``/
        ``fault_hook``/...)."""
        if self.svc is None:
            raise RuntimeError("reshard_ps needs a PS tier (ps > 0)")
        js = os.path.join(self.base_dir, "reshard_js")
        os.makedirs(js, exist_ok=True)
        return self.svc.reshard_ps(n_new, js, **kw)

    def _watch(self) -> None:
        while not self._watch_stop.wait(0.3):
            for k, p in enumerate(self._trainer_procs):
                rc = p.poll()
                if rc is not None and rc != 0 and p.pid not in self._expected_dead:
                    if self.trainer_restarts >= self.max_restarts:
                        logger.error("trainer %d dead (rc=%s); restart budget "
                                     "exhausted", k, rc)
                        self._expected_dead.add(p.pid)
                        continue
                    self.trainer_restarts += 1
                    logger.warning(
                        "trainer %d died (rc=%s); auto-resume %d/%d",
                        k, rc, self.trainer_restarts, self.max_restarts,
                    )
                    self._trainer_procs[k] = self._spawn_trainer(k)

    # ----------------------------------------------------------------- state

    def trainer_running(self) -> bool:
        return any(p.poll() is None for p in self._trainer_procs)

    def trainer_step(self, k: int = 0) -> int:
        from persia_tpu.chaos import read_progress

        return read_progress(self.progress_file(k))

    def stats(self) -> Dict:
        out = {
            "trainer_steps": [self.trainer_step(k)
                              for k in range(self.n_trainers)],
            "trainer_restarts": self.trainer_restarts,
            "replica_ports": list(self.replica_ports),
        }
        if self.gateway is not None:
            out["gateway"] = self.gateway.stats()
        if self.delta_chaos is not None:
            out["delta_channel"] = dict(self.delta_chaos.counts)
        if self.autopilot is not None:
            out["autopilot_rounds"] = self.autopilot.rounds
        if self.healer is not None:
            out["heal_verdicts"] = self.healer.detector.verdicts()
            out["heal_mttr_s"] = list(self.healer.mttr_s)
        if self.svc is not None:
            out["n_ps"] = self.svc.n_ps
            if self.svc.ps_ring is not None:
                out["ps_ring"] = [int(x) for x in self.svc.ps_ring]
        return out

    # ------------------------------------------------------------- telemetry

    def telemetry_endpoints(self) -> Dict[str, Dict]:
        """``role -> {pid, port}`` read from the atomic ``<role>.endpoint``
        files every armed role writes on boot (empty when tracing is off)."""
        out: Dict[str, Dict] = {}
        if not self.trace_dir:
            return out
        for fn in sorted(os.listdir(self.trace_dir)):
            if not fn.endswith(".endpoint"):
                continue
            try:
                with open(os.path.join(self.trace_dir, fn)) as f:
                    info = json.load(f)
                out[str(info["role"])] = {
                    "pid": int(info["pid"]), "port": int(info["port"]),
                }
            except (OSError, ValueError, KeyError):
                continue
        return out

    @staticmethod
    def _scrape(port: int, path: str, drain: bool = False):
        """GET one telemetry endpoint; returns ``(doc, offset_us)`` where
        ``offset_us`` is the remote clock minus the local clock, estimated
        from the remote ``now_us`` sample against the local midpoint of the
        request (the classic NTP-style half-RTT handshake)."""
        import urllib.request

        url = f"http://127.0.0.1:{port}{path}" + ("?drain=1" if drain else "")
        t0 = time.time()
        with urllib.request.urlopen(url, timeout=5) as resp:
            doc = json.loads(resp.read())
        t1 = time.time()
        offset_us = float(doc.get("now_us", 0.0)) - (t0 + t1) / 2.0 * 1e6
        return doc, offset_us

    def _role_events(self, role: str, info: Dict, kind: str, drain: bool):
        """One role's span (or flight) events, clock-aligned into THIS
        process's wall clock. A dead role falls back to the trace file its
        atexit export left behind (offset 0 — same host, same clock)."""
        try:
            doc, offset_us = self._scrape(
                info["port"], f"/{kind}", drain=drain
            )
            return doc.get("spans" if kind == "spans" else "events", []), \
                offset_us
        except (OSError, ValueError):
            if kind != "spans":
                # a finished role's flight ring lives in its atexit dump
                # (trainers exit long before the merge; their health.* /
                # fence events must still make the ledger)
                path = os.path.join(self.trace_dir, f"{role}.flight.json")
                try:
                    with open(path) as f:
                        return json.load(f).get("events", []), 0.0
                except (OSError, ValueError):
                    return [], 0.0
            path = os.path.join(self.trace_dir, f"{role}.trace.json")
            try:
                with open(path) as f:
                    return json.load(f).get("traceEvents", []), 0.0
            except (OSError, ValueError):
                return [], 0.0

    def merge_traces(self, out_path: Optional[str] = None,
                     drain: bool = False) -> Optional[str]:
        """Fleet aggregation: scrape every role's ``/spans`` ring, align
        clocks via the offset handshake, and write ONE Perfetto-loadable
        timeline (plus a merged flight-event ledger) into the trace dir.
        Returns the merged trace path (None when tracing is off)."""
        from persia_tpu import tracing

        if not self.trace_dir:
            return None
        merged: List[Dict] = []
        flight: List[Dict] = []
        meta: List[Dict] = []
        offsets: Dict[str, float] = {}
        for role, info in sorted(self.telemetry_endpoints().items()):
            events, offset_us = self._role_events(role, info, "spans", drain)
            offsets[role] = offset_us
            for ev in events:
                ev = dict(ev)
                ev["ts"] = float(ev.get("ts", 0.0)) - offset_us
                merged.append(ev)
            fl, f_off = self._role_events(role, info, "flight", drain)
            for ev in fl:
                ev = dict(ev)
                ev["ts_us"] = float(ev.get("ts_us", 0.0)) - f_off
                ev["role"] = role
                flight.append(ev)
            # Perfetto names each process track after its role
            meta.append({
                "name": "process_name", "ph": "M", "pid": info["pid"],
                "args": {"name": role},
            })
        merged.sort(key=lambda ev: ev.get("ts", 0.0))
        flight.sort(key=lambda ev: ev.get("ts_us", 0.0))
        doc = {
            "traceEvents": meta + merged,
            "displayTimeUnit": "ms",
            "metadata": {
                "merged_by_pid": os.getpid(),
                "clock_offsets_us": offsets,
                "roles": sorted(offsets),
            },
        }
        out = out_path or os.path.join(self.trace_dir, "merged_trace.json")
        tracing._atomic_write_json(out, doc)
        tracing._atomic_write_json(
            os.path.join(self.trace_dir, "merged_flight.json"),
            {"events": flight},
        )
        logger.info("merged %d spans + %d flight events from %d roles -> %s",
                    len(merged), len(flight), len(offsets), out)
        return out

    def stop(self) -> None:
        if self.healer is not None:
            self.healer.stop()
            self.healer.detector.close()
            self.healer = None
        self._ap_stop.set()
        if self._ap_thread is not None:
            self._ap_thread.join(timeout=5)
        self._watch_stop.set()
        if self._watch_thread is not None:
            self._watch_thread.join(timeout=5)
        if self.gateway is not None:
            self.gateway.stop()
        if self.delta_chaos is not None:
            self.delta_chaos.stop()
        procs = [p for p in self._trainer_procs if p is not None]
        procs += [p for p in self._replica_procs if p is not None]
        for p in procs:
            if p.poll() is None:
                p.terminate()
        deadline = time.time() + 10
        for p in procs:
            try:
                p.wait(timeout=max(0.1, deadline - time.time()))
            except subprocess.TimeoutExpired:
                p.kill()
        if self.svc is not None:
            self.svc.__exit__(None, None, None)
            self.svc = None

    def __exit__(self, *exc):
        self.stop()
        return False


def main(argv: Optional[List[str]] = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if not argv:
        print("usage: python -m persia_tpu.topology {trainer|replica} ...",
              file=sys.stderr)
        return 2
    role, rest = argv[0], argv[1:]
    if role == "trainer":
        return trainer_main(rest)
    if role == "replica":
        return replica_main(rest)
    print(f"unknown topology role {role!r}", file=sys.stderr)
    return 2


if __name__ == "__main__":
    sys.exit(main())
