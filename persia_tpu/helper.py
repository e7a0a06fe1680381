"""ServiceCtx: single-machine fake cluster for tests and quick starts.

Parity target: `persia/helper.py:125-331` — spawns nats-server + embedding
workers + parameter servers as local subprocesses with random ports so
integration tests exercise the real multi-process topology without a
cluster; includes a crash watchdog (helper.py:296-315).

Here: an in-process Coordinator + N parameter-server subprocesses + M
embedding-worker subprocesses; `worker_clients()` hands back RPC clients
with the EmbeddingWorker surface for TrainCtx/DataLoader.
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys
import threading
import time
from typing import List, Optional

from persia_tpu.config import EmbeddingConfig
from persia_tpu.logger import get_default_logger
from persia_tpu.service.clients import StoreClient, WorkerClient
from persia_tpu.service.discovery import Coordinator, CoordinatorClient

logger = get_default_logger("persia_tpu.helper")


class ServiceCtx:
    def __init__(
        self,
        num_parameter_servers: int = 1,
        num_embedding_workers: int = 1,
        embedding_config_path: Optional[str] = None,
        global_config_path: Optional[str] = None,
        capacity: int = 1 << 18,
        num_internal_shards: int = 4,
        backend: str = "auto",
        seed: int = 0,
        startup_timeout_s: float = 60.0,
    ):
        self.n_ps = num_parameter_servers
        self.n_workers = num_embedding_workers
        self.embedding_config_path = embedding_config_path
        self.global_config_path = global_config_path
        self.capacity = capacity
        self.num_internal_shards = num_internal_shards
        self.backend = backend
        self.seed = seed
        self.startup_timeout_s = startup_timeout_s
        self.procs: List[subprocess.Popen] = []
        self.coordinator: Optional[Coordinator] = None
        self._watchdog_stop = threading.Event()
        self._crashed: Optional[str] = None
        self._expected_dead: set = set()
        # failover state: last dump_shard snapshot per PS index (fed by
        # snapshot_ps / the snapshot guard; replayed by restart_ps /
        # promote_standby), and any spawned-but-unregistered standbys
        self._ps_snapshots: dict = {}
        self._standbys: List[tuple] = []  # (addr, Popen)
        self._guard_stop = threading.Event()
        self._guard_thread: Optional[threading.Thread] = None
        # elastic tier: the PS ring currently in force (None = the legacy
        # modulo topology every fresh cluster starts with); set by
        # reshard_ps / resume_reshard and published to the coordinator KV
        # as "ps_ring" so late joiners route by the live ring
        self.ps_ring = None

    # ------------------------------------------------------------ lifecycle

    def __enter__(self) -> "ServiceCtx":
        try:
            return self._enter_impl()
        except BaseException:
            # __exit__ never runs if __enter__ raises: reap spawned services
            self._teardown(grace_s=0.0)
            raise

    def _enter_impl(self) -> "ServiceCtx":
        self.coordinator = Coordinator(port=0).start()
        coord_addr = f"127.0.0.1:{self.coordinator.port}"
        env = dict(os.environ)
        env["PYTHONPATH"] = (
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            + os.pathsep
            + env.get("PYTHONPATH", "")
        )
        # services never need a TPU; keep them off the chip
        env["JAX_PLATFORMS"] = "cpu"

        self._env = env
        self._coord_addr = coord_addr
        self._ps_procs: List[subprocess.Popen] = []
        for i in range(self.n_ps):
            p = subprocess.Popen(self._ps_cmd(i), env=env)
            self._ps_procs.append(p)
            self.procs.append(p)

        for i in range(self.n_workers):
            cmd = [
                sys.executable, "-m", "persia_tpu.service.worker_server",
                "--replica-index", str(i), "--replica-size", str(self.n_workers),
                "--coordinator", coord_addr,
                "--num-parameter-servers", str(self.n_ps),
            ]
            if self.embedding_config_path:
                cmd += ["--embedding-config", self.embedding_config_path]
            if self.global_config_path:
                cmd += ["--global-config", self.global_config_path]
            self.procs.append(subprocess.Popen(cmd, env=env))

        self.coord_client = CoordinatorClient(coord_addr)
        # wait for BOTH roles: a worker-less cluster (e.g. the cached tier's
        # trainer-direct-to-PS shape) must still see its PS replicas
        # registered before ps_clients() is usable
        self.coord_client.wait_for(
            "parameter_server", self.n_ps, timeout_s=self.startup_timeout_s
        )
        self.coord_client.wait_for(
            "embedding_worker", self.n_workers, timeout_s=self.startup_timeout_s
        )
        self._watchdog = threading.Thread(target=self._watch, daemon=True)
        self._watchdog.start()
        return self

    def _ps_cmd(self, i: int, port: int = 0) -> List[str]:
        cmd = [
            sys.executable, "-m", "persia_tpu.service.ps_server",
            "--replica-index", str(i), "--replica-size", str(self.n_ps),
            "--coordinator", self._coord_addr,
            "--capacity", str(self.capacity),
            "--num-internal-shards", str(self.num_internal_shards),
            "--backend", self.backend, "--seed", str(self.seed),
        ]
        if port:
            cmd += ["--port", str(port)]
        if self.global_config_path:
            cmd += ["--global-config", self.global_config_path]
        return cmd

    # ---------------------------------------------------- failure injection

    def kill_ps(self, i: int) -> None:
        """SIGKILL parameter server ``i`` (fault injection for recovery
        tests; the watchdog ignores PSs killed through this API)."""
        p = self._ps_procs[i]
        self._expected_dead.add(p.pid)
        p.kill()
        p.wait(timeout=10)

    def snapshot_ps(self, i: int, job_state=None) -> int:
        """Record PS ``i``'s full state (every internal shard's
        ``dump_shard`` bytes, plus the registered optimizer config — a
        restored shard serving lookups without its optimizer would
        re-initialize every restored entry on entry-width mismatch) for a
        later replaying restart/promotion. Returns the snapshot's total
        byte size.

        ``job_state`` (a directory or :class:`~persia_tpu.jobstate.
        JobStateManager`) additionally commits the snapshot as a DURABLE
        manifest epoch, so the failover state survives the ServiceCtx
        process itself: a fresh process calls
        :meth:`restore_ps_snapshots` and can ``restart_ps(restore=True)``
        replicas it never snapshotted in-memory."""
        c = StoreClient(self.ps_addrs()[i])
        shards = [
            c.dump_shard(s) for s in range(c.num_internal_shards)
        ]
        opt = c.get_optimizer()
        opt_dict = opt.to_dict() if opt else None
        self._ps_snapshots[i] = (shards, opt_dict)
        if job_state is not None:
            from persia_tpu import jobstate

            writer = jobstate.coerce_manager(job_state).begin_epoch()
            for si, blob in enumerate(shards):
                writer.add_blob(f"ps/replica_{i}_shard_{si}.emb", blob)
            writer.commit({
                "kind": "ps_failover",
                "replica_index": i,
                "n_shards": len(shards),
                "optimizer": opt_dict,
            })
        return sum(len(s) for s in shards)

    def restore_ps_snapshots(self, job_state) -> List[int]:
        """Rebuild the in-memory failover snapshot cache from durable
        ``snapshot_ps(..., job_state=)`` manifests — the path a REPLACEMENT
        ServiceCtx process takes after the original host died. Newest
        manifest per replica wins; replicas already cached in memory are
        left alone. Returns the replica indices restored."""
        from persia_tpu import jobstate

        mgr = jobstate.coerce_manager(job_state)
        found: List[int] = []
        for _e, d in reversed(mgr._epoch_dirs()):
            m = mgr._load_manifest(d)
            if m is None or m.meta.get("kind") != "ps_failover":
                continue
            ri = int(m.meta["replica_index"])
            if ri in self._ps_snapshots or ri in found:
                continue
            shards = [
                m.read_blob(f"ps/replica_{ri}_shard_{si}.emb")
                for si in range(int(m.meta["n_shards"]))
            ]
            self._ps_snapshots[ri] = (shards, m.meta.get("optimizer"))
            found.append(ri)
        return found

    def start_snapshot_guard(
        self, interval_s: float = 5.0, job_state=None
    ) -> None:
        """Background snapshot loop over every PS — the failover state
        source when a shard dies without warning. Snapshot staleness is
        bounded by ``interval_s`` (the accepted loss window, exactly like
        a periodic checkpoint). ``job_state`` makes every guard snapshot
        durable (see :meth:`snapshot_ps`)."""
        if self._guard_thread is not None:
            return

        def loop():
            while not self._guard_stop.wait(interval_s):
                for i in range(self.n_ps):
                    try:
                        self.snapshot_ps(i, job_state=job_state)
                    except Exception as e:  # noqa: BLE001 — shard may be down
                        logger.warning("snapshot guard: ps %d failed: %s", i, e)

        self._guard_thread = threading.Thread(
            target=loop, daemon=True, name="ps-snapshot-guard"
        )
        self._guard_thread.start()

    def restart_ps(self, i: int, restore: bool = False) -> None:
        """Respawn parameter server ``i`` on its ORIGINAL port so existing
        clients reconnect transparently. ``restore=False``: fresh store
        (k8s pod restart without a boot checkpoint). ``restore=True``:
        replay the last ``snapshot_ps`` state as a BOOT load
        (``--load-shards``) — the new process only answers its first probe
        after the replay, so a reconnecting client can never observe the
        un-restored store and mistake trained signs for cold ones (loss
        stays bounded by snapshot staleness)."""
        import json
        import tempfile

        addr = self.ps_addrs()[i]
        port = int(addr.rsplit(":", 1)[1])
        cmd = self._ps_cmd(i, port=port)
        snap = self._ps_snapshots.get(i) if restore else None
        tmp_files = []
        if snap:
            shards, opt_dict = snap
            fd, snap_file = tempfile.mkstemp(prefix=f"ps{i}_boot_", suffix=".shards")
            tmp_files.append(snap_file)
            with os.fdopen(fd, "wb") as f:
                for raw in shards:
                    f.write(len(raw).to_bytes(8, "little"))
                    f.write(raw)
            cmd += ["--load-shards", snap_file]
            if opt_dict:
                fd, opt_file = tempfile.mkstemp(prefix=f"ps{i}_opt_", suffix=".json")
                tmp_files.append(opt_file)
                with os.fdopen(fd, "w") as f:
                    json.dump(opt_dict, f)
                cmd += ["--boot-optimizer", opt_file]
        p = subprocess.Popen(cmd, env=self._env)
        self.procs.append(p)
        self._ps_procs[i] = p
        try:
            StoreClient(addr).wait_ready(timeout_s=self.startup_timeout_s)
        finally:
            for path in tmp_files:
                try:
                    os.unlink(path)
                except OSError:
                    pass

    def _replay_snapshot(self, i: int, client: StoreClient) -> int:
        snap = self._ps_snapshots.get(i)
        if not snap:
            return 0
        shards, opt_dict = snap
        if opt_dict:
            # optimizer FIRST: a store without it re-initializes restored
            # entries on the first train lookup (entry-width mismatch)
            from persia_tpu.embedding.optim import OptimizerConfig

            client.register_optimizer(OptimizerConfig.from_dict(opt_dict))
        return sum(client.load_shard_bytes(raw) for raw in shards)

    # ---------------------------------------------------- standby failover

    def spawn_standby_ps(self) -> str:
        """Start a spare, UNREGISTERED parameter server (same config) and
        return its address. It idles until ``promote_standby`` loads a dead
        shard's snapshot into it and re-points the coordinator entry."""
        # reserve a port (races are theoretically possible but this is a
        # single-machine test/bench topology)
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        cmd = [
            sys.executable, "-m", "persia_tpu.service.ps_server",
            "--port", str(port),
            "--replica-index", "0", "--replica-size", str(self.n_ps),
            "--capacity", str(self.capacity),
            "--num-internal-shards", str(self.num_internal_shards),
            "--backend", self.backend, "--seed", str(self.seed),
        ]
        if self.global_config_path:
            cmd += ["--global-config", self.global_config_path]
        p = subprocess.Popen(cmd, env=self._env)
        self.procs.append(p)
        addr = f"127.0.0.1:{port}"
        StoreClient(addr).wait_ready(timeout_s=self.startup_timeout_s)
        self._standbys.append((addr, p))
        return addr

    def promote_standby(self, i: int, standby_addr: Optional[str] = None,
                        batch_advances: Optional[dict] = None) -> str:
        """Fail shard ``i`` over onto a standby: replay the last snapshot
        into it and upsert the coordinator registration so new clients
        resolve the standby's address. Callers holding an in-process
        router should also swap the replica handle
        (``router.replace_replica(i, StoreClient(new_addr))``).

        ``batch_advances`` (``{group: count}``) re-advances the standby's
        per-group optimizer batch counters to the fleet's fence — a parked
        standby never saw a batch, so its Adam beta powers sit at t=0 while
        the survivors advanced; shard snapshots carry entries, NOT the
        batch-state clock (same contract as the elastic joiner path).
        Returns the promoted address."""
        from persia_tpu import elastic

        proc = None
        if standby_addr is None:
            if not self._standbys:
                raise RuntimeError("no standby spawned (spawn_standby_ps first)")
            standby_addr, proc = self._standbys.pop(0)
        else:
            for j, (a, p) in enumerate(self._standbys):
                if a == standby_addr:
                    proc = self._standbys.pop(j)[1]
                    break
        c = StoreClient(standby_addr)
        c.wait_ready(timeout_s=self.startup_timeout_s)
        self._replay_snapshot(i, c)
        # optimizer came from the snapshot replay; only the batch-state
        # clock is left to catch up
        elastic.prime_joiner(c, None, batch_advances)
        self.coord_client.register("parameter_server", i, standby_addr)
        if proc is not None:
            while len(self._ps_procs) <= i:
                self._ps_procs.append(proc)
            self._ps_procs[i] = proc
        return standby_addr

    # ------------------------------------------------------- self-heal hooks

    def heal_promote(self, i: int, *, router=None,
                     batch_advances: Optional[dict] = None,
                     fault_hook=None) -> str:
        """Autonomous failover of a DEAD shard ``i``: promote a warm
        standby (spawning one when none is parked), then swap the live
        router handle so in-flight callers migrate without an operator.

        Idempotent end to end — snapshot replay into a fresh standby is
        deterministic, batch re-advance is replayed from the same counts,
        and the coordinator registration is an upsert — so the healer's
        two-phase journal may re-drive this after a mid-heal SIGKILL and
        converge on a bit-identical fleet (a half-promoted orphan standby
        is re-pointed away from and reaped at teardown). ``fault_hook``
        (stage names ``"promoted"``/``"swapped"``) is the chaos plane's
        mid-heal crash injection point."""
        if not self._standbys:
            self.spawn_standby_ps()
        addr = self.promote_standby(i, batch_advances=batch_advances)
        if fault_hook is not None:
            fault_hook("promoted")
        if router is not None:
            router.replace_replica(i, StoreClient(addr))
        if fault_hook is not None:
            fault_hook("swapped")
        logger.info("heal: promoted standby %s for dead ps %d", addr, i)
        return addr

    def heal_drain_gray(self, i: int, *, router=None,
                        batch_advances: Optional[dict] = None,
                        fault_hook=None) -> str:
        """Replace a limping (GRAY) replica without dropping in-flight
        requests: live-snapshot it (it still answers — that is what makes
        it gray rather than dead), promote a standby from that fresh
        snapshot, swap the router so NEW calls route to the standby while
        calls already in flight finish on the old handle, then drain the
        old process with a graceful shutdown RPC."""
        old_addr = self.ps_addrs()[i]
        old_proc = self._ps_procs[i] if i < len(self._ps_procs) else None
        self.snapshot_ps(i)
        if fault_hook is not None:
            fault_hook("snapshotted")
        if not self._standbys:
            self.spawn_standby_ps()
        addr = self.promote_standby(i, batch_advances=batch_advances)
        if fault_hook is not None:
            fault_hook("promoted")
        if router is not None:
            router.replace_replica(i, StoreClient(addr))
        if fault_hook is not None:
            fault_hook("swapped")
        # drain, don't SIGKILL: the shutdown RPC lets handlers already on
        # the old socket complete before the process exits
        if old_proc is not None and old_proc.poll() is None:
            self._expected_dead.add(old_proc.pid)
            StoreClient(old_addr).shutdown()
        logger.info("heal: drained gray ps %d (%s -> %s)", i, old_addr, addr)
        return addr

    def ps_probes(self, timeout_s: float = 1.0) -> dict:
        """Per-replica one-attempt healthz probes for a FailureDetector."""
        from persia_tpu.service.failure_detector import ps_fleet_probes

        return ps_fleet_probes(self.ps_addrs(), timeout_s=timeout_s)

    def ps_lease_reader(self):
        """Lease scan over the PS fleet's coordinator kv leases."""
        from persia_tpu.service.failure_detector import coordinator_lease_reader

        return coordinator_lease_reader(self.coord_client, "parameter_server")

    # ------------------------------------------------------ elastic reshard

    def _publish_ring(self, splits) -> None:
        import numpy as np

        self.ps_ring = np.asarray(splits, dtype=np.uint64)
        self.coord_client.kv_put(
            "ps_ring", self.ps_ring.astype("<u8").tobytes()
        )

    def _grow_ps(self, i: int) -> str:
        """Bring replica ``i`` (>= current fleet) online: reuse an idle
        standby if one was pre-spawned (warm add — no process startup on
        the critical path), else spawn one, then claim the coordinator
        slot. Extends the per-index process table so restart_ps/kill_ps
        address the new replica like any other."""
        if not self._standbys:
            self.spawn_standby_ps()
        addr, p = self._standbys.pop(0)
        self.coord_client.register("parameter_server", i, addr)
        while len(self._ps_procs) <= i:
            self._ps_procs.append(p)
        self._ps_procs[i] = p
        return addr

    def reshard_ps(
        self,
        n_new: int,
        job_state,
        *,
        step: int = 0,
        splits=None,
        planner=None,
        profiler=None,
        router=None,
        fault_hook=None,
        batch_advances=None,
        abort_check=None,
    ) -> dict:
        """Live-reshard the PS tier to ``n_new`` replicas at a drained
        stream fence (the caller guarantees nothing is in flight). The new
        ring comes from ``splits`` if given, else a sparsity-aware
        ``planner.plan(n_new, profiler=...)`` (load-weighted boundaries
        from the tiering access sketch), else hash-uniform. Handoffs run
        under the exactly-once journal discipline of
        :mod:`persia_tpu.elastic`; a crash at ANY point (ours or a PS's)
        resumes via :meth:`resume_reshard` to a state bit-identical to an
        uninterrupted reshard. ``router`` (a ``ShardedLookup``) is swapped
        to the new ring at the imported boundary; ``fault_hook`` is the
        chaos plane's injection point. ``abort_check`` (the arbiter's
        preemption flag) lets a higher-priority intent roll the reshard
        back at a phase boundary: the engine raises
        ``elastic.ReshardAborted`` after the journaled rollback, and the
        topology bookkeeping (grown joiners, replica count) is restored
        to the old ring before the exception propagates."""
        from persia_tpu import elastic, jobstate
        from persia_tpu.embedding.hashing import uniform_splits

        mgr = jobstate.coerce_manager(job_state)
        old_n = self.n_ps
        old_addrs = self.ps_addrs()
        if splits is None:
            if planner is not None:
                splits = planner.plan(n_new, profiler=profiler).splits
            else:
                splits = uniform_splits(n_new)
        old_splits = None if self.ps_ring is None else [int(x) for x in self.ps_ring]
        plan = elastic.plan_reshard(
            old_n, n_new, old_splits, splits,
            elastic.reshard_base_id(mgr, step),
        )

        sources = [StoreClient(a) for a in old_addrs]
        opt = sources[0].get_optimizer()
        opt_dict = opt.to_dict() if opt else None
        dest_addrs = list(old_addrs[:min(old_n, n_new)])
        for i in range(old_n, n_new):
            dest_addrs.append(self._grow_ps(i))
        dests = [
            sources[i] if i < old_n else StoreClient(dest_addrs[i])
            for i in range(n_new)
        ]
        # joiners need the optimizer BEFORE the first import: a store
        # without it re-initializes imported entries on entry-width
        # mismatch at the first train lookup (see _replay_snapshot), and
        # Adam joiners additionally re-advance beta powers to the fence
        for i in range(old_n, n_new):
            elastic.prime_joiner(dests[i], opt, batch_advances)
        self.n_ps = max(old_n, n_new)

        try:
            stats = elastic.execute_reshard(
                plan, sources, dests, mgr,
                fault_hook=fault_hook,
                on_imported=self._ring_swapper(router, dests, splits),
                extra_meta={"optimizer": opt_dict,
                            "batch_advances": {str(k): int(v) for k, v in
                                               (batch_advances or {}).items()}},
                abort_check=abort_check,
            )
        except elastic.ReshardAborted:
            self._finalize_abort(plan)
            raise
        self._finalize_reshard(plan, splits)
        stats["skew_splits"] = [int(x) for x in splits]
        return stats

    def _ring_swapper(self, router, dests, splits):
        if router is None:
            return None

        def swap():
            import numpy as np

            router.swap_topology(list(dests), ring=np.asarray(splits, np.uint64))

        return swap

    def _finalize_reshard(self, plan, splits) -> None:
        """Post-``done`` topology bookkeeping: drop drained replicas from
        the registry and the process table, publish the new ring."""
        for i in range(plan.new_n, plan.old_n):
            self.coord_client.deregister("parameter_server", i)
            self.kill_ps(i)
        self._ps_procs = self._ps_procs[: plan.new_n]
        self.n_ps = plan.new_n
        self._publish_ring(splits)

    def _finalize_abort(self, plan) -> None:
        """Post-``aborted`` topology bookkeeping: the fleet is back on the
        OLD ring — joiners grown for the preempted plan are drained (their
        imported arcs were released by the abort arm) and the replica
        count restored. The ring was never republished, so there is
        nothing to swap back."""
        for i in range(plan.old_n, plan.new_n):
            self.coord_client.deregister("parameter_server", i)
            self.kill_ps(i)
        self._ps_procs = self._ps_procs[: plan.old_n]
        self.n_ps = plan.old_n

    def resume_reshard(self, job_state, *, router=None, fault_hook=None,
                       abort_check=None):
        """Re-enter a reshard interrupted by a SIGKILL — of a source PS, a
        dest PS, or the coordinating process itself. Restores dead replicas
        per the crash matrix (fence snapshot for sources mid-handoff, fresh
        + re-import for dests mid-handoff, post-import snapshot for dests
        mid-delete), then replays the recorded plan; every op the crashed
        run already applied dedupes against the PS apply-journal. Returns
        the run stats, or None when there is nothing to resume. A plan
        recorded mid-abort (phase ``aborting``) re-enters the rollback arm
        instead: dead survivors restore from the ``handoff`` manifest's
        fence snapshots, the remaining arc releases replay (dedupe), and
        the OLD topology is finalized."""
        from persia_tpu import elastic, jobstate
        from persia_tpu.embedding.optim import OptimizerConfig

        mgr = jobstate.coerce_manager(job_state)
        man = elastic.find_reshard_manifest(mgr)
        if man is None or man.meta.get("phase") in ("done", "aborted"):
            return None
        plan = elastic.ReshardPlan.from_meta(man.meta)
        phase = man.meta["phase"]
        opt_dict = man.meta.get("optimizer")
        self.n_ps = max(plan.old_n, plan.new_n)
        addrs = self.ps_addrs()

        def dead(i: int) -> bool:
            return i >= len(self._ps_procs) or self._ps_procs[i].poll() is not None

        if phase == "aborting":
            # mid-rollback: survivors restore to their fence snapshot (the
            # ``handoff`` manifest holds it); the replayed arc releases
            # then apply as no-ops or dedupe either way. Joiners restart
            # fresh only so the release RPCs land — _finalize_abort drains
            # them right after.
            hman = elastic.find_phase_manifest(mgr, "handoff", plan.base_id)
            for i in range(plan.new_n):
                if not dead(i):
                    continue
                if i < plan.old_n and hman is not None:
                    self._ps_snapshots[i] = (
                        elastic.source_snapshot(hman, i), opt_dict,
                    )
                    self.restart_ps(i, restore=True)
                else:
                    self.restart_ps(i, restore=False)
        elif phase == "handoff":
            for i in range(plan.old_n):
                if dead(i):
                    self._ps_snapshots[i] = (
                        elastic.source_snapshot(man, i), opt_dict,
                    )
                    self.restart_ps(i, restore=True)
            for i in range(plan.old_n, plan.new_n):
                if dead(i):
                    # a joiner's journal died with it: restart FRESH, the
                    # replayed imports re-apply the identical blobs
                    self.restart_ps(i, restore=False)
                    elastic.prime_joiner(
                        StoreClient(addrs[i]),
                        OptimizerConfig.from_dict(opt_dict) if opt_dict else None,
                        man.meta.get("batch_advances"),
                    )
        else:  # "imported": only surviving replicas matter for the deletes
            for i in range(plan.new_n):
                if dead(i):
                    self._ps_snapshots[i] = (
                        elastic.dest_snapshot(man, i), opt_dict,
                    )
                    self.restart_ps(i, restore=True)

        sources = [StoreClient(a) for a in addrs[: plan.old_n]]
        dests = [
            sources[i] if i < plan.old_n else StoreClient(addrs[i])
            for i in range(plan.new_n)
        ]
        splits = plan.new_splits
        try:
            stats = elastic.resume_reshard(
                mgr, sources, dests, fault_hook=fault_hook,
                on_imported=self._ring_swapper(router, dests, splits),
                abort_check=abort_check,
            )
        except elastic.ReshardAborted:
            self._finalize_abort(plan)
            raise
        if stats is not None:
            if stats.get("aborted"):
                self._finalize_abort(plan)
            else:
                self._finalize_reshard(plan, splits)
        return stats

    def _watch(self):
        """Crash watchdog (ref: helper.py:296-315): if any service process
        dies, record it so clients fail fast instead of hanging."""
        while not self._watchdog_stop.wait(0.5):
            for p in self.procs:
                rc = p.poll()
                if rc is not None and rc != 0 and p.pid not in self._expected_dead:
                    self._crashed = f"service pid {p.pid} exited with {rc}"
                    logger.error(self._crashed)
                    return

    def check_healthy(self):
        if self._crashed:
            raise RuntimeError(self._crashed)

    def __exit__(self, *exc):
        self._watchdog_stop.set()
        self._guard_stop.set()
        relayed = False
        try:
            for client in self.worker_clients():
                try:
                    client.shutdown(shutdown_servers=True)
                    relayed = True
                except Exception:
                    pass
        except Exception:
            pass
        # the workers relay the shutdown to the servers: with no worker nobody
        # was asked to stop, and waiting for them to do so is time lost
        self._teardown(grace_s=5.0 if relayed else 0.0)
        return False

    def _teardown(self, grace_s: float):
        deadline = time.time() + grace_s
        for p in self.procs:
            try:
                p.wait(timeout=max(0.1, deadline - time.time()))
            except subprocess.TimeoutExpired:
                p.terminate()
        for p in self.procs:
            if p.poll() is None:
                p.kill()
        for p in self.procs:
            try:
                p.wait(timeout=5)
            except subprocess.TimeoutExpired:
                pass
        if self.coordinator:
            self.coordinator.stop()

    # -------------------------------------------------------------- clients

    def worker_addrs(self) -> List[str]:
        return self.coord_client.list("embedding_worker")

    def ps_addrs(self) -> List[str]:
        return self.coord_client.list("parameter_server")

    def worker_clients(self) -> List[WorkerClient]:
        return [WorkerClient(a) for a in self.worker_addrs()]

    def ps_clients(self) -> List[StoreClient]:
        return [StoreClient(a) for a in self.ps_addrs()]
