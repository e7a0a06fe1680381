"""Deterministic chaos / fault injection for the training-side service plane.

Nothing in a resilience stack is real until a fault can be injected on
demand and the recovery asserted. This module provides the two fault
surfaces the training plane has:

- **transport faults** via :class:`ChaosProxy` — a frame-aware TCP proxy
  slotted between an RPC client and a real server. Per forwarded frame it
  can, driven by a SEEDED RNG (same seed → same fault sequence per
  connection): refuse new connections, cut the stream mid-frame
  (``reset``), delay delivery (``slow``), flip a payload byte
  (``corrupt`` — detected end-to-end when the RPC layer's negotiated
  crc32 trailer is on, ``PERSIA_RPC_CRC=1``), or truncate a frame and
  close. A ``blackhole`` switch emulates a network partition (every new
  and existing connection dies) independent of process liveness.

- **process faults** via :class:`ChaosPlane` — wraps a
  :class:`~persia_tpu.helper.ServiceCtx` local topology: every PS replica
  gets a proxy, and a scripted :class:`ChaosSchedule` (fired from the
  training loop through ``on_step``/``wrap_batches``) can SIGKILL a PS
  shard, restart it (optionally replaying the last snapshot through
  ``dump_shard``/``load_shard_bytes``), or open/heal a partition at a
  chosen step.

Everything is usable from tests (tests/test_chaos.py) and from a
training loop.
"""

from __future__ import annotations

import os
import random
import socket
import struct
import threading
import time
from dataclasses import asdict, dataclass, field
from typing import Dict, List, Optional, Sequence

import numpy as np

from persia_tpu.logger import get_default_logger
from persia_tpu.metrics import get_metrics
from persia_tpu.tracing import record_event

logger = get_default_logger("persia_tpu.chaos")


@dataclass
class ChaosConfig:
    """Per-frame fault probabilities (all default 0 = transparent proxy).

    ``seed`` drives every decision: per accepted connection the proxy
    derives ``Random((seed, conn_id))`` and draws once per forwarded
    frame, so a schedule replays identically run to run (connection
    ARRIVAL order is the only nondeterminism left, and each connection's
    own fault stream is fixed)."""

    seed: int = 0
    refuse_prob: float = 0.0    # close a brand-new connection at accept
    reset_prob: float = 0.0     # cut the stream mid-frame
    slow_prob: float = 0.0      # delay a frame by slow_ms
    slow_ms: float = 50.0
    corrupt_prob: float = 0.0   # flip one byte inside the frame body
    truncate_prob: float = 0.0  # ship a partial frame, then close

    def to_dict(self) -> Dict:
        return asdict(self)


def parse_chaos_spec(spec: str) -> ChaosConfig:
    """Parse a chaos spec string like
    ``"seed=7,reset=0.02,slow=0.01,slow_ms=40,corrupt=0.005"``.
    Keys: seed, refuse, reset, slow, slow_ms, corrupt, truncate."""
    cfg = ChaosConfig()
    if not spec:
        return cfg
    alias = {
        "refuse": "refuse_prob", "reset": "reset_prob", "slow": "slow_prob",
        "corrupt": "corrupt_prob", "truncate": "truncate_prob",
        "seed": "seed", "slow_ms": "slow_ms",
    }
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        key, _, val = part.partition("=")
        attr = alias.get(key.strip())
        if attr is None:
            raise ValueError(f"unknown chaos knob {key!r} in {spec!r}")
        setattr(cfg, attr, int(val) if attr == "seed" else float(val))
    return cfg


def _recv_exact(sock: socket.socket, n: int) -> Optional[bytes]:
    chunks = []
    while n:
        try:
            chunk = sock.recv(min(n, 1 << 20))
        except OSError:
            return None
        if not chunk:
            return None
        chunks.append(chunk)
        n -= len(chunk)
    return b"".join(chunks)


class ChaosProxy:
    """Frame-aware TCP proxy injecting transport faults.

    Understands the RPC framing (``u32 length | body`` in BOTH
    directions), so faults land on frame boundaries the way real network
    damage presents to the framing layer: a ``reset`` delivers a partial
    frame then EOF, a ``corrupt`` flips a byte inside the body (never the
    length prefix — the point is payload damage the framing alone cannot
    see), a ``truncate`` ships a prefix and closes.
    """

    def __init__(self, backend_addr: str, cfg: Optional[ChaosConfig] = None,
                 name: str = ""):
        host, port = backend_addr.rsplit(":", 1)
        self.backend = (host, int(port))
        self.cfg = cfg or ChaosConfig()
        self.name = name or backend_addr
        self.blackhole = threading.Event()
        # forced per-frame latency floor (gray-failure injection): unlike
        # the probabilistic ``slow_prob`` this delays EVERY frame, turning
        # the backend into a replica that still answers — just at p99 far
        # above its peers. Float so tests can set sub-ms floors.
        self._forced_latency_s = 0.0
        self._listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._listener.bind(("127.0.0.1", 0))
        self._listener.listen(64)
        self.port = self._listener.getsockname()[1]
        self.addr = f"127.0.0.1:{self.port}"
        self._stop = threading.Event()
        self._conn_id = 0
        self._live_socks: List[socket.socket] = []
        self._lock = threading.Lock()
        # injected-fault accounting (tests assert the schedule actually
        # fired; bench records it in the artifact)
        self.counts: Dict[str, int] = {
            "frames": 0, "refused": 0, "reset": 0, "slow": 0,
            "corrupt": 0, "truncated": 0, "grayed": 0,
        }
        m = get_metrics()
        self._m_injected = m.counter(
            "persia_tpu_chaos_faults_injected", "faults injected by ChaosProxy"
        )
        self._accept_t = threading.Thread(
            target=self._accept_loop, daemon=True,
            name=f"chaos-accept-{self.name}",
        )
        self._accept_t.start()

    def _note_fault(self, kind: str) -> None:
        """ONE ledger per injected fault: the counts dict (tests), the
        metric (scrapes), and the flight recorder (post-mortem
        correlation against the breaker/quarantine events it caused)."""
        self.counts[kind] += 1
        self._m_injected.inc(kind=kind)
        record_event(f"chaos.{kind}", proxy=self.name)

    # ----------------------------------------------------------- lifecycle

    def stop(self) -> None:
        self._stop.set()
        try:
            self._listener.close()
        except OSError:
            pass
        self._kill_live()

    def _kill_live(self) -> None:
        # shutdown (not close) wakes pump threads blocked in recv without
        # freeing the fd under them (close here would race a concurrent
        # recv with fd reuse — observed as 5 s client hangs); each pump
        # closes its own read-side socket on exit
        with self._lock:
            socks, self._live_socks = self._live_socks, []
        for s in socks:
            try:
                s.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass

    def set_blackhole(self, on: bool) -> None:
        """Partition emulation: while on, new connections are refused and
        every existing one is cut."""
        if on:
            self.blackhole.set()
            self._kill_live()
        else:
            self.blackhole.clear()

    def set_latency(self, ms: float) -> None:
        """Gray-failure injection: force a latency floor of ``ms`` onto
        EVERY forwarded frame (0 restores transparency). The backend keeps
        answering correctly — it just becomes a sustained latency outlier
        against its peers, which is exactly the failure class a liveness
        probe alone cannot see."""
        self._forced_latency_s = max(float(ms), 0.0) / 1e3
        record_event("chaos.set_latency", proxy=self.name, ms=float(ms))

    # ------------------------------------------------------------- pumping

    def _accept_loop(self) -> None:
        while not self._stop.is_set():
            try:
                client, _ = self._listener.accept()
            except OSError:
                return
            with self._lock:
                self._conn_id += 1
                cid = self._conn_id
            # int-mixed seeds (tuple seeding is deprecated): one stream
            # per connection per direction, stable across runs
            rng = random.Random(self.cfg.seed * 1_000_003 + cid * 2)
            if self.blackhole.is_set() or (
                self.cfg.refuse_prob and rng.random() < self.cfg.refuse_prob
            ):
                self._note_fault("refused")
                try:
                    client.close()
                except OSError:
                    pass
                continue
            try:
                upstream = socket.create_connection(self.backend, timeout=10)
            except OSError:
                try:
                    client.close()
                except OSError:
                    pass
                continue
            for s in (client, upstream):
                s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            with self._lock:
                self._live_socks += [client, upstream]
            # each direction gets its own deterministic fault stream
            threading.Thread(
                target=self._pump, args=(client, upstream, rng),
                daemon=True, name=f"chaos-c2s-{self.name}-{cid}",
            ).start()
            threading.Thread(
                target=self._pump,
                args=(upstream, client,
                      random.Random(self.cfg.seed * 1_000_003 + cid * 2 + 1)),
                daemon=True, name=f"chaos-s2c-{self.name}-{cid}",
            ).start()

    def _close_pair(self, a: socket.socket, b: socket.socket) -> None:
        """Terminate a proxied connection: SHUTDOWN both sockets — this
        sends FIN to both peers immediately AND wakes the sibling pump
        thread blocked in recv — but do NOT close fds here: the sibling
        may still be inside recv() on one of them, and closing would free
        the fd under it (fd-reuse hands it someone else's bytes). Each
        pump closes its own read-side socket when it exits."""
        for s in (a, b):
            try:
                s.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass

    def _pump(self, src: socket.socket, dst: socket.socket,
              rng: random.Random) -> None:
        try:
            self._pump_loop(src, dst, rng)
        finally:
            # this thread is the only reader of ``src`` — safe to close it
            # now that our recv loop has exited
            try:
                src.close()
            except OSError:
                pass

    def _pump_loop(self, src: socket.socket, dst: socket.socket,
                   rng: random.Random) -> None:
        cfg = self.cfg
        while not self._stop.is_set():
            header = _recv_exact(src, 4)
            if header is None:
                self._close_pair(src, dst)
                return
            (total,) = struct.unpack("<I", header)
            frame = _recv_exact(src, total) if total else b""
            if frame is None:
                self._close_pair(src, dst)
                return
            self.counts["frames"] += 1
            if self.blackhole.is_set():
                self._close_pair(src, dst)
                return
            try:
                forced = self._forced_latency_s
                if forced > 0.0:
                    self._note_fault("grayed")
                    time.sleep(forced)
                r = rng.random()
                if cfg.reset_prob and r < cfg.reset_prob:
                    # mid-frame cut: the peer sees a partial frame + EOF
                    self._note_fault("reset")
                    dst.sendall(header + frame[: len(frame) // 2])
                    self._close_pair(src, dst)
                    return
                if cfg.truncate_prob and r < cfg.reset_prob + cfg.truncate_prob:
                    self._note_fault("truncated")
                    dst.sendall(header + frame[: max(len(frame) - 3, 0)])
                    self._close_pair(src, dst)
                    return
                if cfg.slow_prob and rng.random() < cfg.slow_prob:
                    self._note_fault("slow")
                    time.sleep(cfg.slow_ms / 1e3)
                if (
                    cfg.corrupt_prob and len(frame) > 1
                    and rng.random() < cfg.corrupt_prob
                ):
                    # flip one byte INSIDE the body (never byte 0: damaging
                    # the flags/status byte changes protocol dispatch rather
                    # than payload content, which is a different fault class)
                    self._note_fault("corrupt")
                    pos = 1 + rng.randrange(len(frame) - 1)
                    frame = bytearray(frame)
                    frame[pos] ^= 0xFF
                    frame = bytes(frame)
                dst.sendall(header + frame)
            except OSError:
                self._close_pair(src, dst)
                return


# ------------------------------------------------------- delta-channel chaos


class DeltaChannelChaos:
    """Fault-injecting relay for the incremental delta channel.

    The train-to-serve delta stream (persia_tpu/incremental.py) is a
    storage directory, not a TCP stream, so :class:`ChaosProxy` cannot
    damage it. This relay gives each serving replica its OWN delivery
    directory and copies packets + done-markers from the trainer's source
    dir into it — with per-delivery faults decided by a SEEDED hash of
    ``(seed, replica, name)``, so a schedule replays identically:

    - ``corrupt_prob`` — flip one byte inside the packet body (caught by
      the v2 crc32 frame);
    - ``truncate_prob`` — deliver a torn prefix (caught by the crc/framing
      check);
    - ``drop_prob`` — never deliver the packet (a seq gap at the consumer);
    - ``set_blackhole(i)`` — stop delivering ANYTHING to replica ``i``
      (partition: its freshness head freezes and its lag grows until the
      gateway quarantines it);
    - :meth:`redeliver` — recopy every retained source file fresh (the
      consumer's resync path re-fetches from durable storage).

    Damaged deliveries stay damaged until redelivered — exactly how object
    storage presents a torn upload.
    """

    def __init__(self, src_dir, base_dir, n_replicas: int,
                 cfg: Optional[ChaosConfig] = None, seed: int = 0):
        from persia_tpu.storage import storage_path

        self.src = storage_path(str(src_dir))
        self.cfg = cfg or ChaosConfig()
        self.seed = seed if not (cfg and cfg.seed) else cfg.seed
        self.replica_dirs = [
            storage_path(str(base_dir)).join(f"replica_{i}")
            for i in range(n_replicas)
        ]
        for d in self.replica_dirs:
            d.makedirs()
        self._delivered: List[set] = [set() for _ in range(n_replicas)]
        self._blackholed: List[bool] = [False] * n_replicas
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.counts: Dict[str, int] = {
            "delivered": 0, "corrupt": 0, "truncated": 0, "dropped": 0,
            "blackholed": 0, "redelivered": 0,
        }

    def inc_dir(self, i: int) -> str:
        """The delivery directory replica ``i``'s IncrementalLoader scans."""
        return str(self.replica_dirs[i])

    def set_blackhole(self, i: int, on: bool) -> None:
        with self._lock:
            self._blackholed[i] = on
        record_event("chaos.blackhole" if on else "chaos.heal", replica=i)

    def _fault_for(self, replica: int, name: str) -> str:
        """Deterministic per-(replica, delivery) fault draw."""
        rng = random.Random(f"{self.seed}:{replica}:{name}")
        r = rng.random()
        cfg = self.cfg
        if cfg.corrupt_prob and r < cfg.corrupt_prob:
            return "corrupt"
        r -= cfg.corrupt_prob
        if cfg.truncate_prob and r < cfg.truncate_prob:
            return "truncated"
        r -= cfg.truncate_prob
        # reuse refuse_prob as the drop knob (a refused delivery = a gap)
        if cfg.refuse_prob and r < cfg.refuse_prob:
            return "dropped"
        return "ok"

    def _damage(self, blob: bytes, fault: str, replica: int, name: str) -> bytes:
        rng = random.Random(f"{self.seed}:damage:{replica}:{name}")
        if fault == "corrupt" and len(blob) > 40:
            # flip a byte INSIDE the body (past the 36-byte v2 header): the
            # point is payload damage only the crc frame can see
            pos = 40 + rng.randrange(len(blob) - 40)
            out = bytearray(blob)
            out[pos] ^= 0xFF
            return bytes(out)
        if fault == "truncated":
            return blob[: max(len(blob) - max(4, len(blob) // 3), 1)]
        return blob

    def _src_names(self) -> List[str]:
        """Published packet + done-marker names only — never a publisher's
        in-flight ``.tmp_*`` file (temp + atomic-rename means those vanish
        under a concurrent read)."""
        from persia_tpu.incremental import _MARKER_RE, _PACKET_RE
        from persia_tpu.storage import StorageError

        try:
            names = sorted(self.src.list()) if self.src.exists() else []
        except StorageError:
            return []
        return [n for n in names if _PACKET_RE.match(n) or _MARKER_RE.match(n)]

    def pump_once(self) -> int:
        """Relay every undelivered source file to every non-blackholed
        replica. Returns deliveries made."""
        names = self._src_names()
        made = 0
        for i, dst in enumerate(self.replica_dirs):
            with self._lock:
                if self._blackholed[i]:
                    self.counts["blackholed"] += 1  # pumps withheld
                    continue
                todo = [n for n in names if n not in self._delivered[i]]
            for name in todo:
                made += self._deliver(i, dst, name)
        return made

    def _deliver(self, i: int, dst, name: str, force_clean: bool = False) -> int:
        from persia_tpu.storage import StorageError

        try:
            blob = self.src.join(name).read_bytes()
        except StorageError:
            return 0  # pruned mid-pump; next scan settles
        fault = "ok" if force_clean else self._fault_for(i, name)
        with self._lock:
            self._delivered[i].add(name)
            if fault == "dropped":
                self.counts["dropped"] += 1
                record_event("chaos.dropped", replica=i, packet=name)
                return 0
            if fault != "ok":
                self.counts[fault] += 1
                record_event(f"chaos.{fault}", replica=i, packet=name)
            self.counts["delivered"] += 1
        try:
            dst.join(name).write_bytes(self._damage(blob, fault, i, name))
        except StorageError:
            with self._lock:
                self._delivered[i].discard(name)  # retry next pump
            return 0
        return 1

    def redeliver(self, i: int) -> int:
        """Resync support: recopy every retained source file to replica
        ``i`` fresh (clean — the durable source is intact; the damage
        happened in delivery). Clears the delivery memory first so future
        pumps stay consistent."""
        names = self._src_names()
        with self._lock:
            self._delivered[i].clear()
        n = 0
        for name in names:
            n += self._deliver(i, self.replica_dirs[i], name, force_clean=True)
        with self._lock:
            self.counts["redelivered"] += n
        return n

    def start(self, interval_s: float = 0.2) -> "DeltaChannelChaos":
        if self._thread is None:
            def loop():
                while not self._stop.wait(interval_s):
                    try:
                        self.pump_once()
                    except Exception:  # noqa: BLE001 — relay must survive
                        logger.exception("delta-channel pump failed")

            self._thread = threading.Thread(
                target=loop, daemon=True, name="chaos-delta-relay"
            )
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None


@dataclass
class DataPlaneChaosConfig:
    """Per-batch data-corruption probabilities for :class:`DataPlaneChaos`
    (all default 0 = transparent). One fault class fires per batch at
    most — draws share a single uniform sample so probabilities compose
    the same way as :class:`ChaosConfig`."""

    seed: int = 0
    nan_prob: float = 0.0          # NaN/Inf written into a dense feature
    label_flip_prob: float = 0.0   # binary labels inverted
    sign_corrupt_prob: float = 0.0 # high bits set on id-feature signs
    spike_prob: float = 0.0        # dense features scaled by spike_scale
    spike_scale: float = 1e6       # finite, but large enough to spike grads

    def to_dict(self) -> Dict:
        return asdict(self)


def parse_data_chaos_spec(spec: str) -> DataPlaneChaosConfig:
    """Parse a data-plane chaos spec string like
    ``"seed=7,nan=0.01,label_flip=0.02,sign=0.01,spike=0.01"``.
    Keys: seed, nan, label_flip, sign, spike, spike_scale."""
    cfg = DataPlaneChaosConfig()
    if not spec:
        return cfg
    alias = {
        "nan": "nan_prob", "label_flip": "label_flip_prob",
        "sign": "sign_corrupt_prob", "spike": "spike_prob",
        "spike_scale": "spike_scale", "seed": "seed",
    }
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        key, _, val = part.partition("=")
        attr = alias.get(key.strip())
        if attr is None:
            raise ValueError(f"unknown data-chaos knob {key!r} in {spec!r}")
        setattr(cfg, attr, int(val) if attr == "seed" else float(val))
    return cfg


class DataPlaneChaos:
    """Seeded batch-level fault injector for the training data plane.

    The transport fault classes above damage bytes in flight; this one
    damages batch CONTENT — the poisons the health layer
    (persia_tpu/health) exists to catch: non-finite dense features and
    labels (validator reject), out-of-domain signs (validator reject),
    flipped labels and finite gradient spikes (on-device sentinel /
    host z-score). The fault draw hashes ``(seed, batch_index)`` so a
    schedule replays identically — the same property that makes the
    bit-parity rollback test deterministic.

    Mutated arrays are COPIES: the source batch stays clean, so a
    clean-vs-poisoned parity run can share one dataset object.
    """

    def __init__(self, cfg: Optional[DataPlaneChaosConfig] = None):
        self.cfg = cfg or DataPlaneChaosConfig()
        self.counts: Dict[str, int] = {
            "batches": 0, "nan": 0, "label_flip": 0, "sign_corrupt": 0,
            "spike": 0,
        }

    def _fault_for(self, index: int) -> str:
        rng = random.Random(f"{self.cfg.seed}:batch:{index}")
        r = rng.random()
        cfg = self.cfg
        for name, prob in (("nan", cfg.nan_prob),
                           ("label_flip", cfg.label_flip_prob),
                           ("sign_corrupt", cfg.sign_corrupt_prob),
                           ("spike", cfg.spike_prob)):
            if prob and r < prob:
                return name
            r -= prob
        return "ok"

    def _poison(self, batch, fault: str, index: int):
        from persia_tpu.data import (IDTypeFeature, Label, NonIDTypeFeature,
                                     PersiaBatch)

        rng = random.Random(f"{self.cfg.seed}:poison:{index}")
        id_feats = batch.id_type_features
        dense = list(batch.non_id_type_features)
        labels = list(batch.labels)
        if fault == "nan" and dense:
            fi = rng.randrange(len(dense))
            arr = dense[fi].data.astype(np.float32, copy=True)
            flat = arr.reshape(-1)
            flat[rng.randrange(flat.size)] = (
                np.nan if rng.random() < 0.5 else np.inf
            )
            dense[fi] = NonIDTypeFeature(arr, name=dense[fi].name)
        elif fault == "label_flip" and labels:
            li = rng.randrange(len(labels))
            arr = labels[li].data.astype(np.float32, copy=True)
            labels[li] = Label(1.0 - arr, name=labels[li].name)
        elif fault == "sign_corrupt" and id_feats:
            fi = rng.randrange(len(id_feats))
            feat = id_feats[fi]
            flat, cnts = feat.flat_counts()
            if flat.size:
                flat = flat.copy()
                flat[rng.randrange(flat.size)] |= np.uint64(1) << np.uint64(63)
                id_feats = list(id_feats)
                id_feats[fi] = IDTypeFeature.from_flat(feat.name, flat, cnts)
        elif fault == "spike" and dense:
            fi = rng.randrange(len(dense))
            arr = dense[fi].data.astype(np.float32, copy=True)
            dense[fi] = NonIDTypeFeature(
                arr * np.float32(self.cfg.spike_scale), name=dense[fi].name
            )
        return PersiaBatch(
            id_type_features=id_feats,
            non_id_type_features=dense,
            labels=labels,
            requires_grad=batch.requires_grad,
            batch_id=batch.batch_id,
            meta=batch.meta,
        )

    def wrap(self, batches):
        """Yield each batch, poisoned per the seeded schedule."""
        for index, batch in enumerate(batches):
            self.counts["batches"] += 1
            fault = self._fault_for(index)
            if fault != "ok":
                self.counts[fault] += 1
                record_event("chaos.data_fault", fault=fault, batch=index)
                batch = self._poison(batch, fault, index)
            yield batch


# ----------------------------------------------------------- trainer kills


def write_progress(path: str, step: int) -> None:
    """Trainer-side step beacon for an external killer/watchdog: atomic
    replace so a reader never sees a torn value. Called once per step by a
    trainer under chaos test (tests/jobstate_trainer_main.py)."""
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        f.write(str(int(step)))
    os.replace(tmp, path)


def read_progress(path: str) -> int:
    """-1 until the trainer has published its first step."""
    try:
        with open(path) as f:
            return int(f.read().strip() or -1)
    except (OSError, ValueError):
        return -1


class TrainerKiller:
    """SIGKILL a trainer subprocess when its progress beacon reaches a
    target step — the process-fault half of the trainer-crash story (the
    PS-side kills live in :class:`ChaosPlane`). The kill is a real
    ``SIGKILL`` mid-step: no atexit, no flush, exactly the failure a TPU
    preemption or OOM-kill presents."""

    def __init__(self, proc, progress_path: str, kill_at_step: int,
                 poll_s: float = 0.02):
        self.proc = proc
        self.progress_path = progress_path
        self.kill_at_step = int(kill_at_step)
        self.poll_s = poll_s
        self.killed_at: Optional[int] = None
        self._done = threading.Event()
        self._thread = threading.Thread(
            target=self._watch, daemon=True, name="chaos-trainer-killer"
        )

    def start(self) -> "TrainerKiller":
        self._thread.start()
        return self

    def _watch(self) -> None:
        try:
            while self.proc.poll() is None:
                step = read_progress(self.progress_path)
                if step >= self.kill_at_step:
                    self.proc.kill()
                    self.proc.wait(timeout=30)
                    self.killed_at = step
                    logger.info(
                        "chaos: SIGKILLed trainer pid %d at step %d",
                        self.proc.pid, step,
                    )
                    return
                time.sleep(self.poll_s)
        finally:
            self._done.set()

    def wait(self, timeout_s: float = 120.0) -> bool:
        """True once the watcher finished (kill fired, or the trainer
        exited on its own first — ``killed_at`` distinguishes)."""
        return self._done.wait(timeout_s)


# ---------------------------------------------------------- load schedules


@dataclass
class LoadShapeConfig:
    """Seeded TRAFFIC-shape schedule — the workload half of the chaos
    plane. The fault machinery above perturbs the transport; this perturbs
    the LOAD so a closed-loop controller (persia_tpu/autopilot) has
    something real to react to. Three composable shapes, all driven by the
    step ordinal and ``seed`` alone (bit-reproducible run to run):

    - **zipf exponent ramp**: the sign distribution's zipf exponent
      interpolates ``zipf_a0 → zipf_a1`` over steps
      ``[ramp_start, ramp_end]`` — skew concentrates (or relaxes) under
      the fleet, moving the per-shard load balance the sketch measures;
    - **step traffic spike**: modeled request rate multiplies by
      ``spike_x`` inside ``[spike_start, spike_end)`` — the serving-plane
      scale-up/scale-down trigger;
    - **hot-set rotation**: every ``rotate_every`` steps the IDENTITY of
      the hot head shifts by ``rotate_stride`` sign positions (the
      distribution's shape is unchanged, its support moves) — yesterday's
      heavy hitters go cold, invalidating any placement pinned to them.

    A spec string builds one through :func:`parse_load_spec`."""

    seed: int = 7
    vocab: int = 1 << 17
    zipf_a0: float = 1.2
    zipf_a1: float = 1.2
    ramp_start: int = 0
    ramp_end: int = 0
    base_qps: float = 100.0
    spike_x: float = 1.0
    spike_start: int = 0
    spike_end: int = 0
    rotate_every: int = 0  # 0 = no rotation
    rotate_stride: int = 7919  # prime stride keeps rotations disjoint

    def to_dict(self) -> Dict:
        return asdict(self)


def parse_load_spec(spec: str) -> LoadShapeConfig:
    """Parse a load-shape spec string like
    ``"a0=1.1,a1=1.7,ramp=10:50,spike=4x20:30,rotate=16,seed=7"``.
    Keys: seed, vocab, a0, a1, ramp=START:END, qps, spike=Xx|spike=XxS:E,
    rotate (= rotate_every), stride."""
    cfg = LoadShapeConfig()
    if not spec:
        return cfg
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        key, _, val = part.partition("=")
        key = key.strip()
        if key == "seed":
            cfg.seed = int(val)
        elif key == "vocab":
            cfg.vocab = int(val)
        elif key == "a0":
            cfg.zipf_a0 = float(val)
        elif key == "a1":
            cfg.zipf_a1 = float(val)
        elif key == "ramp":
            s, _, e = val.partition(":")
            cfg.ramp_start, cfg.ramp_end = int(s), int(e)
        elif key == "qps":
            cfg.base_qps = float(val)
        elif key == "spike":
            mult, _, window = val.partition("x")
            cfg.spike_x = float(mult)
            if window:
                s, _, e = window.partition(":")
                cfg.spike_start, cfg.spike_end = int(s), int(e)
        elif key == "rotate":
            cfg.rotate_every = int(val)
        elif key == "stride":
            cfg.rotate_stride = int(val)
        else:
            raise ValueError(f"unknown load knob {key!r} in {spec!r}")
    return cfg


class LoadSchedule:
    """Materializes a :class:`LoadShapeConfig`: per-step zipf exponent,
    modeled request rate, and seeded sign batches. Every draw derives its
    generator from ``(seed, step, slot)`` so any step is reproducible in
    isolation — a resumed soak replays the exact traffic of the run it
    resumes (the same discipline the fault proxies keep per connection)."""

    def __init__(self, cfg: Optional[LoadShapeConfig] = None):
        self.cfg = cfg or LoadShapeConfig()

    def zipf_a(self, step: int) -> float:
        c = self.cfg
        if c.ramp_end <= c.ramp_start:
            return c.zipf_a0
        t = min(max((step - c.ramp_start) / (c.ramp_end - c.ramp_start), 0.0),
                1.0)
        return c.zipf_a0 + t * (c.zipf_a1 - c.zipf_a0)

    def qps(self, step: int) -> float:
        c = self.cfg
        if c.spike_end > c.spike_start and c.spike_start <= step < c.spike_end:
            return c.base_qps * c.spike_x
        return c.base_qps

    def rotation(self, step: int) -> int:
        c = self.cfg
        return 0 if c.rotate_every <= 0 else step // c.rotate_every

    def signs(self, step: int, n: int, slot: int = 0) -> np.ndarray:
        """One seeded sign batch: zipf(``zipf_a(step)``) ranks, rotated by
        the step's hot-set rotation, offset into the slot's sign space
        (u64, never 0 — sign 0 is the stores' reserved empty key)."""
        c = self.cfg
        rng = np.random.default_rng(
            (c.seed * 1_000_003 + step) * 1_000_033 + slot
        )
        ranks = rng.zipf(max(self.zipf_a(step), 1.001), n).astype(np.uint64)
        rot = np.uint64((self.rotation(step) * c.rotate_stride) % c.vocab)
        ids = (ranks + rot) % np.uint64(c.vocab)
        return ids + np.uint64(slot * c.vocab + 1)


# --------------------------------------------------------------- schedules


@dataclass
class ChaosAction:
    """One scripted process/topology fault, fired when the driving loop
    reaches ``step``. ``op``: ``kill_ps`` | ``restart_ps`` |
    ``kill_restart_ps`` (kill + immediate same-port restart) |
    ``kill_ps_autoheal`` (snapshot then SIGKILL, and deliberately NO
    restart — the self-healing autopilot is expected to detect the death
    and promote a standby on its own; the schedule just makes the hole) |
    ``blackhole`` / ``heal`` (partition one shard's proxy) |
    ``gray_ps`` / ``ungray_ps`` (force/clear a per-frame latency floor of
    ``latency_ms`` on one shard's proxy — the replica still answers, at
    p99 far above its peers: the gray-failure injector) |
    ``heartbeat_ghost`` (SIGKILL the shard but keep publishing its
    heartbeat lease from this process — heartbeat-only death: the lease
    plane says alive while the data plane is gone) |
    ``snapshot`` (record the shard's state for a later replaying
    restart).

    ``after_s > 0`` executes the op in a BACKGROUND thread after the
    delay — the idiom for a real outage window: fire ``kill_ps`` inline
    at step N and a delayed ``restart_ps`` in the same step, so the
    training loop keeps issuing (and failing, and breaker-tripping)
    lookups while the shard is genuinely gone."""

    step: int
    op: str
    idx: int = 0
    restore: bool = False  # restart replays the last snapshot
    after_s: float = 0.0   # 0 = synchronous at fire time
    fired: bool = False
    # ``op="kill_trainer"`` SIGKILLs the subprocess registered via
    # ChaosPlane.attach_trainer — only meaningful when the schedule is
    # driven from OUTSIDE the trainer process (a parent harness walking
    # the trainer's progress beacon), since a trainer cannot outlive
    # firing its own SIGKILL.
    #
    # ``op="kill_during_reshard"`` does not kill at fire time: it ARMS the
    # plane so the NEXT reshard driven with ``reshard_fault_hook()``
    # SIGKILLs PS ``idx`` when the handoff reaches (handoff_op, op_index)
    # — the reshard engine's own injection point, so the kill lands
    # between two journaled ops rather than between two steps.
    # ``op_index < 0`` draws the target op ordinal from the plane's seed
    # instead (same seed → same kill point, run to run).
    handoff_op: str = "import"  # "import" | "delete"
    op_index: int = 0
    latency_ms: float = 250.0  # gray_ps forced per-frame latency floor


class ChaosPlane:
    """Chaos harness over a :class:`~persia_tpu.helper.ServiceCtx`.

    Every PS replica is fronted by a :class:`ChaosProxy`; ``ps_clients``
    hands back StoreClients wired through the proxies, so transport
    faults hit the same code paths production traffic uses. Process
    faults run from a scripted schedule driven by the training loop
    (``on_step`` / ``wrap_batches``) — deterministic by construction.
    """

    def __init__(
        self,
        svc,
        cfg: Optional[ChaosConfig] = None,
        schedule: Optional[Sequence[ChaosAction]] = None,
    ):
        self.svc = svc
        self.cfg = cfg or ChaosConfig()
        self.schedule: List[ChaosAction] = sorted(
            (schedule or []), key=lambda a: a.step
        )
        self.proxies: List[ChaosProxy] = [
            ChaosProxy(addr, self.cfg, name=f"ps{i}")
            for i, addr in enumerate(svc.ps_addrs())
        ]
        self._step = -1
        self._trainer_proc = None
        # kill_during_reshard arms land here; reshard_fault_hook consumes
        self._reshard_arms: List[ChaosAction] = []
        self._reshard_counts: Dict[str, int] = {"reshard_kills": 0}
        # heartbeat_ghost publishers keep a dead shard's lease fresh until
        # stop() exorcises them
        self._ghosts: List["HeartbeatGhost"] = []

    def attach_trainer(self, proc) -> None:
        """Register the trainer subprocess the ``kill_trainer`` op targets
        (the watchdogging parent harness owns the Popen)."""
        self._trainer_proc = proc

    def ps_addrs(self) -> List[str]:
        return [p.addr for p in self.proxies]

    def ps_clients(self, **kwargs) -> List:
        from persia_tpu.service.clients import StoreClient

        return [StoreClient(p.addr, **kwargs) for p in self.proxies]

    def fault_counts(self) -> Dict[str, int]:
        total: Dict[str, int] = dict(self._reshard_counts)
        for p in self.proxies:
            for k, v in p.counts.items():
                total[k] = total.get(k, 0) + v
        return total

    def reshard_fault_hook(self):
        """The ``fault_hook`` to pass into ``ServiceCtx.reshard_ps`` /
        ``resume_reshard``: fires every armed ``kill_during_reshard``
        action whose (handoff_op, op_index) the engine reaches. A seeded
        arm (``op_index < 0``) resolves its target ordinal from the
        plane's chaos seed, counting hook invocations of its op kind."""
        import random as _random

        for a in self._reshard_arms:
            if a.op_index < 0:
                a.op_index = _random.Random(
                    self.cfg.seed * 1_000_003 + a.idx * 2 + a.step
                ).randrange(0, 4)
        def hook(kind: str, idx: int, mv) -> None:
            for a in list(self._reshard_arms):
                if a.handoff_op == kind and a.op_index == idx:
                    self._reshard_arms.remove(a)
                    self._reshard_counts["reshard_kills"] += 1
                    record_event(
                        "chaos.kill_during_reshard", idx=a.idx,
                        handoff_op=kind, op_index=idx,
                    )
                    logger.info(
                        "chaos: SIGKILL ps%d during reshard at %s[%d]",
                        a.idx, kind, idx,
                    )
                    self.svc.kill_ps(a.idx)

        return hook

    # ------------------------------------------------------------ schedule

    def on_step(self, step: int) -> None:
        """Fire every not-yet-fired action with ``action.step <= step``."""
        self._step = step
        for a in self.schedule:
            if a.fired or a.step > step:
                continue
            a.fired = True
            logger.info(
                "chaos: firing %s(idx=%d) at step %d%s", a.op, a.idx, step,
                f" after {a.after_s}s" if a.after_s else "",
            )
            if a.after_s > 0:
                threading.Thread(
                    target=self._fire_delayed, args=(a,), daemon=True,
                    name=f"chaos-delayed-{a.op}",
                ).start()
            else:
                self._execute(a)

    def _fire_delayed(self, a: ChaosAction) -> None:
        time.sleep(a.after_s)
        try:
            self._execute(a)
        except Exception:  # noqa: BLE001 — must not die silently
            logger.exception("chaos: delayed %s(idx=%d) failed", a.op, a.idx)

    def _execute(self, a: ChaosAction) -> None:
        record_event(f"chaos.{a.op}", idx=a.idx, step=a.step)
        if a.op == "snapshot":
            self.svc.snapshot_ps(a.idx)
        elif a.op == "kill_ps":
            self.svc.kill_ps(a.idx)
        elif a.op == "restart_ps":
            self.svc.restart_ps(a.idx, restore=a.restore)
        elif a.op == "kill_restart_ps":
            if a.restore:
                self.svc.snapshot_ps(a.idx)
            self.svc.kill_ps(a.idx)
            self.svc.restart_ps(a.idx, restore=a.restore)
        elif a.op == "kill_ps_autoheal":
            # snapshot first so the healer's standby promotion has a fresh
            # fence to boot-load from; then make the hole and WALK AWAY —
            # recovery is the autopilot's job, not the schedule's
            self.svc.snapshot_ps(a.idx)
            self.svc.kill_ps(a.idx)
        elif a.op == "blackhole":
            self.proxies[a.idx].set_blackhole(True)
        elif a.op == "heal":
            self.proxies[a.idx].set_blackhole(False)
        elif a.op == "gray_ps":
            self.proxies[a.idx].set_latency(a.latency_ms)
        elif a.op == "ungray_ps":
            self.proxies[a.idx].set_latency(0.0)
        elif a.op == "heartbeat_ghost":
            self._ghosts.append(HeartbeatGhost.haunt(self.svc, a.idx))
            self.svc.kill_ps(a.idx)
        elif a.op == "kill_during_reshard":
            self._reshard_arms.append(a)
        elif a.op == "kill_trainer":
            if self._trainer_proc is None:
                raise RuntimeError(
                    "kill_trainer scheduled but no trainer attached "
                    "(ChaosPlane.attach_trainer)"
                )
            self._trainer_proc.kill()
            self._trainer_proc.wait(timeout=30)
        else:
            raise ValueError(f"unknown chaos op {a.op!r}")

    def wrap_batches(self, batches):
        """Drive the schedule from a batch stream: yields each batch after
        firing the actions scheduled for its ordinal."""
        for i, b in enumerate(batches):
            self.on_step(i)
            yield b

    def stop(self) -> None:
        for g in self._ghosts:
            g.stop()
        self._ghosts = []
        for p in self.proxies:
            p.stop()


# -------------------------------------------------- detector-facing chaos


class HeartbeatGhost:
    """Heartbeat-only death: keeps publishing a DEAD replica's lease.

    Wraps a :class:`~persia_tpu.service.failure_detector.LeasePublisher`
    bound to the victim's (role, index, addr) identity, run from the
    chaos harness's own process. To the lease plane the replica looks
    perfectly alive (seq keeps advancing); to the data plane it is gone.
    A detector that trusts heartbeats over probes never evicts it — the
    exact failure mode the verdict matrix's "fresh lease does not rescue
    failing probes" rule exists for.
    """

    def __init__(self, coord, role: str, index: int, addr: str,
                 interval_s: float = 0.2):
        from persia_tpu.service.failure_detector import LeasePublisher

        self._pub = LeasePublisher(
            coord, role, index, addr, interval_s=interval_s
        )
        self._pub.start()
        record_event("chaos.heartbeat_ghost", role=role, index=index)
        logger.info("chaos: heartbeat ghost haunting %s/%d (%s)",
                    role, index, addr)

    @classmethod
    def haunt(cls, svc, idx: int, interval_s: float = 0.2) -> "HeartbeatGhost":
        """Possess PS ``idx`` of a ServiceCtx: publish its lease identity
        from here. Call BEFORE (or right after) killing the process."""
        return cls(svc.coord_client, "parameter_server", idx,
                   svc.ps_addrs()[idx], interval_s=interval_s)

    def stop(self) -> None:
        self._pub.stop()


def partition_view(probes: Dict[int, "object"], cut: Sequence[int]) -> Dict:
    """Observer-side partial partition: wrap a detector probe dict so the
    probes for replicas in ``cut`` raise (this OBSERVER cannot reach them;
    the replicas themselves are fine and other observers still can). Feed
    the wrapped dict to a FailureDetector to exercise the
    majority-of-peers witness rule: an observer cut off from most of the
    fleet must suspect ITSELF (withhold DEAD) rather than evict everyone
    it cannot see."""
    cut_set = set(int(i) for i in cut)

    def _severed(idx: int, inner):
        def probe() -> None:
            raise OSError(f"chaos: partitioned from replica {idx}")

        probe.addr = getattr(inner, "addr", "")  # type: ignore[attr-defined]
        probe.close = getattr(inner, "close", lambda: None)  # type: ignore[attr-defined]
        return probe

    return {
        idx: (_severed(idx, p) if idx in cut_set else p)
        for idx, p in probes.items()
    }
