"""Durable job-state layer: step-fenced manifests + exactly-once resume.

PR 3's chaos plane proved recovery from *PS-side* faults while the trainer
stays alive; this module closes the other half: the trainer (or its TPU
host) dies with ``kill -9`` and the whole hybrid job must resume mid-epoch
with no re-trained and no double-applied gradients. Three pieces:

- **Epoch manifests** (:class:`JobStateManager` / :class:`EpochWriter`):
  every snapshot fence captures the job's components — PS shards, dense
  params + optimizer state, cache/ring occupancy, loader cursor, RNG
  streams — under one monotonic ``job_epoch`` directory. Every file is
  written temp + fsync + atomic rename; the ``MANIFEST.json`` (which
  records a crc32 per component) is written LAST, so a crash mid-capture
  leaves a manifest-less directory the scanner skips; a ``LAST_GOOD``
  pointer is published after the manifest and older epochs remain as
  fallbacks if the newest turns out torn.

- **Journal ids** (:func:`make_journal_id`): each gradient batch applied
  to a PS shard between fences is tagged ``(job_epoch, step, shard)`` plus
  a crc32 of its payload. The PS keeps a bounded apply-journal (see
  ``native/ps.cpp`` ``ps_journal_*`` and ``EmbeddingStore.journal_*``), so
  a resuming trainer replaying steps past the fence can detect and skip
  updates the crashed run already applied — the double-apply window
  between "gradient sent" and "manifest committed" closes.

- **PS capture/restore** (:func:`capture_ps` / :func:`restore_ps`): dump
  every replica's internal shards into the manifest; restore rewinds the
  PS (clear + replay + journal clear + batch-state re-advance) to the
  fence, which is what makes a resumed run BIT-IDENTICAL to a fault-free
  replay (journal-only resume — ``restore_ps=False`` — keeps the PS's
  post-fence updates and guarantees exactly-once application instead).

Everything here is local-disk-first (temp + fsync + rename needs POSIX
semantics); remote checkpoint directories keep flowing through
:mod:`persia_tpu.checkpoint` / :mod:`persia_tpu.storage`.
"""

from __future__ import annotations

import json
import os
import re
import tempfile
import time
import zlib
from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from persia_tpu.analysis.crashcheck import reach
from persia_tpu.logger import get_default_logger

logger = get_default_logger("persia_tpu.jobstate")

MANIFEST_NAME = "MANIFEST.json"
LAST_GOOD = "LAST_GOOD"
_EPOCH_RE = re.compile(r"^epoch_(\d{8})$")

# sampled once, same rationale as persia_tpu.storage
_UMASK = os.umask(0)
os.umask(_UMASK)


class ManifestError(RuntimeError):
    """Job-state manifest problem (missing, torn, or inconsistent)."""


class CorruptManifestError(ManifestError):
    """A manifest component failed its crc32 check."""


# ------------------------------------------------------------ durable writes


def fsync_write_bytes(path: str, data: bytes) -> None:
    """Crash-durable atomic publish on local disk: temp file in the target
    directory + ``fsync`` + atomic rename + directory ``fsync``. A reader
    can never observe a partial file, and a power cut after return cannot
    lose the rename (the directory entry is durable too)."""
    d = os.path.dirname(path) or "."
    os.makedirs(d, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=d, prefix=".tmp_" + os.path.basename(path))
    try:
        os.fchmod(fd, 0o666 & ~_UMASK)
        with os.fdopen(fd, "wb") as f:
            f.write(data)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        _fsync_dir(d)
    except BaseException:
        if os.path.exists(tmp):
            os.remove(tmp)
        raise


def _fsync_dir(d: str) -> None:
    try:
        dfd = os.open(d, os.O_RDONLY)
    except OSError:
        return  # non-POSIX-dir-fsync filesystem — rename atomicity still holds
    try:
        os.fsync(dfd)
    except OSError:
        pass
    finally:
        os.close(dfd)


# --------------------------------------------------------------- journal ids


def make_journal_id(job_epoch: int, step: int) -> int:
    """u64 apply-journal id for one trainer gradient batch: the epoch of
    the last committed manifest (24 bits), the global step (32 bits), and
    a low byte left for the router to mix the PS replica index in — so a
    resumed replay of step ``s`` under the SAME manifest epoch produces
    the exact ids the crashed run recorded, per shard."""
    return ((job_epoch & 0xFFFFFF) << 40) | ((step & 0xFFFFFFFF) << 8)


def journal_shard_id(base_id: int, replica_index: int) -> int:
    """Mix the PS replica index into a :func:`make_journal_id` base.
    Replica indices must stay below 0x80 — the 0x80 low-byte half belongs
    to the handoff/replication/scrub namespaces (the namespace prover in
    ``analysis/protocol.py`` certifies the split)."""
    if not 0 <= replica_index < 0x80:
        raise ValueError(
            f"replica_index {replica_index} outside the gradient-id namespace "
            "[0, 0x80) — the high low-byte half is reserved for handoff ids"
        )
    return base_id | replica_index


def handoff_journal_id(base_id: int, op_index: int) -> int:
    """Journal id for one reshard-handoff op (range import or delete):
    the 0x80 low-byte namespace — real PS replica indices stay < 0x80
    (:func:`journal_shard_id`), so a handoff at the same fence step can
    never collide with a gradient batch's per-replica id. ``op_index``
    numbers the ops of one reshard plan (< 128)."""
    return base_id | 0x80 | (op_index & 0x7F)


def replication_journal_id(job_epoch: int, step: int, op_index: int) -> int:
    """Journal id for one hot-sign read-replication copy (owner range
    export → replica journaled import; persia_tpu/autopilot/replicate).
    The low byte reuses the handoff's 0x80 namespace, so the STEP field's
    top bit (bit 31 — fence steps never reach 2^31) separates the two: a
    replication refresh and a reshard handoff at the SAME fence step
    dedupe independently on a shared destination replica. ``op_index``
    numbers the hot signs of one refresh round (< 128)."""
    return handoff_journal_id(
        make_journal_id(job_epoch, (step & 0x3FFFFFFF) | 0x80000000), op_index
    )


def abort_journal_id(job_epoch: int, step: int, op_index: int) -> int:
    """Journal id for one reshard-ABORT rollback op (the journaled range
    delete that releases a partially imported arc when a higher-priority
    intent preempts an in-flight reshard; persia_tpu/elastic.py). Step
    bits 30-31 are the namespace subspace tags — handoff ``00``, scrub
    ``01``, replication ``10`` — and the abort family takes the last
    combination, ``11``: a rollback delete at the same fence step dedupes
    independently of the forward import it is undoing, which is what
    makes the abort arm exactly-once under SIGKILL+resume. ``op_index``
    numbers the rollback ops of one abort (< 128)."""
    return handoff_journal_id(
        make_journal_id(job_epoch, (step & 0x3FFFFFFF) | 0xC0000000), op_index
    )


def payload_crc(*arrays) -> int:
    """crc32 of a gradient batch's payload arrays — the ``crc`` member of
    the journal's (step, shard, crc) record. A replay that produces a
    DIFFERENT payload under the same id is a divergence bug, and the
    journal turns it into a loud error instead of silent corruption."""
    c = 0
    for a in arrays:
        c = zlib.crc32(np.ascontiguousarray(a).view(np.uint8).data, c)
    return c & 0xFFFFFFFF


# --------------------------------------------------------------- RNG streams


def capture_rng_streams(
    generators: Optional[Dict[str, np.random.Generator]] = None,
) -> Dict:
    """JSON-able snapshot of the process's RNG streams: the global numpy
    MT19937 state plus any named ``np.random.Generator`` the caller threads
    through (e.g. a dataset's ``.rng``)."""
    kind, keys, pos, has_gauss, cached = np.random.get_state()
    out: Dict = {
        "numpy_global": [kind, np.asarray(keys).tolist(), int(pos),
                         int(has_gauss), float(cached)],
    }
    for name, g in (generators or {}).items():
        out[f"gen:{name}"] = g.bit_generator.state
    return out


def restore_rng_streams(
    state: Dict, generators: Optional[Dict[str, np.random.Generator]] = None,
) -> None:
    g = state.get("numpy_global")
    if g:
        kind, keys, pos, has_gauss, cached = g
        np.random.set_state(
            (kind, np.asarray(keys, dtype=np.uint32), int(pos),
             int(has_gauss), float(cached))
        )
    for name, gen in (generators or {}).items():
        s = state.get(f"gen:{name}")
        if s is not None:
            gen.bit_generator.state = s


# ------------------------------------------------------------------ manifest


class Manifest:
    """Read view of one committed epoch. ``meta`` is the MANIFEST.json
    content; blobs re-verify their recorded crc32 on every read."""

    def __init__(self, epoch_dir: str, meta: Dict):
        self.dir = epoch_dir
        self.meta = meta

    @property
    def job_epoch(self) -> int:
        return int(self.meta["job_epoch"])

    @property
    def step(self) -> int:
        return int(self.meta.get("step", 0))

    @property
    def components(self) -> Dict[str, Dict]:
        return self.meta.get("components", {})

    def has(self, name: str) -> bool:
        return name in self.components

    def read_blob(self, name: str) -> bytes:
        comp = self.components.get(name)
        if comp is None:
            raise ManifestError(f"manifest {self.dir} has no component {name!r}")
        path = os.path.join(self.dir, name)
        with open(path, "rb") as f:
            data = f.read()
        if len(data) != int(comp["bytes"]) or (
            zlib.crc32(data) & 0xFFFFFFFF
        ) != int(comp["crc32"]):
            raise CorruptManifestError(
                f"component {name!r} of {self.dir} is torn or corrupt "
                f"({len(data)} bytes, crc mismatch vs manifest record)"
            )
        return data

    def read_json(self, name: str):
        return json.loads(self.read_blob(name).decode())


class EpochWriter:
    """Accumulates one epoch's components, then atomically commits the
    manifest (written LAST — until it exists, the epoch is invisible)."""

    def __init__(self, root: str, job_epoch: int):
        self.root = root
        self.job_epoch = job_epoch
        self.dir = os.path.join(root, f"epoch_{job_epoch:08d}")
        self._components: Dict[str, Dict] = {}
        self._committed = False
        os.makedirs(self.dir, exist_ok=True)

    def add_blob(self, name: str, data: bytes) -> None:
        if self._committed:
            raise ManifestError("epoch already committed")
        reach("jobstate.commit.component")
        fsync_write_bytes(os.path.join(self.dir, name), data)
        self._components[name] = {
            "bytes": len(data), "crc32": zlib.crc32(data) & 0xFFFFFFFF,
        }

    def add_json(self, name: str, obj) -> None:
        self.add_blob(name, json.dumps(obj).encode())

    def commit(self, meta: Optional[Dict] = None) -> Manifest:
        """Publish: MANIFEST.json (atomic), then the LAST_GOOD pointer.
        A crash before the manifest write leaves an invisible directory; a
        crash between manifest and pointer is covered by the scanner's
        newest-first fallback."""
        manifest = dict(meta or {})
        manifest["job_epoch"] = self.job_epoch
        manifest["components"] = self._components
        manifest.setdefault("datetime", time.strftime("%Y-%m-%dT%H:%M:%S"))
        reach("jobstate.commit.manifest")
        fsync_write_bytes(
            os.path.join(self.dir, MANIFEST_NAME), json.dumps(manifest).encode()
        )
        reach("jobstate.commit.pointer")
        fsync_write_bytes(
            os.path.join(self.root, LAST_GOOD),
            json.dumps(
                {"job_epoch": self.job_epoch, "dir": os.path.basename(self.dir)}
            ).encode(),
        )
        self._committed = True
        return Manifest(self.dir, manifest)


class JobStateManager:
    """Owns a job-state root directory of epoch manifests."""

    def __init__(self, root: str):
        self.root = str(root)
        os.makedirs(self.root, exist_ok=True)

    # ------------------------------------------------------------- epochs

    def _epoch_dirs(self) -> List[Tuple[int, str]]:
        out = []
        try:
            names = os.listdir(self.root)
        except OSError:
            return []
        for n in names:
            m = _EPOCH_RE.match(n)
            if m:
                out.append((int(m.group(1)), os.path.join(self.root, n)))
        return sorted(out)

    def begin_epoch(self) -> EpochWriter:
        dirs = self._epoch_dirs()
        nxt = (dirs[-1][0] + 1) if dirs else 1
        return EpochWriter(self.root, nxt)

    def _load_manifest(self, epoch_dir: str) -> Optional[Manifest]:
        """Load + verify one epoch's manifest: the JSON must parse and every
        declared component file must exist with its recorded size (full crc
        verification happens per blob on read — size check here keeps the
        scan cheap while still rejecting torn captures)."""
        path = os.path.join(epoch_dir, MANIFEST_NAME)
        try:
            with open(path, "rb") as f:
                meta = json.loads(f.read().decode())
        except (OSError, ValueError):
            return None
        if "job_epoch" not in meta or "components" not in meta:
            return None
        for name, comp in meta["components"].items():
            fpath = os.path.join(epoch_dir, name)
            try:
                if os.path.getsize(fpath) != int(comp["bytes"]):
                    return None
            except OSError:
                return None
        return Manifest(epoch_dir, meta)

    def latest(self) -> Optional[Manifest]:
        """The newest loadable manifest: the LAST_GOOD pointer first, then a
        newest-first scan (covers a crash between manifest and pointer, and
        a pointer referencing a since-corrupted epoch)."""
        tried = set()
        ptr = self._read_pointer()
        if ptr is not None:
            d = os.path.join(self.root, ptr)
            tried.add(d)
            m = self._load_manifest(d)
            if m is not None:
                return m
            logger.warning(
                "jobstate: LAST_GOOD points at %s but its manifest does not "
                "verify — falling back to the newest good epoch", ptr,
            )
        for _e, d in reversed(self._epoch_dirs()):
            if d in tried:
                continue
            m = self._load_manifest(d)
            if m is not None:
                return m
        return None

    def _read_pointer(self) -> Optional[str]:
        try:
            with open(os.path.join(self.root, LAST_GOOD), "rb") as f:
                return json.loads(f.read().decode()).get("dir")
        except (OSError, ValueError):
            return None

    def prune(self, keep: int = 2) -> int:
        """Remove all but the newest ``keep`` GOOD epochs (and never the one
        LAST_GOOD points at). Returns directories removed."""
        import shutil

        ptr = self._read_pointer()
        good = [
            (e, d) for e, d in self._epoch_dirs()
            if self._load_manifest(d) is not None
        ]
        removed = 0
        for e, d in good[:-keep] if keep > 0 else good:
            if ptr is not None and os.path.basename(d) == ptr:
                continue
            shutil.rmtree(d, ignore_errors=True)
            removed += 1
        return removed


# --------------------------------------------------------- trainer snapshots


def coerce_manager(job_state: Union[str, "JobStateManager"]) -> "JobStateManager":
    return job_state if isinstance(job_state, JobStateManager) else JobStateManager(job_state)


def snapshot_job(
    mgr: "JobStateManager",
    step: int,
    *,
    state_bytes: Optional[bytes] = None,
    replicas: Optional[Sequence] = None,
    batch_advances: Optional[Dict[int, int]] = None,
    components: Optional[Dict[str, object]] = None,
    meta: Optional[Dict] = None,
    generators: Optional[Dict[str, np.random.Generator]] = None,
    prune_keep: int = 2,
) -> Manifest:
    """One step-fenced snapshot: PS shards + dense/opt state + extra JSON
    components + RNG streams under a fresh epoch, committed atomically.
    The caller guarantees the fence invariant — nothing in flight (stream
    drained / loader flushed) when this runs."""
    writer = mgr.begin_epoch()
    m: Dict = {"step": int(step)}
    if replicas is not None:
        m.update(capture_ps(writer, replicas))
        if batch_advances:
            m["ps_batch_advances"] = {
                str(k): int(v) for k, v in batch_advances.items()
            }
    if state_bytes is not None:
        writer.add_blob("dense.state", state_bytes)
    for name, obj in (components or {}).items():
        writer.add_json(name, obj)
    writer.add_json("rng.json", capture_rng_streams(generators))
    m.update(meta or {})
    manifest = writer.commit(m)
    mgr.prune(prune_keep)
    return manifest


def resume_job(
    mgr: "JobStateManager",
    *,
    replicas: Optional[Sequence] = None,
    rewind_ps: bool = True,
    optimizer=None,
    generators: Optional[Dict[str, np.random.Generator]] = None,
) -> Tuple[Optional[Manifest], Dict]:
    """Load the newest good manifest and rebuild the fence state. Returns
    ``(manifest_or_None, recovery_info)`` — the info dict holds the
    recovery metrics.

    ``rewind_ps=True`` restores the PS shards to the fence (clear + replay
    + journal clear): the replayed window then re-applies its gradients
    and the run is BIT-IDENTICAL to a fault-free replay. ``rewind_ps=False``
    keeps the PS's post-fence state; the replayed window's applies dedupe
    against the apply-journal instead (exactly-once, bounded staleness)."""
    t0 = time.monotonic()
    manifest = mgr.latest()
    if manifest is None:
        return None, {"resumed": False, "step": 0, "job_epoch": 0}
    adv = {
        int(k): int(v)
        for k, v in manifest.meta.get("ps_batch_advances", {}).items()
    }
    restored = 0
    if rewind_ps and replicas is not None and manifest.meta.get("ps_replicas"):
        restored = restore_ps(
            manifest, replicas, optimizer=optimizer, batch_advances=adv
        )
    if manifest.has("rng.json"):
        restore_rng_streams(manifest.read_json("rng.json"), generators)
    info = {
        "resumed": True,
        "step": manifest.step,
        "job_epoch": manifest.job_epoch,
        "ps_rewound": bool(rewind_ps),
        "ps_entries_restored": restored,
        "time_to_resume_s": round(time.monotonic() - t0, 4),
        "batch_advances": adv,
    }
    return manifest, info


# -------------------------------------------------------- PS capture/restore


def _shard_blob_name(replica: int, shard: int) -> str:
    return os.path.join("ps", f"replica_{replica}_shard_{shard}.emb")


def capture_ps(writer: EpochWriter, replicas: Sequence) -> Dict:
    """Dump every PS replica's internal shards into the epoch (the trainer-
    side sibling of ``ServiceCtx.snapshot_ps`` — replicas are anything with
    the store surface: in-process stores or ``StoreClient`` handles).
    Returns the topology meta recorded in the manifest."""
    shards_per = []
    total = 0
    for ri, rep in enumerate(replicas):
        n = int(rep.num_internal_shards)
        shards_per.append(n)
        for si in range(n):
            blob = rep.dump_shard(si)
            writer.add_blob(_shard_blob_name(ri, si), blob)
            total += len(blob)
    return {
        "ps_replicas": len(replicas),
        "ps_internal_shards": shards_per,
        "ps_bytes": total,
    }


def restore_ps(
    manifest: Manifest, replicas: Sequence,
    optimizer=None, batch_advances: Optional[Dict[int, int]] = None,
) -> int:
    """Rewind the PS tier to the manifest's fence: per replica clear, replay
    shard blobs, CLEAR THE APPLY-JOURNAL (post-fence ids must re-apply after
    a rewind — a stale journal entry would wrongly skip them), re-register
    the optimizer, and re-advance Adam batch state to the fence's counts.
    Returns entries restored."""
    meta = manifest.meta
    n_reps = int(meta.get("ps_replicas", 0))
    if n_reps != len(replicas):
        raise ManifestError(
            f"manifest captured {n_reps} PS replicas but the resuming job "
            f"has {len(replicas)} — re-shard via checkpoint.load_store instead"
        )
    shards_per = meta.get("ps_internal_shards", [])
    restored = 0
    for ri, rep in enumerate(replicas):
        rep.clear()
        if hasattr(rep, "journal_clear"):
            rep.journal_clear()
        if optimizer is not None:
            rep.register_optimizer(optimizer)
        for si in range(int(shards_per[ri])):
            restored += rep.load_shard_bytes(
                manifest.read_blob(_shard_blob_name(ri, si))
            )
        for group, count in (batch_advances or {}).items():
            for _ in range(int(count)):
                rep.advance_batch_state(int(group))
    return restored
