"""The comparison that decides ``correct``.

Set-up drives the program, from the seed, through its first steps with the
window's own call and feed (``observe_program``); once the window has closed
and the program's state is freed, the plain reference follows the same batches
from the seed (``judge``). An entry names the compared steps after
which it can be read (``snapshot_after``): ``(1, 3)`` where the window drives
one step a call, ``(K,)`` where it drives packs of K steps in one program.
Compared, each against a limit of its own (``perf/limits/<workload>.json``):

- ``loss_gap``: each compared step's loss, the worst relative gap;
- ``grad_gap``: the norm of the gradient as the optimizer got it, worked out
  from the state before and after the first read (Adam's first moment:
  (mu_k - b1^k mu_0) / (1 - b1), the first gradient itself where k = 1, the
  b1-weighted sum of the pack's k gradients otherwise; for the table, where
  k = 1, (w0 - w1) * sqrt(acc1 + eps) / lr over the touched rows), by the worst
  leaf: the gap between the program's norm and the reference's, measured
  against the reference's norm of that leaf or of the median leaf, whichever
  is larger;
- ``change_gap``: the norm of each leaf's change over the compared steps, the
  same way; leaves whose reference gradient is under a thousandth of the
  median leaf's are left out (they move under Adam by round-off alone);
- ``grad_gap_median_leaf``, ``change_gap_median_leaf``: the median leaf's gap
  in place of the worst one's. One unit's ReLU that falls on the other side of
  zero for one sample, in the program and in the reference, moves every leaf
  below it by 1e-05 to 1e-04 (about one step in 200); a lower precision moves
  all of them, so the median tells the two apart where the worst leaf cannot;
- ``held_row_gap`` (cached entries): rows trained in the compared steps and
  never touched again, a sample drawn from the seed, read back after the
  window from wherever cache and parameter server hold them, against the
  reference's rows after that step: the largest absolute gap over the largest
  reference value. A row that neither holds reads as 1.

Norms are taken in float64 on the host.
"""

from __future__ import annotations

import json
import os
from typing import Dict, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
WATCHED_ROWS = 2048
FAULTS = ("state_unchanged", "half_batch")


def _norm(x) -> float:
    return float(np.sqrt(np.sum(np.square(np.asarray(x, np.float64)))))


def load_limits(workload: str, root: Optional[str] = None) -> Dict[str, float]:
    base = os.path.join(root, "perf") if root else HERE
    with open(os.path.join(base, "limits", f"{workload}.json")) as f:
        return {k: float(v) for k, v in json.load(f)["limits"].items()}


# ------------------------------------------------------------------ program

class RowWatcher:
    """Sees every batch in order. From the first ``n_first`` (the compared
    steps) it draws a sample of row keys from the seed; of those it then
    strikes every key a later batch touches. What is left was trained in the
    compared steps alone."""

    def __init__(self, keys_of, seed: int, n_first: int):
        self._keys_of, self._seed, self._n = keys_of, int(seed), int(n_first)
        self._first: List[np.ndarray] = []
        self.sample: Optional[np.ndarray] = None
        self.touched: Optional[np.ndarray] = None

    def tap(self, b: dict) -> None:
        keys = self._keys_of(b).reshape(-1)
        if self.sample is None:
            self._first.append(keys)
            if len(self._first) == self._n:
                union = np.unique(np.concatenate(self._first))
                rng = np.random.Generator(np.random.PCG64([self._seed, 0xC0DE]))
                take = min(WATCHED_ROWS, len(union))
                self.sample = np.sort(rng.choice(union, take, replace=False))
                self.touched = np.zeros(take, bool)
                self._first = []
            return
        at = np.minimum(np.searchsorted(self.sample, keys), len(self.sample) - 1)
        hit = self.sample[at] == keys
        self.touched[at[hit]] = True

    def read(self, entry) -> dict:
        keys = self.sample[~self.touched]
        rows, acc, found = entry.held_rows(keys)
        return {"keys": keys, "rows": rows, "acc": acc, "found": found}


def observe_program(entry, stream) -> dict:
    """Drive the program through the compared steps; keep the batches, its
    losses and its state before them and after each step
    ``entry.snapshot_after`` names (host copies). Where an entry's run of
    compared steps did not go through the window's program (a K-step pack that
    fell apart returns None), those batches become a lead-in, which the
    reference follows too, and the next ones are tried."""
    lead: List[dict] = []
    for _ in range(3):
        first = [next(stream) for _ in range(entry.snapshot_after[-1])]
        keys = np.unique(np.concatenate([entry.keys(b).reshape(-1) for b in first]))
        snaps = {0: entry.snapshot(keys)}
        losses, at = [], 0
        for upto in entry.snapshot_after:
            got = entry.compared_run(first[at:upto])
            if got is None:
                break
            losses += got
            snaps[upto] = entry.snapshot(keys)
            at = upto
        else:
            return {"lead": lead, "first": first, "keys": keys, "losses": losses, "snaps": snaps}
        lead += first
    raise RuntimeError("the compared steps never ran through the window's own program")


def plant_fault(entry, fault: str) -> None:
    """Break the timed path underneath the harness (tests and
    ``perf/limits_study.py`` only)."""
    if fault == "half_batch":  # half of the batch left out, the mean taken over the rest
        inner = entry.to_program_batch

        def half(b):
            h = b["labels"].shape[0] // 2
            return inner({"ids": b["ids"][:, :h], "dense": b["dense"][:h],
                          "labels": b["labels"][:h]})

        entry.to_program_batch = half
    elif fault == "state_unchanged":  # a step that returns its state unchanged
        inner_run = entry.compared_run

        def frozen(batches):
            import jax
            import jax.numpy as jnp

            before = jax.tree.map(jnp.copy, entry.ctx.state)
            losses = inner_run(batches)
            entry.ctx.state = before
            return losses

        entry.compared_run = frozen
    else:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")


# ---------------------------------------------------------------- reference

def run_reference(config: dict, entry, lead: List[dict], first: List[dict], seed: int,
                  keys: np.ndarray, passes: int = 6) -> dict:
    """The reference over the same batches from the seed; the same readings as
    ``observe_program``. ``passes`` below 6 makes it the control."""
    from perf import weights
    from perf.reference.dlrm import ReferenceDLRM

    ref = ReferenceDLRM(config, weights.dense_params(config, seed), entry.row_birth,
                        passes=passes, adam_start=getattr(entry, "adam_start", None))

    def snap():
        rows, acc = ref.lookup(keys)
        return {"dense": [(np.asarray(k), np.asarray(b)) for k, b in ref.dense],
                "adam_mu": [(np.asarray(k), np.asarray(b)) for k, b in ref.m],
                "rows": rows, "acc": acc}

    for b in lead:
        ref.step(entry.keys(b), b["dense"], b["labels"])
    snaps = {0: snap()}
    losses = []
    for i, b in enumerate(first, start=1):
        losses.append(ref.step(entry.keys(b), b["dense"], b["labels"]))
        if i in entry.snapshot_after:
            snaps[i] = snap()
    return {"keys": keys, "losses": losses, "snaps": snaps, "ref": ref, "n_lead": len(lead)}


def held_by(ref_run: dict, keys: np.ndarray) -> dict:
    """What a reference run holds for ``keys``, in the form ``RowWatcher.read``
    gives for the program: the control's read-back."""
    rows, acc = ref_run["ref"].lookup(keys)
    return {"keys": keys, "rows": rows, "acc": acc, "found": np.ones(len(keys), bool)}


# ----------------------------------------------------------------- readings

def _leaf_grads(config: dict, snaps: dict) -> Dict[str, float]:
    k = min(i for i in snaps if i > 0)  # the first read after the compared steps began
    b1 = float(config["dense_optimizer"]["b1"])
    so = config["sparse_optimizer"]
    out = {}
    for l, ((k0, b0), (k1, bb1)) in enumerate(zip(snaps[0]["adam_mu"], snaps[k]["adam_mu"])):
        out[f"L{l}.kernel"] = _norm(np.asarray(k1, np.float64) - b1 ** k * k0) / (1.0 - b1)
        out[f"L{l}.bias"] = _norm(np.asarray(bb1, np.float64) - b1 ** k * b0) / (1.0 - b1)
    if k == 1:
        w0 = np.asarray(snaps[0]["rows"], np.float64)
        w1 = np.asarray(snaps[1]["rows"], np.float64)
        acc1 = np.asarray(snaps[1]["acc"], np.float64)
        out["table"] = _norm((w0 - w1) * np.sqrt(acc1 + float(so["eps"])) / float(so["lr"]))
    return out


def _leaf_changes(snaps: dict, last: int) -> Dict[str, float]:
    out = {}
    for l, ((k0, b0), (k3, b3)) in enumerate(zip(snaps[0]["dense"], snaps[last]["dense"])):
        out[f"L{l}.kernel"] = _norm(np.asarray(k3, np.float64) - k0)
        out[f"L{l}.bias"] = _norm(np.asarray(b3, np.float64) - b0)
    out["table"] = _norm(np.asarray(snaps[last]["rows"], np.float64) - snaps[0]["rows"])
    return out


def _leaf_gaps(prog: Dict[str, float], ref: Dict[str, float], leaves) -> Dict[str, float]:
    med = float(np.median([ref[n] for n in leaves]))
    return {n: abs(prog[n] - ref[n]) / max(ref[n], med, 1e-300) for n in leaves}


def readings(config: dict, prog: dict, ref: dict, after: Optional[dict] = None,
             detail: Optional[dict] = None) -> Dict[str, float]:
    """The numbers compared, program (or control, or fault) against reference."""
    last = max(prog["snaps"])
    out = {"loss_gap": max(abs(p - r) / abs(r) for p, r in zip(prog["losses"], ref["losses"]))}
    gp, gr = _leaf_grads(config, prog["snaps"]), _leaf_grads(config, ref["snaps"])
    leaves = sorted(gr)
    by_leaf = {"grad": _leaf_gaps(gp, gr, leaves)}
    out["grad_gap"] = max(by_leaf["grad"].values())
    med_g = float(np.median([gr[n] for n in leaves]))
    moving = [n for n in leaves if gr[n] >= 1e-3 * med_g]
    if "table" not in gr:  # a pack gives no one-step table gradient: its change is compared
        moving.append("table")
    by_leaf["change"] = _leaf_gaps(
        _leaf_changes(prog["snaps"], last), _leaf_changes(ref["snaps"], last), moving)
    out["change_gap"] = max(by_leaf["change"].values())
    out["change_gap_median_leaf"] = float(np.median(list(by_leaf["change"].values())))
    out["grad_gap_median_leaf"] = float(np.median(list(by_leaf["grad"].values())))
    if detail is not None:
        detail.update(by_leaf, ref_grad_norms=gr)
    if after and not len(after["keys"]):
        out["held_row_gap"] = 1.0  # every sampled row was trained again: nothing was read
    elif after:
        rows_r, acc_r = ref["ref"].lookup(after["keys"])
        held = np.concatenate([after["rows"], after["acc"]], axis=1).astype(np.float64)
        want = np.concatenate([rows_r, acc_r], axis=1).astype(np.float64)
        gap = np.abs(held - want).max(axis=1) / np.abs(want).max()
        gap[~after["found"]] = 1.0
        out["held_row_gap"] = float(gap.max())
    return out


def judge(config: dict, entry, observed: dict, after: dict, seed: int, workload: str,
          root: Optional[str] = None, reference: Optional[dict] = None) -> dict:
    """``observed`` (the program's ``observe_program``, or a control or fault
    in its place) against the reference, each number against its limit."""
    ref = reference
    if ref is None or ref["n_lead"] != len(observed["lead"]):  # made for other batches
        ref = run_reference(config, entry, observed["lead"], observed["first"], seed,
                            observed["keys"])
    detail: dict = {}
    got = readings(config, observed, ref, after or None, detail)
    limits = load_limits(workload, root)
    missing = sorted(set(limits) - set(got))
    if missing:
        raise RuntimeError(f"numbers with a limit but no reading: {missing}")
    compared = {k: (got[k], limits[k]) for k in sorted(limits)}
    correct = all(np.isfinite(v) and v <= lim for v, lim in compared.values())
    return {"correct": correct, "compared": {k: [v, lim] for k, (v, lim) in compared.items()},
            "all_readings": got, "by_leaf": detail, "reference": ref}
