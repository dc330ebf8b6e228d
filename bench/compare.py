"""The comparison that decides ``correct``: what the program produced in
round 1 against what the plain reference produced from the same inputs.

Each number has its own limit (``limits.json``, with the readings it was
set from in PERF.md):

client_change   per client and parameter leaf, the gap between the norms
                of the program's and the reference's change from the
                initial global, over the larger of the reference leaf's
                norm and its median leaf's; worst leaf of any client
bank_logits     the logit bank: largest gap of any row and class, over
                the root mean square of the reference's bank
accuracy        largest gap of the accuracies the round reports before
                any distillation (FedDF's pre-distillation accuracy of
                the mean, the heterogeneous ensemble's accuracy, FedAvg's
                test and validation accuracy), in accuracy units
distill_change  per distillation and leaf, over its first chunk of steps
                (the compiled chunk's own first call, before any
                checkpoint is chosen): the gap between the norms of the
                program's and the reference's change of the student, over
                the larger of the reference leaf's norm and its median
                leaf's; the median leaf, largest over the distillations
distill_grad    the same for the gradient norm that Adam's second moment
                holds after that chunk (the root of its bias-corrected
                sum): the fused kernel's gradient as the optimizer got it

The distill numbers take the median leaf, not the worst: the chunk's
Adam steps start from a KL gradient near zero, so which leaves its
round-off tilts most changes from seed to seed (PERF.md).

Leaves whose first gradient in the reference is under a thousandth of the
median leaf's move by round-off alone and are left out of the changes
(the clients' first local gradient for client_change, the distillation's
for the distill numbers).  A distillation's leaves are also left out
where their gradient in the reference is under a thousandth of the
clients' median leaf: there the student already agrees with the bank to
the last digits of a saturated softmax, Adam divides a gradient far below
its epsilon, and the student moves by round-off alone.  A distillation
with no leaf left is left out of the distill numbers; where every one is,
they are not reported (``not_compared`` counts them).

Read for the record and not compared (``not_compared``): the first
chunk's worst leaves, and the student after the whole distillation (its
change, the median leaf's gap) and its accuracies (PERF.md).
"""
from __future__ import annotations

import json
import os
from typing import Dict, List, Optional

import numpy as np

NUMBERS = ("client_change", "bank_logits", "accuracy", "distill_change",
           "distill_grad")
LIMITS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "limits.json")
#: leaves with a first gradient under this share of the median are noise
GRAD_FLOOR = 1e-3


def load_limits(path: str = LIMITS_FILE) -> Dict[str, float]:
    with open(path) as f:
        return {k: float(v) for k, v in json.load(f)["limits"].items()}


def kept_leaves(first_grad: Dict[str, float]) -> List[str]:
    med = float(np.median(list(first_grad.values())))
    return [k for k, g in first_grad.items() if g >= GRAD_FLOOR * med]


def distill_leaves(chunk_grad: Dict[str, float],
                   first_grad: Dict[str, float]) -> List[str]:
    """The leaves of one distillation that count: those ``kept_leaves``
    keeps of its own gradient, at or above a thousandth of the median leaf
    of the clients' first gradient."""
    floor = GRAD_FLOOR * float(np.median(list(first_grad.values())))
    return [k for k in kept_leaves(chunk_grad) if chunk_grad[k] >= floor]


def leaf_gaps(prog: Dict[str, float], ref: Dict[str, float],
              keep: List[str]) -> Dict[str, float]:
    """Per leaf: |‖Δ_prog‖ - ‖Δ_ref‖| / max(‖Δ_ref‖, median leaf)."""
    keys = [k for k in keep if k in ref]
    med = float(np.median([ref[k] for k in keys]))
    out = {}
    for k in keys:
        denom = max(ref[k], med)
        gap = abs(prog[k] - ref[k]) / denom if denom > 0 else \
            abs(prog[k] - ref[k])
        out[k] = float(gap) if np.isfinite(gap) else float("inf")
    return out


def change_gap(prog: Dict[str, float], ref: Dict[str, float],
               keep: List[str]) -> float:
    """The worst leaf's gap (:func:`leaf_gaps`)."""
    return max(leaf_gaps(prog, ref, keep).values())


def worst_leaves(prog, ref, top: int = 3) -> Dict[str, list]:
    """For a failed check's reader: the leaves with the largest gaps, with
    the program's and the reference's change norms."""
    keep = kept_leaves(ref.first_grad)
    out = {}
    rows = []
    for g, (pg, rg) in enumerate(zip(prog.clients, ref.clients)):
        for c, (pc, rc) in enumerate(zip(pg, rg)):
            rows += [(v, f"g{g}c{c}{k}", pc[k], rc[k])
                     for k, v in leaf_gaps(pc, rc, keep).items()]
    out["client_change"] = sorted(rows, reverse=True)[:top]
    rows = []
    for g, (pf, rf) in enumerate(zip(prog.fused, ref.fused)):
        rows += [(v, f"g{g}{k}", pf[k], rf[k])
                 for k, v in leaf_gaps(pf, rf, keep).items()]
    out["fused_change"] = sorted(rows, reverse=True)[:top]
    for part in ("change", "grad"):
        rows = []
        for g, (pc, rc) in enumerate(zip(prog.chunks, ref.chunks)):
            keep = distill_leaves(rc["grad"], ref.first_grad)
            if keep:
                rows += [(v, f"d{g}{k}", pc[part][k], rc[part][k])
                         for k, v in leaf_gaps(pc[part], rc[part],
                                               keep).items()]
        out[f"distill_{part}"] = sorted(rows, reverse=True)[:top]
    return out


def chunk_gaps(prog, ref, part: str, stat=max) -> Optional[float]:
    """``stat`` (the worst leaf, or the median) of the first chunks' leaf
    gaps of ``part`` (``change`` or ``grad``), the largest over the
    distillations that count (:func:`distill_leaves`); None when none
    does, inf when the program ran another number of them."""
    if len(prog.chunks) != len(ref.chunks):
        return float("inf")
    out = None
    for pc, rc in zip(prog.chunks, ref.chunks):
        keep = distill_leaves(rc["grad"], ref.first_grad)
        if keep:
            gaps = leaf_gaps(pc[part], rc[part], keep)
            out = max(out or 0.0, float(stat(list(gaps.values()))))
    return out


def _gap(x, y) -> float:
    return float("inf") if x is None else abs(x - y)


def numbers(prog, ref) -> Dict[str, float]:
    """The compared numbers.  ``prog`` and ``ref`` are both
    ``reference.RoundOne``: the program's read from its round-1 outputs,
    the reference's computed."""
    keep = kept_leaves(ref.first_grad)
    out: Dict[str, float] = {}
    gaps = [0.0]
    for pg, rg in zip(prog.clients, ref.clients, strict=True):
        for pc, rc in zip(pg, rg, strict=True):
            gaps.append(change_gap(pc, rc, keep))
    out["client_change"] = max(gaps)
    if ref.bank is not None:
        if prog.bank is None or prog.bank.shape != ref.bank.shape:
            out["bank_logits"] = float("inf")
        else:
            rms = float(np.sqrt(np.mean(np.square(ref.bank))))
            gap = float(np.max(np.abs(prog.bank.astype(np.float64)
                                      - ref.bank)))
            out["bank_logits"] = gap / max(rms, 1e-30) \
                if np.isfinite(gap) else float("inf")
    accs = [_gap(x, y) for x, y in zip(prog.pre_acc, ref.pre_acc,
                                        strict=True) if y is not None]
    if ref.ens_acc is not None:
        accs.append(_gap(prog.ens_acc, ref.ens_acc))
    for p, done in enumerate(ref.distilled):
        if not done:
            accs += [_gap(prog.test_acc[p], ref.test_acc[p]),
                     _gap(prog.val_acc[p], ref.val_acc[p])]
    out["accuracy"] = max(accs)
    for part in ("change", "grad") if ref.chunks else ():
        gap = chunk_gaps(prog, ref, part, np.median)
        if gap is not None:
            out[f"distill_{part}"] = gap
    return {k: float(v) for k, v in out.items()}


def not_compared(prog, ref) -> Dict[str, float]:
    """What distillation produced, read for the record: the median leaf's
    gap of the fused globals' change and the largest gap of their test
    and validation accuracies, over the distilled groups, and how many
    distillations the distill numbers leave out."""
    keep = kept_leaves(ref.first_grad)
    out = {"fused_change": 0.0, "distilled_accuracy": 0.0,
           "distill_change_worst": 0.0, "distill_grad_worst": 0.0,
           "distill_left_out": float(sum(
               not distill_leaves(rc["grad"], ref.first_grad)
               for rc in ref.chunks))}
    if ref.chunks:
        for part in ("change", "grad"):
            out[f"distill_{part}_worst"] = chunk_gaps(prog, ref, part) or 0.0
    for p, done in enumerate(ref.distilled):
        if done:
            gaps = leaf_gaps(prog.fused[p], ref.fused[p], keep)
            out["fused_change"] = max(out["fused_change"],
                                      float(np.median(list(gaps.values()))))
            out["distilled_accuracy"] = max(
                out["distilled_accuracy"],
                _gap(prog.test_acc[p], ref.test_acc[p]),
                _gap(prog.val_acc[p], ref.val_acc[p]))
    return out


def judge(nums: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(np.isfinite(v) and v <= limits[k] for k, v in nums.items())


def lines(nums: Dict[str, float], limits: Dict[str, float]) -> List[str]:
    return [f"check {k} = {nums[k]!r} limit {limits[k]!r} "
            f"{'ok' if np.isfinite(nums[k]) and nums[k] <= limits[k] else 'FAIL'}"
            for k in NUMBERS if k in nums]


def as_json(nums: Dict[str, float], limits: Dict[str, float]) -> dict:
    big = lambda v: v if np.isfinite(v) else 1e300
    return {k: {"value": big(nums[k]), "limit": limits[k]}
            for k in NUMBERS if k in nums}
