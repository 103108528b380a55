"""The numbers that decide ``correct``, and the comparison with limits.

Every number is a gap between what the timed path produced and what the
plain reference (``bench/reference.py``) gives from the same seed, taken
at its worst over the lanes or rows checked.  Each number's limit sits in
``bench/limits/<cell>.json`` with the readings it was set from.  A gap
that is not finite (a NaN or an infinite loss, weight or latent on either
side) reads as infinite, so it fails every limit.
"""
from __future__ import annotations

import numpy as np

STAGES = ("g1_active", "g1_passive", "g2", "g3")
INF = float("inf")


def finite_or_inf(x: float) -> float:
    x = float(x)
    return x if np.isfinite(x) else INF


def rel(a: float, b: float) -> float:
    return finite_or_inf(abs(float(a) - float(b)) / max(abs(float(b)), 1e-30))


def leaves(tree, prefix=""):
    """``{path: array}`` of a nested dict of arrays."""
    if isinstance(tree, dict):
        out = {}
        for k in sorted(tree):
            out.update(leaves(tree[k], f"{prefix}/{k}"))
        return out
    return {prefix: np.asarray(tree, np.float64)}


def change_gaps(prog, ref_final, init) -> dict:
    """Per leaf, the gap between the norms of the program's and the
    reference's weight change from the same initial weights, each against
    the reference's change of that leaf or of the median leaf, whichever
    is larger.  A leaf missing or misshapen on the program's side reads
    infinite."""
    p, r, i = leaves(prog), leaves(ref_final), leaves(init)
    ref_change = {k: np.linalg.norm(r[k] - i[k]) for k in r}
    floor = float(np.median(list(ref_change.values())))
    out = {}
    for k, rc in ref_change.items():
        if k not in p or p[k].shape != r[k].shape:
            out[k] = INF
            continue
        pc = np.linalg.norm(p[k] - i[k])
        out[k] = finite_or_inf(abs(pc - rc) / max(rc, floor, 1e-30))
    return out


def change_gap(prog, ref_final, init) -> float:
    """The worst leaf of ``change_gaps``."""
    return max(change_gaps(prog, ref_final, init).values())


def epoch1_gap(prog: dict, ref_stage: dict) -> float:
    """The first epoch's mean training loss and the validation loss after
    it, the larger relative gap."""
    return max(rel(prog["train_loss"][0], ref_stage["train_loss"][0]),
               rel(prog["val_loss"][0], ref_stage["val_loss"][0]))


def final_loss_gap(prog: dict, ref_stage: dict) -> float:
    """The best validation loss of the fit and the last epoch's mean
    training loss, the larger relative gap."""
    if not len(prog["val_loss"]) or not len(prog["train_loss"]):
        return INF
    return max(rel(np.min(prog["val_loss"]), np.min(ref_stage["val_loss"])),
               rel(prog["train_loss"][-1], ref_stage["train_loss"][-1]))


def patience_stop(val_loss, patience: int = 10) -> int:
    """The epochs a fit with early stopping at ``patience`` would run on
    this validation history (improvement by more than 1e-6)."""
    best, since = INF, 0
    for e, v in enumerate(val_loss):
        if v < best - 1e-6:
            best, since = v, 0
        else:
            since += 1
        if since >= patience:
            return e + 1
    return len(val_loss)


def protocol_numbers(prog: dict, ref: dict) -> dict:
    """One seed lane of a protocol fit against the reference's run of the
    same seed.  The g1 lanes start from the seed alone, so their first
    epoch is compared apart from g2's and g3's, whose inputs come out of
    the 200 epochs before them.  ``final_loss`` holds every stage at the
    end of its fit, and ``probe_disagree`` is the share of rows whose
    k-fold probe prediction differs from the reference's.  The rest are
    readings that no limit holds (``bench/limits``)."""
    e1 = {"g1_epoch1": 0.0, "downstream_epoch1": 0.0}
    fin, wc, med, best_gap, stop = 0.0, 0.0, 0.0, 0, []
    for st in STAGES:
        p = prog["stages"].get(st)
        if p is None:
            return {}
        key = "g1_epoch1" if st.startswith("g1") else "downstream_epoch1"
        e1[key] = max(e1[key], epoch1_gap(p, ref[st]))
        fin = max(fin, final_loss_gap(p, ref[st]))
        gaps = change_gaps(p["params"], ref[st]["params"], ref[st]["init"])
        wc = max(wc, max(gaps.values()))
        med = max(med, float(np.median(list(gaps.values()))))
        best_gap = max(best_gap, abs(int(np.argmin(p["val_loss"]))
                                     - int(np.argmin(ref[st]["val_loss"]))))
        stop.append(patience_stop(p["val_loss"]))
    zr = np.asarray(ref["exchange"], np.float64)
    zp = np.asarray(prog["exchange"], np.float64)
    ex = (rel(np.linalg.norm(zp), np.linalg.norm(zr))
          if zp.shape == zr.shape else INF)
    pp = np.asarray(prog["probe_pred"])
    pr = np.asarray(ref["probe_pred"])
    dis = float(np.mean(pp != pr)) if pp.shape == pr.shape else INF
    return {**e1, "exchange_norm": ex, "final_loss": fin,
            "probe_disagree": dis, "weight_change": wc,
            "median_change": med, "best_epoch_gap": best_gap,
            "probe_accuracy": finite_or_inf(abs(
                float(prog["accuracy"]) - ref["metrics"]["accuracy"])),
            "patience10_epochs": stop}


def lane_numbers(prog: dict, ref_stage: dict, init) -> dict:
    """One lane of a lane-engine fit against the reference's fit."""
    return {"epoch1_loss": epoch1_gap(prog, ref_stage),
            "final_val_loss": rel(prog["val_loss"][-1],
                                  ref_stage["val_loss"][-1]),
            "weight_change": change_gap(prog["params"], ref_stage["params"],
                                        init)}


def judge(readings: list, limits: dict) -> dict:
    """Each limited number at its worst over the readings, beside its
    limit; a number missing from a reading, or not finite, counts as
    infinite."""
    out = {}
    for name, limit in limits.items():
        vals = [finite_or_inf(r.get(name, INF)) for r in readings] or [INF]
        v = max(vals)
        out[name] = {"value": v if np.isfinite(v) else 1e300,
                     "limit": float(limit)}
    return out
