"""The comparison that decides ``correct``: the program's readings
against the reference's, each number beside a limit of its own that the
configuration file states with the readings it was set from."""

from __future__ import annotations

import statistics
from typing import Dict, List, Optional

import numpy as np


def _entry(name: str, value: float, limit: Optional[float]) -> dict:
    ok = limit is not None and np.isfinite(value) and value <= limit
    return {"check": name, "value": float(value), "limit": limit,
            "ok": bool(ok)}


def worst_leaf_gap(got: Dict[str, object], want: Dict[str, object]) -> dict:
    """The gap between the program's norm and the reference's, by the
    worst leaf, measured against the reference's norm of that leaf or of
    the median leaf, whichever is larger (some gradients are all but
    zero)."""
    names, g, w = [], [], []
    for key in sorted(want):
        wv = np.atleast_1d(np.asarray(want[key], np.float64))
        gv = np.atleast_1d(np.asarray(got[key], np.float64))
        if gv.shape != wv.shape:
            raise ValueError(f"leaf {key}: program has {gv.shape} norms, "
                             f"reference {wv.shape}")
        for i in range(len(wv)):
            names.append(f"{key}[{i}]" if len(wv) > 1 else key)
        g.extend(gv.tolist())
        w.extend(wv.tolist())
    g, w = np.asarray(g), np.asarray(w)
    scale = np.maximum(w, statistics.median(w.tolist()))
    gaps = np.abs(g - w) / scale
    worst = int(np.argmax(gaps))
    return {"gap": float(gaps[worst]), "leaf": names[worst]}


def train_checks(got: dict, want: dict, limits: dict) -> List[dict]:
    out = []
    if len(got["losses"]) != len(want["losses"]):
        raise ValueError("program and reference followed different "
                         "numbers of steps")
    for i, (a, b) in enumerate(zip(got["losses"], want["losses"])):
        e = _entry(f"loss_gap.step{i + 1}", abs(a - b),
                   limits.get("loss_gap"))
        e.update(program=a, reference=b)
        out.append(e)
    for key in ("grad_norms", "delta_norms"):
        worst = worst_leaf_gap(got[key], want[key])
        name = key[:-1] + "_gap"
        e = _entry(name, worst["gap"], limits.get(name))
        e["worst_leaf"] = worst["leaf"]
        out.append(e)
    e = _entry("loss_fall", got["last_loss"] - got["losses"][0],
               limits.get("loss_fall"))
    e.update(first=got["losses"][0], last=got["last_loss"])
    out.append(e)
    return out


def serve_checks(gaps: List[float], n_requests: int, limits: dict) -> List[dict]:
    if not gaps:
        e = _entry("served_logit_gap", float("inf"),
                   limits.get("served_logit_gap"))
    else:
        e = _entry("served_logit_gap", max(gaps),
                   limits.get("served_logit_gap"))
    e.update(tokens=len(gaps), requests=n_requests,
             mean_gap=float(np.mean(gaps)) if gaps else None,
             gaps_over_half_limit=sum(
                 g > 0.5 * (limits.get("served_logit_gap") or 0.0)
                 for g in gaps))
    return [e]
