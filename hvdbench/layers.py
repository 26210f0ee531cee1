"""What a per-layer metric's reader is handed, and how the harness
finds the readers: every ``*.py`` under ``hvdbench/layer_metrics/`` is
one reader (of one metric, or of one quantity under several suffixes).
No list of metrics lives in ``run.py``."""

from __future__ import annotations

import dataclasses
import importlib
import os
from typing import Dict, List, Optional, Set

_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "layer_metrics")


@dataclasses.dataclass
class RunView:
    """One finished run as a reader sees it."""

    cell: dict
    config: dict
    traffic: dict
    facts: dict                  # the driver's host-side counts and times
    memory: dict                 # device.memory_record()
    device_kind: str
    rows: Optional[List[dict]]   # reduced trace rows, traced runs only
    busy: Optional[dict]         # reduce.xplane.busy(rows)


def named(wanted: Set[str], base: str) -> List[str]:
    """The wanted metric names that are ``base`` or ``base.<suffix>``."""
    return sorted(n for n in wanted if n == base or n.startswith(base + "."))


def read_all(wanted: Set[str], view: RunView) -> Dict[str, float]:
    """Every reader's values for this run.  A reader that finds nothing
    to read returns nothing, and the metric is left out."""
    out: Dict[str, float] = {}
    for fname in sorted(os.listdir(_DIR)):
        if not fname.endswith(".py") or fname.startswith("_"):
            continue
        module = importlib.import_module(
            f"hvdbench.layer_metrics.{fname[:-3]}")
        for name, value in (module.read(wanted, view) or {}).items():
            if name in wanted and value is not None:
                out[name] = float(value)
    return out
