"""Paged cache by kind of layer: bytes in use, the mean over the
window's decode steps, in GB (``engine.kv_stats()``'s
``kv_<kind>_block_steps``, what it grew by between the window's opening
and its close, times the kind's bytes a block; ``_mimo_v2.py``).  Window
layers hold a ring a slot and do not grow with the context; full layers
hold every position and do."""
from hvdbench.layer_metrics import _mimo_v2
from hvdbench.layers import named


def read(wanted, view):
    per = _mimo_v2.counters_a_step(view) or {}
    out = {}
    for kind in ("window", "full"):
        nbytes = per.get(f"bytes_{kind}")
        if nbytes:
            out.update({n: nbytes / 1e9
                        for n in named(wanted, f"kv_{kind}_gb")})
    return out
