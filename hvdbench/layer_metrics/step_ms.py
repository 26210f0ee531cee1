"""Train step: elapsed time of the window over its whole steps."""
from hvdbench.layers import named


def read(wanted, view):
    f = view.facts
    if "tokens_per_step" not in f:
        return {}
    return {n: f["elapsed_s"] / f["steps"] * 1e3
            for n in named(wanted, "step_ms")}
