"""Load generator: 95th percentile of actual minus due submit time."""
from hvdbench import stats
from hvdbench.layers import named


def read(wanted, view):
    sample = view.facts.get("generator_late_ms")
    if not sample:
        return {}
    return {n: stats.percentile(sample, 95)
            for n in named(wanted, "generator_late_ms")}
