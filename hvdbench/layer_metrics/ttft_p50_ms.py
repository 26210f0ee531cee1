"""Scheduler: median, over the requests whose first token fell in the
window, of ``first_token_at`` minus the time the request was due: queue
wait plus prefill."""
from hvdbench import stats
from hvdbench.layers import named


def read(wanted, view):
    sample = view.facts.get("ttft_ms")
    if not sample:
        return {}
    return {n: stats.median(sample) for n in named(wanted, "ttft_p50_ms")}
