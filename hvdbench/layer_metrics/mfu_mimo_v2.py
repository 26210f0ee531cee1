"""Model, ``mimo_v2`` family, served: the whole step's share of the
chip's peak — the operations a token needs on this chip's share of the
model (from the configuration's shapes, ``flops_mimo_v2.py``: every
matrix product of the layers held here, the held experts at the
expectation under even routing, the attention over the mean context the
engine's counters give) times the tokens the window was credited a
second (prompt tokens and generated ones), over the published peak.
Small in decode, where a step is bound by the bytes of the weights."""
from hvdbench import flops, flops_mimo_v2
from hvdbench.layer_metrics import _mimo_v2
from hvdbench.layers import named


def read(wanted, view):
    names = named(wanted, "mfu_mimo_v2")
    f = view.facts
    per = _mimo_v2.counters_a_step(view) if names else None
    if not per or not per.get("rows") or not f.get("elapsed_s"):
        return {}
    rate = f["tokens"] / f["elapsed_s"]
    try:
        peak = flops.peaks(view.device_kind)["bf16_flops_per_s"]
    except KeyError:    # a rehearsal's device has no published peak
        return {}
    need = flops_mimo_v2.serve_flops_per_token(
        _mimo_v2.sizes(view), per["positions_full"] / per["rows"])
    return {n: 100.0 * rate * need / peak for n in names}
