"""Experts, served (the ``.tpot`` metrics; ``moe_experts.py`` reads a
train step's): ``moe_experts_ms`` is device-0 milliseconds a decode
step under the program's ``hvd_tpu_moe_experts`` scope (the grouped
products over the held experts, all expert layers), ``moe_route_ms``
the same under ``hvd_tpu_moe_route`` (router, top-k, sort, gather,
weighted un-sort) — a prefill inside the traced window lies under the
same scopes and is counted with the steps; ``moe_experts_roofline`` the
least time the chip could take for a step's products — the weights of
the held experts that were sent a pair and the pairs' rows in and out,
by the engine's own counters (``experts_touched``,
``expert_pairs_held``; ``hvdbench/flops_mimo_v2.py``) — over that
time."""
import json

from hvdbench import flops, flops_mimo_v2
from hvdbench.layer_metrics import _mimo_v2
from hvdbench.layers import named

_SCOPES = {"moe_experts_ms": "hvd_tpu_moe_experts",
           "moe_route_ms": "hvd_tpu_moe_route"}


def _served(wanted, base):
    return [n for n in named(wanted, base) if n.endswith(".tpot")]


def read(wanted, view):
    names = {base: _served(wanted, base) for base in _SCOPES}
    share = _served(wanted, "moe_experts_roofline")
    if not share and not any(names.values()):
        return {}
    out = {}
    for base, scope in _SCOPES.items():
        value = _mimo_v2.ms_a_step(view, scope)
        if value is not None:
            out.update({n: value for n in names[base]})
    value = _mimo_v2.ms_a_step(view, "hvd_tpu_moe_experts")
    per = _mimo_v2.counters_a_step(view)
    if share and value and per and "experts_touched" in per:
        cost = flops_mimo_v2.decode_experts_cost(
            _mimo_v2.sizes(view), per["experts_touched"],
            per["expert_pairs"])
        roof = flops.roofline_share(cost, value / 1e3, view.device_kind)
        print(json.dumps({"moe_serve": {
            "ms_a_step": value, "a_step": per, "flops": cost["flops"],
            "bytes": cost["bytes"], "bound": roof["bound"]}}), flush=True)
        out.update({n: roof["percent"] for n in share})
    return out
