"""Experts: ``moe_experts_ms`` is device-0 milliseconds a step under
the program's ``hvd_tpu_moe_experts`` scope (the grouped products over
the held experts, all layers, forward and backward);
``moe_route_ms`` the same under ``hvd_tpu_moe_route`` (router, top-k,
sort, gather, weighted un-sort: what dropless routing costs beside the
products it feeds); ``moe_experts_roofline`` the least time the chip
could take for the products — operations and bytes of the pairs this
chip held (``hvdbench/flops_nemotron_h.py``) — over the measured time.
The pairs held are not assumed even: the family counts them by sending
the ring's own batches through the seed's router
(``models/nemotron_h.py::pairs_held``: the seed's weights as made, not
as the window left them)."""
import importlib
import json

from hvdbench import flops, flops_nemotron_h
from hvdbench.layer_metrics import _nemotron_h
from hvdbench.layers import named

_SCOPES = {"moe_experts_ms": "hvd_tpu_moe_experts",
           "moe_route_ms": "hvd_tpu_moe_route"}


def read(wanted, view):
    names = {base: named(wanted, base) for base in _SCOPES}
    share = named(wanted, "moe_experts_roofline")
    if not share and not any(names.values()):
        return {}
    found = _nemotron_h.ms_a_step(view)
    if not found:
        return {}
    out = {n: found[scope] for base, scope in _SCOPES.items()
           if scope in found for n in names[base]}
    value = found.get("hvd_tpu_moe_experts")
    if share and value:
        try:
            family = importlib.import_module(
                f"hvdbench.models.{view.config['family']}")
            ref = importlib.import_module(
                f"hvdbench.reference.{view.config['reference']}")
            pairs = family.pairs_held(view.config, view.traffic)
        except Exception as e:   # a reader never takes the result line down
            print(json.dumps({"moe_experts": f"pairs not counted: "
                              f"{type(e).__name__}: {e}"}), flush=True)
            return out
        cost = flops_nemotron_h.experts_cost(ref.sizes(view.config),
                                             pairs["mean_a_layer"])
        roof = flops.roofline_share(cost, value / 1e3, view.device_kind)
        print(json.dumps({"moe_experts": {
            "ms_a_step": value, "pairs_held": pairs, "flops": cost["flops"],
            "bytes": cost["bytes"], "bound": roof["bound"]}}), flush=True)
        out.update({n: roof["percent"] for n in share})
    return out
