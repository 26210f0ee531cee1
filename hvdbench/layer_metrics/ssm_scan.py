"""State-space scan: ``ssm_scan_ms`` is device-0 milliseconds a step
under the program's ``hvd_tpu_ssm_scan`` scope, all layers together,
forward and backward (the scan alone: not the projections, the
convolution or the norm); ``ssm_scan_roofline`` the least time the chip
could take for it — the larger of the chunked algorithm's operations
over the compute peak and its bytes over the memory peak
(``hvdbench/flops_nemotron_h.py``) — over that time."""
import json

from hvdbench import flops, flops_nemotron_h
from hvdbench.layer_metrics import _nemotron_h
from hvdbench.layers import named


def read(wanted, view):
    ms = named(wanted, "ssm_scan_ms")
    share = named(wanted, "ssm_scan_roofline")
    if not ms and not share:
        return {}
    found = _nemotron_h.ms_a_step(view)
    if not found or not found.get("hvd_tpu_ssm_scan"):
        return {}
    value = found["hvd_tpu_ssm_scan"]
    out = {n: value for n in ms}
    if share:
        import importlib

        ref = importlib.import_module(
            f"hvdbench.reference.{view.config['reference']}")
        cost = flops_nemotron_h.scan_cost(ref.sizes(view.config),
                                          view.facts["tokens_per_step"])
        roof = flops.roofline_share(cost, value / 1e3, view.device_kind)
        print(json.dumps({"ssm_scan": {
            "ms_a_step": value, "flops": cost["flops"],
            "bytes": cost["bytes"], "bound": roof["bound"]}}), flush=True)
        out.update({n: roof["percent"] for n in share})
    return out
