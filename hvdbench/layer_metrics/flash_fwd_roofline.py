"""Kernels: the flash-attention forward kernel's events in the trace,
found by the kernel name the configuration file gives; operations and
bytes from shapes; the larger of operations over peak and bytes over
peak, over the measured time of one call."""
import json

from hvdbench import flops
from hvdbench.layers import named
from hvdbench.reduce import xplane


def read(wanted, view):
    kernel = view.config["run"].get("kernels", {}).get("flash_fwd")
    if not view.rows or not kernel or "rows_per_chip" not in view.facts:
        return {}
    seconds, calls = xplane.time_of(view.rows, kernel["match"])
    if not calls:
        return {}
    d, heads = view.config["n_embd"], view.config["n_head"]
    cost = flops.flash_fwd_cost(view.facts["rows_per_chip"], heads,
                                view.facts["seq_len"], d // heads)
    share = flops.roofline_share(cost, seconds / calls, view.device_kind)
    print(json.dumps({"flash_fwd": {
        "calls": calls, "seconds_per_call": seconds / calls,
        "bound": share["bound"]}}), flush=True)
    return {n: share["percent"] for n in named(wanted, "flash_fwd_roofline")}
