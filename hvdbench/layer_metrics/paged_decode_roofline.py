"""Decode kernel: the least time the chip could take for one decode
step's attention over the paged cache — the key and value rows of the
positions each layer sees, by the engine's own counters
(``paged_live_positions_<kind>``: every position so far in a full layer,
at most the window in a window layer) times the rows' bytes
(``hvdbench/flops_mimo_v2.py``), over the memory peak, or its
operations over the compute peak if that is more — over the device time
of the kernel ``hvd_tpu_paged_decode`` itself, all layers, a step."""
import json

from hvdbench import flops, flops_mimo_v2
from hvdbench.layer_metrics import _mimo_v2
from hvdbench.layers import named


def read(wanted, view):
    names = named(wanted, "paged_decode_roofline")
    if not names:
        return {}
    found = _mimo_v2.scope_seconds(view)
    per = _mimo_v2.counters_a_step(view)
    if not found or not per or not found["kernel_calls"] \
            or not found["decode_steps"]:
        return {}
    seconds = found["kernel_s"] / found["decode_steps"]
    cost = flops_mimo_v2.decode_attention_cost(
        _mimo_v2.sizes(view), per["positions_full"], per["positions_window"],
        per["rows"])
    share = flops.roofline_share(cost, seconds, view.device_kind)
    print(json.dumps({"paged_decode": {
        "kernel_seconds_a_step": seconds, "a_step": per,
        "kernel_calls": found["kernel_calls"], "bytes": cost["bytes"],
        "flops": cost["flops"], "bound": share["bound"]}}), flush=True)
    return {n: share["percent"] for n in names}
