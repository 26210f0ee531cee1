"""Retention: the least time the chip could take for one decode step's
retention — the larger of its bytes over the memory peak and its
operations over the compute peak (``hvdbench/flops_retention.py``) —
over the device time the step spends under
``hvd_tpu_retention_decode``.  The slots whose state a step reads and
writes come from the engine's own counter (the driver's ``facts``), so
that a program that skips idle slots is not read over 100 %."""
import json

from hvdbench import flops, flops_retention
from hvdbench.layer_metrics import _retention
from hvdbench.layers import named


def read(wanted, view):
    names = named(wanted, "retention_decode_roofline")
    touched = (view.facts.get("state") or {}).get("state_slots_touched")
    if not names or not touched:
        return {}
    seconds = _retention.decode_step_seconds(view)
    if seconds is None:
        return {}
    cfg = view.config
    cost = flops_retention.decode_cost(
        int(touched), int(cfg["num_hidden_layers"]),
        int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"]),
        int(cfg["head_dim"]))
    share = flops.roofline_share(cost, seconds, view.device_kind)
    print(json.dumps({"retention_decode": {
        "seconds_per_step": seconds, "bytes": cost["bytes"],
        "flops": cost["flops"], "bound": share["bound"]}}), flush=True)
    return {n: share["percent"] for n in names}
