"""A block step's device time by what it does, and two shares of a
roofline (``_sdar.py`` reads the scopes and the counters;
``flops_sdar.py`` counts): ``block_attention_ms`` — device-0
milliseconds a block step under ``hvd_tpu_paged_attention`` (every
layer's attention: the decode kernel over the folded block and the few
small operations around it); ``block_transfer_ms`` — under
``hvd_tpu_block_transfer`` (the head over the whole vocabulary, the
softmax's sum, the confidences, the ranking and the transfer);
``block_decode_roofline`` — the least time the chip could take for a
step's attention (the key and value rows of the positions every row's
block sees, by the engine's ``paged_live_positions_full``, and its
operations with ``B x H`` queries) over the device time of the kernel
``hvd_tpu_paged_decode`` itself; ``moe_experts_roofline_sdar`` — the
least time for a step's grouped products (the weights of the experts
that were sent a pair and the pairs' rows in and out, by
``experts_touched`` and ``expert_pairs_held``) over the time under
``hvd_tpu_moe_experts``."""
import json

from hvdbench import flops, flops_sdar
from hvdbench.layer_metrics import _sdar
from hvdbench.layers import named

_SCOPES = {"block_attention_ms": "hvd_tpu_paged_attention",
           "block_transfer_ms": "hvd_tpu_block_transfer"}


def read(wanted, view):
    names = {base: named(wanted, base) for base in _SCOPES}
    kernel = named(wanted, "block_decode_roofline")
    experts = named(wanted, "moe_experts_roofline_sdar")
    if not (kernel or experts or any(names.values())):
        return {}
    out = {}
    for base, scope in _SCOPES.items():
        value = _sdar.ms_a_step(view, scope)
        if value is not None:
            out.update({n: value for n in names[base]})
    found, per = _sdar.scope_seconds(view), _sdar.grown(view)
    if not found or not per or not found["decode_steps"]:
        return out
    s, steps = _sdar.sizes(view), per["block_steps"]
    if kernel and found["kernel_calls"]:
        seconds = found["kernel_s"] / found["decode_steps"]
        cost = flops_sdar.block_attention_cost(
            s, per["paged_live_positions_full"] / steps,
            per["paged_live_rows"] / steps)
        share = flops.roofline_share(cost, seconds, view.device_kind)
        print(json.dumps({"block_decode": {
            "kernel_seconds_a_step": seconds,
            "kernel_calls": found["kernel_calls"], "bytes": cost["bytes"],
            "flops": cost["flops"], "bound": share["bound"]}}), flush=True)
        out.update({n: share["percent"] for n in kernel})
    value = _sdar.ms_a_step(view, "hvd_tpu_moe_experts")
    if experts and value and "experts_touched" in per:
        cost = flops_sdar.block_experts_cost(
            s, per["experts_touched"] / steps,
            per["expert_pairs_held"] / steps)
        share = flops.roofline_share(cost, value / 1e3, view.device_kind)
        print(json.dumps({"block_experts": {
            "ms_a_step": value,
            "experts_touched_a_step": per["experts_touched"] / steps,
            "pairs_a_step": per["expert_pairs_held"] / steps,
            "bytes": cost["bytes"], "flops": cost["flops"],
            "bound": share["bound"]}}), flush=True)
        out.update({n: share["percent"] for n in experts})
    return out
