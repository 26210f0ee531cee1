"""Model: the operations the forward and backward passes require per
token (from the configuration's shapes, ``flops.py``) times tokens per
second, over chips times the published peak."""
from hvdbench import flops
from hvdbench.layers import named


def read(wanted, view):
    f = view.facts
    if "tokens_per_step" not in f:
        return {}
    rate = f["steps"] * f["tokens_per_step"] / f["elapsed_s"]
    peak = flops.peaks(view.device_kind)["bf16_flops_per_s"]
    need = flops.train_flops_per_token(view.config, f["seq_len"])
    return {n: 100.0 * rate * need / (f["chips"] * peak)
            for n in named(wanted, "mfu")}
