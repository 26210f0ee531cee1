"""Model, ``nemotron_h`` family: the whole step's share of the chips'
peak — the operations the forward and backward passes require per token
(from the configuration's shapes, ``flops_nemotron_h.py``; the routed
experts at the expectation under even routing; recomputed operations
not counted) times tokens per second, over chips times the published
peak."""
import importlib

from hvdbench import flops, flops_nemotron_h
from hvdbench.layers import named


def read(wanted, view):
    names = named(wanted, "mfu_nemotron_h")
    f = view.facts
    if not names or "tokens_per_step" not in f:
        return {}
    ref = importlib.import_module(
        f"hvdbench.reference.{view.config['reference']}")
    rate = f["steps"] * f["tokens_per_step"] / f["elapsed_s"]
    peak = flops.peaks(view.device_kind)["bf16_flops_per_s"]
    need = flops_nemotron_h.train_flops_per_token(ref.sizes(view.config),
                                                  f["seq_len"])
    return {n: 100.0 * rate * need / (f["chips"] * peak) for n in names}
