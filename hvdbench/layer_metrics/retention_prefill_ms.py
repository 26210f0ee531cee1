"""Retention: device milliseconds a prefill under the program's
``hvd_tpu_retention_prefill`` scope, all layers together (the chunked
form: masked power scores inside a chunk, the state carried between
chunks), averaged over the traced window's prefills, whatever their
buckets."""
from hvdbench.layer_metrics import _retention
from hvdbench.layers import named


def read(wanted, view):
    names = named(wanted, "retention_prefill_ms")
    if not names:
        return {}
    found = _retention.scope_seconds(view)
    if not found or not found["prefills"] or not found["prefill_s"]:
        return {}
    return {n: found["prefill_s"] / found["prefills"] * 1e3 for n in names}
