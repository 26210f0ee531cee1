"""Retention: device milliseconds a decode step under the program's
``hvd_tpu_retention_decode`` scope, all layers together: the operator
alone (the state's read-modify-write and the read-out), without the
block's projections."""
from hvdbench.layer_metrics import _retention
from hvdbench.layers import named


def read(wanted, view):
    names = named(wanted, "retention_decode_ms")
    if not names:
        return {}
    seconds = _retention.decode_step_seconds(view)
    if seconds is None:
        return {}
    return {n: seconds * 1e3 for n in names}
