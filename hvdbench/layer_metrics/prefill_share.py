"""Engine: device time under ``engine_prefill`` spans over the time the
device was busy in the traced window."""
from hvdbench.layers import named
from hvdbench.reduce import xplane


def read(wanted, view):
    if not view.rows or not view.busy:
        return {}
    under = xplane.device_time_under(view.rows, "engine_prefill")
    if not under:
        return {}
    return {n: 100.0 * sum(under) / view.busy["busy_s"]
            for n in named(wanted, "prefill_share")}
