"""Device: 1 minus the union of device-operation intervals over the
traced window, averaged over the chips used."""
from hvdbench.layers import named


def read(wanted, view):
    if not view.busy:
        return {}
    idle = 100.0 * (1.0 - view.busy["busy_s"] / view.busy["window_s"])
    return {n: idle for n in named(wanted, "device_idle")}
