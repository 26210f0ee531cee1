"""Device: ``memory_stats()`` after the window, on the fullest chip:
live arrays at their peak, and what compiled programs reserved."""
from hvdbench.layers import named


def read(wanted, view):
    out = {n: view.memory["peak_bytes_in_use"] / 1e9
           for n in named(wanted, "hbm_peak_in_use_gb")}
    if view.memory.get("peak_bytes_reserved"):
        out.update({n: view.memory["peak_bytes_reserved"] / 1e9
                    for n in named(wanted, "hbm_peak_reserved_gb")})
    return out
