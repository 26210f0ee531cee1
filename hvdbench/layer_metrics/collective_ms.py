"""Gradient wire: per step, from the device trace, the time inside
all-reduce / reduce-scatter / all-gather operations on device 0, and the
part of it during which no other operation ran there."""
from hvdbench.layers import named
from hvdbench.reduce import xplane


def read(wanted, view):
    steps = view.facts.get("traced_steps")
    if not view.rows or not steps:
        return {}
    c = xplane.collectives(view.rows)
    if not c["count"]:
        return {}
    out = {n: c["total_s"] / steps * 1e3
           for n in named(wanted, "collective_ms")}
    out.update({n: c["exposed_s"] / steps * 1e3
                for n in named(wanted, "collective_exposed_ms")})
    return out
