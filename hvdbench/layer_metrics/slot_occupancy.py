"""Scheduler: mean slots holding a request after a scheduling step."""
from hvdbench.layers import named


def read(wanted, view):
    if "slot_occupancy" not in view.facts:
        return {}
    return {n: view.facts["slot_occupancy"]
            for n in named(wanted, "slot_occupancy")}
