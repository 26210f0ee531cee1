"""State cache: bytes of the retention state the engine holds for all
its slots and layers (``engine.kv_stats()["state_bytes"]``, read by the
driver before the engine is freed), in GB.  It does not grow with the
context."""
from hvdbench.layers import named


def read(wanted, view):
    nbytes = (view.facts.get("state") or {}).get("state_bytes")
    if not nbytes:
        return {}
    return {n: nbytes / 1e9 for n in named(wanted, "state_gb")}
