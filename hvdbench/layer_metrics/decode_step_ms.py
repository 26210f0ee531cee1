"""Engine: median device time under the benchmark's ``engine_decode``
span (one span around each ``engine.step()``)."""
from hvdbench import stats
from hvdbench.layers import named
from hvdbench.reduce import xplane


def read(wanted, view):
    if not view.rows:
        return {}
    per_span = [s for s in xplane.device_time_under(view.rows,
                                                    "engine_decode") if s > 0]
    if not per_span:
        return {}
    return {n: stats.median(per_span) * 1e3
            for n in named(wanted, "decode_step_ms")}
