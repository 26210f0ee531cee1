"""Engine: what the device waits before a step's program begins —
median over the ``hvd_tpu_engine_decode`` spans of the traced tail of
(start of the first device-0 operation that begins after the span's
``hvd_tpu_decode_dispatch`` annotation opens, less the span's start):
the host's preparation and as much of the dispatch call as passes
before the device starts."""
from hvdbench import stats
from hvdbench.layer_metrics import _decode_phases as phases
from hvdbench.layers import named
from hvdbench.reduce import program_spans as ps


def read(wanted, view):
    names = named(wanted, "decode_launch_ms")
    if not names:
        return {}
    try:
        launch = [s["launch_ns"] / 1e6 for s in phases.steps(phases.rows(view))
                  if s["launch_ns"] is not None]
        if not launch:
            return {}
        return {n: stats.median(launch) for n in names}
    except Exception as e:   # a reader never takes the result line down
        ps.say(decode_launch_ms=f"not read: {type(e).__name__}: {e}")
        return {}
