"""Engine: what the host takes to learn the tokens once the device has
them — median over the ``hvd_tpu_engine_decode`` spans of the traced
tail of (end of the span's ``hvd_tpu_decode_fence`` annotation, less
the end of the last device-0 operation that began inside the span):
what a loop that launches step N + 1 before it reads step N would hide
(ROADMAP S4)."""
from hvdbench import stats
from hvdbench.layer_metrics import _decode_phases as phases
from hvdbench.layers import named
from hvdbench.reduce import program_spans as ps


def read(wanted, view):
    names = named(wanted, "decode_readback_ms")
    if not names:
        return {}
    try:
        back = [s["readback_ns"] / 1e6
                for s in phases.steps(phases.rows(view))
                if s["readback_ns"] is not None]
        if not back:
            return {}
        return {n: stats.median(back) for n in names}
    except Exception as e:   # a reader never takes the result line down
        ps.say(decode_readback_ms=f"not read: {type(e).__name__}: {e}")
        return {}
