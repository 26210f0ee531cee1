"""Scheduler: the batcher's own time in a scheduling step — median over
the program's ``hvd_tpu_serve_step`` spans in the traced window of the
span's duration minus what its ``hvd_tpu_engine_prefill`` /
``hvd_tpu_engine_decode`` children cover (self time, on the host plane
of the profiler's trace).  Expiry, admission, emitting tokens, stats:
what a leaner host loop can win."""
from hvdbench import stats
from hvdbench.layers import named
from hvdbench.reduce import program_spans as ps


def read(wanted, view):
    names = named(wanted, "sched_self_ms")
    if not names:
        return {}
    try:
        own = ps.self_times(ps.rows(view), ps.SERVE_STEP,
                            (ps.ENGINE_PREFILL, ps.ENGINE_DECODE))
        if not own:
            return {}
        return {n: stats.median(own) * 1e3 for n in names}
    except Exception as e:   # a reader never takes the result line down
        ps.say(sched_self_ms=f"not read: {type(e).__name__}: {e}")
        return {}
