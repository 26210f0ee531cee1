"""Engine: the host's part of a decode step — median over the program's
``hvd_tpu_engine_decode`` spans of the span's duration minus device 0's
busy time inside it: building the block tables, the dispatch, and the
fence on the sampled tokens.  It sits beside ``decode_step_ms`` (the
device's part, under the benchmark's own ``engine_decode`` wrapper)."""
from hvdbench import stats
from hvdbench.layers import named
from hvdbench.reduce import program_spans as ps


def read(wanted, view):
    names = named(wanted, "engine_host_ms")
    if not names:
        return {}
    try:
        host = ps.host_times_under(ps.rows(view), ps.ENGINE_DECODE)
        if not host:
            return {}
        return {n: stats.median(host) * 1e3 for n in names}
    except Exception as e:   # a reader never takes the result line down
        ps.say(engine_host_ms=f"not read: {type(e).__name__}: {e}")
        return {}
