"""Engine: the decode program's dispatch call — median
``args.dispatch_us`` over the program's ``hvd_tpu_engine_decode`` spans
that ended inside the measured window, from the span ring (the whole
window, not the traced tail): argument handling and the enqueue, what
fewer, stacked arguments would shorten (ROADMAP S4)."""
from hvdbench import stats
from hvdbench.layer_metrics import _decode_phases as phases
from hvdbench.layers import named
from hvdbench.reduce import program_spans as ps


def read(wanted, view):
    names = named(wanted, "decode_dispatch_ms")
    if not names:
        return {}
    try:
        spans = phases.stamped(phases.window_decodes(view))
        if not spans:
            return {}
        took = {k: [s["args"][k + "_us"] / 1e3 for s in spans]
                for k in ("prepare", "dispatch", "fence")}
        ps.say(decode_phases_ms={
            "steps": len(spans),
            "span_p50": stats.median([s["dur_us"] / 1e3 for s in spans]),
            "sum_of_phases_p50": stats.median(
                [sum(ms) for ms in zip(*took.values())]),
            **{k + "_p50": stats.median(v) for k, v in took.items()},
            **{k + "_p95": stats.percentile(v, 95)
               for k, v in took.items()}})
        return {n: stats.median(took["dispatch"]) for n in names}
    except Exception as e:   # a reader never takes the result line down
        ps.say(decode_dispatch_ms=f"not read: {type(e).__name__}: {e}")
        return {}
