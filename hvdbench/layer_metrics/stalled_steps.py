"""Engine: decode steps of the measured window that one phase held up —
the program's ``hvd_tpu_engine_decode`` spans in the span ring that
carry ``args.stalled`` (a phase took over four times its usual length
and 50 ms more: ``serve/engine.py``).  On an earlier line
``stalled_steps``, for each (at most ten): the phase, its microseconds,
``uploads``, ``active`` and, where the step lies in the traced tail,
device 0's busy seconds inside the span with the step's own launch and
read-back (``_decode_phases.steps``) — whether the host waited for a
device that was working, for one that began late or for one that had
finished.  On the same line ``stalled_prefills``: the window's
``hvd_tpu_engine_prefill`` spans that carry ``args.stalled`` (the same
rule on a prefill's dispatch and fence, by bucket), which the count
leaves out — the pause that holds a decode step hits a prefill as
well."""
from hvdbench.layer_metrics import _decode_phases as phases
from hvdbench.layers import named
from hvdbench.reduce import program_spans as ps


def _ms(step, key):
    return None if not step or step[key] is None else step[key] / 1e6


def read(wanted, view):
    names = named(wanted, "stalled_steps")
    if not names:
        return {}
    try:
        spans = phases.window_decodes(view)
        if not phases.stamped(spans):
            return {}
        stalled = [s for s in spans if "stalled" in s["args"]]
        traced = phases.steps(phases.rows(view)) if stalled else []
        said = []
        for s in stalled[:10]:
            args, step = s["args"], phases.trace_step_of(s, spans, traced)
            said.append({
                "phase": args["stalled"],
                "us": args.get(args["stalled"] + "_us"),
                "span_us": s["dur_us"], "uploads": args.get("uploads"),
                "active": args.get("active"),
                "device_busy_s": (step["busy_ns"] / 1e9 if step else None),
                "launch_ms": _ms(step, "launch_ns"),
                "readback_ms": _ms(step, "readback_ns")})
        prefills = phases.window_prefills(view)
        ps.say(stalled_steps=said, steps=len(spans), stalled_prefills=[
            {"phase": a["stalled"], "us": a.get(a["stalled"] + "_us"),
             "span_us": s["dur_us"], "bucket": a.get("bucket"),
             "prompt_len": a.get("prompt_len")}
            for s in prefills for a in [s["args"]] if "stalled" in a][:10],
            prefills=len(prefills))
        return {n: len(stalled) for n in names}
    except Exception as e:   # a reader never takes the result line down
        ps.say(stalled_steps=f"not read: {type(e).__name__}: {e}")
        return {}
