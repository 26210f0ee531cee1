"""Engine: the share of the measured window's decode steps that had to
upload something before their dispatch — the program's
``hvd_tpu_engine_decode`` spans in the span ring with ``args.uploads``
over 0 (a block table that moved, a bind, a clear), over those that
carry the count (a speculative step does not).  The ring outlives the
engine, so the step protocol's other counts go on an earlier line
``step_protocol`` from the same spans: ``runtime_pokes`` (``args.poked``),
``sampling_steps`` (``args.sampling``) and the sum of ``args.live_blocks``."""
from hvdbench.layer_metrics import _decode_phases as phases
from hvdbench.layers import named
from hvdbench.reduce import program_spans as ps


def read(wanted, view):
    names = named(wanted, "step_uploads_share")
    if not names:
        return {}
    try:
        args = [s["args"] for s in phases.window_decodes(view)
                if "uploads" in s.get("args", {})]
        if not args:
            return {}
        uploading = sum(a["uploads"] > 0 for a in args)
        ps.say(step_protocol={
            "decode_steps": len(args), "upload_steps": uploading,
            "uploads": sum(a["uploads"] for a in args),
            "runtime_pokes": sum(bool(a.get("poked")) for a in args),
            "sampling_steps": sum(a.get("sampling", 0) for a in args),
            "live_blocks": sum(a.get("live_blocks", 0) for a in args)})
        return {n: 100.0 * uploading / len(args) for n in names}
    except Exception as e:   # a reader never takes the result line down
        ps.say(step_uploads_share=f"not read: {type(e).__name__}: {e}")
        return {}
