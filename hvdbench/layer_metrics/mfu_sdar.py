"""Model, ``sdar`` family, served: the whole step's share of the chip's
peak — the operations a token needs on this stage (every matrix product
of its layers at ``top_k`` experts, the attention over the mean context
the engine's counters give, the head; ``flops_sdar.py``) times the
forwards a token is given (forwards a block over the block's length, by
the engine's counters) times the tokens the window completed a second
(``tokens_final`` over the window: a prefill's tokens are left out, as
a prefill runs no head), over the published peak.  Small in decode,
where a step is bound by the bytes of the experts' weights."""
from hvdbench import flops, flops_sdar
from hvdbench.layer_metrics import _sdar
from hvdbench.layers import named


def read(wanted, view):
    names = named(wanted, "mfu_sdar")
    per = _sdar.grown(view) if names else None
    elapsed = view.facts.get("elapsed_s")
    if (not per or not elapsed or not per.get("blocks_committed")
            or not per.get("paged_live_rows")):
        return {}
    try:
        peak = flops.peaks(view.device_kind)["bf16_flops_per_s"]
    except KeyError:    # a rehearsal's device has no published peak
        return {}
    s = _sdar.sizes(view)
    forwards = ((per["denoise_forwards"] + per["commit_forwards"])
                / per["blocks_committed"])
    need = flops_sdar.serve_flops_per_token(
        s, per["paged_live_positions_full"] / per["paged_live_rows"],
        forwards)
    return {n: 100.0 * per["tokens_final"] / elapsed * need / peak
            for n in names}
