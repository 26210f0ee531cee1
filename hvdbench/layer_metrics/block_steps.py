"""A block-diffusion model's scheduler and cache counters, over the
window (``engine.kv_stats()`` at its close less at its opening;
``_sdar.py``): ``tokens_a_step`` — tokens that became final a row a
block step (``tokens_final / paged_live_rows``: a block of ``B`` every
``T + 1`` forwards of a row, 1.33 at ``B = 4``, ``T = 2``; higher is
better); ``forwards_a_block`` — ``(denoise_forwards + commit_forwards)
/ blocks_committed`` (``T + 1``: 3.0 there; lower is better; blocks in
flight at the window's two edges move it a little off); ``kv_gb`` —
the paged cache's bytes in use, the mean over the window's steps."""
from hvdbench.layer_metrics import _sdar
from hvdbench.layers import named


def read(wanted, view):
    names = {base: named(wanted, base)
             for base in ("tokens_a_step", "forwards_a_block", "kv_gb")}
    per = _sdar.grown(view) if any(names.values()) else None
    if not per:
        return {}
    out = {}
    if per.get("paged_live_rows"):
        out.update({n: per["tokens_final"] / per["paged_live_rows"]
                    for n in names["tokens_a_step"]})
    if per.get("blocks_committed"):
        out.update({n: (per["denoise_forwards"] + per["commit_forwards"])
                    / per["blocks_committed"]
                    for n in names["forwards_a_block"]})
    if per.get("kv_full_block_steps") and per.get("bytes_per_block"):
        out.update({n: per["kv_full_block_steps"] / per["block_steps"]
                    * per["bytes_per_block"] / 1e9 for n in names["kv_gb"]})
    return out
