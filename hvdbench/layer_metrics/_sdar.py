"""What the readers of a block-diffusion model's metrics share: device-0
seconds of the traced window under the scopes the program puts inside
its compiled block step — around the block's attention
(``hvd_tpu_paged_attention``), around the head, the confidence and the
transfer (``hvd_tpu_block_transfer``) and around the grouped products of
the experts (``hvd_tpu_moe_experts``) — read from the operations'
metadata by ``reduce/xspace.py``, only where an operation's name begins
with a decode program's (``jit(_decode_block_impl)/...``), so that a
prefill inside the trace is not counted with the steps; how many block
steps the trace held; and the engine's own counters (``kv_stats()``,
read by the harness when the window opens and at its close:
``facts["kv_at_open"]``, ``facts["kv"]``) as what each grew by over the
window.  Nothing is returned, and nothing raised, where the run was not
traced, the program has no such scope or the engine no such counter."""

from __future__ import annotations

import re
from typing import Dict, Optional

from hvdbench.reduce import program_spans as ps
from hvdbench.reduce import xplane, xspace

# The innermost scope of an operation of a decode program (the greedy
# ``.*`` leaves the group the last one).
DECODE = re.compile(r"^jit\(\w*decode\w*\).*(hvd_tpu_(?:paged_attention"
                    r"|block_transfer|moe_route|moe_experts))")
KERNEL = "hvd_tpu_paged_decode"
_cache: Dict[str, Optional[dict]] = {}


def scope_seconds(view) -> Optional[dict]:
    """``{scope: seconds}`` of this run's trace, with ``decode_steps``
    and the decode kernel's own ``kernel_s`` / ``kernel_calls``; None
    where there is nothing to read."""
    path = ps.trace_file(view)
    planes = xplane.device_planes(view.rows) if path else []
    if not planes:
        return None
    if path not in _cache:
        try:
            found = xspace.seconds_by_scope(path, planes[0], xplane.OP_LINE,
                                            DECODE)
        except Exception as e:
            ps.say(sdar=f"scopes not read: {type(e).__name__}: {e}")
            found = None
        if found is not None and not found["by_scope"]:
            ps.say(sdar="no operation of the trace lies under a scope of "
                   "the block step", ops=found["ops"],
                   ops_with_op_name=found["named"])
            found = None
        if found is not None:
            found = dict(found["by_scope"])
            found["decode_steps"] = sum(
                1 for s in xplane.device_time_under(view.rows,
                                                    "engine_decode") if s > 0)
            found["kernel_s"], found["kernel_calls"] = xplane.time_of(
                view.rows, KERNEL)
            ps.say(sdar_scopes=found)
        _cache[path] = found
    return _cache[path]


def ms_a_step(view, scope: str) -> Optional[float]:
    found = scope_seconds(view)
    if not found or not found["decode_steps"] or not found.get(scope):
        return None
    return found[scope] / found["decode_steps"] * 1e3


def grown(view) -> Optional[dict]:
    """What each of the engine's counters grew by between the window's
    opening and its close; None where the engine counts no block
    steps or the window held none."""
    kv = view.facts.get("kv") or {}
    at_open = view.facts.get("kv_at_open") or {}
    if "block_steps" not in kv:
        return None
    out = {key: kv[key] - at_open.get(key, 0) for key in (
        "block_steps", "denoise_forwards", "commit_forwards",
        "blocks_committed", "tokens_final", "paged_live_positions_full",
        "paged_live_rows", "experts_touched", "expert_pairs_held",
        "kv_full_block_steps") if key in kv}
    if out["block_steps"] <= 0:
        return None
    out["bytes_per_block"] = kv.get("bytes_per_block")
    return out


def sizes(view) -> dict:
    import importlib

    ref = importlib.import_module(
        f"hvdbench.reference.{view.config['reference']}")
    return ref.sizes(view.config)
