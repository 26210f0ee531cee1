"""What the readers of the window, full-attention and expert metrics of
a served model share: device-0 seconds of the traced window under the
scopes the program puts inside its compiled decode program around each
kind of layer's attention (``hvd_tpu_paged_attention_window``,
``hvd_tpu_paged_attention_full``) and around the expert layer's routing
and grouped products (``hvd_tpu_moe_route``, ``hvd_tpu_moe_experts``),
read from the operations' metadata by ``reduce/xspace.py``; how many
decode steps the trace held (the benchmark's ``engine_decode`` spans
under which the device ran); and the engine's own counters
(``kv_stats()``, which the harness reads when the window opens and at
its close: ``facts["kv_at_open"]``, ``facts["kv"]``) as a step's means
over the window's steps.  A prefill inside the trace runs the expert
layers under the same two names: an operation counts only where its
name begins with a decode program's (``jit(_decode_paged_impl)/...``,
``DECODE``), so that a step's time is a step's.  Nothing is returned,
and nothing raised, where the run was not traced, the program has no
such scope or the engine no such counter."""

from __future__ import annotations

import re
from typing import Dict, Optional

from hvdbench.reduce import program_spans as ps
from hvdbench.reduce import xplane, xspace

_SCOPES = (r"hvd_tpu_(?:paged_attention_window|paged_attention_full"
           r"|moe_route|moe_experts)")
# The innermost scope of an operation of a decode program (the greedy
# ``.*`` leaves the group the last one), and of any program: for a
# trace whose names do not begin with the program's.
DECODE = re.compile(r"^jit\(\w*decode\w*\).*(" + _SCOPES + ")")
SCOPE = re.compile(_SCOPES)
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
        found = None
        for pattern in (DECODE, SCOPE):
            try:
                found = xspace.seconds_by_scope(
                    path, planes[0], xplane.OP_LINE, pattern)
            except Exception as e:
                ps.say(mimo_v2=f"scopes not read: {type(e).__name__}: {e}")
                found = None
            if found is None or found["by_scope"]:
                break
            ps.say(mimo_v2="no operation's name begins with a decode "
                   "program's: prefills are counted with the steps")
        if found is not None and not found["by_scope"]:
            ps.say(mimo_v2="no operation of the trace lies under a window, "
                   "full-attention or expert scope of the program",
                   ops=found["ops"], ops_with_op_name=found["named"])
            found = None
        if found is not None:
            found = dict(found["by_scope"])
            found["decode_steps"] = sum(
                1 for s in xplane.device_time_under(view.rows,
                                                    "engine_decode") if s > 0)
            found["kernel_s"], found["kernel_calls"] = xplane.time_of(
                view.rows, KERNEL)
            ps.say(mimo_v2_scopes=found)
        _cache[path] = found
    return _cache[path]


def ms_a_step(view, scope: str) -> Optional[float]:
    found = scope_seconds(view)
    if not found or not found["decode_steps"] or not found.get(scope):
        return None
    return found[scope] / found["decode_steps"] * 1e3


def counters_a_step(view) -> Optional[dict]:
    """The engine's counters of the mixed cache and the expert layers
    as means over the plain decode steps of the window (what each grew
    by since the window opened, over the steps since); None where the
    engine has none."""
    kv = view.facts.get("kv") or {}
    at_open = view.facts.get("kv_at_open") or {}
    if "paged_live_positions_window" not in kv:
        return None

    def grown(key):
        return kv[key] - at_open.get(key, 0)

    steps = grown("paged_decode_steps")
    if steps <= 0:
        return None
    out = {name: grown(key) / steps for name, key in (
        ("positions_full", "paged_live_positions_full"),
        ("positions_window", "paged_live_positions_window"),
        ("rows", "paged_live_rows"),
        ("experts_touched", "experts_touched"),
        ("expert_pairs", "expert_pairs_held")) if key in kv}
    for kind, per_block in (("full", "bytes_per_block"),
                            ("window", "kv_window_bytes_per_block")):
        if f"kv_{kind}_block_steps" in kv and kv.get(per_block):
            out[f"bytes_{kind}"] = (grown(f"kv_{kind}_block_steps") / steps
                                    * kv[per_block])
    out["steps"] = steps
    return out


def sizes(view) -> dict:
    import importlib

    ref = importlib.import_module(
        f"hvdbench.reference.{view.config['reference']}")
    return ref.sizes(view.config)
