"""What the readers of the retention metrics share: device-0 seconds of
the traced window under the two scopes the program puts around the
operator inside its compiled decode and prefill programs
(``hvd_tpu_retention_decode``, ``hvd_tpu_retention_prefill``; read from
the operations' metadata by ``reduce/xspace.py``), and how many decode
steps and prefills the window held (the benchmark's ``engine_decode`` /
``engine_prefill`` spans under which the device ran).  Nothing is
returned, and nothing raised, where the run was not traced or the
program has no such scope."""

from __future__ import annotations

import re
from typing import Dict, Optional

from hvdbench.reduce import program_spans as ps
from hvdbench.reduce import xplane, xspace

SCOPE = re.compile(r"hvd_tpu_retention_(?:decode|prefill)")
_cache: Dict[str, Optional[dict]] = {}


def scope_seconds(view) -> Optional[dict]:
    """``{"decode_s", "prefill_s", "decode_steps", "prefills"}`` of this
    run's trace, or None."""
    path = ps.trace_file(view)
    planes = xplane.device_planes(view.rows) if path else []
    if not planes:
        return None
    if path not in _cache:
        try:
            found = xspace.seconds_by_scope(path, planes[0], xplane.OP_LINE,
                                            SCOPE)
        except Exception as e:
            ps.say(retention=f"scopes not read: {type(e).__name__}: {e}")
            found = None
        if found is not None and not found["by_scope"]:
            ps.say(retention="no operation of the trace lies under a "
                   "retention scope of the program", ops=found["ops"],
                   ops_with_op_name=found["named"])
            found = None
        if found is not None:
            by = found["by_scope"]
            found = {
                "decode_s": by.get("hvd_tpu_retention_decode", 0.0),
                "prefill_s": by.get("hvd_tpu_retention_prefill", 0.0),
                "decode_steps": sum(1 for s in xplane.device_time_under(
                    view.rows, "engine_decode") if s > 0),
                "prefills": sum(1 for s in xplane.device_time_under(
                    view.rows, "engine_prefill") if s > 0)}
            # The decode step's kernel by its own name, where the
            # program has one: the scope's time holds it and the small
            # operations around it.
            kernel_s, calls = xplane.time_of(view.rows,
                                             "hvd_tpu_retention_step")
            if calls:
                found.update(step_kernel_s=kernel_s,
                             step_kernel_calls=calls)
            ps.say(retention_scopes=found)
        _cache[path] = found
    return _cache[path]


def decode_step_seconds(view) -> Optional[float]:
    found = scope_seconds(view)
    if not found or not found["decode_steps"] or not found["decode_s"]:
        return None
    return found["decode_s"] / found["decode_steps"]
