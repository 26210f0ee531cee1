"""What the readers of the state-space and expert metrics share:
device-0 seconds of the traced window under the scopes the program puts
inside its compiled train step around the scan alone
(``hvd_tpu_ssm_scan``), the routing (``hvd_tpu_moe_route``), the
grouped products over the held experts (``hvd_tpu_moe_experts``) and
the shared expert (``hvd_tpu_moe_shared``), forward and transpose; read
from the operations' metadata by ``reduce/xspace.py``.  Nothing is
returned, and nothing raised, where the run was not traced or the
program has no such scope."""

from __future__ import annotations

import re
from typing import Dict, Optional

from hvdbench.reduce import program_spans as ps
from hvdbench.reduce import xplane, xspace

SCOPE = re.compile(r"hvd_tpu_(?:ssm_scan|moe_route|moe_experts|moe_shared)")
_cache: Dict[str, Optional[dict]] = {}


def ms_a_step(view) -> Optional[dict]:
    """``{scope: device-0 milliseconds a traced step}``, or None."""
    steps = view.facts.get("traced_steps")
    path = ps.trace_file(view)
    planes = xplane.device_planes(view.rows) if path else []
    if not steps or not planes:
        return None
    if path not in _cache:
        try:
            found = xspace.seconds_by_scope(path, planes[0], xplane.OP_LINE,
                                            SCOPE)
        except Exception as e:
            ps.say(nemotron_h=f"scopes not read: {type(e).__name__}: {e}")
            found = None
        if found is not None and not found["by_scope"]:
            ps.say(nemotron_h="no operation of the trace lies under a "
                   "state-space or expert scope of the program",
                   ops=found["ops"], ops_with_op_name=found["named"])
            found = None
        if found is not None:
            found = {k: v / steps * 1e3 for k, v in found["by_scope"].items()}
            ps.say(nemotron_h_scopes_ms_a_step=found)
        _cache[path] = found
    return _cache[path]
