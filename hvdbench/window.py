"""Window arithmetic: which tokens a measured window holds.

A serve driver stamps the clock after every scheduling step (the engine
has fenced on the step's tokens by then) and records what that step
produced.  Tokens belong to the step that produced them: a request's
prompt tokens to the step whose prefill returned its first token, each
generated token to the step that emitted it.  Nothing waits for a
request to finish, and no window holds a drain.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple


@dataclasses.dataclass
class StepRecord:
    t_before: float          # clock before the step was called
    t_after: float           # clock after it returned
    prompt_tokens: int       # prompts whose prefill returned in it
    new_tokens: int          # tokens generated in it
    admitted: Tuple[int, ...]  # stream indices of requests admitted
    active: int = 0          # slots holding a request after the step
    cpu_s: float = 0.0       # CPU time of the driving thread in the step
    prefill_s: float = 0.0   # wall time inside engine.start
    decode_s: float = 0.0    # wall time inside engine.step


def credited_tokens(steps: List[StepRecord], t0: float, t1: float) -> int:
    """Tokens credited on a clock reading in ``(t0, t1]``."""
    return sum(s.prompt_tokens + s.new_tokens for s in steps
               if t0 < s.t_after <= t1)


def block_boundaries(steps: List[StepRecord], block: int) -> Dict[int, float]:
    """Boundary ``b`` is the clock reading just before the step that
    admits the first request of block ``b``.  Admission is first in,
    first out and one request a step, so between two boundaries every
    prompt of the blocks between them is prefilled exactly once."""
    out: Dict[int, float] = {}
    for s in steps:
        for index in s.admitted:
            if index % block == 0:
                out.setdefault(index // block, s.t_before)
    return out


def whole_block_window(steps: List[StepRecord], block: int, first_block: int,
                       seconds: float) -> Optional[Tuple[float, float, int]]:
    """The measured interval of a backlog run: from boundary
    ``first_block`` to the last boundary at most ``seconds`` later.
    Returns ``(t0, t1, blocks)`` or None when not one whole block fits."""
    bounds = block_boundaries(steps, block)
    if first_block not in bounds:
        return None
    t0 = bounds[first_block]
    last = max((b for b, t in bounds.items()
                if b > first_block and t - t0 <= seconds), default=None)
    if last is None:
        return None
    return t0, bounds[last], last - first_block


def token_gaps(token_times: List[float], t0: float, t1: float) -> List[float]:
    """Gaps between consecutive tokens of one request whose later token
    fell in ``(t0, t1]``."""
    return [b - a for a, b in zip(token_times, token_times[1:])
            if t0 < b <= t1]
