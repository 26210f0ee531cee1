"""Native (C++) control-plane runtime, loaded via ctypes.

The reference's runtime core is C++ (SURVEY.md §2.1); this package holds
the TPU framework's native equivalents — the *control plane* only: tensor
bytes live in XLA device buffers and never cross this boundary.

Inventory (``src/``):

* ``planner.cc`` — fusion bucket planner (:mod:`.planner`)
* ``wire.{h,cc}`` — Request/Response wire format (message.fbs analogue)
* ``tensor_queue.h`` — framework→coordinator handoff queue
* ``controller.{h,cc}`` — rank-0 consensus + fusion (ComputeResponseList)
* ``response_cache.h`` — steady-state decision cache
* ``group_table.h`` — grouped-collective atomicity
* ``stall_inspector.h`` — some-but-not-all-ranks stall tracking
* ``timeline.{h,cc}`` — background-thread Chrome-trace writer
* ``coordinator.{h,cc}`` — TCP negotiation service (background-loop
  equivalent for the eager multi-process path)
* ``c_api.cc`` — plain-C ABI (:mod:`.bindings`)

Components build lazily with the in-image toolchain (``g++``) on first
use and cache the shared object beside the package, named after a
digest of its sources (``build.py``; no ``.so`` is committed); every
native entry point has a pure-python fallback, so a missing compiler
only costs speed, never correctness (``horovodtpurun --check-build`` reports which
path is active).
"""

from . import bindings  # noqa: F401
from . import planner  # noqa: F401
from .runtime import (  # noqa: F401
    Controller, Coordinator, NativeStallInspector, NativeTensorQueue,
    NativeTimeline, NativeUnavailableError, Request, Response, available,
    encode_requests, decode_requests, encode_responses, decode_responses,
)
