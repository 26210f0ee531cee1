"""XLA typed-FFI custom-call library: build, load, register, call.

Reference analogue: ``horovod/tensorflow/xla_mpi_ops.cc`` — the adapter
that registers Horovod's collectives as XLA custom calls so they execute
*inside* a compiled graph (SURVEY.md §2.3, "the highest-leverage file
for the TPU port"; mount empty, unverified).

TPU-native redesign: on TPU the collectives themselves are native HLO
(``ops/collectives.py``) — XLA:TPU neither needs nor runs user
custom-call handlers on-device.  The native half lives where host code
actually executes: the **CPU backend**, where the fusion buffer's
scatter/gather (``hvd_bucket_pack``/``unpack``) and the Adasum pairwise
combine run as typed-FFI handlers spliced into the jitted program (see
``src/ffi_ops.cc``).  ``ops/fusion.py`` routes its pack/split legs
through these handlers inside manual SPMD regions (``shard_map``) —
the fused-gradient hot path of ``make_train_step`` on the CPU
controller/test substrate — making the library load-bearing there;
under the *auto* partitioner the plain-HLO path is kept (an opaque
custom call would force operand all-gathers; measured in
``benchmarks/ffi_bench.py``, where the FFI path measured 3.88x vs the
HLO path in its manual-mode home — hlo 3334.5ms vs ffi 859.6ms, CPU
controller tier).

Registration uses ``jax.ffi.register_ffi_target`` with PyCapsules minted
from ``dlsym`` addresses via ctypes — no pybind11 (not in this image).
"""

from __future__ import annotations

import ctypes
import os
import threading
from typing import List, Optional, Sequence

from ..utils.logging import get_logger
from .build import build_shared

logger = get_logger(__name__)

_HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(_HERE, "src", "ffi_ops.cc")

_TARGETS = ("hvd_bucket_pack", "hvd_bucket_unpack", "hvd_adasum_combine")

_lock = threading.Lock()
_registered = False   # guarded-by: _lock
_failed = False       # guarded-by: _lock


def build() -> Optional[str]:
    """The FFI library's path, compiled against the jaxlib headers when
    its source (or jaxlib) changed; None on failure."""
    import jax.ffi
    import jaxlib

    try:
        return build_shared(
            "hvdtpu_ffi", [SRC],
            lambda out: ["g++", "-O3", "-std=c++17", "-shared", "-fPIC",
                         f"-I{jax.ffi.include_dir()}", SRC, "-o", out],
            key=jaxlib.__version__)
    except RuntimeError as e:
        logger.warning("%s; HLO fallbacks active", e)
        return None


def ensure_registered() -> bool:
    """Build (if stale), dlopen, and register every FFI target for the
    CPU platform.  Idempotent; returns availability."""
    global _registered, _failed
    with _lock:
        if _registered:
            return True
        if _failed:
            return False
        path = build()
        if path is None:
            _failed = True
            return False
        try:
            import jax.ffi

            lib = ctypes.cdll.LoadLibrary(path)
            for name in _TARGETS:
                fn = getattr(lib, name)
                jax.ffi.register_ffi_target(
                    name, jax.ffi.pycapsule(fn), platform="cpu")
            # pack/unpack treat each leading-dim row independently, so the
            # SPMD partitioner may keep dim-0 (slot) sharding and run the
            # handler per-shard — without this, slot-sharded operands get
            # all-gathered before the custom call.  (adasum_combine is NOT
            # partitionable: its dot products are global.)
            for name in ("hvd_bucket_pack", "hvd_bucket_unpack"):
                jax.ffi.register_ffi_target_as_batch_partitionable(name)
            _registered = True
            return True
        except Exception as e:  # registration must never break the core
            logger.warning("FFI registration failed: %s", e)
            _failed = True
            return False


def available() -> bool:
    """True when the FFI library is built, loadable, and registered —
    and not disabled via ``HVD_TPU_USE_NATIVE_FFI=0``."""
    if os.environ.get("HVD_TPU_USE_NATIVE_FFI", "1") in ("0", "false"):
        return False
    return ensure_registered()


# --- callable wrappers -------------------------------------------------------

def bucket_pack(leaves: Sequence) -> "jax.Array":
    """Fuse ``[L, n_i]`` arrays into one ``[L, sum(n_i)]`` buffer via the
    native handler (one strided-memcpy pass).  Jit-safe on CPU."""
    import jax
    import jax.numpy as jnp

    leaves = [jnp.asarray(x) for x in leaves]
    rows = leaves[0].shape[0]
    total = sum(int(x.shape[1]) for x in leaves)
    out_t = jax.ShapeDtypeStruct((rows, total), leaves[0].dtype)
    return jax.ffi.ffi_call("hvd_bucket_pack", out_t)(*leaves)


def bucket_unpack(flat, cols: Sequence[int]) -> List:
    """Split one ``[L, sum(cols)]`` buffer back into ``[L, c]`` pieces."""
    import jax

    rows = flat.shape[0]
    outs = [jax.ShapeDtypeStruct((rows, int(c)), flat.dtype) for c in cols]
    res = jax.ffi.ffi_call("hvd_bucket_unpack", outs)(flat)
    return list(res)


def adasum_combine(a, b):
    """Native Adasum pairwise rule (reference: ``adasum.h`` dot/norm +
    scaled-add kernels fused into one pass); f32/f64."""
    import jax

    out_t = jax.ShapeDtypeStruct(a.shape, a.dtype)
    return jax.ffi.ffi_call("hvd_adasum_combine", out_t)(a, b)
