"""TF-XLA adapter loader: collectives inside ``jit_compile=True``.

Reference: ``horovod/tensorflow/xla_mpi_ops.cc`` (SURVEY.md §2.3 — the
"highest-leverage file for the TPU port"; mount empty, unverified): an
XLA custom call re-entering the collective core so XLA-compiled TF
graphs keep their allreduces.  Scope there: allreduce only, XLA:GPU
only.  Scope here: allreduce (dense), every TF execution tier.

Mechanics (see ``native/src/tf_xla_ops.cc``): one custom TF op,
``HvdTpuAllreduce``, with a plain CPU kernel and an XLA kernel that
lowers to a host CustomCall registered in TF's own XLA runtime —
libtensorflow_cc.so exports ``xla::CustomCallTargetRegistry`` and the
tf2xla op registry, so the adapter builds against the pip package's
bundled headers (``tf.sysconfig``).  Both kernels re-enter Python and
run the SAME host-binding closure the py_function bridge would, keyed
through a trace-time closure table; the opaque payload carries only
``(key, dtype, dims)``.

Build is lazy and content-cached like the rest of the native tier
(``native/build.py``); any
failure (no g++, header drift) degrades to ``available() == False``
and the py_function bridge keeps working — only jit_compile support is
lost, with the pinned error naming this module.
"""

from __future__ import annotations

import ctypes
import itertools
import os
import threading
from typing import Callable, Dict, Optional, Tuple

import numpy as np

from ..utils.logging import get_logger

logger = get_logger(__name__)

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(os.path.dirname(_HERE), "native", "src", "tf_xla_ops.cc")

_lock = threading.Lock()
_lib = None          # guarded-by: _lock (tf.load_op_library module)
_load_error: Optional[str] = None   # guarded-by: _lock

# Trace-time closure table: table_key -> fn(np_in) -> np_out.  Keys are
# allocated per op emission; entries live as long as the process (they
# are tiny closures; graphs that re-trace allocate fresh keys).
_table: Dict[int, Callable[[np.ndarray], np.ndarray]] = {}
_keys = itertools.count()

# TF DataType enum value -> numpy dtype (bfloat16/half via ml_dtypes /
# np.float16; values are the stable proto enum).
_DT_TO_NP: Dict[int, np.dtype] = {}


def _dt_map():
    if _DT_TO_NP:
        return _DT_TO_NP
    import ml_dtypes

    _DT_TO_NP.update({
        1: np.dtype(np.float32),
        2: np.dtype(np.float64),
        3: np.dtype(np.int32),
        9: np.dtype(np.int64),
        14: np.dtype(ml_dtypes.bfloat16),
        19: np.dtype(np.float16),
    })
    return _DT_TO_NP


def _trampoline(key: int, dtype_enum: int, dims: Tuple[int, ...],
                in_ptr: int, out_ptr: int) -> None:
    """Called from the C++ kernels (GIL held): run the table closure on
    a view of the input buffer and write the result into the output."""
    fn = _table[key]
    dt = _dt_map()[dtype_enum]
    n = int(np.prod(dims)) if dims else 1
    nbytes = n * dt.itemsize
    in_buf = (ctypes.c_char * nbytes).from_address(in_ptr)
    x = np.frombuffer(in_buf, dtype=dt, count=n).reshape(dims).copy()
    out = np.ascontiguousarray(np.asarray(fn(x), dtype=dt)).reshape(dims)
    out_buf = (ctypes.c_char * nbytes).from_address(out_ptr)
    out_buf[:] = out.tobytes()


def _build() -> str:
    import tensorflow as tf

    from ..native.build import build_shared

    py_inc = __import__("sysconfig").get_paths()["include"]
    tf_inc = tf.sysconfig.get_include()
    flags = ([f"-I{py_inc}",
              # Bazel-vendored third-party headers referenced by TF's own
              # public headers resolve under include/external/*.
              f"-I{os.path.join(tf_inc, 'external', 'highwayhash')}",
              f"-I{os.path.join(tf_inc, 'external', 'com_google_highway')}",
              f"-I{os.path.join(tf_inc, 'external', 'farmhash_archive', 'src')}"]
             + tf.sysconfig.get_compile_flags()
             + tf.sysconfig.get_link_flags()
             + ["-l:libtensorflow_cc.so.2"])
    return build_shared(
        "hvdtpu_tf_xla", [_SRC],
        lambda out: ["g++", "-O2", "-shared", "-fPIC", _SRC, "-o", out,
                     *flags],
        key=tf.__version__, timeout=600)


def _ensure_loaded():
    global _lib, _load_error
    with _lock:
        if _lib is not None or _load_error is not None:
            return
        try:
            import tensorflow as tf

            so = _build()
            _lib = tf.load_op_library(so)
            cdll = ctypes.CDLL(so)
            cdll.HvdTpuTfXlaSetCallback.argtypes = [ctypes.py_object]
            cdll.HvdTpuTfXlaSetCallback.restype = None
            cdll.HvdTpuTfXlaSetCallback(_trampoline)
            logger.info("TF-XLA adapter loaded (%s)", os.path.basename(so))
        except Exception as e:  # degrade to the py_function tier
            _load_error = f"{type(e).__name__}: {e}"
            logger.info("TF-XLA adapter unavailable: %s", _load_error)


def preload() -> None:
    """Load the adapter NOW.  Called at ``horovod_tpu.tensorflow``
    import time: TF finalizes its XLA compilation-kernel registry at
    the FIRST XLA compile in the process, and ops registered after
    that never become jit_compile-visible — so the op library must be
    in the process before any ``jit_compile=True`` trace.  Importing
    ``horovod_tpu.tensorflow`` before compiling is the documented
    contract (``docs/migration.md``)."""
    _ensure_loaded()


def available() -> bool:
    _ensure_loaded()
    return _lib is not None


def load_error() -> Optional[str]:
    _ensure_loaded()
    return _load_error


def supported_dtype(tf_dtype) -> bool:
    import tensorflow as tf

    return tf_dtype in (tf.float32, tf.float64, tf.int32, tf.int64,
                        tf.bfloat16, tf.float16)


def allreduce(tensor, fn: Callable[[np.ndarray], np.ndarray], name: str):
    """Emit the native allreduce op running ``fn`` on the host tensor.

    ``fn(np_in) -> np_out`` is the same closure the py_function bridge
    would run (op/process-set/compression/scale baked in).  Works in
    eager, graph, and ``jit_compile=True`` tiers.
    """
    _ensure_loaded()
    if _lib is None:
        raise RuntimeError(f"TF-XLA adapter unavailable: {_load_error}")
    key = next(_keys)
    _table[key] = fn
    return _lib.hvd_tpu_allreduce(tensor=tensor, table_key=key)
