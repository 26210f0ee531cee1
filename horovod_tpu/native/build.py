"""Build driver for the native libraries.

Reference analogue: the CMake/setup.py machinery that produces
``libhorovod`` once per framework ABI (SURVEY.md §2.7, mount empty,
unverified).  Here every library is one ``g++`` invocation over
``src/``, run lazily on first use.  No shared object is committed: a
checkout (or a copy of one, which keeps no file times) builds from
source, so staleness is decided by source *content* — the library is
named after a digest of its inputs, and a name that exists is fresh.
``python -m horovod_tpu.native.build`` builds the runtime library
eagerly (the packaging hook calls this at wheel build time).
"""

from __future__ import annotations

import glob
import hashlib
import os
import subprocess
import tempfile
from typing import Callable, List, Optional, Sequence

from ..utils.logging import get_logger

logger = get_logger(__name__)

_HERE = os.path.dirname(os.path.abspath(__file__))
SRC_DIR = os.path.join(_HERE, "src")
_TMP_SUFFIX = ".tmp.so"   # matches the ``*.so`` ignore rule if orphaned


def build_shared(stem: str, deps: Sequence[str],
                 cmd_for: Callable[[str], List[str]], *,
                 key: str = "", timeout: float = 300.0) -> str:
    """Path of ``lib<stem>.<digest>.so``, compiled unless it exists.

    ``cmd_for(out_path)`` returns the compiler argv; ``deps`` are the
    files whose bytes decide staleness and ``key`` folds in anything
    else the binary depends on (a header package's version).  The
    compiler writes a temporary name that is renamed into place, so
    processes that build at once (the multi-process tests) each see a
    whole library or none.  Raises ``RuntimeError`` when the compiler is
    missing or fails."""
    digest = hashlib.sha256(key.encode())
    for path in sorted(deps):
        digest.update(os.path.basename(path).encode() + b"\0")
        with open(path, "rb") as f:
            digest.update(f.read())
    out = os.path.join(_HERE, f"lib{stem}.{digest.hexdigest()[:16]}.so")
    if os.path.exists(out):
        return out
    fd, tmp = tempfile.mkstemp(dir=_HERE, prefix=f"lib{stem}.",
                               suffix=_TMP_SUFFIX)
    os.close(fd)
    try:
        proc = subprocess.run(cmd_for(tmp), capture_output=True,
                              timeout=timeout)
        if proc.returncode != 0:
            raise RuntimeError(
                f"lib{stem} build failed (rc={proc.returncode}): "
                f"{proc.stderr.decode(errors='replace')[-800:]}")
        os.replace(tmp, out)
    except (subprocess.SubprocessError, OSError) as e:
        raise RuntimeError(f"lib{stem} build failed: {e}") from e
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    # Libraries of earlier source states are dead weight in a dev tree.
    for old in glob.glob(os.path.join(_HERE, f"lib{stem}.*.so")):
        if old != out and not old.endswith(_TMP_SUFFIX):
            try:
                os.unlink(old)
            except OSError:
                pass
    return out


def sources() -> List[str]:
    # ffi_ops.cc is the XLA FFI library (C++17 + jaxlib headers) and
    # tf_xla_ops.cc is the TF-XLA adapter (TF headers + libtensorflow);
    # both have their own toolchain contracts and builders
    # (native/ffi.py, tensorflow/xla_ops.py).
    return sorted(p for p in glob.glob(os.path.join(SRC_DIR, "*.cc"))
                  if not p.endswith(("ffi_ops.cc", "tf_xla_ops.cc")))


def build() -> Optional[str]:
    """The runtime library's path, compiling it if its sources changed;
    None on failure (every consumer has a pure-Python fallback, so the
    failure is a warning, not an error)."""
    try:
        return build_shared(
            "hvdtpu_native",
            sources() + glob.glob(os.path.join(SRC_DIR, "*.h")),
            lambda out: ["g++", "-O2", "-std=c++14", "-shared", "-fPIC",
                         *sources(), "-o", out, "-lpthread"])
    except RuntimeError as e:
        logger.warning("%s; python fallbacks active", e)
        return None


if __name__ == "__main__":
    path = build()
    print(path or "BUILD FAILED")
    raise SystemExit(0 if path else 1)
