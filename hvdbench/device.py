"""The device as the benchmark sees it: which one a run is on, where
compiled programs are kept, how many programs were built, peak memory,
and the profiler."""

from __future__ import annotations

import glob
import os
import shutil

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# Where a run leaves what it builds; listed in .gitignore.
OUT_DIR = os.path.join(ROOT, "hvdbench_out")

_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def place_compile_cache() -> str:
    """JAX's persistent compilation cache: where
    ``JAX_COMPILATION_CACHE_DIR`` points, else ``.jax_cache`` in the
    checkout (the same rule as the program's
    ``utils/platform.py::place_compile_cache``, so both name one
    directory).  A fixed path: the path is part of the cache's key."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    # Small programs too: whatever is not cached compiles in every run.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def require_chips(chips: int, rehearsal: bool = False):
    """The devices of this run: exactly ``chips`` TPU chips.  Anything
    else raises, so that no result is printed.  ``rehearsal`` (the
    tests' CPU run, never the command line) accepts whatever JAX has."""
    import jax

    devices = jax.devices()
    if rehearsal:
        return devices
    if devices[0].platform != "tpu":
        raise RuntimeError(
            f"this benchmark measures a TPU and JAX selected platform "
            f"{devices[0].platform!r} ({devices[0].device_kind})")
    if len(devices) != chips:
        raise RuntimeError(f"the cell asks for {chips} chip(s) and JAX "
                           f"reports {len(devices)}")
    return devices


class CompileCounter:
    """Counts the programs JAX built (compiled, or loaded from the
    persistent cache) since it was made: every jit cache miss ends in
    one ``backend_compile`` event."""

    def __init__(self):
        from jax._src import monitoring

        self.count = 0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kwargs) -> None:
        if event == _BACKEND_COMPILE_EVENT:
            self.count += 1


def memory_record(devices) -> dict:
    """Peak memory of the fullest chip as the runtime counts it: live
    arrays (``peak_bytes_in_use``) and, apart from them where the
    backend reports it, what compiled programs reserved."""
    best = {"peak_bytes_in_use": 0, "peak_bytes_reserved": 0,
            "bytes_limit": 0}
    for dev in devices:
        stats = dev.memory_stats() or {}
        if stats.get("peak_bytes_in_use", 0) >= best["peak_bytes_in_use"]:
            best = {k: int(stats.get(k, 0)) for k in best}
    return best


def device_record(devices, memory: dict) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": memory["peak_bytes_in_use"]}


def start_trace(name: str) -> str:
    """Start the profiler into a directory of this cell's own, emptied
    first.  The Python tracer is off: it would bury the trace."""
    import jax

    path = os.path.join(OUT_DIR, "trace", name)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path, exist_ok=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(path, profiler_options=options)
    return path


def stop_trace(path: str) -> str:
    """Stop the profiler; returns the ``.xplane.pb`` it wrote."""
    import jax

    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(path, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {path}, "
                           f"found {found}")
    return found[0]
