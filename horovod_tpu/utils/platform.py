"""Process-level platform choices for scripts, benchmarks and the chip
smoke run: which backend a run is allowed to use, and where its
compiled programs are kept.

JAX takes the TPU by default and falls back to the CPU where there is
none, silently.  A measurement must not: entry points whose numbers are
device numbers call :func:`require_tpu` after ``hvd.init()``, and the
CPU is used only where a flag asks for it by name (``--preset tiny``,
``--cpu-mesh`` → :func:`force_cpu_mesh`).
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def force_cpu_mesh(n_devices: int = 8) -> None:
    """Pin this process to the CPU backend with ``n_devices`` virtual
    devices.  Call before any jax device use (backend init)."""
    flag = "--xla_force_host_platform_device_count"
    if flag not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + f" {flag}={n_devices}")
    import jax

    jax.config.update("jax_platforms", "cpu")


def require_tpu():
    """The first device, which must be a TPU chip: a run whose output
    is read as a device result raises here when JAX found no
    accelerator (or was pinned to the CPU) — it never carries on."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"this run needs a TPU but JAX selected platform "
            f"{dev.platform!r} ({dev.device_kind}); the CPU path exists "
            "only behind --preset tiny / --cpu-mesh")
    return dev


def device_record(devices=None) -> dict:
    """How a result names the device it ran on: platform, kind and
    count as JAX reports them (``jax.devices()`` unless given)."""
    if devices is None:
        import jax

        devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def place_compile_cache() -> str:
    """Directory of JAX's persistent compilation cache for this run.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already uses it and
    nothing is touched.  Otherwise the cache goes to ``.jax_cache`` in
    the checkout — a fixed path, because the path is part of the cache
    key and a directory that moves never hits.  Call before the first
    compile."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
