"""MFU accounting shared by the benchmarks.

One place for (a) the published per-chip peaks and (b) the AOT-compile
+ ``cost_analysis`` flops readout, so every benchmark reports a
consistent ``mfu_pct`` for the same hardware.

``cost_analysis()`` caveats (measured on this jax/XLA version):

* A ``lax.scan`` BODY IS COUNTED ONCE regardless of trip count — cost a
  length-1 chunk and scale by steps yourself (see bench.py).
* Partitioning semantics differ by lowering path: through ``shard_map``
  the count is the post-partitioning per-device module; through plain
  GSPMD jit it can be the whole-module count.  On the headline config
  (one real chip) the two coincide, which is where mfu_pct is read.

The compiled executable is returned for reuse — ``lower().compile()``
does not populate the jit dispatch cache, and compiling twice would
double benchmark startup.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple


class ChipPeaks(NamedTuple):
    bf16_tflops: float   # dense bf16 matmul peak, TFLOP/s per chip
    hbm_gbps: float      # HBM bandwidth, GB/s per chip


# Keyed by ``jax.Device.device_kind``.  Source: Google Cloud
# documentation, "TPU v5e" system architecture page — 197 TFLOP/s bf16
# and 819 GB/s HBM per chip.  JAX reports that chip as "TPU v5 lite".
# A kind that is not listed is an error, not a default: add the row
# with its source when another chip is measured.
PEAKS = {
    "TPU v5 lite": ChipPeaks(197.0, 819.0),
    "TPU v5e": ChipPeaks(197.0, 819.0),
}


def chip_peaks(device) -> ChipPeaks:
    """Published peaks of ``device`` (a jax Device); raises ``KeyError``
    for a ``device_kind`` the table does not list."""
    kind = getattr(device, "device_kind", None)
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {kind!r} "
            f"(known: {', '.join(sorted(PEAKS))}); add it to "
            "horovod_tpu.utils.mfu.PEAKS with its source") from None


def peak_tflops(device) -> float:
    """Dense bf16 peak TFLOP/s of ``device``; raises for an unknown
    kind (see :func:`chip_peaks`)."""
    return chip_peaks(device).bf16_tflops


def estimate_compute_us(flops: float, device) -> float:
    """Modeled wall time of ``flops`` at the chip's published dense-bf16
    peak — the compute term of the overlap cost model (how much backward
    time is available to hide a collective under; see
    ``ops.fusion.estimate_overlap_hidden_fraction``)."""
    return float(flops) / (peak_tflops(device) * 1e12) * 1e6


def aot_compile_with_flops(jitted, *args) -> Tuple[Any, Optional[float]]:
    """AOT-compile ``jitted(*args)``; returns ``(compiled, flops)`` with
    ``flops`` the per-device flops of one call, or None where the
    backend's cost analysis reports none.  A failed compile raises."""
    compiled = jitted.lower(*args).compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    return compiled, (float((cost or {}).get("flops", 0.0)) or None)
