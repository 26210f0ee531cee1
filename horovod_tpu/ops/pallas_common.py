"""Shared plumbing for the Pallas kernel tier.

Every kernel in the tree (``ops/pallas_attention.py``,
``ops/pallas_collectives.py``) follows the same pattern from
``/opt/skills/guides/pallas_guide.md``: a grid + block specs, VMEM
scratch for carried state, and an ``interpret=`` escape hatch so the
identical kernel runs under the CPU test mesh.  This module hoists the
pieces that pattern repeats — interpret-flag resolution, block-multiple
rounding/padding, and the TPU lane constant — so new kernels thread
them instead of copy-pasting.

The hvdlint ``pallas-interpret-flag`` check (docs/lint.md) enforces the
contract these helpers exist for: every ``pl.pallas_call`` threads a
non-hardcoded ``interpret`` parameter, and the defining module exposes
it as a public keyword.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

# TPU vector lane count: scalar-per-row scratch is replicated across it
# to keep VMEM tiles well-formed ((rows, _LANES) instead of (rows,)).
_LANES = 128

# Sublane multiple: the second-to-last block dim must be a multiple of
# this (or equal to the array dim) for the TPU lowering to tile it.
_SUBLANES = 8


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """The tree-wide default for the ``interpret=`` escape hatch.  None
    means "compile for the TPU when the platform is the TPU, interpret
    anywhere else", so the same kernel runs under the CPU test mesh
    without callers passing a flag.  On the TPU the interpreter is
    therefore never chosen unless the caller passed True (a numerics-
    debug move); ``chip_smoke.py`` checks the lowered program for the
    kernel rather than trust this flag."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def round_up(value: int, multiple: int) -> int:
    """``value`` rounded up to a multiple of ``multiple`` (the pad-to-
    block-size arithmetic every padded kernel entry repeats)."""
    m = max(1, int(multiple))
    return -(-int(value) // m) * m


def pad_dim(x: jnp.ndarray, multiple: int, axis: int = 0,
            ) -> Tuple[jnp.ndarray, int]:
    """Zero-pad ``x`` along ``axis`` up to a multiple of ``multiple``;
    returns ``(padded, pad)`` so callers can slice the pad back off.
    Zero is the safe fill for every in-tree kernel: quantization blocks
    ignore it (zeros cannot raise an absmax scale) and causal attention
    masks it."""
    size = x.shape[axis]
    pad = round_up(size, multiple) - size
    if not pad:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad
