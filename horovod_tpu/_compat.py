"""The few JAX spellings the tree routes through one place.

Written for the one installed JAX (0.9.0, pinned in ``pyproject.toml``):
no version branches.  What is left is house defaults (``shard_map``
without replication checking), a multi-axis ``axis_size``, and two
small helpers whose home would otherwise be arbitrary.
"""

from __future__ import annotations

import jax
import numpy as np
from jax import lax

enable_x64 = jax.enable_x64


def shard_map(f, *, mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map`` with the tree's default: varying-manual-axes
    checking off (the collective bodies mix replicated and per-slot
    values on purpose)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


def axis_size(axis) -> int:
    """Static width of a named mesh axis inside an SPMD region.  A
    tuple of names (a multi-axis MeshPlan's reduce wire) is the product
    of the per-name widths."""
    if isinstance(axis, (tuple, list)):
        n = 1
        for a in axis:
            n *= lax.axis_size(a)
        return n
    return lax.axis_size(axis)


def sanitize_checkpoint_tree(tree):
    """Normalize a pytree for orbax's ``StandardSave``, which accepts
    only ``int``/``float``/``np.ndarray``/``jax.Array`` leaves: numpy
    *scalars* (``np.int64(7)`` — the idiomatic step counter) fail its
    type check.  Wrap them as 0-d ndarrays, which round-trip with dtype
    intact; everything else passes through."""
    def fix(leaf):
        if isinstance(leaf, np.generic):
            return np.asarray(leaf)
        return leaf

    return jax.tree.map(fix, tree)


def is_tracer(x) -> bool:
    """True when ``x`` is a JAX tracer (i.e. we are inside a trace)."""
    return isinstance(x, jax.core.Tracer)
