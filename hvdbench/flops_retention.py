"""Operations and bytes of power retention's decode step, from shapes:
what the algorithm needs, not what a compiler counted.

A decode step reads and rewrites the state of every slot it touches: per
slot, layer and KV head the ``d (d + 1) / 2`` distinct products of the
symmetric degree-2 feature map, each with a value vector of ``d`` in
``S`` and one number in ``z``, float32.  That is the packed triangle
(8,256 features for ``d`` = 128); the program lays its features out by
offset and holds 0.8 % more (``horovod_tpu/ops/retention.py``), which
is its own cost and not the algorithm's.  Beside that it
takes the step's q, k, v (bfloat16) and gate (float32) and gives the
heads' outputs."""

from __future__ import annotations


def features(head_dim: int) -> int:
    """Distinct products ``x_i x_j``, ``i <= j``."""
    return head_dim * (head_dim + 1) // 2


def state_bytes(slots: int, layers: int, kv_heads: int, head_dim: int,
                itemsize: int = 4) -> float:
    """Bytes of the ``(S, z)`` pairs of ``slots`` slots."""
    return float(slots * layers * kv_heads * features(head_dim)
                 * (head_dim + 1) * itemsize)


def decode_cost(slots_touched: int, layers: int, heads: int, kv_heads: int,
                head_dim: int) -> dict:
    """One decode step's retention over all layers: the state read and
    written once each for every slot touched, the small operands once,
    and the arithmetic of the update and the read-out."""
    n = features(head_dim)
    group = heads // kv_heads
    per_head = slots_touched * layers * kv_heads
    nbytes = (2 * state_bytes(slots_touched, layers, kv_heads, head_dim)
              + slots_touched * layers * (
                  (heads + 2 * kv_heads) * head_dim * 2    # q, k, v
                  + kv_heads * 4                           # the gate
                  + heads * head_dim * 2))                 # the outputs
    flops = per_head * (
        3 * n * head_dim                 # S = g S + phi(k) v^T
        + 2 * n                          # z = g z + phi(k)
        + group * 2 * n * head_dim       # phi(q)^T S
        + group * 2 * n                  # phi(q) . z
        + (1 + group) * 2 * n)           # phi(k), phi(q)
    return {"flops": float(flops), "bytes": float(nbytes)}
