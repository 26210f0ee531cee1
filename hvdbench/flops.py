"""Operations and bytes from shapes, and the table of peaks.

What the algorithm needs, not what a compiler counted: recomputed
operations do not count, and ``cost_analysis()`` (ambiguous under scan
and GSPMD) is not consulted.
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """Published peaks of a device kind; an unknown kind raises."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["peaks"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} (known: {sorted(table)}); add it "
                       f"to hvdbench/peaks.json with its source")
    return table[device_kind]


def matmul_params(config: dict) -> int:
    """Parameters that sit in matrix multiplications: the blocks' four
    projections and the output head (untied here).  No embedding or
    position look-up, no LayerNorm."""
    d, L = int(config["n_embd"]), int(config["n_layer"])
    ff = int(config.get("n_inner") or 4 * d)
    per_layer = d * 3 * d + d * d + d * ff + ff * d
    return L * per_layer + d * int(config["vocab_size"])


def total_params(config: dict) -> int:
    """Every parameter of the model as the program holds it: untied
    head, LayerNorm scale and bias, no linear biases."""
    d, L = int(config["n_embd"]), int(config["n_layer"])
    V, P = int(config["vocab_size"]), int(config["n_positions"])
    return matmul_params(config) + V * d + P * d + L * 4 * d + 2 * d


def train_flops_per_token(config: dict, seq_len: int) -> float:
    """Forward and backward: 6 x the matmul parameters, plus causal
    attention's 6 * L * T * d (QK^T and PV, 2 * 2 * T * d a token
    forward, halved by the causal mask, times three for the backward)."""
    d, L = int(config["n_embd"]), int(config["n_layer"])
    return 6.0 * matmul_params(config) + 6.0 * L * seq_len * d


def flash_fwd_cost(batch: int, heads: int, seq_len: int, head_dim: int,
                   itemsize: int = 2) -> dict:
    """One causal flash-attention forward call: operations (QK^T and
    PV over the lower triangle) and the bytes it has to move (q, k, v in,
    o out, once each)."""
    flops = 2 * 2 * batch * heads * seq_len * seq_len * head_dim / 2
    nbytes = 4 * batch * heads * seq_len * head_dim * itemsize
    return {"flops": float(flops), "bytes": float(nbytes)}


def roofline_share(cost: dict, seconds: float, device_kind: str) -> dict:
    """Least time the chip could take over measured time, in percent,
    and which bound it is."""
    pk = peaks(device_kind)
    t_flops = cost["flops"] / pk["bf16_flops_per_s"]
    t_bytes = cost["bytes"] / pk["hbm_bytes_per_s"]
    return {"percent": 100.0 * max(t_flops, t_bytes) / seconds,
            "bound": "compute" if t_flops >= t_bytes else "memory"}
