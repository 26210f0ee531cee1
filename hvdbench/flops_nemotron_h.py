"""Operations and bytes of the ``nemotron_h`` family, from shapes: what
the algorithm needs, not what a compiler counted.  Recomputed
operations (this configuration recomputes each layer in the backward
pass) do not count.

``s`` is ``reference.nemotron_h.sizes(config)``.
"""

from __future__ import annotations


def matmul_params_met(s: dict) -> dict:
    """Parameters in matrix products that ONE token meets, by part.
    The routed experts count at the expectation under even routing: a
    token sends ``top_k`` pairs over ``E`` experts of which ``held``
    are here, so it meets ``top_k * held / E`` experts' kernels."""
    d = s["d"]
    inner, bc = s["mh"] * s["mp"], 2 * s["mg"] * s["mn"]
    n = {c: s["pattern"].count(c) for c in "ME*"}
    return {
        "scan_projections": n["M"] * (d * (2 * inner + bc + s["mh"])
                                      + inner * d),
        "attention_projections": n["*"] * (
            d * (s["H"] + 2 * s["K"]) * s["D"] + s["H"] * s["D"] * d),
        "router": n["E"] * d * s["E"],
        "shared_expert": n["E"] * 2 * d * s["shared_ff"],
        "routed_experts": n["E"] * (s["top_k"] * s["held"][1] / s["E"])
        * 2 * d * s["ff"],
        "head": d * s["V"],
    }


def scan_flops_per_token(s: dict) -> float:
    """One scan layer's own arithmetic for one token, forward, by the
    chunked algorithm at chunk ``Q``: inside a chunk the causal half of
    the ``C_i . B_j`` scores (a group) and of the weighted sum of inputs
    (a head), on average ``(Q + 1) / 2`` earlier tokens at two
    operations each; the token's part of the state its chunk leaves and
    its read-out of the state before the chunk, ``2 P N`` a head each;
    the pass between chunks, ``2 P N`` a head and chunk."""
    Q, HP = s["chunk"], s["mh"] * s["mp"]
    return ((Q + 1) * (s["mg"] * s["mn"] + HP) + 4 * HP * s["mn"]
            + 2 * HP * s["mn"] / Q)


def conv_flops_per_token(s: dict) -> float:
    return 2.0 * s["taps"] * (s["mh"] * s["mp"] + 2 * s["mg"] * s["mn"])


def train_flops_per_token(s: dict, seq_len: int) -> float:
    """Forward and backward: 6 x the matmul parameters a token meets,
    causal attention's ``6 T H D`` a layer (QK^T and PV over the lower
    triangle, times three for the backward), and three times the scan's
    and the convolution's own forward arithmetic."""
    n = {c: s["pattern"].count(c) for c in "M*"}
    return (6.0 * sum(matmul_params_met(s).values())
            + 6.0 * n["*"] * seq_len * s["H"] * s["D"]
            + 3.0 * n["M"] * (scan_flops_per_token(s)
                              + conv_flops_per_token(s)))


def scan_cost(s: dict, tokens: int, itemsize: int = 2) -> dict:
    """All scan layers of one train step, the scan alone: three times
    the forward arithmetic, and the bytes the chunked algorithm has to
    move — x, B, C (``itemsize``) and dt (float32) in, y out, one
    float32 state a chunk written and read — forward, and twice that
    backward (the same operands and their cotangents).  The same count
    whatever implements the scan."""
    n = s["pattern"].count("M")
    HP, GN = s["mh"] * s["mp"], s["mg"] * s["mn"]
    fwd_bytes = (tokens * ((2 * HP + 2 * GN) * itemsize + s["mh"] * 4)
                 + 2 * (tokens / s["chunk"]) * HP * s["mn"] * 4)
    return {"flops": 3.0 * n * tokens * scan_flops_per_token(s),
            "bytes": 3.0 * n * fwd_bytes}


def experts_cost(s: dict, pairs_held, itemsize: int = 2) -> dict:
    """The grouped products over the held experts of one train step:
    ``pairs_held`` is the token-expert pairs held here, one count per
    expert layer, as the run's own router sent them.  A pair costs
    ``2 d ff`` up and as much down, times three for the backward; the
    bytes are the held experts' kernels and a pair's rows (in, hidden
    written and read, out), forward, and twice that backward."""
    d, ff, held = s["d"], s["ff"], s["held"][1]
    pairs = float(sum(pairs_held))
    layers = len(pairs_held)
    fwd_bytes = (layers * held * 2 * d * ff
                 + pairs * (2 * d + 2 * ff)) * itemsize
    return {"flops": 6.0 * pairs * 2 * d * ff, "bytes": 3.0 * fwd_bytes}
