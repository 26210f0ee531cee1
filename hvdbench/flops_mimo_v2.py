"""Operations and bytes of the ``mimo_v2`` family's serving step, from
shapes: what the algorithm needs, not what a compiler counted.  ``s`` is
``reference.mimo_v2.sizes(config)``: this chip's share of the model.

* **Attention of a decode step** reads, for every row with a request,
  the key row and the value row of each position the layer sees — every
  position so far in a full layer, the last ``window`` in a window
  layer — once each, in the pool's dtype; the query and the output are
  small beside them and are counted.  Padding of a block's unused rows
  is the program's cost and not the algorithm's.
* **The expert layer of a decode step** reads the three matrices of
  each held expert that was sent a pair, once however many rows use it,
  and a row in and a row out for each pair.
* **A served token** needs the matrix products of every layer held
  here (the projections, the feed-forward or the held experts' expected
  share ``top_k x held / experts`` of a pair, the router, the head) and
  the attention's two products over the context it sees.
"""

from __future__ import annotations


def _layers(s: dict, kind: str) -> int:
    return sum(1 for w in s["pattern"] if bool(w) == (kind == "window"))


def kv_row_bytes(s: dict, kind: str, itemsize: int = 2) -> int:
    """Bytes of one position's key row and value row in a layer."""
    return s["K_" + kind] * (s["D"] + s["Dv"]) * itemsize


def decode_attention_cost(s: dict, positions_full: float,
                          positions_window: float, rows: float,
                          itemsize: int = 2) -> dict:
    """One decode step's attention over all layers: ``positions_<kind>``
    positions read by a layer of the kind (summed over the rows with a
    request), ``rows`` of them."""
    nbytes = flops = 0.0
    for kind, positions in (("full", positions_full),
                            ("window", positions_window)):
        n = _layers(s, kind)
        nbytes += n * (positions * kv_row_bytes(s, kind, itemsize)
                       + rows * s["H"] * (s["D"] + s["Dv"]) * itemsize)
        flops += n * positions * 2 * s["H"] * (s["D"] + s["Dv"])
    return {"flops": flops, "bytes": nbytes}


def expert_bytes(s: dict, itemsize: int = 2) -> int:
    """One expert's gate, up and down."""
    return 3 * s["d"] * s["eff"] * itemsize


def decode_experts_cost(s: dict, touched: float, pairs: float,
                        itemsize: int = 2) -> dict:
    """One decode step's grouped products over all expert layers:
    ``touched`` held experts read (summed over the layers), ``pairs``
    token-expert pairs computed."""
    return {"flops": pairs * 3 * 2 * s["d"] * s["eff"],
            "bytes": (touched * expert_bytes(s, itemsize)
                      + pairs * 2 * s["d"] * itemsize)}


def serve_flops_per_token(s: dict, context: float) -> float:
    """Operations one token needs on this chip's share, at a context of
    ``context`` positions: 2 x the parameters it meets in matrix
    products, and the scores and read-out over what each layer sees."""
    d, H, D, Dv = s["d"], s["H"], s["D"], s["Dv"]
    total = 2.0 * d * s["V"]                                  # the head
    for window, moe in zip(s["pattern"], s["moe"]):
        K = s["K_window" if window else "K_full"]
        total += 2.0 * d * (H * D + K * D + K * Dv) + 2.0 * H * Dv * d
        seen = min(context, s["window"]) if window else context
        total += 2.0 * H * (D + Dv) * seen
        if moe:
            share = s["top_k"] * s["held"][1] / s["E"]
            total += 2.0 * d * s["E"] + share * 3 * 2.0 * d * s["eff"]
        else:
            total += 3 * 2.0 * d * s["ff"]
    return total
