"""Operations and bytes of the ``sdar`` family's block step, from
shapes: what the algorithm needs, not what a compiler counted or a
kernel's layout reads.  ``s`` is ``reference.sdar.sizes(config)``: one
pipeline stage of the model, every expert of each of its layers held.

* **Attention of a block step** reads, for every row with a request,
  the key row and the value row of each position the block sees —
  everything up to its own end — once each, in the pool's dtype, for
  all ``B`` queries of the row together; the ``B x H`` queries and
  outputs are small beside them and are counted.  The scores and the
  read-out are ``B`` queries' worth.
* **The expert layer of a block step** reads the three matrices of
  each expert that was sent a pair, once however many pairs use it, and
  a row in and a row out for each pair.
* **A served token** needs, on this stage, the matrix products of
  every layer (the projections, the router, ``top_k`` experts), the
  attention's two products over the context it sees and the head —
  once for every forward its block is given, so ``forwards a token`` =
  forwards a block over the block's length.
"""

from __future__ import annotations


def kv_row_bytes(s: dict, itemsize: int = 2) -> int:
    """Bytes of one position's key row and value row in a layer."""
    return s["K"] * 2 * s["D"] * itemsize


def block_attention_cost(s: dict, positions: float, rows: float,
                         itemsize: int = 2) -> dict:
    """One block step's attention over all layers: ``positions``
    positions seen (each row's block end, summed over the rows with a
    request), ``rows`` of them, each bringing ``B`` queries."""
    B, H, D, L = s["B"], s["H"], s["D"], s["L"]
    return {"flops": L * positions * B * 2 * H * 2 * D,
            "bytes": L * (positions * kv_row_bytes(s, itemsize)
                          + rows * B * H * 2 * D * itemsize)}


def expert_bytes(s: dict, itemsize: int = 2) -> int:
    """One expert's gate, up and down."""
    return 3 * s["d"] * s["eff"] * itemsize


def block_experts_cost(s: dict, touched: float, pairs: float,
                       itemsize: int = 2) -> dict:
    """One block step's grouped products over all layers: ``touched``
    experts read (summed over the layers), ``pairs`` position-expert
    pairs computed."""
    return {"flops": pairs * 3 * 2 * s["d"] * s["eff"],
            "bytes": (touched * expert_bytes(s, itemsize)
                      + pairs * 2 * s["d"] * itemsize)}


def forward_flops_per_position(s: dict, context: float) -> float:
    """Operations one position of one forward needs on this stage, at
    a context of ``context`` positions: 2 x the parameters it meets in
    matrix products, the scores and read-out over what it sees, the
    head."""
    d, H, K, D = s["d"], s["H"], s["K"], s["D"]
    layer = (2.0 * d * (H * D + 2 * K * D) + 2.0 * H * D * d
             + 2.0 * H * 2 * D * context
             + 2.0 * d * s["E"] + s["top_k"] * 3 * 2.0 * d * s["eff"])
    return s["L"] * layer + 2.0 * d * s["V"]


def serve_flops_per_token(s: dict, context: float,
                          forwards_a_block: float) -> float:
    """Operations a served token needs: its position's, in every
    forward its block was given."""
    return forwards_a_block * forward_flops_per_position(s, context)
