"""The grouped product of an expert layer at a stated load, timed on the
chip at a few row tiles: ``rows`` sorted pairs spread evenly over
``groups`` experts of ``d_in x d_out``, through ``parallel/moe.py::
grouped_dot``'s kernel (``megablox.gmm``) at each tile in ``--tiles``.
A tile of 512 rows visits ``rows / 512 + groups`` (tile, group) pairs
of 512 rows each, so where a group holds a dozen rows most of a visit
multiplies masked rows; this says what that costs.

    python3 hvdbench/tools/grouped_dot_tiles.py --rows 1536 --groups 128 --d-in 2048 --d-out 768 --tiles 512,256,128
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> None:
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from hvdbench import device
    from horovod_tpu.parallel import moe

    parser = argparse.ArgumentParser()
    parser.add_argument("--rows", type=int, default=1536)
    parser.add_argument("--groups", type=int, default=128)
    parser.add_argument("--d-in", type=int, default=2048)
    parser.add_argument("--d-out", type=int, default=768)
    parser.add_argument("--tiles", default="512,256,128")
    parser.add_argument("--calls", type=int, default=50)
    args = parser.parse_args()
    device.require_chips(1, False)
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (args.rows, args.d_in), jnp.bfloat16)
    w = jax.random.normal(key, (args.groups, args.d_in, args.d_out),
                          jnp.bfloat16)
    sizes = jnp.full((args.groups,), args.rows // args.groups, jnp.int32)
    sizes = sizes.at[0].add(args.rows - int(sizes.sum()))
    for tm in (int(t) for t in args.tiles.split(",")):
        fn = jax.jit(lambda x, w, s, tm=tm: gmm(
            x, w, s, jnp.bfloat16, (tm,) + moe.GMM_TILES[1:]))
        jax.block_until_ready(fn(x, w, sizes))
        t = time.monotonic()
        for _ in range(args.calls):
            out = fn(x, w, sizes)
        jax.block_until_ready(out)
        ms = (time.monotonic() - t) / args.calls * 1e3
        print(json.dumps({
            "tile_rows": tm, "ms_a_call": ms,
            "weights_gb_per_s": w.size * 2 / ms / 1e6,
            "rows": args.rows, "groups": args.groups,
            "shape": [args.d_in, args.d_out]}), flush=True)


if __name__ == "__main__":
    main()
