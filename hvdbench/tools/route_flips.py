"""How many token-expert pairs the program routes otherwise than the
float32 reference does, on the chip at the cell's own size: the top-k of
a hundred near-equal sigmoid scores changes for a few tokens when the
residual stream is bfloat16, and a pair that changes moves a whole
token's rows from one expert to another.  Read once, outside any
window, for what ``check.limits`` has to allow (PERF.md section 2).

    python3 hvdbench/tools/route_flips.py --workload <name> --seeds 1,2

Prints one JSON line a seed: the pairs a layer routes, how many of the
program's are not the reference's in each expert layer, and how many of
those go to or leave an expert held here."""

import argparse
import importlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def flips(config, traffic, seed: int) -> dict:
    import jax
    import jax.numpy as jnp

    from horovod_tpu.parallel import moe
    from hvdbench import generator

    family = importlib.import_module(f"hvdbench.models.{config['family']}")
    ref = importlib.import_module(f"hvdbench.reference.{config['reference']}")
    s = ref.sizes(config)
    tokens = generator.train_batch(
        traffic, seed, 0, int(config["run"]["rows_per_chip"]),
        config["vocab_size"])[0]
    model = family.build_model(config, config["run"]["attention"])

    @jax.jit
    def program(params, tokens):
        _, found = model.apply(
            {"params": params}, tokens, return_hidden=True,
            capture_intermediates=lambda m, _: m.name == "router")
        found = found["intermediates"]
        return jnp.stack([
            moe.route(jax.nn.sigmoid(found[f"block_{i}"]["experts"]["router"]
                                     ["__call__"][0]),
                      params[f"block_{i}"]["experts"]["select_bias"],
                      s["top_k"], s["scale"])[0].reshape(tokens.shape + (-1,))
            for i in ref.layers_of(s, "E")])

    def chosen(experts):             # [layers, B, T, K] -> [layers, S, E]
        return jax.nn.one_hot(experts, s["E"], dtype=bool).any(axis=-2) \
            .reshape(experts.shape[0], -1, s["E"])

    ours = chosen(program(family.make_params(config, seed), tokens))
    theirs = chosen(jax.jit(lambda k, t: ref.routing(
        ref.init_params(k, s), t, s))(ref.seed_key(seed), tokens))
    lo, n = s["held"]
    differ = ours & ~theirs
    held = (ours ^ theirs)[..., lo:lo + n]
    return {"seed": seed, "pairs_a_layer": int(ours[0].sum()),
            "differ": differ.sum(axis=(1, 2)).tolist(),
            "differ_held": held.sum(axis=(1, 2)).tolist()}


def main() -> None:
    from hvdbench import device, run

    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    args = parser.parse_args()
    _, cell, config, traffic = run.load_cell(args.workload)
    device.place_compile_cache()
    for seed in (int(x) for x in args.seeds.split(",")):
        print(json.dumps(dict(flips(config, traffic, seed),
                              workload=cell["name"])), flush=True)


if __name__ == "__main__":
    main()
