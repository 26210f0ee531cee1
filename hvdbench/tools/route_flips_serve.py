"""How many token-expert pairs a *served* model's program routes
otherwise than the float32 reference does, on the chip at the cell's
own widths: one seeded sequence as long as the largest prefill bucket
through the program's forward (bfloat16 residual stream, float32
router) and through the reference; the top 8 of 256 near-equal sigmoid
scores change for a few tokens, and a pair that changes moves a token's
row from one expert to another.  Read once, outside any window, for
what ``check.limits`` has to allow (PERF.md section 2).

    python3 hvdbench/tools/route_flips_serve.py --workload <name> --seeds 1,2

Prints one JSON line a seed: the pairs a layer routes, how many of the
program's are not the reference's in each expert layer, and how many of
those go to or leave an expert held here.  (``route_flips.py`` does the
same for a training cell's batch.)"""

import argparse
import importlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def flips(config, seed: int) -> dict:
    import jax
    import jax.numpy as jnp

    from horovod_tpu.parallel import moe
    from hvdbench import generator

    family = importlib.import_module(f"hvdbench.models.{config['family']}")
    ref = importlib.import_module(f"hvdbench.reference.{config['reference']}")
    s = ref.sizes(config)
    length = max(config["run"]["engine"]["prefill_buckets"])
    tokens = jnp.asarray(generator.warmup_prompts(
        [length], seed, config["vocab_size"]), jnp.int32)
    model = family.build_model(config, config["run"]["attention"])
    layers = [i for i, m in enumerate(s["moe"]) if m]

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
            for i in layers])

    def chosen(experts):             # [layers, B, T, K] -> [layers, S, E]
        return jax.device_get(
            jax.nn.one_hot(experts, s["E"], dtype=bool).any(axis=-2)
            .reshape(experts.shape[0], -1, s["E"]))

    params = family.make_params(config, seed)
    ours = chosen(program(params, tokens))
    del params              # the reference streams 2 GB a layer
    theirs = chosen(ref.routing(ref.seed_key(seed), tokens, s))
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
    _, cell, config, _ = run.load_cell(args.workload)
    device.place_compile_cache()
    for seed in (int(x) for x in args.seeds.split(",")):
        print(json.dumps(dict(flips(config, seed), workload=cell["name"])),
              flush=True)


if __name__ == "__main__":
    main()
