"""Adasum-on-ResNet-50 benchmark — BASELINE.json config #5.

The driver's baseline list names "Adasum gradient aggregation
(op=hvd.Adasum) on ResNet-50" (SURVEY.md §6; reference vehicle:
``pytorch_synthetic_benchmark.py`` with ``op=hvd.Adasum``).  Same
methodology as ``bench.py`` but the gradient combiner is the explicit
``hvd.make_train_step(..., op=hvd.Adasum)`` path — the scale-invariant
pairwise projection rule of ``ops/adasum.py`` — instead of the implicit
GSPMD batch-gradient psum.

    python benchmarks/adasum_resnet_bench.py                # TPU chip
    python benchmarks/adasum_resnet_bench.py --preset tiny  # CPU mesh

Prints ONE JSON line like ``bench.py``.  On a 1-chip world Adasum is the
identity (the reference degenerates the same way at np=1); the tiny CPU
preset runs the real 8-way distance-doubling.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--preset", choices=["full", "tiny"], default="full")
    parser.add_argument("--batch-size", type=int, default=None)
    parser.add_argument("--warmup", type=int, default=1)
    parser.add_argument("--iters", type=int, default=4)
    parser.add_argument("--steps-per-call", type=int, default=5)
    args = parser.parse_args()

    if args.preset == "tiny":
        from horovod_tpu.utils.platform import force_cpu_mesh

        force_cpu_mesh()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.models import ResNet18, ResNet50

    from horovod_tpu.utils.platform import place_compile_cache, require_tpu

    hvd.init()
    if args.preset == "full":
        # A full-preset number is a device number: no TPU, no run.
        require_tpu()
        place_compile_cache()
    n_chips = hvd.size()

    if args.preset == "tiny":
        model = ResNet18(num_classes=10, width=8)
        batch = args.batch_size or 8 * n_chips
        hw, classes, dtype = 32, 10, jnp.float32
    else:
        model = ResNet50(num_classes=1000, dtype=jnp.bfloat16)
        batch = args.batch_size or 256 * n_chips
        hw, classes, dtype = 224, 1000, jnp.bfloat16

    rng = np.random.RandomState(0)
    images = jnp.asarray(rng.randn(batch, hw, hw, 3), dtype)
    labels = jnp.asarray(rng.randint(0, classes, batch), jnp.int32)

    variables = model.init(jax.random.PRNGKey(0), images[:2])
    params, batch_stats = variables["params"], variables["batch_stats"]

    # BatchNorm state rides as part of the carried params pytree: the
    # loss closes over batch_stats read-only (synthetic data, fixed
    # batch — stats drift does not affect throughput measurement).
    def loss_fn(p, batch):
        imgs, labs = batch
        logits, _ = model.apply(
            {"params": p, "batch_stats": batch_stats}, imgs,
            mutable=["batch_stats"])
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        return -jnp.mean(jnp.take_along_axis(logp, labs[:, None], axis=-1))

    tx = optax.sgd(0.1, momentum=0.9)
    step = hvd.make_train_step(loss_fn, tx, op=hvd.Adasum, donate=False)
    opt_state = tx.init(params)

    @partial(jax.jit, donate_argnums=(0, 1))
    def chunk(params, opt_state):
        loss = jnp.zeros((), jnp.float32)
        for _ in range(args.steps_per_call):
            params, opt_state, loss = step(params, opt_state,
                                           (images, labels))
        return params, opt_state, loss

    from horovod_tpu.utils.mfu import aot_compile_with_flops

    run_chunk, _ = aot_compile_with_flops(chunk, params, opt_state)

    for _ in range(args.warmup):
        params, opt_state, loss = run_chunk(params, opt_state)
    if args.warmup:
        float(loss)  # fence (scalar readback; see bench.py)

    t0 = time.perf_counter()
    for _ in range(args.iters):
        params, opt_state, loss = run_chunk(params, opt_state)
    float(loss)
    dt = time.perf_counter() - t0

    imgs_per_sec = batch * args.iters * args.steps_per_call / dt
    print(json.dumps({
        "metric": ("resnet50_adasum_images_per_sec_per_chip"
                   if args.preset == "full"
                   else "resnet18_adasum_tiny_images_per_sec_per_chip"),
        "value": round(imgs_per_sec / n_chips, 2),
        "unit": "images/sec/chip",
        "vs_baseline": None,
        "op": "adasum",
        "world": n_chips,
    }))
    sys.stdout.flush()


if __name__ == "__main__":
    main()
