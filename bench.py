"""Synthetic ResNet-50 benchmark — the reference's headline metric.

Mirrors ``examples/pytorch/pytorch_synthetic_benchmark.py`` (SURVEY.md §6;
mount empty, unverified): images/sec over synthetic ImageNet-shaped
batches, full training step (forward + backward + SGD-momentum update,
BatchNorm in training mode).  Runs on whatever devices the platform
offers (the driver runs it on one real TPU chip); batch is sharded over
the framework mesh so the same script scales to a slice.

Prints ONE JSON line:
``{"metric": ..., "value": N, "unit": ..., "vs_baseline": N}``.

``vs_baseline``: BASELINE.json recorded no reference number
(``published: {}``); the denominator used here is 2500 img/s/chip — the
order of a single A100's ResNet-50 AMP training throughput in the
reference's 8×A100 NCCL target config — so >1.0 beats one baseline chip.

Auto-batch: with no explicit ``--batch-size`` the full preset
quick-times a few per-chip batch sizes (the HBM-throughput knee varies
by chip generation) and measures at the best — the model, input size,
step content, and metric are unchanged, so numbers stay comparable
across rounds (``--no-auto-batch`` pins the r2 default).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from functools import partial

import numpy as np


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--preset", choices=["full", "tiny"], default="full",
                        help="tiny = CPU smoke test (small model/batch)")
    parser.add_argument("--model", default="resnet50",
                        choices=["resnet50", "resnet101", "vgg16",
                                 "inception3"],
                        help="full-preset model (reference benchmark "
                             "family: docs/benchmarks.rst rows)")
    parser.add_argument("--fp16-allreduce", action="store_true",
                        help="reference flag: explicit DistributedOptimizer "
                             "gradient allreduce with Compression.fp16 "
                             "(instead of the implicit GSPMD batch-grad "
                             "psum)")
    parser.add_argument("--batch-size", type=int, default=None)
    parser.add_argument("--warmup", type=int, default=2)
    parser.add_argument("--iters", type=int, default=6,
                        help="timed dispatches; each runs --steps-per-call steps")
    parser.add_argument("--steps-per-call", type=int, default=10,
                        help="training steps fused into one dispatch "
                             "(lax.scan) to amortize host dispatch latency")
    parser.add_argument("--profile-dir", default=None,
                        help="capture a jax.profiler trace of the timed "
                             "region into this directory")
    parser.add_argument("--no-auto-batch", action="store_true",
                        help="skip the per-chip batch-size quick sweep "
                             "and use the fixed default")
    args = parser.parse_args()

    metric_name = (f"{args.model}_images_per_sec_per_chip"
                   if args.preset == "full"
                   else "resnet18_tiny_images_per_sec")

    if args.preset == "tiny":
        # CPU smoke: the tiny preset is defined as the CPU-mesh check.
        from horovod_tpu.utils.platform import force_cpu_mesh

        force_cpu_mesh()

    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.models import (
        InceptionV3, ResNet18, ResNet50, ResNet101, VGG16,
    )
    from horovod_tpu.parallel.train import shard_batch
    from horovod_tpu.utils.mfu import aot_compile_with_flops, peak_tflops
    from horovod_tpu.utils.platform import (device_record,
                                            place_compile_cache, require_tpu)

    hvd.init()
    peak = None
    if args.preset == "full":
        # A full-preset number is a device number: no TPU, no run.
        peak = peak_tflops(require_tpu())
        place_compile_cache()
    gm = hvd.global_mesh()
    n_chips = hvd.size()

    if args.batch_size is not None and (
            args.batch_size <= 0 or args.batch_size % n_chips):
        sys.exit(f"--batch-size {args.batch_size} must be a positive "
                 f"multiple of the chip count ({n_chips}): each chip "
                 "takes an equal shard")
    if args.preset == "tiny":
        model = ResNet18(num_classes=100, width=16)
        default_per_chip = (args.batch_size or 8 * n_chips) // n_chips
        hw, n_classes, dtype = 32, 100, jnp.float32
    else:
        # The reference benchmark family (docs/benchmarks.rst rows).
        # Default per-chip batches sized to v5e-class HBM.
        cls, hw, default_per_chip = {
            "resnet50": (ResNet50, 224, 256),
            "resnet101": (ResNet101, 224, 160),
            "vgg16": (VGG16, 224, 128),
            "inception3": (InceptionV3, 299, 128),
        }[args.model]
        model = cls(num_classes=1000, dtype=jnp.bfloat16)
        if args.batch_size:
            default_per_chip = args.batch_size // n_chips
        n_classes, dtype = 1000, jnp.bfloat16

    tx = optax.sgd(0.1, momentum=0.9)
    rng = np.random.RandomState(0)

    def apply_model(p, stats, imgs):
        if stats is None:
            return model.apply({"params": p}, imgs), None
        logits, mutated = model.apply(
            {"params": p, "batch_stats": stats}, imgs,
            mutable=["batch_stats"])
        return logits, mutated["batch_stats"]

    def xent(logits, labs):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        return -jnp.mean(jnp.take_along_axis(logp, labs[:, None], axis=-1))

    # Compiled-chunk cache: the sweep quick-times a candidate, then the
    # final measurement reuses the SAME compiled executable (fresh
    # state; buffers are donated per call) — without this the winner
    # would pay its multi-minute ResNet compile twice.
    _compiled: dict = {}

    def _build(per_chip_batch: int, steps_per_call: int):
        batch = per_chip_batch * n_chips
        images = jnp.asarray(rng.randn(batch, hw, hw, 3), dtype)
        labels = jnp.asarray(rng.randint(0, n_classes, batch), jnp.int32)
        images = shard_batch(images, gm.mesh, P(gm.axis_name))
        labels = shard_batch(labels, gm.mesh, P(gm.axis_name))

        variables = model.init(jax.random.PRNGKey(0), images[:2])
        params = variables["params"]
        batch_stats = variables.get("batch_stats")  # None for BN-free VGG

        if args.fp16_allreduce:
            # The reference's --fp16-allreduce: explicit gradient
            # allreduce through DistributedOptimizer with fp16 wire
            # compression (BN statistics frozen for the throughput run,
            # like the adasum benchmark).
            def loss_fn(p, batch_):
                logits, _ = apply_model(p, batch_stats, batch_[0])
                return xent(logits, batch_[1])

            dtx = hvd.DistributedOptimizer(tx,
                                           compression=hvd.Compression.fp16)
            inner = hvd.make_train_step(loss_fn, dtx, donate=False)
            opt_state = dtx.init(params)

            def make_chunk(length):
                @partial(jax.jit, donate_argnums=(0, 1))
                def train_chunk(params, opt_state):
                    def body(carry, _):
                        p, o = carry
                        p, o, loss = inner(p, o, (images, labels))
                        return (p, o), loss

                    (params, opt_state), losses = jax.lax.scan(
                        body, (params, opt_state), None, length=length)
                    return params, opt_state, losses[-1]

                return train_chunk

            state = (params, opt_state)
        else:
            opt_state = tx.init(params)

            def train_step(carry, _):
                params, stats, opt_state = carry

                def loss_fn(p):
                    logits, new_stats = apply_model(p, stats, images)
                    return xent(logits, labels), new_stats

                (loss, new_stats), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params)
                updates, opt_state = tx.update(grads, opt_state, params)
                params = optax.apply_updates(params, updates)
                return (params,
                        new_stats if new_stats is not None else stats,
                        opt_state), loss

            def make_chunk(length):
                @partial(jax.jit, donate_argnums=(0, 1, 2))
                def train_chunk(params, stats, opt_state):
                    (params, stats, opt_state), losses = jax.lax.scan(
                        train_step, (params, stats, opt_state), None,
                        length=length)
                    return params, stats, opt_state, losses[-1]

                return train_chunk

            state = (params, batch_stats, opt_state)

        # cost_analysis() counts a lax.scan BODY ONCE regardless of trip
        # count (measured: flops_per_image scaled as 1/steps_per_call),
        # so flops come from an AOT-lowered length-1 chunk, scaled by
        # steps_per_call; the length-N chunk is what actually runs.
        run_chunk, _ = aot_compile_with_flops(
            make_chunk(steps_per_call), *state)
        return {"run_chunk": run_chunk, "state": state, "batch": batch,
                "make_chunk": make_chunk, "step_flops": None,
                "flops_known": False}

    def measure(per_chip_batch: int, *, iters: int, steps_per_call: int,
                warmup: int, profile_dir=None, want_flops: bool = True):
        """Run the timed region at ``per_chip_batch`` rows per chip;
        returns ``(per_chip_imgs_per_sec, chunk_flops, dt, batch)``.
        One device fence (a scalar read back to the host) at the end of
        the timed region, so the host round-trip is paid once and not
        per chunk."""
        key = (per_chip_batch, steps_per_call)
        entry = _compiled.get(key)
        if entry is None:
            entry = _compiled[key] = _build(per_chip_batch, steps_per_call)
        if want_flops and not entry["flops_known"]:
            _, entry["step_flops"] = aot_compile_with_flops(
                entry["make_chunk"](1), *entry["state"])
            entry["flops_known"] = True
        chunk_flops = (entry["step_flops"] * steps_per_call
                       if entry["step_flops"] else None)
        run_chunk, batch = entry["run_chunk"], entry["batch"]
        # state buffers are donated by the chunk; hand ownership over
        # and drop the cache's reference (a later call on the same key
        # continues from the final state returned below).
        state = entry["state"]

        def unpack(out):  # (*state, loss) -> state tuple, loss
            return out[:-1], out[-1]

        for _ in range(warmup):
            state, loss = unpack(run_chunk(*state))
        if warmup:
            float(loss)  # fence: warmup fully done before the clock

        prof_ctx = (jax.profiler.trace(profile_dir)
                    if profile_dir else contextlib.nullcontext())
        with prof_ctx:
            t0 = time.perf_counter()
            for _ in range(iters):
                state, loss = unpack(run_chunk(*state))
            float(loss)  # single end-of-run fence
            dt = time.perf_counter() - t0

        entry["state"] = state
        per_chip = batch * iters * steps_per_call / dt / n_chips
        return per_chip, chunk_flops, dt, batch

    # --- auto-batch: quick-time candidates, measure at the best -----------
    per_chip_batch = default_per_chip
    steps_per_call = args.steps_per_call
    sweep_log = None
    if (args.preset == "full" and args.batch_size is None
            and not args.no_auto_batch):
        candidates = sorted({default_per_chip,
                             default_per_chip * 5 // 4,
                             default_per_chip * 3 // 2,
                             default_per_chip * 2})
        sweep_log = []
        best_rate = -1.0
        for cand in candidates:
            try:
                rate, _, _, _ = measure(cand, iters=2,
                                        steps_per_call=args.steps_per_call,
                                        warmup=1, want_flops=False)
            except jax.errors.JaxRuntimeError as e:
                # The one failure the sweep expects is a candidate that
                # does not fit HBM; anything else must surface.
                if "RESOURCE_EXHAUSTED" not in str(e):
                    raise
                print(f"auto-batch: {cand}/chip does not fit "
                      f"({type(e).__name__})", file=sys.stderr)
                # Drop any half-built cache entry (its donated state may
                # be unusable) so a fallback re-measure starts clean.
                _compiled.pop((cand, args.steps_per_call), None)
                sweep_log.append({"per_chip_batch": cand, "rate": None})
                continue
            sweep_log.append({"per_chip_batch": cand,
                              "rate": round(rate, 1)})
            if rate > best_rate:
                # Evict the dethroned leader's device state (params,
                # optimizer state, batch, executable) — retained losers
                # would squat in HBM, OOMing larger candidates or the
                # final measurement.  (Guard: on the first iteration the
                # "leader" slot still names cand itself.)
                if per_chip_batch != cand:
                    _compiled.pop((per_chip_batch, args.steps_per_call),
                                  None)
                best_rate, per_chip_batch = rate, cand
            else:
                _compiled.pop((cand, args.steps_per_call), None)
        # Second knob at the winning batch: doubled steps-per-call
        # halves the residual per-chunk dispatch overhead.  Same
        # winner-comparison basis: quick-timed like the batch
        # candidates.
        for spc in (args.steps_per_call * 2,):
            try:
                rate, _, _, _ = measure(per_chip_batch, iters=2,
                                        steps_per_call=spc, warmup=1,
                                        want_flops=False)
            except jax.errors.JaxRuntimeError as e:
                if "RESOURCE_EXHAUSTED" not in str(e):
                    raise
                print(f"auto-batch: spc={spc} does not fit "
                      f"({type(e).__name__})", file=sys.stderr)
                _compiled.pop((per_chip_batch, spc), None)
                continue
            sweep_log.append({"per_chip_batch": per_chip_batch,
                              "steps_per_call": spc,
                              "rate": round(rate, 1)})
            if rate > best_rate:
                _compiled.pop((per_chip_batch, steps_per_call), None)
                best_rate, steps_per_call = rate, spc
        print(f"auto-batch sweep: {sweep_log} -> {per_chip_batch}/chip "
              f"x {steps_per_call} steps/call", file=sys.stderr)

    per_chip, chunk_flops, dt, batch = measure(
        per_chip_batch, iters=args.iters,
        steps_per_call=steps_per_call, warmup=args.warmup,
        profile_dir=args.profile_dir)

    baseline_per_chip = 2500.0  # see module docstring
    is_headline = args.preset == "full" and args.model == "resnet50"
    out = {
        "metric": metric_name,
        "value": round(per_chip, 2),
        "unit": "images/sec/chip",
        # The 2500 img/s denominator is a ResNet-50/224px number — only
        # meaningful for the default full preset.
        "vs_baseline": (round(per_chip / baseline_per_chip, 4)
                        if is_headline else None),
    }
    if args.preset == "full":
        out["device"] = device_record()
        out["per_chip_batch"] = per_chip_batch
        out["steps_per_call"] = steps_per_call
        if sweep_log is not None:
            out["auto_batch_sweep"] = sweep_log
    if args.fp16_allreduce:
        out["fp16_allreduce"] = True
    if chunk_flops:
        # chunk_flops is per-device (see above): per-chip rate directly.
        per_chip_flops_s = chunk_flops * args.iters / dt
        out["model_tflops_per_chip"] = round(per_chip_flops_s / 1e12, 2)
        out["flops_per_image"] = round(
            chunk_flops / (batch / n_chips * steps_per_call) / 1e9,
            3)  # GFLOPs, per-chip flops over the per-chip batch share
        if peak:
            out["mfu_pct"] = round(
                100.0 * per_chip_flops_s / (peak * 1e12), 2)
    print(json.dumps(out))
    sys.stdout.flush()


if __name__ == "__main__":
    main()
