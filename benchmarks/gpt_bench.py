"""GPT training-throughput benchmark (tokens/sec/chip + MFU).

No single-number reference analogue (the reference's transformer config
is the BERT fine-tune — see ``bert_finetune_bench.py``); this is the
flagship-model vehicle for the TPU-first perf story: decoder-only GPT
with the Pallas flash-attention path, bf16 activations, full training
step (forward + backward + AdamW), `6 * n_params * tokens`-style model
FLOPs read from the compiled program for MFU.

    python benchmarks/gpt_bench.py                 # TPU chip (GPT ~350M)
    python benchmarks/gpt_bench.py --preset tiny   # CPU smoke

Prints ONE JSON line like ``bench.py``.
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
    parser.add_argument("--seq-len", type=int, default=None)
    parser.add_argument("--attention", default=None,
                        help="full|flash (default: flash on TPU, full on cpu)")
    parser.add_argument("--vocab-chunk", type=int, default=0,
                        help=">0: chunked-vocab cross-entropy "
                             "(ops/xent.py) — [B,T,V] logits never "
                             "materialized; enables larger batch")
    parser.add_argument("--warmup", type=int, default=1)
    parser.add_argument("--iters", type=int, default=4)
    parser.add_argument("--steps-per-call", type=int, default=5)
    parser.add_argument("--microbatches", type=int, default=0,
                        help=">1: accumulate gradients over this many "
                             "microbatches per step inside one compiled "
                             "scan (0 = HVD_TPU_MICROBATCHES)")
    parser.add_argument("--overlap", action=argparse.BooleanOptionalAction,
                        default=None,
                        help="overlap-schedule the gradient wire: issue "
                             "microbatch i-1's bucketed reduce-scatter "
                             "under microbatch i's backward, all-gather "
                             "deferred to the update boundary; "
                             "--no-overlap pins the accumulate-then-"
                             "reduce baseline (default: "
                             "HVD_TPU_OVERLAP_REDUCE)")
    parser.add_argument("--compressor", default="none",
                        choices=["none", "fp16", "bf16", "int8"],
                        help="gradient-wire compression tier "
                             "(hvd.Compression.<tier>)")
    parser.add_argument("--layout", action="append", default=None,
                        metavar="SPEC",
                        help="repeatable: sweep mesh-plan layouts "
                             "('data=8', 'data=4,fsdp=2', ...) through "
                             "the SAME train step — one JSON row per "
                             "layout with tokens/sec/chip and the "
                             "modeled per-axis wire bytes "
                             "(docs/mesh_plan.md)")
    parser.add_argument("--trace", default=None, metavar="DIR",
                        help="write a merged per-run trace artifact "
                             "(Perfetto JSON + critical-path report; "
                             "docs/tracing.md) into DIR")
    args = parser.parse_args()
    if args.microbatches < 0:
        parser.error("--microbatches must be >= 0")

    if args.preset == "tiny":
        from horovod_tpu.utils.platform import force_cpu_mesh

        force_cpu_mesh()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.models import GPT, GPTConfig
    from horovod_tpu.models.transformer import lm_loss_fn
    from horovod_tpu.parallel.train import shard_batch

    from horovod_tpu.utils.mfu import (aot_compile_with_flops,
                                       estimate_compute_us, peak_tflops)
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

    if args.preset == "tiny":
        cfg = GPTConfig(vocab_size=512, n_layer=2, n_head=2, d_model=32,
                        d_ff=64, max_seq_len=128,
                        attention=args.attention or "full",
                        dtype=jnp.float32)
        batch = args.batch_size or 4 * n_chips
        seq = args.seq_len or 128
    else:
        # ~350M-param GPT-medium shape; flash attention on-chip.
        cfg = GPTConfig(vocab_size=32000, n_layer=24, n_head=16,
                        d_model=1024, d_ff=4096, max_seq_len=1024,
                        attention=args.attention or "flash")
        batch = args.batch_size or 8 * n_chips
        seq = args.seq_len or 1024

    model = GPT(cfg)
    rng = np.random.RandomState(0)
    tokens = rng.randint(0, cfg.vocab_size, (batch, seq + 1))
    inputs = jnp.asarray(tokens[:, :-1], jnp.int32)
    targets = jnp.asarray(tokens[:, 1:], jnp.int32)
    inputs = shard_batch(inputs, gm.mesh, P(gm.axis_name))
    targets = shard_batch(targets, gm.mesh, P(gm.axis_name))

    params = model.init(jax.random.PRNGKey(0), inputs[:1])["params"]
    n_params = sum(x.size for x in jax.tree.leaves(params))
    tx = optax.adamw(3e-4)
    loss_fn = lm_loss_fn(model, vocab_chunk_size=args.vocab_chunk)
    compressor = (None if args.compressor == "none"
                  else getattr(hvd.Compression, args.compressor))
    # Effective microbatch count: the request clamped to a divisor of
    # the per-slot batch via the SAME snapping policy the step uses at
    # trace time (the bench clamps up front so a round-number request
    # never crashes the run; the step would raise on an explicit
    # non-divisor).
    from horovod_tpu.optim.distributed_optimizer import snap_microbatches

    per_slot_rows = max(1, batch // n_chips)
    mb_req = args.microbatches or hvd.config().microbatches
    mb = snap_microbatches(mb_req, per_slot_rows)
    if args.layout:
        # Layout sweep (docs/mesh_plan.md): every spec rides the SAME
        # step factory — only the session MeshPlan differs, so rows are
        # comparable layout-for-layout.  One JSON line per layout
        # (bench_regress reads the JSONL stream); the modeled per-axis
        # wire carries the _est suffix so gating skips it.
        from horovod_tpu import basics as _basics

        stem = ("gpt_medium" if args.preset == "full" else "gpt_tiny")
        grad_bytes = sum(leaf.size * leaf.dtype.itemsize
                         for leaf in jax.tree.leaves(params))
        original_spec = hvd.config().mesh_plan
        try:
            for spec in args.layout:
                plan = hvd.apply_mesh_plan(spec)
                b_in = shard_batch(inputs, plan.mesh, plan.batch_spec())
                b_tg = shard_batch(targets, plan.mesh, plan.batch_spec())
                step = hvd.make_train_step(
                    loss_fn, tx, donate=False,
                    microbatches=mb if args.microbatches
                    else (mb if mb > 1 else None),
                    overlap=args.overlap, compression=compressor)
                p = jax.tree.map(jnp.copy, params)
                s = tx.init(p)

                @partial(jax.jit, donate_argnums=(0, 1))
                def chunk(p, s):
                    loss = jnp.zeros((), jnp.float32)
                    for _ in range(args.steps_per_call):
                        p, s, loss = step(p, s, (b_in, b_tg))
                    return p, s, loss

                for _ in range(args.warmup):
                    p, s, loss = chunk(p, s)
                if args.warmup:
                    float(loss)
                t0 = time.perf_counter()
                for _ in range(args.iters):
                    p, s, loss = chunk(p, s)
                float(loss)
                dt = time.perf_counter() - t0
                tps = (batch * seq * args.iters
                       * args.steps_per_call / dt)
                tag = spec.replace("=", "").replace(",", "_")
                row = {
                    "metric": f"{stem}_train_tokens_per_sec_per_chip"
                              f"_layout_{tag}",
                    "value": round(tps / n_chips, 2),
                    "unit": "tokens/sec/chip",
                    "vs_baseline": None,
                    "layout": spec,
                    "n_params": n_params,
                    "seq_len": seq,
                    "microbatches": mb,
                }
                for ax, nbytes in sorted(
                        plan.modeled_wire_bytes(grad_bytes).items()):
                    row[f"wire_bytes_{ax}_est"] = nbytes
                print(json.dumps(row))
                sys.stdout.flush()
        finally:
            hvd.apply_mesh_plan(original_spec)
        return

    # An explicit --microbatches (even 1) pins the count; only an unset
    # flag defers to HVD_TPU_MICROBATCHES — so the JSON row always
    # describes the experiment that actually ran.
    step = hvd.make_train_step(loss_fn, tx, donate=False,
                               microbatches=mb if args.microbatches
                               else (mb if mb > 1 else None),
                               overlap=args.overlap,
                               compression=compressor)
    opt_state = tx.init(params)

    @partial(jax.jit, donate_argnums=(0, 1))
    def chunk(params, opt_state):
        loss = jnp.zeros((), jnp.float32)
        for _ in range(args.steps_per_call):
            params, opt_state, loss = step(params, opt_state,
                                           (inputs, targets))
        return params, opt_state, loss

    run_chunk, chunk_flops = aot_compile_with_flops(chunk, params, opt_state)

    for _ in range(args.warmup):
        params, opt_state, loss = run_chunk(params, opt_state)
    if args.warmup:
        float(loss)  # fence (scalar readback; see bench.py)

    t0 = time.perf_counter()
    for _ in range(args.iters):
        params, opt_state, loss = run_chunk(params, opt_state)
    float(loss)
    dt = time.perf_counter() - t0

    tokens_per_sec = batch * seq * args.iters * args.steps_per_call / dt
    out = {
        "metric": ("gpt_medium_train_tokens_per_sec_per_chip"
                   if args.preset == "full"
                   else "gpt_tiny_train_tokens_per_sec_per_chip"),
        "value": round(tokens_per_sec / n_chips, 2),
        "unit": "tokens/sec/chip",
        "vs_baseline": None,
        "n_params": n_params,
        "seq_len": seq,
        "attention": cfg.attention,
        "vocab_chunk": args.vocab_chunk,
        "microbatches": mb,
        "overlap": bool(args.overlap) if args.overlap is not None
        else hvd.config().overlap_reduce,
        "compressor": args.compressor,
    }
    out["device"] = device_record()
    if mb > 1 and not out["overlap"]:
        # Nothing is scheduled under the backward: the honest estimate
        # of hidden communication is zero.
        out["hidden_comm_frac_est"] = 0.0
        out["hidden_comm_basis"] = "overlap_off"
    elif mb > 1:
        # Estimated hidden-communication fraction of the overlap
        # schedule (ops/fusion.py cost model): per-microbatch backward
        # time from the chip's published peak on the TPU preset, else
        # from the measured wall clock (the tiny CPU preset — the basis
        # field records which).
        from horovod_tpu.ops.fusion import estimate_overlap_hidden_fraction

        sizes = [leaf.size * leaf.dtype.itemsize
                 for leaf in jax.tree.leaves(params)]
        if peak and chunk_flops:
            basis = "modeled_peak"
            bwd_us = estimate_compute_us(
                (2.0 / 3.0) * chunk_flops / args.steps_per_call / mb,
                jax.devices()[0])
        else:
            basis = "measured_wall"
            bwd_us = (dt / (args.iters * args.steps_per_call * mb)) \
                * (2.0 / 3.0) * 1e6
        hvd_cfg = hvd.config()
        est = estimate_overlap_hidden_fraction(
            sizes, hvd_cfg.fusion_threshold, world_size=n_chips,
            microbatches=mb, compute_us_per_microbatch=bwd_us,
            alpha_us=hvd_cfg.cost_alpha_us,
            beta_gbps=hvd_cfg.cost_beta_gbps)
        out["hidden_comm_frac_est"] = round(est["hidden_frac"], 4)
        out["hidden_comm_wire_us_est"] = round(est["wire_us"], 2)
        out["hidden_comm_basis"] = basis
        from horovod_tpu.obs import instrument as obs_instr_est

        obs_instr_est.set_hidden_comm_estimate(est["wire_us"],
                                               est["hidden_us"])
    if chunk_flops:
        per_chip_flops_s = chunk_flops * args.iters / dt
        out["model_tflops_per_chip"] = round(per_chip_flops_s / 1e12, 2)
        if peak:
            out["mfu_pct"] = round(
                100.0 * per_chip_flops_s / (peak * 1e12), 2)
    # Final telemetry snapshot (diagnostic block — bench_regress skips
    # it): wire bytes per tier, step-time distribution, microbatch plan.
    from horovod_tpu.obs import export as obs_export
    from horovod_tpu.obs import instrument as obs_instr

    if "mfu_pct" in out:
        obs_instr.set_mfu(out["mfu_pct"])
    out["metrics"] = obs_export.json_snapshot()["metrics"]
    if args.trace:
        # Merged per-run trace artifact (single-process merge) plus the
        # headline critical-path report embedded under "trace" — a
        # diagnostic block like "metrics"; bench_regress skips both.
        from horovod_tpu.obs import trace as obs_trace

        os.makedirs(args.trace, exist_ok=True)
        tpath = os.path.join(args.trace, f"TRACE_{out['metric']}.json")
        rep = obs_trace.dump_merged(tpath)
        out["trace"] = {"file": tpath,
                        **({"critical_path": rep} if rep else {})}
    print(json.dumps(out))
    sys.stdout.flush()


if __name__ == "__main__":
    main()
