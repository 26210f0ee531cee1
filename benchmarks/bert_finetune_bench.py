"""BERT-Large fine-tune benchmark — BASELINE.json config #4.

The driver's baseline list names "BERT-Large fine-tune with tensor
fusion + fp16 Compression" (SURVEY.md §6).  This runs that config end to
end on the in-tree BERT (``horovod_tpu/models/bert.py``): synthetic
GLUE-shaped batches, full fine-tune step (forward + backward + AdamW)
under ``hvd.DistributedOptimizer(compression=Compression.fp16)`` with
the tensor-fusion bucketing active, and reports sequences/sec.

    python benchmarks/bert_finetune_bench.py                # TPU chip
    python benchmarks/bert_finetune_bench.py --preset tiny  # CPU smoke

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
    parser.add_argument("--preset", choices=["full", "tiny"], default="full",
                        help="full = BERT-Large seq 128; tiny = CPU smoke")
    parser.add_argument("--batch-size", type=int, default=None)
    parser.add_argument("--seq-len", type=int, default=None)
    parser.add_argument("--warmup", type=int, default=1)
    parser.add_argument("--iters", type=int, default=4)
    parser.add_argument("--steps-per-call", type=int, default=4)
    args = parser.parse_args()

    if args.preset == "tiny":
        from horovod_tpu.utils.platform import force_cpu_mesh

        force_cpu_mesh()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.models import BertConfig, BertForSequenceClassification
    from horovod_tpu.models.bert import classification_loss_fn
    from horovod_tpu.parallel.train import shard_batch

    from horovod_tpu.utils.platform import place_compile_cache, require_tpu

    hvd.init()
    if args.preset == "full":
        # A full-preset number is a device number: no TPU, no run.
        require_tpu()
        place_compile_cache()
    gm = hvd.global_mesh()
    n_chips = hvd.size()

    if args.preset == "tiny":
        cfg = BertConfig.base(vocab_size=512, n_layer=2, n_head=2,
                              d_model=32, d_ff=64, max_seq_len=64,
                              dtype=jnp.float32)
        batch = args.batch_size or 8 * n_chips
        seq = args.seq_len or 32
    else:
        # The standard GLUE fine-tune shape: seq 128.  Attention is the
        # Pallas flash path (128 % block == 0, no padding mask needed on
        # synthetic full-length batches).
        cfg = BertConfig.large(attention="flash")
        batch = args.batch_size or 32 * n_chips
        seq = args.seq_len or 128

    model = BertForSequenceClassification(cfg, num_classes=2)
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)), jnp.int32)
    labels = jnp.asarray(rng.randint(0, 2, batch), jnp.int32)
    ids = shard_batch(ids, gm.mesh, P(gm.axis_name))
    labels = shard_batch(labels, gm.mesh, P(gm.axis_name))

    params = model.init(jax.random.PRNGKey(0), ids[:2])["params"]
    # The baseline config verbatim: fusion (on by default inside
    # DistributedOptimizer) + fp16 wire compression.
    tx = hvd.DistributedOptimizer(optax.adamw(2e-5),
                                  compression=hvd.Compression.fp16)
    opt_state = tx.init(params)
    loss_fn = classification_loss_fn(model)
    inner_step = hvd.make_train_step(loss_fn, tx, donate=False)

    # Chain steps_per_call steps per dispatch to amortize host->device
    # dispatch latency (same rationale as bench.py).
    @partial(jax.jit, donate_argnums=(0, 1))
    def chunk(params, opt_state):
        loss = jnp.zeros((), jnp.float32)
        for _ in range(args.steps_per_call):
            params, opt_state, loss = inner_step(params, opt_state,
                                                 (ids, labels))
        return params, opt_state, loss

    from horovod_tpu.utils.mfu import aot_compile_with_flops

    run_chunk, chunk_flops = aot_compile_with_flops(chunk, params, opt_state)

    for _ in range(args.warmup):
        params, opt_state, loss = run_chunk(params, opt_state)
    if args.warmup:
        float(loss)  # fence (see bench.py: scalar readback, not block_until_ready)

    t0 = time.perf_counter()
    for _ in range(args.iters):
        params, opt_state, loss = run_chunk(params, opt_state)
    float(loss)
    dt = time.perf_counter() - t0

    seqs_per_sec = batch * args.iters * args.steps_per_call / dt
    out = {
        "metric": ("bert_large_finetune_seqs_per_sec_per_chip"
                   if args.preset == "full"
                   else "bert_tiny_finetune_seqs_per_sec_per_chip"),
        "value": round(seqs_per_sec / n_chips, 2),
        "unit": "sequences/sec/chip",
        "vs_baseline": None,  # BASELINE.json `published` is {} for BERT
        "seq_len": seq,
        "compression": "fp16",
    }
    if chunk_flops:
        out["model_tflops_per_chip"] = round(
            chunk_flops * args.iters / dt / 1e12, 2)
    print(json.dumps(out))
    sys.stdout.flush()


if __name__ == "__main__":
    main()
