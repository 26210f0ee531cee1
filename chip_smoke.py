"""Chip smoke run: the main path, once, on the TPU, through the public API.

    python chip_smoke.py             # one chip: collectives, trainer, server
    python chip_smoke.py --chips 4   # one host, four chips: data parallel only

One process, no children.  The model is GPT-medium as
``benchmarks/gpt_bench.py`` builds it (24 layers, d_model 1024, 16 heads,
d_ff 4096, vocab 32000, sequence 1024, flash attention, bf16) with
random weights made from ``--seed``.  Every phase prints one JSON line
when it has passed; the last line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

and is printed only if every phase passed on TPU devices.  Anything
else — no accelerator, a wrong device count, a phase that raises, a
train step that lowered without its Pallas kernel — ends in a traceback
and a non-zero exit code.  ``tests/test_chip_smoke.py`` rehearses
:func:`run` at a tiny size on the CPU; the sizes live there, not behind
an option here.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import dataclasses
import gc
import json
import shutil
import statistics
import time
from typing import List, Sequence, Tuple

# Greedy tokens of the engine are accepted against the full-forward
# reference when, at every generated position, the reference logit of
# the engine's token is within this margin of the reference maximum:
# random bf16 weights give near-ties that the two attention paths
# (paged einsum vs. full) may break differently.
LOGIT_MARGIN_TOL = 0.1
# |loss(4 chips, 2 sequences each) - loss(1 device, 8 sequences)| per
# step, same seeded batch and weights: bf16 activations, f32 reductions
# in another order.
DP_LOSS_TOL = 5e-2
# AdamW step size.  At gpt_bench's 3e-4 the loss on a repeated batch of
# random tokens bounces up on the 4th step before it goes on down
# (measured on the chip, PR 21); at 1e-4 it falls on every step, which
# is the signal a smoke run wants.
LEARNING_RATE = 1e-4


@dataclasses.dataclass(frozen=True)
class Sizes:
    vocab_size: int = 32000
    n_layer: int = 24
    n_head: int = 16
    d_model: int = 1024
    d_ff: int = 4096
    seq_len: int = 1024
    dtype: str = "bfloat16"
    batch_per_chip: int = 8
    train_steps: int = 4
    compare_batch: int = 8          # global batch of the 4-vs-1 comparison
    prompt_lens: Tuple[int, ...] = (37, 9, 60, 100, 180, 250)
    max_new_tokens: int = 16


def phases_for(chips: int) -> List[str]:
    """Names of the phases a run on ``chips`` devices executes."""
    if chips == 1:
        return ["collectives", "trainer", "server"]
    return ["collectives", "data_parallel_trainer", "dp_vs_single_device"]


def final_line(devices) -> str:
    from horovod_tpu.utils.platform import device_record

    return json.dumps({"ok": True, "device": device_record(devices)})


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, "ok": True, **fields}), flush=True)


def peak_gb(device):
    """Peak device memory so far as the runtime counts it: live arrays
    (``in_use``) and, apart from them, what compiled programs reserved
    for their temporaries (``reserved``)."""
    stats = device.memory_stats() or {}
    return {name: round(stats[key] / 1e9, 3)
            for name, key in (("in_use", "peak_bytes_in_use"),
                              ("reserved", "peak_bytes_reserved"))
            if key in stats}


# --- model and data ----------------------------------------------------------

def gpt(sizes: Sizes, attention: str):
    import jax.numpy as jnp

    from horovod_tpu.models import GPT, GPTConfig

    return GPT(GPTConfig(
        vocab_size=sizes.vocab_size, n_layer=sizes.n_layer,
        n_head=sizes.n_head, d_model=sizes.d_model, d_ff=sizes.d_ff,
        max_seq_len=sizes.seq_len, attention=attention,
        dtype=jnp.dtype(sizes.dtype)))


def init_params(model, seed: int):
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda: model.init(
        jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))["params"])()


def token_batch(sizes: Sizes, rows: int, seed: int):
    import numpy as np

    tokens = np.random.RandomState(seed).randint(
        0, sizes.vocab_size, (rows, sizes.seq_len + 1)).astype(np.int32)
    return tokens[:, :-1], tokens[:, 1:]


def check_losses(losses: Sequence[float]) -> None:
    import math

    if not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not decrease: {losses}")


# --- phases ------------------------------------------------------------------

def collectives_phase() -> None:
    """The eager API against NumPy, each slot contributing its own
    values (at size 1 that still proves the dispatch tier compiles)."""
    import numpy as np

    import horovod_tpu as hvd

    n = hvd.size()

    def close(got, want):
        np.testing.assert_allclose(np.asarray(got), want,
                                   rtol=1e-6, atol=1e-6)

    rng = np.random.RandomState(1)
    x = rng.randn(n, 4, 3).astype(np.float32) + np.arange(
        n, dtype=np.float32)[:, None, None]
    close(hvd.allreduce(x, op=hvd.Sum), x.sum(axis=0))
    close(hvd.allreduce(x, op=hvd.Average), x.mean(axis=0))
    close(hvd.allgather(x), x.reshape(-1, 3))
    close(hvd.broadcast(x, root_rank=n - 1), x[n - 1])
    k = 2
    y = rng.randn(n, n * k, 3).astype(np.float32)
    close(hvd.reducescatter(y, op=hvd.Sum), y.sum(axis=0).reshape(n, k, 3))
    close(hvd.alltoall(y), y.reshape(n, n, k, 3).transpose(1, 0, 2, 3)
          .reshape(n, n * k, 3))
    handle = hvd.allreduce_async(x, op=hvd.Sum)
    close(hvd.synchronize(handle), x.sum(axis=0))
    if not hvd.poll(handle):
        raise AssertionError("synchronized handle does not poll done")
    hvd.barrier()
    emit("collectives", size=n, ops=[
        "allreduce_sum", "allreduce_average", "allgather", "broadcast",
        "reducescatter", "alltoall", "allreduce_async+synchronize",
        "barrier"])


def train(sizes: Sizes, rows_per_chip: int, seed: int):
    """``hvd.make_train_step`` on the whole mesh, the same seeded batch
    every step.  Returns ``(report, params)``."""
    import jax
    import optax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.models.transformer import lm_loss_fn
    from horovod_tpu.parallel.train import shard_batch

    gm = hvd.global_mesh()
    n = hvd.size()
    model = gpt(sizes, "flash")
    batch = shard_batch(token_batch(sizes, rows_per_chip * n, seed),
                        gm.mesh, P(gm.axis_name))
    holders = {s.device for s in batch[0].addressable_shards}
    if len(holders) != n:
        raise AssertionError(f"batch lives on {len(holders)} of {n} devices")

    tx = hvd.DistributedOptimizer(optax.adamw(LEARNING_RATE))
    step = hvd.make_train_step(lm_loss_fn(model), tx)
    params = jax.device_put(init_params(model, seed), gm.replicated())
    params = hvd.broadcast_parameters(params, root_rank=0)
    opt_state = jax.jit(tx.init, out_shardings=gm.replicated())(params)

    # What the compiler made of it, read from the program and not from
    # a flag: the Pallas kernel must be in the lowered text, and across
    # chips the gradient reduction must be in the compiled text.  The
    # executable lands in the persistent cache, where step() finds it.
    jitted = getattr(step, "__wrapped__", step)
    t0 = time.perf_counter()
    lowered = jitted.lower(params, opt_state, batch)
    has_kernel = "tpu_custom_call" in lowered.as_text()
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    if jax.devices()[0].platform == "tpu" and not has_kernel:
        raise AssertionError(
            "attention='flash' lowered without a tpu_custom_call: the "
            "Pallas kernel is not in the train step")
    text = compiled.as_text()
    reduces = text.count("all-reduce") + text.count("reduce-scatter")
    if n > 1 and not reduces:
        raise AssertionError("no all-reduce or reduce-scatter in the "
                             "compiled data-parallel step")
    mem = compiled.memory_analysis()

    losses, times = [], []
    for _ in range(sizes.train_steps):
        t0 = time.perf_counter()
        params, opt_state, loss = step(params, opt_state, batch)
        loss.block_until_ready()
        times.append(time.perf_counter() - t0)
        losses.append(float(loss))
    check_losses(losses)
    report = dict(
        size=n, layers=sizes.n_layer, d_model=sizes.d_model,
        vocab=sizes.vocab_size, seq_len=sizes.seq_len,
        rows_per_chip=rows_per_chip,
        n_params=sum(x.size for x in jax.tree.leaves(params)),
        tpu_custom_call=has_kernel, collectives_in_program=reduces,
        batch_devices=len(holders), losses=losses,
        compile_s=round(compile_s, 1),
        first_step_s=round(times[0], 2),
        step_s=round(statistics.median(times[1:]), 4),
        program_gb={"arguments": round(mem.argument_size_in_bytes / 1e9, 2),
                    "temporaries": round(mem.temp_size_in_bytes / 1e9, 2)},
        peak_gb=peak_gb(jax.devices()[0]))
    return report, params


def trainer_phase(sizes: Sizes, seed: int):
    import horovod_tpu as hvd

    if hvd.size() != 1:
        raise AssertionError(f"one-chip run sees hvd.size()={hvd.size()}")
    report, params = train(sizes, sizes.batch_per_chip, seed)
    emit("trainer", **report)
    return params


def server_phase(sizes: Sizes, params, seed: int) -> None:
    """The same weights behind InferenceEngine (paged KV) +
    ContinuousBatcher + InferenceServer on loopback, asked through the
    Router as a client would."""
    import jax
    import numpy as np

    from horovod_tpu.runner.common.secret import make_secret_key
    from horovod_tpu.serve import (ContinuousBatcher, InferenceEngine,
                                   InferenceServer, ReplicaSpec, Router)

    model = gpt(sizes, "flash")
    engine = InferenceEngine(model, params, seed=seed)
    rng = np.random.RandomState(seed + 1)
    prompts = [rng.randint(0, sizes.vocab_size, n).tolist()
               for n in sizes.prompt_lens]
    buckets = sorted({engine.bucket_for(len(p)) for p in prompts})
    if len(buckets) < 2:
        raise AssertionError(f"prompts fall in one prefill bucket {buckets}")
    n_new = sizes.max_new_tokens
    key = make_secret_key()
    batcher = ContinuousBatcher(engine)
    server = InferenceServer(batcher, key=key, name="smoke",
                             host="127.0.0.1")
    try:
        router = Router([ReplicaSpec("smoke", [("127.0.0.1", server.port)])],
                        key)
        # One request per bucket first: each pays that prefill program's
        # compile (and the first the decode program's), so no deadline.
        t0 = time.perf_counter()
        for b in buckets:
            warm = router.generate([1] * min(b, sizes.seq_len - 3),
                                   max_new_tokens=2, deadline_s=0)
            if warm.error is not None:
                raise AssertionError(f"warm-up request failed: {warm.error}")
        compile_s = time.perf_counter() - t0

        t0 = time.perf_counter()
        with concurrent.futures.ThreadPoolExecutor(len(prompts)) as pool:
            responses = list(pool.map(
                lambda p: router.generate(p, max_new_tokens=n_new), prompts))
        serve_s = time.perf_counter() - t0
        for resp in responses:
            if resp.error is not None or len(resp.tokens) != n_new:
                raise AssertionError(
                    f"request {resp.request_id}: error={resp.error} "
                    f"tokens={resp.tokens}")
        snap = batcher.snapshot()
    finally:
        server.shutdown()
    done = len(prompts) + len(buckets)
    if snap["requests_failed"] or snap["requests_completed"] != done:
        raise AssertionError(f"server counters: {snap}")

    # The engine's own oracle: a plain full forward (no cache, XLA
    # attention) of the same weights in the same dtype over prompt +
    # generated tokens.  Row i of the logits is what a greedy full-
    # forward decode would have chosen from at step i, so the first row
    # whose argmax differs is where that decode and the engine part.
    prompt, got = prompts[0], responses[0].tokens
    ref_model = gpt(sizes, "full")
    n_seq = len(prompt) + n_new
    padded = np.zeros((1, min(sizes.seq_len, -(-n_seq // 64) * 64)),
                      np.int32)
    padded[0, :n_seq] = prompt + got
    logits = jax.jit(lambda p, t: ref_model.apply({"params": p}, t))(
        params, padded)
    rows = np.asarray(logits[0, len(prompt) - 1:len(prompt) - 1 + n_new],
                      np.float32)
    if not np.isfinite(rows).all():
        raise AssertionError("reference logits are not finite")
    margins = rows.max(axis=-1) - rows[np.arange(n_new), got]
    agree = rows.argmax(axis=-1) == np.asarray(got)
    matched = int(np.argmin(agree)) if not agree.all() else n_new
    if margins.max() > LOGIT_MARGIN_TOL:
        raise AssertionError(
            f"engine token at step {int(margins.argmax())} is "
            f"{margins.max():.4f} below the reference maximum "
            f"(tolerance {LOGIT_MARGIN_TOL}); margins {margins.tolist()}")
    emit("server", requests=len(prompts), prompt_lens=list(sizes.prompt_lens),
         prefill_buckets=buckets, max_new_tokens=n_new,
         completed=snap["requests_completed"], failed=snap["requests_failed"],
         tokens_out=snap["tokens_out"],
         reference_tokens_matched=f"{matched}/{n_new}",
         reference_argmax_agree=f"{int(agree.sum())}/{n_new}",
         reference_max_margin=round(float(margins.max()), 4),
         margin_tolerance=LOGIT_MARGIN_TOL,
         compile_s=round(compile_s, 1), serve_s=round(serve_s, 2),
         ttft_ms_p50=snap["ttft_ms_p50"], tpot_ms_p50=snap["tpot_ms_p50"],
         kv_blocks=engine.kv_blocks, peak_gb=peak_gb(jax.devices()[0]))


def data_parallel_phase(sizes: Sizes, seed: int) -> None:
    report, params = train(sizes, sizes.batch_per_chip, seed)
    del params
    emit("data_parallel_trainer", **report)


def dp_vs_single_phase(sizes: Sizes, seed: int) -> None:
    """One seeded global batch twice from the same weights: split over
    the chips through ``make_train_step``, and whole on device 0
    through a plain ``jax.jit`` of the same loss and optimizer."""
    import jax
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.models.transformer import lm_loss_fn

    n = hvd.size()
    if sizes.compare_batch % n:
        raise AssertionError(f"compare_batch {sizes.compare_batch} % {n}")
    report, params = train(sizes, sizes.compare_batch // n, seed)
    del params
    gc.collect()

    model = gpt(sizes, "flash")
    loss_fn = lm_loss_fn(model)
    tx = optax.adamw(LEARNING_RATE)

    @jax.jit
    def single_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    dev0 = jax.devices()[0]
    params = jax.device_put(init_params(model, seed), dev0)
    opt_state = jax.jit(tx.init)(params)
    batch = jax.device_put(token_batch(sizes, sizes.compare_batch, seed),
                           dev0)
    single = []
    for _ in range(sizes.train_steps):
        params, opt_state, loss = single_step(params, opt_state, batch)
        single.append(float(loss))
    check_losses(single)
    diffs = [abs(a - b) for a, b in zip(report["losses"], single)]
    if max(diffs) > DP_LOSS_TOL:
        raise AssertionError(
            f"{n}-chip and single-device losses differ by {max(diffs):.4f} "
            f"(tolerance {DP_LOSS_TOL}): {report['losses']} vs {single}")
    emit("dp_vs_single_device", size=n, global_batch=sizes.compare_batch,
         rows_per_chip=report["rows_per_chip"],
         losses_dp=report["losses"],
         losses_single=single, max_abs_diff=max(diffs),
         tolerance=DP_LOSS_TOL,
         batch_devices=report["batch_devices"],
         collectives_in_program=report["collectives_in_program"],
         compile_s=report["compile_s"])


def run(chips: int, sizes: Sizes, seed: int) -> None:
    """Every phase of a ``chips``-device run, in :func:`phases_for`
    order; raises on the first that fails.  ``hvd.init()`` has run."""
    import horovod_tpu as hvd

    if hvd.size() != chips:
        raise AssertionError(f"hvd.size()={hvd.size()}, asked for {chips}")
    collectives_phase()
    if chips == 1:
        params = trainer_phase(sizes, seed)
        gc.collect()
        server_phase(sizes, params, seed)
    else:
        data_parallel_phase(sizes, seed)
        gc.collect()
        dp_vs_single_phase(sizes, seed)


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4 = the data-parallel phases on one "
                             "four-chip host, and nothing else")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    import jax

    import horovod_tpu as hvd
    from horovod_tpu import native
    from horovod_tpu.utils.platform import place_compile_cache, require_tpu

    hvd.init()
    device = require_tpu()
    if len(jax.devices()) != args.chips:
        raise RuntimeError(f"--chips {args.chips} but JAX reports "
                           f"{len(jax.devices())} device(s)")
    print(json.dumps({
        "phase": "start", "jax": jax.__version__, "chips": args.chips,
        "device_kind": device.device_kind, "phases": phases_for(args.chips),
        "compile_cache": place_compile_cache(),
        "native_tier": native.available(),
        "gxx": shutil.which("g++")}), flush=True)
    t0 = time.perf_counter()
    run(args.chips, Sizes(), args.seed)
    print(json.dumps({"phase": "done",
                      "wall_s": round(time.perf_counter() - t0, 1),
                      "peak_gb": peak_gb(device),
                      "hbm_gb": round((device.memory_stats() or {}).get(
                          "bytes_limit", 0) / 1e9, 2)}), flush=True)
    print(final_line(jax.devices()), flush=True)


if __name__ == "__main__":
    main()
