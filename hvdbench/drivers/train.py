"""Traffic kind ``train``: the program's own train step
(``hvd.make_train_step`` over ``hvd.DistributedOptimizer``) fed from a
ring of seeded token batches staged on the device, a new one each step.
Steps are dispatched without a fence between them; whole optimizer
steps are counted between two ``block_until_ready`` fences.

Set-up builds ONE object — the compiled step with its state — drives it
through its first steps on the ring's first batches, and hands that
same object to the window.  After the window has closed and the
program's state is freed, the reference follows those first steps from
the seed and the readings are compared.
"""

from __future__ import annotations

import gc
import statistics
import time

from hvdbench import check, device, generator
from hvdbench.reduce import xplane

SPANS = ("train_step_dispatch", "train_window")


def _first_adam_state(opt_state):
    import jax
    import optax

    found = [x for x in jax.tree.leaves(
        opt_state, is_leaf=lambda n: isinstance(n, optax.ScaleByAdamState))
        if isinstance(x, optax.ScaleByAdamState)]
    if len(found) != 1:
        raise RuntimeError(f"expected one Adam state in the optimizer "
                           f"state, found {len(found)}")
    return found[0]


def run(ctx) -> dict:
    import jax
    import optax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.models.transformer import lm_loss_fn
    from horovod_tpu.parallel.train import shard_batch

    cfg, traffic, seed = ctx.config, ctx.traffic, ctx.seed
    family = ctx.family
    split = ctx.setup_split

    t = time.monotonic()
    hvd.init()
    devices = device.require_chips(ctx.cell["chips"], ctx.rehearsal)
    n = hvd.size()
    if n != len(devices):
        raise RuntimeError(f"hvd.size()={n} on {len(devices)} devices")
    gm = hvd.global_mesh()
    split["init_s"] = time.monotonic() - t

    t = time.monotonic()
    opt_cfg = {k: v for k, v in cfg["run"]["optimizer"].items()
               if k != "name"}
    rows = int(cfg["run"]["rows_per_chip"]) * n
    if rows != int(traffic["rows"]):
        raise RuntimeError(
            f"traffic asks for {traffic['rows']} rows a step; the "
            f"configuration's {cfg['run']['rows_per_chip']} a chip on "
            f"{n} chips make {rows}")
    model = family.build_model(cfg, cfg["run"]["attention"])
    params = family.make_params(cfg, seed, gm.replicated())
    tx = hvd.DistributedOptimizer(optax.adamw(**opt_cfg))
    opt_state = jax.jit(tx.init, out_shardings=gm.replicated())(params)
    step = hvd.make_train_step(lm_loss_fn(model), tx)
    ring = [shard_batch(generator.train_batch(
        traffic, seed, i, rows, cfg["vocab_size"]), gm.mesh,
        P(gm.axis_name)) for i in range(int(traffic["ring"]))]
    jax.block_until_ready((params, opt_state, ring))
    split["state_s"] = time.monotonic() - t

    # The first steps, through the window's own call and feed.
    t = time.monotonic()
    b1 = float(opt_cfg["b1"])
    grad_norms_fn = jax.jit(lambda mu: jax.tree.map(
        lambda x: x / (1.0 - b1), family.leaf_norms_like_reference(mu)))
    n_checked = int(traffic["checked_steps"])
    losses, grad_norms = [], None
    for i in range(n_checked):
        params, opt_state, loss = step(params, opt_state, ring[i])
        if i == 0:
            grad_norms = jax.device_get(
                grad_norms_fn(_first_adam_state(opt_state).mu))
        losses.append(float(loss))
    delta_norms = jax.device_get(family.delta_norms(cfg, params, seed))
    split["first_steps_s"] = time.monotonic() - t

    # Calibration: fenced steps, to size the window in whole steps.
    t = time.monotonic()
    times = []
    k = n_checked
    for _ in range(int(traffic["calibration_steps"])):
        t0 = time.monotonic()
        params, opt_state, loss = step(params, opt_state,
                                       ring[k % len(ring)])
        loss.block_until_ready()
        times.append(time.monotonic() - t0)
        k += 1
    step_s = statistics.median(times)
    split["calibration_s"] = time.monotonic() - t
    n_steps = max(1, int(ctx.seconds / step_s))
    n_traced = min(int(traffic["trace_steps"]), n_steps) if ctx.trace else 0

    def dispatch(count, k):
        nonlocal params, opt_state, loss
        for _ in range(count):
            if ctx.trace:
                with jax.profiler.TraceAnnotation("train_step_dispatch"):
                    params, opt_state, loss = step(params, opt_state,
                                                   ring[k % len(ring)])
            else:
                params, opt_state, loss = step(params, opt_state,
                                               ring[k % len(ring)])
            k += 1
        return k

    # The window.
    counter = device.CompileCounter()
    loss.block_until_ready()
    t_open = time.monotonic()
    k = dispatch(n_steps - n_traced, k)
    trace_path = trace_window = None
    if n_traced:
        loss.block_until_ready()
        trace_dir = device.start_trace(ctx.cell["name"])
        t_tr0 = time.monotonic()
        with jax.profiler.TraceAnnotation("train_window"):
            k = dispatch(n_traced, k)
            loss.block_until_ready()
        t_close = time.monotonic()
        trace_window = t_close - t_tr0
        trace_path = device.stop_trace(trace_dir)
    else:
        loss.block_until_ready()
        t_close = time.monotonic()
    compilations = counter.count
    elapsed = t_close - t_open
    last_loss = float(loss)
    memory = device.memory_record(devices)
    tokens_per_step = rows * int(traffic["seq_len"])

    # Free the program's state, then let the reference follow.
    del params, opt_state, ring, step, loss
    gc.collect()
    t = time.monotonic()
    batches = [generator.train_batch(traffic, seed, i, rows,
                                     cfg["vocab_size"])
               for i in range(n_checked)]
    want = ctx.reference.train_readings(
        seed, ctx.reference.sizes(cfg), batches, opt_cfg,
        rows_per_block=int(cfg["check"]["reference_rows_per_block"]))
    split["reference_after_window_s"] = time.monotonic() - t
    got = {"losses": losses, "grad_norms": grad_norms,
           "delta_norms": delta_norms, "last_loss": last_loss}
    checks = check.train_checks(got, want, cfg["check"]["limits"])

    facts = {"steps": n_steps, "elapsed_s": elapsed, "step_s_fenced": step_s,
             "tokens_per_step": tokens_per_step, "chips": n,
             "traced_steps": n_traced, "trace_window_s": trace_window,
             "window_compilations": compilations,
             "losses": losses, "last_loss": last_loss,
             "seq_len": int(traffic["seq_len"]),
             "rows_per_chip": int(cfg["run"]["rows_per_chip"])}
    return {
        "t_window_open": t_open,
        "attempted": n_steps, "failed": 0,
        "end_to_end": {
            "train_tokens_per_s": n_steps * tokens_per_step / elapsed},
        "checks": checks, "facts": facts, "devices": devices,
        "memory": memory, "trace_path": trace_path, "spans": SPANS,
    }
