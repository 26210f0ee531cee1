"""Serving-path benchmark: continuous-batching throughput + latency.

The serving twin of ``allreduce_bench.py``: drives the
``horovod_tpu.serve`` engine+batcher with a closed-loop synthetic
workload (random prompt lengths, per-request sampling params) and
emits the same JSON-lines contract — one row per finished request and
ONE trailing summary line:

    {"metric": "serving_tok_per_s", "value": ..., "unit": "tok/s",
     "ttft_ms_p50": ..., "ttft_ms_p99": ...,
     "tpot_ms_p50": ..., "tpot_ms_p99": ...,
     "occupancy_mean": ..., ...}

TTFT is measured from *submission* (queueing included — the number a
user feels), TPOT as the post-first-token cadence.  Without
``--cpu-mesh`` the run needs a TPU and fails where JAX finds none;
``--cpu-mesh`` asks for the virtual CPU mesh by name (default tiny
model) — a functional datapoint, never a device number.

**Prefix-heavy workload** (``--prefix-shared N``): every request
carries the same N-token system prompt plus a short unique tail — the
paged KV pool (serve/kv/) serves the shared prefix from resident
blocks, so the summary splits TTFT into ``ttft_miss_ms`` (first
request: full prefill) vs ``ttft_hit_ms`` (prefix served from cache)
and reports ``prefix_hit_ratio`` + KV pool occupancy.  Requests run
closed-loop-sequential in this mode so the hit/miss split measures
prefill work, not queue luck.  ``--spec-k K`` adds speculative
decoding (``--drafter self`` verifies against the target itself — the
perfect-drafter harness bound; deployments pass a distilled model) and
reports the accepted-token rate per verify step.

**Mixed-tenant QoS overload** (``--tenants SPEC``; docs/qos.md): an
open-loop multi-tenant arrival schedule against the weighted-fair,
preemption-enabled scheduler behind the QoS-gated router — an unloaded
interactive-only baseline phase, then the full flood.  Reports
per-class p99 TTFT/TPOT, goodput-under-overload, sheds/preemptions,
and ``interactive_ttft_degradation_x`` (the ISSUE 15 acceptance bound:
interactive p99 TTFT within 1.5× its unloaded value while batch floods
at 4× capacity).

Usage::

    python benchmarks/serving_bench.py --cpu-mesh          # tiny, functional
    python benchmarks/serving_bench.py --requests 128 --slots 16
    python benchmarks/serving_bench.py --prefix-shared 48 --spec-k 4
    python benchmarks/serving_bench.py \\
        --tenants "alice:interactive:2,bulk:batch:16"
    python benchmarks/serving_bench.py --out SERVING_r01.json
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import random
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

METRIC = "serving_tok_per_s"


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--requests", type=int, default=32,
                        help="measured requests (closed loop)")
    parser.add_argument("--warmup", type=int, default=2,
                        help="warmup requests excluded from stats "
                             "(compile noise otherwise owns ttft_p99)")
    parser.add_argument("--max-new-tokens", type=int, default=16)
    parser.add_argument("--prompt-min", type=int, default=4)
    parser.add_argument("--prompt-max", type=int, default=48)
    parser.add_argument("--slots", type=int, default=4,
                        help="continuous-batching slots")
    parser.add_argument("--queue-depth", type=int, default=64)
    parser.add_argument("--buckets", default="16,64",
                        help="prefill length buckets (comma-separated)")
    parser.add_argument("--temperature", type=float, default=0.0)
    parser.add_argument("--top-k", type=int, default=0)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--prefix-shared", type=int, default=0,
                        help="prefix-heavy workload: every request "
                             "shares this many leading prompt tokens "
                             "(a system prompt); 0 = off")
    parser.add_argument("--kv-cache", choices=("paged", "dense"),
                        default=None,
                        help="override HVD_TPU_SERVE_KV for the engine")
    parser.add_argument("--spec-k", type=int, default=0,
                        help="speculative decoding draft length; 0 = off")
    parser.add_argument("--drafter", choices=("none", "self"),
                        default=None,
                        help="drafter model for --spec-k (default: "
                             "'self' when --spec-k > 0)")
    # Tiny-but-real decoder; flags let a TPU run scale it up.
    parser.add_argument("--layers", type=int, default=2)
    parser.add_argument("--d-model", type=int, default=64)
    parser.add_argument("--heads", type=int, default=2)
    parser.add_argument("--vocab", type=int, default=512)
    parser.add_argument("--max-seq-len", type=int, default=128)
    parser.add_argument("--cpu-mesh", action="store_true",
                        help="force the virtual CPU mesh (functional "
                             "check, not a perf number)")
    parser.add_argument("--tp", type=int, default=0, metavar="N",
                        help="tensor-parallel replica mode (serve/tp.py; "
                             "docs/tp_serving.md): shard ONE replica's "
                             "engine over N devices on the MeshPlan "
                             "'tensor' axis, drive the same closed-loop "
                             "workload at TP=1 and TP=N (token-identity "
                             "checked), and measure a hot-swap manifest "
                             "pull at both degrees — per-shard pull "
                             "bytes must drop to <= 60% of the TP=1 "
                             "pull (the r19 acceptance bound)")
    parser.add_argument("--fleet", default=None, metavar="PREFILLxDECODE",
                        help="disaggregated fleet mode (serve/fleet/): "
                             "e.g. 1x2 builds 1 prefill + 2 decode "
                             "replicas behind the role-aware router, "
                             "drives an open-loop BURSTY workload, and "
                             "compares tail TTFT + migration overhead "
                             "against a unified fleet of the same chip "
                             "count")
    parser.add_argument("--collector", action="store_true",
                        help="fleet mode: re-run the fleet phase with "
                             "a live 1s telemetry collector "
                             "(obs/collector.py) scraping every "
                             "replica over the HMAC wire, and gate its "
                             "overhead — p99 TTFT with the collector "
                             "must stay within 1.05x the baseline "
                             "(collector_overhead_violations, "
                             "zero-tolerance; docs/observability.md)")
    parser.add_argument("--burst", type=int, default=0,
                        help="fleet/swap mode: requests per arrival "
                             "burst (default 2 x --slots)")
    parser.add_argument("--burst-interval", type=float, default=0.25,
                        help="fleet/swap mode: seconds between bursts")
    parser.add_argument("--swap", type=int, default=0, metavar="N",
                        help="zero-downtime hot-swap mode "
                             "(serve/swap.py): drive an open-loop "
                             "bursty load through a 2-replica fleet "
                             "while rolling N weight hot-swaps from a "
                             "checkpoint store; reports swap_latency_ms "
                             "(store-newer -> fleet fully flipped), "
                             "requests_dropped_during_swap (must be 0) "
                             "and in-window vs steady-state p99 TTFT")
    parser.add_argument("--swap-replicas", type=int, default=2,
                        help="swap mode: unified replicas behind the "
                             "router")
    parser.add_argument("--tenants", default=None, metavar="SPEC",
                        help="mixed-tenant QoS overload mode "
                             "(serve/qos/; docs/qos.md): comma-"
                             "separated tenant:class:count entries "
                             "(count = requests per burst), e.g. "
                             "'alice:interactive:2,bulk:batch:16'. "
                             "Drives an UNLOADED phase (interactive "
                             "only, the baseline) then an open-loop "
                             "OVERLOAD phase (all tenants) and reports "
                             "per-class p99 TTFT/TPOT, goodput under "
                             "overload, sheds/preemptions, and the "
                             "interactive TTFT degradation factor")
    parser.add_argument("--slo-ms", type=float, default=2000.0,
                        help="tenants mode: interactive TTFT SLO "
                             "(deadline + brownout trigger)")
    parser.add_argument("--trace", default=None, metavar="DIR",
                        help="write a merged per-run trace artifact "
                             "(Perfetto JSON + critical-path report; "
                             "docs/tracing.md) into DIR")
    parser.add_argument("--out", default=None,
                        help="also write the full run as a JSON artifact")
    args = parser.parse_args()
    if args.prompt_min < 1 or args.prompt_max < args.prompt_min:
        parser.error("--prompt-min/--prompt-max must satisfy "
                     "1 <= min <= max")
    prompt_cap = (args.prefix_shared + 8 if args.prefix_shared > 0
                  else args.prompt_max)
    if prompt_cap + args.max_new_tokens >= args.max_seq_len:
        parser.error("longest prompt + --max-new-tokens must fit below "
                     "--max-seq-len (the KV-cache length)")
    if args.spec_k > 0 and args.drafter is None:
        args.drafter = "self"

    if args.cpu_mesh:
        from horovod_tpu.utils.platform import force_cpu_mesh

        force_cpu_mesh()

    import jax
    import jax.numpy as jnp

    from horovod_tpu.models.transformer import GPT, GPTConfig
    from horovod_tpu.obs import trace as obs_trace
    from horovod_tpu.serve import (ContinuousBatcher, InferenceEngine,
                                   QueueFullError, SamplingParams,
                                   ServingStats)
    import horovod_tpu as hvd
    from horovod_tpu.utils.platform import place_compile_cache, require_tpu

    hvd.init()
    if not args.cpu_mesh:
        # Without --cpu-mesh the rows are device numbers: no TPU, no run.
        require_tpu()
        place_compile_cache()

    buckets = tuple(int(b) for b in args.buckets.split(",") if b.strip())
    cfg = GPTConfig(
        vocab_size=args.vocab, n_layer=args.layers, n_head=args.heads,
        d_model=args.d_model, d_ff=4 * args.d_model,
        max_seq_len=args.max_seq_len)
    model = GPT(cfg)
    rng = jax.random.PRNGKey(args.seed)
    params = model.init(rng, jnp.zeros((1, 8), jnp.int32))["params"]
    if args.tp > 1:
        run_tp(args, model, params, buckets)
        return
    if args.fleet:
        run_fleet(args, model, params, buckets)
        return
    if args.swap > 0:
        run_swap(args, model, params, buckets)
        return
    if args.tenants:
        run_tenants(args, model, params, buckets)
        return
    drafter = (model, params) if args.drafter == "self" else None
    engine = InferenceEngine(model, params, max_slots=args.slots,
                             prefill_buckets=buckets,
                             max_seq_len=args.max_seq_len,
                             kv_cache=args.kv_cache,
                             drafter=drafter,
                             spec_k=args.spec_k or None,
                             seed=args.seed)
    batcher = ContinuousBatcher(engine, max_queue=args.queue_depth,
                                default_deadline_s=0)

    py_rng = random.Random(args.seed)
    shared_prefix = [py_rng.randrange(args.vocab)
                     for _ in range(max(0, args.prefix_shared))]

    def mk_prompt():
        if args.prefix_shared > 0:
            tail = py_rng.randint(2, 8)
            return shared_prefix + [py_rng.randrange(args.vocab)
                                    for _ in range(tail)]
        n = py_rng.randint(args.prompt_min,
                           min(args.prompt_max, engine.prefill_buckets[-1]))
        return [py_rng.randrange(args.vocab) for _ in range(n)]

    sampling = SamplingParams(max_new_tokens=args.max_new_tokens,
                              temperature=args.temperature,
                              top_k=args.top_k,
                              spec=args.spec_k > 0)

    def submit_one(prompt):
        if not args.trace:
            return batcher.submit(prompt, sampling)
        # --trace: root one trace per request at admission (the router's
        # job in a real deployment).  submit() only enqueues, so the
        # root span's interval (submit -> finish) is only known at
        # completion: mint the identity now — the batcher captures it,
        # parenting its queued/prefill/decode phases under it — and
        # record the span itself in drive() once the request finishes.
        with obs_trace.use_context(obs_trace.new_context()):
            return batcher.submit(prompt, sampling)

    def drive(prompts, one_at_a_time=False):
        live = []
        if one_at_a_time:
            # Prefix-heavy mode: one request in flight at a time, so
            # the hit/miss TTFT split measures prefill work (resident
            # prefix vs full recompute), not queue scheduling luck.
            for p in prompts:
                req = submit_one(p)
                live.append(req)
                while not req.done.is_set():
                    batcher.step()
        else:
            pending = collections.deque(prompts)
            while pending or any(not r.done.is_set() for r in live):
                while pending:
                    try:
                        live.append(submit_one(pending[0]))
                        pending.popleft()
                    except QueueFullError:
                        break
                batcher.step()
        if args.trace:
            # Deferred roots: each request's span covers its full
            # submit->finish latency (monotonic, re-anchored onto the
            # span clock like the batcher's phases), so the artifact's
            # critical-path report attributes real request latency.
            now_us, now_mono = obs_trace.now_us(), time.monotonic()
            for r in live:
                if r.trace_ctx is None or r.finished_at is None:
                    continue
                obs_trace.record_span(
                    "hvd_tpu_serve_request", parent=None,
                    start_us=now_us - (now_mono - r.submitted_at) * 1e6,
                    dur_us=(r.finished_at - r.submitted_at) * 1e6,
                    ctx=r.trace_ctx,
                    args={"bench": METRIC, "tokens": len(r.tokens)})
        return live

    # Warmup compiles EVERY prefill bucket plus the decoder — a bucket
    # first touched inside the measured window would bill its compile
    # to some unlucky request's TTFT.
    warm = [[1] * b for b in engine.prefill_buckets
            if b < args.max_seq_len]
    warm += [mk_prompt() for _ in range(max(0, args.warmup - len(warm)))]
    drive(warm)
    batcher.stats = ServingStats()  # measured window starts clean
    if args.trace:
        obs_trace.clear()   # the artifact covers the measured window only
    t0 = time.perf_counter()
    done = drive([mk_prompt() for _ in range(args.requests)],
                 one_at_a_time=args.prefix_shared > 0)
    elapsed = time.perf_counter() - t0

    rows = []
    for r in done:
        row = {
            "request": r.request_id, "prompt_len": len(r.prompt),
            "tokens": len(r.tokens), "error": r.error,
            "prefix_hit": r.prefix_hit_tokens,
            "ttft_ms": (round((r.first_token_at - r.submitted_at) * 1e3, 3)
                        if r.first_token_at else None),
            "total_ms": (round((r.finished_at - r.submitted_at) * 1e3, 3)
                         if r.finished_at else None),
        }
        rows.append(row)
        print(json.dumps(row), flush=True)

    snap = batcher.snapshot()
    tokens_out = sum(len(r.tokens) for r in done if r.error is None)
    summary = {
        "metric": METRIC,
        "value": round(tokens_out / elapsed, 3) if elapsed > 0 else 0.0,
        "unit": "tok/s",
        "requests": args.requests,
        "failed": sum(1 for r in done if r.error is not None),
        "slots": args.slots,
        "prefill_buckets": list(engine.prefill_buckets),
        "max_new_tokens": args.max_new_tokens,
        "ttft_ms_p50": snap["ttft_ms_p50"],
        "ttft_ms_p99": snap["ttft_ms_p99"],
        "tpot_ms_p50": snap["tpot_ms_p50"],
        "tpot_ms_p99": snap["tpot_ms_p99"],
        "occupancy_mean": snap["occupancy_mean"],
        "model": {"layers": args.layers, "d_model": args.d_model,
                  "heads": args.heads, "vocab": args.vocab},
    }
    if args.prefix_shared > 0:
        from horovod_tpu.serve.metrics import percentile as _pct

        def _mean_ttft(reqs):
            # Median, not mean: the miss class is often a single
            # sample and a host-scheduling spike inside one hit would
            # otherwise swamp the structural prefill gap.
            vals = [(r.first_token_at - r.submitted_at) * 1e3
                    for r in reqs
                    if r.error is None and r.first_token_at is not None]
            v = _pct(vals, 50)
            return round(v, 3) if v is not None else None

        hits = [r for r in done if r.prefix_hit_tokens > 0]
        misses = [r for r in done if r.prefix_hit_tokens == 0]
        summary.update({
            "prefix_shared": args.prefix_shared,
            "ttft_hit_ms": _mean_ttft(hits),       # cache-hit TTFT
            "ttft_miss_ms": _mean_ttft(misses),    # full-prefill TTFT
            "prefix_hit_ratio": snap.get("prefix_hit_ratio"),
            "kv_blocks_cached": snap.get("kv_blocks_cached"),
            "kv_blocks_in_use": snap.get("kv_blocks_in_use"),
            "kv_evictions": snap.get("kv_evictions_total"),
            "kv_cow_copies": snap.get("kv_cow_copies_total"),
        })
    if args.spec_k > 0:
        summary["spec_k"] = args.spec_k
        summary["spec_accept_per_verify"] = snap.get(
            "spec_accept_per_verify")
    trace_block = None
    if args.trace:
        # Merged per-run trace artifact (single-process merge) — a
        # diagnostic block like "metrics"; bench_regress skips "trace".
        os.makedirs(args.trace, exist_ok=True)
        tpath = os.path.join(args.trace, f"TRACE_{METRIC}.json")
        rep = obs_trace.dump_merged(tpath)
        trace_block = {"file": tpath,
                       **({"critical_path": rep} if rep else {})}
        summary["trace"] = trace_block
    print(json.dumps(summary))
    if args.out:
        # Diagnostic telemetry block (bench_regress skips "metrics").
        from horovod_tpu.obs import export as obs_export

        with open(args.out, "w") as f:
            json.dump({"platform": jax.default_backend(),
                       "device_kind": jax.devices()[0].device_kind,
                       "summary": summary, "stats": snap, "rows": rows,
                       "metrics": obs_export.json_snapshot()["metrics"],
                       **({"trace": trace_block} if trace_block else {})},
                      f, indent=1)


def run_tp(args, model, params, buckets) -> None:
    """Tensor-parallel replica bench (serve/tp.py; docs/tp_serving.md):
    the SAME closed-loop workload runs on a TP=1 engine and a TP=N
    engine (one model sharded over N devices on the MeshPlan ``tensor``
    axis), then a hot-swap manifest pull runs at both degrees against
    the same perturbed checkpoint.  Three claims, all checked here:

    * **token identity** — the sharded engine emits bit-identical
      tokens (column-parallel matmuls keep full contractions per
      output element; docs/tp_serving.md) — the run aborts otherwise;
    * **TPOT vs TP degree** — decode cadence at each degree (on the
      virtual CPU mesh a functional datapoint; on real chips the
      speedup curve);
    * **swap pull bytes** — each shard pulls only its owned parameter
      slices (``plan.tp_owned_slice``), so the replica's critical-path
      pull (max over shards) must be <= 60% of the TP=1 pull for the
      same manifest diff — the r19 acceptance bound, asserted.
    """
    import shutil
    import tempfile

    import jax
    import numpy as np

    from horovod_tpu.ckpt import ShardStore, take_snapshot
    from horovod_tpu.serve import (ContinuousBatcher, InferenceEngine,
                                   QueueFullError, SamplingParams,
                                   ServingStats, WeightSubscriber)

    tp = args.tp
    if args.heads % tp:
        raise SystemExit(f"--tp {tp} must divide --heads {args.heads} "
                         f"(attention heads shard head-wise)")
    if len(jax.devices()) < tp:
        raise SystemExit(f"--tp {tp} needs >= {tp} devices; pass "
                         f"--cpu-mesh for the 8-way virtual CPU mesh")

    py_rng = random.Random(args.seed)
    prompts = [[py_rng.randrange(args.vocab)
                for _ in range(py_rng.randint(args.prompt_min,
                                              args.prompt_max))]
               for _ in range(args.requests)]
    sampling = SamplingParams(max_new_tokens=args.max_new_tokens,
                              temperature=args.temperature,
                              top_k=args.top_k)

    def bench_degree(deg):
        engine = InferenceEngine(
            model, params, max_slots=args.slots,
            prefill_buckets=buckets, max_seq_len=args.max_seq_len,
            kv_cache="paged", tp=deg, seed=args.seed)
        batcher = ContinuousBatcher(engine, max_queue=args.queue_depth,
                                    default_deadline_s=0)

        def drive(ps):
            live, pending = [], collections.deque(ps)
            while pending or any(not r.done.is_set() for r in live):
                while pending:
                    try:
                        live.append(batcher.submit(pending[0], sampling))
                        pending.popleft()
                    except QueueFullError:
                        break
                batcher.step()
            return live

        warm = [[1] * b for b in engine.prefill_buckets
                if b < args.max_seq_len]
        drive(warm)
        batcher.stats = ServingStats()
        t0 = time.perf_counter()
        done = drive(list(prompts))
        elapsed = time.perf_counter() - t0
        snap = batcher.snapshot()
        toks = sum(len(r.tokens) for r in done if r.error is None)
        return {
            "tok_per_s": (round(toks / elapsed, 3)
                          if elapsed > 0 else 0.0),
            "tpot_ms_p50": snap["tpot_ms_p50"],
            "tpot_ms_p99": snap["tpot_ms_p99"],
            "failed": sum(1 for r in done if r.error is not None),
            "tokens": [list(r.tokens) for r in done],
        }

    base = bench_degree(1)
    sharded = bench_degree(tp)
    identical = base["tokens"] == sharded["tokens"]
    if not identical:
        raise SystemExit(
            f"TP={tp} tokens diverged from TP=1 — the sharded forward "
            f"is not bitwise-identical (docs/tp_serving.md)")

    # --- swap-pull phase: same manifest diff, both degrees ------------------
    def perturbed(v):
        # Perturb EVERY leaf so the manifest diff covers the whole
        # model — the pull-ratio then measures the shard ownership
        # split, not which leaf happened to change.
        leaf_rng = random.Random(1000 + v)

        def bump(x):
            return x + np.float32(1e-3 * leaf_rng.random())

        return jax.tree_util.tree_map(bump, params)

    store_dir = tempfile.mkdtemp(prefix="tp_bench_store_")
    try:
        store = ShardStore(store_dir)
        host = jax.tree_util.tree_map(np.asarray, params)
        store.write_step(take_snapshot(host, step=1), world=1,
                         scheme="dp")
        host2 = jax.tree_util.tree_map(np.asarray, perturbed(2))
        store.write_step(take_snapshot(host2, step=2), world=1,
                         scheme="dp")

        def pull_bytes(deg):
            engine = InferenceEngine(
                model, params, max_slots=args.slots,
                prefill_buckets=buckets, max_seq_len=args.max_seq_len,
                kv_cache="paged", tp=deg, weights_version=1,
                seed=args.seed)
            batcher = ContinuousBatcher(engine,
                                        max_queue=args.queue_depth,
                                        default_deadline_s=0)
            batcher.start()   # the flip commits at the batcher barrier
            try:
                sub = WeightSubscriber(batcher, store_dir)
                info = sub.swap_to_info(2)
                return int(info["pulled_bytes"])
            finally:
                batcher.stop()

        pulled_tp1 = pull_bytes(1)
        pulled_tp = pull_bytes(tp)
    finally:
        shutil.rmtree(store_dir, ignore_errors=True)

    ratio = round(pulled_tp / pulled_tp1, 4) if pulled_tp1 else None
    summary = {
        "metric": "serving_tp_tok_per_s",
        "value": sharded["tok_per_s"],
        "unit": "tok/s",
        "tp": tp,
        "requests": args.requests,
        "failed": sharded["failed"],
        "tokens_identical": identical,
        "tok_per_s_tp1": base["tok_per_s"],
        "tpot_ms_p50": sharded["tpot_ms_p50"],
        "tpot_ms_p99": sharded["tpot_ms_p99"],
        "tpot_tp1_ms_p50": base["tpot_ms_p50"],
        "tpot_tp1_ms_p99": base["tpot_ms_p99"],
        # Swap economics: the replica's critical-path pull is the max
        # over its shards' parallel pulls; <= 0.6x TP=1 is acceptance.
        "swap_pulled_bytes_tp1": pulled_tp1,
        "swap_pulled_bytes_tp": pulled_tp,
        "swap_pull_ratio": ratio,
        "swap_pull_ratio_bound": 0.6,
        "model": {"layers": args.layers, "d_model": args.d_model,
                  "heads": args.heads, "vocab": args.vocab},
    }
    print(json.dumps(summary))
    if args.out:
        from horovod_tpu.obs import export as obs_export

        with open(args.out, "w") as f:
            json.dump({"platform": jax.default_backend(),
                       "device_kind": jax.devices()[0].device_kind,
                       "summary": summary,
                       "metrics": obs_export.json_snapshot()["metrics"]},
                      f, indent=1)
    if ratio is not None and ratio > 0.6:
        raise SystemExit(
            f"swap pull ratio {ratio} exceeds the 0.6 bound: TP={tp} "
            f"shards are not pulling ~1/{tp} of the manifest diff")


def run_tenants(args, model, params, buckets) -> None:
    """Mixed-tenant QoS overload bench (docs/qos.md): a weighted-fair,
    preemption-enabled replica behind the QoS-gated router, driven by
    an open-loop multi-tenant arrival schedule.  Two phases over
    identical fleets:

    * **unloaded** — interactive tenants only: the baseline p99 TTFT
      the SLO is judged against;
    * **overload** — every tenant, with the batch flood at whatever
      multiple of capacity the spec encodes.

    The acceptance numbers: ``interactive_ttft_degradation_x``
    (overload p99 / unloaded p99 — the ISSUE 15 bound is 1.5×),
    per-class goodput under overload (batch degrades *gracefully*:
    smaller, not zero, and nothing collapses globally), and the
    shed/preemption counters showing the machinery that did it."""
    import threading

    import jax

    from horovod_tpu.serve import (BrownoutController, BudgetExhaustedError,
                                   ContinuousBatcher, FleetController,
                                   InferenceEngine, InferenceServer,
                                   QosGate, ReplicaLauncher, ReplicaSpec,
                                   RequestShedError, Router, ServingStats)
    from horovod_tpu.serve.metrics import percentile as _pct
    from horovod_tpu.utils.retry import RetryPolicy

    key = b"serving-bench-qos-key-012345678"
    specs = []
    try:
        for entry in args.tenants.split(","):
            tenant, cls, count = entry.strip().split(":")
            if cls not in ("interactive", "standard", "batch"):
                raise ValueError
            specs.append((tenant.strip(), cls, int(count)))
        if not specs or any(c < 1 for _, _, c in specs):
            raise ValueError
    except ValueError:
        raise SystemExit(
            f"--tenants expects tenant:class:count entries (class in "
            f"interactive|standard|batch), got {args.tenants!r}")
    slo_s = args.slo_ms / 1e3
    py_rng = random.Random(args.seed)

    def mk_prompt():
        n = py_rng.randint(args.prompt_min, args.prompt_max)
        return [py_rng.randrange(args.vocab) for _ in range(n)]

    def build():
        engine = InferenceEngine(
            model, params, max_slots=args.slots,
            prefill_buckets=buckets, max_seq_len=args.max_seq_len,
            kv_cache=args.kv_cache or "paged", seed=args.seed)
        batcher = ContinuousBatcher(engine, max_queue=args.queue_depth,
                                    default_deadline_s=0,
                                    qos_slo_ttft_ms=args.slo_ms)
        server = InferenceServer(batcher, key=key, name="qos-rep",
                                 host="127.0.0.1")
        router = Router(
            [ReplicaSpec(server.name, [("127.0.0.1", server.port)])],
            key, retry_policy=RetryPolicy(attempts=4, base_delay_s=0.05,
                                          max_delay_s=0.5))
        # The shed ladder is the SECOND line of defense: preemption
        # fires at the request SLO, shedding only on a sustained 4x
        # breach (preemption can no longer keep up) or a near-full
        # queue — "shed batch first", never a hair-trigger.
        gate = QosGate(brownout=BrownoutController(
            queue_capacity=args.queue_depth, high=0.9, low=0.5,
            hold_s=2 * args.burst_interval,
            slo_ttft_ms=4 * args.slo_ms))
        router.attach_qos(gate)
        # The controller feeds the brownout ladder the fleet signals;
        # pinned replica counts keep the base launcher un-called.
        controller = FleetController(router, ReplicaLauncher(),
                                     min_per_role=1, max_replicas=1,
                                     qos_gate=gate)
        return server, batcher, router, gate, controller

    # ONE arrival stagger for every phase, derived from the FULL spec:
    # the unloaded baseline must drive interactive at the same arrival
    # cadence as the overload phase (only the flood differs), or the
    # degradation factor compares different intra-class queueing, not
    # the flood's effect.
    full_per_burst = sum(c for _, _, c in specs)
    arrival_gap = args.burst_interval / (2 * max(1, full_per_burst))

    def drive_phase(router, gate, controller, tag, phase_specs,
                    bursts, prompt_fn):
        rows, lock, threads = [], threading.Lock(), []
        stop_poll = threading.Event()
        state = {"max_level": 0}

        def poll_loop():
            while not stop_poll.is_set():
                controller.poll_once()
                state["max_level"] = max(state["max_level"],
                                         gate.brownout.level)
                stop_poll.wait(args.burst_interval)

        def fire(rid, tenant, cls, prompt):
            t0 = time.perf_counter()
            row = {"request": rid, "tenant": tenant, "class": cls,
                   "error": None, "shed": False, "ttft_ms": None,
                   "tokens": 0, "latency_ms": None}
            try:
                # The completion deadline is decoupled from (and far
                # looser than) the TTFT SLO: the SLO drives preemption
                # urgency, the deadline only bounds true runaways.
                resp = router.generate(
                    prompt, max_new_tokens=args.max_new_tokens,
                    deadline_s=(max(8 * slo_s, 10.0)
                                if cls == "interactive" else None),
                    request_id=rid, tenant=tenant, qos_class=cls)
                row["error"] = resp.error
                row["ttft_ms"] = resp.ttft_ms
                row["tokens"] = len(resp.tokens or ())
            except RequestShedError as e:
                row["error"], row["shed"] = "shed", True
                row["retry_after_s"] = round(e.retry_after_s, 3)
            except BudgetExhaustedError as e:
                row["error"] = "budget_exhausted"
                row["retry_after_s"] = round(e.retry_after_s, 3)
            except Exception as e:   # router gave up: a lost request
                row["error"] = str(e)
            row["latency_ms"] = round((time.perf_counter() - t0) * 1e3, 3)
            with lock:
                rows.append(row)

        poller = threading.Thread(target=poll_loop, daemon=True)
        poller.start()
        t_start = time.perf_counter()
        j = 0
        # Arrivals are open-loop (the clock, not completions, gates
        # them) but staggered inside each burst: real traffic at 4x
        # capacity is a sustained rate, not N simultaneous sockets —
        # and an instantaneous N-thread stampede measures the host's
        # GIL, not the scheduler.
        gap = arrival_gap
        for b in range(bursts):
            if b:
                time.sleep(args.burst_interval / 2)
            for tenant, cls, count in phase_specs:
                for _ in range(count):
                    th = threading.Thread(
                        target=fire,
                        args=(f"{tag}-{j}", tenant, cls, prompt_fn()),
                        daemon=True)
                    th.start()
                    threads.append(th)
                    j += 1
                    time.sleep(gap)
        for th in threads:
            th.join(timeout=300.0)
        elapsed = time.perf_counter() - t_start
        stop_poll.set()
        poller.join(timeout=10.0)
        with lock:
            out = list(rows)
        hung = sum(1 for th in threads if th.is_alive())
        if hung:
            out.extend({"request": f"{tag}-hung-{i}", "tenant": "?",
                        "class": "?", "error": "hung_past_join_timeout",
                        "shed": False, "ttft_ms": None, "tokens": 0,
                        "latency_ms": None} for i in range(hung))
        return out, elapsed, state["max_level"]

    def cls_agg(rows, elapsed, cls):
        mine = [r for r in rows if r["class"] == cls]
        ok = [r for r in mine if r["error"] is None]
        ttfts = [r["ttft_ms"] for r in ok if r["ttft_ms"] is not None]
        tpots = [(r["latency_ms"] - r["ttft_ms"]) / (r["tokens"] - 1)
                 for r in ok
                 if r["ttft_ms"] is not None and r["tokens"] > 1
                 and r["latency_ms"] is not None]
        toks = sum(r["tokens"] for r in ok)
        return {
            "requests": len(mine), "completed": len(ok),
            "failed": sum(1 for r in mine
                          if r["error"] is not None and not r["shed"]),
            "shed": sum(1 for r in mine if r["shed"]),
            "goodput_tok_per_s": (round(toks / elapsed, 3)
                                  if elapsed > 0 else 0.0),
            "ttft_ms_p99": (round(_pct(ttfts, 99), 3) if ttfts else None),
            "tpot_ms_p99": (round(_pct(tpots, 99), 3) if tpots else None),
        }

    inter_specs = [s for s in specs if s[1] == "interactive"]
    if not inter_specs:
        raise SystemExit("--tenants needs at least one interactive "
                         "tenant (the SLO class the bench measures)")

    # Warmup prompts are FIXED and shared, and cycle over EVERY
    # prefill bucket: beyond the per-bucket prefill and decode
    # programs this compiles the COW copy path (shared partial block
    # -> kv_copy) and the larger buckets preemption-resume recompute
    # lands in — a 100ms compile spike inside a ~10ms p99 would swamp
    # the degradation factor with noise.
    warm_lens = sorted({max(2, min(b - 2, args.max_seq_len
                                   - args.max_new_tokens - 2))
                        for b in buckets})
    _warm_i = collections.deque(warm_lens * 64)

    def warm_prompt():
        _warm_i.rotate(-1)
        return [7] * _warm_i[0]

    def run_phase(tag, phase_specs):
        server, batcher, router, gate, controller = build()
        try:
            drive_phase(router, gate, controller, f"{tag}-warm",
                        phase_specs, 3, warm_prompt)
            # Measured window starts clean: replica-side stats (which
            # feed the brownout SLO signal) must not carry warmup
            # compile spikes.
            batcher.stats = ServingStats(
                weights_version=batcher.engine.weights_version)
            rows, elapsed, max_level = drive_phase(
                router, gate, controller, tag, phase_specs,
                args.requests, mk_prompt)
            return rows, elapsed, max_level, \
                router.replica_stats(timeout=5.0)
        finally:
            server.shutdown()

    # Phase 1 — unloaded baseline (fresh fleet, interactive only);
    # phase 2 — overload (identical fresh fleet, all tenants).
    un_rows, un_elapsed, _, _ = run_phase("qos-base", inter_specs)
    ov_rows, ov_elapsed, max_level, fleet_stats = run_phase(
        "qos-load", specs)

    for row in ov_rows:
        print(json.dumps(row), flush=True)

    inter = cls_agg(ov_rows, ov_elapsed, "interactive")
    std = cls_agg(ov_rows, ov_elapsed, "standard")
    batch = cls_agg(ov_rows, ov_elapsed, "batch")
    un_inter = cls_agg(un_rows, un_elapsed, "interactive")
    preempts = sum(e["stats"].get("preemptions", 0)
                   for e in fleet_stats.values() if "stats" in e)
    total_ok_toks = sum(r["tokens"] for r in ov_rows
                        if r["error"] is None)
    degradation = None
    if inter["ttft_ms_p99"] and un_inter["ttft_ms_p99"]:
        degradation = round(inter["ttft_ms_p99"]
                            / un_inter["ttft_ms_p99"], 3)
    summary = {
        "metric": "serving_qos_tok_per_s",
        "value": (round(total_ok_toks / ov_elapsed, 3)
                  if ov_elapsed > 0 else 0.0),
        "unit": "tok/s",
        "tenants": args.tenants,
        "requests": args.requests,
        "slo_ms": args.slo_ms,
        "failed_interactive": inter["failed"],
        "interactive_ttft_ms_p99": inter["ttft_ms_p99"],
        "interactive_tpot_ms_p99": inter["tpot_ms_p99"],
        "interactive_goodput_tok_per_s": inter["goodput_tok_per_s"],
        "interactive_unloaded_ttft_ms_p99": un_inter["ttft_ms_p99"],
        # The ISSUE 15 acceptance bound: <= 1.5 with batch flooding at
        # 4x capacity ("ttft" in the name keeps bench_regress's
        # direction lower-is-better).
        "interactive_ttft_degradation_x": degradation,
        "standard_ttft_ms_p99": std["ttft_ms_p99"],
        "standard_goodput_tok_per_s": std["goodput_tok_per_s"],
        "batch_ttft_ms_p99": batch["ttft_ms_p99"],
        "batch_tpot_ms_p99": batch["tpot_ms_p99"],
        "batch_goodput_tok_per_s": batch["goodput_tok_per_s"],
        # Operational counters ride a nested block (bench_regress
        # compares only top-level numerics — a busier run shedding
        # more is not a perf regression).
        "qos_counters": {
            "sheds_batch": batch["shed"], "sheds_standard": std["shed"],
            "preemptions": preempts, "brownout_level_max": max_level,
            "batch_completed": batch["completed"],
            "batch_requests": batch["requests"],
        },
        "model": {"layers": args.layers, "d_model": args.d_model,
                  "heads": args.heads, "vocab": args.vocab},
    }
    print(json.dumps(summary))
    if args.out:
        from horovod_tpu.obs import export as obs_export

        with open(args.out, "w") as f:
            json.dump({"platform": jax.default_backend(),
                       "device_kind": jax.devices()[0].device_kind,
                       "summary": summary, "rows": ov_rows,
                       "unloaded_rows": un_rows,
                       "fleet_stats": {
                           k: e.get("stats") for k, e in
                           fleet_stats.items()},
                       "metrics": obs_export.json_snapshot()["metrics"]},
                      f, indent=1)


def run_swap(args, model, params, buckets) -> None:
    """Hot-swap bench: an open-loop bursty load runs CONTINUOUSLY over
    a small unified fleet while the controller rolls ``--swap`` weight
    deployments from a checkpoint store (each step a perturbed param
    set committed with manifests + digests).  The three numbers the
    acceptance reads:

    * ``swap_latency_ms`` — store-newer → fleet fully flipped (every
      replica reporting the new version), per swap and mean;
    * ``requests_dropped_during_swap`` — requests submitted inside any
      swap window that did NOT complete successfully (must be 0: a
      swap holds admission briefly, it never sheds work);
    * ``ttft_swap_ms_p99`` vs ``ttft_steady_ms_p99`` — what the flip
      barrier costs the tail while it drains.
    """
    import shutil
    import tempfile

    key = b"serving-bench-swap-key-01234567"
    store_dir = tempfile.mkdtemp(prefix="swap_bench_store_")
    try:
        _run_swap_inner(args, model, params, buckets, key, store_dir)
    finally:
        # One full weight snapshot per version lives here — repeated
        # bench/soak runs must not accumulate them in /tmp.
        shutil.rmtree(store_dir, ignore_errors=True)


def _run_swap_inner(args, model, params, buckets, key, store_dir) -> None:
    import threading

    import jax
    import numpy as np

    from horovod_tpu.ckpt import ShardStore, take_snapshot
    from horovod_tpu.serve import (ContinuousBatcher, FleetController,
                                   InferenceEngine, InferenceServer,
                                   ReplicaLauncher, ReplicaSpec, Router)
    from horovod_tpu.serve.metrics import percentile as _pct
    from horovod_tpu.utils.retry import RetryPolicy

    store = ShardStore(store_dir)

    def version_params(v):
        # Version 1 is the boot set; later versions perturb ONE block's
        # weights (a fine-tune-like delta: the manifest diff should
        # move a fraction of the bytes, not the model).
        if v == 1:
            return params
        leaf_rng = jax.random.PRNGKey(1000 + v)
        flat, treedef = jax.tree_util.tree_flatten(params)
        flat = list(flat)
        flat[0] = flat[0] + 1e-3 * v * jax.random.normal(
            leaf_rng, flat[0].shape, flat[0].dtype)
        return jax.tree_util.tree_unflatten(treedef, flat)

    host = jax.tree_util.tree_map(np.asarray, version_params(1))
    store.write_step(take_snapshot(host, step=1), world=1, scheme="dp")

    n_rep = max(1, args.swap_replicas)
    servers = []
    for i in range(n_rep):
        engine = InferenceEngine(
            model, params, max_slots=args.slots,
            prefill_buckets=buckets, max_seq_len=args.max_seq_len,
            kv_cache=args.kv_cache or "paged", weights_version=1,
            seed=args.seed)
        batcher = ContinuousBatcher(engine, max_queue=args.queue_depth,
                                    default_deadline_s=0)
        servers.append(InferenceServer(
            batcher, key=key, name=f"swap-rep-{i}", host="127.0.0.1",
            swap_store=store_dir, subscribe=False))
    router = Router(
        [ReplicaSpec(s.name, [("127.0.0.1", s.port)]) for s in servers],
        key, retry_policy=RetryPolicy(attempts=8, base_delay_s=0.05,
                                      max_delay_s=0.5))
    controller = FleetController(router, ReplicaLauncher(),
                                 min_per_role=1)

    py_rng = random.Random(args.seed)

    def mk_prompt():
        n = py_rng.randint(args.prompt_min, args.prompt_max)
        return [py_rng.randrange(args.vocab) for _ in range(n)]

    burst = args.burst or 2 * args.slots
    rows, rows_lock = [], threading.Lock()
    stop_load = threading.Event()
    threads = []

    def fire(rid, prompt):
        t0 = time.perf_counter()
        try:
            resp = router.generate(prompt,
                                   max_new_tokens=args.max_new_tokens,
                                   request_id=rid)
            err, ttft, ver = (resp.error, resp.ttft_ms,
                              resp.weights_version)
            n_tok = len(resp.tokens or ())
        except Exception as e:
            err, ttft, ver, n_tok = str(e), None, None, 0
        with rows_lock:
            rows.append({"request": rid, "submitted": t0, "error": err,
                         "ttft_ms": ttft, "tokens": n_tok,
                         "weights_version": ver,
                         "latency_ms": round(
                             (time.perf_counter() - t0) * 1e3, 3)})

    def load_loop():
        j = 0
        while not stop_load.is_set():
            for _ in range(burst):
                th = threading.Thread(target=fire,
                                      args=(f"swap-req-{j}", mk_prompt()),
                                      daemon=True)
                th.start()
                threads.append(th)
                j += 1
            stop_load.wait(args.burst_interval)

    # Warmup compiles every replica's programs before measurement.
    warm = [threading.Thread(target=fire, args=(f"warm-{i}", mk_prompt()),
                             daemon=True) for i in range(2 * n_rep)]
    for t in warm:
        t.start()
    for t in warm:
        t.join(timeout=120.0)
    with rows_lock:
        rows.clear()

    loader = threading.Thread(target=load_loop, daemon=True)
    t_bench0 = time.perf_counter()
    loader.start()
    swap_windows = []
    swaps = []
    for s in range(2, args.swap + 2):
        time.sleep(2 * args.burst_interval)
        host_s = jax.tree_util.tree_map(np.asarray, version_params(s))
        w0 = time.perf_counter()
        store.write_step(take_snapshot(host_s, step=s), world=1,
                         scheme="dp")
        outcomes = controller.roll_swap(s, timeout=120.0)
        w1 = time.perf_counter()
        ok = all(o["ok"] for o in outcomes)
        swap_windows.append((w0, w1))
        swaps.append({"step": s, "ok": ok,
                      "swap_latency_ms": round((w1 - w0) * 1e3, 3),
                      "pulled_bytes": sum(o["pulled_bytes"] or 0
                                          for o in outcomes),
                      "outcomes": outcomes})
    # One rollback through the same path (the journaled-step drill).
    time.sleep(args.burst_interval)
    rb0 = time.perf_counter()
    rb = controller.rollback(1, timeout=120.0)
    rollback_ms = round((time.perf_counter() - rb0) * 1e3, 3)
    time.sleep(2 * args.burst_interval)
    stop_load.set()
    loader.join(timeout=30.0)   # stop appending before iterating
    for th in threads:
        th.join(timeout=120.0)
    elapsed = time.perf_counter() - t_bench0
    for s in servers:
        s.shutdown()

    def in_window(row):
        t = row["submitted"]
        return any(w0 <= t <= w1 + 0.001 for w0, w1 in swap_windows)

    with rows_lock:
        all_rows = list(rows)
    ok_rows = [r for r in all_rows if r["error"] is None]
    swap_rows = [r for r in all_rows if in_window(r)]
    steady_rows = [r for r in all_rows if not in_window(r)]
    dropped_during_swap = sum(1 for r in swap_rows
                              if r["error"] is not None)
    ttft_swap = [r["ttft_ms"] for r in swap_rows
                 if r["error"] is None and r["ttft_ms"] is not None]
    ttft_steady = [r["ttft_ms"] for r in steady_rows
                   if r["error"] is None and r["ttft_ms"] is not None]
    lat = [s["swap_latency_ms"] for s in swaps]
    toks = sum(r["tokens"] for r in ok_rows)
    summary = {
        "metric": "serving_swap_tok_per_s",
        "value": round(toks / elapsed, 3) if elapsed > 0 else 0.0,
        "unit": "tok/s",
        "swaps": len(swaps),
        "swaps_ok": sum(1 for s in swaps if s["ok"]),
        "replicas": n_rep,
        "requests": len(all_rows),
        "failed": len(all_rows) - len(ok_rows),
        "requests_dropped_during_swap": dropped_during_swap,
        "requests_during_swap": len(swap_rows),
        "swap_latency_ms_mean": (round(sum(lat) / len(lat), 3)
                                 if lat else None),
        "swap_latency_ms_max": (round(max(lat), 3) if lat else None),
        "swap_pulled_bytes_total": sum(s["pulled_bytes"] for s in swaps),
        "rollback_ms": rollback_ms,
        "rollback_ok": all(o["ok"] for o in rb),
        "ttft_swap_ms_p99": (round(_pct(ttft_swap, 99), 3)
                             if ttft_swap else None),
        "ttft_steady_ms_p99": (round(_pct(ttft_steady, 99), 3)
                               if ttft_steady else None),
        "model": {"layers": args.layers, "d_model": args.d_model,
                  "heads": args.heads, "vocab": args.vocab},
    }
    for s in swaps:
        print(json.dumps({k: v for k, v in s.items()
                          if k != "outcomes"}), flush=True)
    print(json.dumps(summary))
    if args.out:
        from horovod_tpu.obs import export as obs_export

        with open(args.out, "w") as f:
            json.dump({"platform": jax.default_backend(),
                       "device_kind": jax.devices()[0].device_kind,
                       "summary": summary, "swaps": swaps,
                       "rows": all_rows,
                       "metrics": obs_export.json_snapshot()["metrics"]},
                      f, indent=1)


def run_fleet(args, model, params, buckets) -> None:
    """Disaggregated-fleet bench: PREFILLxDECODE replicas behind the
    role-aware router vs a UNIFIED fleet of the same chip count, both
    under the same open-loop bursty arrival schedule.  Open loop means
    arrivals fire on the clock whether or not earlier requests
    finished — the regime where tail TTFT actually shows queueing, and
    the number a closed loop structurally hides."""
    import threading

    import jax

    from horovod_tpu.serve import (ContinuousBatcher, InferenceEngine,
                                   InferenceServer, ReplicaSpec, Router)
    from horovod_tpu.serve.metrics import percentile as _pct
    from horovod_tpu.utils.retry import RetryPolicy

    key = b"serving-bench-fleet-key-0123456"
    try:
        p_n, d_n = (int(x) for x in args.fleet.lower().split("x"))
        if p_n < 1 or d_n < 1:
            raise ValueError
    except ValueError:
        raise SystemExit(f"--fleet expects PREFILLxDECODE (e.g. 1x2), "
                         f"got {args.fleet!r}")

    py_rng = random.Random(args.seed)

    def mk_prompt():
        n = py_rng.randint(args.prompt_min, args.prompt_max)
        return [py_rng.randrange(args.vocab) for _ in range(n)]

    def build(roles):
        servers = []
        for i, role in enumerate(roles):
            engine = InferenceEngine(
                model, params, max_slots=args.slots,
                prefill_buckets=buckets, max_seq_len=args.max_seq_len,
                kv_cache=args.kv_cache or "paged", seed=args.seed)
            batcher = ContinuousBatcher(engine, max_queue=args.queue_depth,
                                        default_deadline_s=0, role=role)
            servers.append(InferenceServer(batcher, key=key,
                                           name=f"{role}-{i}",
                                           host="127.0.0.1"))
        router = Router(
            [ReplicaSpec(s.name, [("127.0.0.1", s.port)], role=s.role)
             for s in servers], key,
            retry_policy=RetryPolicy(attempts=8, base_delay_s=0.05,
                                     max_delay_s=0.5))
        return servers, router

    burst = args.burst or 2 * args.slots

    def drive(router, prompts, tag):
        """Open-loop bursty arrivals: ``burst`` requests fire together,
        then the clock (not completion) gates the next burst.  ``tag``
        namespaces request ids per drive — warmup and measured share a
        router, and a reused id would dedupe-hit the warmup response
        instead of running the measured request."""
        results, lock, threads = [], threading.Lock(), []

        def fire(j, prompt):
            t0 = time.perf_counter()
            try:
                resp = router.generate(prompt,
                                       max_new_tokens=args.max_new_tokens,
                                       request_id=f"{tag}-{j}")
                err, ttft = resp.error, resp.ttft_ms
                migrated = resp.migrated_to is not None
                mig_ms = resp.migrate_ms
                n_tok = len(resp.tokens or ())
            except Exception as e:   # router gave up: a lost request
                err, ttft, migrated, mig_ms, n_tok = (str(e), None,
                                                      False, None, 0)
            with lock:
                results.append({
                    "request": f"{tag}-{j}", "error": err,
                    "ttft_ms": ttft, "migrated": migrated,
                    "migrate_ms": mig_ms, "tokens": n_tok,
                    "latency_ms": round(
                        (time.perf_counter() - t0) * 1e3, 3)})

        t_start = time.perf_counter()
        for j, prompt in enumerate(prompts):
            if j and j % burst == 0:
                time.sleep(args.burst_interval)
            th = threading.Thread(target=fire, args=(j, prompt),
                                  daemon=True)
            th.start()
            threads.append(th)
        for th in threads:
            th.join(timeout=300.0)
        with lock:
            done_ids = {r["request"] for r in results}
            # Abandoned (still-hung) request threads never appended a
            # row: record them as failed instead of letting a lost
            # request silently vanish from the summary's failed count.
            for j in range(len(prompts)):
                if f"{tag}-{j}" not in done_ids:
                    results.append({"request": f"{tag}-{j}",
                                    "error": "hung_past_join_timeout",
                                    "ttft_ms": None, "migrated": False,
                                    "migrate_ms": None, "tokens": 0,
                                    "latency_ms": None})
        return results, time.perf_counter() - t_start

    # One prompt set, generated ONCE and reused by both phases: the
    # fleet-vs-unified comparison must differ only in fleet shape, not
    # in workload (a shared RNG stream across phases would hand the
    # second phase different prompt lengths and prefix behavior).
    warm_n = max(args.warmup, 2 * (p_n + d_n))
    warm_prompts = [mk_prompt() for _ in range(warm_n)]
    measured_prompts = [mk_prompt() for _ in range(args.requests)]

    def phase(roles, tag="fleet-req", with_collector=False):
        servers, router = build(roles)
        plane = stop = scraper = None
        try:
            # Warmup compiles every replica's programs (prefill buckets,
            # decode, import) so compiles don't bill measured TTFT.
            drive(router, warm_prompts, "warm")
            if with_collector:
                # The live telemetry plane at its production cadence:
                # one concurrent StatsRequest sweep per second over the
                # same HMAC wire the measured requests ride.
                from horovod_tpu.obs.collector import (FleetCollector,
                                                       Target,
                                                       TelemetryPlane)
                targets = [Target(name=s.name,
                                  addresses=(("127.0.0.1", s.port),),
                                  role=s.role) for s in servers]
                plane = TelemetryPlane(
                    FleetCollector(targets, key=key, timeout_s=1.0),
                    period_s=1.0)
                stop = threading.Event()

                def scrape_loop():
                    while not stop.is_set():
                        plane.run_round()
                        stop.wait(plane.period_s)

                scraper = threading.Thread(target=scrape_loop,
                                           daemon=True)
                scraper.start()
            rows, elapsed = drive(router, measured_prompts, tag)
            if stop is not None:
                stop.set()
                scraper.join(timeout=10.0)
            stats = router.replica_stats(timeout=5.0)
            occ = {}
            for entry in stats.values():
                if "stats" not in entry:
                    continue
                occ.setdefault(entry["role"], []).append(
                    entry["stats"].get("occupancy_mean") or 0.0)
            occ = {role: round(sum(v) / len(v), 4)
                   for role, v in occ.items() if v}
            return rows, elapsed, occ, plane
        finally:
            if stop is not None:
                stop.set()
            for s in servers:
                s.shutdown()

    fleet_rows, fleet_s, fleet_occ, _ = phase(
        ["prefill"] * p_n + ["decode"] * d_n)
    unified_rows, unified_s, _, _ = phase(["unified"] * (p_n + d_n))

    for row in fleet_rows:
        print(json.dumps(row), flush=True)

    def agg(rows, elapsed):
        ok = [r for r in rows if r["error"] is None]
        ttfts = [r["ttft_ms"] for r in ok if r["ttft_ms"] is not None]
        toks = sum(r["tokens"] for r in ok)
        return {
            "failed": len(rows) - len(ok),
            "tok_per_s": round(toks / elapsed, 3) if elapsed > 0 else 0.0,
            "ttft_ms_p50": (round(_pct(ttfts, 50), 3) if ttfts else None),
            "ttft_ms_p99": (round(_pct(ttfts, 99), 3) if ttfts else None),
        }

    fa, ua = agg(fleet_rows, fleet_s), agg(unified_rows, unified_s)
    col_block = {}
    if args.collector:
        # Collector-overhead gate: identical fleet shape + prompt set,
        # with the 1s scrape plane live through the measured window.
        col_rows, col_s, _, plane = phase(
            ["prefill"] * p_n + ["decode"] * d_n, tag="fleet-col",
            with_collector=True)
        ca = agg(col_rows, col_s)
        overhead = None
        if fa["ttft_ms_p99"] and ca["ttft_ms_p99"]:
            overhead = ca["ttft_ms_p99"] / fa["ttft_ms_p99"]
        col_block = {
            "collector_ttft_ms_p50": ca["ttft_ms_p50"],
            "collector_ttft_ms_p99": ca["ttft_ms_p99"],
            "collector_failed": ca["failed"],
            "collect_rounds": (plane.collector.rounds
                               if plane is not None else 0),
            "collector_overhead_x": (round(overhead, 4)
                                     if overhead is not None else None),
            # The r20 acceptance bound: a live 1s collector may not
            # move serving p99 TTFT past 1.05x baseline.
            "collector_overhead_violations": int(
                overhead is None or overhead > 1.05),
        }
    migs = [r["migrate_ms"] for r in fleet_rows
            if r["migrate_ms"] is not None]
    summary = {
        "metric": "serving_fleet_tok_per_s",
        "value": fa["tok_per_s"],
        "unit": "tok/s",
        "fleet": args.fleet,
        "requests": args.requests,
        "burst": burst,
        "failed": fa["failed"],
        "ttft_ms_p50": fa["ttft_ms_p50"],
        "ttft_ms_p99": fa["ttft_ms_p99"],
        "migrations": len(migs),
        "migrate_ms_mean": (round(sum(migs) / len(migs), 3)
                            if migs else None),
        "migrate_ms_p99": (round(_pct(migs, 99), 3) if migs else None),
        "occupancy_prefill": fleet_occ.get("prefill"),
        "occupancy_decode": fleet_occ.get("decode"),
        # Same chip count, same arrival schedule, no disaggregation:
        # the comparison baseline for the tail-TTFT claim.
        "unified_failed": ua["failed"],
        "unified_tok_per_s": ua["tok_per_s"],
        "unified_ttft_ms_p50": ua["ttft_ms_p50"],
        "unified_ttft_ms_p99": ua["ttft_ms_p99"],
        **col_block,
        "model": {"layers": args.layers, "d_model": args.d_model,
                  "heads": args.heads, "vocab": args.vocab},
    }
    print(json.dumps(summary))
    if args.out:
        from horovod_tpu.obs import export as obs_export

        with open(args.out, "w") as f:
            json.dump({"platform": jax.default_backend(),
                       "device_kind": jax.devices()[0].device_kind,
                       "summary": summary,
                       "rows": fleet_rows,
                       "unified_rows": unified_rows,
                       "metrics": obs_export.json_snapshot()["metrics"]},
                      f, indent=1)


if __name__ == "__main__":
    main()
