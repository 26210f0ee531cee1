"""The headline benchmark artifact itself: ``bench.py`` must always
print its one-line JSON contract (the driver consumes it blindly at
round end — a crash there loses the round's perf datapoint)."""

import json
import os
import subprocess
import sys

import pytest

# End-to-end bench harness runs (50-60s each) carry their own
# @pytest.mark.slow; the bench_regress smoke tests below are pure-Python
# and tier-1-safe (no module-wide slow mark).

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_bench(*flags):
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench.py"), "--preset", "tiny",
         "--iters", "1", "--steps-per-call", "1", "--warmup", "0", *flags],
        capture_output=True, text=True, timeout=420,
        env={**os.environ, "XLA_FLAGS": "", "JAX_PLATFORMS": ""},
    )
    assert out.returncode == 0, out.stderr[-800:]
    line = out.stdout.strip().splitlines()[-1]
    return json.loads(line)


@pytest.mark.slow
def test_bench_json_contract():
    row = _run_bench()
    assert row["unit"] == "images/sec/chip"
    assert row["value"] > 0
    assert "metric" in row and "vs_baseline" in row


@pytest.mark.slow
def test_bench_fp16_allreduce_flag():
    row = _run_bench("--fp16-allreduce")
    assert row["fp16_allreduce"] is True
    assert row["value"] > 0


@pytest.mark.slow
@pytest.mark.parametrize("script", ["bench.py",
                                    os.path.join("benchmarks",
                                                 "gpt_bench.py")])
def test_full_preset_without_tpu_exits_nonzero(script):
    """A full-preset number is a device number: with no TPU the run
    fails with a traceback — never a ``value: 0.0`` line, never a CPU
    run under the device metric's name."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, script)],
        capture_output=True, text=True, timeout=180,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""},
    )
    assert out.returncode != 0, out.stdout[-400:]
    assert "Traceback" in out.stderr and "needs a TPU" in out.stderr
    assert '"value"' not in out.stdout


@pytest.mark.slow
def test_serving_bench_json_contract():
    """ISSUE 3 satellite: the serving bench must produce its JSON
    report on CPU — tok/s plus TTFT/TPOT percentiles and occupancy."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks",
                                      "serving_bench.py"),
         "--cpu-mesh",
         "--requests", "4", "--warmup", "1", "--max-new-tokens", "4",
         "--buckets", "16", "--slots", "2", "--prompt-max", "12"],
        capture_output=True, text=True, timeout=420,
        env={**os.environ, "XLA_FLAGS": "", "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stderr[-800:]
    row = json.loads(out.stdout.strip().splitlines()[-1])
    assert row["metric"] == "serving_tok_per_s"
    assert row["unit"] == "tok/s"
    assert row["value"] > 0
    assert row["failed"] == 0
    for key in ("ttft_ms_p50", "ttft_ms_p99", "tpot_ms_p50",
                "tpot_ms_p99", "occupancy_mean"):
        assert row[key] is not None and row[key] > 0, (key, row)


@pytest.mark.slow
def test_serving_bench_prefix_heavy_contract(tmp_path):
    """ISSUE 10 satellite: the prefix-heavy workload reports cache-hit
    vs cache-miss TTFT, KV pool occupancy, and the speculative
    accepted-token rate; hit TTFT beats miss TTFT (resident prefix =
    suffix-bucket prefill) and self-drafting accepts > 1 token per
    verify step."""
    out_path = str(tmp_path / "serving_prefix.json")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks",
                                      "serving_bench.py"),
         "--cpu-mesh",
         "--requests", "8", "--warmup", "1", "--max-new-tokens", "6",
         "--buckets", "16,128", "--slots", "2", "--max-seq-len", "192",
         "--d-model", "128", "--prefix-shared", "112", "--spec-k", "2",
         "--out", out_path],
        capture_output=True, text=True, timeout=420,
        env={**os.environ, "XLA_FLAGS": "", "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stderr[-800:]
    row = json.loads(out.stdout.strip().splitlines()[-1])
    assert row["failed"] == 0
    # Cache-hit TTFT strictly below cache-miss TTFT: the miss pays the
    # 128-bucket prefill, a hit runs only the <=16-token suffix — an 8x
    # prefill-length gap, so the inequality is structural, not timing
    # luck.
    assert row["ttft_hit_ms"] < row["ttft_miss_ms"], row
    assert row["prefix_hit_ratio"] >= 0.8, row
    assert row["kv_blocks_cached"] > 0 or row["kv_blocks_in_use"] > 0
    # Speculative accepted-token rate > 1 token per verify step.
    assert row["spec_accept_per_verify"] > 1.0, row
    with open(out_path) as f:
        artifact = json.load(f)
    assert artifact["stats"]["kv_prefix_hits_total"] >= 7
    assert artifact["stats"]["spec_accept_per_verify"] > 1.0
    assert "metrics" in artifact   # embedded telemetry block


@pytest.mark.slow
def test_serving_bench_fleet_contract(tmp_path):
    """ISSUE 11 satellite: the disaggregated-fleet bench runs on CPU
    and reports per-role occupancy, migration overhead per request,
    and p99 TTFT for both the fleet and the same-chip-count unified
    regime; ``bench_regress`` accepts the artifact."""
    out_path = str(tmp_path / "serving_fleet.json")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks",
                                      "serving_bench.py"),
         "--cpu-mesh",
         "--fleet", "1x1", "--requests", "6", "--warmup", "1",
         "--max-new-tokens", "4", "--buckets", "16", "--slots", "2",
         "--prompt-max", "12", "--burst", "3", "--burst-interval",
         "0.05", "--out", out_path],
        capture_output=True, text=True, timeout=420,
        env={**os.environ, "XLA_FLAGS": "", "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stderr[-800:]
    row = json.loads(out.stdout.strip().splitlines()[-1])
    assert row["metric"] == "serving_fleet_tok_per_s"
    assert row["value"] > 0
    assert row["failed"] == 0 and row["unified_failed"] == 0
    # Every request crossed the fleet: prefill->decode KV migration
    # with measurable per-request overhead.
    assert row["migrations"] > 0
    assert row["migrate_ms_mean"] and row["migrate_ms_mean"] > 0
    assert row["ttft_ms_p99"] and row["ttft_ms_p99"] > 0
    assert row["unified_ttft_ms_p99"] and row["unified_ttft_ms_p99"] > 0
    assert "occupancy_prefill" in row and "occupancy_decode" in row
    regress = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts",
                                      "bench_regress.py"),
         out_path, out_path],
        capture_output=True, text=True, timeout=60)
    assert regress.returncode == 0, regress.stdout[-500:]


@pytest.mark.slow
def test_serving_bench_tp_contract(tmp_path):
    """ISSUE 19 satellite + acceptance: the tensor-parallel replica
    bench runs TP=1 and TP=2 over the same workload (token identity is
    asserted inside the bench — it exits non-zero on divergence),
    reports TPOT at both degrees, and shows the hot-swap manifest pull
    dropping to <= 60% of the TP=1 bytes; ``bench_regress`` accepts
    the artifact."""
    out_path = str(tmp_path / "serving_tp.json")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks",
                                      "serving_bench.py"),
         "--tp", "2", "--cpu-mesh", "--requests", "6", "--warmup", "1",
         "--max-new-tokens", "4", "--buckets", "16", "--slots", "2",
         "--prompt-max", "12", "--out", out_path],
        capture_output=True, text=True, timeout=420,
        env={**os.environ, "XLA_FLAGS": "", "JAX_PLATFORMS": ""},
    )
    assert out.returncode == 0, out.stderr[-800:]
    row = json.loads(out.stdout.strip().splitlines()[-1])
    assert row["metric"] == "serving_tp_tok_per_s"
    assert row["tp"] == 2
    assert row["value"] > 0 and row["tok_per_s_tp1"] > 0
    assert row["failed"] == 0
    assert row["tokens_identical"] is True
    assert row["tpot_ms_p50"] and row["tpot_tp1_ms_p50"]
    # The r19 acceptance bound: a TP=2 swap pull moves <= 60% of the
    # bytes the TP=1 replica pulls for the same manifest diff.
    assert row["swap_pulled_bytes_tp1"] > 0
    assert row["swap_pull_ratio"] <= 0.6, row
    artifact = json.load(open(out_path))
    assert artifact["summary"]["swap_pull_ratio"] <= 0.6
    assert "metrics" in artifact
    regress = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts",
                                      "bench_regress.py"),
         out_path, out_path],
        capture_output=True, text=True, timeout=60)
    assert regress.returncode == 0, regress.stdout[-500:]


@pytest.mark.slow
def test_serving_bench_swap_contract(tmp_path):
    """ISSUE 14 satellite: the hot-swap bench drives bursty load
    through rolling weight swaps from a checkpoint store and reports
    swap latency, requests dropped during the swap window (must be 0)
    and in-window vs steady-state p99 TTFT; ``bench_regress`` accepts
    the artifact."""
    out_path = str(tmp_path / "serving_swap.json")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks",
                                      "serving_bench.py"),
         "--cpu-mesh",
         "--swap", "2", "--swap-replicas", "2", "--slots", "2",
         "--max-new-tokens", "4", "--buckets", "16", "--prompt-max",
         "12", "--burst", "2", "--burst-interval", "0.2",
         "--out", out_path],
        capture_output=True, text=True, timeout=420,
        env={**os.environ, "XLA_FLAGS": "", "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stderr[-800:]
    row = json.loads(out.stdout.strip().splitlines()[-1])
    assert row["metric"] == "serving_swap_tok_per_s"
    assert row["swaps"] == 2 and row["swaps_ok"] == 2
    assert row["failed"] == 0
    assert row["requests_dropped_during_swap"] == 0
    assert row["swap_latency_ms_mean"] and row["swap_latency_ms_mean"] > 0
    # The manifest diff moved bytes (a perturbed leaf per version).
    assert row["swap_pulled_bytes_total"] > 0
    assert row["rollback_ok"] is True and row["rollback_ms"] > 0
    artifact = json.load(open(out_path))
    assert artifact["summary"]["requests_dropped_during_swap"] == 0
    assert len(artifact["swaps"]) == 2
    assert "metrics" in artifact
    regress = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts",
                                      "bench_regress.py"),
         out_path, out_path],
        capture_output=True, text=True, timeout=60)
    assert regress.returncode == 0, regress.stdout[-500:]


@pytest.mark.slow
def test_serving_bench_tenants_contract(tmp_path):
    """ISSUE 15 satellite + acceptance: the mixed-tenant overload
    bench reports per-class p99 TTFT/TPOT and goodput-under-overload,
    and with batch flooding at 4x capacity the interactive p99 TTFT
    stays within 1.5x its unloaded value while batch goodput degrades
    gracefully (sheds > 0, completions > 0 — no global collapse);
    ``bench_regress`` accepts the artifact."""
    out_path = str(tmp_path / "serving_qos.json")

    def run_once():
        out = subprocess.run(
            [sys.executable, os.path.join(ROOT, "benchmarks",
                                          "serving_bench.py"),
             "--cpu-mesh",
             "--tenants",
             "alice:interactive:2,bob:standard:2,bulk:batch:12",
             "--requests", "16", "--max-new-tokens", "12",
             "--buckets", "16,32", "--slots", "2", "--prompt-max", "12",
             "--max-seq-len", "64", "--burst-interval", "0.25",
             "--slo-ms", "25", "--out", out_path],
            capture_output=True, text=True, timeout=420,
            env={**os.environ, "XLA_FLAGS": "", "JAX_PLATFORMS": "cpu"},
        )
        assert out.returncode == 0, out.stderr[-800:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    row = run_once()
    if (row["interactive_ttft_degradation_x"] is None
            or row["interactive_ttft_degradation_x"] > 1.5):
        # p99 over ~32 samples is one scheduling hiccup away from its
        # max on shared CI hardware; the bound must hold on a clean
        # re-measurement, not on the unluckier of two runs.
        row = run_once()
    assert row["metric"] == "serving_qos_tok_per_s"
    assert row["value"] > 0
    # The SLO class never fails under the flood.
    assert row["failed_interactive"] == 0
    for key in ("interactive_ttft_ms_p99", "interactive_tpot_ms_p99",
                "interactive_goodput_tok_per_s",
                "interactive_unloaded_ttft_ms_p99",
                "batch_ttft_ms_p99", "batch_goodput_tok_per_s"):
        assert row[key] is not None and row[key] > 0, (key, row)
    # THE acceptance bound: interactive p99 TTFT within 1.5x its
    # unloaded value while batch floods at 4x capacity...
    assert row["interactive_ttft_degradation_x"] is not None
    assert row["interactive_ttft_degradation_x"] <= 1.5, row
    # ...while batch degrades gracefully, not to zero: the brownout
    # shed SOME batch (overload was real) and batch still completed
    # work (no global collapse).
    qc = row["qos_counters"]
    assert qc["sheds_batch"] > 0, qc
    assert qc["batch_completed"] > 0, qc
    assert row["batch_goodput_tok_per_s"] > 0
    artifact = json.load(open(out_path))
    assert artifact["summary"]["interactive_ttft_degradation_x"] <= 1.5
    assert "metrics" in artifact and "unloaded_rows" in artifact
    regress = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts",
                                      "bench_regress.py"),
         out_path, out_path],
        capture_output=True, text=True, timeout=60)
    assert regress.returncode == 0, regress.stdout[-500:]


@pytest.mark.slow
def test_serving_bench_trace_artifact(tmp_path):
    """ISSUE 7 satellite: ``--trace DIR`` writes a merged Perfetto
    trace for the measured window and embeds its path + critical-path
    report under ``"trace"`` (which bench_regress skips)."""
    trace_dir = str(tmp_path / "traces")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks",
                                      "serving_bench.py"),
         "--cpu-mesh",
         "--requests", "3", "--warmup", "1", "--max-new-tokens", "4",
         "--buckets", "16", "--slots", "2", "--prompt-max", "12",
         "--trace", trace_dir],
        capture_output=True, text=True, timeout=420,
        env={**os.environ, "XLA_FLAGS": "", "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stderr[-800:]
    row = json.loads(out.stdout.strip().splitlines()[-1])
    tblock = row["trace"]
    assert os.path.isfile(tblock["file"]), tblock
    with open(tblock["file"]) as f:
        perfetto = json.load(f)
    events = perfetto["traceEvents"]
    assert any(e.get("ph") == "X" and
               e.get("name") == "hvd_tpu_serve_request" for e in events)
    # The report names the phase that dominated request latency.
    assert tblock["critical_path"]["total_us"] > 0
    assert tblock["critical_path"]["dominant"]


@pytest.mark.slow
def test_checkpoint_bench_json_contract(tmp_path):
    """ISSUE 9 satellite: the checkpoint bench reports sync vs async
    save stall, the N→N′ restore rows, bytes moved per rank, and a
    bench_regress-compatible artifact — and the measured async stall
    clears the <10% acceptance ratio on the CPU tier."""
    out_path = str(tmp_path / "ckpt_bench.json")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks",
                                      "checkpoint_bench.py"),
         "--mb", "32", "--iters", "3", "--world", "4",
         "--out", out_path],
        capture_output=True, text=True, timeout=420,
        env={**os.environ, "XLA_FLAGS": "", "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stderr[-800:]
    row = json.loads(out.stdout.strip().splitlines()[-1])
    assert row["metric"] == "ckpt_async_save_stall_ms"
    assert row["unit"] == "ms"
    assert row["value"] > 0 and row["sync_save_ms"] > 0
    # THE acceptance ratio: async save stall < 10% of the sync wall.
    assert row["stall_time_frac"] < 0.10, row
    with open(out_path) as f:
        artifact = json.load(f)
    assert "metrics" in artifact and "rows" in artifact
    worlds = {r["world_to"] for r in artifact["rows"]}
    assert worlds == {2, 4, 8}             # N/2, N, 2N
    for r in artifact["rows"]:
        assert r["bytes_per_rank_max"] <= r["bytes_total"]
        assert r["value"] > 0
    # Doubling the world must shrink what any one rank moves.
    by_world = {r["world_to"]: r for r in artifact["rows"]}
    assert by_world[8]["bytes_per_rank_max"] < \
        by_world[2]["bytes_per_rank_max"]
    # bench_regress accepts the artifact against itself (exit 0).
    rc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts",
                                      "bench_regress.py"),
         out_path, out_path],
        capture_output=True, text=True, timeout=120).returncode
    assert rc == 0


@pytest.mark.slow
def test_bench_rejects_nonpositive_batch_size():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench.py"), "--preset", "tiny",
         "--batch-size", "0"],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "XLA_FLAGS": "", "JAX_PLATFORMS": ""},
    )
    assert out.returncode != 0
    assert "positive" in out.stderr


def test_no_benchmark_entrypoint_hides_a_missing_device():
    """The exit-0 outage scaffolding is gone for good: no entry point
    imports a backend probe or wraps init — each calls ``hvd.init()``
    and lets a missing backend raise — and none places the compile
    cache itself (``utils.platform.place_compile_cache`` honours
    ``JAX_COMPILATION_CACHE_DIR``)."""
    import glob

    entrypoints = [os.path.join(ROOT, "bench.py"),
                   os.path.join(ROOT, "chip_smoke.py")] + sorted(
        glob.glob(os.path.join(ROOT, "benchmarks", "*.py")))
    assert len(entrypoints) >= 7
    banned = ("backend_probe", "guarded_init", "jax_compilation_cache_dir",
              "os.execv", "os._exit")
    offenders = [(os.path.basename(path), word)
                 for path in entrypoints
                 for word in banned if word in open(path).read()]
    assert not offenders, offenders
    assert not os.path.exists(
        os.path.join(ROOT, "horovod_tpu", "utils", "backend_probe.py"))


@pytest.mark.slow
def test_gpt_bench_overlap_contract():
    """ISSUE 4 acceptance: `gpt_bench.py --microbatches N --overlap`
    emits a JSON row with tokens/s AND the estimated hidden-comm
    fraction on CPU."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "gpt_bench.py"),
         "--preset", "tiny", "--microbatches", "4", "--overlap",
         "--compressor", "bf16", "--iters", "1", "--steps-per-call", "1",
         "--warmup", "0"],
        capture_output=True, text=True, timeout=420,
        env={**os.environ, "XLA_FLAGS": "", "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stderr[-800:]
    row = json.loads(out.stdout.strip().splitlines()[-1])
    assert row["unit"] == "tokens/sec/chip" and row["value"] > 0
    assert row["microbatches"] == 4
    assert row["overlap"] is True
    assert row["compressor"] == "bf16"
    assert 0.0 <= row["hidden_comm_frac_est"] <= 1.0
    assert row["hidden_comm_frac_est"] > 0.0
    assert row["hidden_comm_basis"] in ("modeled_peak", "measured_wall")


@pytest.mark.slow
def test_allreduce_bench_topology_contract(tmp_path):
    """ISSUE 8 acceptance: `allreduce_bench.py --topology PODSxCHIPS`
    sweeps flat vs two-phase vs hierarchical on the simulated two-tier
    mesh, every row carries the per-size modeled costs + the compiler's
    `chosen` pick, the summary asserts modeled-vs-chosen agreement, and
    the artifact diffs cleanly through bench_regress."""
    art = tmp_path / "topo.json"
    out = subprocess.run(
        [sys.executable,
         os.path.join(ROOT, "benchmarks", "allreduce_bench.py"),
         "--topology", "2x4", "--cpu-mesh", "--min-elems", "4096",
         "--max-elems", "65536", "--iters", "1", "--warmup", "0",
         "--out", str(art)],
        capture_output=True, text=True, timeout=420,
        env={**os.environ, "XLA_FLAGS": "", "JAX_PLATFORMS": ""},
    )
    assert out.returncode == 0, out.stderr[-800:]
    doc = json.loads(art.read_text())
    summary, rows = doc["summary"], doc["rows"]
    assert summary["vehicle"] == "topo_schedule_wire"
    assert summary["topology"] == "2x4"
    assert summary["modeled_vs_chosen_agree"] is True
    assert summary["crossover_bytes"] > 0
    assert summary["metric"] == "allreduce_topo_hierarchical_busbw_peak"
    assert summary["value"] > 0
    paths = {r["path"] for r in rows}
    assert paths == {"flat", "two_phase", "hierarchical"}
    for r in rows:
        assert r["chosen"] in ("flat", "two_phase", "hierarchical")
        assert r["modeled_flat_us"] > 0
        assert r["modeled_hierarchical_us"] > 0
    # bench_regress reads the {"summary", "rows"} artifact shape.
    regress = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "bench_regress.py"),
         str(art), str(art)],
        capture_output=True, text=True, timeout=60)
    assert regress.returncode == 0, regress.stderr


# --- scripts/bench_regress.py (tier-1-safe: pure-Python JSON diffing) --------

def _regress(tmp_path, old, new, *flags):
    a, b = tmp_path / "old.json", tmp_path / "new.json"
    a.write_text(json.dumps(old))
    b.write_text(json.dumps(new))
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "bench_regress.py"),
         str(a), str(b), *flags],
        capture_output=True, text=True, timeout=60)


def test_bench_regress_passes_on_improvement(tmp_path):
    old = {"metric": "tok_per_s", "value": 100.0, "mfu_pct": 10.0}
    new = {"metric": "tok_per_s", "value": 120.0, "mfu_pct": 12.0}
    out = _regress(tmp_path, old, new)
    assert out.returncode == 0, out.stderr
    report = json.loads(out.stdout)
    assert report["regressions"] == 0 and report["compared"] == 2


def test_bench_regress_fails_on_regression(tmp_path):
    old = {"metric": "tok_per_s", "value": 100.0}
    new = {"metric": "tok_per_s", "value": 85.0}   # -15% > 10% tolerance
    out = _regress(tmp_path, old, new)
    assert out.returncode == 1
    assert "REGRESSION" in out.stderr
    report = json.loads(out.stdout)
    assert report["rows"][0]["regressed"] is True


def test_bench_regress_threshold_flag(tmp_path):
    old = {"metric": "tok_per_s", "value": 100.0}
    new = {"metric": "tok_per_s", "value": 95.0}   # -5%
    assert _regress(tmp_path, old, new).returncode == 0
    assert _regress(tmp_path, old, new,
                    "--threshold", "0.02").returncode == 1


def test_bench_regress_lower_is_better_metrics(tmp_path):
    old = {"metric": "serving", "value": 50.0, "ttft_ms_p99": 100.0}
    new = {"metric": "serving", "value": 50.0, "ttft_ms_p99": 150.0}
    out = _regress(tmp_path, old, new)
    assert out.returncode == 1
    report = json.loads(out.stdout)
    bad = [r for r in report["rows"] if r["regressed"]]
    assert bad[0]["metric"] == "serving.ttft_ms_p99"
    assert bad[0]["direction"] == "lower_is_better"


def test_bench_regress_ratio_and_rate_are_higher_is_better(tmp_path):
    """ISSUE 10 satellite: the serving bench's cache/speculation
    quality fields regress when they DROP — direction overrides win
    over the latency-token inference, while the hit/miss TTFT split
    stays lower-is-better."""
    old = {"metric": "serving", "value": 50.0, "prefix_hit_ratio": 0.9,
           "spec_accept_per_verify": 4.0, "ttft_hit_ms": 5.0}
    new = {"metric": "serving", "value": 50.0, "prefix_hit_ratio": 0.4,
           "spec_accept_per_verify": 1.0, "ttft_hit_ms": 4.0}
    out = _regress(tmp_path, old, new)
    assert out.returncode == 1
    report = json.loads(out.stdout)
    rows = {r["metric"]: r for r in report["rows"]}
    assert rows["serving.prefix_hit_ratio"]["direction"] == \
        "higher_is_better"
    assert rows["serving.prefix_hit_ratio"]["regressed"] is True
    assert rows["serving.spec_accept_per_verify"]["regressed"] is True
    assert rows["serving.ttft_hit_ms"]["direction"] == "lower_is_better"
    assert rows["serving.ttft_hit_ms"]["regressed"] is False


def test_bench_regress_direction_overrides_are_word_anchored(tmp_path):
    """A latency name merely CONTAINING 'rate' ('separate_ms') must not
    flip to higher-is-better — the override matches _-separated words."""
    old = {"metric": "m", "value": 1.0, "separate_ms": 10.0}
    new = {"metric": "m", "value": 1.0, "separate_ms": 20.0}
    out = _regress(tmp_path, old, new)
    assert out.returncode == 1
    report = json.loads(out.stdout)
    rows = {r["metric"]: r for r in report["rows"]}
    assert rows["m.separate_ms"]["direction"] == "lower_is_better"
    assert rows["m.separate_ms"]["regressed"] is True


def test_bench_regress_disjoint_is_loud(tmp_path):
    old = {"metric": "a", "value": 1.0}
    new = {"metric": "b", "value": 1.0}
    assert _regress(tmp_path, old, new).returncode == 3
    assert _regress(tmp_path, old, new,
                    "--allow-disjoint").returncode == 0


def test_bench_regress_reads_summary_artifacts(tmp_path):
    """allreduce_bench --out shape: {"summary": ..., "rows": ...} —
    the summary is the comparable surface."""
    old = {"summary": {"metric": "allreduce_busbw_peak", "value": 10.0},
           "rows": [{"elems": 1, "busbw_GBps": 1.0}]}
    new = {"summary": {"metric": "allreduce_busbw_peak", "value": 4.0},
           "rows": []}
    out = _regress(tmp_path, old, new)
    assert out.returncode == 1


def test_bench_regress_skips_outage_rows(tmp_path):
    """A measured-outage artifact (error field, value 0) must not count
    as a baseline to regress from OR a regression itself."""
    outage = {"metric": "tok_per_s", "value": 0.0,
              "error": "tpu_backend_unavailable"}
    good = {"metric": "tok_per_s", "value": 100.0}
    assert _regress(tmp_path, outage, good,
                    "--allow-disjoint").returncode == 0


def test_bench_regress_skips_metrics_block(tmp_path):
    """The embedded telemetry snapshot is diagnostic, not a regression
    signal: two artifacts differing only in their metrics block
    compare clean."""
    metrics_a = {"hvd_tpu_steps_total": [{"labels": {}, "value": 10.0}]}
    metrics_b = {"hvd_tpu_steps_total": [{"labels": {}, "value": 9999.0}]}
    old = {"metric": "tok_per_s", "value": 100.0, "metrics": metrics_a}
    new = {"metric": "tok_per_s", "value": 100.0, "metrics": metrics_b}
    out = _regress(tmp_path, old, new)
    assert out.returncode == 0, out.stderr
    report = json.loads(out.stdout)
    assert report["compared"] == 1          # only tok_per_s
    assert report["regressions"] == 0


def test_bench_regress_zero_tolerance_for_violations(tmp_path):
    """ISSUE 17 satellite: invariant-violation metrics gate with zero
    tolerance — the old==0 "nothing to regress from" skip must not
    wave new violations through (0 -> N is exactly the failure the
    fleet sim exists to catch)."""
    old = {"metric": "fleet_sim_events_per_s", "value": 10000.0,
           "invariant_violations": 0}
    new = {"metric": "fleet_sim_events_per_s", "value": 10000.0,
           "invariant_violations": 3}
    out = _regress(tmp_path, old, new)
    assert out.returncode == 1
    report = json.loads(out.stdout)
    rows = {r["metric"]: r for r in report["rows"]}
    row = rows["fleet_sim_events_per_s.invariant_violations"]
    assert row["direction"] == "zero_tolerance"
    assert row["regressed"] is True
    # And the reverse (violations FIXED) is an improvement, not a diff
    # failure.
    assert _regress(tmp_path, new, old).returncode == 0


def test_bench_regress_sim_artifact_shape(tmp_path):
    """The fleet-sim artifact (benchmarks/fleet_sim_bench.py): event
    counts and fault tallies are scenario structure (skipped), the
    calibration errors gate lower-is-better, and a worsened
    calibration regresses."""
    base = {"summary": {
        "metric": "fleet_sim_events_per_s", "value": 14000.0,
        "replicas": 1000, "requests": 10000, "events": 50000,
        "sim_wall_time_s": 3.5, "kills": 13, "faults_injected": 13,
        "invariant_checks": 10000, "invariant_violations": 0,
        "calibration_error_p50": 0.04, "calibration_error_p99": 0.11,
        "profile_ttft_ms_p50": 121.9, "profile_ttft_ms_p99": 4508.4}}
    worse = json.loads(json.dumps(base))
    worse["summary"]["events"] = 90000        # structure: not gated
    worse["summary"]["kills"] = 40            # structure: not gated
    out = _regress(tmp_path, base, worse)
    assert out.returncode == 0, out.stderr
    worse["summary"]["calibration_error_p99"] = 0.5
    out = _regress(tmp_path, base, worse)
    assert out.returncode == 1
    rows = {r["metric"]: r
            for r in json.loads(out.stdout)["rows"]}
    bad = rows["fleet_sim_events_per_s.calibration_error_p99"]
    assert bad["direction"] == "lower_is_better"
    assert bad["regressed"] is True


@pytest.mark.sim
def test_fleet_sim_bench_smoke(tmp_path):
    """End-to-end fleet_sim_bench at toy scale: runs clean, emits the
    gated artifact, and bench_regress accepts it against itself."""
    art = tmp_path / "SIM_smoke.json"
    run = subprocess.run(
        [sys.executable,
         os.path.join(ROOT, "benchmarks", "fleet_sim_bench.py"),
         "--replicas", "8", "--requests", "400", "--rate-rps", "200",
         "--calibration-requests", "1500", "--out", str(art)],
        capture_output=True, text=True, timeout=300)
    assert run.returncode == 0, run.stderr
    doc = json.loads(art.read_text())
    s = doc["summary"]
    assert s["metric"] == "fleet_sim_events_per_s" and s["value"] > 0
    assert s["invariant_violations"] == 0
    # Toy-scale band: 1500 samples put ~15 in the p99 tail, so the
    # estimator is noisier than the full bench's ±15%.
    assert s["calibration_error_p99"] < 0.30
    regress = subprocess.run(
        [sys.executable,
         os.path.join(ROOT, "scripts", "bench_regress.py"),
         str(art), str(art)],
        capture_output=True, text=True, timeout=60)
    assert regress.returncode == 0, regress.stderr


def test_bench_regress_skips_trace_block(tmp_path):
    """The embedded per-run trace pointer + critical-path report
    (--trace; docs/tracing.md) is diagnostic like "metrics": two
    artifacts differing only there compare clean."""
    trace_a = {"file": "a/TRACE_x.json",
               "critical_path": {"total_us": 100.0, "dominant": "d"}}
    trace_b = {"file": "b/TRACE_x.json",
               "critical_path": {"total_us": 9e9, "dominant": "other"}}
    old = {"metric": "tok_per_s", "value": 100.0, "trace": trace_a}
    new = {"metric": "tok_per_s", "value": 100.0, "trace": trace_b}
    out = _regress(tmp_path, old, new)
    assert out.returncode == 0, out.stderr
    report = json.loads(out.stdout)
    assert report["compared"] == 1          # only tok_per_s
    assert report["regressions"] == 0


def _metrics_dump(*args):
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "metrics_dump.py"),
         *args],
        capture_output=True, text=True, timeout=60)


def test_metrics_dump_renders_artifact_block(tmp_path):
    art = tmp_path / "bench.json"
    art.write_text(json.dumps({
        "metric": "tok_per_s", "value": 100.0,
        "metrics": {
            "hvd_tpu_steps_total": [
                {"labels": {"kind": "train"}, "value": 3.0}],
            "hvd_tpu_step_time_seconds": [
                {"labels": {"kind": "train"}, "count": 3, "sum": 0.3,
                 "p50": 0.1, "p90": 0.12, "p99": 0.2, "mean": 0.1}],
        },
    }))
    out = _metrics_dump(str(art))
    assert out.returncode == 0, out.stderr
    assert "hvd_tpu_steps_total{kind=train}  3" in out.stdout
    assert "count=3" in out.stdout and "p99=0.2" in out.stdout
    # --json round-trips the block verbatim.
    raw = _metrics_dump(str(art), "--json")
    assert raw.returncode == 0
    assert "hvd_tpu_steps_total" in json.loads(raw.stdout)["metrics"]


def test_metrics_dump_missing_block_is_loud(tmp_path):
    art = tmp_path / "old.json"
    art.write_text(json.dumps({"metric": "tok_per_s", "value": 1.0}))
    out = _metrics_dump(str(art))
    assert out.returncode != 0
    assert "no embedded 'metrics' block" in out.stderr


def test_metrics_dump_requires_exactly_one_source(tmp_path):
    assert _metrics_dump().returncode != 0
    art = tmp_path / "a.json"
    art.write_text("{}")
    assert _metrics_dump(str(art), "--url", "http://x").returncode != 0


def test_metrics_dump_fleet_sweep(tmp_path):
    """``--fleet`` smoke (docs/observability.md): one concurrent
    MetricsRequest sweep over live wire endpoints — per-replica series
    gain a ``replica`` label, an unreachable port degrades into
    ``fleet_errors`` instead of killing the sweep."""
    from horovod_tpu.obs import instrument
    from horovod_tpu.runner.common.network import BasicService

    instrument._reg().counter("hvd_tpu_fleet_dump_probe_total").inc()
    key = b"fleet-dump-secret"
    secret = tmp_path / "secret"
    secret.write_bytes(key)
    a = BasicService("dump-a", key, host="127.0.0.1")
    b = BasicService("dump-b", key, host="127.0.0.1")
    try:
        spec = (f"127.0.0.1:{a.port},127.0.0.1:{b.port},"
                f"127.0.0.1:1")   # nothing listens on port 1
        out = _metrics_dump("--fleet", spec, "--secret-file",
                            str(secret), "--json")
        assert out.returncode == 0, out.stderr
        doc = json.loads(out.stdout)
        assert doc["fleet_replicas"] == 3
        assert list(doc["fleet_errors"]) == ["127.0.0.1:1"]
        series = doc["metrics"]["hvd_tpu_fleet_dump_probe_total"]
        replicas = sorted(s["labels"]["replica"] for s in series)
        assert replicas == sorted([f"127.0.0.1:{a.port}",
                                   f"127.0.0.1:{b.port}"])
    finally:
        a.shutdown()
        b.shutdown()


def test_fleet_top_one_shot_tick(tmp_path):
    """``scripts/fleet_top.py`` smoke: a one-shot ``--json`` tick
    against a metrics-only endpoint (a BasicService with no serving
    stats) renders the fleet roll-up and downgrades the replica to
    ``metrics-only`` rather than declaring it dead."""
    from horovod_tpu.runner.common.network import BasicService

    key = b"fleet-top-secret"
    secret = tmp_path / "secret"
    secret.write_bytes(key)
    svc = BasicService("top-a", key, host="127.0.0.1")
    try:
        out = subprocess.run(
            [sys.executable, os.path.join(ROOT, "scripts", "fleet_top.py"),
             "--fleet", f"127.0.0.1:{svc.port}",
             "--secret-file", str(secret), "--json", "--timeout", "5"],
            capture_output=True, text=True, timeout=60)
        assert out.returncode == 0, out.stderr
        doc = json.loads(out.stdout)
        assert doc["fleet"]["total"] == 1
        assert doc["fleet"]["ok"] == 1
        (row,) = doc["replicas"]
        assert row["error"] == "metrics-only"
        assert row["families"] > 0
        # A metrics-only endpoint is still a failed *stats* scrape, so
        # the dashboard must surface the plane's verdict, not hide it.
        assert "collect_stale" in doc["active_alerts"]
    finally:
        svc.shutdown()
