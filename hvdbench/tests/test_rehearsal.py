"""A CPU rehearsal of each traffic kind at a tiny size: the whole of a
run but the look for a chip, ending in a well-formed result that is
marked as a rehearsal and refused as a measurement.  Then the two tests
that ``correct`` owes: the control (the reference in the next lower
precision, in the program's place) comes out as not correct, and so
does a run whose timed path is broken underneath.

Run by hand on the CPU (about a minute):

    JAX_PLATFORMS=cpu python -m pytest hvdbench/tests -q
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from hvdbench import check, generator, run  # noqa: E402
from hvdbench.reference import gpt2 as ref  # noqa: E402
from hvdbench.tests import tiny  # noqa: E402

CELLS = {"train": "gpt2m-train-1chip", "serve-backlog": "gpt2xl-serve-score",
         "serve-open": "gpt2xl-serve-chat-loaded"}


def rehearse(cell_name, *, trace=False, seconds=1.5, seed=2**31 + 11):
    bench = tiny.bench()
    cell = next(c for c in bench["workloads"] if c["name"] == cell_name)
    return run.run_cell(
        bench, cell, tiny.config(cell["config"]),
        tiny.traffic(cell["traffic"]), seed=seed, seconds=seconds,
        trace=trace, rehearsal=True, t_start=time.monotonic())


@pytest.mark.parametrize("kind", sorted(CELLS))
def test_rehearsal_ends_in_a_well_formed_line_that_is_no_measurement(kind):
    bench = tiny.bench()
    line = rehearse(CELLS[kind])
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["rehearsal"] is True and line["device"]["platform"] == "cpu"
    assert line["correct"] is True, "sound run, tiny limits"
    assert line["attempted"] > 0 and line["failed"] == 0
    want = set(run.metric_names(bench, CELLS[kind], "end_to_end"))
    assert set(line["metrics"]) == want and "setup_s" in want
    for name, m in line["metrics"].items():
        assert m["value"] > 0 and isinstance(m["unit"], str)
    json.dumps(line)
    with pytest.raises(RuntimeError, match="rehearsal"):
        run.refuse_rehearsal(line)


def test_the_heap_is_frozen_when_the_window_opens(monkeypatch):
    """``serve_open.run`` freezes Python's heap between warm-up and the
    stream, so no window holds a walk over what start-up left."""
    import gc

    from hvdbench.drivers import _serve

    seen = []
    real = _serve.ServeHarness.submit

    def submit(self, spec, due):
        if not seen:    # once: the count walks the whole frozen list
            seen.append(gc.get_freeze_count())
        return real(self, spec, due)

    monkeypatch.setattr(_serve.ServeHarness, "submit", submit)
    before = gc.get_freeze_count()      # what imports froze: a few hundred
    line = rehearse(CELLS["serve-open"])
    assert line["correct"] is True
    assert seen and seen[0] > before + 10_000   # at the first arrival
    assert gc.get_freeze_count() == before    # and given back for the check


def test_traced_rehearsal_reports_per_layer_metrics_only():
    bench = tiny.bench()
    line = rehearse(CELLS["serve-open"], trace=True)
    per_layer = set(run.metric_names(bench, CELLS["serve-open"],
                                     "per_layer"))
    assert set(line["metrics"]) <= per_layer
    # What needs no device trace is read even on the CPU.
    assert {"window_compilations.tpot", "slot_occupancy.tpot",
            "ttft_p50_ms.itl", "generator_late_ms.tpot"} <= set(
                line["metrics"])
    assert line["metrics"]["window_compilations.tpot"]["value"] == 0


def test_a_train_file_for_other_chips_is_refused():
    """``lm-32x1024`` is the four-chip cell's; on one chip the driver
    stops before it builds anything."""
    bench = tiny.bench()
    cell = dict(next(c for c in bench["workloads"]
                     if c["name"] == CELLS["train"]), traffic="lm-32x1024")
    with pytest.raises(RuntimeError, match="rows a step"):
        run.run_cell(bench, cell, tiny.config(cell["config"]),
                     tiny.traffic(cell["traffic"]), seed=3, seconds=1.0,
                     trace=False, rehearsal=True, t_start=time.monotonic())


def test_the_command_fails_and_prints_no_result_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "hvdbench", "run.py"),
         "--workload", CELLS["train"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=300)
    assert proc.returncode != 0
    assert '"metrics"' not in proc.stdout
    assert "TPU" in proc.stderr


# --- the control -------------------------------------------------------------

def test_training_control_in_fp8_is_not_correct():
    cfg, traffic = tiny.config("gpt2-medium"), tiny.traffic("lm-8x1024")
    s = ref.sizes(cfg)
    opt = {k: v for k, v in cfg["run"]["optimizer"].items() if k != "name"}
    batches = [generator.train_batch(traffic, 5, i, 4, cfg["vocab_size"])
               for i in range(3)]
    want = ref.train_readings(5, s, batches, opt, rows_per_block=2)
    verdicts = {}
    for precision in ("bf16", cfg["run"]["control_precision"]):
        got = ref.train_readings(5, s, batches, opt, rows_per_block=2,
                                 precision=precision)
        got["last_loss"] = got["losses"][0] - 1.0
        checks = check.train_checks(got, want, cfg["check"]["limits"])
        verdicts[precision] = all(e["ok"] for e in checks)
        if precision == "fp8":
            failed = [e["check"] for e in checks if not e["ok"]]
            assert "grad_norm_gap" in failed
    assert verdicts == {"bf16": True, "fp8": False}


def test_serving_control_in_fp8_is_not_correct():
    import jax

    cfg = tiny.config("gpt2-xl")
    s = ref.sizes(cfg)
    params = jax.jit(lambda k: ref.init_params(k, s))(ref.seed_key(3))
    rng = np.random.default_rng(0)
    # Greedy tokens of the float32 reference itself: a sound program.
    seqs = []
    for n in (20, 37, 60):
        prompt = rng.integers(0, s["V"], n).tolist()
        served = []
        for _ in range(12):
            lg = ref.logits(params, np.asarray([prompt + served]), s)
            served.append(int(np.argmax(np.asarray(lg[0, -1]))))
        seqs.append((prompt, served))
    gaps, _ = ref.served_token_gaps(params, seqs, s, pad_to=32)
    # The control reads positions, not tokens: at each position of a
    # sequence, the gap of the token the lower precision puts first.
    long = [(rng.integers(0, s["V"], 30).tolist(),
             rng.integers(0, s["V"], 90).tolist()) for _ in range(4)]
    _, low = ref.served_token_gaps(
        params, long, s, pad_to=32,
        control_precision=cfg["run"]["control_precision"])
    _, same = ref.served_token_gaps(params, long, s, pad_to=32,
                                    control_precision="bf16")
    limits = cfg["check"]["limits"]
    assert check.serve_checks(gaps, 3, limits)[0]["ok"]
    assert check.serve_checks(same, 4, limits)[0]["ok"]
    assert not check.serve_checks(low, 4, limits)[0]["ok"]


# --- the timed path, broken underneath ---------------------------------------

def test_a_train_step_that_returns_its_state_unchanged_is_not_correct(
        monkeypatch):
    import horovod_tpu as hvd

    real = hvd.make_train_step

    def broken(loss_fn, tx, **kw):
        step = real(loss_fn, tx, donate=False, **kw)

        def unchanged(params, opt_state, batch):
            return params, opt_state, step(params, opt_state, batch)[2]

        return unchanged

    monkeypatch.setattr(hvd, "make_train_step", broken)
    line = rehearse(CELLS["train"])
    assert line["correct"] is False


def test_a_part_of_the_batch_left_out_is_not_correct(monkeypatch):
    import horovod_tpu as hvd

    real = hvd.make_train_step

    def broken(loss_fn, tx, **kw):
        def half(params, batch):
            return loss_fn(params, tuple(x[:2] for x in batch))

        return real(half, tx, **kw)

    monkeypatch.setattr(hvd, "make_train_step", broken)
    line = rehearse(CELLS["train"])
    assert line["correct"] is False


@pytest.mark.parametrize("kind", ["serve-backlog", "serve-open"])
def test_a_token_altered_where_it_is_produced_is_not_correct(
        kind, monkeypatch):
    from horovod_tpu.serve import InferenceEngine

    real = InferenceEngine.step

    def altered(self):
        out = real(self)
        vocab = self._model.config.vocab_size
        return {slot: [(t + 1) % vocab for t in toks]
                for slot, toks in out.items()}

    monkeypatch.setattr(InferenceEngine, "step", altered)
    line = rehearse(CELLS[kind])
    assert line["correct"] is False
    assert line["attempted"] > 0
