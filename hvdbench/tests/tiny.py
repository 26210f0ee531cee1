"""Tiny configurations and traffic for the CPU rehearsals: the same
files' shapes as ``hvdbench/configs`` and ``hvdbench/traffic``, at
sizes a test run can hold."""

import copy
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def bench() -> dict:
    """BENCHMARK.json, and beside its cells the backlog cell that
    PERF.md section 7 keeps for a later PR (its traffic file and its
    traffic kind are here and are rehearsed like the others)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    name = "gpt2xl-serve-score"
    if all(w["name"] != name for w in b["workloads"]):
        b["workloads"].append({"name": name, "config": "gpt2-xl",
                               "traffic": "score", "chips": 1,
                               "why": "kept for a later PR"})
        b["end_to_end"].append({
            "name": "serve_tokens_per_s", "unit": "tokens/s",
            "better": "higher", "bound": 0.01, "source": "host_clock",
            "workloads": [name]})
    return b


def _load(kind: str, name: str) -> dict:
    with open(os.path.join(ROOT, "hvdbench", kind, name + ".json")) as f:
        return json.load(f)


def config(name: str) -> dict:
    cfg = copy.deepcopy(_load("configs", name))
    cfg.update(vocab_size=211, n_positions=128, n_ctx=128, n_embd=32,
               n_layer=2, n_head=4, n_inner=128)
    cfg["run"]["attention"] = "full"
    # Limits of this size, read here on the CPU as PERF.md section 2
    # says the real ones were read on the chip.  Training: sound runs
    # reach 0.001 (gradient) and 0.003 (change), the fp8 control 0.016.
    # Serving: sound runs reach 0.0014, the fp8 control's widest gap
    # over 360 positions is 0.011 to 0.014 on three seeds.
    cfg["check"]["limits"] = (
        {"served_logit_gap": 0.004} if "engine" in cfg["run"] else
        {"loss_gap": 2e-4, "grad_norm_gap": 0.004,
         "delta_norm_gap": 0.01, "loss_fall": 0.0})
    cfg["run"]["rows_per_chip"] = 4
    if "engine" in cfg["run"]:
        cfg["run"]["engine"] = {
            "max_slots": 8, "prefill_buckets": [64, 128],
            "max_seq_len": 128, "kv_cache": "paged", "kv_block": 16,
            "kv_blocks": 129}
        cfg["check"]["pad_to"] = 32
    return cfg


def traffic(name: str) -> dict:
    t = copy.deepcopy(_load("traffic", name))
    if t["kind"] == "train":
        t.update(seq_len=64, ring=4, rows=t["rows"] // 2)
    else:
        for key, top in (("prompt_len", 100), ("output_len", 12)):
            d = t[key]
            d["max"] = min(d["max"], top)
            d["min"] = min(d["min"], 8 if key == "prompt_len" else d["min"])
            if "median" in d:
                d["median"] = d["max"] / 3
        t["trace_seconds"] = 1
    if t["kind"] == "serve-open":
        t.update(rate_per_s=20.0, preroll_s=0.5)
    return t
