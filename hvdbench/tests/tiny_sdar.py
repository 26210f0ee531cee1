"""The ``sdar-30b-a3b`` configuration at a size a CPU test holds, with
every ratio kept: grouped KV heads (4 query heads on 2), a head wider
than the hidden size over the heads, 16 experts of which every token
takes 4, all held, blocks of 4 positions over cache blocks of 4."""

import copy
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def config(**over) -> dict:
    with open(os.path.join(ROOT, "hvdbench", "configs",
                           "sdar-30b-a3b.json")) as f:
        cfg = copy.deepcopy(json.load(f))
    cfg.update(
        vocab_size=211, hidden_size=32, intermediate_size=96,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        num_hidden_layers=3, moe_intermediate_size=16, num_experts=16,
        num_experts_per_tok=4)
    cfg["run"]["engine"].update(max_slots=4, prefill_buckets=[16, 64],
                                max_seq_len=128, kv_block=4, kv_blocks=129)
    cfg["run"]["batcher"].update(max_new_tokens=64)
    cfg["run"]["generation"].update(mask_token=210)
    cfg["check"].update(pad_to=32)
    cfg.update(over)
    return cfg
