"""The ``mimo-v2-flash`` configuration at a size a CPU test holds, with
every ratio kept: keys wider than values (24 / 16), rotary on a third
of a head (8 of 24), KV heads by kind (1 full, 2 window), two rotary
bases, a window of 8 over blocks of 4, sinks in window layers, scaled
values, a dense layer 0 and gated experts (top 4 of 16, 4 held) in the
others."""

import copy
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def config(**over) -> dict:
    with open(os.path.join(ROOT, "hvdbench", "configs",
                           "mimo-v2-flash.json")) as f:
        cfg = copy.deepcopy(json.load(f))
    cfg.update(
        vocab_size=211, hidden_size=32, intermediate_size=64,
        num_attention_heads=4, swa_num_attention_heads=4,
        num_key_value_heads=1, swa_num_key_value_heads=2,
        head_dim=24, swa_head_dim=24, v_head_dim=16, swa_v_head_dim=16,
        sliding_window=8, sliding_window_size=8, attention_chunk_size=8,
        num_hidden_layers=4, hybrid_layer_pattern=[0, 1, 1, 0],
        moe_layer_freq=[0, 1, 1, 1], moe_intermediate_size=16,
        n_routed_experts=4, num_experts_per_tok=4)
    cfg["run"].update(router_outputs=16,
                      experts_held={"offset": 0, "count": 4})
    cfg["run"]["engine"].update(max_slots=4, prefill_buckets=[16, 64],
                                max_seq_len=128, kv_block=4, kv_blocks=129)
    cfg["run"]["batcher"].update(max_new_tokens=64)
    cfg["check"].update(pad_to=32)
    cfg.update(over)
    return cfg
