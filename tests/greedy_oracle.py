"""The serving tests' decode-correctness oracle: a naive full-forward
argmax loop, independent of the KV-cache paths under test."""

import jax
import jax.numpy as jnp
import numpy as np

_FORWARD = {}


def greedy_reference(model, params, prompt, n_tokens):
    """Greedy tokens of ``model`` after ``prompt`` from cache-free full
    forwards.  One jitted forward per model at the full positional
    length: the sequence is zero-padded behind its last token, which a
    causal model cannot see, so row ``len(seq) - 1`` of the logits is
    the next-token distribution — without an eager op-by-op forward
    (and its per-length compiles) for every token of every call."""
    fwd = _FORWARD.get(model)
    if fwd is None:
        fwd = _FORWARD[model] = jax.jit(
            lambda p, t: model.apply({"params": p}, t))
    seq = list(prompt)
    out = []
    for _ in range(n_tokens):
        padded = np.zeros((1, model.config.max_seq_len), np.int32)
        padded[0, :len(seq)] = seq
        tok = int(jnp.argmax(fwd(params, padded)[0, len(seq) - 1]))
        out.append(tok)
        seq.append(tok)
    return out
