"""Train step and gradient wire: device-0 time a step under each scope
the program puts inside its compiled step (``jax.named_scope``, read
from the operations' metadata by ``reduce/xspace.py``):

* ``fwd_bwd_ms``    — ``hvd_tpu_fwd_bwd``: the loss's value-and-grad;
* ``optimizer_ms``  — ``hvd_tpu_optimizer``: the update and its apply;
* ``wire_pack_ms``  — ``hvd_tpu_wire_pack`` + ``hvd_tpu_wire_unpack``:
  the copies into and out of the fusion buckets around the collectives.

An operation under nested scopes counts for the innermost.  The
collectives themselves (``hvd_tpu_wire_bucket_<i>``) and the rest of
the step go on an earlier line, so that the parts can be added up
against ``step_ms``."""
from hvdbench.layers import named
from hvdbench.reduce import program_spans as ps

_PARTS = {"fwd_bwd_ms": ("hvd_tpu_fwd_bwd",),
          "optimizer_ms": ("hvd_tpu_optimizer",),
          "wire_pack_ms": ("hvd_tpu_wire_pack", "hvd_tpu_wire_unpack")}


def read(wanted, view):
    names = {base: named(wanted, base) for base in _PARTS}
    steps = view.facts.get("traced_steps")
    if not steps or not any(names.values()):
        return {}
    try:
        found = ps.scope_seconds(view)
        if found is None:
            return {}
        per_step = {k: v / steps * 1e3 for k, v in found["by_scope"].items()}
        buckets = sum(v for k, v in per_step.items()
                      if k.startswith("hvd_tpu_wire_bucket_"))
        ps.say(program_scopes_ms_a_step={
            **{k: v for k, v in sorted(per_step.items())
               if not k.startswith("hvd_tpu_wire_bucket_")},
            "hvd_tpu_wire_buckets": buckets,
            "unattributed": found["other_s"] / steps * 1e3,
            "unattributed_top": [[k, v / steps * 1e3]
                                 for k, v in found["other_top"]],
            "ops": found["ops"], "ops_with_op_name": found["named"]})
        out = {}
        for base, scopes in _PARTS.items():
            if any(s in per_step for s in scopes):
                value = sum(per_step.get(s, 0.0) for s in scopes)
                out.update({n: value for n in names[base]})
        return out
    except Exception as e:   # a reader never takes the result line down
        ps.say(scope_ms=f"not read: {type(e).__name__}: {e}")
        return {}
