"""Window and full attention: device-0 milliseconds a decode step
spends under the program's ``hvd_tpu_paged_attention_window`` scope
(every window layer's attention over its ring: the kernel and the few
small operations around it) and under ``hvd_tpu_paged_attention_full``
(every full layer's over its whole chain).  A model of one kind of
layer has neither scope and neither metric."""
from hvdbench.layer_metrics import _mimo_v2
from hvdbench.layers import named

_SCOPES = {"window_attention_ms": "hvd_tpu_paged_attention_window",
           "full_attention_ms": "hvd_tpu_paged_attention_full"}


def read(wanted, view):
    names = {base: named(wanted, base) for base in _SCOPES}
    if not any(names.values()):
        return {}
    out = {}
    for base, scope in _SCOPES.items():
        value = _mimo_v2.ms_a_step(view, scope)
        if value is not None:
            out.update({n: value for n in names[base]})
    return out
