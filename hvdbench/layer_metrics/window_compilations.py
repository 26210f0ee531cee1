"""Start-up: programs traced or built inside the window (the engine's
``trace_counts`` before and after, and JAX's ``backend_compile``
events).  Must read 0."""
from hvdbench.layers import named


def read(wanted, view):
    if "window_compilations" not in view.facts:
        return {}
    return {n: view.facts["window_compilations"]
            for n in named(wanted, "window_compilations")}
