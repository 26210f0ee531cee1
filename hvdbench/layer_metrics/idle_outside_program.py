"""Load generator: the share of the traced window in which device 0
sat idle while the program was not running — the idle seconds that lie
under no ``hvd_tpu_serve_step`` span: the harness's own loop between
two steps (its bookkeeping, the generator's submits).  A device starved
from the harness's side reads as a slow server, as a late generator
would.  On an earlier line ``idle_by_program_phase``: the whole of the
window's idle seconds by what the program was doing, each gap cut where
the program's phases begin and end (``_decode_phases.idle_by_phase``),
which add up to ``device_idle`` x the window."""
from hvdbench.layer_metrics import _decode_phases as phases
from hvdbench.layers import named
from hvdbench.reduce import program_spans as ps
from hvdbench.reduce import xplane


def read(wanted, view):
    names = named(wanted, "idle_outside_program")
    if not names or not view.busy:
        return {}
    try:
        all_rows = phases.rows(view)
        if not xplane.spans_of(all_rows, ps.SERVE_STEP):
            return {}
        parts = phases.idle_by_phase(all_rows, view.busy["window_ns"])
        if parts is None:
            return {}
        ps.say(idle_by_program_phase=parts, idle_s=sum(parts.values()),
               window_s=view.busy["window_s"])
        return {n: 100.0 * parts[phases.OUTSIDE] / view.busy["window_s"]
                for n in names}
    except Exception as e:   # a reader never takes the result line down
        ps.say(idle_outside_program=f"not read: {type(e).__name__}: {e}")
        return {}
