"""hvdlint: the distributed-correctness static-analysis subsystem.

Two layers of coverage:

* **Fixture tests** — a minimal fake package per check with a good and
  a bad variant, proving each analyzer fires exactly on its violation
  class (rank-divergent collective, knob drift, lock discipline,
  lock-order cycle, registry drift, suppression lifecycle) and that a
  deliberately rank-divergent fused plan fails the jaxpr check.
* **The gate** — every analyzer over the real package asserting ZERO
  unsuppressed findings, which is what makes the invariants stick for
  every future PR (acceptance criterion of the analysis issue).
"""

from __future__ import annotations

import json
import subprocess
import sys
from pathlib import Path

import pytest

import horovod_tpu as hvd
from horovod_tpu import analysis
from horovod_tpu.analysis import jaxpr_check
from horovod_tpu.analysis.core import LintConfig, run_checks
from horovod_tpu.analysis.knobs import KnobChecker
from horovod_tpu.analysis.locks import LockChecker
from horovod_tpu.analysis.rank_divergence import RankDivergenceChecker
from horovod_tpu.analysis.registries import (FaultSiteChecker,
                                             MeshAxisChecker,
                                             MetricNameChecker,
                                             ObservabilityChecker,
                                             SpanNameChecker)

pytestmark = pytest.mark.analysis

REPO = Path(__file__).resolve().parent.parent

# Minimal config.py for fixture packages: enough surface for the knob
# and fault-site checkers to key off (they parse THIS, not the real one).
FIXTURE_CONFIG = '''
import dataclasses, os

PRE_INIT_KNOBS = ("PROCESS_ID",)
FAULT_SITES = ("collective", "rpc")
MESH_AXES = ("data", "fsdp", "hvd")
_NOOP_KNOBS = {"CYCLE_TIME": "no cycle loop here"}


def _env(name, default=None):
    for p in ("HOROVOD_", "HVD_TPU_"):
        v = os.environ.get(p + name)
        if v is not None:
            return v
    return default


def _env_int(name, default):
    v = _env(name)
    return int(v) if v is not None else default


@dataclasses.dataclass(frozen=True)
class Config:
    fusion_threshold: int = 1
    cycle_time_ms: float = 1.0

    @staticmethod
    def from_env():
        return Config(
            fusion_threshold=_env_int("FUSION_THRESHOLD", 1),
            cycle_time_ms=_env_int("CYCLE_TIME", 1),
        )
'''

FIXTURE_ENV_DOC = """
| `HOROVOD_FUSION_THRESHOLD` | 1 | bucket bytes |
| `HOROVOD_CYCLE_TIME` | 1.0 | no-op |
| `HVD_TPU_PROCESS_ID` | unset | rank wiring |
"""

FIXTURE_FAULT_DOC = """
| `collective` | dispatch | raise | boom |
| `rpc` | client | drop | gone |
"""

# Consumes Config.fusion_threshold so the fixture baseline is clean.
FIXTURE_CONSUMER = "def use(cfg):\n    return cfg.fusion_threshold\n"


def lint(tmp_path, files, checkers, docs=None, select=None):
    """Materialize a fixture package and run the given checkers."""
    pkg = tmp_path / "horovod_tpu"
    pkg.mkdir(parents=True, exist_ok=True)
    (pkg / "__init__.py").write_text("")
    for rel, text in files.items():
        p = pkg / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(text)
    docdir = tmp_path / "docs"
    docdir.mkdir(exist_ok=True)
    for name, text in {"env_vars.md": FIXTURE_ENV_DOC,
                       "fault_injection.md": FIXTURE_FAULT_DOC,
                       "metrics.md": "", **(docs or {})}.items():
        (docdir / name).write_text(text)
    cfg = LintConfig(root=tmp_path, select=select)
    return run_checks(cfg, checker_classes=checkers)


def checks_of(findings):
    return sorted({f.check for f in findings})


# --- rank-divergent collectives ---------------------------------------------

BAD_RANK_BRANCH = """
from . import rank, allreduce

def log_and_sync(x):
    if rank() == 0:
        x = allreduce(x)   # only rank 0 reaches the rendezvous
    return x
"""

BAD_RANK_EARLY_EXIT = """
from . import rank, barrier

def save(x):
    r = rank()
    if r != 0:
        return None
    barrier()   # only rank 0 still executing
    return x
"""

GOOD_RANK_BRANCH = """
from . import rank, allreduce

def log_and_sync(x):
    x = allreduce(x)       # every rank participates...
    if rank() == 0:
        print("synced", x)  # ...and only the log is rank-conditioned
    return x
"""


def test_rank_divergent_collective_positive(tmp_path):
    fs = lint(tmp_path, {"m.py": BAD_RANK_BRANCH},
              [RankDivergenceChecker])
    assert checks_of(fs) == ["rank-divergent-collective"]
    assert "allreduce" in fs[0].message


def test_rank_divergent_early_exit_positive(tmp_path):
    fs = lint(tmp_path, {"m.py": BAD_RANK_EARLY_EXIT},
              [RankDivergenceChecker])
    assert checks_of(fs) == ["rank-divergent-collective"]
    assert "early exit" in fs[0].message


def test_rank_conditioned_logging_negative(tmp_path):
    # The keras-callbacks pattern: rank-0 verbose print, collective
    # hoisted out — provably collective-free conditioned branch.
    fs = lint(tmp_path, {"m.py": GOOD_RANK_BRANCH},
              [RankDivergenceChecker])
    assert fs == []


def test_keras_callbacks_rank_branches_are_collective_free(tmp_path):
    """The real tensorflow/keras/callbacks.py: its rank-0-verbose
    logging (and every sibling rank-conditioned path) must stay
    provably collective-free — this pins the file specifically, beyond
    the whole-tree gate."""
    src = (REPO / "horovod_tpu" / "tensorflow" / "keras"
           / "callbacks.py").read_text()
    fs = lint(tmp_path, {"callbacks.py": src}, [RankDivergenceChecker])
    assert fs == [], "\n".join(f.format() for f in fs)


# --- knob consistency --------------------------------------------------------

def test_unknown_knob(tmp_path):
    fs = lint(tmp_path,
              {"config.py": FIXTURE_CONFIG, "c.py": FIXTURE_CONSUMER,
               "m.py": 'import os\nV = os.environ.get("HVD_TPU_MYSTERY")\n'},
              [KnobChecker])
    assert "unknown-knob" in checks_of(fs)


def test_raw_env_read_of_declared_knob(tmp_path):
    fs = lint(tmp_path,
              {"config.py": FIXTURE_CONFIG, "c.py": FIXTURE_CONSUMER,
               "m.py": 'import os\n'
                       'V = os.environ.get("HVD_TPU_FUSION_THRESHOLD")\n'},
              [KnobChecker])
    assert "raw-env-read" in checks_of(fs)


def test_pre_init_knob_read_is_allowed(tmp_path):
    fs = lint(tmp_path,
              {"config.py": FIXTURE_CONFIG, "c.py": FIXTURE_CONSUMER,
               "m.py": 'import os\n'
                       'V = os.environ.get("HVD_TPU_PROCESS_ID")\n'},
              [KnobChecker])
    assert fs == []


def test_undocumented_knob(tmp_path):
    fs = lint(tmp_path,
              {"config.py": FIXTURE_CONFIG, "c.py": FIXTURE_CONSUMER},
              [KnobChecker],
              docs={"env_vars.md": "| `HOROVOD_CYCLE_TIME` | 1.0 | x |\n"
                                   "| `HVD_TPU_PROCESS_ID` | unset | x |\n"})
    assert checks_of(fs) == ["undocumented-knob"]
    assert "FUSION_THRESHOLD" in fs[0].message


def test_unconsumed_knob(tmp_path):
    # No module reads .fusion_threshold -> dead knob.  cycle_time_ms is
    # in _NOOP_KNOBS, so it stays exempt.
    fs = lint(tmp_path, {"config.py": FIXTURE_CONFIG}, [KnobChecker])
    assert checks_of(fs) == ["unconsumed-knob"]
    assert "fusion_threshold" in fs[0].message


# --- lock discipline ---------------------------------------------------------

BAD_LOCK = """
import threading

class Box:
    def __init__(self):
        self._lock = threading.Lock()
        self._items = []   # guarded-by: _lock

    def ok(self, x):
        with self._lock:
            self._items.append(x)

    def racy(self, x):
        self._items.append(x)   # no lock held
"""

GOOD_LOCK = BAD_LOCK.replace(
    "    def racy(self, x):\n        self._items.append(x)   # no lock held\n",
    "")

LOCK_CYCLE = """
import threading

_la = threading.Lock()
_lb = threading.Lock()

def ab():
    with _la:
        with _lb:
            pass

def ba():
    with _lb:
        with _la:
            pass
"""

CROSS_FN_CYCLE = """
import threading

_la = threading.Lock()
_lb = threading.Lock()

def inner_b():
    with _lb:
        pass

def holds_a():
    with _la:
        inner_b()

def inner_a():
    with _la:
        pass

def holds_b():
    with _lb:
        inner_a()
"""


def test_unguarded_mutation_positive(tmp_path):
    fs = lint(tmp_path, {"m.py": BAD_LOCK}, [LockChecker])
    assert checks_of(fs) == ["unguarded-mutation"]
    assert "_items" in fs[0].message


def test_guarded_mutation_negative(tmp_path):
    assert lint(tmp_path, {"m.py": GOOD_LOCK}, [LockChecker]) == []


def test_lock_order_cycle_nested(tmp_path):
    fs = lint(tmp_path, {"m.py": LOCK_CYCLE}, [LockChecker])
    assert checks_of(fs) == ["lock-order-cycle"]
    assert "_la" in fs[0].message and "_lb" in fs[0].message


def test_lock_order_cycle_one_line_with(tmp_path):
    # `with _la, _lb:` vs `with _lb, _la:` — the ABBA one-liner form
    # must edge exactly like the nested form.
    src = ("import threading\n"
           "_la = threading.Lock()\n"
           "_lb = threading.Lock()\n"
           "def ab():\n"
           "    with _la, _lb:\n"
           "        pass\n"
           "def ba():\n"
           "    with _lb, _la:\n"
           "        pass\n")
    fs = lint(tmp_path, {"m.py": src}, [LockChecker])
    assert checks_of(fs) == ["lock-order-cycle"]


def test_lock_order_cycle_through_calls(tmp_path):
    # A->B via holds_a->inner_b, B->A via holds_b->inner_a: cycle only
    # visible through the call graph.
    fs = lint(tmp_path, {"m.py": CROSS_FN_CYCLE}, [LockChecker])
    assert checks_of(fs) == ["lock-order-cycle"]


def test_lock_order_no_cycle(tmp_path):
    fs = lint(tmp_path,
              {"m.py": LOCK_CYCLE.replace(
                  "with _lb:\n        with _la:", "with _lb:\n        if 1:")},
              [LockChecker])
    assert fs == []


def test_unguarded_mutation_inside_closure(tmp_path):
    # Thread-target closures execute later, NOT under any enclosing
    # with — their mutations must stay visible to the checker.
    src = BAD_LOCK.replace(
        "    def racy(self, x):\n        self._items.append(x)   # no lock held\n",
        "    def spawn(self, x):\n"
        "        def worker():\n"
        "            self._items.append(x)   # closure, no lock held\n"
        "        return worker\n")
    fs = lint(tmp_path, {"m.py": src}, [LockChecker])
    assert checks_of(fs) == ["unguarded-mutation"]


def test_wrong_lock_does_not_satisfy_guard(tmp_path):
    # Holding a DIFFERENT object's same-named lock is the race this
    # check exists for — exact lock identity is required.
    src = """
import threading

class Box:
    def __init__(self, other):
        self._lock = threading.Lock()
        self._other = other
        self._items = []   # guarded-by: _lock

    def racy(self, x):
        with self._other._lock:
            self._items.append(x)   # wrong lock!
"""
    fs = lint(tmp_path, {"m.py": src}, [LockChecker])
    assert checks_of(fs) == ["unguarded-mutation"]


# --- suppressions ------------------------------------------------------------

def test_suppression_honored(tmp_path):
    suppressed = BAD_LOCK.replace(
        "self._items.append(x)   # no lock held",
        "self._items.append(x)   # hvdlint: disable=unguarded-mutation "
        "-- fixture: caller holds the lock")
    assert lint(tmp_path, {"m.py": suppressed}, [LockChecker]) == []


def test_suppression_expired_is_reported(tmp_path):
    # A suppression matching nothing must not rot silently.
    fs = lint(tmp_path,
              {"m.py": GOOD_LOCK + "\nX = 1  # hvdlint: "
               "disable=unguarded-mutation -- stale excuse\n"},
              [LockChecker])
    assert checks_of(fs) == ["useless-suppression"]


def test_suppression_without_justification_is_a_finding(tmp_path):
    fs = lint(tmp_path,
              {"m.py": "X = 1  # hvdlint: disable=unguarded-mutation\n"},
              [LockChecker])
    assert checks_of(fs) == ["bad-suppression"]


def test_suppression_unknown_id_is_a_finding(tmp_path):
    fs = lint(tmp_path,
              {"m.py": "X = 1  # hvdlint: disable=not-a-check -- why\n"},
              [LockChecker])
    assert checks_of(fs) == ["bad-suppression"]


def test_select_scoped_run_keeps_suppressions_matched(tmp_path):
    # A --select run that deselects the suppressed check must not
    # misread the (legitimate) suppression as useless: matching happens
    # against the full finding set, filtering after.
    suppressed = BAD_LOCK.replace(
        "self._items.append(x)   # no lock held",
        "self._items.append(x)   # hvdlint: disable=unguarded-mutation "
        "-- fixture: caller holds the lock")
    fs = lint(tmp_path, {"m.py": suppressed}, [LockChecker],
              select=["useless-suppression"])
    assert fs == []


def test_suppression_in_string_literal_is_ignored(tmp_path):
    fs = lint(tmp_path,
              {"m.py": 'DOC = "# hvdlint: disable=unguarded-mutation"\n'},
              [LockChecker])
    assert fs == []


# --- registry consistency ----------------------------------------------------

def test_unknown_fault_site(tmp_path):
    fs = lint(tmp_path,
              {"config.py": FIXTURE_CONFIG, "c.py": FIXTURE_CONSUMER,
               "m.py": "from . import faults\n\n"
                       "def drill():\n"
                       '    with faults.inject("nosite:step=1"):\n'
                       "        pass\n"},
              [FaultSiteChecker])
    assert checks_of(fs) == ["unknown-fault-site"]


def test_fault_site_doc_drift(tmp_path):
    fs = lint(tmp_path,
              {"config.py": FIXTURE_CONFIG, "c.py": FIXTURE_CONSUMER},
              [FaultSiteChecker],
              docs={"fault_injection.md": "| `collective` | x | raise | y |\n"})
    assert checks_of(fs) == ["fault-site-doc-drift"]
    assert "rpc" in fs[0].message


def test_unknown_mesh_axis_in_partition_spec(tmp_path):
    """ISSUE 18 satellite: a typo'd axis in a P(...) spec (including
    the multi-axis tuple form) must be flagged against the MESH_AXES
    plan catalog instead of silently diverging from the MeshPlan."""
    fs = lint(tmp_path,
              {"config.py": FIXTURE_CONFIG, "c.py": FIXTURE_CONSUMER,
               "m.py": "from jax.sharding import PartitionSpec as P\n\n"
                       "def specs():\n"
                       '    ok = P("data", None)\n'
                       '    ok2 = P(("data", "fsdp"))\n'
                       '    bad = P("dataa", None)\n'
                       '    bad2 = P(("data", "fspd"))\n'},
              [MeshAxisChecker])
    assert checks_of(fs) == ["unknown-mesh-axis"]
    assert len(fs) == 2
    assert "dataa" in fs[0].message and "fspd" in fs[1].message


def test_unknown_mesh_axis_in_axis_kwargs_and_defaults(tmp_path):
    fs = lint(tmp_path,
              {"config.py": FIXTURE_CONFIG, "c.py": FIXTURE_CONSUMER,
               "m.py": "def reduce(x, collective):\n"
                       '    return collective(x, axis_name="hvdd")\n\n'
                       'def step(x, dp_axis="dta"):\n'
                       "    return x\n"},
              [MeshAxisChecker])
    assert checks_of(fs) == ["unknown-mesh-axis"]
    assert len(fs) == 2


def test_known_mesh_axes_are_clean(tmp_path):
    fs = lint(tmp_path,
              {"config.py": FIXTURE_CONFIG, "c.py": FIXTURE_CONSUMER,
               "m.py": "from jax.sharding import PartitionSpec as P\n\n"
                       'def step(x, collective, axis_name="hvd",\n'
                       '         dp_axis="data"):\n'
                       '    spec = P(("data", "fsdp"), None)\n'
                       "    return collective(x, axis_name=axis_name)\n"},
              [MeshAxisChecker])
    assert fs == []


def test_metric_naming_rules(tmp_path):
    src = (
        "def instrument(reg):\n"
        '    reg.counter("hvd_tpu_good_total").inc()\n'
        '    reg.counter("hvd_tpu_bad_counter").inc()\n'      # no _total
        '    reg.gauge("hvd_tpu_bad_gauge_total").set(1)\n'   # _total gauge
    )
    fs = lint(tmp_path, {"m.py": src}, [MetricNameChecker],
              docs={"metrics.md": "hvd_tpu_good_total hvd_tpu_bad_counter "
                                  "hvd_tpu_bad_gauge_total"})
    assert checks_of(fs) == ["metric-name"]
    assert len(fs) == 2


def test_metric_doc_drift(tmp_path):
    fs = lint(tmp_path,
              {"m.py": 'def f(reg):\n'
                       '    reg.counter("hvd_tpu_undocumented_total")\n'},
              [MetricNameChecker], docs={"metrics.md": "# catalog\n"})
    assert checks_of(fs) == ["metric-doc-drift"]


def test_metric_tenant_cardinality_flags_uncapped_labels(tmp_path):
    """ISSUE 15 satellite: a tenant-id label minted outside the obs
    registry (whose 64-series cap bounds it) is one series per tenant
    forever — flagged at lint time."""
    src = (
        "def instrument(reg, exporter, tenant):\n"
        # Registry-chained: rides the cap — clean.
        '    reg.counter("hvd_tpu_ok_total").labels(tenant=tenant).inc()\n'
        # One-level local family binding: also the capped idiom.
        '    fam = reg.counter("hvd_tpu_fam_total")\n'
        "    fam.labels(tenant=tenant).inc()\n"
        # Hand-rolled series object: unbounded — flagged.
        "    exporter.labels(tenant=tenant)\n"
        # tenant_id spelling is held to the same rule.
        "    exporter.labels(tenant_id=tenant)\n"
    )
    fs = lint(tmp_path, {"m.py": src}, [MetricNameChecker],
              docs={"metrics.md": "hvd_tpu_ok_total hvd_tpu_fam_total"})
    assert checks_of(fs) == ["metric-tenant-cardinality"]
    assert len(fs) == 2
    assert all("64-series" in f.message for f in fs)


def test_metric_tenant_cardinality_clean_without_tenant_labels(tmp_path):
    src = (
        "def instrument(reg, exporter):\n"
        '    reg.counter("hvd_tpu_x_total").labels(site="a").inc()\n'
        '    exporter.labels(kind="b")\n'   # no tenant label: not ours
    )
    fs = lint(tmp_path, {"m.py": src}, [MetricNameChecker],
              docs={"metrics.md": "hvd_tpu_x_total"})
    assert checks_of(fs) == []


def test_span_naming_rules(tmp_path):
    src = (
        "from ..obs import trace as trace_mod\n\n"
        "def hop():\n"
        '    with trace_mod.span("hvd_tpu_good"):\n'
        "        pass\n"
        '    trace_mod.instant("bare_name")\n'          # no prefix
        '    trace_mod.record_span("also_bare", parent=None,\n'
        "                          start_us=0.0, dur_us=1.0)\n"
    )
    fs = lint(tmp_path, {"m.py": src}, [SpanNameChecker],
              docs={"tracing.md": "hvd_tpu_good"})
    assert checks_of(fs) == ["span-name"]
    assert len(fs) == 2


def test_span_rules_cover_record_phase_forwarder(tmp_path):
    # batcher-style span-forwarding helper: the name rides in the
    # SECOND positional — self._record_phase(req, "name", t0, t1).
    src = (
        "class B:\n"
        "    def work(self, req):\n"
        '        self._record_phase(req, "bare_phase", 0.0, 1.0)\n'
        '        self._record_phase(req, "hvd_tpu_phase_ok", 0.0, 1.0)\n'
    )
    fs = lint(tmp_path, {"m.py": src}, [SpanNameChecker],
              docs={"tracing.md": "hvd_tpu_phase_ok"})
    assert checks_of(fs) == ["span-name"]
    assert "bare_phase" in fs[0].message


def test_span_doc_drift(tmp_path):
    fs = lint(tmp_path,
              {"m.py": "from ..obs import trace\n\n"
                       "def hop():\n"
                       '    with trace.span("hvd_tpu_undocumented"):\n'
                       "        pass\n"},
              [SpanNameChecker], docs={"tracing.md": "# span catalog\n"})
    assert checks_of(fs) == ["span-doc-drift"]


def test_span_rules_cover_annotate(tmp_path):
    # A phase entered for the profiler alone is named like a span.
    src = (
        "from ..obs import trace as trace_mod\n\n"
        "def step():\n"
        '    with trace_mod.annotate("hvd_tpu_phase_documented"):\n'
        "        pass\n"
        '    with trace_mod.annotate("hvd_tpu_phase_new"):\n'
        "        pass\n"
        '    with trace_mod.annotate("bare_phase"):\n'
        "        pass\n"
    )
    fs = lint(tmp_path, {"m.py": src}, [SpanNameChecker],
              docs={"tracing.md": "hvd_tpu_phase_documented"})
    assert checks_of(fs) == ["span-doc-drift", "span-name"]
    assert "hvd_tpu_phase_new" in fs[0].message
    assert "bare_phase" in fs[1].message


def test_span_rules_ignore_non_trace_receivers(tmp_path):
    # Timeline-style .span()/.record() lookalikes on other receivers
    # carry free-form names and are not held to span rules.
    fs = lint(tmp_path,
              {"m.py": "def f(timeline):\n"
                       '    timeline.span("free-form name")\n'},
              [SpanNameChecker], docs={"tracing.md": ""})
    assert fs == []


# --- wire-protocol consistency ----------------------------------------------

PROTOCOL_FIXTURE = """
class AckResponse:
    pass


class PingRequest:
    pass


class PingResponse:
    pass


class EchoRequest:
    pass


class EchoResponse:
    pass


class BasicService:
    def _handle(self, req, addr):
        if isinstance(req, PingRequest):
            return PingResponse()
        if isinstance(req, EchoRequest):
            return self._echo(req)
        return AckResponse()

    def _echo(self, req):
        return EchoResponse()
"""

PROTOCOL_DOC = "| `PingRequest` | x |\n| `EchoRequest` | x |\n" \
               "| `GhostRequest` | x |\n"


def test_protocol_clean_fixture(tmp_path):
    from horovod_tpu.analysis.protocol import ProtocolChecker

    fs = lint(tmp_path, {"net.py": PROTOCOL_FIXTURE}, [ProtocolChecker],
              docs={"serving.md": PROTOCOL_DOC})
    assert fs == [], "\n".join(f.format() for f in fs)


def test_protocol_unhandled_frame(tmp_path):
    from horovod_tpu.analysis.protocol import ProtocolChecker

    src = PROTOCOL_FIXTURE + "\n\nclass GhostRequest:\n    pass\n"
    fs = lint(tmp_path, {"net.py": src}, [ProtocolChecker],
              docs={"serving.md": PROTOCOL_DOC})
    assert checks_of(fs) == ["unhandled-request-frame"]
    assert "GhostRequest" in fs[0].message


def test_protocol_mismatched_response(tmp_path):
    from horovod_tpu.analysis.protocol import ProtocolChecker

    # The Ping branch answers Ack even though PingResponse exists:
    # pairing drift a typed client would break on.
    src = PROTOCOL_FIXTURE.replace(
        "        if isinstance(req, PingRequest):\n"
        "            return PingResponse()",
        "        if isinstance(req, PingRequest):\n"
        "            return AckResponse()")
    fs = lint(tmp_path, {"net.py": src}, [ProtocolChecker],
              docs={"serving.md": PROTOCOL_DOC})
    assert checks_of(fs) == ["mismatched-response"]
    assert "PingResponse" in fs[0].message


def test_protocol_doc_drift(tmp_path):
    from horovod_tpu.analysis.protocol import ProtocolChecker

    fs = lint(tmp_path, {"net.py": PROTOCOL_FIXTURE}, [ProtocolChecker],
              docs={"serving.md": "| `PingRequest` | x |\n"})
    assert checks_of(fs) == ["protocol-doc-drift"]
    assert "EchoRequest" in fs[0].message


def test_protocol_ignores_non_service_modules(tmp_path):
    from horovod_tpu.analysis.protocol import ProtocolChecker

    # A *Request class in a module with no BasicService is an internal
    # queue item (ServeRequest pattern), not a wire frame.
    fs = lint(tmp_path, {"m.py": "class ServeRequest:\n    pass\n"},
              [ProtocolChecker], docs={"serving.md": ""})
    assert fs == []


# --- bounded-wait discipline -------------------------------------------------

def test_unbounded_thread_join(tmp_path):
    from horovod_tpu.analysis.waits import WaitChecker

    src = ("import threading\n"
           "def f(fn):\n"
           "    t = threading.Thread(target=fn)\n"
           "    t.start()\n"
           "    t.join()\n")
    fs = lint(tmp_path, {"m.py": src}, [WaitChecker])
    assert checks_of(fs) == ["unbounded-wait"]
    assert "join" in fs[0].message


def test_bounded_thread_join_ok(tmp_path):
    from horovod_tpu.analysis.waits import WaitChecker

    src = ("def f(self):\n"
           "    self._thread.join(timeout=5)\n")
    assert lint(tmp_path, {"m.py": src}, [WaitChecker]) == []


def test_str_join_is_not_a_thread_wait(tmp_path):
    from horovod_tpu.analysis.waits import WaitChecker

    src = 'def f(xs):\n    return ", ".join(str(x) for x in xs)\n'
    assert lint(tmp_path, {"m.py": src}, [WaitChecker]) == []


def test_unbounded_condition_wait(tmp_path):
    from horovod_tpu.analysis.waits import WaitChecker

    src = ("def f(self):\n"
           "    with self._cv:\n"
           "        self._cv.wait()\n"
           "        self._cv.wait_for(lambda: True)\n")
    fs = lint(tmp_path, {"m.py": src}, [WaitChecker])
    assert checks_of(fs) == ["unbounded-wait"] and len(fs) == 2


def test_bounded_condition_wait_ok(tmp_path):
    from horovod_tpu.analysis.waits import WaitChecker

    src = ("def f(self):\n"
           "    with self._cv:\n"
           "        self._cv.wait(timeout=1.0)\n"
           "        self._cv.wait_for(lambda: True, timeout=2.0)\n")
    assert lint(tmp_path, {"m.py": src}, [WaitChecker]) == []


def test_unbounded_queue_get_and_request(tmp_path):
    from horovod_tpu.analysis.waits import WaitChecker

    src = ("def f(self, client):\n"
           "    item = self.task_queue.get()\n"
           "    resp = client.request(PingRequest())\n")
    fs = lint(tmp_path, {"m.py": src}, [WaitChecker])
    assert checks_of(fs) == ["unbounded-wait"] and len(fs) == 2


def test_bounded_request_ok(tmp_path):
    from horovod_tpu.analysis.waits import WaitChecker

    src = ("def f(client):\n"
           "    return client.request(PingRequest(), timeout=30.0)\n")
    assert lint(tmp_path, {"m.py": src}, [WaitChecker]) == []


def test_handle_wait_is_not_flagged(tmp_path):
    from horovod_tpu.analysis.waits import WaitChecker

    # Collective Handle.wait() results are synchronous API forwarders,
    # not thread waits — receiver-name sensitivity keeps them exempt.
    src = ("def allreduce(tensor, handle):\n"
           "    return handle.wait()\n")
    assert lint(tmp_path, {"m.py": src}, [WaitChecker]) == []


def test_unbounded_wait_suppression(tmp_path):
    from horovod_tpu.analysis.waits import WaitChecker

    src = ("def supervise(proc_thread):\n"
           "    proc_thread.join()  # hvdlint: disable=unbounded-wait "
           "-- agent supervises the worker for the job's whole life\n")
    assert lint(tmp_path, {"m.py": src}, [WaitChecker]) == []


def test_select_group_aliases_expand():
    from horovod_tpu.analysis.core import expand_select

    assert expand_select(["protocol,waits"]) == [
        "unhandled-request-frame", "mismatched-response",
        "protocol-doc-drift", "unbounded-wait"]
    assert expand_select(None) is None
    assert expand_select(["unknown-knob"]) == ["unknown-knob"]


# --- jaxpr analyzer ----------------------------------------------------------

def _toy():
    import jax.numpy as jnp
    import optax

    def loss_fn(params, batch):
        x, y = batch
        return jnp.mean((x @ params["w"] + params["b"] - y) ** 2)

    params = {"w": jnp.zeros((64, 32)), "b": jnp.zeros((32,))}
    tx = optax.sgd(0.1)
    batch = (jnp.ones((16, 64)), jnp.ones((16, 32)))
    return loss_fn, params, tx, batch


def test_jaxpr_checks_pass_on_shipped_factories():
    assert analysis.run_jaxpr_checks() == []


def test_jaxpr_check_catches_rank_divergent_fused_plan():
    import jax

    from horovod_tpu.optim.distributed_optimizer import make_train_step

    loss_fn, params, tx, batch = _toy()

    def bad_factory():
        # Deliberately rank-divergent fused plan: rank 0 compiles the
        # overlapped RS+AG wire, every other rank the plain allreduce —
        # the schedules rendezvous differently and would deadlock.
        if jax.process_index() == 0:
            return make_train_step(loss_fn, tx, microbatches=2,
                                   overlap=True)
        return make_train_step(loss_fn, tx)

    fs = jaxpr_check.check_step_rank_consistency(
        bad_factory, lambda: (params, tx.init(params), batch))
    assert len(fs) == 1
    assert fs[0].check == "jaxpr-rank-divergence"
    assert "reduce_scatter" in fs[0].message


def test_jaxpr_extractor_sees_collectives_in_subjaxprs():
    import jax

    from horovod_tpu.optim.distributed_optimizer import make_train_step

    loss_fn, params, tx, batch = _toy()
    step = make_train_step(loss_fn, tx, microbatches=2, overlap=True)
    jaxpr = jax.make_jaxpr(lambda *a: step(*a))(params, tx.init(params),
                                                batch)
    seq = jaxpr_check.extract_collective_sequence(jaxpr)
    # 1 bucket x 2 microbatches reduce-scatter + 1 deferred all-gather
    # + the loss-mean psum, all nested under shard_map/scan/pjit.
    assert sum(1 for p in seq if "reduce_scatter" in p) == 2
    assert sum(1 for p in seq if "all_gather" in p) == 1


# --- observability tie-in ----------------------------------------------------

def test_lint_findings_metric_recorded():
    from horovod_tpu.analysis.core import Finding
    from horovod_tpu.obs import metrics as obs_metrics

    analysis.record_findings_metric([
        Finding("unknown-knob", "x.py", 1, "m"),
        Finding("unknown-knob", "y.py", 2, "m"),
        Finding("metric-name", "z.py", 3, "m"),
    ])
    snap = obs_metrics.registry().snapshot()
    series = {tuple(sorted(s["labels"].items())): s["value"]
              for s in snap["hvd_tpu_lint_findings_total"]}
    assert series[(("check", "unknown-knob"),)] >= 2
    assert series[(("check", "metric-name"),)] >= 1


# --- the gate ----------------------------------------------------------------

def test_repo_tree_is_clean():
    """THE acceptance invariant: zero unsuppressed findings over the
    shipped package.  Any future PR that introduces a rank-divergent
    collective, an undocumented knob, an unguarded mutation or catalog
    drift fails tier-1 right here."""
    findings = analysis.run(REPO)
    assert findings == [], "\n" + "\n".join(f.format() for f in findings)


def test_repo_tree_clean_for_protocol_and_waits():
    """The two PR-13 static passes, scoped: every wire frame dispatched,
    paired, documented; every blocking call deadline-bound (or
    justified).  Group aliases exercise the --select expansion path."""
    findings = analysis.run(REPO, select=["protocol", "waits"])
    assert findings == [], "\n" + "\n".join(f.format() for f in findings)


def test_check_catalog_matches_docs():
    """docs/lint.md documents every check id (and no stale ones)."""
    doc = (REPO / "docs" / "lint.md").read_text()
    for check_id in analysis.CHECK_CATALOG:
        assert f"`{check_id}`" in doc, f"{check_id} missing from docs/lint.md"


def test_cli_exit_contract(tmp_path):
    """scripts/hvdlint.py: 0 on the clean tree + JSON artifact shape."""
    out = tmp_path / "lint.json"
    proc = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "hvdlint.py"),
         "--json", str(out)],
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(out.read_text())
    assert payload["tool"] == "hvdlint"
    assert payload["findings"] == []
    assert payload["counts"] == {}


def test_cli_nonzero_on_findings(tmp_path):
    """A planted violation exits 1 and lands in the artifact."""
    pkg = tmp_path / "horovod_tpu"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "config.py").write_text(FIXTURE_CONFIG)
    (pkg / "c.py").write_text(FIXTURE_CONSUMER)
    (pkg / "bad.py").write_text(BAD_RANK_BRANCH)
    docs = tmp_path / "docs"
    docs.mkdir()
    (docs / "env_vars.md").write_text(FIXTURE_ENV_DOC)
    (docs / "fault_injection.md").write_text(FIXTURE_FAULT_DOC)
    (docs / "metrics.md").write_text("")
    proc = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "hvdlint.py"),
         "--root", str(tmp_path), "--json", "-"],
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "rank-divergent-collective" in proc.stdout


# --- Pallas interpret-flag discipline ----------------------------------------

GOOD_PALLAS = """
from jax.experimental import pallas as pl

def _kern(x_ref, o_ref):
    o_ref[...] = x_ref[...]

def copy(x, *, interpret=None):
    from horovod_tpu.ops.pallas_common import resolve_interpret
    return pl.pallas_call(_kern, out_shape=x,
                          interpret=resolve_interpret(interpret))(x)
"""

MISSING_INTERPRET = """
from jax.experimental import pallas as pl

def _kern(x_ref, o_ref):
    o_ref[...] = x_ref[...]

def copy(x, *, interpret=None):
    return pl.pallas_call(_kern, out_shape=x)(x)
"""

HARDCODED_INTERPRET = """
from jax.experimental import pallas as pl

def _kern(x_ref, o_ref):
    o_ref[...] = x_ref[...]

def copy(x, *, interpret=None):
    return pl.pallas_call(_kern, out_shape=x, interpret=True)(x)
"""

NO_PUBLIC_ESCAPE = """
from jax.experimental import pallas as pl

def _kern(x_ref, o_ref):
    o_ref[...] = x_ref[...]

def _copy(x, interpret):
    return pl.pallas_call(_kern, out_shape=x, interpret=interpret)(x)

def copy(x):
    return _copy(x, False)
"""


def test_pallas_interpret_threaded_ok(tmp_path):
    from horovod_tpu.analysis.pallas import PallasChecker

    assert lint(tmp_path, {"m.py": GOOD_PALLAS}, [PallasChecker]) == []


def test_pallas_interpret_missing(tmp_path):
    from horovod_tpu.analysis.pallas import PallasChecker

    fs = lint(tmp_path, {"m.py": MISSING_INTERPRET}, [PallasChecker])
    assert checks_of(fs) == ["pallas-interpret-flag"]
    assert "without interpret=" in fs[0].message


def test_pallas_interpret_hardcoded(tmp_path):
    from horovod_tpu.analysis.pallas import PallasChecker

    fs = lint(tmp_path, {"m.py": HARDCODED_INTERPRET}, [PallasChecker])
    assert checks_of(fs) == ["pallas-interpret-flag"]
    assert "hardcodes" in fs[0].message


def test_pallas_no_public_escape_hatch(tmp_path):
    from horovod_tpu.analysis.pallas import PallasChecker

    fs = lint(tmp_path, {"m.py": NO_PUBLIC_ESCAPE}, [PallasChecker])
    assert checks_of(fs) == ["pallas-interpret-flag"]
    assert "public" in fs[0].message


def test_pallas_modules_without_kernels_are_ignored(tmp_path):
    from horovod_tpu.analysis.pallas import PallasChecker

    src = "def pallas_call_lookalike(x):\n    return x\n"
    assert lint(tmp_path, {"m.py": src}, [PallasChecker]) == []


def test_pallas_check_in_default_set():
    from horovod_tpu import analysis
    from horovod_tpu.analysis.pallas import PallasChecker

    assert PallasChecker in analysis.default_checkers()


# --- the telemetry-plane alert catalog (ObservabilityChecker) ----------------

FIXTURE_DETECT = '''
DETECTORS = (
    ("never_shed_interactive", "page"),
    ("stuck_swap", "ticket"),
)
'''

FIXTURE_SLO = '''
def evaluate(clause):
    return {"alert": f"slo_burn:{clause}", "severity": "page"}
'''

FIXTURE_OBS_DOC = """
| alert | severity | meaning |
|---|---|---|
| `never_shed_interactive` | page | interactive lane starved |
| `stuck_swap` | ticket | weights roll wedged |

SLO violations page as `slo_burn:<slo>`.
"""


def test_observability_clean_fixture(tmp_path):
    fs = lint(tmp_path, {"obs/detect.py": FIXTURE_DETECT,
                         "obs/slo.py": FIXTURE_SLO},
              [ObservabilityChecker],
              docs={"observability.md": FIXTURE_OBS_DOC})
    assert checks_of(fs) == []


def test_observability_undocumented_detector(tmp_path):
    """A detector id with no row in the operator-facing catalog is a
    page nobody can act on."""
    doc = FIXTURE_OBS_DOC.replace("| `stuck_swap` | ticket |"
                                  " weights roll wedged |\n", "")
    fs = lint(tmp_path, {"obs/detect.py": FIXTURE_DETECT,
                         "obs/slo.py": FIXTURE_SLO},
              [ObservabilityChecker],
              docs={"observability.md": doc})
    assert checks_of(fs) == ["detector-doc-drift"]
    assert len(fs) == 1 and "stuck_swap" in fs[0].message


def test_observability_bad_severity(tmp_path):
    """A typo'd severity silently drops out of the paging pipeline."""
    bad = FIXTURE_DETECT.replace('"ticket"', '"warn"')
    doc = FIXTURE_OBS_DOC.replace("| ticket |", "| warn |")
    fs = lint(tmp_path, {"obs/detect.py": bad, "obs/slo.py": FIXTURE_SLO},
              [ObservabilityChecker],
              docs={"observability.md": doc})
    assert checks_of(fs) == ["alert-severity"]
    assert "warn" in fs[0].message


def test_observability_slo_burn_family_doc_drift(tmp_path):
    """obs/slo.py emits the slo_burn: family — the doc must describe
    it even though it is not a row in the DETECTORS catalog."""
    doc = FIXTURE_OBS_DOC.replace(
        "SLO violations page as `slo_burn:<slo>`.\n", "")
    fs = lint(tmp_path, {"obs/detect.py": FIXTURE_DETECT,
                         "obs/slo.py": FIXTURE_SLO},
              [ObservabilityChecker],
              docs={"observability.md": doc})
    assert checks_of(fs) == ["detector-doc-drift"]
    assert "slo_burn" in fs[0].message


def test_observability_check_in_default_set():
    assert ObservabilityChecker in analysis.default_checkers()
