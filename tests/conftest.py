"""Test harness: an 8-device CPU mesh standing in for a TPU slice.

Mirrors the reference's CI strategy (SURVEY.md §4): run real collectives
on loopback (there: Gloo/MPI over 127.0.0.1 with oversubscribed slots;
here: XLA's CPU backend with ``--xla_force_host_platform_device_count=8``
virtual devices).  No mocked backends — every test exercises the same HLO
lowering path as TPU hardware.

The platform is pinned to the CPU here, before any backend initializes,
so the suite runs the same way on a machine that has a chip.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# No persistent compilation cache under test, wherever the environment
# points it: XLA:CPU reloads warn about host features, and an AOT
# compile for a described TPU (tests/test_tpu_compile.py) can be written
# to the cache but not read back without the chip.
jax.config.update("jax_enable_compilation_cache", False)

import pytest  # noqa: E402

import horovod_tpu as hvd  # noqa: E402
from horovod_tpu.analysis import sanitizer as _sanitizer  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _init_horovod_tpu():
    # hvdsan (HVD_TPU_SANITIZE=1): instrument every `# guarded-by`
    # class attribute BEFORE init builds the long-lived singletons, so
    # the whole suite runs under read+write lock assertions and the
    # Eraser lockset pass (docs/lint.md).
    if _sanitizer.enabled():
        _sanitizer.install()
    hvd.init()
    yield
    hvd.shutdown()


@pytest.fixture(autouse=True)
def _hvdsan_teardown_audit(request):
    """Per-test resource-lifecycle audit (sanitize mode only): any
    refcounted resource — KV blocks, snapshot buffers, reserved elastic
    slots — still held when the test ends fails THAT test with the
    leak named, instead of poisoning a later one."""
    if not _sanitizer.enabled() \
            or request.node.get_closest_marker("no_leak_audit"):
        yield
        return
    import gc

    # Baseline-and-delta, not reset: registrations persist across tests
    # so a SHARED fixture's pool is still audited — the test is charged
    # only for what it added on top of the state it inherited.
    baseline = _sanitizer.audit_baseline()
    yield
    # Collect first: a pool that died WITH the test leaked nothing (its
    # blocks die with it) — the audit targets resources still held by
    # survivors (shared fixtures, cross-test engines), the class that
    # poisons later tests.
    gc.collect()
    leaks = _sanitizer.audit_check(record=False, baseline=baseline)
    if leaks:
        pytest.fail("hvdsan resource-lifecycle audit: "
                    + "; ".join(leaks), pytrace=False)


@pytest.fixture(scope="session")
def world_size():
    return hvd.size()
