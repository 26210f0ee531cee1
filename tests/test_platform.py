"""Device-facing start-up choices: where the compile cache goes, which
peaks a chip has, and the JAX spellings of the one installed version."""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu import _compat
from horovod_tpu.utils import mfu, platform

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --- compile cache placement -------------------------------------------------

@pytest.fixture
def cache_dir_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_cache_env_set_leaves_config_untouched(monkeypatch, tmp_path,
                                               cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a, **k: calls.append(a))
    assert platform.place_compile_cache() == str(tmp_path)
    assert calls == []


def test_cache_env_unset_goes_to_checkout(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = platform.place_compile_cache()
    assert path == os.path.join(ROOT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path


def test_cache_path_is_fixed(monkeypatch, cache_dir_config):
    """The path is part of the cache key: nothing of this process or
    this moment may be in it, and home is not a fallback."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("HOROVOD_COMPILE_CACHE", "/nowhere")   # retired knob
    path = platform.place_compile_cache()
    assert path == platform.place_compile_cache()
    assert os.path.dirname(path) == ROOT      # not home, not a temporary
    name = os.path.basename(path)
    assert str(os.getpid()) not in name and not re.search(r"\d", name)


# --- peaks table -------------------------------------------------------------

class _Dev:
    def __init__(self, kind):
        self.device_kind = kind


@pytest.mark.parametrize("kind", ["TPU v5 lite", "TPU v5e"])
def test_v5e_peaks(kind):
    assert mfu.peak_tflops(_Dev(kind)) == 197.0
    assert mfu.chip_peaks(_Dev(kind)).hbm_gbps == 819.0


@pytest.mark.parametrize("kind", ["cpu", "TPU v5e chip", "TPU v9", None])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(KeyError, match="no published peaks"):
        mfu.peak_tflops(_Dev(kind))


def test_no_env_override_of_peaks(monkeypatch):
    monkeypatch.setenv("HVD_TPU_PEAK_TFLOPS", "123")
    assert mfu.peak_tflops(_Dev("TPU v5e")) == 197.0
    with pytest.raises(KeyError):
        mfu.peak_tflops(_Dev("cpu"))


def test_aot_compile_does_not_swallow_a_failed_compile():
    def bad(x):
        raise ValueError("boom at trace time")

    with pytest.raises(ValueError, match="boom"):
        mfu.aot_compile_with_flops(jax.jit(bad), jnp.ones(3))
    compiled, flops = mfu.aot_compile_with_flops(
        jax.jit(lambda x: x @ x), jnp.ones((8, 8)))
    assert flops and compiled(jnp.ones((8, 8))).shape == (8, 8)


# --- _compat on the installed jax --------------------------------------------

def test_compat_names_are_the_installed_jax():
    assert _compat.enable_x64 is jax.enable_x64
    src = open(_compat.__file__).read()
    assert "ImportError" not in src and "hasattr" not in src
    with _compat.enable_x64(True):
        assert jnp.asarray(np.float64(1.0)).dtype == jnp.float64
    assert jnp.asarray(np.float64(1.0)).dtype == jnp.float32


def test_compat_shard_map_axis_size_and_tracer():
    mesh = hvd.global_mesh().mesh
    n = hvd.size()
    seen = {}

    def body(x):
        seen["one"] = _compat.axis_size("hvd")
        seen["product"] = _compat.axis_size(("hvd", "hvd"))
        seen["tracer"] = _compat.is_tracer(x)
        return jax.lax.psum(x, "hvd")

    out = _compat.shard_map(body, mesh=mesh, in_specs=P("hvd"),
                            out_specs=P())(jnp.ones((n, 3)))
    np.testing.assert_allclose(np.asarray(out), np.full((1, 3), n))
    assert seen == {"one": n, "product": n * n, "tracer": True}
    assert not _compat.is_tracer(out)
