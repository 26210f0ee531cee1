"""TF-binding tests.

Reference pattern: ``test/parallel/test_tensorflow.py`` +
``test_tensorflow2_keras.py`` run under ``horovodrun -np 2``
(SURVEY.md §4) — same body at any world size, rank-aware asserts.
Here: single-controller semantics in-process (world size 1, real
collectives underneath on the 8-device CPU mesh) plus a 2-process
integration test over jax.distributed on loopback.
"""

import os
import sys
import textwrap

import numpy as np
import pytest

tf = pytest.importorskip("tensorflow")

import horovod_tpu.tensorflow as hvd  # noqa: E402
from horovod_tpu.runner import run  # noqa: E402


class TestSingleWorkerOps:
    def test_world(self):
        assert hvd.size() == 1
        assert hvd.rank() == 0

    @pytest.mark.parametrize("op", [hvd.Average, hvd.Sum, hvd.Min, hvd.Max,
                                    hvd.Product, hvd.Adasum])
    def test_allreduce_identity(self, op):
        t = tf.reshape(tf.range(6, dtype=tf.float32) + 1, (2, 3))
        out = hvd.allreduce(t, op=op)
        assert out.dtype == t.dtype
        np.testing.assert_allclose(out.numpy(), t.numpy())

    @pytest.mark.parametrize("dtype", [tf.float32, tf.float64, tf.float16,
                                       tf.bfloat16, tf.int32, tf.int64])
    def test_allreduce_dtypes(self, dtype):
        t = tf.cast(tf.range(4) + 1, dtype)
        out = hvd.allreduce(t, op=hvd.Sum)
        assert out.dtype == dtype
        np.testing.assert_array_equal(
            tf.cast(out, tf.float32).numpy(), tf.cast(t, tf.float32).numpy())

    def test_allreduce_scalar(self):
        # 0-dim tensors must survive the host bridge (regression: numpy
        # scalar decay broke torch.from_numpy / tf conversion).
        out = hvd.allreduce(tf.constant(3.0), op=hvd.Average)
        assert float(out) == pytest.approx(3.0)

    def test_allreduce_prescale(self):
        t = tf.ones((3,))
        out = hvd.allreduce(t, op=hvd.Sum, prescale_factor=2.0)
        np.testing.assert_allclose(out.numpy(), 2 * np.ones(3))

    def test_allreduce_fp16_compression(self):
        t = tf.constant([1.0, 2.0, 3.0])
        out = hvd.allreduce(t, op=hvd.Sum, compression=hvd.Compression.fp16)
        assert out.dtype == tf.float32
        np.testing.assert_allclose(out.numpy(), [1.0, 2.0, 3.0], rtol=1e-2)

    def test_grouped_allreduce(self):
        ts = [tf.ones((2,)), tf.range(3, dtype=tf.float32)]
        outs = hvd.grouped_allreduce(ts, op=hvd.Sum)
        assert len(outs) == 2
        np.testing.assert_allclose(outs[0].numpy(), np.ones(2))
        np.testing.assert_allclose(outs[1].numpy(), np.arange(3))

    def test_allgather(self):
        t = tf.reshape(tf.range(6, dtype=tf.float32), (3, 2))
        out = hvd.allgather(t)
        np.testing.assert_allclose(out.numpy(), t.numpy())

    def test_broadcast(self):
        t = tf.constant([1, 2, 3], dtype=tf.int32)
        out = hvd.broadcast(t, root_rank=0)
        np.testing.assert_array_equal(out.numpy(), [1, 2, 3])

    def test_alltoall(self):
        t = tf.range(4, dtype=tf.float32)
        out = hvd.alltoall(t)
        np.testing.assert_allclose(out.numpy(), np.arange(4))

    def test_alltoall_splits(self):
        t = tf.range(3, dtype=tf.float32)
        out, rsplits = hvd.alltoall(t, splits=tf.constant([3]))
        np.testing.assert_allclose(out.numpy(), np.arange(3))
        assert rsplits.numpy().tolist() == [3]

    def test_reducescatter(self):
        t = tf.range(4, dtype=tf.float32)
        out = hvd.reducescatter(t, op=hvd.Sum)
        np.testing.assert_allclose(out.numpy(), np.arange(4))

    def test_grouped_reducescatter(self):
        ts = [tf.range(4, dtype=tf.float32), tf.ones((2, 3))]
        outs = hvd.grouped_reducescatter(ts, op=hvd.Sum)
        np.testing.assert_allclose(outs[0].numpy(), np.arange(4))
        np.testing.assert_allclose(outs[1].numpy(), np.ones((2, 3)))

    def test_allreduce_indexed_slices(self):
        g = tf.IndexedSlices(values=tf.ones((2, 3)),
                             indices=tf.constant([0, 2]),
                             dense_shape=tf.constant([4, 3]))
        out = hvd.allreduce(g)
        assert isinstance(out, tf.IndexedSlices)
        np.testing.assert_allclose(out.values.numpy(), np.ones((2, 3)))

    def test_barrier_join(self):
        hvd.barrier()
        # join() returns the last-joined slot rank (reference: the last
        # joined worker's rank).
        assert hvd.join() >= 0

    def test_inside_tf_function(self):
        @tf.function
        def step(x):
            return hvd.allreduce(x, op=hvd.Sum)

        x = tf.constant([1.0, 2.0])
        out = step(x)
        np.testing.assert_allclose(out.numpy(), [1.0, 2.0])

    def test_alltoall_splits_inside_tf_function(self):
        # splits is a symbolic tensor while tracing (regression: the
        # bridge called .numpy() on it at trace time).
        @tf.function
        def step(x, s):
            out, rs = hvd.alltoall(x, splits=s)
            return out, rs

        out, rs = step(tf.range(3, dtype=tf.float32), tf.constant([3]))
        np.testing.assert_allclose(out.numpy(), np.arange(3))
        assert rs.numpy().tolist() == [3]

    def test_broadcast_variables(self):
        v = tf.Variable([1.0, 2.0])
        b = tf.Variable([True, False])
        hvd.broadcast_variables([v, b], root_rank=0)
        np.testing.assert_allclose(v.numpy(), [1.0, 2.0])
        assert b.numpy().tolist() == [True, False]


class TestDistributedOptimizer:
    def _model(self):
        m = tf.keras.Sequential(
            [tf.keras.layers.Dense(2, use_bias=False,
                                   kernel_initializer="ones")])
        m.build((None, 3))
        return m

    def test_wraps_and_applies(self):
        m = self._model()
        opt = hvd.DistributedOptimizer(tf.keras.optimizers.SGD(0.1))
        w0 = m.trainable_variables[0].numpy().copy()
        with tf.GradientTape() as tape:
            loss = tf.reduce_sum(m(tf.ones((1, 3))))
        grads = tape.gradient(loss, m.trainable_variables)
        opt.apply_gradients(zip(grads, m.trainable_variables))
        np.testing.assert_allclose(
            m.trainable_variables[0].numpy(), w0 - 0.1 * np.ones((3, 2)),
            atol=1e-6)

    def test_num_groups_splits_fused_groups(self):
        """Reference arg num_groups: the dense grad set rides N fused
        grouped ops instead of one — applied update identical."""
        m = tf.keras.Sequential([
            tf.keras.layers.Dense(2, use_bias=True,
                                  kernel_initializer="ones"),
            tf.keras.layers.Dense(1, use_bias=True,
                                  kernel_initializer="ones"),
        ])
        m.build((None, 3))
        opt = hvd.DistributedOptimizer(tf.keras.optimizers.SGD(0.1),
                                       num_groups=3)
        w0 = [v.numpy().copy() for v in m.trainable_variables]
        with tf.GradientTape() as tape:
            loss = tf.reduce_sum(m(tf.ones((1, 3))))
        grads = tape.gradient(loss, m.trainable_variables)
        opt.apply_gradients(zip(grads, m.trainable_variables))
        for v, w, g in zip(m.trainable_variables, w0, grads):
            np.testing.assert_allclose(v.numpy(), w - 0.1 * g.numpy(),
                                       atol=1e-6)

    def test_num_groups_negative_rejected(self):
        m = self._model()
        opt = hvd.DistributedOptimizer(tf.keras.optimizers.SGD(0.1),
                                       num_groups=-1)
        with tf.GradientTape() as tape:
            loss = tf.reduce_sum(m(tf.ones((1, 3))))
        grads = tape.gradient(loss, m.trainable_variables)
        with pytest.raises(ValueError, match="num_groups"):
            opt.apply_gradients(zip(grads, m.trainable_variables))

    def test_gradient_tape_num_groups(self):
        m = self._model()
        tape = hvd.DistributedGradientTape(tf.GradientTape(), num_groups=2)
        with tape:
            loss = tf.reduce_sum(m(tf.ones((1, 3))))
        grads = tape.gradient(loss, m.trainable_variables)
        np.testing.assert_allclose(grads[0].numpy(), np.ones((3, 2)),
                                   atol=1e-6)

    def test_double_wrap_rejected(self):
        opt = hvd.DistributedOptimizer(tf.keras.optimizers.SGD(0.1))
        with pytest.raises(ValueError, match="already distributed"):
            hvd.DistributedOptimizer(opt)

    def test_backward_passes_per_step(self):
        m = self._model()
        opt = hvd.DistributedOptimizer(tf.keras.optimizers.SGD(0.1),
                                       backward_passes_per_step=2)
        w0 = m.trainable_variables[0].numpy().copy()
        g1 = [tf.ones((3, 2))]
        g2 = [3.0 * tf.ones((3, 2))]
        opt.apply(g1, m.trainable_variables)  # accumulate only
        np.testing.assert_allclose(m.trainable_variables[0].numpy(), w0)
        opt.apply(g2, m.trainable_variables)  # mean (=2) applied
        np.testing.assert_allclose(
            m.trainable_variables[0].numpy(), w0 - 0.1 * 2.0 * np.ones((3, 2)),
            atol=1e-6)
        # accumulators reset: next pair starts fresh
        opt.apply(g1, m.trainable_variables)
        np.testing.assert_allclose(
            m.trainable_variables[0].numpy(), w0 - 0.1 * 2.0 * np.ones((3, 2)),
            atol=1e-6)

    def test_backward_passes_with_none_grad(self):
        # Unconnected variables produce None grads; aggregation must not
        # crash on them (regression: tf.zeros_like(None)).
        m = self._model()
        extra = tf.Variable([1.0], name="unconnected")
        opt = hvd.DistributedOptimizer(tf.keras.optimizers.SGD(0.1),
                                       backward_passes_per_step=2)
        g = [tf.ones((3, 2)), None]
        opt.apply(g, m.trainable_variables + [extra])
        opt.apply(g, m.trainable_variables + [extra])
        np.testing.assert_allclose(extra.numpy(), [1.0])  # untouched

    def test_model_fit(self):
        m = self._model()
        m.compile(optimizer=hvd.DistributedOptimizer(
                      tf.keras.optimizers.SGD(0.01)),
                  loss="mse", jit_compile=False)
        x = np.random.RandomState(0).randn(8, 3).astype(np.float32)
        y = np.zeros((8, 2), np.float32)
        h = m.fit(x, y, epochs=1, batch_size=4, verbose=0)
        assert np.isfinite(h.history["loss"][0])

    def test_gradient_tape(self):
        v = tf.Variable([1.0, 2.0])
        with hvd.DistributedGradientTape(tf.GradientTape()) as tape:
            loss = tf.reduce_sum(v * v)
        g = tape.gradient(loss, [v])[0]
        np.testing.assert_allclose(g.numpy(), [2.0, 4.0])


class TestKerasCallbacks:
    def _fit(self, callbacks, epochs=2, lr=0.4):
        import horovod_tpu.tensorflow.keras as hvdk

        m = tf.keras.Sequential([tf.keras.layers.Dense(1)])
        m.compile(optimizer=hvdk.DistributedOptimizer(
                      tf.keras.optimizers.SGD(lr)),
                  loss="mse", jit_compile=False)
        x = np.ones((8, 2), np.float32)
        y = np.ones((8, 1), np.float32)
        m.fit(x, y, epochs=epochs, batch_size=4, verbose=0,
              callbacks=callbacks)
        return m

    def test_broadcast_callback(self):
        import horovod_tpu.tensorflow.keras as hvdk

        cb = hvdk.callbacks.BroadcastGlobalVariablesCallback(root_rank=0)
        self._fit([cb], epochs=1)
        assert cb.broadcast_done

    def test_metric_average_callback(self):
        import horovod_tpu.tensorflow.keras as hvdk

        self._fit([hvdk.callbacks.MetricAverageCallback()], epochs=1)

    def test_warmup_callback(self):
        import horovod_tpu.tensorflow.keras as hvdk

        cb = hvdk.callbacks.LearningRateWarmupCallback(
            initial_lr=0.4, warmup_epochs=2)
        m = self._fit([cb], epochs=3, lr=0.4)
        # After warmup completes the LR is the full target rate.
        assert float(m.optimizer.learning_rate.numpy()) == pytest.approx(0.4)

    def test_schedule_callback(self):
        import horovod_tpu.tensorflow.keras as hvdk

        cb = hvdk.callbacks.LearningRateScheduleCallback(
            initial_lr=0.4, multiplier=lambda e: 0.5 ** e, staircase=True)
        m = self._fit([cb], epochs=2, lr=0.4)
        assert float(m.optimizer.learning_rate.numpy()) == pytest.approx(0.2)

    def test_momentum_correction(self):
        from horovod_tpu.tensorflow.keras.callbacks import _set_lr

        v = tf.Variable([1.0, 2.0])
        opt = tf.keras.optimizers.SGD(0.1, momentum=0.9)
        opt.build([v])
        opt.apply([tf.ones((2,))], [v])   # populate momentum buffer
        mom_before = [x.numpy().copy() for x in opt.variables
                      if "momentum" in str(getattr(x, "path", x.name)).lower()]
        assert mom_before, "SGD momentum slot not found"
        _set_lr(opt, 0.2, momentum_correction=True)
        mom_after = [x.numpy() for x in opt.variables
                     if "momentum" in str(getattr(x, "path", x.name)).lower()]
        for b, a in zip(mom_before, mom_after):
            np.testing.assert_allclose(a, b * 2.0, rtol=1e-6)

    def test_standalone_keras_alias(self):
        import horovod_tpu.keras as hvk

        assert hvk.DistributedOptimizer is not None
        assert hvk.size() == 1


_WORKER = textwrap.dedent("""
    import os
    os.environ['JAX_PLATFORMS'] = 'cpu'
    import jax
    jax.config.update('jax_platforms', 'cpu')
    import numpy as np
    import tensorflow as tf
    import horovod_tpu.tensorflow as hvd

    hvd.init()
    assert hvd.size() == 2, hvd.size()
    r = hvd.rank()

    t = tf.fill((4,), float(r + 1))
    np.testing.assert_allclose(hvd.allreduce(t).numpy(), np.full(4, 1.5))
    np.testing.assert_allclose(hvd.allreduce(t, op=hvd.Sum).numpy(),
                               np.full(4, 3.0))
    np.testing.assert_allclose(hvd.allreduce(t, op=hvd.Min).numpy(),
                               np.full(4, 1.0))

    outs = hvd.grouped_allreduce(
        [tf.fill((2,), float(r)), tf.fill((3,), 2.0 * r)], op=hvd.Sum)
    np.testing.assert_allclose(outs[0].numpy(), np.ones(2))
    np.testing.assert_allclose(outs[1].numpy(), np.full(3, 2.0))

    # ragged allgather: 2 rows from rank0, 3 from rank1
    g = hvd.allgather(tf.fill((2 + r, 2), float(r)))
    assert g.shape == (5, 2), g.shape
    np.testing.assert_allclose(g.numpy()[:2], np.zeros((2, 2)))
    np.testing.assert_allclose(g.numpy()[2:], np.ones((3, 2)))

    out = hvd.broadcast(tf.fill((2,), float(r)), root_rank=1)
    np.testing.assert_allclose(out.numpy(), np.ones(2))

    x = tf.range(4, dtype=tf.float32) + 10 * r
    got = hvd.alltoall(x)
    exp = np.array([2.0 * r, 2.0 * r + 1, 10 + 2.0 * r, 10 + 2.0 * r + 1])
    np.testing.assert_allclose(got.numpy(), exp)

    x = tf.range(4, dtype=tf.float32) * (r + 1)
    out = hvd.reducescatter(x, op=hvd.Sum)
    exp = np.array([0.0, 3.0]) if r == 0 else np.array([6.0, 9.0])
    np.testing.assert_allclose(out.numpy(), exp)

    # inside tf.function too
    @tf.function
    def fstep(v):
        return hvd.allreduce(v, op=hvd.Sum)
    np.testing.assert_allclose(fstep(tf.fill((2,), float(r + 1))).numpy(),
                               np.full(2, 3.0))

    # DistributedOptimizer: different grads -> averaged update
    m = tf.keras.Sequential([tf.keras.layers.Dense(
        2, use_bias=False, kernel_initializer='ones')])
    m.build((None, 3))
    hvd.broadcast_variables(m.variables, root_rank=0)
    w0 = m.trainable_variables[0].numpy().copy()
    opt = hvd.DistributedOptimizer(tf.keras.optimizers.SGD(0.1))
    grads = [tf.fill((3, 2), float(r + 1))]   # avg = 1.5
    opt.apply_gradients(zip(grads, m.trainable_variables))
    np.testing.assert_allclose(m.trainable_variables[0].numpy(),
                               w0 - 0.1 * 1.5 * np.ones((3, 2)), atol=1e-6)

    obj = hvd.broadcast_object({'rank': r}, root_rank=1)
    assert obj['rank'] == 1
    hvd.barrier()
    print('tf worker', r, 'ok')
""")


@pytest.mark.slow
class TestTwoWorkerIntegration:
    def test_two_worker_tf_numerics(self, tmp_path):
        script = tmp_path / "tf_worker.py"
        script.write_text(_WORKER)
        repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = {"PYTHONPATH": repo_root + os.pathsep
               + os.environ.get("PYTHONPATH", "")}
        rc = run(2, [sys.executable, str(script)], start_timeout=300, env=env)
        assert rc == 0


class TestOpConstants:
    def test_world_fact_ops(self):
        import tensorflow as tf

        import horovod_tpu.tensorflow as hvt

        assert int(hvt.size_op()) == 1          # one controller process
        assert int(hvt.rank_op()) == 0
        assert int(hvt.local_rank_op()) == 0
        assert int(hvt.process_set_included_op()) == 1
        assert hvt.size_op().dtype == tf.int32

    def test_ops_usable_in_graph(self):
        import tensorflow as tf

        import horovod_tpu.tensorflow as hvt

        @tf.function
        def f(x):
            return x * tf.cast(hvt.size_op(), tf.float32) + \
                tf.cast(hvt.rank_op(), tf.float32)

        out = f(tf.constant(3.0))
        assert float(out) == 3.0


class TestJitCompile:
    """tf.function(jit_compile=True) — the round-4 waiver is RETIRED.

    The native TF-XLA adapter (``tensorflow/xla_ops.py`` +
    ``native/src/tf_xla_ops.cc``) is the reference's ``xla_mpi_ops.cc``
    equivalent: collectives inside XLA-compiled TF graphs lower to a
    host CustomCall registered in TF's own XLA runtime.  These tests
    pin the capability; the Adasum-grouped case pins the REMAINING
    boundary (per-tensor projections don't commute with the concat
    fusion buffer).
    """

    def test_allreduce_under_jit_compile(self):
        import tensorflow as tf

        import horovod_tpu.tensorflow as hvt
        from horovod_tpu.tensorflow import xla_ops

        assert xla_ops.available(), xla_ops.load_error()

        @tf.function(jit_compile=True)
        def f(x):
            return hvt.allreduce(x, op=hvt.Sum) * 2.0

        out = f(tf.constant([1.0, 2.0]))
        # Single controller: sum over one process is identity; the op
        # executed INSIDE the compiled program (x2 fused around it).
        assert np.allclose(out.numpy(), [2.0, 4.0]), out

    def test_grouped_allreduce_and_tape_under_jit_compile(self):
        import tensorflow as tf

        import horovod_tpu.tensorflow as hvt

        v = tf.Variable([[1.0, 2.0], [3.0, 4.0]])
        w = tf.Variable([5.0, 6.0])

        @tf.function(jit_compile=True)
        def step():
            with tf.GradientTape() as tape:
                loss = tf.reduce_sum(v * v) + tf.reduce_sum(w)
            tape = hvt.DistributedGradientTape(tape)
            gv, gw = tape.gradient(loss, [v, w])
            return gv, gw

        gv, gw = step()
        assert np.allclose(gv.numpy(), 2 * v.numpy())
        assert np.allclose(gw.numpy(), [1.0, 1.0])

    def test_mixed_dtype_grouped_under_jit_compile(self):
        import tensorflow as tf

        import horovod_tpu.tensorflow as hvt

        @tf.function(jit_compile=True)
        def f(a, b):
            return hvt.grouped_allreduce([a, b], op=hvt.Sum)

        a, b = f(tf.ones((3,)), tf.ones((2,), tf.int32) * 2)
        assert np.allclose(a.numpy(), 1.0) and a.dtype == tf.float32
        assert np.all(b.numpy() == 2) and b.dtype == tf.int32

    def test_fp16_compression_under_jit_compile(self):
        import tensorflow as tf

        import horovod_tpu.tensorflow as hvt

        @tf.function(jit_compile=True)
        def f(x):
            return hvt.allreduce(x, op=hvt.Average,
                                 compression=hvt.Compression.fp16)

        out = f(tf.fill((8,), 1.5))
        assert out.dtype == tf.float32
        assert np.allclose(out.numpy(), 1.5, atol=1e-3)

    def test_adasum_grouped_under_jit_compile(self):
        """Adasum groups emit one native call per tensor (projections
        are per-tensor; concat would corrupt them) — compiled fine."""
        import tensorflow as tf

        import horovod_tpu.tensorflow as hvt

        @tf.function(jit_compile=True)
        def f(x, y):
            return hvt.grouped_allreduce([x, y], op=hvt.Adasum)

        a, b = f(tf.fill((2,), 3.0), tf.fill((3,), 5.0))
        # Single controller: Adasum over one rank is the identity.
        assert np.allclose(a.numpy(), 3.0) and np.allclose(b.numpy(), 5.0)

    def test_keras_fit_with_jit_compile(self):
        """The reference's HOROVOD_ENABLE_XLA_OPS demo scenario:
        ``model.compile(..., jit_compile=True)`` with the distributed
        optimizer — the whole Keras train step XLA-compiles with the
        gradient allreduce inside."""
        import tensorflow as tf

        import horovod_tpu.tensorflow.keras as hvk

        model = tf.keras.Sequential([
            tf.keras.Input(shape=(4,)),
            tf.keras.layers.Dense(8, activation="relu"),
            tf.keras.layers.Dense(1),
        ])
        opt = hvk.DistributedOptimizer(tf.keras.optimizers.SGD(0.05))
        model.compile(optimizer=opt, loss="mse", jit_compile=True)
        rng = np.random.RandomState(0)
        x = rng.randn(64, 4).astype(np.float32)
        y = (x @ np.array([[1.], [2.], [-1.], [.5]],
                          np.float32)).astype(np.float32)
        h = model.fit(x, y, epochs=3, batch_size=16, verbose=0)
        assert h.history["loss"][-1] < h.history["loss"][0], h.history

    def test_sparse_allgather_remains_pinned_boundary(self):
        """The remaining jit_compile boundary: non-allreduce
        collectives (broadcast/allgather/alltoall/reducescatter,
        IndexedSlices) still ride py_function — matching the reference
        adapter's allreduce-only scope; use sparse_as_dense=True."""
        import tensorflow as tf

        import horovod_tpu.tensorflow as hvt

        @tf.function(jit_compile=True)
        def f(x):
            return hvt.allgather(x)

        with pytest.raises(tf.errors.InvalidArgumentError,
                           match="EagerPyFunc"):
            f(tf.ones((2, 2)))

    def test_plain_tf_function_is_the_supported_path(self):
        import tensorflow as tf

        import horovod_tpu.tensorflow as hvt

        @tf.function  # no jit_compile: the documented alternative
        def f(x):
            return hvt.allreduce(x, op=hvt.Sum)

        out = f(tf.ones((4,)))
        assert float(tf.reduce_sum(out)) == 4.0
