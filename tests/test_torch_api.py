"""Torch-binding tests.

Reference pattern: ``test/parallel/test_torch.py`` run under
``horovodrun -np 2`` (SURVEY.md §4) — same test body at any world size
with rank-aware asserts.  Here: single-controller semantics checked
in-process (world size 1 from the torch worker's view, real collectives
underneath on the 8-device CPU mesh), and the true multi-worker numerics
in a 2-process integration test over jax.distributed on loopback.
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
import torch

import horovod_tpu.torch as hvd
from horovod_tpu.runner import run


class TestSingleWorkerOps:
    """With one controller process, torch-world size is 1: reductions are
    identities but still traverse the full slot-stack collective path."""

    def test_world(self):
        assert hvd.size() == 1
        assert hvd.rank() == 0

    @pytest.mark.parametrize("op", [hvd.Average, hvd.Sum, hvd.Min, hvd.Max,
                                    hvd.Product, hvd.Adasum])
    def test_allreduce_identity(self, op):
        t = torch.arange(6, dtype=torch.float32).reshape(2, 3) + 1
        out = hvd.allreduce(t, op=op)
        assert torch.allclose(out, t), (op, out)
        assert out.dtype == t.dtype

    @pytest.mark.parametrize("dtype", [torch.float32, torch.float64,
                                       torch.float16, torch.bfloat16,
                                       torch.int32, torch.int64])
    def test_allreduce_dtypes(self, dtype):
        t = (torch.arange(4) + 1).to(dtype)
        out = hvd.allreduce(t, op=hvd.Sum)
        assert out.dtype == dtype
        assert torch.equal(out.float(), t.float())

    def test_allreduce_scalar(self):
        # 0-dim tensors must survive the host bridge (regression: numpy
        # scalar decay broke torch.from_numpy).
        out = hvd.allreduce(torch.tensor(3.0), op=hvd.Average)
        assert out.item() == pytest.approx(3.0)
        assert out.dim() == 0

    def test_allreduce_inplace(self):
        t = torch.ones(3)
        out = hvd.allreduce_(t, op=hvd.Sum)
        assert out is t

    def test_allreduce_async_poll(self):
        t = torch.ones(4)
        h = hvd.allreduce_async(t)
        out = hvd.synchronize(h)
        assert hvd.poll(h)
        assert torch.allclose(out, t)

    def test_allreduce_scales(self):
        t = torch.full((3,), 2.0)
        out = hvd.allreduce(t, op=hvd.Sum, prescale_factor=0.5,
                            postscale_factor=10.0)
        assert torch.allclose(out, torch.full((3,), 10.0))

    def test_allreduce_fp16_compression(self):
        t = torch.full((5,), 3.0)
        out = hvd.allreduce(t, op=hvd.Sum, compression=hvd.Compression.fp16)
        assert out.dtype == torch.float32
        assert torch.allclose(out, t)

    def test_grouped_allreduce(self):
        ts = [torch.ones(3), torch.full((2, 2), 2.0)]
        outs = hvd.grouped_allreduce(ts, op=hvd.Sum)
        assert len(outs) == 2
        assert torch.allclose(outs[0], ts[0])
        assert torch.allclose(outs[1], ts[1])

    def test_allgather(self):
        t = torch.arange(6, dtype=torch.float32).reshape(3, 2)
        out = hvd.allgather(t)
        assert torch.equal(out, t)

    def test_broadcast(self):
        t = torch.arange(4, dtype=torch.float32)
        out = hvd.broadcast(t, root_rank=0)
        assert torch.equal(out, t)
        t2 = torch.zeros(4)
        hvd.broadcast_(t2, root_rank=0)
        assert torch.equal(t2, torch.zeros(4))

    def test_alltoall(self):
        t = torch.arange(4, dtype=torch.float32)
        out = hvd.alltoall(t)
        assert torch.equal(out, t)

    def test_alltoall_splits(self):
        t = torch.arange(3, dtype=torch.float32)
        out, rsplits = hvd.alltoall(t, torch.tensor([3]))
        assert torch.equal(out, t)
        assert rsplits.tolist() == [3]

    def test_reducescatter(self):
        t = torch.arange(4, dtype=torch.float32)
        out = hvd.reducescatter(t)
        assert torch.equal(out, t)

    def test_grouped_reducescatter(self):
        ts = [torch.arange(4, dtype=torch.float32),
              torch.ones(2, 3)]
        outs = hvd.grouped_reducescatter(ts)
        assert torch.equal(outs[0], ts[0])
        assert torch.equal(outs[1], ts[1])

    def test_barrier_and_join(self):
        hvd.barrier()
        assert hvd.join() >= 0

    def test_broadcast_object(self):
        assert hvd.broadcast_object({"a": 1}) == {"a": 1}
        assert hvd.allgather_object(7) == [7]


class TestBroadcastState:
    def test_broadcast_parameters_state_dict(self):
        model = torch.nn.Linear(4, 2)
        before = {k: v.clone() for k, v in model.state_dict().items()}
        hvd.broadcast_parameters(model.state_dict(), root_rank=0)
        for k, v in model.state_dict().items():
            assert torch.allclose(v, before[k])

    def test_broadcast_optimizer_state(self):
        model = torch.nn.Linear(4, 2)
        opt = torch.optim.Adam(model.parameters(), lr=0.01)
        hvd.broadcast_optimizer_state(opt, root_rank=0)
        # Lazy Adam state must have been materialized for the broadcast.
        assert len(opt.state_dict()["state"]) > 0

    def test_rejects_positional_params(self):
        model = torch.nn.Linear(2, 2)
        with pytest.raises(ValueError):
            hvd.broadcast_parameters(list(model.parameters()))


class TestDistributedOptimizer:
    def _models(self):
        torch.manual_seed(0)
        model = torch.nn.Sequential(torch.nn.Linear(4, 8), torch.nn.Tanh(),
                                    torch.nn.Linear(8, 2))
        ref = torch.nn.Sequential(torch.nn.Linear(4, 8), torch.nn.Tanh(),
                                  torch.nn.Linear(8, 2))
        ref.load_state_dict(model.state_dict())
        return model, ref

    def test_matches_plain_sgd(self):
        model, ref = self._models()
        opt = hvd.DistributedOptimizer(
            torch.optim.SGD(model.parameters(), lr=0.1, momentum=0.9),
            named_parameters=model.named_parameters())
        ropt = torch.optim.SGD(ref.parameters(), lr=0.1, momentum=0.9)
        assert isinstance(opt, torch.optim.SGD)
        x = torch.randn(8, 4)
        for _ in range(3):
            opt.zero_grad()
            model(x).pow(2).sum().backward()
            opt.step()
            ropt.zero_grad()
            ref(x).pow(2).sum().backward()
            ropt.step()
        for p, q in zip(model.parameters(), ref.parameters()):
            assert torch.allclose(p, q, atol=1e-6)

    def test_num_groups_matches_per_param_path(self):
        """Reference arg num_groups: dense grads ride num_groups fused
        grouped ops instead of one per parameter — numerics identical."""
        model, ref = self._models()
        opt = hvd.DistributedOptimizer(
            torch.optim.SGD(model.parameters(), lr=0.1, momentum=0.9),
            named_parameters=model.named_parameters(), num_groups=2)
        ropt = hvd.DistributedOptimizer(
            torch.optim.SGD(ref.parameters(), lr=0.1, momentum=0.9),
            named_parameters=ref.named_parameters())
        x = torch.randn(8, 4)
        for _ in range(2):
            opt.zero_grad()
            model(x).pow(2).sum().backward()
            opt.step()
            ropt.zero_grad()
            ref(x).pow(2).sum().backward()
            ropt.step()
        for p, q in zip(model.parameters(), ref.parameters()):
            assert torch.allclose(p, q, atol=1e-6)

    def test_num_groups_dispatches_group_when_full(self):
        """Overlap path: a group's fused op is issued as soon as every
        member's hook fired — before synchronize()/step()."""
        model, _ = self._models()
        opt = hvd.DistributedOptimizer(
            torch.optim.SGD(model.parameters(), lr=0.1),
            named_parameters=model.named_parameters(), num_groups=1)
        model(torch.randn(2, 4)).sum().backward()
        # All params got grads, so the single group must already be
        # in-flight ("group" handles), not parked as pending.
        kinds = {h[0] for h in opt._handles.values()
                 if isinstance(h, tuple)}
        assert kinds == {"group"}, kinds
        opt.step()

    def test_num_groups_with_sparse_as_dense(self):
        """Densified sparse grads join their fused group (parity with
        the TF binding's sparse_as_dense + num_groups behavior)."""
        torch.manual_seed(0)
        emb = torch.nn.EmbeddingBag(10, 4, sparse=True, mode="sum")
        ref = torch.nn.EmbeddingBag(10, 4, sparse=True, mode="sum")
        ref.load_state_dict(emb.state_dict())
        opt = hvd.DistributedOptimizer(
            torch.optim.SGD(emb.parameters(), lr=0.1),
            named_parameters=emb.named_parameters(),
            sparse_as_dense=True, num_groups=1)
        ropt = torch.optim.SGD(ref.parameters(), lr=0.1)
        idx = torch.tensor([1, 2, 4, 1])
        off = torch.tensor([0, 2])
        opt.zero_grad()
        emb(idx, off).sum().backward()
        opt.step()
        ref(idx, off).sum().backward()
        ref.weight.grad = ref.weight.grad.to_dense()
        ropt.step()
        assert torch.allclose(emb.weight, ref.weight, atol=1e-6)

    def test_grouped_double_backward_without_step_raises(self):
        """A parameter enqueued twice in the grouped path before step()
        would double-count inside the fused wire (silent corruption);
        mirror the reference's "gradient computed twice" assertion."""
        model, _ = self._models()
        opt = hvd.DistributedOptimizer(
            torch.optim.SGD(model.parameters(), lr=0.1),
            named_parameters=model.named_parameters(), num_groups=1)
        x = torch.randn(2, 4)
        model(x).sum().backward()
        with pytest.raises((AssertionError, RuntimeError),
                           match="computed twice"):
            model(x).sum().backward()

    def test_num_groups_caps_and_validates(self):
        model, _ = self._models()
        # More groups than params: capped, still correct.
        opt = hvd.DistributedOptimizer(
            torch.optim.SGD(model.parameters(), lr=0.1),
            named_parameters=model.named_parameters(), num_groups=99)
        model(torch.randn(2, 4)).sum().backward()
        opt.step()
        with pytest.raises(ValueError, match="num_groups"):
            hvd.DistributedOptimizer(
                torch.optim.SGD(model.parameters(), lr=0.1), num_groups=-1)

    def test_backward_passes_per_step(self):
        model, ref = self._models()
        opt = hvd.DistributedOptimizer(
            torch.optim.SGD(model.parameters(), lr=0.1),
            named_parameters=model.named_parameters(),
            backward_passes_per_step=2)
        ropt = torch.optim.SGD(ref.parameters(), lr=0.1)
        xs = [torch.randn(4, 4) for _ in range(2)]
        opt.zero_grad()
        for x in xs:
            model(x).sum().backward()
        opt.step()
        # Reference semantics: the accumulated gradient is averaged over
        # the local passes before the cross-worker average.
        ropt.zero_grad()
        for x in xs:
            (ref(x).sum() / 2).backward()
        ropt.step()
        for p, q in zip(model.parameters(), ref.parameters()):
            assert torch.allclose(p, q, atol=1e-6)

    def test_zero_grad_race_guard(self):
        model, _ = self._models()
        opt = hvd.DistributedOptimizer(
            torch.optim.SGD(model.parameters(), lr=0.1),
            named_parameters=model.named_parameters())
        model(torch.randn(2, 4)).sum().backward()
        with pytest.raises(AssertionError):
            opt.zero_grad()
        opt.synchronize()
        with opt.skip_synchronize():
            opt.step()

    def test_synchronize_then_skip(self):
        model, ref = self._models()
        opt = hvd.DistributedOptimizer(
            torch.optim.SGD(model.parameters(), lr=0.1),
            named_parameters=model.named_parameters())
        ropt = torch.optim.SGD(ref.parameters(), lr=0.1)
        x = torch.randn(4, 4)
        opt.zero_grad()
        model(x).sum().backward()
        opt.synchronize()
        torch.nn.utils.clip_grad_norm_(model.parameters(), 1e9)
        with opt.skip_synchronize():
            opt.step()
        ropt.zero_grad()
        ref(x).sum().backward()
        ropt.step()
        for p, q in zip(model.parameters(), ref.parameters()):
            assert torch.allclose(p, q, atol=1e-6)

    def test_predivide_factor(self):
        model, ref = self._models()
        opt = hvd.DistributedOptimizer(
            torch.optim.SGD(model.parameters(), lr=0.1),
            named_parameters=model.named_parameters(),
            gradient_predivide_factor=4.0)
        ropt = torch.optim.SGD(ref.parameters(), lr=0.1)
        x = torch.randn(4, 4)
        opt.zero_grad()
        model(x).sum().backward()
        opt.step()
        ropt.zero_grad()
        ref(x).sum().backward()
        ropt.step()
        for p, q in zip(model.parameters(), ref.parameters()):
            assert torch.allclose(p, q, atol=1e-6)


class TestSyncBatchNorm:
    @pytest.mark.parametrize("dims", [2, 4])
    def test_matches_batchnorm_single_worker(self, dims):
        torch.manual_seed(0)
        shape = (6, 3) if dims == 2 else (6, 3, 4, 4)
        x = torch.randn(*shape, dtype=torch.float64, requires_grad=True)
        xr = x.detach().clone().requires_grad_(True)
        sbn = hvd.SyncBatchNorm(3).double()
        bn = (torch.nn.BatchNorm1d(3) if dims == 2
              else torch.nn.BatchNorm2d(3)).double()
        bn.load_state_dict({k: v.clone() for k, v in sbn.state_dict().items()})

        y = sbn(x)
        yr = bn(xr)
        assert torch.allclose(y, yr, atol=1e-10)
        y.pow(2).sum().backward()
        yr.pow(2).sum().backward()
        assert torch.allclose(x.grad, xr.grad, atol=1e-8)
        assert torch.allclose(sbn.weight.grad, bn.weight.grad, atol=1e-8)
        assert torch.allclose(sbn.bias.grad, bn.bias.grad, atol=1e-8)
        assert torch.allclose(sbn.running_mean, bn.running_mean, atol=1e-10)
        assert torch.allclose(sbn.running_var, bn.running_var, atol=1e-10)

    def test_eval_mode(self):
        torch.manual_seed(0)
        sbn = hvd.SyncBatchNorm(3).double()
        x = torch.randn(4, 3, dtype=torch.float64)
        sbn(x)  # one training step to move running stats
        sbn.eval()
        y = sbn(x)
        bn = torch.nn.BatchNorm1d(3).double()
        bn.load_state_dict(sbn.state_dict())
        bn.eval()
        assert torch.allclose(y, bn(x), atol=1e-12)

    def test_eval_mode_backward(self):
        sbn = hvd.SyncBatchNorm(3).double()
        sbn(torch.randn(4, 3, dtype=torch.float64))
        sbn.eval()
        x = torch.randn(4, 3, dtype=torch.float64, requires_grad=True)
        sbn(x).sum().backward()
        assert x.grad is not None

    def test_affine_false_backward(self):
        sbn = hvd.SyncBatchNorm(3, affine=False).double()
        x = torch.randn(4, 3, dtype=torch.float64, requires_grad=True)
        sbn(x).pow(2).sum().backward()
        assert x.grad is not None

    def test_no_running_stats(self):
        sbn = hvd.SyncBatchNorm(3, track_running_stats=False).double()
        x = torch.randn(4, 3, dtype=torch.float64)
        y_train = sbn(x)
        sbn.eval()
        y_eval = sbn(x)  # batch stats in eval too, like nn.BatchNorm
        assert torch.allclose(y_train, y_eval, atol=1e-12)


_WORKER = textwrap.dedent("""
    import os
    os.environ['JAX_PLATFORMS'] = 'cpu'
    import jax
    jax.config.update('jax_platforms', 'cpu')
    import numpy as np
    import torch
    import horovod_tpu.torch as hvd

    hvd.init()
    assert hvd.size() == 2, hvd.size()
    r = hvd.rank()

    # allreduce: average / sum / min / max, out-of-place + in-place
    t = torch.full((4,), float(r + 1))
    assert torch.allclose(hvd.allreduce(t), torch.full((4,), 1.5))
    assert torch.allclose(hvd.allreduce(t, op=hvd.Sum), torch.full((4,), 3.0))
    assert torch.allclose(hvd.allreduce(t, op=hvd.Min), torch.full((4,), 1.0))
    assert torch.allclose(hvd.allreduce(t, op=hvd.Max), torch.full((4,), 2.0))
    t2 = torch.full((3,), float(r + 1))
    hvd.allreduce_(t2)
    assert torch.allclose(t2, torch.full((3,), 1.5))

    # grouped
    outs = hvd.grouped_allreduce(
        [torch.full((2,), float(r)), torch.full((3,), 2.0 * r)], op=hvd.Sum)
    assert torch.allclose(outs[0], torch.full((2,), 1.0))
    assert torch.allclose(outs[1], torch.full((3,), 2.0))

    # allgather with ragged first dims: 2 rows from rank0, 3 from rank1
    g = hvd.allgather(torch.full((2 + r, 2), float(r)))
    assert g.shape == (5, 2), g.shape
    assert torch.allclose(g[:2], torch.zeros(2, 2))
    assert torch.allclose(g[2:], torch.ones(3, 2))

    # broadcast from rank 1
    out = hvd.broadcast(torch.full((2,), float(r)), root_rank=1)
    assert torch.allclose(out, torch.full((2,), 1.0))

    # alltoall, equal splits
    x = torch.arange(4, dtype=torch.float32) + 10 * r
    got = hvd.alltoall(x)
    exp = torch.tensor([2.0 * r, 2.0 * r + 1, 10 + 2.0 * r, 10 + 2.0 * r + 1])
    assert torch.allclose(got, exp), (got, exp)

    # alltoall, ragged splits
    x = torch.arange(3, dtype=torch.float32) + 10 * r
    splits = torch.tensor([1, 2]) if r == 0 else torch.tensor([2, 1])
    got, rsplits = hvd.alltoall(x, splits)
    if r == 0:
        assert got.tolist() == [0.0, 10.0, 11.0], got
        assert rsplits.tolist() == [1, 2]
    else:
        assert got.tolist() == [1.0, 2.0, 12.0], got
        assert rsplits.tolist() == [2, 1]

    # reducescatter
    x = torch.arange(4, dtype=torch.float32) * (r + 1)
    out = hvd.reducescatter(x)
    exp = torch.tensor([0.0, 3.0]) if r == 0 else torch.tensor([6.0, 9.0])
    assert torch.allclose(out, exp), (out, exp)

    # DistributedOptimizer: different grads per worker -> averaged update
    torch.manual_seed(r)   # deliberately different init; broadcast fixes it
    model = torch.nn.Linear(3, 2)
    hvd.broadcast_parameters(model.state_dict(), root_rank=0)
    w0 = model.weight.detach().clone()
    opt = hvd.DistributedOptimizer(
        torch.optim.SGD(model.parameters(), lr=0.1),
        named_parameters=model.named_parameters())
    x = torch.ones(2, 3) * (r + 1)
    opt.zero_grad()
    model(x).sum().backward()
    opt.step()
    # dL/dW = 2*(r+1) per entry; cross-worker average = 3.0
    assert torch.allclose(model.weight.detach(),
                          w0 - 0.1 * 3.0 * torch.ones(2, 3), atol=1e-6)

    # SyncBatchNorm: half the batch on each worker == full-batch BN
    torch.manual_seed(42)
    full = torch.randn(6, 4, dtype=torch.float64)
    local = full[r * 3:(r + 1) * 3].clone().requires_grad_(True)
    fullref = full.clone().requires_grad_(True)
    sbn = hvd.SyncBatchNorm(4).double()
    bn = torch.nn.BatchNorm1d(4).double()
    bn.load_state_dict({k: v.clone() for k, v in sbn.state_dict().items()})
    y = sbn(local)
    yr = bn(fullref)
    assert torch.allclose(y, yr[r * 3:(r + 1) * 3], atol=1e-10)
    y.pow(2).sum().backward()
    yr.pow(2).sum().backward()
    assert torch.allclose(local.grad, fullref.grad[r * 3:(r + 1) * 3],
                          atol=1e-8)
    assert torch.allclose(sbn.running_mean, bn.running_mean, atol=1e-10)
    assert torch.allclose(sbn.running_var, bn.running_var, atol=1e-10)

    # object broadcast
    obj = hvd.broadcast_object({'rank': r}, root_rank=1)
    assert obj['rank'] == 1
    assert hvd.allgather_object(r) == [0, 1]

    hvd.barrier()
    print('torch worker', r, 'ok')
""")


@pytest.mark.slow
class TestTwoWorkerIntegration:
    def test_two_worker_torch_numerics(self, tmp_path):
        script = tmp_path / "torch_worker.py"
        script.write_text(_WORKER)
        repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = {"PYTHONPATH": repo_root + os.pathsep
               + os.environ.get("PYTHONPATH", "")}
        rc = run(2, [sys.executable, str(script)], start_timeout=240, env=env)
        assert rc == 0


class TestSparseGradients:
    def _embedding_step(self, sparse_as_dense):
        import horovod_tpu.torch as hvt

        torch.manual_seed(0)
        emb = torch.nn.Embedding(10, 4, sparse=True)
        opt = hvt.DistributedOptimizer(
            torch.optim.SGD(emb.parameters(), lr=0.5),
            named_parameters=emb.named_parameters(),
            sparse_as_dense=sparse_as_dense)
        idx = torch.tensor([1, 3, 3])
        loss = emb(idx).sum()
        loss.backward()
        assert emb.weight.grad.is_sparse or sparse_as_dense
        opt.synchronize()
        return emb

    def test_sparse_allreduce_path(self):
        """Reference sparse path: values/indices allgather, duplicate
        indices coalesce-summed; single process -> grad unchanged."""
        emb = self._embedding_step(sparse_as_dense=False)
        g = emb.weight.grad.to_dense()
        assert torch.allclose(g[3], torch.full((4,), 2.0)), g[3]
        assert torch.allclose(g[1], torch.ones(4)), g[1]
        assert torch.allclose(g[0], torch.zeros(4))

    def test_sparse_as_dense_densifies(self):
        emb = self._embedding_step(sparse_as_dense=True)
        assert not emb.weight.grad.is_sparse
        g = emb.weight.grad
        assert torch.allclose(g[3], torch.full((4,), 2.0)), g[3]

    def test_sparse_adasum_rejected(self):
        import horovod_tpu.torch as hvt

        emb = torch.nn.Embedding(6, 2, sparse=True)
        opt = hvt.DistributedOptimizer(
            torch.optim.SGD(emb.parameters(), lr=0.1), op=hvt.Adasum)
        with pytest.raises(NotImplementedError, match="sparse"):
            # the hook fires during backward on new torch; older torch
            # defers the check to synchronize()
            emb(torch.tensor([0, 1])).sum().backward()
            opt.synchronize()
