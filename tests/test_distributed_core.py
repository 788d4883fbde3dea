"""Distributed core: topology grid arithmetic + functional collectives inside
shard_map on the 8-device virtual CPU mesh (SURVEY.md §4 — single-process SPMD
replaces the reference's multi-GPU subprocess pattern)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from paddle_tpu.distributed.shard_map_compat import shard_map
from jax.sharding import PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.distributed as dist


class TestTopology:
    def test_grid_arithmetic(self):
        topo = dist.CommunicateTopology(
            ["data", "pipe", "sharding", "sep", "model"], [2, 2, 1, 1, 2]
        )
        assert topo.world_size == 8
        assert topo.get_dim("model") == 2
        # rank 0 is coordinate (0,0,0,0,0); last rank is all-max
        assert topo.get_rank(data=0, pipe=0, sharding=0, sep=0, model=0) == 0
        assert topo.get_rank(data=1, pipe=1, sharding=0, sep=0, model=1) == 7
        c = topo.get_coord(5)
        assert topo.get_rank(**c._asdict()) == 5
        # comm lists partition the world
        comms = topo.get_comm_list("model")
        flat = sorted(r for comm in comms for r in comm)
        assert flat == list(range(8))
        assert all(len(c) == 2 for c in comms)

    def test_hcg_groups(self):
        hcg = dist.create_hybrid_communicate_group(dp=2, mp=2, pp=2)
        assert hcg.get_data_parallel_world_size() == 2
        assert hcg.get_model_parallel_world_size() == 2
        assert hcg.get_pipe_parallel_world_size() == 2
        assert hcg.get_sharding_parallel_world_size() == 1
        g = hcg.get_model_parallel_group()
        assert g.axis_name == "mp" and g.nranks == 2
        assert set(hcg.mesh.axis_names) == {"dp", "pp", "sharding", "sep", "mp"}
        assert dist.get_hybrid_communicate_group() is hcg

    def test_rank_from_stage(self):
        hcg = dist.create_hybrid_communicate_group(dp=2, pp=4)
        assert hcg.get_rank_from_stage(0) == 0
        assert hcg.get_stage_id() == 0 and hcg.is_first_stage


class TestShardMapCompat:
    def test_names_are_the_jax_0_9_spelling(self):
        """The module is the one place the shard_map spelling lives: the
        resolved function is ``jax.shard_map`` itself, it accepts the
        NO_CHECK keyword, and ``axis_size`` is ``lax.axis_size``."""
        import inspect as _inspect

        from paddle_tpu.distributed.shard_map_compat import (
            NO_CHECK, axis_size, shard_map as resolved,
        )

        assert resolved is jax.shard_map
        assert axis_size is jax.lax.axis_size
        assert NO_CHECK == {"check_vma": False}
        params = _inspect.signature(resolved).parameters
        assert all(k in params for k in NO_CHECK)


class TestCollectives:
    @pytest.fixture()
    def dp8(self):
        hcg = dist.create_hybrid_communicate_group(dp=8)
        return hcg, hcg.get_data_parallel_group()

    def test_all_reduce_sum_max(self, dp8):
        hcg, g = dp8

        def body(x):
            with dist.axis_scope("dp"):
                t = paddle.Tensor(x)
                dist.all_reduce(t, group=g)
                m = paddle.Tensor(x)
                dist.all_reduce(m, op=dist.ReduceOp.MAX, group=g)
            return t._data, m._data

        f = shard_map(body, mesh=hcg.mesh, in_specs=P("dp"),
                      out_specs=(P("dp"), P("dp")))
        x = np.arange(16.0, dtype=np.float32).reshape(8, 2)
        s, m = f(x)
        np.testing.assert_allclose(np.asarray(s), np.tile(x.sum(0), (8, 1)))
        np.testing.assert_allclose(np.asarray(m), np.tile(x.max(0), (8, 1)))

    def test_all_gather(self, dp8):
        hcg, g = dp8

        def body(x):
            with dist.axis_scope("dp"):
                out = dist.all_gather(None, paddle.Tensor(x), group=g)
            return out._data.reshape(-1, x.shape[-1])

        f = shard_map(body, mesh=hcg.mesh, in_specs=P("dp"), out_specs=P(None),
                      check_vma=False)
        x = np.arange(16.0, dtype=np.float32).reshape(8, 2)
        out = np.asarray(f(x))
        np.testing.assert_allclose(out, x)

    def test_reduce_scatter(self, dp8):
        hcg, g = dp8

        def body(x):
            with dist.axis_scope("dp"):
                out = paddle.Tensor(jnp.zeros((1,), jnp.float32))
                dist.reduce_scatter(out, paddle.Tensor(x), group=g)
            return out._data

        f = shard_map(body, mesh=hcg.mesh, in_specs=P(None), out_specs=P("dp"))
        x = np.arange(8.0, dtype=np.float32)
        out = np.asarray(f(x))
        np.testing.assert_allclose(out, 8.0 * x)

    def test_broadcast(self, dp8):
        hcg, g = dp8

        def body(x):
            with dist.axis_scope("dp"):
                t = paddle.Tensor(x)
                dist.broadcast(t, src=3, group=g)
            return t._data

        f = shard_map(body, mesh=hcg.mesh, in_specs=P("dp"), out_specs=P("dp"))
        x = np.arange(8.0, dtype=np.float32).reshape(8, 1)
        out = np.asarray(f(x))
        np.testing.assert_allclose(out, np.full((8, 1), 3.0))

    def test_alltoall_single(self, dp8):
        hcg, g = dp8

        def body(x):
            with dist.axis_scope("dp"):
                out = paddle.Tensor(jnp.zeros_like(x))
                dist.alltoall_single(out, paddle.Tensor(x), group=g)
            return out._data

        f = shard_map(body, mesh=hcg.mesh, in_specs=P("dp"), out_specs=P("dp"))
        x = np.arange(64.0, dtype=np.float32).reshape(64, 1)
        out = np.asarray(f(x)).reshape(8, 8)
        np.testing.assert_allclose(out, x.reshape(8, 8).T)

    def test_shift_ring(self, dp8):
        hcg, g = dp8

        def body(x):
            with dist.axis_scope("dp"):
                out = dist.shift(paddle.Tensor(x), offset=1, group=g)
            return out._data

        f = shard_map(body, mesh=hcg.mesh, in_specs=P("dp"), out_specs=P("dp"))
        x = np.arange(8.0, dtype=np.float32).reshape(8, 1)
        out = np.asarray(f(x)).ravel()
        np.testing.assert_allclose(out, np.roll(np.arange(8.0), 1))

    def test_send_recv_pipeline_pair(self, dp8):
        hcg, g = dp8

        def body(x):
            with dist.axis_scope("dp"):
                t = paddle.Tensor(x)
                dist.send(t, dst=(g.rank + 1) % g.nranks, group=g)
                out = paddle.Tensor(jnp.zeros_like(x))
                dist.recv(out, src=(g.rank - 1) % g.nranks, group=g)
            return out._data

        f = shard_map(body, mesh=hcg.mesh, in_specs=P("dp"), out_specs=P("dp"))
        x = np.arange(8.0, dtype=np.float32).reshape(8, 1)
        out = np.asarray(f(x)).ravel()
        np.testing.assert_allclose(out, np.roll(np.arange(8.0), 1))

    def test_collective_gradients(self, dp8):
        """psum has a correct vjp through the tape (grad of allreduce-sum is
        allreduce-sum of the upstream grad)."""
        hcg, g = dp8

        def body(x):
            with dist.axis_scope("dp"):
                t = paddle.Tensor(x, stop_gradient=False)
                y = t * t
                dist.all_reduce(y, group=g)
                loss = y.sum()
                loss.backward()
            return t.grad._data

        f = shard_map(body, mesh=hcg.mesh, in_specs=P("dp"), out_specs=P("dp"))
        x = np.arange(8.0, dtype=np.float32).reshape(8, 1)
        grad = np.asarray(f(x)).ravel()
        np.testing.assert_allclose(grad, 2.0 * np.arange(8.0))

    def test_eager_world1_identity(self):
        g = dist.new_group([0])
        t = paddle.to_tensor([1.0, 2.0])
        assert dist.all_reduce(t, group=g) is None
        np.testing.assert_allclose(t.numpy(), [1.0, 2.0])
        out = []
        dist.all_gather(out, t, group=g)
        assert len(out) == 1
        dist.barrier()

    def test_eager_multirank_raises(self):
        g = dist.Group(axis_name="mp", nranks=4)
        t = paddle.to_tensor([1.0])
        with pytest.raises(RuntimeError, match="shard_map"):
            dist.all_reduce(t, group=g)


class TestParallelEnv:
    def test_init_parallel_env_single(self):
        dist.set_hybrid_communicate_group(None)
        g = dist.init_parallel_env()
        assert g.nranks == jax.device_count()
        assert dist.get_world_size() == jax.device_count()
        assert dist.get_rank() == 0

    def test_data_parallel_wrapper(self):
        import paddle_tpu.nn as nn

        dist.set_hybrid_communicate_group(None)
        dist.create_hybrid_communicate_group(dp=8)
        m = nn.Linear(4, 2)
        dp = dist.DataParallel(m)
        x = paddle.randn([8, 4])
        out = dp(x)
        ref = m(x)
        np.testing.assert_allclose(out.numpy(), ref.numpy(), rtol=1e-6)
        with dp.no_sync():
            pass
        assert len(dp.state_dict()) == len(m.state_dict())
