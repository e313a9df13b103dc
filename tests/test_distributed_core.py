"""Distributed core: ProcessMesh, placements, shard_tensor/reshard,
collectives, DataParallel — on the virtual 8-device CPU mesh.

Mirrors the reference test strategy (`test/auto_parallel/test_shard_tensor_api`,
`test/collective/*`) but single-process over simulated devices — something the
reference cannot do (SURVEY.md §4 implication).
"""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.distributed as dist


@pytest.fixture(autouse=True)
def _env():
    dist.init_parallel_env()
    yield


def test_process_mesh_basics():
    mesh = dist.ProcessMesh(np.arange(8).reshape(2, 4), ["dp", "mp"])
    assert mesh.shape == [2, 4]
    assert mesh.ndim == 2
    assert mesh.get_dim_size("mp") == 4
    assert mesh.process_ids == list(range(8))
    sub = mesh.get_mesh_with_dim("mp")
    assert sub.dim_names == ["mp", "dp"]
    jm = mesh.to_jax_mesh()
    assert jm.devices.shape == (2, 4)
    assert mesh == dist.ProcessMesh(np.arange(8).reshape(2, 4), ["dp", "mp"])


def test_mesh_naming_more_ranks_than_devices_is_an_error():
    """A mesh never wraps its extra ranks round onto the first devices."""
    import jax

    mesh = dist.ProcessMesh(np.arange(2 * jax.device_count()), ["x"])
    with pytest.raises(ValueError, match=r"JAX has \d+ device"):
        mesh.to_jax_mesh()


def test_place_naming_a_missing_device_is_an_error():
    """A Place resolves to the device it names or raises — never to a CPU
    device in its stead."""
    from paddle_tpu.framework.place import Place

    assert Place("cpu", 1).jax_device.id == 1
    with pytest.raises(ValueError, match="device"):
        Place("cpu", 99).jax_device
    with pytest.raises(RuntimeError):      # no TPU backend on the test mesh
        paddle.TPUPlace(0).jax_device


def test_placements():
    assert dist.Shard(0) == dist.Shard(0)
    assert dist.Shard(0) != dist.Shard(1)
    assert dist.Replicate().is_replicated()
    assert dist.Partial().is_partial()
    assert dist.Shard(1).is_shard(1) and not dist.Shard(1).is_shard(0)


def test_shard_tensor_shard_and_replicate():
    mesh = dist.ProcessMesh(np.arange(8), ["x"])
    x = paddle.Tensor(np.arange(64, dtype=np.float32).reshape(8, 8))
    xs = dist.shard_tensor(x, mesh, [dist.Shard(0)])
    assert xs.shape == [8, 8]
    assert dist.auto_parallel.placements_of(xs) == [dist.Shard(0)]
    # each device holds one row
    shards = xs._data.addressable_shards
    assert len(shards) == 8
    assert shards[0].data.shape == (1, 8)
    xr = dist.shard_tensor(x, mesh, [dist.Replicate()])
    assert xr._data.addressable_shards[0].data.shape == (8, 8)
    np.testing.assert_array_equal(np.asarray(xs._data), np.asarray(x._data))


def test_reshard_s_to_r_and_s_to_s():
    mesh = dist.ProcessMesh(np.arange(8), ["x"])
    x = paddle.Tensor(np.random.rand(8, 16).astype(np.float32))
    xs = dist.shard_tensor(x, mesh, [dist.Shard(0)])
    xr = dist.reshard(xs, mesh, [dist.Replicate()])
    np.testing.assert_array_equal(np.asarray(xr._data), np.asarray(x._data))
    assert xr._data.addressable_shards[0].data.shape == (8, 16)
    x1 = dist.reshard(xs, mesh, [dist.Shard(1)])  # all-to-all
    assert x1._data.addressable_shards[0].data.shape == (8, 2)
    np.testing.assert_array_equal(np.asarray(x1._data), np.asarray(x._data))


def test_partial_to_replicate():
    mesh = dist.ProcessMesh(np.arange(8), ["x"])
    x = paddle.Tensor(np.full((8, 4), 3.0, np.float32))
    xp = dist.shard_tensor(x, mesh, [dist.Partial()])
    assert dist.auto_parallel.placements_of(xp)[0].is_partial()
    xr = dist.reshard(xp, mesh, [dist.Replicate()])
    # slot-0 value + 7 neutral zeros -> the original value
    np.testing.assert_allclose(np.asarray(xr._data), np.full((8, 4), 3.0))
    xs = dist.reshard(xp, mesh, [dist.Shard(0)])  # p->s: reduce-scatter
    np.testing.assert_allclose(np.asarray(xs._data), np.full((8, 4), 3.0))
    assert xs._data.addressable_shards[0].data.shape == (1, 4)


def test_2d_mesh_tp_dp_matmul_propagates():
    """GSPMD does the SPMD-rule work: dp-sharded batch x mp-sharded weight."""
    mesh = dist.ProcessMesh(np.arange(8).reshape(2, 4), ["dp", "mp"])
    x = paddle.Tensor(np.random.rand(4, 16).astype(np.float32))
    w = paddle.Tensor(np.random.rand(16, 8).astype(np.float32))
    xd = dist.shard_tensor(x, mesh, [dist.Shard(0), dist.Replicate()])
    wd = dist.shard_tensor(w, mesh, [dist.Replicate(), dist.Shard(1)])
    out = paddle.matmul(xd, wd)
    np.testing.assert_allclose(np.asarray(out._data),
                               np.asarray(x._data) @ np.asarray(w._data),
                               rtol=1e-5, atol=1e-5)


def test_dtensor_from_to_local():
    mesh = dist.ProcessMesh(np.arange(8), ["x"])
    local = paddle.Tensor(np.ones((2, 4), np.float32))
    gt = dist.dtensor_from_local(local, mesh, [dist.Shard(0)])
    assert gt.shape == [16, 4]
    back = dist.dtensor_to_local(gt)
    assert back.shape == [2, 4]
    rep = dist.unshard_dtensor(gt)
    assert rep.shape == [16, 4]


def test_shard_layer_and_optimizer_stage1():
    from paddle_tpu import nn

    mesh = dist.ProcessMesh(np.arange(8), ["dp"])
    model = nn.Linear(16, 16)
    dist.shard_layer(model, mesh)  # replicate params
    assert dist.auto_parallel.is_dist_tensor(model.weight)
    opt = paddle.optimizer.AdamW(parameters=model.parameters())
    opt = dist.shard_optimizer(opt, dist.ShardingStage1(), mesh=mesh)
    x = paddle.Tensor(np.random.rand(8, 16).astype(np.float32))
    loss = (model(x) ** 2).mean()
    loss.backward()
    opt.step()
    # moment accumulators are sharded over dp
    accs = opt._inner._accumulators["moment1"]
    arr = next(iter(accs.values()))
    assert arr.addressable_shards[0].data.shape[0] == 2  # 16/8
    opt.clear_grad()


def test_shard_optimizer_stage3_shards_params():
    from paddle_tpu import nn

    mesh = dist.ProcessMesh(np.arange(8), ["dp"])
    model = nn.Linear(16, 16)
    opt = paddle.optimizer.SGD(parameters=model.parameters())
    opt = dist.shard_optimizer(opt, dist.ShardingStage3(), mesh=mesh)
    meta = dist.auto_parallel.placements_of(model.weight)
    assert meta is not None and meta[0] == dist.Shard(0)
    x = paddle.Tensor(np.random.rand(4, 16).astype(np.float32))
    loss = model(x).sum()
    loss.backward()
    opt.step()
    assert np.isfinite(np.asarray(model.weight._data)).all()


# ---------------------------------------------------------------------------
# collectives
# ---------------------------------------------------------------------------

def _ranked(shape=(8, 4)):
    """Stacked per-rank tensor: rank r holds value r."""
    mesh = dist.ProcessMesh(np.arange(8), ["x"])
    vals = np.stack([np.full(shape[1:], r, np.float32) for r in range(8)])
    return dist.shard_tensor(paddle.Tensor(vals), mesh, [dist.Shard(0)])


def test_all_reduce_stacked():
    t = _ranked()
    dist.all_reduce(t)
    np.testing.assert_allclose(np.asarray(t._data),
                               np.full((8, 4), 28.0))  # sum 0..7


def test_all_reduce_plain_replicated():
    t = paddle.Tensor(np.ones((3, 3), np.float32))
    dist.all_reduce(t)
    np.testing.assert_allclose(np.asarray(t._data), np.full((3, 3), 8.0))


def test_all_reduce_max():
    t = _ranked()
    dist.all_reduce(t, op=dist.ReduceOp.MAX)
    np.testing.assert_allclose(np.asarray(t._data), np.full((8, 4), 7.0))


def test_all_gather():
    t = _ranked()
    out = []
    dist.all_gather(out, t)
    assert len(out) == 8
    np.testing.assert_allclose(np.asarray(out[3]._data), np.full((4,), 3.0))


def test_broadcast():
    t = _ranked()
    dist.broadcast(t, src=5)
    np.testing.assert_allclose(np.asarray(t._data), np.full((8, 4), 5.0))


def test_reduce_to_dst():
    t = _ranked()
    dist.reduce(t, dst=2)
    arr = np.asarray(t._data)
    np.testing.assert_allclose(arr[2], np.full((4,), 28.0))
    np.testing.assert_allclose(arr[1], np.full((4,), 1.0))


def test_scatter_and_alltoall():
    mesh = dist.ProcessMesh(np.arange(8), ["x"])
    parts = [paddle.Tensor(np.full((2,), float(i), np.float32))
             for i in range(8)]
    target = paddle.Tensor(np.zeros((16,), np.float32))
    dist.scatter(target, parts, src=0)
    assert target._data.shape == (8, 2)
    out = []
    dist.alltoall(out, parts)
    assert len(out) == 8
    np.testing.assert_allclose(np.asarray(out[4]._data), np.full((2,), 4.0))


def test_reduce_scatter():
    # each rank contributes [r, r, ..., r] of length 16; chunk per rank = 2
    t = _ranked(shape=(8, 16))
    dist.reduce_scatter(t)
    arr = np.asarray(t._data)
    assert arr.shape == (8, 2)
    np.testing.assert_allclose(arr, np.full((8, 2), 28.0))


def test_p2p_shift_and_mailbox():
    t = _ranked()
    shifted = dist.communication.collective.p2p_shift(t, 1)
    arr = np.asarray(shifted._data)
    np.testing.assert_allclose(arr[1], np.full((4,), 0.0))
    np.testing.assert_allclose(arr[0], np.full((4,), 7.0))
    # mailbox p2p
    src = paddle.Tensor(np.arange(4, dtype=np.float32))
    dst = paddle.Tensor(np.zeros(4, np.float32))
    dist.send(src, dst=0)
    dist.recv(dst, src=0)
    np.testing.assert_array_equal(np.asarray(dst._data),
                                  np.asarray(src._data))
    # recv posted BEFORE send via batch_isend_irecv: the deferred handle
    # pops the mailbox at wait() time instead of raising
    buf = paddle.Tensor(np.zeros(4, np.float32))
    tasks = dist.batch_isend_irecv([
        dist.P2POp(dist.irecv, buf, 0),
        dist.P2POp(dist.isend, src, 0),
    ])
    assert tasks[0].is_completed()  # send has been posted by now
    for tk in tasks:
        tk.wait()
    np.testing.assert_array_equal(np.asarray(buf._data),
                                  np.asarray(src._data))


def test_groups_and_env():
    g = dist.new_group([0, 1, 2, 3])
    assert g.nranks == 4
    assert dist.get_world_size() == 8
    assert dist.get_rank() == 0
    assert dist.get_world_size(g) == 4
    env = dist.ParallelEnv()
    assert env.world_size == 8
    dist.barrier()
    # sub-group collective
    vals = np.stack([np.full((2,), r, np.float32) for r in range(4)])
    t = paddle.Tensor(vals)
    dist.communication.collective._mark_stacked(t)
    dist.all_reduce(t, group=g)
    np.testing.assert_allclose(np.asarray(t._data), np.full((4, 2), 6.0))


def test_all_gather_object():
    objs = []
    dist.all_gather_object(objs, {"a": 1})
    assert len(objs) == 8 and objs[0] == {"a": 1}


def test_data_parallel_wrapper():
    from paddle_tpu import nn

    mesh = dist.ProcessMesh(np.arange(8), ["dp"])
    model = nn.Linear(8, 4)
    dp = dist.DataParallel(model, mesh=mesh)
    x = paddle.Tensor(np.random.rand(16, 8).astype(np.float32))
    out = dp(x)
    assert out.shape == [16, 4]
    loss = out.sum()
    loss.backward()
    assert model.weight.grad is not None
    assert np.isfinite(np.asarray(model.weight.grad._data)).all()
