"""Distributed tests on the 8-device virtual CPU mesh.

Parity role: the reference's localhost-subprocess distributed tests
(test_dist_base.py, test_collective_base.py, hybrid_parallel_mp_*.py —
SURVEY.md §4): N-way parallel results are compared against single-device
runs, here via shardings on one host instead of subprocesses.
"""

import numpy as np
import pytest

import jax

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
import paddle_tpu.optimizer as opt
from paddle_tpu.distributed import fleet
from paddle_tpu.distributed import mesh as mesh_mod
from paddle_tpu.distributed.fleet import meta_parallel as mpp


@pytest.fixture(autouse=True)
def _reset_mesh():
    yield
    mesh_mod._MESH = None


def _mean_loss_net(net, x, y):
    return F.mse_loss(net(x), y)


def test_mesh_has_8_devices():
    assert len(jax.devices()) == 8


def test_fleet_init_data_parallel_training():
    fleet.init(is_collective=True)
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 1))
    dp_model = fleet.distributed_model(net)
    o = fleet.distributed_optimizer(opt.Adam(0.02, parameters=net.parameters()))
    rng = np.random.RandomState(0)
    w = rng.randn(8, 1).astype("float32")
    losses = []
    for _ in range(60):
        xb = rng.randn(32, 8).astype("float32")
        x = paddle.to_tensor(xb)
        y = paddle.to_tensor((xb @ w).astype("float32"))
        # inputs auto-shard over dp inside the wrapper
        loss = F.mse_loss(dp_model(x), paddle.Tensor(mesh_mod.shard_batch(y._array)))
        loss.backward()
        o.step()
        o.clear_grad()
        losses.append(float(loss.numpy()))
    assert losses[-1] < losses[0] * 0.5


def test_dp_matches_single_device():
    """N-way DP must match the single-device run exactly (same global batch)."""
    rng = np.random.RandomState(3)
    xb = rng.randn(16, 4).astype("float32")
    yb = rng.randn(16, 1).astype("float32")

    def run(parallel):
        paddle.seed(42)
        net = nn.Linear(4, 1)
        if parallel:
            fleet.init(is_collective=True)
            model = fleet.distributed_model(net)
        else:
            model = net
        o = opt.SGD(0.1, parameters=net.parameters())
        for _ in range(5):
            x, y = paddle.to_tensor(xb), paddle.to_tensor(yb)
            loss = F.mse_loss(model(x), y if not parallel else paddle.Tensor(
                mesh_mod.shard_batch(y._array)))
            loss.backward()
            o.step()
            o.clear_grad()
        return net.weight.numpy()

    w_single = run(False)
    mesh_mod._MESH = None
    w_dp = run(True)
    np.testing.assert_allclose(w_single, w_dp, rtol=1e-5, atol=1e-6)


def test_tensor_parallel_layers_match_serial():
    fleet.init(is_collective=True, strategy=_strategy(mp=4, dp=2))
    paddle.seed(1)
    rng = np.random.RandomState(1)
    x = paddle.to_tensor(rng.randn(4, 8).astype("float32"))

    col = mpp.ColumnParallelLinear(8, 16, gather_output=False, has_bias=True)
    row = mpp.RowParallelLinear(16, 8, input_is_parallel=True, has_bias=True)
    out = row(col(x))
    assert out.shape == [4, 8]

    # serial reference with the same weights
    wc, bc = col.weight.numpy(), col.bias.numpy()
    wr, br = row.weight.numpy(), row.bias.numpy()
    ref = (x.numpy() @ wc + bc) @ wr + br
    np.testing.assert_allclose(out.numpy(), ref, rtol=1e-4, atol=1e-5)

    # gradients flow
    out.sum().backward()
    assert col.weight.grad is not None and row.weight.grad is not None


def test_vocab_parallel_embedding_and_parallel_ce():
    fleet.init(is_collective=True, strategy=_strategy(mp=4, dp=2))
    paddle.seed(2)
    emb = mpp.VocabParallelEmbedding(32, 16)
    ids = paddle.to_tensor(np.array([[1, 5, 31], [0, 7, 2]], dtype="int64"))
    out = emb(ids)
    assert out.shape == [2, 3, 16]
    np.testing.assert_allclose(out.numpy(), emb.weight.numpy()[ids.numpy()], rtol=1e-6)

    ce = mpp.ParallelCrossEntropy()
    logits = paddle.to_tensor(np.random.RandomState(0).randn(4, 32).astype("float32"))
    logits.stop_gradient = False
    labels = paddle.to_tensor(np.array([1, 30, 7, 0], dtype="int64"))
    loss = ce(logits, labels)
    # reference softmax-CE
    lg = logits.numpy()
    ref = -(lg[np.arange(4), labels.numpy()] - np.log(np.exp(lg - lg.max(-1, keepdims=True)).sum(-1)) - lg.max(-1))
    np.testing.assert_allclose(loss.numpy().reshape(-1), ref, rtol=1e-4, atol=1e-5)
    loss.sum().backward()
    assert logits.grad is not None


def test_parallel_cross_entropy_ignores_the_order_of_leading_dims():
    """ParallelCrossEntropy is indifferent to its leading dims: [S, B, V]
    logits + [S, B, 1] labels give the transposed [B, S] losses."""
    from paddle_tpu.distributed.fleet.meta_parallel import ParallelCrossEntropy

    rng = np.random.RandomState(0)
    s, b, v = 8, 4, 32
    logits = rng.randn(b, s, v).astype("float32")
    labels = rng.randint(0, v, (b, s, 1)).astype("int64")
    ce = ParallelCrossEntropy()
    ref = ce(paddle.to_tensor(logits), paddle.to_tensor(labels)).numpy()
    out = ce(paddle.to_tensor(np.transpose(logits, (1, 0, 2))),
             paddle.to_tensor(np.transpose(labels, (1, 0, 2)))).numpy()
    np.testing.assert_allclose(np.transpose(out, (1, 0, 2)), ref,
                               rtol=1e-6, atol=1e-6)


def _strategy(dp=1, mp=1, pp=1, sharding=1):
    s = fleet.DistributedStrategy()
    s.hybrid_configs = {
        "dp_degree": dp, "mp_degree": mp, "pp_degree": pp, "sharding_degree": sharding,
    }
    return s


def test_hybrid_topology_groups():
    from paddle_tpu.distributed.fleet.topology import CommunicateTopology

    topo = CommunicateTopology(dims=(2, 2, 1, 2))
    assert topo.world_size() == 8
    assert topo.get_dim("model") == 2
    mp_groups = topo.get_comm_list("model")
    assert len(mp_groups) == 4
    for g in mp_groups:
        assert len(g) == 2
    # ranks differ only in the model axis
    c0 = topo.get_coord(mp_groups[0][0])
    c1 = topo.get_coord(mp_groups[0][1])
    assert c0.data == c1.data and c0.pipe == c1.pipe and c0.model != c1.model


def test_hcg_parallel_mode_detection():
    fleet.init(is_collective=True, strategy=_strategy(dp=2, mp=4))
    hcg = fleet.get_hybrid_communicate_group()
    assert hcg.get_parallel_mode() == "tensor_parallel"
    assert hcg.get_model_parallel_world_size() == 4
    assert hcg.get_data_parallel_world_size() == 2
    assert mesh_mod.get_mesh().shape["mp"] == 4


def test_sharding_optimizer_states_sharded():
    fleet.init(is_collective=True, strategy=_strategy(sharding=8))
    paddle.seed(0)
    net = nn.Linear(64, 8)
    inner = opt.Adam(0.01, parameters=net.parameters())
    o = mpp.DygraphShardingOptimizer(inner, fleet.get_hybrid_communicate_group())
    loss = net(paddle.randn([4, 64])).mean()
    loss.backward()
    o.step()
    m1 = inner._accumulators["moment1"][net.weight.name]
    shard = m1._array.sharding
    # moment sharded over the 'sharding' axis (64 rows / 8 devices)
    assert not shard.is_fully_replicated
    # training still correct
    before = float(loss.numpy())
    for _ in range(10):
        loss = net(paddle.ones([4, 64])).mean()
        loss.backward()
        o.step()
        o.clear_grad()


def test_spmd_pipeline_matches_serial():
    """The shard_map 1F1B engine must equal running stages sequentially."""
    fleet.init(is_collective=True, strategy=_strategy(pp=8))
    import jax.numpy as jnp
    from paddle_tpu.distributed.fleet.meta_parallel.pipeline_engine import spmd_pipeline

    S, M, mb, d = 8, 4, 2, 16
    rng = np.random.RandomState(0)
    Ws = jnp.asarray(rng.randn(S, d, d).astype("float32") * 0.1)
    xs = jnp.asarray(rng.randn(M, mb, d).astype("float32"))

    def stage_fn(w, x):
        return jnp.tanh(x @ w)

    apply = spmd_pipeline(stage_fn, S)
    mesh = mesh_mod.get_mesh()
    from jax.sharding import NamedSharding, PartitionSpec as P

    Ws_sharded = jax.device_put(Ws, NamedSharding(mesh, P("pp")))
    out = apply(Ws_sharded, xs)

    ref = xs
    for s in range(S):
        ref = jnp.tanh(ref @ Ws[s])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-5)

    # and gradients flow through the pipeline
    def loss(Wst):
        return apply(Wst, xs).sum()

    g = jax.grad(loss)(Ws_sharded)
    assert np.isfinite(np.asarray(g)).all()


def test_pipeline_layer_partition_and_engine():
    fleet.init(is_collective=True, strategy=_strategy(pp=8))
    paddle.seed(0)

    class Block(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc = nn.Linear(8, 8)

        def forward(self, x):
            return F.tanh(self.fc(x))

    from paddle_tpu.distributed.fleet.meta_parallel import LayerDesc, PipelineLayer

    pl = PipelineLayer(
        layers=[LayerDesc(Block) for _ in range(8)],
        num_stages=8,
        loss_fn=nn.MSELoss(),
    )
    assert pl.get_num_stages() == 8
    assert pl.segment_parts == list(range(9))
    # whole-stack forward works (eval path)
    x = paddle.randn([4, 8])
    y = pl(x)
    assert y.shape == [4, 8]

    model = mpp.PipelineParallel(pl, fleet.get_hybrid_communicate_group(),
                                 _strategy(pp=8), loss_fn=nn.MSELoss())
    model.accumulate_steps = 4
    rng = np.random.RandomState(0)
    data = (paddle.to_tensor(rng.randn(8, 8).astype("float32")),
            paddle.to_tensor(rng.randn(8, 8).astype("float32")))
    l0 = float(model.train_batch(data, optimizer=opt.SGD(0.05)).numpy())
    for _ in range(15):
        loss = model.train_batch(data, optimizer=opt.SGD(0.05))
    assert float(loss.numpy()) < l0
