"""Parallel core: mesh building, collectives (eager + traced), sharding rules,
fleet strategy composition.  Runs on the 8-device virtual CPU mesh (conftest)
— the rebuild's analogue of the reference's multi-process-on-localhost
distributed tests (test_collective_base.py, SURVEY.md §4.3)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec
from paddle_tpu.parallel.collective import shard_map

import paddle_tpu
import paddle_tpu.distributed as dist
from paddle_tpu.parallel import (
    MeshConfig, ShardingRules, collective, infer_sharding, mesh as mesh_mod,
    shard_layer, shard_params,
)


@pytest.fixture(autouse=True)
def _reset_mesh():
    yield
    mesh_mod.set_mesh(None)


def test_build_mesh_shapes():
    m = mesh_mod.build_mesh(MeshConfig(dp=2, tp=4))
    assert m.axis_names == ("dp", "tp") and m.shape == {"dp": 2, "tp": 4}
    m = mesh_mod.build_mesh(MeshConfig())  # all-dp default
    assert m.shape["dp"] == 8
    m = mesh_mod.build_mesh(MeshConfig(dp=-1, pp=2, tp=2))
    assert m.shape == {"dp": 2, "pp": 2, "tp": 2}
    with pytest.raises(ValueError):
        mesh_mod.build_mesh(MeshConfig(dp=3, tp=4))


def test_init_parallel_env_sets_global():
    m = dist.init_parallel_env(tp=2)
    assert mesh_mod.current_mesh() is m
    assert mesh_mod.mesh_axis_size("tp") == 2
    assert mesh_mod.mesh_axis_size("dp") == 4


def test_all_reduce_eager_sharded():
    from jax.sharding import NamedSharding
    m = dist.init_parallel_env()
    # Per-rank semantics follow the input's actual placement: sharded input
    # -> each rank contributes its shard.
    x = jax.device_put(jnp.arange(8.0), NamedSharding(m, PartitionSpec("dp")))
    out = dist.all_reduce(x)
    np.testing.assert_allclose(np.asarray(out), np.full((1,), 28.0))
    # Replicated input -> every rank holds x, sum = world_size * x.
    y = dist.all_reduce(jnp.ones(3))
    np.testing.assert_allclose(np.asarray(y), np.full((3,), 8.0))


def test_all_reduce_traced_psum():
    m = dist.init_parallel_env(tp=2)

    def f(x):
        return collective.all_reduce(x, group="tp")

    g = shard_map(f, mesh=m, in_specs=(PartitionSpec("tp"),),
                  out_specs=PartitionSpec("tp"), check_vma=False)
    x = jnp.arange(4.0)
    out = g(x)  # two tp shards [0,1],[2,3] -> each psums to [2,4]
    np.testing.assert_allclose(np.asarray(out), [2., 4., 2., 4.])


def test_all_reduce_ops():
    m = dist.init_parallel_env(tp=2)

    def run(op):
        def f(x):
            return collective.all_reduce(x, op=op, group="tp")
        return shard_map(f, mesh=m, in_specs=(PartitionSpec("tp"),),
                         out_specs=PartitionSpec("tp"), check_vma=False)(
            jnp.array([1.0, 2.0, 3.0, 4.0]))

    np.testing.assert_allclose(np.asarray(run("max")), [3, 4, 3, 4])
    np.testing.assert_allclose(np.asarray(run("min")), [1, 2, 1, 2])
    np.testing.assert_allclose(np.asarray(run("avg")), [2, 3, 2, 3])
    np.testing.assert_allclose(np.asarray(run("prod")), [3, 8, 3, 8], rtol=1e-6)


def test_all_gather_traced_and_eager():
    m = dist.init_parallel_env(tp=4)

    def f(x):
        return collective.all_gather(x, group="tp")

    out = shard_map(f, mesh=m, in_specs=(PartitionSpec("tp"),),
                    out_specs=PartitionSpec(("dp", "tp")), check_vma=False)(
        jnp.arange(4.0))
    # every tp rank gathers the full [0..3]; dp=2 ranks each contribute a copy
    assert out.shape == (32,) or out.shape == (16,)

    from jax.sharding import NamedSharding
    x2 = jax.device_put(jnp.arange(8.0),
                        NamedSharding(m, PartitionSpec(("dp", "tp"))))
    out2 = dist.all_gather(x2)  # sharded input: gather-to-full
    np.testing.assert_allclose(np.asarray(out2), np.arange(8.0))


def test_reduce_scatter_traced():
    m = dist.init_parallel_env(tp=2)

    def f(x):
        return collective.reduce_scatter(x, group="tp")

    out = shard_map(f, mesh=m, in_specs=(PartitionSpec(None),),
                    out_specs=PartitionSpec("tp"), check_vma=False)(
        jnp.arange(4.0))
    # each rank holds replicated [0,1,2,3]; psum_scatter -> rank0 [0,2] rank1 [4,6]
    np.testing.assert_allclose(np.asarray(out), [0., 2., 4., 6.])


def test_broadcast_traced():
    m = dist.init_parallel_env(tp=2)

    def f(x):
        return collective.broadcast(x, src=1, group="tp")

    out = shard_map(f, mesh=m, in_specs=(PartitionSpec("tp"),),
                    out_specs=PartitionSpec("tp"), check_vma=False)(
        jnp.array([10.0, 20.0]))
    np.testing.assert_allclose(np.asarray(out), [20., 20.])


def test_all_to_all_traced():
    m = dist.init_parallel_env(tp=2)

    def f(x):
        return collective.all_to_all(x, group="tp", split_axis=0, concat_axis=1)

    x = jnp.arange(8.0).reshape(4, 2)  # per rank: (2,2) after tp split on dim0
    out = shard_map(f, mesh=m, in_specs=(PartitionSpec("tp", None),),
                    out_specs=PartitionSpec("tp", None), check_vma=False)(x)
    assert out.shape == (2, 4)


def test_scatter_and_barrier():
    dist.init_parallel_env()
    chunks = [jnp.full((2,), float(i)) for i in range(8)]
    out = dist.scatter(None, tensor_list=chunks, src=0)
    assert np.asarray(out).shape == (8, 2)
    dist.barrier()  # smoke


def test_group_registry():
    dist.init_parallel_env(tp=2)
    g = dist.new_group("tp")
    assert g.nranks == 2
    assert dist.get_group(g.id) is g
    g0 = dist.get_group(0)
    assert g0.size() == 8


def test_sharding_rules_and_infer():
    m = dist.init_parallel_env(tp=2)
    rules = ShardingRules([(r"w1$", (None, "tp")), (r"emb", ("tp", None))])
    params = {"w1": np.zeros((4, 8)), "emb": np.zeros((16, 4)),
              "b": np.zeros((5,)), "odd_w1": np.zeros((3, 3))}
    sh = infer_sharding(params, m, rules)
    assert sh["w1"].spec == PartitionSpec(None, "tp")
    assert sh["emb"].spec == PartitionSpec("tp")
    assert sh["b"].spec == PartitionSpec()
    assert sh["odd_w1"].spec == PartitionSpec()  # 3 not divisible by tp=2

    placed = shard_params(params, m, rules)
    assert placed["w1"].sharding.spec == PartitionSpec(None, "tp")


def test_zero_stage3_sharding():
    m = dist.init_parallel_env(dp=8)
    params = {"w": np.zeros((16, 8)), "tiny": np.zeros((3,))}
    sh = infer_sharding(params, m, zero_stage=3)
    assert sh["w"].spec == PartitionSpec("dp")
    assert sh["tiny"].spec == PartitionSpec()


def test_shard_layer_annotations():
    import paddle_tpu.nn as nn
    m = dist.init_parallel_env(tp=2)
    lin = nn.Linear(8, 4)
    lin.weight.sharding_axes = (None, "tp")
    shard_layer(lin, m)
    assert lin.weight.value.sharding.spec == PartitionSpec(None, "tp")


def test_fleet_init_and_strategy():
    strategy = dist.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 2, "pp_degree": 2}
    dist.fleet.init(is_collective=True, strategy=strategy)
    assert dist.fleet.mesh.shape == {"dp": 2, "pp": 2, "tp": 2}
    assert dist.fleet.worker_num() >= 1
    assert dist.fleet.is_first_worker() or dist.fleet.worker_index() > 0


def test_fleet_gradient_merge():
    import paddle_tpu.optimizer as opt
    strategy = dist.DistributedStrategy()
    strategy.gradient_merge = True
    strategy.gradient_merge_configs.k_steps = 2
    dist.fleet.init(strategy=strategy)
    sgd = opt.SGD(learning_rate=1.0)
    dopt = dist.fleet.distributed_optimizer(sgd, strategy)

    params = {"w": jnp.ones((2,))}
    state = dopt.init(params)
    g = {"w": jnp.ones((2,))}
    p1, state = dopt.update(g, state, params)
    np.testing.assert_allclose(np.asarray(p1["w"]), [1., 1.])  # accumulated only
    p2, state = dopt.update(g, state, p1)
    np.testing.assert_allclose(np.asarray(p2["w"]), [0., 0.])  # avg grad 1 applied


def test_fleet_loss_scaler_skips_nonfinite():
    import paddle_tpu.optimizer as opt
    strategy = dist.DistributedStrategy()
    strategy.amp = True
    strategy.amp_configs.use_dynamic_loss_scaling = True
    strategy.amp_configs.init_loss_scaling = 4.0
    dist.fleet.init(strategy=strategy)
    dopt = dist.fleet.distributed_optimizer(opt.SGD(learning_rate=1.0), strategy)
    params = {"w": jnp.ones((2,))}
    state = dopt.init(params)
    bad = {"w": jnp.array([jnp.inf, 1.0])}
    p1, state = dopt.update(bad, state, params)
    np.testing.assert_allclose(np.asarray(p1["w"]), [1., 1.])  # skipped
    np.testing.assert_allclose(float(state["loss_scale"]), 2.0)  # decr_ratio
    good = {"w": jnp.array([4.0, 4.0])}
    p2, state = dopt.update(good, state, p1)
    np.testing.assert_allclose(np.asarray(p2["w"]), [-1., -1.])  # unscaled by 2


def test_fleet_lamb_swap():
    import paddle_tpu.optimizer as opt
    from paddle_tpu.optimizer.optimizers import Lamb
    strategy = dist.DistributedStrategy()
    strategy.lamb = True
    dist.fleet.init(strategy=strategy)
    dopt = dist.fleet.distributed_optimizer(opt.Adam(learning_rate=0.1), strategy)
    assert isinstance(dopt.inner, Lamb)


def test_all_reduce_subaxis_group_preserves_other_sharding():
    # Regression: reducing over one axis of a multi-axis-sharded input must
    # keep the result sharded over the untouched axes (per-dp results differ).
    from jax.sharding import NamedSharding
    m = dist.init_parallel_env(dp=2, tp=4)
    x = jax.device_put(jnp.arange(8.0), NamedSharding(m, PartitionSpec(("dp", "tp"))))
    out = dist.all_reduce(x, group="tp")
    np.testing.assert_allclose(np.asarray(out), [6.0, 22.0])
    out_spec = out.sharding.spec
    assert "dp" in str(out_spec) and "tp" not in str(out_spec)


def test_collectives_ignore_absent_group_axes():
    # Regression: a group naming an axis the mesh omitted (degree-1) must
    # reduce over the axes that exist, not crash on an unbound axis name.
    m = dist.init_parallel_env(dp=8)  # no 'tp' axis in the mesh
    out = dist.all_reduce(jnp.ones(4), group=("dp", "tp"))
    np.testing.assert_allclose(np.asarray(out), np.full((4,), 8.0))
    out = dist.all_gather(jnp.ones((1, 2)), group=("dp", "tp"))
    assert out.shape == (8, 2)


def test_fleet_skip_step_preserves_momentum_state():
    # Regression: a non-finite (skipped) step must leave Adam moments and
    # params untouched — zeroed grads would still move params via momentum.
    import paddle_tpu.optimizer as opt
    strategy = dist.DistributedStrategy()
    strategy.amp = True
    strategy.amp_configs.use_dynamic_loss_scaling = True
    strategy.amp_configs.init_loss_scaling = 1.0
    dist.fleet.init(strategy=strategy)
    dopt = dist.fleet.distributed_optimizer(opt.Adam(learning_rate=0.1), strategy)
    params = {"w": jnp.ones((2,))}
    state = dopt.init(params)
    p1, state = dopt.update({"w": jnp.ones((2,))}, state, params)  # real step
    m_before = np.asarray(state["inner"]["per_param"][0][0])
    step_before = int(state["inner"]["step"])
    p2, state = dopt.update({"w": jnp.array([jnp.inf, 1.0])}, state, p1)
    np.testing.assert_allclose(np.asarray(p2["w"]), np.asarray(p1["w"]))
    np.testing.assert_allclose(
        np.asarray(state["inner"]["per_param"][0][0]), m_before)
    assert int(state["inner"]["step"]) == step_before
    assert float(state["loss_scale"]) == 0.5


def test_fleet_lamb_swap_keeps_scheduler():
    import paddle_tpu.optimizer as opt
    from paddle_tpu.optimizer.lr import LRScheduler
    strategy = dist.DistributedStrategy()
    strategy.lamb = True
    dist.fleet.init(strategy=strategy)
    sched = opt.lr.StepDecay(learning_rate=0.1, step_size=10)
    dopt = dist.fleet.distributed_optimizer(
        opt.Adam(learning_rate=sched), strategy)
    assert isinstance(dopt.inner._lr, LRScheduler)


def test_distributed_optimizer_step_without_grads_raises():
    import paddle_tpu.optimizer as opt
    import paddle_tpu.nn as nn
    dist.fleet.init(strategy=dist.DistributedStrategy())
    lin = nn.Linear(2, 2)
    dopt = dist.fleet.distributed_optimizer(
        opt.SGD(learning_rate=0.1, parameters=lin.parameters()))
    with pytest.raises(ValueError, match="explicit grads"):
        dopt.step()


def test_cloned_encoder_layers_keep_configured_initializer():
    import paddle_tpu.nn as nn
    layer = nn.TransformerEncoderLayer(16, 2, 32)
    enc = nn.TransformerEncoder(layer, 3)
    # every clone records an initializer on its projection weights, and
    # clone values are re-drawn (not copies of layer 0)
    w0 = None
    for i, sub in enumerate(enc.layers):
        p = sub.self_attn.q_proj.weight
        assert p.initializer is not None
        if i == 0:
            w0 = np.asarray(p.value)
        else:
            assert not np.allclose(np.asarray(p.value), w0)
