"""DataParallel wrapper + fleet distributed metrics on the 8-device CPU mesh.

Mirrors the reference's parallel_dygraph_* tests: DP training equals
single-device training on the concatenated batch; metrics allreduce."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

import paddle_tpu as pd
from paddle_tpu.parallel.collective import shard_map
import paddle_tpu.nn as nn
from paddle_tpu.autograd import functional_call, parameters_dict
from paddle_tpu.distributed import env as dist_env
from paddle_tpu.parallel import DataParallel, apply_collective_grads, metrics


def _mesh():
    devs = np.array(jax.devices()[:8]).reshape(8)
    return Mesh(devs, ("dp",))


def test_dp_wrapper_delegates_and_identity_single_process():
    net = nn.Linear(4, 2)
    dp = DataParallel(net)
    x = jnp.ones((3, 4))
    np.testing.assert_allclose(np.asarray(dp(x)), np.asarray(net(x)))
    sd = dp.state_dict()
    assert any("weight" in k for k in sd)
    # no mesh context: collective grads are identity
    g = {"w": jnp.ones(3)}
    out = dp.apply_collective_grads(g)
    np.testing.assert_allclose(np.asarray(out["w"]), 1.0)


def test_dp_grads_match_single_device():
    """pmean'd per-shard grads == grads of the full batch (the DP
    correctness contract the reference's TestDistBase asserts)."""
    mesh = _mesh()
    net = nn.Linear(8, 4)
    params = parameters_dict(net)
    rng = np.random.RandomState(0)
    X = rng.rand(16, 8).astype(np.float32)
    Y = rng.randint(0, 4, 16)

    def loss_fn(p, x, y):
        return pd.nn.functional.cross_entropy(
            functional_call(net, p, (x,)), jnp.asarray(y)).mean()

    # single-device reference
    ref_grads = jax.grad(loss_fn)(params, jnp.asarray(X), jnp.asarray(Y))

    # sharded: each device computes grads on its shard, then pmean
    def shard_step(p, x, y):
        with dist_env.data_axis_scope("dp"):
            g = jax.grad(loss_fn)(p, x, y)
            return apply_collective_grads(g)

    # check_vma=True: apply_collective_grads reads each value's vma set to
    # pick pmean vs divide-by-n, so VMA tracking must stay on.
    sharded = shard_map(
        shard_step, mesh=mesh,
        in_specs=(P(), P("dp"), P("dp")), out_specs=P(), check_vma=True)
    dp_grads = sharded(params, jnp.asarray(X), jnp.asarray(Y))
    for k in ref_grads:
        np.testing.assert_allclose(np.asarray(dp_grads[k]),
                                   np.asarray(ref_grads[k]),
                                   rtol=2e-4, atol=1e-5)


def test_scale_loss_under_shard_map():
    mesh = _mesh()

    def f(x):
        with dist_env.data_axis_scope("dp"):
            from paddle_tpu.parallel import scale_loss
            # per-shard loss varies over dp, so the scaled value does too:
            # out_specs must keep the dp axis (VMA replication rule)
            return scale_loss(x.sum())[None]

    out = shard_map(f, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
                    check_vma=True)(jnp.ones(8))
    np.testing.assert_allclose(np.asarray(out), np.full(8, 1.0 / 8))


def test_distributed_metrics_psum():
    mesh = _mesh()

    def f(correct, total):
        with dist_env.data_axis_scope("dp"):
            return metrics.acc(correct.sum(), total.sum())

    # worker i contributes i correct of 10
    correct = jnp.arange(8, dtype=jnp.float32)
    total = jnp.full(8, 10.0)
    out = shard_map(f, mesh=mesh, in_specs=(P("dp"), P("dp")), out_specs=P())(
        correct, total)
    np.testing.assert_allclose(float(out), sum(range(8)) / 80.0)


def test_distributed_auc_merges_histograms():
    # two workers' histograms merged == single histogram of all data
    from paddle_tpu.metric import Auc
    rng = np.random.RandomState(0)
    preds = rng.rand(200)
    labels = (preds + rng.randn(200) * 0.3 > 0.5).astype(np.int64)

    full = Auc(num_thresholds=255)
    full.update(preds, labels)

    h1, h2 = Auc(num_thresholds=255), Auc(num_thresholds=255)
    h1.update(preds[:100], labels[:100])
    h2.update(preds[100:], labels[100:])
    merged = metrics.auc(h1._stat_pos + h2._stat_pos,
                         h1._stat_neg + h2._stat_neg)
    np.testing.assert_allclose(merged, full.accumulate(), rtol=1e-9)
