"""Pipeline parallelism: circular ppermute schedule == sequential execution,
and gradients flow through the pipeline (SURVEY.md §2.2 "Pipeline
parallelism" — ref PipelineOptimizer fluid/optimizer.py:3661)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec

import paddle_tpu.distributed as dist
from paddle_tpu.parallel import mesh as mesh_mod
from paddle_tpu.parallel.collective import shard_map
from paddle_tpu.parallel.pipeline import (
    PipelineStage,
    blockwise_stage_fn,
    microbatch,
    pipeline_apply,
    stack_block_params,
    unmicrobatch,
)


@pytest.fixture(autouse=True)
def _reset_mesh():
    yield
    mesh_mod.set_mesh(None)


def _block_fn(blk, x):
    return jnp.tanh(x @ blk["w"] + blk["b"])


def _make_blocks(n_blocks, d, seed=0):
    rng = np.random.default_rng(seed)
    return [{"w": jnp.asarray(rng.normal(0, 0.5, (d, d)), jnp.float32),
             "b": jnp.asarray(rng.normal(0, 0.1, (d,)), jnp.float32)}
            for _ in range(n_blocks)]


def _sequential(blocks, x):
    for blk in blocks:
        x = _block_fn(blk, x)
    return x


def test_stack_block_params():
    blocks = _make_blocks(4, 8)
    stacked = stack_block_params(blocks)
    assert stacked["w"].shape == (4, 8, 8)
    with pytest.raises(ValueError, match="identical parameter"):
        stack_block_params([{"w": jnp.zeros(2)}, {"x": jnp.zeros(2)}])


def test_pipeline_matches_sequential():
    m = dist.init_parallel_env(dp=2, pp=4)
    blocks = _make_blocks(8, 16)  # 2 blocks per stage
    stacked = stack_block_params(blocks)
    x = jnp.asarray(np.random.default_rng(1).normal(0, 1, (8, 16)), jnp.float32)
    ref = _sequential(blocks, x)

    stage = blockwise_stage_fn(_block_fn)

    def run(p, xs):
        return pipeline_apply(stage, p, xs, axis="pp")

    f = shard_map(run, mesh=m,
                  in_specs=({"w": PartitionSpec("pp"), "b": PartitionSpec("pp")},
                            PartitionSpec()),
                  out_specs=PartitionSpec(), check_vma=False)
    out = unmicrobatch(f(stacked, microbatch(x, 4)))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)


def test_pipeline_gradients_match_sequential():
    m = dist.init_parallel_env(pp=4)
    blocks = _make_blocks(4, 8)
    stacked = stack_block_params(blocks)
    x = jnp.asarray(np.random.default_rng(2).normal(0, 1, (4, 8)), jnp.float32)

    def seq_loss(p):
        h = x
        for i in range(4):
            h = _block_fn({"w": p["w"][i], "b": p["b"][i]}, h)
        return jnp.sum(h ** 2)

    stage = blockwise_stage_fn(_block_fn)

    def pipe_loss(p):
        def run(pp_params, xs):
            return pipeline_apply(stage, pp_params, xs, axis="pp")

        f = shard_map(run, mesh=m,
                      in_specs=({"w": PartitionSpec("pp"), "b": PartitionSpec("pp")},
                                PartitionSpec()),
                      out_specs=PartitionSpec(), check_vma=False)
        out = unmicrobatch(f(p, microbatch(x, 2)))
        return jnp.sum(out ** 2)

    g_ref = jax.grad(seq_loss)(stacked)
    g_pipe = jax.grad(pipe_loss)(stacked)
    for k in g_ref:
        np.testing.assert_allclose(np.asarray(g_pipe[k]), np.asarray(g_ref[k]),
                                   rtol=1e-4, atol=1e-5)


def test_pipeline_stage_wrapper():
    m = dist.init_parallel_env(pp=4)
    blocks = _make_blocks(4, 8)
    pipe = PipelineStage(_block_fn, stack_block_params(blocks), num_micro=2)
    pipe.shard_params()
    x = jnp.asarray(np.random.default_rng(3).normal(0, 1, (4, 8)), jnp.float32)
    out = pipe(x)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_sequential(blocks, x)),
                               rtol=2e-5, atol=2e-5)


def test_pipeline_stage_degenerate_single_stage():
    dist.init_parallel_env(dp=8)  # no pp axis -> plain scan
    blocks = _make_blocks(3, 8)
    pipe = PipelineStage(_block_fn, stack_block_params(blocks), num_micro=2)
    x = jnp.ones((4, 8), jnp.float32)
    np.testing.assert_allclose(np.asarray(pipe(x)),
                               np.asarray(_sequential(blocks, x)),
                               rtol=2e-5, atol=2e-5)


def test_microbatch_roundtrip_and_errors():
    x = jnp.arange(24.0).reshape(6, 4)
    mb = microbatch(x, 3)
    assert mb.shape == (3, 2, 4)
    np.testing.assert_allclose(np.asarray(unmicrobatch(mb)), np.asarray(x))
    with pytest.raises(ValueError, match="not divisible"):
        microbatch(x, 4)


# -- 1F1B -------------------------------------------------------------------

def _head_loss(hp, y, tgt, micro_idx=0):
    """Per-micro-batch loss: linear head + MSE (mean over the micro-batch)."""
    pred = y @ hp["w_out"]
    return jnp.mean((pred - tgt) ** 2)


def _run_1f1b(m, stacked, head, x, tgts, num_micro):
    from paddle_tpu.parallel.pipeline import pipeline_train_1f1b

    base = blockwise_stage_fn(_block_fn)
    stage = lambda p, x_, b: base(p, x_)

    def run(pp_params, hp, xs, ts):
        return pipeline_train_1f1b(stage, _head_loss, pp_params, hp, xs, ts,
                                   axis="pp")

    pspec = {"w": PartitionSpec("pp"), "b": PartitionSpec("pp")}
    f = shard_map(run, mesh=m,
                  in_specs=(pspec, PartitionSpec(), PartitionSpec(),
                            PartitionSpec()),
                  out_specs=(PartitionSpec(), pspec, PartitionSpec(),
                             PartitionSpec()),
                  check_vma=False)
    return f(stacked, head, microbatch(x, num_micro),
             microbatch(tgts, num_micro))


def _ref_loss_and_grads(stacked, head, x, tgts, num_micro):
    def total(p, hp, xs_in):
        def per_micro(xm, tm):
            h = xm
            for i in range(stacked["w"].shape[0]):
                h = _block_fn({"w": p["w"][i], "b": p["b"][i]}, h)
            return _head_loss(hp, h, tm)
        xs = microbatch(xs_in, num_micro)
        ts = microbatch(tgts, num_micro)
        losses = jax.vmap(per_micro)(xs, ts)
        return jnp.mean(losses)

    l, grads = jax.value_and_grad(total, argnums=(0, 1))(stacked, head, x)
    dxs = jax.grad(total, argnums=2)(stacked, head, x)
    return l, grads[0], grads[1], dxs


def test_pipeline_1f1b_matches_reference_loss_and_grads():
    m = dist.init_parallel_env(pp=4)
    rng = np.random.default_rng(4)
    blocks = _make_blocks(4, 8, seed=4)
    stacked = stack_block_params(blocks)
    head = {"w_out": jnp.asarray(rng.normal(0, 0.5, (8, 3)), jnp.float32)}
    num_micro, mb = 8, 2
    x = jnp.asarray(rng.normal(0, 1, (num_micro * mb, 8)), jnp.float32)
    tgts = jnp.asarray(rng.normal(0, 1, (num_micro * mb, 3)), jnp.float32)

    loss, sg, hg, dxs = _run_1f1b(m, stacked, head, x, tgts, num_micro)
    ref_l, ref_sg, ref_hg, ref_dx = _ref_loss_and_grads(
        stacked, head, x, tgts, num_micro)

    np.testing.assert_allclose(float(loss), float(ref_l), rtol=1e-5)
    for k in ref_sg:
        np.testing.assert_allclose(np.asarray(sg[k]), np.asarray(ref_sg[k]),
                                   rtol=2e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(hg["w_out"]),
                               np.asarray(ref_hg["w_out"]),
                               rtol=2e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(unmicrobatch(dxs)),
                               np.asarray(ref_dx), rtol=2e-4, atol=1e-5)


def test_pipeline_1f1b_peak_memory_below_gpipe():
    """The 1F1B property: stashed state is O(n_stages), not O(num_micro).
    Compare XLA's temp-buffer sizing for many micro-batches."""
    from paddle_tpu.parallel.pipeline import pipeline_train_1f1b

    m = dist.init_parallel_env(pp=4)
    rng = np.random.default_rng(5)
    d, num_micro, mb = 64, 32, 4
    blocks = _make_blocks(4, d, seed=5)
    stacked = stack_block_params(blocks)
    head = {"w_out": jnp.asarray(rng.normal(0, 0.5, (d, 3)), jnp.float32)}
    x = jnp.asarray(rng.normal(0, 1, (num_micro * mb, d)), jnp.float32)
    tgts = jnp.asarray(rng.normal(0, 1, (num_micro * mb, 3)), jnp.float32)
    base = blockwise_stage_fn(_block_fn)
    stage = lambda p, x_, b: base(p, x_)
    gstage = base
    pspec = {"w": PartitionSpec("pp"), "b": PartitionSpec("pp")}

    def run_1f1b(p, hp, xs, ts):
        return pipeline_train_1f1b(stage, _head_loss, p, hp, xs, ts,
                                   axis="pp")

    f1 = jax.jit(shard_map(run_1f1b, mesh=m,
                           in_specs=(pspec, PartitionSpec(), PartitionSpec(),
                                     PartitionSpec()),
                           out_specs=(PartitionSpec(), pspec, PartitionSpec(),
                                      PartitionSpec()),
                           check_vma=False))

    def gpipe_loss(p, hp, xs):
        def run(pp_params, xs_):
            return pipeline_apply(gstage, pp_params, xs_, axis="pp")

        g = shard_map(run, mesh=m, in_specs=(pspec, PartitionSpec()),
                      out_specs=PartitionSpec(), check_vma=False)
        ys = g(p, xs)
        pred = ys @ hp["w_out"]
        return jnp.mean((pred - microbatch(tgts, num_micro)) ** 2)

    f2 = jax.jit(jax.value_and_grad(gpipe_loss, argnums=(0, 1)))

    xs = microbatch(x, num_micro)
    ts = microbatch(tgts, num_micro)
    mem1 = f1.lower(stacked, head, xs, ts).compile().memory_analysis()
    mem2 = f2.lower(stacked, head, xs).compile().memory_analysis()
    t1 = mem1.temp_size_in_bytes
    t2 = mem2.temp_size_in_bytes
    assert t1 < t2, (t1, t2)
    # and it still computes the right loss
    loss, *_ = f1(stacked, head, xs, ts)
    ref, _ = f2(stacked, head, xs)
    np.testing.assert_allclose(float(loss), float(ref), rtol=1e-5)
