"""HybridPretrainer: the flagship hybrid-parallel train step (dp/pp/tp/sp/ep)
compiles, runs, and the pipelined encoder matches the sequential one."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.distributed as dist
from paddle_tpu.optimizer import Adam
from paddle_tpu.parallel import mesh as mesh_mod
from paddle_tpu.text.ernie import ErnieConfig
from paddle_tpu.text.pretrainer import HybridPretrainer


@pytest.fixture(autouse=True)
def _reset_mesh():
    yield
    mesh_mod.set_mesh(None)


CFG = dict(vocab_size=64, hidden_size=32, num_hidden_layers=2,
           num_attention_heads=2, intermediate_size=64,
           max_position_embeddings=32, hidden_dropout_prob=0.0,
           attention_probs_dropout_prob=0.0)


def _batch(rng, bs, seq, vocab):
    return {
        "input_ids": rng.integers(1, vocab, (bs, seq)).astype(np.int32),
        "token_type_ids": np.zeros((bs, seq), np.int32),
        "mlm_labels": rng.integers(0, vocab, (bs, seq)).astype(np.int32),
        "nsp_labels": rng.integers(0, 2, (bs,)).astype(np.int32),
    }


def _run_step(mesh_axes, moe=0, num_micro=2, seed=0):
    m = dist.init_parallel_env(**mesh_axes)
    trainer = HybridPretrainer(ErnieConfig(**CFG), mesh=m,
                               num_micro=num_micro, moe_experts=moe)
    opt = Adam(learning_rate=1e-3)
    params = trainer.place_params(trainer.init_params())
    state = opt.init(params)
    rng = np.random.default_rng(seed)
    batch = _batch(rng, 4 * num_micro, 16, trainer.cfg.vocab_size)
    sh = trainer.data_shardings(m)
    batch = {k: jax.device_put(v, sh[k]) for k, v in batch.items()}
    step = jax.jit(trainer.make_train_step(opt))
    with m:
        new_params, _, loss = step(params, state, batch, jax.random.PRNGKey(0))
    return trainer, params, new_params, float(loss)


def test_dp_tp_step():
    _, _, _, loss = _run_step(dict(dp=4, tp=2))
    assert np.isfinite(loss)


def test_pp_pipeline_matches_unpipelined():
    # same init (seeded) run with pp=4 vs single-stage: losses must agree
    import paddle_tpu
    paddle_tpu.seed(7)
    m1 = dist.init_parallel_env(dp=4, pp=2)
    t1 = HybridPretrainer(ErnieConfig(**CFG), mesh=m1, num_micro=2)
    p1 = t1.place_params(t1.init_params())
    rng = np.random.default_rng(0)
    batch = _batch(rng, 4, 16, t1.cfg.vocab_size)
    with m1:
        l_pipe = float(jax.jit(t1.loss_fn)(
            jax.tree_util.tree_map(jnp.asarray, p1),
            {k: jnp.asarray(v) for k, v in batch.items()},
            jax.random.PRNGKey(0)))

    # rebuild identical params on a pp-free mesh by reusing p1's raw values
    mesh_mod.set_mesh(None)
    m2 = dist.init_parallel_env(dp=8)
    t2 = HybridPretrainer(ErnieConfig(**CFG), mesh=m2, num_micro=2)
    raw = jax.tree_util.tree_map(np.asarray, p1)
    with m2:
        l_seq = float(jax.jit(t2.loss_fn)(
            jax.tree_util.tree_map(jnp.asarray, raw),
            {k: jnp.asarray(v) for k, v in batch.items()},
            jax.random.PRNGKey(0)))
    np.testing.assert_allclose(l_pipe, l_seq, rtol=1e-4)


def test_moe_sp_ep_step():
    _, _, _, loss = _run_step(dict(dp=2, sp=2, ep=2), moe=4)
    assert np.isfinite(loss)


def test_params_change_and_tied_weight_single_leaf():
    trainer, params, new_params, loss = _run_step(dict(dp=4, tp=2))
    ((tied, emb),) = trainer.model.tied.items()
    assert tied not in params["head"]
    # embedding table leaf received gradient (tied MLM decoder contributes)
    delta = np.abs(np.asarray(new_params["embed"][emb]) -
                   np.asarray(params["embed"][emb])).max()
    assert delta > 0


def test_1f1b_schedule_matches_gpipe():
    """PipelineConfig.schedule="1f1b" runs the manual-VJP schedule and
    produces the same loss and updated params as the GPipe path (dropout is
    0 in CFG, so the schedules are numerically comparable)."""
    from paddle_tpu.parallel.fleet import DistributedStrategy

    import paddle_tpu
    paddle_tpu.seed(13)
    m = dist.init_parallel_env(dp=4, pp=2)

    strat = DistributedStrategy()
    strat.pipeline = True
    strat.pipeline_configs.schedule = "1f1b"
    strat.pipeline_configs.micro_batch = 4

    t_1f1b = HybridPretrainer(ErnieConfig(**CFG), mesh=m, strategy=strat)
    assert t_1f1b.pp_schedule == "1f1b" and t_1f1b.num_micro == 4
    p0 = t_1f1b.place_params(t_1f1b.init_params())
    raw = jax.tree_util.tree_map(np.asarray, p0)

    # SGD, not Adam: Adam's first-step update is ~lr*sign(g), which turns
    # fp-noise-level grad differences between the two schedules into
    # full-scale param deltas.  SGD keeps param deltas proportional to g.
    from paddle_tpu.optimizer import SGD
    opt = SGD(learning_rate=0.1)
    rng = np.random.default_rng(0)
    batch = _batch(rng, 16, 16, t_1f1b.cfg.vocab_size)

    def run(trainer, params_np):
        params = trainer.place_params(
            jax.tree_util.tree_map(jnp.asarray, params_np))
        state = opt.init(params)
        sh = trainer.data_shardings(m)
        placed = {k: jax.device_put(v, sh[k]) for k, v in batch.items()}
        step = jax.jit(trainer.make_train_step(opt))
        with m:
            new_p, _, loss = step(params, state, placed,
                                  jax.random.PRNGKey(0))
        return float(loss), jax.tree_util.tree_map(np.asarray, new_p)

    l1, np1 = run(t_1f1b, raw)

    t_gp = HybridPretrainer(ErnieConfig(**CFG), mesh=m, num_micro=4)
    assert t_gp.pp_schedule == "gpipe"
    l2, np2 = run(t_gp, raw)

    np.testing.assert_allclose(l1, l2, rtol=1e-5)
    flat1 = jax.tree_util.tree_leaves(np1)
    flat2 = jax.tree_util.tree_leaves(np2)
    for a, b in zip(flat1, flat2):
        np.testing.assert_allclose(a, b, rtol=5e-4, atol=1e-6)


def test_unknown_pipeline_schedule_rejected():
    from paddle_tpu.parallel.fleet import DistributedStrategy

    m = dist.init_parallel_env(dp=4, pp=2)
    strat = DistributedStrategy()
    strat.pipeline = True
    strat.pipeline_configs.schedule = "interleaved-magic"
    with pytest.raises(ValueError, match="unknown pipeline schedule"):
        HybridPretrainer(ErnieConfig(**CFG), mesh=m, strategy=strat)
