"""The hybrid convolution/attention expert family (`text/lfm2_moe.py`) and
what it brought: groups of blocks read from `layer_types` and
`num_dense_layers` as maximal runs of one kind, a mixer that is no
attention under the `attn` region's `conv` scope, grouped-query attention
through the three flash kernels, the routing counters of every group that
has an expert layer, through the one trainer."""
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu
from paddle_tpu.autograd import functional_call, parameters_dict
from paddle_tpu.nn import functional as F
from paddle_tpu.optimizer import Adam
from paddle_tpu.parallel import mesh as mesh_mod
from paddle_tpu.parallel.fleet import DistributedStrategy, Fleet
from paddle_tpu.text import deepseek_v3 as ds
from paddle_tpu.text import lfm2_moe as lm
from paddle_tpu.text.pretrainer import HybridPretrainer, routing_stats
from paddle_tpu.utils import monitor, xprof

TINY = dict(vocab_size=96, hidden_size=64, num_hidden_layers=4,
            num_attention_heads=4, num_key_value_heads=2,
            intermediate_size=96, moe_intermediate_size=32, num_experts=8,
            num_experts_per_tok=2, num_dense_layers=1,
            layer_types=["conv", "full_attention", "conv", "conv"])
RUNS = ["run00_conv_dense", "run01_attention_expert", "run02_conv_expert"]


@pytest.fixture(autouse=True)
def _reset_mesh():
    yield
    mesh_mod.set_mesh(None)


def key(i):
    return jax.random.fold_in(jax.random.PRNGKey(32), i)


# ---------------------------------------------------------------------------
# the groups come from the pattern
# ---------------------------------------------------------------------------
def test_the_published_forty_layers_are_twenty_one_runs():
    cfg = lm.Lfm2MoeConfig()
    assert len(cfg.layer_types) == 40
    assert [i for i, k in enumerate(cfg.layer_types)
            if k == "full_attention"] == list(range(2, 40, 4))
    runs = lm.block_runs(cfg)
    assert runs[0] == ("conv", False, 2)                  # the dense prefix
    assert runs[1:3] == [("full_attention", True, 1), ("conv", True, 3)]
    assert runs[-2:] == [("full_attention", True, 1), ("conv", True, 1)]
    assert len(runs) == 21 and sum(n for *_, n in runs) == 40
    assert sum(n for m, _, n in runs if m == "full_attention") == 10
    names = [lm.run_name(i, m, e) for i, (m, e, _) in enumerate(runs)]
    assert names[:3] == ["run00_conv_dense", "run01_attention_expert",
                         "run02_conv_expert"]
    assert names == sorted(names) and len(set(names)) == 21


@pytest.mark.parametrize("layer_types, dense, want", [
    (["conv", "full_attention", "conv", "conv", "conv"], 1,     # the cut
     [("conv", False, 1), ("full_attention", True, 1), ("conv", True, 3)]),
    (["conv", "conv", "conv"], 1,        # the dense prefix splits a run
     [("conv", False, 1), ("conv", True, 2)]),
    (["full_attention", "full_attention"], 0,
     [("full_attention", True, 2)]),
    (["full_attention", "conv"], 2,
     [("full_attention", False, 1), ("conv", False, 1)]),
])
def test_runs_of_one_kind(layer_types, dense, want):
    cfg = lm.Lfm2MoeConfig(**{**TINY, "num_hidden_layers": len(layer_types),
                              "layer_types": layer_types,
                              "num_dense_layers": dense})
    assert lm.block_runs(cfg) == want
    model = lm.pretrain_model(cfg)
    assert list(model.groups) == [lm.run_name(i, m, e)
                                  for i, (m, e, _) in enumerate(want)]
    assert [len(g.layers) for g in model.groups.values()] == \
        [n for *_, n in want]


@pytest.mark.parametrize("layer_types", [["conv"] * 3, ["conv", "window"] * 2])
def test_layer_types_must_name_every_layer(layer_types):
    with pytest.raises(ValueError, match="layer_types"):
        lm.Lfm2MoeConfig(**{**TINY, "layer_types": layer_types})


# ---------------------------------------------------------------------------
# the pieces
# ---------------------------------------------------------------------------
def test_rotary_halves_rotates_the_halves():
    x = jax.random.normal(key(1), (2, 5, 8))
    got = lm.rotary_halves(x, 1e4)
    a, b = np.asarray(x[..., :4]), np.asarray(x[..., 4:])
    ang = np.arange(5)[:, None] * (1e4 ** (-np.arange(0, 8, 2) / 8))[None, :]
    want = np.concatenate([a * np.cos(ang) - b * np.sin(ang),
                           b * np.cos(ang) + a * np.sin(ang)], -1)
    np.testing.assert_allclose(got, want, atol=1e-6)
    np.testing.assert_allclose(got[:, 0], x[:, 0], atol=1e-7)   # angle 0


def test_short_conv_is_the_published_depthwise_conv1d():
    """Channels on lanes and three shifted multiply-adds against the
    published form: channels first, Conv1d(kernel 3, groups hidden,
    padding 2) cut to the first s outputs."""
    cfg = lm.Lfm2MoeConfig(**TINY)
    layer = lm.ShortConv(cfg)
    assert layer.taps.value.shape == (3, 64)
    x = jax.random.normal(key(2), (2, 16, 64))
    gate_b, gate_c, u = jnp.split(layer.in_proj(x), 3, axis=-1)
    z = (gate_b * u).transpose(0, 2, 1)
    c = F.conv1d(z, layer.taps.value.T[:, None, :], padding=2,
                 groups=64)[..., :16]
    want = layer.out_proj(gate_c * c.transpose(0, 2, 1))
    np.testing.assert_allclose(layer(x), want, atol=1e-6, rtol=1e-4)
    # and its gradient, through the checkpoint
    g = jax.grad(lambda x: jnp.sum(layer(x) ** 2))(x)
    assert np.isfinite(np.asarray(g)).all() and np.asarray(g).any()


def test_attention_takes_the_flash_dispatch_with_grouped_keys(monkeypatch):
    """k and v reach `ops.attention.flash_attention` with their own two
    heads (never repeated to four), causal, scaled by 1/sqrt(head size)."""
    from paddle_tpu.ops import attention as attn_ops
    seen = {}

    def spy(q, k, v, **kw):
        seen.update(q=q.shape, k=k.shape, v=v.shape, **kw)
        return attn_ops.scaled_dot_product_attention(
            q, k, v, is_causal=kw["is_causal"], scale=kw["scale"])

    monkeypatch.setattr(attn_ops, "flash_attention", spy)
    layer = lm.GroupedQueryAttention(lm.Lfm2MoeConfig(**TINY))
    out = layer(jax.random.normal(key(3), (2, 16, 64)))
    assert out.shape == (2, 16, 64)
    assert (seen["q"], seen["k"], seen["v"]) == (
        (2, 4, 16, 16), (2, 2, 16, 16), (2, 2, 16, 16))
    assert seen["is_causal"] is True and seen["scale"] == 0.25
    assert layer.q_norm.epsilon == layer.k_norm.epsilon == 1e-5


def test_every_norm_takes_the_configs_epsilon():
    from paddle_tpu import nn
    model = lm.pretrain_model(lm.Lfm2MoeConfig(**TINY, norm_eps=3e-5))
    layers = [model.head] + [b for g in model.groups.values()
                             for b in g.layers]
    norms = [l for top in layers for l in top.sublayers()
             if isinstance(l, nn.RMSNorm)]
    assert len(norms) == 1 + 4 * 2 + 2
    assert all(n.epsilon == 3e-5 for n in norms)
    assert nn.RMSNorm(8).epsilon == 1e-6            # the default stays


def test_expert_layers_have_no_shared_expert_and_the_familys_epsilon():
    from paddle_tpu import nn
    model = lm.pretrain_model(lm.Lfm2MoeConfig(**TINY, held_experts=(4, 4)))
    moes = [l for g in model.groups.values() for b in g.layers
            for l in b.sublayers() if isinstance(l, nn.DroplessMoE)]
    assert len(moes) == 3
    for layer in moes:
        assert layer.shared_mlp is None and layer.norm_eps == 1e-6
        assert layer.held == (4, 4) and layer.top_k == 2
        assert layer.n_routed_experts == 8


# ---------------------------------------------------------------------------
# through the one trainer
# ---------------------------------------------------------------------------
def build(cfg, dp=1):
    """Seed, then draw the model's own initial values: the same weights
    whatever ran before."""
    strategy = DistributedStrategy()
    strategy.hybrid_configs.dp_degree = dp
    fleet = Fleet().init(strategy=strategy, devices=jax.devices()[:dp])
    paddle_tpu.seed(0)
    trainer = HybridPretrainer(lm.pretrain_model(cfg), mesh=fleet.mesh,
                               strategy=strategy)
    opt = fleet.distributed_optimizer(Adam(learning_rate=1e-3))
    return trainer, opt


def batch_of(trainer=None):
    ids = np.random.default_rng(0).integers(1, 96, (2, 32)).astype(np.int32)
    if trainer is None:
        return {"input_ids": jnp.asarray(ids)}
    return {"input_ids": jax.device_put(
        ids, trainer.data_shardings()["input_ids"])}


@pytest.fixture(scope="module")
def tiny_lm():
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    try:
        cfg = lm.Lfm2MoeConfig(**TINY, held_experts=(4, 4))
        trainer, opt = build(cfg)
        step = jax.jit(trainer.make_train_step(opt,
                                               compute_dtype=jnp.bfloat16))
        params = trainer.place_params(trainer.init_params())
        shapes = jax.tree_util.tree_map(lambda x: x.shape, params)
        shardings = trainer.param_shardings(params)
        batch = batch_of()
        text = step.lower(params, opt.init(params), batch,
                          jax.random.PRNGKey(0)).compile().as_text()
        state, losses = opt.init(params), []
        for _ in range(4):
            params, state, loss = step(params, state, batch,
                                       jax.random.PRNGKey(0))
            losses.append(float(loss))
        stats = routing_stats(trainer, params, batch)
        via_deepseek = ds.routing_stats(trainer, params, batch)
    finally:
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          False)
        mesh_mod.set_mesh(None)
    return {"trainer": trainer, "params": params, "shapes": shapes,
            "shardings": shardings, "losses": losses, "text": text,
            "stats": stats, "via_deepseek": via_deepseek}


def test_the_hybrid_trains_through_the_one_trainer(tiny_lm):
    shapes, losses = tiny_lm["shapes"], tiny_lm["losses"]
    assert sorted(shapes) == sorted(RUNS + ["embed", "head"])
    assert shapes["embed"] == {"word_embeddings.weight": (96, 64)}
    assert shapes["head"] == {"final_norm.weight": (64,)}      # tied
    assert shapes["run00_conv_dense"] == {
        "operator_norm.weight": (1, 64), "ffn_norm.weight": (1, 64),
        "operator.in_proj.weight": (1, 64, 192),
        "operator.taps": (1, 3, 64),
        "operator.out_proj.weight": (1, 64, 64),
        "feed_forward.gate_up.weight": (1, 64, 192),
        "feed_forward.down.weight": (1, 96, 64)}
    assert shapes["run01_attention_expert"] == {
        "operator_norm.weight": (1, 64), "ffn_norm.weight": (1, 64),
        "operator.qkv_proj.weight": (1, 64, 128),
        "operator.q_norm.weight": (1, 16), "operator.k_norm.weight": (1, 16),
        "operator.out_proj.weight": (1, 64, 64),
        "feed_forward.router_weight": (1, 64, 8),
        "feed_forward.router_bias": (1, 8),
        "feed_forward.w_in": (1, 4, 64, 64),
        "feed_forward.w_out": (1, 4, 32, 64)}
    assert shapes["run02_conv_expert"]["operator.taps"] == (2, 3, 64)
    assert shapes["run02_conv_expert"]["feed_forward.w_in"] == (2, 4, 64, 64)
    assert all(np.isfinite(losses)) and losses[-1] < losses[0] - 0.1
    assert abs(losses[0] - math.log(96)) < 0.2   # untrained: uniform
    assert tiny_lm["trainer"].data_shardings().keys() == {"input_ids"}


def test_first_three_losses_are_pinned(tiny_lm):
    """bf16 compute on the CPU backend from `paddle_tpu.seed(0)`: a change
    of the model's arithmetic moves these in the third digit, a reordered
    sum in the fifth."""
    assert tiny_lm["losses"][:3] == pytest.approx(PINNED_LOSSES, rel=2e-3)


def test_every_leaf_is_whole_on_the_one_chip_mesh(tiny_lm):
    flat = jax.tree_util.tree_leaves(
        tiny_lm["shardings"], is_leaf=lambda x: hasattr(x, "spec"))
    assert len(flat) == 1 + 1 + 7 + 10 + 9
    assert all(not any(s.spec) for s in flat)      # dp only: no axis named


def test_two_data_parallel_shards_read_the_same_losses(tiny_lm):
    if jax.device_count() < 2:
        pytest.skip("needs the virtual CPU mesh")
    cfg = lm.Lfm2MoeConfig(**TINY, held_experts=(4, 4))
    trainer, opt = build(cfg, dp=2)
    step = jax.jit(trainer.make_train_step(opt, compute_dtype=jnp.bfloat16))
    params = trainer.place_params(trainer.init_params())
    batch = batch_of(trainer)
    state, losses = opt.init(params), []
    for _ in range(4):
        params, state, loss = step(params, state, batch,
                                   jax.random.PRNGKey(0))
        losses.append(float(loss))
    # the same seed, weights and batch: bf16 sums in another order
    np.testing.assert_allclose(losses, tiny_lm["losses"], rtol=2e-3)


def test_the_tied_head_reads_the_embedding_and_both_uses_train_it(tiny_lm):
    trainer, params = tiny_lm["trainer"], tiny_lm["params"]
    assert trainer.model.tied == {"lm_weight": "word_embeddings.weight"}
    batch = batch_of()
    g = jax.grad(lambda p: trainer.loss_fn(p, batch, jax.random.PRNGKey(0)))(
        params)
    table = np.asarray(g["embed"]["word_embeddings.weight"])
    seen = np.unique(np.asarray(batch["input_ids"]))
    unseen = np.setdiff1d(np.arange(96), seen)
    # rows no token looked up still get the logits' gradient
    assert unseen.size and np.abs(table[unseen]).max() > 0
    # the head owns the final norm alone: the matrix is the embedding's
    assert set(trainer.init_params()["head"]) == {"final_norm.weight"}


def test_routing_stats_walk_every_group_with_an_expert_layer(tiny_lm):
    stats = tiny_lm["stats"]
    # three expert layers in two groups, in the model's order
    assert stats["pairs_routed"].tolist() == [2 * 32 * 2] * 3
    assert (stats["pairs_dropped"] == 0).all()
    assert (0 < stats["pairs_held"]).all() and \
        (stats["pairs_held"] < 2 * 32 * 2).all()
    for name in stats:
        np.testing.assert_array_equal(stats[name],
                                      tiny_lm["via_deepseek"][name])
    reg = monitor.default_registry()
    # the rows the layer's passes run over: whole chunks that cover the held
    assert (stats["buffer_rows"] >= stats["pairs_held"]).all() and \
        (stats["buffer_rows"] <= stats["pairs_routed"]).all()
    for name in ("pairs_routed", "pairs_held", "held_load_max_over_mean",
                 "pairs_dropped", "buffer_rows"):
        samples = dict((l["layer"], v)
                       for l, v in reg.get(f"moe.{name}").samples())
        assert samples.keys() == {"0", "1", "2"}
        assert samples["2"] == pytest.approx(float(stats[name][2]))


def test_routing_stats_of_a_model_without_expert_layers_is_empty():
    cfg = lm.Lfm2MoeConfig(**{**TINY, "num_dense_layers": 4})
    trainer, _ = build(cfg)
    params = trainer.place_params(trainer.init_params())
    assert routing_stats(trainer, params, batch_of()) == {}


@pytest.mark.parametrize("scope", [
    "attn/conv", "attn/core", "ffn/router", "ffn/experts"])
def test_the_compiled_step_carries_the_scopes(tiny_lm, scope):
    paths = set(re.findall(r'op_name="([^"]*)"', tiny_lm["text"]))
    region, sub = scope.split("/")
    # a Layer attribute's own scope (`operator`) may lie between
    under = re.compile(rf"/{region}/(?:[\w.]+/)*?{sub}/")
    mine = [p for p in paths if under.search(p)]
    assert any("transpose(" in p for p in mine), scope
    assert any("transpose(" not in p for p in mine), scope


def test_no_shared_scope_and_the_conv_products_lie_under_conv(tiny_lm):
    paths = set(re.findall(r'op_name="([^"]*)"', tiny_lm["text"]))
    assert not any(re.search(r"/ffn/(?:[\w.]+/)*?shared/", p) for p in paths)
    assert any(re.search(r"/attn/(?:[\w.]+/)*?conv/.*dot_general", p)
               for p in paths)


def test_no_layer_attribute_is_named_like_a_scope():
    model = lm.pretrain_model(lm.Lfm2MoeConfig(**TINY))
    taken = {r.split("/")[-1] for r in xprof.REGIONS} | {"attn"} | {
        c for children in xprof.SUBSCOPES.values() for c in children}
    assert xprof.SCOPE_CONV == "conv"
    assert {"conv", "proj", "prep", "pointwise", "dispatch", "products",
            "gated", "combine"} <= taken and "latent" not in taken
    layers = [model.embeddings, model.head] + [
        s.layers[0] for s in model.groups.values()]
    for layer in layers:
        for name, _ in layer.named_sublayers():
            assert not taken & set(name.split(".")), name
    assert not taken & set(model.groups)


def test_a_block_gives_its_routing_only_when_asked():
    cfg = lm.Lfm2MoeConfig(**TINY, held_experts=(0, 4))
    block = lm.Lfm2MoeBlock(cfg, "conv", expert=True)
    x = jax.random.normal(key(9), (2, 8, 64))
    y = block(x)
    y2, stats = block(x, routing_stats=True)
    np.testing.assert_array_equal(y, y2)
    assert int(stats["pairs_routed"]) == 2 * 8 * 2
    assert int(stats["pairs_dropped"]) == 0
    p = parameters_dict(block)
    np.testing.assert_allclose(functional_call(block, p, (x,)), y)


PINNED_LOSSES = [4.60382, 4.42741, 4.27769]
