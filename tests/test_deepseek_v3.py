"""The decoder-only family (`text/deepseek_v3.py`) and what it brought: the
flash kernel with a `v` head size of its own, the dropless expert layer that
is told which experts it holds, RMSNorm's lean backward, the trainer that
takes a model's pieces (ERNIE unchanged by it), the region scopes of the new
step."""
import hashlib
import json
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu
import paddle_tpu.nn as nn
from paddle_tpu.autograd import functional_call, parameters_dict
from paddle_tpu.nn import functional as F
from paddle_tpu.ops import attention as attn_ops
from paddle_tpu.ops.pallas import config as pcfg
from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.optimizer import Adam
from paddle_tpu.parallel import mesh as mesh_mod
from paddle_tpu.parallel.fleet import DistributedStrategy, Fleet
from paddle_tpu.text import deepseek_v3 as ds
from paddle_tpu.text.ernie import ErnieConfig
from paddle_tpu.text.pretrainer import HybridPretrainer, PretrainModel
from paddle_tpu.utils import monitor, xprof

TINY = dict(vocab_size=96, hidden_size=32, num_hidden_layers=3,
            num_attention_heads=2, intermediate_size=64,
            moe_intermediate_size=16, n_routed_experts=16,
            n_shared_experts=2, num_experts_per_tok=3, kv_lora_rank=16,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16)


@pytest.fixture(autouse=True)
def _reset_mesh():
    yield
    mesh_mod.set_mesh(None)


def key(i):
    return jax.random.fold_in(jax.random.PRNGKey(28), i)


# ---------------------------------------------------------------------------
# the flash kernel at q·k 192 / v 128
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def flash_192_128():
    """Output and the three gradients, kernel (interpret mode) and jnp."""
    b, h, s, d, dv = 1, 2, 256, 192, 128
    q, k = (jax.random.normal(key(i), (b, h, s, d)) for i in (1, 2))
    v, g = (jax.random.normal(key(i), (b, h, s, dv)) for i in (3, 4))

    def kernel(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, block_q=128,
                                  block_k=128)

    def plain(q, k, v):
        return attn_ops.scaled_dot_product_attention(q, k, v, is_causal=True)

    out = {}
    for name, f in (("kernel", kernel), ("plain", plain)):
        o, vjp = jax.vjp(f, q, k, v)
        out[name] = dict(zip(("o", "dq", "dk", "dv"), (o,) + vjp(g)))
    return out


@pytest.mark.parametrize("which, shape", [
    ("o", (1, 2, 256, 128)), ("dq", (1, 2, 256, 192)),
    ("dk", (1, 2, 256, 192)), ("dv", (1, 2, 256, 128))])
def test_flash_kernel_with_its_own_v_head_size(flash_192_128, which, shape):
    a, b = flash_192_128["kernel"][which], flash_192_128["plain"][which]
    assert a.shape == b.shape == shape
    # float32 end to end in interpret mode: the block-wise softmax differs
    # from the one-pass one by summation order only
    np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)


def test_flash_supported_takes_the_v_head_size():
    assert fa.supported(4096, 192, 128) and fa.supported(512, 64)
    assert not fa.supported(4096, 192, 100)
    assert not fa.supported(100, 192, 128)


def _fallbacks():
    c = monitor.default_registry().get("pallas.fallbacks")
    return {(l.get("kernel"), l.get("reason")): n for l, n in c.samples()}


def test_a_causal_mask_free_miss_of_the_kernel_is_counted(monkeypatch):
    monkeypatch.setattr(pcfg, "kernel_enabled", lambda name: True)
    q = jnp.ones((1, 2, 100, 24))       # seq 100: the kernel's refusal
    before = _fallbacks().get(("flash_attention", "unsupported"), 0)
    out = attn_ops.flash_attention(q, q, q[..., :16], is_causal=True)
    assert out.shape == (1, 2, 100, 16)
    assert _fallbacks()[("flash_attention", "unsupported")] == before + 1
    # q and k of different shapes: counted with its own reason
    attn_ops.flash_attention(q, q[:, :, :50], q[:, :, :50], is_causal=True)
    assert ("flash_attention", "shapes") in _fallbacks()
    # a masked or non-causal miss was never the kernel's to take in silence:
    # not counted (the encoder's eager paths would flood the counter)
    n = sum(_fallbacks().values())
    attn_ops.flash_attention(q, q, q)
    assert sum(_fallbacks().values()) == n


def test_off_the_tpu_nothing_is_missed_and_nothing_counted():
    n = sum(_fallbacks().values())
    q = jnp.ones((1, 2, 128, 64))
    attn_ops.flash_attention(q, q, q[..., :32], is_causal=True)
    assert sum(_fallbacks().values()) == n


# ---------------------------------------------------------------------------
# RMSNorm, rotary
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 2e-6),
                                        (jnp.bfloat16, 2e-2)])
def test_rms_norm_backward_from_lean_residuals(dtype, tol):
    x = jax.random.normal(key(5), (3, 5, 64)).astype(dtype)
    w = (1 + 0.1 * jax.random.normal(key(6), (64,))).astype(dtype)
    g = jax.random.normal(key(7), (3, 5, 64)).astype(dtype)

    def plain(x, w):
        xf = x.astype(jnp.float32)
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        return (xf / jnp.sqrt(ms + 1e-6)).astype(x.dtype) * w

    def loss(f):
        return lambda x, w: jnp.sum((f(x, w) * g).astype(jnp.float32))

    assert (F.rms_norm(x, w) == plain(x, w)).all()
    got = jax.grad(loss(F.rms_norm), (0, 1))(x, w)
    want = jax.grad(loss(plain), (0, 1))(x, w)
    for a, b in zip(got, want):
        # bf16: one rounding of the result (2^-8 relative); f32: round-off
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=tol, atol=tol * 4)


def test_rotary_pairs_leave_every_score_where_the_published_form_has_it():
    """The published code gathers even and odd channels into halves, then
    rotates halves; here pairs rotate in place.  Scores agree."""
    q, k = jax.random.normal(key(8), (2, 3, 16, 8)), \
        jax.random.normal(key(9), (2, 1, 16, 8))
    theta = 1e6

    def published(x):
        d = x.shape[-1]
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
        inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        ang = jnp.arange(x.shape[-2])[:, None] * inv[None, :]
        cos, sin = (jnp.concatenate([f(ang), f(ang)], -1)
                    for f in (jnp.cos, jnp.sin))
        half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
        return x * cos + half * sin

    ours = jnp.einsum("bhqd,bhkd->bhqk", ds.rotary_interleaved(q, theta),
                      jnp.broadcast_to(ds.rotary_interleaved(k, theta),
                                       q.shape))
    theirs = jnp.einsum("bhqd,bhkd->bhqk", published(q),
                        jnp.broadcast_to(published(k), q.shape))
    np.testing.assert_allclose(ours, theirs, atol=1e-5)
    # position 0 is not rotated; a relative shift is all a score sees
    np.testing.assert_allclose(ds.rotary_interleaved(q, theta)[..., 0, :],
                               q[..., 0, :], atol=1e-6)


# ---------------------------------------------------------------------------
# the dropless expert layer
# ---------------------------------------------------------------------------
D, FF, E, K = 32, 16, 16, 3


def moe(held=None):
    return nn.DroplessMoE(D, FF, E, K, held=held, n_shared_experts=2,
                          routed_scaling_factor=2.448)


@pytest.fixture(scope="module")
def moe_weights():
    names = parameters_dict(moe())
    return {k: 0.2 * jax.random.normal(key(10 + i), v.shape)
            for i, (k, v) in enumerate(names.items())}


def dense_moe(p, x, scaling=2.448):
    """Every expert over every token, the router's weight on its result."""
    t = x.reshape(-1, D)
    s = jax.nn.sigmoid(t @ p["router_weight"])
    _, ids = jax.lax.top_k(s + p["router_bias"], K)
    w = jnp.take_along_axis(s, ids, -1)
    w = w / w.sum(-1, keepdims=True) * scaling
    weights = jnp.zeros((t.shape[0], E)).at[
        jnp.arange(t.shape[0])[:, None], ids].set(w)
    gate, up = jnp.split(jnp.einsum("td,edf->tef", t, p["w_in"]), 2, -1)
    out = jnp.einsum("tef,efd->ted", jax.nn.silu(gate) * up, p["w_out"])
    y = jnp.einsum("te,ted->td", weights, out)
    gs, us = jnp.split(t @ p["shared_mlp.gate_up.weight"], 2, -1)
    shared = (jax.nn.silu(gs) * us) @ p["shared_mlp.down.weight"]
    return (y + shared).reshape(x.shape), shared.reshape(x.shape)


def share_of(p, first, count):
    return {**p, "w_in": p["w_in"][first:first + count],
            "w_out": p["w_out"][first:first + count]}


def test_whole_layer_is_the_dense_sum(moe_weights):
    x = jax.random.normal(key(30), (2, 24, D))
    got = functional_call(moe(), moe_weights, (x,))
    np.testing.assert_allclose(got, dense_moe(moe_weights, x)[0], atol=2e-5)


def test_gradients_are_the_dense_sums_and_the_bias_takes_none(moe_weights):
    x = jax.random.normal(key(31), (2, 24, D))
    layer = moe()
    got = jax.grad(lambda p, x: jnp.sum(
        functional_call(layer, p, (x,)) ** 2), (0, 1))(moe_weights, x)
    want = jax.grad(lambda p, x: jnp.sum(dense_moe(p, x)[0] ** 2),
                    (0, 1))(moe_weights, x)
    assert not np.asarray(got[0]["router_bias"]).any()
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("count", [2, 4, 16])
def test_the_shares_add_up_to_the_whole_layer(moe_weights, count):
    """Every holder's part, the shared experts counted once."""
    x = jax.random.normal(key(32), (2, 24, D))
    whole, shared = dense_moe(moe_weights, x)
    total = shared
    for first in range(0, E, count):
        part = functional_call(moe((first, count)),
                               share_of(moe_weights, first, count), (x,))
        total = total + part - shared
    np.testing.assert_allclose(total, whole, atol=5e-5)


def test_a_skewed_router_drops_no_pair(moe_weights):
    """Every token's first choice is expert 5 (its score's bias is huge):
    the holder of expert 5 gets a pair from every token and computes it."""
    p = dict(moe_weights)
    p["router_bias"] = p["router_bias"].at[5].set(100.0)
    x = jax.random.normal(key(33), (4, 32, D))
    layer = moe((4, 4))
    with paddle_tpu.autograd._swapped(layer, share_of(p, 4, 4)):
        stats = jax.device_get(layer.routing_stats(x))
        ids, _ = layer.route(x.reshape(-1, D))
    assert (np.asarray(ids) == 5).any(axis=1).all()
    assert stats["pairs_routed"] == 4 * 32 * K
    assert stats["pairs_held"] >= 4 * 32 and stats["pairs_dropped"] == 0
    assert stats["held_load_max_over_mean"] > 1.5
    whole, shared = dense_moe(p, x)
    others = sum(functional_call(moe((f, 4)), share_of(p, f, 4), (x,))
                 - shared for f in (0, 8, 12))
    mine = functional_call(layer, share_of(p, 4, 4), (x,))
    np.testing.assert_allclose(mine + others, whole, atol=5e-5)


def test_rows_of_no_held_pair_may_hold_anything(moe_weights, monkeypatch):
    """A grouped-product kernel writes its groups' rows and leaves the rest
    as it found them, forward and transposed.  With NaN there (and only the
    groups' rows read, as the kernel reads), result and gradients stand."""
    plain = jax.lax.ragged_dot

    def in_groups(x, sizes, fill):
        keep = (jnp.arange(x.shape[0]) < jnp.sum(sizes))[:, None]
        return jnp.where(keep, x, fill)

    def poisoned(lhs, rhs, sizes):
        @jax.custom_vjp
        def dot(lhs, rhs):
            return in_groups(plain(in_groups(lhs, sizes, 0), rhs, sizes),
                             sizes, jnp.nan)

        def fwd(lhs, rhs):
            return dot(lhs, rhs), (lhs, rhs)

        def bwd(res, g):
            _, vjp = jax.vjp(lambda a, b: plain(a, b, sizes),
                             in_groups(res[0], sizes, 0), res[1])
            d_lhs, d_rhs = vjp(in_groups(g, sizes, 0))
            return in_groups(d_lhs, sizes, jnp.nan), d_rhs

        dot.defvjp(fwd, bwd)
        return dot(lhs, rhs)

    x = jax.random.normal(key(36), (2, 24, D))
    layer, p = moe((4, 4)), share_of(moe_weights, 4, 4)

    def loss(p, x):
        return jnp.sum(functional_call(layer, p, (x,)) ** 2)

    want = jax.value_and_grad(loss, (0, 1))(p, x)
    monkeypatch.setattr(jax.lax, "ragged_dot", poisoned)
    got = jax.value_and_grad(loss, (0, 1))(p, x)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.isfinite(a).all()
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


def test_holding_nothing_selected_gives_the_shared_experts_alone(moe_weights):
    p = dict(moe_weights)
    p["router_bias"] = p["router_bias"].at[:K].set(100.0)   # experts 0..2
    x = jax.random.normal(key(34), (2, 8, D))
    got = functional_call(moe((8, 4)), share_of(p, 8, 4), (x,))
    np.testing.assert_allclose(got, dense_moe(p, x)[1], atol=1e-6)


def test_grouped_matmul_kernel_is_the_ragged_dot_on_its_groups():
    """The Pallas grouped product (interpret mode) against `lax.ragged_dot`,
    forward and both gradients, an empty group among them; rows past the
    last group are masked on both sides as the layer masks them."""
    from paddle_tpu.ops.pallas import grouped_matmul as gm
    m, k, n = 512, 128, 256
    assert gm.supported(m, k, n) and not gm.supported(m + 8, k, n)
    # each product its own tiles: the forward at (k, n) = (2048, 1536), the
    # input's gradient with the two exchanged
    assert gm._tiling(49152, 2048, 1536) == (256, 2048, 768)
    assert gm._tiling(49152, 1536, 2048) == (256, 1536, 1024)
    x = jax.random.normal(key(40), (m, k))
    w = jax.random.normal(key(41), (3, k, n))
    sizes = jnp.asarray([100, 0, 200], jnp.int32)
    valid = (jnp.arange(m) < 300)[:, None]

    def loss(dot):
        return lambda x, w: jnp.sum(jnp.square(jnp.where(
            valid, dot(jnp.where(valid, x, 0), w, sizes), 0)))

    got = jax.value_and_grad(loss(gm.grouped_matmul), (0, 1))(x, w)
    want = jax.value_and_grad(loss(jax.lax.ragged_dot), (0, 1))(x, w)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-2)
    assert not np.asarray(got[1][1][1]).any()     # the empty group


@pytest.mark.parametrize("held", [(-1, 4), (14, 4), (0, 0)])
def test_held_must_be_a_range_of_the_routed_experts(held):
    with pytest.raises(ValueError, match="held"):
        moe(held)


def test_swiglu_is_the_gated_ffn():
    layer = nn.SwiGLU(8, 12)
    x = jax.random.normal(key(35), (3, 8))
    wi, wo = layer.gate_up.weight.value, layer.down.weight.value
    want = (jax.nn.silu(x @ wi[:, :12]) * (x @ wi[:, 12:])) @ wo
    np.testing.assert_allclose(layer(x), want, atol=1e-6)


# ---------------------------------------------------------------------------
# the trainer takes the model's pieces
# ---------------------------------------------------------------------------
def build(cfg, dp=1, pp=1):
    strategy = DistributedStrategy()
    strategy.hybrid_configs.dp_degree = dp
    strategy.hybrid_configs.pp_degree = pp
    fleet = Fleet().init(strategy=strategy, devices=jax.devices()[:dp * pp])
    paddle_tpu.seed(0)
    trainer = HybridPretrainer(cfg, mesh=fleet.mesh, strategy=strategy)
    opt = fleet.distributed_optimizer(Adam(learning_rate=1e-3))
    return trainer, opt


@pytest.fixture(scope="module")
def tiny_lm():
    cfg = ds.DeepseekV3Config(**TINY, held_experts=(4, 4))
    trainer, opt = build(ds.pretrain_model(cfg))
    step = jax.jit(trainer.make_train_step(opt, compute_dtype=jnp.bfloat16))
    params = trainer.place_params(trainer.init_params())
    batch = {"input_ids": jnp.asarray(np.random.default_rng(0).integers(
        1, 96, (2, 32)), jnp.int32)}
    # the persistent cache keys a program without its metadata: an entry
    # compiled under other scopes would serve its own text
    name = "jax_compilation_cache_include_metadata_in_key"
    jax.config.update(name, True)
    try:
        text = step.lower(params, opt.init(params), batch,
                          jax.random.PRNGKey(0)).compile().as_text()
    finally:
        jax.config.update(name, False)
    state, losses = opt.init(params), []
    for _ in range(4):
        params, state, loss = step(params, state, batch,
                                   jax.random.PRNGKey(0))
        losses.append(float(loss))
    stats = ds.routing_stats(trainer, params, batch)
    mesh_mod.set_mesh(None)
    return {"trainer": trainer, "params": params, "losses": losses,
            "text": text, "stats": stats}


def test_the_decoder_trains_through_the_one_trainer(tiny_lm):
    params, losses = tiny_lm["params"], tiny_lm["losses"]
    assert sorted(params) == ["dense_blocks", "embed", "expert_blocks",
                              "head"]
    assert params["dense_blocks"]["mlp.gate_up.weight"].shape == (1, 32, 128)
    assert params["expert_blocks"]["mlp.w_in"].shape == (2, 4, 32, 32)
    assert params["expert_blocks"]["mlp.router_weight"].shape == (2, 32, 16)
    assert set(params["head"]) == {"final_norm.weight", "lm_proj.weight"}
    assert all(np.isfinite(losses)) and losses[-1] < losses[0] - 0.1
    assert abs(losses[0] - math.log(96)) < 0.2   # untrained: uniform
    assert tiny_lm["trainer"].data_shardings().keys() == {"input_ids"}


def test_two_data_parallel_shards_read_the_same_losses(tiny_lm):
    if jax.device_count() < 2:
        pytest.skip("needs the virtual CPU mesh")
    cfg = ds.DeepseekV3Config(**TINY, held_experts=(4, 4))
    trainer, opt = build(ds.pretrain_model(cfg), dp=2)
    step = jax.jit(trainer.make_train_step(opt, compute_dtype=jnp.bfloat16))
    params = trainer.place_params(trainer.init_params())
    batch = {"input_ids": jax.device_put(
        np.random.default_rng(0).integers(1, 96, (2, 32)).astype(np.int32),
        trainer.data_shardings()["input_ids"])}
    state, losses = opt.init(params), []
    for _ in range(4):
        params, state, loss = step(params, state, batch,
                                   jax.random.PRNGKey(0))
        losses.append(float(loss))
    # the same seed, weights and batch: bf16 sums in another order
    np.testing.assert_allclose(losses, tiny_lm["losses"], rtol=2e-3)


def test_routing_stats_fill_the_moe_counters(tiny_lm):
    stats = tiny_lm["stats"]
    assert stats["pairs_routed"].tolist() == [2 * 32 * 3] * 2
    assert (stats["pairs_dropped"] == 0).all()
    assert (0 < stats["pairs_held"]).all() and \
        (stats["pairs_held"] < 2 * 32 * 3).all()
    reg = monitor.default_registry()
    # the rows the layer's passes run over: whole chunks that cover the held
    assert (stats["buffer_rows"] >= stats["pairs_held"]).all() and \
        (stats["buffer_rows"] <= stats["pairs_routed"]).all()
    for name in ("pairs_routed", "pairs_held", "held_load_max_over_mean",
                 "pairs_dropped", "buffer_rows"):
        samples = dict((l["layer"], v)
                       for l, v in reg.get(f"moe.{name}").samples())
        assert samples.keys() == {"0", "1"}
        assert samples["1"] == pytest.approx(float(stats[name][1]))


def test_every_instruction_of_the_step_lies_in_a_region(tiny_lm):
    """Coverage: what the compiled step's instructions carry as `op_name`
    maps to a region for all but the step's own plumbing."""
    paths = re.findall(r'op_name="([^"]*)"', tiny_lm["text"])
    regions = [xprof.step_region(p)[0] for p in paths if "/" in p]
    assert len(regions) > 200
    assert sum(r is not None for r in regions) / len(regions) >= 0.99
    assert set(xprof.REGIONS) <= set(regions)


@pytest.mark.parametrize("scope", ["ffn/router", "ffn/experts", "ffn/shared",
                                   "attn/proj", "attn/prep", "attn/core"])
def test_the_finer_scopes_are_in_the_step_forward_and_backward(tiny_lm, scope):
    paths = set(re.findall(r'op_name="([^"]*)"', tiny_lm["text"]))
    region, sub = scope.split("/")
    # a Layer attribute's own scope (`mlp`, `self_attn`) may lie between
    under = re.compile(rf"/{region}/(?:[\w.]+/)*?{sub}/")
    mine = [p for p in paths if under.search(p)]
    assert any("transpose(" in p for p in mine)
    assert any("transpose(" not in p for p in mine)


def test_no_layer_attribute_is_named_like_a_scope():
    cfg = ds.DeepseekV3Config(**TINY)
    model = ds.pretrain_model(cfg)
    taken = {r.split("/")[-1] for r in xprof.REGIONS} | {"attn"} | {
        c for children in xprof.SUBSCOPES.values() for c in children}
    assert {"proj", "prep", "dispatch", "products", "gated",
            "combine"} <= taken and "latent" not in taken
    layers = [model.embeddings, model.head] + [
        s.layers[0] for s in model.groups.values()]
    for layer in layers:
        for name, _ in layer.named_sublayers():
            assert not taken & set(name.split(".")), name


def test_more_than_one_group_does_not_pipeline():
    cfg = ds.DeepseekV3Config(**TINY)
    with pytest.raises(ValueError, match="pp > 1"):
        build(ds.pretrain_model(cfg), pp=2)


def test_a_description_takes_no_ernie_option():
    model = ds.pretrain_model(ds.DeepseekV3Config(**TINY))
    assert isinstance(model, PretrainModel)
    with pytest.raises(ValueError, match="moe_experts"):
        HybridPretrainer(model, moe_experts=4)
    bad = PretrainModel(model.embeddings, {"head": model.groups[
        "dense_blocks"]}, model.head, model.criterion, ("input_ids",))
    with pytest.raises(ValueError, match="named"):
        HybridPretrainer(bad)


# ---------------------------------------------------------------------------
# ERNIE is the first description: nothing of it moved
# ---------------------------------------------------------------------------
# read on the parent commit (4415295) with the same code, 2026-10-01
ERNIE_PARAMS = "e3f60e80d1e2c5edc8c20b56ddbbdc196c13b80656e988c9a4ff4cb5adda0811"
ERNIE_SHARDINGS = \
    "aec68b565aeaaf470623e4811910e6964888b66af3a248de7fb000a39296d4a9"
ERNIE_LOSSES = {1: [4.888332366943359, 4.420132637023926, 4.206201076507568],
                4: [4.888332366943359, 4.420136451721191, 4.206201553344727]}


@pytest.fixture(scope="module", params=[1, 4])
def ernie(request):
    dp = request.param
    if jax.device_count() < dp:
        pytest.skip("needs the virtual CPU mesh")
    cfg = ErnieConfig(vocab_size=64, hidden_size=32, num_hidden_layers=2,
                      num_attention_heads=2, intermediate_size=64,
                      max_position_embeddings=32, hidden_dropout_prob=0.0,
                      attention_probs_dropout_prob=0.0)
    trainer, opt = build(cfg, dp=dp)
    step = jax.jit(trainer.make_train_step(opt, compute_dtype=jnp.float32))
    params = trainer.init_params()
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    digest = hashlib.sha256()
    for path, v in flat:
        digest.update(jax.tree_util.keystr(path).encode())
        digest.update(np.asarray(v).tobytes())
    shardings = {jax.tree_util.keystr(p): str(s.spec) for p, s in
                 jax.tree_util.tree_flatten_with_path(
                     trainer.param_shardings(params))[0]}
    params = trainer.place_params(params)
    rng = np.random.default_rng(0)
    batch = {
        "input_ids": rng.integers(1, 64, (4, 16)).astype(np.int32),
        "token_type_ids": np.zeros((4, 16), np.int32),
        "masked_positions": np.argsort(rng.random((4, 16)), axis=1)[:, :3]
        .astype(np.int32),
        "mlm_labels": rng.integers(0, 64, (4, 3)).astype(np.int32),
        "nsp_labels": rng.integers(0, 2, (4,)).astype(np.int32)}
    dsh = trainer.data_shardings()
    batch = {k: jax.device_put(v, dsh[k]) for k, v in batch.items()}
    state, losses = opt.init(params), []
    for _ in range(3):
        params, state, loss = step(params, state, batch,
                                   jax.random.PRNGKey(0))
        losses.append(float(loss))
    mesh_mod.set_mesh(None)
    return {"dp": dp, "params": digest.hexdigest(), "n": len(flat),
            "shardings": hashlib.sha256(json.dumps(
                shardings, sort_keys=True).encode()).hexdigest(),
            "keys": list(dsh), "losses": losses, "trainer": trainer}


def test_ernie_parameters_are_the_parents(ernie):
    assert ernie["n"] == 30 and ernie["params"] == ERNIE_PARAMS


def test_ernie_shardings_are_the_parents(ernie):
    assert ernie["shardings"] == ERNIE_SHARDINGS
    assert ernie["keys"] == ["input_ids", "token_type_ids", "mlm_labels",
                             "nsp_labels", "masked_positions"]


def test_ernie_first_three_losses_are_the_parents(ernie):
    # the same program on the same backend: equal to float32 round-off
    np.testing.assert_allclose(ernie["losses"], ERNIE_LOSSES[ernie["dp"]],
                               rtol=2e-6)


def test_ernie_is_a_description_like_any_other(ernie):
    model = ernie["trainer"].model
    assert list(model.groups) == ["blocks"]
    assert model.tied == {"cls.predictions.decoder_weight":
                          "word_embeddings.weight"}
    assert model.embed_inputs == ("input_ids", "token_type_ids")
    assert model.head_inputs == ("masked_positions",)
