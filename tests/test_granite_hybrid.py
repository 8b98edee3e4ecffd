"""The Mamba-2/attention hybrid family (`text/granite_hybrid.py`) and what it
brought: the chunked state-space scan (`ops/ssd.py`) against the recurrence
itself, a Mamba-2 mixer under the `attn` region's `ssm` scope with its scan
under `ssd`, attention without positions at a scale that is not 1/sqrt(d),
scaled residuals, per-block recomputation through the trainer's strategy,
the run grouping shared with `text/lfm2_moe.py`."""
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import paddle_tpu
from paddle_tpu.nn import functional as F
from paddle_tpu.ops.ssd import state_space_scan
from paddle_tpu.optimizer import Adam
from paddle_tpu.parallel import mesh as mesh_mod
from paddle_tpu.parallel.fleet import DistributedStrategy, Fleet
from paddle_tpu.text import granite_hybrid as gh
from paddle_tpu.text import lfm2_moe as lm
from paddle_tpu.text.pretrainer import (HybridPretrainer, run_groups,
                                        runs_of_one_kind)
from paddle_tpu.utils import monitor, xprof

TINY = dict(vocab_size=96, hidden_size=32, num_hidden_layers=4,
            num_attention_heads=4, num_key_value_heads=2,
            shared_intermediate_size=48,
            layer_types=["mamba", "mamba", "attention", "mamba"],
            mamba_n_heads=4, mamba_d_head=16, mamba_d_state=8,
            mamba_chunk_size=8)
RUNS = ["run00_mamba", "run01_attention", "run02_mamba"]


@pytest.fixture(autouse=True)
def _reset_mesh():
    yield
    mesh_mod.set_mesh(None)


def key(i):
    return jax.random.fold_in(jax.random.PRNGKey(34), i)


# ---------------------------------------------------------------------------
# the scan: the chunked form against the recurrence itself
# ---------------------------------------------------------------------------
def recurrence(x, dt, A, B, C, D):
    """S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T, y_t = S_t C_t + D x_t, one
    position at a time."""
    b, s, h, p = x.shape

    def position(S, at):
        x_t, dt_t, B_t, C_t = at
        S = jnp.exp(dt_t * A)[..., None, None] * S \
            + (dt_t[..., None] * x_t)[..., None] * B_t[:, None, None, :]
        return S, jnp.einsum("bhpn,bn->bhp", S, C_t) + D[:, None] * x_t

    along = tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, B, C))
    _, y = lax.scan(position, jnp.zeros((b, h, p, B.shape[-1])), along)
    return jnp.moveaxis(y, 0, 1)


def scan_inputs(chunk_sum, s=48, b=2, h=3, p=4, n=5):
    """Inputs whose `dt·A` adds up to about `chunk_sum` over the sequence."""
    x, B, C = (jax.random.normal(key(i), shape) for i, shape in enumerate(
        [(b, s, h, p), (b, s, n), (b, s, n)]))
    D = 1.0 + 0.1 * jax.random.normal(key(3), (h,))
    A = -jnp.exp(0.1 * jax.random.normal(key(4), (h,)))
    dt = jax.nn.softplus(jax.random.normal(key(5), (b, s, h)))
    dt = dt * (-chunk_sum / s / jnp.mean(dt))
    return x, dt, A, B, C, D


@pytest.mark.parametrize("chunks", [1, 3, 8, 16])
@pytest.mark.parametrize("chunk_sum", [-0.3, -200.0])
def test_chunked_scan_is_the_recurrence(chunks, chunk_sum):
    """Values and the gradient of every input, at chunk lengths that cut 48
    positions into 1, 3, 8 and 16 chunks (the cell's count), with a chunk's
    decay sum near 0 and near -200 (where exp(cs_i)·exp(-cs_j) would be
    0·inf): finite and equal to the recurrence's to float32 round-off."""
    args = scan_inputs(chunk_sum * chunks)      # per chunk: about chunk_sum
    chunk = 48 // chunks
    per_chunk = float(jnp.mean(jnp.sum(
        (args[1] * args[2]).reshape(2, chunks, chunk, 3), axis=2)))
    assert per_chunk == pytest.approx(chunk_sum, rel=0.2)
    got, want = state_space_scan(*args, chunk), recurrence(*args)
    assert got.shape == want.shape == (2, 48, 3, 4)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    w = jax.random.normal(key(6), want.shape)
    every = tuple(range(6))
    g_got = jax.grad(lambda *a: jnp.sum(w * state_space_scan(*a, chunk)),
                     argnums=every)(*args)
    g_want = jax.grad(lambda *a: jnp.sum(w * recurrence(*a)),
                      argnums=every)(*args)
    for name, a, b in zip("x dt A B C D".split(), g_got, g_want):
        assert np.isfinite(np.asarray(a)).all(), name
        scale = float(jnp.max(jnp.abs(b))) or 1.0
        assert float(jnp.max(jnp.abs(a - b))) / scale < 1e-4, name


def test_scan_keeps_the_decay_in_float32_under_bfloat16_operands():
    """bf16 operands, float32 decay sums: the result stays within bf16
    rounding of the float32 recurrence where a bf16 running sum of 48 steps
    would not, and comes back in the operands' dtype."""
    x, dt, A, B, C, D = scan_inputs(-20.0)
    low = lambda t: t.astype(jnp.bfloat16)  # noqa: E731
    got = state_space_scan(low(x), dt, A, low(B), low(C), D, 16)
    assert got.dtype == jnp.bfloat16
    want = recurrence(low(x).astype(jnp.float32), dt, A,
                      low(B).astype(jnp.float32), low(C).astype(jnp.float32),
                      D)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
    assert err < 0.03 * float(jnp.max(jnp.abs(want)))


def test_scan_refuses_a_chunk_that_does_not_divide_and_counts_its_calls():
    args = scan_inputs(-1.0)
    with pytest.raises(ValueError, match="does not divide"):
        state_space_scan(*args, 5)
    calls = monitor.default_registry().get("ssm.scan_calls")
    before = dict((tuple(sorted(l.items())), n) for l, n in calls.samples())
    state_space_scan(*args, 12)
    after = dict((tuple(sorted(l.items())), n) for l, n in calls.samples())
    label = (("chunk", "12"), ("impl", "xla"))
    assert after[label] == before.get(label, 0) + 1


# ---------------------------------------------------------------------------
# the groups come from the pattern, by the function lfm2 uses
# ---------------------------------------------------------------------------
def test_the_published_forty_layers_are_nine_runs():
    cfg = gh.GraniteHybridConfig()
    assert len(cfg.layer_types) == 40
    assert [i for i, k in enumerate(cfg.layer_types)
            if k == "attention"] == [5, 15, 25, 35]
    assert runs_of_one_kind(cfg.layer_types) == [
        ("mamba", 5), ("attention", 1), ("mamba", 9), ("attention", 1),
        ("mamba", 9), ("attention", 1), ("mamba", 9), ("attention", 1),
        ("mamba", 4)]
    cut = gh.GraniteHybridConfig(num_hidden_layers=10)
    assert runs_of_one_kind(cut.layer_types) == [
        ("mamba", 5), ("attention", 1), ("mamba", 4)]
    assert list(gh.pretrain_model(gh.GraniteHybridConfig(
        **{**TINY, "num_hidden_layers": 10,
           "layer_types": cut.layer_types})).groups) == [
        "run00_mamba", "run01_attention", "run02_mamba"]


def test_both_hybrids_group_their_runs_with_one_function():
    assert runs_of_one_kind("aabccc") == [("a", 2), ("b", 1), ("c", 3)]
    assert runs_of_one_kind([]) == []
    from paddle_tpu import nn
    groups = run_groups(["x", "x", "y"], str.upper, lambda kind: nn.ReLU())
    assert list(groups) == ["run00_X", "run01_Y"]
    assert [len(g.layers) for g in groups.values()] == [2, 1]
    for module in (gh, lm):
        assert module.run_groups is run_groups
    assert lm.runs_of_one_kind is runs_of_one_kind
    model = gh.pretrain_model(gh.GraniteHybridConfig(**TINY))
    assert list(model.groups) == RUNS
    assert [len(g.layers) for g in model.groups.values()] == [2, 1, 1]


@pytest.mark.parametrize("over", [
    {"layer_types": ["mamba"] * 3}, {"layer_types": ["mamba", "conv"] * 2},
    {"num_local_experts": 8}, {"mamba_n_groups": 2},
    {"position_embedding_type": "rope"}, {"mamba_conv_bias": False},
    {"tie_word_embeddings": False}, {"mamba_expand": 4}])
def test_config_refuses_what_is_not_built(over):
    with pytest.raises(ValueError):
        gh.GraniteHybridConfig(**{**TINY, **over})


# ---------------------------------------------------------------------------
# the pieces
# ---------------------------------------------------------------------------
def test_convolution_is_the_published_depthwise_conv1d_with_bias():
    """Channels on lanes and four shifted multiply-adds against the
    published form: channels first, Conv1d(kernel 4, groups channels,
    padding 3, bias) cut to the first s outputs."""
    x = jax.random.normal(key(7), (2, 16, 80))
    taps = jax.random.normal(key(8), (4, 80))
    bias = jax.random.normal(key(9), (80,))
    got = gh.causal_depthwise_conv(x, taps, bias)
    want = lax.conv_general_dilated(
        x.transpose(0, 2, 1), taps.T[:, None, :], (1,), [(3, 0)],
        feature_group_count=80) + bias[:, None]
    np.testing.assert_allclose(got, want.transpose(0, 2, 1), atol=1e-5)
    via_paddle = F.conv1d(x.transpose(0, 2, 1), taps.T[:, None, :], bias,
                          padding=3, groups=80)[..., :16]
    np.testing.assert_allclose(got, via_paddle.transpose(0, 2, 1), atol=1e-5)
    # causal: moving position 9 moves outputs 9..12 alone
    moved = gh.causal_depthwise_conv(x.at[:, 9].add(1.0), taps, bias)
    changed = np.flatnonzero(np.abs(np.asarray(moved - got)).max((0, 2)))
    assert changed.tolist() == [9, 10, 11, 12]


def test_mixer_splits_its_projection_five_ways_in_the_published_order():
    """[z | xBC | dt] at 64 | 80 | 4 and [x | B | C] at 64 | 8 | 8: the mixer
    against its equations written out over the recurrence."""
    cfg = gh.GraniteHybridConfig(**TINY)
    layer = gh.Mamba2Mixer(cfg)
    assert layer.in_proj.weight.value.shape == (32, 64 + 80 + 4)
    assert layer.taps.value.shape == (4, 80)
    assert layer.taps_bias.value.shape == (80,)
    assert layer.gate_norm.weight.value.shape == (64,)
    assert layer.gate_norm.epsilon == 1e-5
    # values that a dropped leaf would show in
    layer.dt_bias.value = 0.5 * jax.random.normal(key(10), (4,))
    layer.a_log.value = 0.3 * jax.random.normal(key(11), (4,))
    layer.d_skip.value = 1.0 + 0.3 * jax.random.normal(key(12), (4,))
    layer.taps.value = 0.5 * jax.random.normal(key(13), (4, 80))
    layer.in_proj.weight.value = 0.3 * jax.random.normal(key(14), (32, 148))
    x = jax.random.normal(key(15), (2, 24, 32))
    proj = x @ layer.in_proj.weight.value
    z, xbc, dt = proj[..., :64], proj[..., 64:144], proj[..., 144:]
    xbc = jax.nn.silu(gh.causal_depthwise_conv(
        xbc, layer.taps.value, layer.taps_bias.value))
    u, B, C = xbc[..., :64], xbc[..., 64:72], xbc[..., 72:]
    y = recurrence(u.reshape(2, 24, 4, 16),
                   jax.nn.softplus(dt + layer.dt_bias.value),
                   -jnp.exp(layer.a_log.value), B, C, layer.d_skip.value)
    y = y.reshape(2, 24, 64) * jax.nn.silu(z)          # the gate first
    y = y / jnp.sqrt(jnp.mean(y * y, -1, keepdims=True) + 1e-5) \
        * layer.gate_norm.weight.value
    want = y @ layer.out_proj.weight.value
    np.testing.assert_allclose(layer(x), want, atol=2e-6, rtol=1e-4)


def test_attention_takes_the_flash_dispatch_with_grouped_keys_and_no_rotary(
        monkeypatch):
    """k and v reach `ops.attention.flash_attention` with their own two
    heads, causal, scaled by `attention_multiplier` (not 1/sqrt(8)), and
    exactly as the projection made them: no rotary, no norm."""
    from paddle_tpu.ops import attention as attn_ops
    seen = {}

    def spy(q, k, v, **kw):
        seen.update(q=q, k=k, v=v, **kw)
        return attn_ops.scaled_dot_product_attention(
            q, k, v, is_causal=kw["is_causal"], scale=kw["scale"])

    monkeypatch.setattr(attn_ops, "flash_attention", spy)
    cfg = gh.GraniteHybridConfig(**TINY, attention_multiplier=0.2)
    layer = gh.NopeAttention(cfg)
    x = jax.random.normal(key(16), (2, 16, 32))
    out = layer(x)
    assert out.shape == (2, 16, 32)
    assert (seen["q"].shape, seen["k"].shape, seen["v"].shape) == (
        (2, 4, 16, 8), (2, 2, 16, 8), (2, 2, 16, 8))
    assert seen["is_causal"] is True and seen["scale"] == 0.2
    assert seen["scale"] != 1 / math.sqrt(8)
    qkv = (x @ layer.qkv_proj.weight.value).reshape(2, 16, 8, 8)
    np.testing.assert_array_equal(seen["q"], qkv[:, :, :4].transpose(0, 2, 1, 3))
    np.testing.assert_array_equal(seen["k"], qkv[:, :, 4:6].transpose(0, 2, 1, 3))
    # no position anywhere: the same tokens in another order before the
    # last one give the last position the same output
    perm = jnp.concatenate([jnp.arange(15)[::-1], jnp.array([15])])
    np.testing.assert_allclose(layer(x[:, perm])[:, -1], out[:, -1],
                               atol=1e-6)
    names = {n for n, _ in layer.named_parameters()}
    assert names == {"qkv_proj.weight", "out_proj.weight"}


def test_every_norm_takes_the_configs_epsilon():
    from paddle_tpu import nn
    model = gh.pretrain_model(gh.GraniteHybridConfig(**TINY,
                                                     rms_norm_eps=3e-5))
    layers = [model.head] + [b for g in model.groups.values()
                             for b in g.layers]
    norms = [l for top in layers for l in top.sublayers()
             if isinstance(l, nn.RMSNorm)]
    assert len(norms) == 1 + 4 * 2 + 3          # + a gated norm a mixer
    assert all(n.epsilon == 3e-5 for n in norms)


# ---------------------------------------------------------------------------
# through the one trainer
# ---------------------------------------------------------------------------
def build(cfg, dp=1, recompute=False):
    """Seed, then draw the model's own initial values: the same weights
    whatever ran before."""
    strategy = DistributedStrategy()
    strategy.hybrid_configs.dp_degree = dp
    strategy.recompute = recompute
    strategy.recompute_configs.policy = None
    fleet = Fleet().init(strategy=strategy, devices=jax.devices()[:dp])
    paddle_tpu.seed(0)
    trainer = HybridPretrainer(gh.pretrain_model(cfg), mesh=fleet.mesh,
                               strategy=strategy)
    opt = fleet.distributed_optimizer(Adam(learning_rate=1e-3))
    return trainer, opt


def batch_of(trainer=None):
    ids = np.random.default_rng(0).integers(1, 96, (2, 32)).astype(np.int32)
    if trainer is None:
        return {"input_ids": jnp.asarray(ids)}
    return {"input_ids": jax.device_put(
        ids, trainer.data_shardings()["input_ids"])}


def train(trainer, opt, steps=4, batch=None, dtype=jnp.bfloat16):
    step = jax.jit(trainer.make_train_step(opt, compute_dtype=dtype))
    params = trainer.place_params(trainer.init_params())
    batch = batch_of() if batch is None else batch
    state, losses = opt.init(params), []
    for _ in range(steps):
        params, state, loss = step(params, state, batch,
                                   jax.random.PRNGKey(0))
        losses.append(float(loss))
    return step, params, losses


@pytest.fixture(scope="module")
def tiny_lm():
    """The tiny model with recomputation on, as the benchmark's cell runs
    it: trained four steps in bf16, its compiled step's text kept."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    try:
        trainer, opt = build(gh.GraniteHybridConfig(**TINY), recompute=True)
        params = trainer.place_params(trainer.init_params())
        shapes = jax.tree_util.tree_map(lambda x: x.shape, params)
        step, params, losses = train(trainer, opt)
        text = step.lower(params, opt.init(params), batch_of(),
                          jax.random.PRNGKey(0)).compile().as_text()
    finally:
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          False)
        mesh_mod.set_mesh(None)
    return {"trainer": trainer, "params": params, "shapes": shapes,
            "losses": losses, "text": text}


def test_the_hybrid_trains_through_the_one_trainer(tiny_lm):
    shapes, losses = tiny_lm["shapes"], tiny_lm["losses"]
    assert tiny_lm["trainer"].recompute is True
    assert tiny_lm["trainer"].recompute_policy is None
    assert sorted(shapes) == sorted(RUNS + ["embed", "head"])
    assert shapes["embed"] == {"word_embeddings.weight": (96, 32)}
    assert shapes["head"] == {"final_norm.weight": (32,)}      # tied
    ffn = {"input_norm.weight": (32,), "post_norm.weight": (32,),
           "mlp.gate_up.weight": (32, 96), "mlp.down.weight": (48, 32)}
    mamba = {**ffn, "mixer.in_proj.weight": (32, 148),
             "mixer.taps": (4, 80), "mixer.taps_bias": (80,),
             "mixer.dt_bias": (4,), "mixer.a_log": (4,),
             "mixer.d_skip": (4,), "mixer.gate_norm.weight": (64,),
             "mixer.out_proj.weight": (64, 32)}
    assert shapes["run00_mamba"] == {k: (2,) + v for k, v in mamba.items()}
    assert shapes["run02_mamba"] == {k: (1,) + v for k, v in mamba.items()}
    assert shapes["run01_attention"] == {
        k: (1,) + v for k, v in {**ffn, "mixer.qkv_proj.weight": (32, 64),
                                 "mixer.out_proj.weight": (32, 32)}.items()}
    assert all(np.isfinite(losses)) and losses[-1] < losses[0] - 0.005
    assert abs(losses[0] - math.log(96)) < 0.2   # untrained: uniform
    assert tiny_lm["trainer"].data_shardings().keys() == {"input_ids"}


def test_first_three_losses_are_pinned(tiny_lm):
    """bf16 compute on the CPU backend from `paddle_tpu.seed(0)`.  The logits
    are divided by 8 and the branches by 4.5, so a step moves the loss by
    0.005: the pin is a fifth of that, where a reordered sum moves the
    fifth digit."""
    assert tiny_lm["losses"][:3] == pytest.approx(PINNED_LOSSES, abs=1e-3)


def test_recompute_on_and_off_give_the_same_losses_and_gradients(tiny_lm):
    """float32: the rematerialised backward is the same arithmetic."""
    cfg, batch = gh.GraniteHybridConfig(**TINY), batch_of()
    out = {}
    for recompute in (False, True):
        trainer, opt = build(cfg, recompute=recompute)
        assert trainer.recompute is recompute
        params = trainer.place_params(trainer.init_params())
        grads = jax.jit(jax.grad(lambda p: trainer.loss_fn(
            p, batch, jax.random.PRNGKey(0))))(params)
        _, _, losses = train(trainer, opt, dtype=jnp.float32)
        out[recompute] = (losses, grads)
        mesh_mod.set_mesh(None)
    np.testing.assert_allclose(out[True][0], out[False][0], rtol=1e-6)
    flat = lambda g: jax.tree_util.tree_leaves_with_path(g)  # noqa: E731
    for (path, a), (_, b) in zip(flat(out[True][1]), flat(out[False][1])):
        scale = float(jnp.max(jnp.abs(b))) or 1.0
        assert float(jnp.max(jnp.abs(a - b))) / scale < 1e-5, path
    # and the wrap is there: the compiled backward runs the scan again
    assert "checkpoint" in tiny_lm["text"] or "rematted" in tiny_lm["text"]


def test_two_data_parallel_shards_read_the_same_losses(tiny_lm):
    if jax.device_count() < 2:
        pytest.skip("needs the virtual CPU mesh")
    trainer, opt = build(gh.GraniteHybridConfig(**TINY), dp=2,
                         recompute=True)
    _, _, losses = train(trainer, opt, batch=batch_of(trainer))
    # the same seed, weights and batch: bf16 sums in another order
    np.testing.assert_allclose(losses, tiny_lm["losses"], rtol=2e-3)


def loss_of(cfg, poke=None):
    """The tiny model's loss on one batch, in float32, from weights at the
    scale of a trained model's: every matrix ten times its initial 0.02 and
    the state-space leaves drawn O(1), so that none of the small leaves and
    scalars is lost in rounding."""
    trainer, _ = build(cfg)
    params = jax.tree_util.tree_map(
        lambda v: 10.0 * v if v.ndim >= 2 and v.shape[-1] > 4 else v,
        trainer.init_params())
    for i, group in enumerate(("run00_mamba", "run02_mamba")):
        blk = dict(params[group])
        for j, name in enumerate(("mixer.dt_bias", "mixer.a_log")):
            blk[name] = 0.5 * jax.random.normal(key(40 + 2 * i + j),
                                                blk[name].shape)
        blk["mixer.d_skip"] = 1.0 + 0.5 * jax.random.normal(
            key(50 + i), blk["mixer.d_skip"].shape)
        params[group] = blk
    if poke:
        params = poke(params)
    loss = trainer.loss_fn(trainer.place_params(params), batch_of(),
                           jax.random.PRNGKey(0))
    mesh_mod.set_mesh(None)
    return float(loss)


@pytest.mark.parametrize("dropped", [
    "embedding_multiplier", "residual_multiplier", "logits_scaling",
    "attention_multiplier", "d_skip", "dt_bias", "gate"])
def test_dropping_a_scalar_or_a_small_leaf_changes_the_loss(
        dropped, monkeypatch):
    """Each is a scalar or a small leaf that a step could lose unnoticed:
    the loss without it is another loss."""
    base_cfg = gh.GraniteHybridConfig(**TINY)
    base = loss_of(base_cfg)
    neutral = {"embedding_multiplier": 1.0, "residual_multiplier": 1.0,
               "logits_scaling": 1.0,
               "attention_multiplier": 1 / math.sqrt(8)}
    if dropped in neutral:
        other = loss_of(gh.GraniteHybridConfig(
            **TINY, **{dropped: neutral[dropped]}))
    elif dropped == "gate":
        monkeypatch.setattr(gh.F, "silu", lambda t: jnp.ones_like(t)
                            if t.shape[-1] == 64 else jax.nn.silu(t))
        other = loss_of(base_cfg)
    else:
        fill = {"d_skip": 0.0, "dt_bias": 0.0}[dropped]

        def poke(params):
            for group in ("run00_mamba", "run02_mamba"):
                params[group] = dict(params[group])
                leaf = params[group][f"mixer.{dropped}"]
                params[group][f"mixer.{dropped}"] = jnp.full_like(leaf, fill)
            return params

        other = loss_of(base_cfg, poke)
    assert np.isfinite(other) and abs(other - base) > 1e-4, (base, other)


def test_the_tied_head_reads_the_embedding_and_both_uses_train_it(tiny_lm):
    trainer, params = tiny_lm["trainer"], tiny_lm["params"]
    assert trainer.model.tied == {"lm_weight": "word_embeddings.weight"}
    batch = batch_of()
    g = jax.grad(lambda p: trainer.loss_fn(p, batch, jax.random.PRNGKey(0)))(
        params)
    table = np.asarray(g["embed"]["word_embeddings.weight"])
    unseen = np.setdiff1d(np.arange(96),
                          np.unique(np.asarray(batch["input_ids"])))
    # rows no token looked up still get the logits' gradient
    assert unseen.size and np.abs(table[unseen]).max() > 0
    assert set(trainer.init_params()["head"]) == {"final_norm.weight"}
    # every state-space leaf takes a gradient
    for name in ("mixer.dt_bias", "mixer.a_log", "mixer.d_skip",
                 "mixer.taps_bias", "mixer.gate_norm.weight"):
        assert np.abs(np.asarray(g["run00_mamba"][name])).max() > 0, name


@pytest.mark.parametrize("scope", [
    "attn/ssm", "attn/ssm/ssd", "attn/core"])
def test_the_compiled_step_carries_the_scopes(tiny_lm, scope):
    paths = set(re.findall(r'op_name="([^"]*)"', tiny_lm["text"]))
    # a Layer attribute's own scope (`mixer`) may lie between
    under = re.compile("/" + r"/(?:[\w.]+/)*?".join(scope.split("/")) + "/")
    mine = [p for p in paths if under.search(p)]
    assert any("transpose(" in p for p in mine), scope
    assert any("transpose(" not in p for p in mine), scope


def test_the_mixers_products_lie_under_ssm_and_the_scans_under_ssd(tiny_lm):
    paths = set(re.findall(r'op_name="([^"]*)"', tiny_lm["text"]))
    ssm = [p for p in paths if re.search(r"/attn/(?:[\w.]+/)*?ssm/", p)]
    assert any("dot_general" in p and "/ssd/" not in p for p in ssm)
    assert any("dot_general" in p and "/ssd/" in p for p in ssm)
    # the state passes from chunk to chunk in one product, not in a loop
    assert not [p for p in ssm
                if "/ssd/" in p and "while" in p.split("/ssd/")[1]]
    # nothing of the scan lies outside the mixer's scope, nothing of it is
    # attention's core, and `scan` stays the block stack's own
    assert not [p for p in paths if "/ssd/" in p and "/ssm/" not in p]
    assert not [p for p in paths if "/ssm/" in p and "/core/" in p]


def test_no_layer_attribute_is_named_like_a_scope():
    """Shared by both hybrids: with `xprof_scopes` on an attribute's name is
    a scope, and a region reader would take it for one."""
    taken = {r.split("/")[-1] for r in xprof.REGIONS} | {"attn", "scan"} | {
        c for children in xprof.SUBSCOPES.values() for c in children}
    assert (xprof.SCOPE_SSM, xprof.SCOPE_SSD) == ("ssm", "ssd")
    assert {"ssm", "ssd", "proj", "prep", "pointwise"} <= taken \
        and "latent" not in taken
    for model in (gh.pretrain_model(gh.GraniteHybridConfig(**TINY)),
                  lm.pretrain_model(lm.Lfm2MoeConfig(
                      vocab_size=96, hidden_size=64, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=2,
                      intermediate_size=96, moe_intermediate_size=32,
                      num_experts=8, num_experts_per_tok=2,
                      num_dense_layers=1,
                      layer_types=["conv", "full_attention"]))):
        layers = [model.embeddings, model.head] + [
            s.layers[0] for s in model.groups.values()]
        for layer in layers:
            for name, _ in layer.named_sublayers():
                assert not taken & set(name.split(".")), name
            for name, _ in layer.named_parameters():
                assert not taken & set(name.split(".")), name
        assert not taken & set(model.groups)


def test_residual_block_emits_no_multiply_without_a_multiplier():
    """The blocks of the two families that share `residual_block` lower as
    they did: the jaxpr of an unscaled block has no `mul` by a scalar of its
    own, a scaled one has two."""
    from paddle_tpu.text.deepseek_v3 import residual_block
    ident = lambda t: t  # noqa: E731
    x = jnp.ones((2, 4, 8))
    plain = jax.make_jaxpr(lambda t: residual_block(
        t, ident, ident, ident, ident))(x)
    scaled = jax.make_jaxpr(lambda t: residual_block(
        t, ident, ident, ident, ident, residual_multiplier=0.22))(x)
    count = lambda j: sum(e.primitive.name == "mul" for e in j.eqns)  # noqa: E731
    assert count(plain) == 0 and count(scaled) == 2
    np.testing.assert_allclose(
        residual_block(x, ident, ident, ident, ident,
                       residual_multiplier=0.5), 2.25 * x)


PINNED_LOSSES = [4.56535, 4.56063, 4.55587]
