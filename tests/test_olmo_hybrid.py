"""The Gated DeltaNet / attention hybrid family (`text/olmo_hybrid.py`) and
what it brought: the chunked gated delta rule (`ops/delta_rule.py`) against
the recurrence itself, token by token, in values and in every gradient; a
float32 state that a bfloat16 one cannot stand in for; the mixer under the
`attn` region's `gdn` scope with its rule under `delta`; attention with
RMSNorm over the whole q and k projections and no positions; the norm after
each sublayer; one group a layer; and the whole model against the
benchmark's plain float32 reference (`references/olmo_hybrid_lm.py`) over
three Adam steps."""
import importlib.util
import math
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from paddle_tpu.ops import delta_rule
from paddle_tpu.ops.delta_rule import gated_delta_rule, unit_lower_inverse
from paddle_tpu.optimizer import Adam
from paddle_tpu.parallel import mesh as mesh_mod
from paddle_tpu.parallel.fleet import DistributedStrategy, Fleet
from paddle_tpu.text import olmo_hybrid as oh
from paddle_tpu.text.pretrainer import HybridPretrainer
from paddle_tpu.utils import monitor, xprof

REPO = pathlib.Path(__file__).resolve().parents[1]
TINY = dict(vocab_size=96, hidden_size=32, intermediate_size=48,
            num_hidden_layers=4, num_attention_heads=4,
            num_key_value_heads=4, linear_num_key_heads=2,
            linear_num_value_heads=2, linear_key_head_dim=8,
            linear_value_head_dim=16, linear_chunk_size=8)
GROUPS = ["run00_linear_attention", "run01_linear_attention",
          "run02_linear_attention", "run03_full_attention"]


@pytest.fixture(autouse=True)
def _reset_mesh():
    yield
    mesh_mod.set_mesh(None)


def key(i):
    return jax.random.fold_in(jax.random.PRNGKey(43), i)


def reference():
    path = REPO / "benchmarks/references/olmo_hybrid_lm.py"
    spec = importlib.util.spec_from_file_location("_olmo_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# the rule: the chunked form against the recurrence itself
# ---------------------------------------------------------------------------
def recurrence(q, k, v, g, beta):
    """S_t = exp(g_t)(I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T,
    o_t = S_t^T q_t / sqrt(dk), one position at a time."""
    b, s, h, dk = q.shape

    def position(S, at):
        q_t, k_t, v_t, g_t, b_t = at
        eye = jnp.eye(dk)
        move = eye - b_t[..., None, None] * k_t[..., :, None] \
            * k_t[..., None, :]
        S = jnp.exp(g_t)[..., None, None] * jnp.einsum(
            "bhij,bhjv->bhiv", move, S, precision=lax.Precision.HIGHEST) \
            + b_t[..., None, None] * k_t[..., :, None] * v_t[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t,
                             precision=lax.Precision.HIGHEST) / math.sqrt(dk)

    along = tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta))
    _, o = lax.scan(position, jnp.zeros((b, h, dk, v.shape[-1])), along)
    return jnp.moveaxis(o, 0, 1)


def rule_inputs(beta_at, decay, s=48, b=2, h=3, dk=8, dv=12):
    """Unit keys and queries (as the mixer's L2 norm makes them), beta about
    `beta_at`, and a log decay of about `decay` a position."""
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(key(0), (b, s, h, dk)))
    k = unit(jax.random.normal(key(1), (b, s, h, dk)))
    v = jax.random.normal(key(2), (b, s, h, dv))
    g = decay * jax.nn.softplus(jax.random.normal(key(3), (b, s, h))) / 0.69
    spread = 0.04 * jax.nn.sigmoid(jax.random.normal(key(4), (b, s, h)))
    beta = beta_at + (spread if beta_at < 1 else -spread)
    return q, k, v, g, beta


@pytest.mark.parametrize("chunks", [1, 3, 16])
@pytest.mark.parametrize("beta_at", [0.02, 1.98], ids=["beta~0", "beta~2"])
@pytest.mark.parametrize("decay", [-0.02, -4.0], ids=["gentle", "steep"])
def test_chunked_rule_is_the_recurrence(chunks, beta_at, decay):
    """Values and the gradient of every input at chunk lengths that cut 48
    positions into 1, 3 and 16 chunks, with beta near 0 and near 2 (the
    transition's eigenvalue 1 - beta near -1) and a decay that keeps the
    state across the sequence or forgets it within a few positions (a
    chunk's decay sum down to -190): finite and the recurrence's to float32
    round-off."""
    args = rule_inputs(beta_at, decay)
    chunk = 48 // chunks
    got, want = gated_delta_rule(*args, chunk), recurrence(*args)
    assert got.shape == want.shape == (2, 48, 3, 12)
    assert np.isfinite(np.asarray(got)).all()
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5 * scale
    w = jax.random.normal(key(6), want.shape)
    every = tuple(range(5))
    g_got = jax.grad(lambda *a: jnp.sum(w * gated_delta_rule(*a, chunk)),
                     argnums=every)(*args)
    g_want = jax.grad(lambda *a: jnp.sum(w * recurrence(*a)),
                      argnums=every)(*args)
    for name, a, b in zip("q k v g beta".split(), g_got, g_want):
        assert np.isfinite(np.asarray(a)).all(), name
        scale = float(jnp.max(jnp.abs(b))) or 1.0
        # the worst entry against the largest: float32 round-off through a
        # solve and 48 transitions taken in another order
        assert float(jnp.max(jnp.abs(a - b))) / scale < 5e-5, name


def test_the_state_reaches_across_chunks():
    """Under the gentle decay the last position still reads the first,
    through 15 passings of the state from chunk to chunk."""
    q, k, v, g, beta = rule_inputs(0.5, -0.02)
    base = gated_delta_rule(q, k, v, g, beta, 3)
    moved = gated_delta_rule(q, k, v.at[:, 0].add(1.0), g, beta, 3)
    assert float(jnp.abs(moved[:, -1] - base[:, -1]).max()) > 1e-3


def test_a_bfloat16_state_fails_the_tolerance(monkeypatch):
    """The carried state is float32 for a reason: rounded to bfloat16 after
    every chunk, it misses the recurrence by far more than the tolerance
    above, on the inputs that pass it."""
    args = rule_inputs(1.0, -0.02)
    want = recurrence(*args)
    scale = float(jnp.max(jnp.abs(want)))
    exact = float(jnp.max(jnp.abs(gated_delta_rule(*args, 3) - want)))
    monkeypatch.setattr(delta_rule, "STATE_DTYPE", jnp.bfloat16)
    rounded = float(jnp.max(jnp.abs(gated_delta_rule(*args, 3) - want)))
    assert exact < 2e-5 * scale < 2e-3 * scale < rounded


@pytest.mark.parametrize("size", [1, 5, 8, 64])
def test_the_solve_is_exact_where_the_series_cancels(size):
    """(I + A)^-1 of a strictly lower A whose keys are all alike and beta
    near 2: entries of A near 2, where the nilpotent series sums powers of
    A that reach 2.5e28 at 64 rows to an inverse of entries under 2.  The
    doubling solve stays within float32 of the float64 inverse, and its
    padding to a power of two leaves the size."""
    k = jnp.ones((size, 8)) + 0.05 * jax.random.normal(key(7), (size, 8))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    a = jnp.tril(1.95 * (k @ k.T), -1)
    got = np.asarray(unit_lower_inverse(a[None]))[0]
    a64 = np.asarray(a, np.float64)
    want = np.linalg.inv(np.eye(size) + a64)
    assert got.shape == (size, size)
    np.testing.assert_allclose(got, want, atol=1e-5 * np.abs(want).max())
    power, largest = np.eye(size), 0.0
    for _ in range(size):
        power = power @ a64
        largest = max(largest, np.abs(power).max())
    assert np.abs(want).max() < 2 and largest >= (0 if size < 64 else 1e20)


def test_rule_keeps_its_sums_in_float32_under_bfloat16_operands():
    """bf16 operands: the result within bf16 rounding of the float32
    recurrence, back in the operands' dtype."""
    q, k, v, g, beta = rule_inputs(1.0, -0.3)
    low = lambda t: t.astype(jnp.bfloat16)  # noqa: E731
    got = gated_delta_rule(low(q), low(k), low(v), g, beta, 16)
    assert got.dtype == jnp.bfloat16
    want = recurrence(*(low(t).astype(jnp.float32) for t in (q, k, v)), g,
                      beta)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
    assert err < 0.03 * float(jnp.max(jnp.abs(want)))


def test_rule_refuses_a_chunk_that_does_not_divide_and_counts_its_passes():
    args = rule_inputs(1.0, -0.3)
    with pytest.raises(ValueError, match="does not divide"):
        gated_delta_rule(*args, 5)
    calls = monitor.default_registry().get("gdn.delta_calls")

    def now():
        return {tuple(sorted(lb.items())): n for lb, n in calls.samples()}

    before = now()
    jax.grad(lambda q: jnp.sum(gated_delta_rule(q, *args[1:], 12)))(args[0])
    after = now()
    for p in ("fwd", "bwd"):
        label = (("chunk", "12"), ("pass", p))
        assert after[label] == before.get(label, 0) + 1, p


# ---------------------------------------------------------------------------
# the configuration and the layers
# ---------------------------------------------------------------------------
def test_the_published_pattern_and_one_group_a_layer():
    cfg = oh.OlmoHybridConfig()
    assert len(cfg.layer_types) == 32
    assert [i for i, t in enumerate(cfg.layer_types)
            if t == oh.FULL] == [3, 7, 11, 15, 19, 23, 27, 31]
    model = oh.pretrain_model(oh.OlmoHybridConfig(**TINY))
    assert list(model.groups) == GROUPS
    assert all(len(g.layers) == 1 for g in model.groups.values())
    assert not model.tied


@pytest.mark.parametrize("over", [
    {"attention_bias": True}, {"tie_word_embeddings": True},
    {"rope_theta": 10000.0}, {"hidden_act": "gelu"},
    {"linear_num_value_heads": 4}, {"num_key_value_heads": 3},
    {"linear_allow_neg_eigval": False},
    {"layer_types": ["linear_attention"] * 3 + ["mamba"]}])
def test_config_refuses_what_is_not_built(over):
    with pytest.raises(ValueError):
        oh.OlmoHybridConfig(**{**TINY, **over})


def layer_params(layer, seed=0):
    """Every leaf of a Layer redrawn O(1) from a seed (norm weights about
    1), so that each shows."""
    from paddle_tpu.autograd import parameters_dict
    out = {}
    for i, (name, p) in enumerate(sorted(parameters_dict(layer).items())):
        x = jax.random.normal(key(100 * seed + i), p.shape)
        out[name] = 1.0 + 0.1 * x if name.endswith("norm.weight") \
            else 0.3 * x
    return out


def test_mixer_splits_its_projection_six_ways_in_the_published_order():
    """q | k | v | gate | a | b: a change in one slice of W_in moves only
    what that slice feeds (the gate and beta leave the rule's inputs, the
    decay's a slice too)."""
    from paddle_tpu.autograd import functional_call
    cfg = oh.OlmoHybridConfig(**TINY)
    mixer = oh.GatedDeltaNet(cfg)
    p = layer_params(mixer)
    assert p["in_proj.weight"].shape == (32, 16 + 16 + 32 + 32 + 2 + 2)
    assert p["taps"].shape == (4, 64) and p["out_norm.weight"].shape == (16,)
    x = jax.random.normal(key(9), (2, 16, 32))
    base = functional_call(mixer, p, (x,))
    for lo, hi in [(0, 16), (16, 32), (32, 64), (64, 96), (96, 98),
                   (98, 100)]:
        w = p["in_proj.weight"].at[:, lo:hi].add(0.5)
        moved = functional_call(mixer, {**p, "in_proj.weight": w}, (x,))
        assert float(jnp.abs(moved - base).max()) > 1e-4, (lo, hi)


def test_attention_norms_the_whole_projection_and_has_no_positions():
    """q_norm and k_norm hold one weight over all heads; with no positional
    term the last query's output is the same whatever order the earlier
    positions come in."""
    from paddle_tpu.autograd import functional_call
    cfg = oh.OlmoHybridConfig(**TINY)
    attn = oh.NormedNopeAttention(cfg)
    p = layer_params(attn)
    assert p["q_norm.weight"].shape == p["k_norm.weight"].shape == (32,)
    x = jax.random.normal(key(10), (1, 16, 32))
    order = jnp.concatenate([jax.random.permutation(key(11), 15),
                             jnp.array([15])])
    a = functional_call(attn, p, (x,))
    b = functional_call(attn, p, (x[:, order],))
    np.testing.assert_allclose(a[:, -1], b[:, -1], atol=1e-5)
    assert float(jnp.abs(a[:, 3] - b[:, 3]).max()) > 1e-3


def test_the_norm_comes_after_each_sublayer():
    """y = h + N2(ffn(h)), h = x + N1(mixer(x)): with identity sublayers
    and norms that double, y = x + 2x + 2(3x)."""
    double = lambda t: 2.0 * t  # noqa: E731
    x = jnp.arange(6.0).reshape(1, 2, 3)
    y = oh.norm_after_block(x, lambda t: t, double, lambda t: t, double)
    np.testing.assert_allclose(y, 9.0 * x)


# ---------------------------------------------------------------------------
# layer by layer and the whole step against the plain reference
# ---------------------------------------------------------------------------
def tiny_model():
    return {**{k: v for k, v in TINY.items()}, "head_dim": 8,
            "layer_types": oh.published_layer_types(4),
            "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
            "attention_bias": False, "hidden_act": "silu",
            "rope_theta": None, "tie_word_embeddings": False,
            "rms_norm_eps": 1e-6, "initializer_range": 0.02,
            "type_vocab_size": 1}


def mm32(spec, a, b):
    return reference()._mm(spec, a, b, "float32")


@pytest.mark.parametrize("mixer", [oh.LINEAR, oh.FULL])
def test_block_and_its_gradient_are_the_references(mixer):
    """One block with O(1) weights (the norms after the sublayers make any
    scale of the branches count) against the reference's `_block`."""
    from paddle_tpu.autograd import functional_call
    cfg, m = oh.OlmoHybridConfig(**TINY), tiny_model()
    block = oh.OlmoHybridBlock(cfg, mixer)
    p = layer_params(block, seed=3)
    x = jax.random.normal(key(12), (2, 32, 32))

    def program(p, x):
        return jnp.sum(jnp.square(functional_call(block, p, (x,))))

    def plain(p, x):
        return jnp.sum(jnp.square(reference()._block(x, p, m, "float32",
                                                     mixer=mixer)))

    a, ga = jax.jit(jax.value_and_grad(program, argnums=(0, 1)))(p, x)
    b, gb = jax.jit(jax.value_and_grad(plain, argnums=(0, 1)))(p, x)
    assert float(a) == pytest.approx(float(b), rel=1e-5)
    assert set(ga[0]) == set(gb[0])
    for name in gb[0]:
        # the worst entry against the leaf's largest: float32 round-off
        # through three norms and a rule of 32 positions in another order
        scale = float(jnp.max(jnp.abs(gb[0][name])))
        assert scale > 0, name
        assert float(jnp.max(jnp.abs(ga[0][name] - gb[0][name]))) \
            < 1e-4 * scale, name
    assert float(jnp.max(jnp.abs(ga[1] - gb[1]))) < 1e-4 * float(
        jnp.max(jnp.abs(gb[1])))


def build(recompute=True):
    strategy = DistributedStrategy()
    strategy.hybrid_configs.dp_degree = 1
    strategy.recompute = recompute
    strategy.recompute_configs.policy = None
    fleet = Fleet().init(strategy=strategy, devices=jax.devices()[:1])
    trainer = HybridPretrainer(oh.pretrain_model(oh.OlmoHybridConfig(**TINY)),
                               mesh=fleet.mesh, strategy=strategy)
    opt = fleet.distributed_optimizer(Adam(learning_rate=1e-3))
    return trainer, opt


def batches(n=3):
    rng = np.random.default_rng(43)
    return [{"input_ids": rng.integers(1, 96, (2, 32)).astype(np.int32)}
            for _ in range(n)]


@pytest.fixture(scope="module")
def against_reference():
    """The program (float32, every block recomputed) and the reference over
    three Adam steps from one seeded draw in the reference's layout."""
    import benchmarks.harness.weights as weights
    ref, m = reference(), tiny_model()
    params = weights.maker(ref.param_spec(m))(weights.seed_key(4300))
    opt_cfg = {"lr": 1e-3, "beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8}
    want = ref.run(m, opt_cfg, params, batches(), devices=jax.devices()[:1],
                   rows_per_block=2)
    trainer, opt = build()
    step = jax.jit(trainer.make_train_step(opt, compute_dtype=jnp.float32))
    p, losses = trainer.place_params(params), []
    state = opt.init(p)
    first_grad = None
    for n, b in enumerate(batches()):
        if n == 0:
            first_grad = jax.jit(jax.grad(lambda q: trainer.loss_fn(
                q, b, jax.random.PRNGKey(0))))(p)
        p, state, loss = step(p, state, b, jax.random.PRNGKey(0))
        losses.append(float(loss))
    change = jax.tree_util.tree_map(jnp.subtract, p, params)
    mesh_mod.set_mesh(None)
    return {"losses": losses, "first_grad": first_grad, "change": change,
            "want": want}


def rel(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    num = sum(float(jnp.sum(jnp.square(jnp.asarray(x) - jnp.asarray(y))))
              for x, y in zip(la, lb))
    return math.sqrt(num / sum(float(jnp.sum(jnp.square(jnp.asarray(y))))
                               for y in lb))


def test_the_step_is_the_references_over_three_adam_steps(against_reference):
    """Loss (float32 round-off, 1e-5 relative), the first gradient (all
    leaves together within 1e-4 of the reference's norm: sums over 64
    positions and a chunked solve in another order) and the parameters'
    change over three Adam steps (1e-3: Adam divides by the root of the
    second moment, which magnifies round-off where a gradient is small)."""
    got, want = against_reference, against_reference["want"]
    assert got["losses"] == pytest.approx(want["losses"], rel=1e-5)
    assert abs(got["losses"][0] - math.log(96)) < 0.2
    assert rel(got["first_grad"], want["first_grad"]) < 1e-4
    assert rel(got["change"], want["param_change"]) < 1e-3
    for path, leaf in jax.tree_util.tree_leaves_with_path(got["change"]):
        assert np.asarray(leaf).any(), path


@pytest.fixture(scope="module")
def compiled_text():
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    try:
        trainer, opt = build()
        step = jax.jit(trainer.make_train_step(opt,
                                               compute_dtype=jnp.bfloat16))
        params = trainer.place_params(trainer.init_params())
        return step.lower(params, opt.init(params), batches(1)[0],
                          jax.random.PRNGKey(0)).compile().as_text()
    finally:
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          False)
        mesh_mod.set_mesh(None)


@pytest.mark.parametrize("scope", ["attn/gdn", "attn/gdn/delta", "attn/core"])
def test_the_compiled_step_carries_the_scopes(compiled_text, scope):
    paths = set(re.findall(r'op_name="([^"]*)"', compiled_text))
    under = re.compile("/" + r"/(?:[\w.]+/)*?".join(scope.split("/")) + "/")
    mine = [p for p in paths if under.search(p)]
    assert any("transpose(" in p for p in mine), scope
    assert any("transpose(" not in p for p in mine), scope


def test_the_mixers_products_lie_under_gdn_and_the_rules_under_delta(
        compiled_text):
    paths = set(re.findall(r'op_name="([^"]*)"', compiled_text))
    gdn = [p for p in paths if re.search(r"/attn/(?:[\w.]+/)*?gdn/", p)]
    assert any("dot_general" in p and "/delta/" not in p for p in gdn)
    assert any("dot_general" in p and "/delta/" in p for p in gdn)
    assert not [p for p in paths if "/delta/" in p and "/gdn/" not in p]
    assert not [p for p in paths if "/gdn/" in p and "/core/" in p]


def test_no_layer_attribute_is_named_like_a_scope():
    taken = {r.split("/")[-1] for r in xprof.REGIONS} | {"attn", "scan"} | {
        c for children in xprof.SUBSCOPES.values() for c in children} | {
        xprof.SCOPE_GDN, xprof.SCOPE_DELTA}
    assert (xprof.SCOPE_GDN, xprof.SCOPE_DELTA) == ("gdn", "delta")
    model = oh.pretrain_model(oh.OlmoHybridConfig(**TINY))
    for layer in [model.embeddings, model.head] + [
            g.layers[0] for g in model.groups.values()]:
        for name, _ in layer.named_sublayers():
            assert not taken & set(name.split(".")), name
        for name, _ in layer.named_parameters():
            assert not taken & set(name.split(".")), name
