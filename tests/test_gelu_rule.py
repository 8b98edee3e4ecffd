"""GELU's value and derivative rule (`nn/functional/activation.py`) and how
the transformer FFN calls it (`nn/layer/transformer._ffn`): against
`jax.nn.gelu` in float32, the primitives a training step traces per call
(one erf or tanh, one exp, no erfc), what the backward keeps, and that
forward mode, vmap, jax.checkpoint and second order still compose."""
import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax._src.ad_checkpoint import saved_residuals

from paddle_tpu import nn
from paddle_tpu.autograd import functional_call, parameters_dict
from paddle_tpu.nn import functional as F
from paddle_tpu.nn.layer.transformer import _ffn

FORMS = [pytest.param(False, id="exact"), pytest.param(True, id="tanh")]
DTYPES = [pytest.param(jnp.float32, id="float32"),
          pytest.param(jnp.bfloat16, id="bfloat16")]
both = lambda f: pytest.mark.parametrize("approximate", FORMS)(  # noqa: E731
    pytest.mark.parametrize("dtype", DTYPES)(f))
# what the rule traces per call: the exact form one erf and one exp, the
# tanh form one tanh; never an erfc
PER_CALL = {False: {"erf": 1, "exp": 1, "tanh": 0, "erfc": 0},
            True: {"erf": 0, "exp": 0, "tanh": 1, "erfc": 0}}


def points(dtype):
    x = np.concatenate([np.linspace(-12.0, 12.0, 4801), [0.0, -0.0, 1e-4],
                        np.random.RandomState(0).randn(2000) * 3.0])
    return jnp.asarray(x, jnp.float32).astype(dtype)


def close(got, want, dtype, x, atol=1e-6):
    """`atol` in float32 (plus |x| times erf's own last place: 1 + erf is
    worth 6e-8 a step where erf is near -1); in bfloat16 one unit in the
    last place of the float32 answer, or that much where the answer is
    tiny."""
    got = np.asarray(got.astype(jnp.float32), np.float64)
    want = np.asarray(want, np.float64)
    tol = atol + 1e-7 * np.abs(np.asarray(x.astype(jnp.float32), np.float64))
    if dtype == jnp.float32:
        return np.abs(got - want) <= tol + 1e-6 * np.abs(want)
    ulp = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(want), 1e-30))) - 7)
    return np.abs(got - want) <= np.maximum(ulp, tol)


def count(jaxpr, names=("erf", "exp", "tanh", "erfc")):
    """Occurrences of each primitive, through every nested jaxpr."""
    out = collections.Counter()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in names:
            out[eqn.primitive.name] += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out.update(count(sub, names))
    return {n: out[n] for n in names}


@both
def test_value_against_float32_reference(approximate, dtype):
    x = points(dtype)
    got = F.gelu(x, approximate=approximate)
    assert got.dtype == dtype
    want = jax.nn.gelu(x.astype(jnp.float32), approximate=approximate)
    ok = close(got, want, dtype, x)
    assert ok.all(), (np.asarray(x, np.float32)[~ok][:5],
                      np.asarray(got, np.float32)[~ok][:5])
    zero = F.gelu(jnp.asarray([0.0, -0.0], dtype), approximate=approximate)
    assert np.all(np.asarray(zero, np.float32) == 0.0)


@both
def test_gradient_against_float32_reference(approximate, dtype):
    x = points(dtype)
    got = jax.grad(lambda v: F.gelu(v, approximate=approximate)
                   .astype(jnp.float32).sum())(x)
    assert got.dtype == dtype
    want = jax.vmap(jax.grad(
        lambda v: jax.nn.gelu(v, approximate=approximate)))(
            x.astype(jnp.float32))
    # jax's own float32 derivative of the tanh form loses 3.5e-6 to
    # 1 - tanh^2 in the tails; the rule's (1 - tanh)(1 + tanh) does not
    ok = close(got, want, dtype, x, atol=5e-6 if approximate else 1e-6)
    assert ok.all(), np.asarray(x, np.float32)[~ok][:5]


@both
def test_traces_no_erfc_and_one_evaluation(approximate, dtype):
    x = points(dtype)
    f = lambda v: F.gelu(v, approximate=approximate)  # noqa: E731
    value = count(jax.make_jaxpr(f)(x).jaxpr)
    assert value == {**PER_CALL[approximate], "exp": 0}   # exp is gelu''s
    grad = count(jax.make_jaxpr(
        jax.grad(lambda v: f(v).astype(jnp.float32).sum()))(x).jaxpr)
    assert grad == PER_CALL[approximate]


class _Block(nn.Layer):
    """The FFN sublayer as the encoder and decoder layers hold it."""

    def __init__(self, d_model, d_ffn):
        super().__init__()
        self.linear1 = nn.Linear(d_model, d_ffn)
        self.linear2 = nn.Linear(d_ffn, d_model)
        self.act_dropout = nn.Dropout(0.0)
        self.activation = F.gelu

    def forward(self, x):
        return x + _ffn(self, x)


def scanned_ffn(dtype, blocks=2, d_model=16, d_ffn=64, rows=(2, 8)):
    """loss(stacked params, x) of `blocks` FFN blocks under lax.scan, as
    HybridPretrainer runs the encoder."""
    template = _Block(d_model, d_ffn)
    one = parameters_dict(template)
    keys = jax.random.split(jax.random.PRNGKey(0), blocks + 1)
    stacked = jax.tree_util.tree_map(
        lambda v: jnp.stack([
            jax.random.normal(k, v.shape, jnp.float32) * 0.3
            for k in keys[:blocks]]).astype(dtype), one)
    x = jax.random.normal(keys[-1], rows + (d_model,), jnp.float32)

    def loss(params, x):
        def body(h, blk):
            return functional_call(template, blk, (h,)).astype(h.dtype), None
        y, _ = lax.scan(body, x, params)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    return loss, stacked, x.astype(dtype), template


@pytest.mark.parametrize("dtype", DTYPES)
def test_scanned_ffn_traces_one_erf_and_one_exp_per_call(dtype):
    """Both residuals come from the one evaluation in the forward body: the
    whole value_and_grad holds per GELU call (one, in the scan's body) one
    erf and one exp, and no erfc."""
    loss, params, x, _ = scanned_ffn(dtype)
    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss))(params, x).jaxpr
    assert count(jaxpr) == PER_CALL[False]
    value, grads = jax.jit(jax.value_and_grad(loss))(params, x)
    assert np.isfinite(float(value))
    assert all(np.isfinite(np.asarray(g, np.float32)).all()
               for g in jax.tree_util.tree_leaves(grads))


@pytest.mark.parametrize("dtype", DTYPES)
def test_ffn_keeps_two_ffn_wide_residuals_in_the_activation_dtype(dtype):
    """The value (the second product's operand) and gelu'; not the
    pre-activation, nothing in float32 when the activations are bfloat16."""
    _, params, x, template = scanned_ffn(dtype, d_ffn=64)
    blk = jax.tree_util.tree_map(lambda v: v[0], params)
    saved = saved_residuals(
        lambda p, h: functional_call(template, p, (h,)), blk, x)
    wide = [aval for aval, _ in saved if aval.shape[-1:] == (64,)
            and aval.ndim == x.ndim]
    assert len(wide) == 2, saved
    assert all(a.dtype == dtype for a in wide), wide


@pytest.mark.parametrize("dtype", DTYPES)
def test_ffn_matches_the_plain_formula(dtype):
    """_ffn's float32 pre-activation changes rounding, not the function."""
    _, params, x, template = scanned_ffn(dtype)
    blk = jax.tree_util.tree_map(lambda v: v[0], params)
    got = functional_call(template, blk, (x,))
    f32 = {k: v.astype(jnp.float32) for k, v in blk.items()}
    xf = x.astype(jnp.float32)
    h = xf @ f32["linear1.weight"] + f32["linear1.bias"]
    want = (xf + jax.nn.gelu(h, approximate=False) @ f32["linear2.weight"]
            + f32["linear2.bias"])
    tol = 1e-5 if dtype == jnp.float32 else 0.15
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               atol=tol, rtol=tol)


def test_dtype_rounds_value_and_derivative_once():
    x = points(jnp.float32)
    y, vjp = jax.vjp(lambda v: F.gelu(v, dtype=jnp.bfloat16), x)
    assert y.dtype == jnp.bfloat16
    want = jax.nn.gelu(x, approximate=False)
    assert close(y, want, jnp.bfloat16, x).all()
    (g,) = vjp(jnp.ones_like(y))
    assert g.dtype == jnp.float32
    assert close(g.astype(jnp.bfloat16), jax.vmap(jax.grad(
        lambda v: jax.nn.gelu(v, approximate=False)))(x), jnp.bfloat16, x).all()


@pytest.mark.parametrize("approximate", FORMS)
@pytest.mark.parametrize("how", ["jvp", "vmap", "checkpoint", "second_order",
                                 "jit_grad", "layer"])
def test_rule_composes(approximate, how):
    f = lambda v: F.gelu(v, approximate=approximate)  # noqa: E731
    ref = lambda v: jax.nn.gelu(v, approximate=approximate)  # noqa: E731
    x = jnp.linspace(-4.0, 4.0, 33, dtype=jnp.float32)
    t = jnp.cos(x)
    if how == "jvp":
        got, want = jax.jvp(f, (x,), (t,)), jax.jvp(ref, (x,), (t,))
    elif how == "vmap":
        xs = jnp.stack([x, 0.5 * x, -x])
        got = jax.vmap(jax.grad(lambda v: f(v).sum()))(xs)
        want = jax.vmap(jax.grad(lambda v: ref(v).sum()))(xs)
    elif how == "checkpoint":
        got = jax.grad(lambda v: jax.checkpoint(f)(v).sum())(x)
        want = jax.grad(lambda v: ref(v).sum())(x)
    elif how == "second_order":
        got = jax.vmap(jax.grad(jax.grad(f)))(x)
        want = jax.vmap(jax.grad(jax.grad(ref)))(x)
    elif how == "jit_grad":
        got = jax.jit(jax.grad(lambda v: (f(v) * t).sum()))(x)
        want = jax.grad(lambda v: (ref(v) * t).sum())(x)
    else:
        got = nn.GELU(approximate=approximate)(x)
        want = ref(x)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=2e-6, rtol=2e-6)
