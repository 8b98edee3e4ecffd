"""Activations (ref: python/paddle/nn/functional/activation.py; operators/
activation_op.cc kernels).  All but GELU map 1:1 onto jax.nn / jnp
primitives, which XLA fuses into adjacent matmuls — no fused-activation
passes needed (ref ir/fuse_elewise_add_act pass is obsolete here).  GELU is
written out: value and derivative from one erf (or tanh) and one exp, in
float32, rounded once (below)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def relu(x):
    return jax.nn.relu(x)


def relu6(x):
    return jax.nn.relu6(x)


def leaky_relu(x, negative_slope=0.01):
    return jax.nn.leaky_relu(x, negative_slope)


def prelu(x, weight):
    weight = jnp.asarray(weight)
    if weight.size > 1 and x.ndim >= 2:
        # per-channel: weight broadcast over channel axis 1 (NCHW convention)
        shape = [1] * x.ndim
        shape[1] = weight.size
        weight = weight.reshape(shape)
    return jnp.where(x >= 0, x, weight * x)


def elu(x, alpha=1.0):
    return jax.nn.elu(x, alpha)


def celu(x, alpha=1.0):
    return jax.nn.celu(x, alpha)


def selu(x):
    return jax.nn.selu(x)


# GELU carries its own derivative rule.  Left to autodiff, exact GELU keeps
# three FFN-wide residuals (x/sqrt2, the cdf, x) next to its output; under
# lax.scan over stacked blocks those are materialized per layer between the
# forward and backward loops — at ERNIE-base b64 x s512 that was 4 x
# bf16[12,64,512,3072] = 9 GB and the step did not fit a 16 GB chip
# (CHANGES PR 21).  With the rules below the only residual is gelu'(x), one
# array in the result's dtype.  custom_jvp (not custom_vjp): forward mode,
# vmap, jax.checkpoint and second order still compose.
#
# Value and derivative share one erf and one exp per element, in float32:
# with c = (1 + erf(x/sqrt2))/2 and p = exp(-x^2/2)/sqrt(2 pi), gelu = x c
# and gelu' = c + x p; both are rounded to `dtype` once.  (jax.nn.gelu on
# bf16 rounds x/sqrt2 and erfc on the way, erfc is three polynomial branches
# all evaluated and selected, and a derivative beside it is a second erf and
# exp: 150 vector operations an element in the FFN's first product's
# epilogue, three times the product's own time on a v5e; PERF.md §6, PR 27.)
# What XLA:TPU does with it in a scanned FFN (jax 0.9, libtpu 0.0.34):
# - fed the product's bf16 result, the one-erf value is cheap enough to the
#   fusion pass that it re-evaluates it in every consumer: three erf a
#   block, one of them in the second product's operand (+7 ms a step).
# - behind lax.optimization_barrier: one erf, but the stacked residuals'
#   writes leave the product's epilogue and become copy passes (+7 ms each).
# - fed the product's float32 sum (`dtype` says what to round to): one
#   fusion, one erf, one exp, value and both stacked residuals written by
#   the product's own epilogue.  nn/layer/transformer._ffn calls it so.
_SQRT_HALF = 0.7071067811865476
_INV_SQRT_2PI = 0.3989422804014327
_SQRT_2_OVER_PI = 0.7978845608028654
_TANH_C = 0.044715


def _erf_parts(xf, derivative):
    """c and, for the rule, x p (as above), in float32."""
    cdf = 0.5 * (1.0 + jax.lax.erf(xf * _SQRT_HALF))
    if not derivative:
        return cdf, None
    return cdf, xf * (jnp.exp(-0.5 * xf * xf) * _INV_SQRT_2PI)


def _tanh_parts(xf, derivative):
    """The tanh form's c = (1 + tanh u)/2, u = sqrt(2/pi) (x + 0.044715 x^3),
    and x c' from the same tanh."""
    x2 = xf * xf
    th = jnp.tanh(_SQRT_2_OVER_PI * xf * (1.0 + _TANH_C * x2))
    cdf = 0.5 * (1.0 + th)
    if not derivative:
        return cdf, None
    du = _SQRT_2_OVER_PI * (1.0 + 3.0 * _TANH_C * x2)
    return cdf, xf * cdf * (1.0 - th) * du      # 1 - th^2 without cancelling


def _gelu_rule(parts):
    """x c rounded to `dtype`, and the rule whose residual is c + x c'."""
    @functools.partial(jax.custom_jvp, nondiff_argnums=(1,))
    def f(x, dtype):
        xf = x.astype(jnp.float32)
        return (xf * parts(xf, False)[0]).astype(dtype)

    @f.defjvp
    def f_jvp(dtype, primals, tangents):
        (x,), (t,) = primals, tangents
        xf = x.astype(jnp.float32)
        c, xdc = parts(xf, True)
        return (xf * c).astype(dtype), t.astype(dtype) * (c + xdc).astype(dtype)

    return f


_gelu_exact = _gelu_rule(_erf_parts)
_gelu_tanh = _gelu_rule(_tanh_parts)


def gelu(x, approximate=False, dtype=None):
    """`dtype`: what the value (and the derivative the backward keeps) is
    rounded to, default x.dtype; the arithmetic is float32 either way."""
    x = jnp.asarray(x)
    dtype = jnp.dtype(x.dtype if dtype is None else dtype)
    return (_gelu_tanh if approximate else _gelu_exact)(x, dtype)


def sigmoid(x):
    return jax.nn.sigmoid(x)


def hardsigmoid(x, slope=1.0 / 6, offset=0.5):
    return jnp.clip(slope * x + offset, 0.0, 1.0)


def hardswish(x):
    return x * jnp.clip(x / 6.0 + 0.5, 0.0, 1.0)


def hardtanh(x, min=-1.0, max=1.0):
    return jnp.clip(x, min, max)


def hardshrink(x, threshold=0.5):
    return jnp.where(jnp.abs(x) > threshold, x, 0.0)


def softshrink(x, threshold=0.5):
    return jnp.where(x > threshold, x - threshold,
                     jnp.where(x < -threshold, x + threshold, 0.0))


def softplus(x, beta=1.0, threshold=20.0):
    scaled = beta * x
    return jnp.where(scaled > threshold, x, jnp.logaddexp(scaled, 0.0) / beta)


def softsign(x):
    return jax.nn.soft_sign(x)


def silu(x):
    return jax.nn.silu(x)


def swish(x):
    return jax.nn.silu(x)


def mish(x):
    return jax.nn.mish(x)


def tanhshrink(x):
    return x - jnp.tanh(x)


def log_sigmoid(x):
    return jax.nn.log_sigmoid(x)


def softmax(x, axis=-1, dtype=None):
    out = jax.nn.softmax(x.astype(jnp.float32) if dtype is None else x.astype(dtype),
                         axis=axis)
    return out.astype(x.dtype) if dtype is None else out


def log_softmax(x, axis=-1):
    return jax.nn.log_softmax(x.astype(jnp.float32), axis=axis).astype(x.dtype)


def glu(x, axis=-1):
    a, b = jnp.split(x, 2, axis=axis)
    return a * jax.nn.sigmoid(b)
