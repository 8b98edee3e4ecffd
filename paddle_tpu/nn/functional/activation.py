"""Activations (ref: python/paddle/nn/functional/activation.py; operators/
activation_op.cc kernels).  All map 1:1 onto jax.nn / jnp primitives, which
XLA fuses into adjacent matmuls — no fused-activation passes needed
(ref ir/fuse_elewise_add_act pass is obsolete here)."""
from __future__ import annotations

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
# (CHANGES PR 21).  With the rule below the only residual is gelu'(x), one
# array, computed in the same elementwise pass as the forward.  custom_jvp
# (not custom_vjp): forward mode, vmap and jax.checkpoint still compose.
_SQRT_HALF = 0.7071067811865476
_INV_SQRT_2PI = 0.3989422804014327
_SQRT_2_OVER_PI = 0.7978845608028654
_TANH_C = 0.044715


@jax.custom_jvp
def _gelu_exact(x):
    return jax.nn.gelu(x, approximate=False)


@_gelu_exact.defjvp
def _gelu_exact_jvp(primals, tangents):
    (x,), (t,) = primals, tangents
    xf = x.astype(jnp.float32)
    cdf = 0.5 * (1.0 + jax.lax.erf(xf * _SQRT_HALF))
    pdf = jnp.exp(-0.5 * xf * xf) * _INV_SQRT_2PI
    return _gelu_exact(x), t * (cdf + xf * pdf).astype(t.dtype)


@jax.custom_jvp
def _gelu_tanh(x):
    return jax.nn.gelu(x, approximate=True)


@_gelu_tanh.defjvp
def _gelu_tanh_jvp(primals, tangents):
    (x,), (t,) = primals, tangents
    xf = x.astype(jnp.float32)
    th = jnp.tanh(_SQRT_2_OVER_PI * (xf + _TANH_C * xf ** 3))
    du = _SQRT_2_OVER_PI * (1.0 + 3.0 * _TANH_C * xf * xf)
    grad = 0.5 * (1.0 + th) + 0.5 * xf * (1.0 - th * th) * du
    return _gelu_tanh(x), t * grad.astype(t.dtype)


def gelu(x, approximate=False):
    return _gelu_tanh(x) if approximate else _gelu_exact(x)


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
