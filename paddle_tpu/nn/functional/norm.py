"""Normalization ops (ref: operators/batch_norm_op.cc, layer_norm_op.cc,
group_norm_op.cc, instance_norm_op.cc; python/paddle/nn/functional/norm.py).

batch_norm takes/returns running stats functionally — the Layer wrapper owns
the mutable buffers (TPU-native: state is explicit, never hidden in kernels).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _bn_train(x, weight, bias, axes, epsilon):
    """Training-mode BN core with a hand-written VJP (ref
    batch_norm_op.cc BatchNormGradKernel — the reference ships a fused
    backward for exactly this reason).

    Forward: ONE-PASS fp32 stats (E[x^2]-E[x]^2) folded to a per-channel
    a·x+b apply — both reductions read x once and fuse into the producing
    conv; the apply input-fuses into the consumer.  Backward: the
    classic two-pass schedule (one fused pass for dβ=Σdy and
    dγ=Σdy·x̂, one elementwise pass for dx) instead of leaving AD to
    schedule the passes (ROADMAP S1).

    Returns (out, mean_f32, var_f32); weight/bias may be None.
    """
    out, mean, var, _, _ = _bn_train_fwd_math(x, weight, bias, axes,
                                              epsilon)
    return out, mean, var


def _bn_train_fwd_math(x, weight, bias, axes, epsilon):
    shape = [1] * x.ndim
    (ch_axis,) = [i for i in range(x.ndim) if i not in axes]
    shape[ch_axis] = -1
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=axes)
    var = jnp.maximum(jnp.mean(xf * xf, axis=axes) - mean * mean, 0.0)
    inv = 1.0 / jnp.sqrt(var + epsilon)
    a = inv if weight is None else inv * weight.astype(jnp.float32)
    b = -mean * a
    if bias is not None:
        b = b + bias.astype(jnp.float32)
    out = x * a.astype(x.dtype).reshape(shape) \
        + b.astype(x.dtype).reshape(shape)
    return out, mean, var, inv, shape


def _bn_train_vjp_fwd(x, weight, bias, axes, epsilon):
    out, mean, var, inv, _ = _bn_train_fwd_math(x, weight, bias, axes,
                                                epsilon)
    return (out, mean, var), (x, weight, bias, mean, inv)


def _bn_train_vjp_bwd(axes, epsilon, res, cts):
    x, weight, bias, mean, inv = res
    dout, dmean, dvar = cts
    shape = [1] * x.ndim
    (ch_axis,) = [i for i in range(x.ndim) if i not in axes]
    shape[ch_axis] = -1
    m = 1
    for ax in axes:
        m *= x.shape[ax]
    mean_b = mean.reshape(shape)
    inv_b = inv.reshape(shape)
    xf = x.astype(jnp.float32)
    dof = dout.astype(jnp.float32)
    xhat = (xf - mean_b) * inv_b
    # pass 1: both reductions in one fused read of (x, dout)
    dbeta = jnp.sum(dof, axis=axes)
    dgamma = jnp.sum(dof * xhat, axis=axes)
    g = jnp.ones_like(inv) if weight is None \
        else weight.astype(jnp.float32)
    # pass 2: elementwise dx (reads x, dout once more, writes dx)
    dx = (g * inv).reshape(shape) * (
        dof - (dbeta / m).reshape(shape)
        - xhat * (dgamma / m).reshape(shape))
    # cotangents of the returned (mean, var): custom_vjp always delivers
    # instantiated arrays — zeros on the buffer path (batch_norm wraps
    # mean/var in stop_gradient), which XLA folds away; the terms stay so
    # direct _bn_train users who DO differentiate mean/var get full grads
    dmean_t = (dmean / m).reshape(shape)
    dvar_t = dvar.reshape(shape) * 2.0 * (xf - mean_b) / m
    dx = (dx + dmean_t + dvar_t).astype(x.dtype)
    dw = None if weight is None else dgamma.astype(weight.dtype)
    db = None if bias is None else dbeta.astype(bias.dtype)
    return dx, dw, db


_bn_train.defvjp(_bn_train_vjp_fwd, _bn_train_vjp_bwd)


def batch_norm(x, running_mean, running_var, weight=None, bias=None,
               training=False, momentum=0.9, epsilon=1e-5, data_format="NCHW"):
    """Returns (out, new_running_mean, new_running_var)."""
    if data_format in ("NCHW", "NCL", "NC"):
        axes = (0,) + tuple(range(2, x.ndim))
        shape = [1, -1] + [1] * (x.ndim - 2)
    else:  # NHWC-style: channel last
        axes = tuple(range(x.ndim - 1))
        shape = [1] * (x.ndim - 1) + [-1]
    if training:
        out, mean, var = _bn_train(x, weight, bias, tuple(axes),
                                   float(epsilon))
        mean = jax.lax.stop_gradient(mean).astype(running_mean.dtype)
        var = jax.lax.stop_gradient(var).astype(running_var.dtype)
        new_rm = momentum * running_mean + (1 - momentum) * mean
        new_rv = momentum * running_var + (1 - momentum) * var
        return out, new_rm, new_rv
    a, b = bn_inference_scale_bias(running_mean, running_var, weight, bias,
                                   epsilon)
    out = x * a.astype(x.dtype).reshape(shape) \
        + b.astype(x.dtype).reshape(shape)
    return out, running_mean, running_var


def _use_fused_bn_act(x, act, data_format) -> bool:
    """Gate for the Pallas fused train-mode BN+act kernel (backend check
    lives in ops.pallas.config so tests can patch it once for every
    vision kernel)."""
    from ...ops.pallas import config as _pcfg
    from ...ops.pallas import conv_fused as _cf

    return (_pcfg.kernel_enabled("use_pallas_conv_fused")
            and _pcfg.counted("bn_act_train",
                              _cf.train_supported(x, act, data_format)))


def batch_norm_act(x, running_mean, running_var, weight=None, bias=None,
                   momentum=0.9, epsilon=1e-5, act="", data_format="NHWC"):
    """Training-mode ``act(batch_norm(x))`` as one fused unit.

    The Pallas path (ops/pallas/conv_fused.fused_bn_act_train) does the
    stats reduction in one pass and the scale/shift+activation in a
    second, with a custom VJP implementing the classic two-pass backward
    — this is the training-mode half of the fused_conv2d_bn_act op (XLA
    keeps the conv; the BN/act epilogue is ours).  Falls back to
    F.batch_norm + the activation, bitwise today's unfused behavior.
    Returns ``(out, new_running_mean, new_running_var)``.
    """
    if _use_fused_bn_act(x, act, data_format):
        from ...ops.pallas import conv_fused as _cf

        c = x.shape[-1]
        gamma = jnp.ones((c,), jnp.float32) if weight is None else weight
        beta = jnp.zeros((c,), jnp.float32) if bias is None else bias
        out, mean, var = _cf.fused_bn_act_train(x, gamma, beta,
                                                float(epsilon), act)
        mean = jax.lax.stop_gradient(mean).astype(running_mean.dtype)
        var = jax.lax.stop_gradient(var).astype(running_var.dtype)
        new_rm = momentum * running_mean + (1 - momentum) * mean
        new_rv = momentum * running_var + (1 - momentum) * var
        return out, new_rm, new_rv
    out, new_rm, new_rv = batch_norm(
        x, running_mean, running_var, weight=weight, bias=bias,
        training=True, momentum=momentum, epsilon=epsilon,
        data_format=data_format)
    if act:
        from . import activation as _act_mod

        # paddle op names vs functional names: hard_swish -> hardswish etc.
        fn = getattr(_act_mod, act, None) \
            or getattr(_act_mod, act.replace("_", ""))
        out = fn(out)
    return out, new_rm, new_rv


def bn_inference_scale_bias(mean, var, weight, bias, epsilon):
    """Fold inference-mode BN to per-channel ``a·x + b`` (fp32 a, b).

    The activation-space fold: the apply input-fuses into the producing conv's
    consumer.  Shared by F.batch_norm's inference path and the graph-level
    conv+BN+act fusion pass (static/passes.py) — the pass replaces the
    conv2d→batch_norm op pair with one ``fused_conv2d_bn_act`` op whose
    lowering scales the conv filter by ``a`` and biases by ``b``, so the
    fold happens once on weights instead of per activation."""
    inv = 1.0 / jnp.sqrt(var.astype(jnp.float32) + epsilon)
    a = inv
    if weight is not None:
        a = a * weight.astype(jnp.float32)
    b = -mean.astype(jnp.float32) * a
    if bias is not None:
        b = b + bias.astype(jnp.float32)
    return a, b


def _fused_ln_shards(x, normalized_shape) -> int:
    """Gate for the Pallas fused-LN kernel: the number of data-parallel
    shards to dispatch it over (parallel.mesh.batch_shards) when the flag,
    backend and PER-SHARD shape allow it, else 0."""
    from ...ops.pallas import config as _pcfg
    from ...ops.pallas import layer_norm as _fused
    from ...parallel import mesh as _mesh

    if not _pcfg.kernel_enabled("use_fused_layer_norm") or x.ndim < 2:
        return 0
    n = _mesh.batch_shards(x.shape[0])
    if not n:
        _pcfg.record_fallback("fused_layer_norm", "partial_manual_mesh")
        return 0
    local = jax.ShapeDtypeStruct((x.shape[0] // n,) + x.shape[1:], x.dtype)
    return n if _fused.supported(local, normalized_shape) else 0


def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-5):
    if isinstance(normalized_shape, int):
        normalized_shape = (normalized_shape,)
    if weight is not None and bias is not None:
        n = _fused_ln_shards(x, tuple(normalized_shape))
        if n:
            from ...ops.pallas import layer_norm as _fused
            from ...parallel import mesh as _mesh

            return _mesh.per_batch_shard(
                lambda x, w, b: _fused.fused_layer_norm(x, w, b, epsilon),
                n, (x,), (weight, bias))
    axes = tuple(range(x.ndim - len(normalized_shape), x.ndim))
    # compute in float32 for bf16 stability (TPU-native AMP practice)
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=axes, keepdims=True)
    var = jnp.var(xf, axis=axes, keepdims=True)
    out = (xf - mean) / jnp.sqrt(var + epsilon)
    out = out.astype(x.dtype)
    if weight is not None:
        out = out * weight
    if bias is not None:
        out = out + bias
    return out


def rms_norm(x, weight=None, epsilon=1e-6):
    """TPU-native addition (no reference equivalent): RMSNorm for modern LLMs.
    With a weight, the backward recomputes the float32 statistics from the
    input as it came (the residuals are x and the weight, not float32
    copies of x and of its normalised form: 8 bytes an element of a
    scanned stack's memory at bf16)."""
    if weight is None:
        return _rms_normalised(x, epsilon)[0].astype(x.dtype)
    return _rms_norm_weighted(x, weight, epsilon)


def _rms_normalised(x, epsilon):
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    root = jnp.sqrt(ms + epsilon)
    return xf / root, 1.0 / root


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rms_norm_weighted(x, weight, epsilon):
    return _rms_normalised(x, epsilon)[0].astype(x.dtype) * weight


def _rms_norm_fwd(x, weight, epsilon):
    return _rms_norm_weighted(x, weight, epsilon), (x, weight)


def _rms_norm_bwd(epsilon, res, g):
    x, weight = res
    normed, inv = _rms_normalised(x, epsilon)
    gf = g.astype(jnp.float32)
    d_normed = gf * weight.astype(jnp.float32)
    d_weight = jnp.sum(gf * normed.astype(x.dtype).astype(jnp.float32),
                       axis=tuple(range(g.ndim - weight.ndim)))
    dx = inv * (d_normed - normed * jnp.mean(d_normed * normed, axis=-1,
                                             keepdims=True))
    return dx.astype(x.dtype), d_weight.astype(weight.dtype)


_rms_norm_weighted.defvjp(_rms_norm_fwd, _rms_norm_bwd)


def group_norm(x, num_groups, weight=None, bias=None, epsilon=1e-5):
    """x: (N, C, *spatial)."""
    n, c = x.shape[0], x.shape[1]
    spatial = x.shape[2:]
    g = x.reshape(n, num_groups, c // num_groups, *spatial)
    axes = tuple(range(2, g.ndim))
    mean = jnp.mean(g, axis=axes, keepdims=True)
    var = jnp.var(g, axis=axes, keepdims=True)
    g = (g - mean) / jnp.sqrt(var + epsilon)
    out = g.reshape(x.shape)
    shape = [1, c] + [1] * len(spatial)
    if weight is not None:
        out = out * weight.reshape(shape)
    if bias is not None:
        out = out + bias.reshape(shape)
    return out


def instance_norm(x, weight=None, bias=None, epsilon=1e-5):
    axes = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    out = (x - mean) / jnp.sqrt(var + epsilon)
    shape = [1, x.shape[1]] + [1] * (x.ndim - 2)
    if weight is not None:
        out = out * weight.reshape(shape)
    if bias is not None:
        out = out + bias.reshape(shape)
    return out


def normalize(x, p=2, axis=1, epsilon=1e-12):
    norm = jnp.sum(jnp.abs(x) ** p, axis=axis, keepdims=True) ** (1.0 / p)
    return x / jnp.maximum(norm, epsilon)
