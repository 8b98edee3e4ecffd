"""Fused conv + BatchNorm + activation Pallas kernels (NHWC).

Backs the ``fused_conv2d_bn_act`` op minted by ``static/passes.py
fuse_conv_bn_act``.  Two modes:

* **Inference** (`conv2d_bn_act`): a direct NHWC convolution whose output
  tiles get the per-channel BN transform ``act(conv(x, w) * a + b)`` as a
  fused epilogue — one HBM pass where the unfused lowering pays conv +
  two elementwise passes.  ``(a, b)`` come from
  ``nn.functional.norm.bn_inference_scale_bias``; unlike the r05
  weight-space fold the weights stay untouched, so the same filter array
  serves fused and unfused traces.
* **Training** (`fused_bn_act_train`): XLA keeps the conv (its MXU conv
  codegen is already good); what it does *not* fuse across the
  conv→BN→act boundary is the stats reduction and the two elementwise
  passes, so those are Pallas here: one stats pass (sum / sum-of-squares
  partials per row block) + one apply pass computing
  ``act(x * a + b)``, with a `jax.custom_vjp` implementing the classic
  two-pass BN backward so the op stays differentiable inside
  ``backward_region`` programs.

Kernel layout: the conv kernel runs one padded batch image per grid step
— block ``(1, Hp, Wp, C)`` in, ``(1, Ho, Wo, O)`` out — and loops the
``kh*kw`` filter taps, each tap a strided window slice feeding an MXU
``(Ho*Wo, C) x (C, O)`` dot accumulated in fp32 VMEM.  `supported()`
gates shapes to lane-aligned channels (C, O multiples of 128), small
filters, stride 1/2, and a VMEM budget; everything else falls back to
the XLA lowering (see static/ops_fused.py).

Off-TPU the kernels run in interpret mode, so CPU CI exercises the same
code paths (tests/test_pallas_vision.py).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from paddle_tpu.ops.pallas import config as _cfg


DEFAULT_BLOCK_ROWS = 256
# Per-grid-step VMEM budget for the whole-image conv blocks (input +
# filter + fp32 accumulator + output), conservative vs the ~16 MB/core.
VMEM_CAP_BYTES = 12 * 1024 * 1024

# Activations the epilogue can apply in-register.  Matches the
# nn.functional lowering (jax.nn.*) so fused-vs-unfused parity holds to
# float tolerance.
EPILOGUE_ACTS = ("", "relu", "relu6", "sigmoid", "tanh", "gelu", "silu",
                 "swish")
# Acts whose gradient the training bwd can rebuild from the saved output.
TRAIN_ACTS = ("", "relu")


def _rows_block(n_rows: int) -> int:
    block = min(DEFAULT_BLOCK_ROWS, n_rows)
    while n_rows % block:
        block //= 2
    return max(block, 1)


def _apply_act(out, act):
    if act == "relu":
        return jax.nn.relu(out)
    if act == "relu6":
        return jax.nn.relu6(out)
    if act == "sigmoid":
        return jax.nn.sigmoid(out)
    if act == "tanh":
        return jnp.tanh(out)
    if act == "gelu":
        return jax.nn.gelu(out, approximate=False)
    if act in ("silu", "swish"):
        return jax.nn.silu(out)
    return out


def _out_hw(size: int, k: int, stride: int, pad: int) -> int:
    return (size + 2 * pad - k) // stride + 1


# ---------------------------------------------------------------------------
# Inference: direct conv with per-channel a*x+b epilogue
# ---------------------------------------------------------------------------

def _conv_bn_act_kernel(x_ref, w_ref, a_ref, b_ref, o_ref, *, kh, kw, sh, sw,
                        out_h, out_w, act):
    # x_ref (1, Hp, Wp, C) one pre-padded image; w_ref (kh, kw, C, O);
    # a_ref/b_ref (1, O) fp32 epilogue scale/bias; o_ref (1, out_h, out_w, O)
    c = x_ref.shape[3]
    o = w_ref.shape[3]
    x = x_ref[0].astype(jnp.float32)
    acc = jnp.zeros((out_h * out_w, o), jnp.float32)
    for i in range(kh):
        for j in range(kw):
            win = jax.lax.slice(
                x, (i, j, 0),
                (i + (out_h - 1) * sh + 1, j + (out_w - 1) * sw + 1, c),
                (sh, sw, 1))
            acc = acc + jnp.dot(win.reshape(out_h * out_w, c),
                                w_ref[i, j].astype(jnp.float32),
                                preferred_element_type=jnp.float32)
    out = _apply_act(acc * a_ref[0][None, :] + b_ref[0][None, :], act)
    o_ref[0] = out.reshape(out_h, out_w, o).astype(o_ref.dtype)


def _conv_vmem_bytes(hp, wp, c, kh, kw, o, out_h, out_w, itemsize) -> int:
    return (hp * wp * c * 4                 # fp32 image copy
            + kh * kw * c * o * itemsize    # filter
            + 2 * out_h * out_w * o * 4     # accumulator + epilogue
            + out_h * out_w * o * itemsize)


def supported(x, w_shape, stride, padding, dilation=(1, 1), groups=1,
              act="", data_format="NHWC") -> bool:
    """Shape/dtype gate for `conv2d_bn_act`.  x is the NHWC input array (or
    anything with .shape/.dtype); w_shape the OIHW filter shape."""
    if data_format != "NHWC" or getattr(x, "ndim", 0) != 4:
        return False
    if groups != 1 or tuple(dilation) != (1, 1):
        return False
    if act not in EPILOGUE_ACTS:
        return False
    if x.dtype not in (jnp.float32, jnp.bfloat16):
        return False
    o, c_in, kh, kw = w_shape
    n, h, w, c = x.shape
    if c != c_in or c % 128 or o % 128:
        return False
    if kh > 7 or kw > 7:
        return False
    sh, sw = stride
    ph, pw = padding
    # Stride 1 only.  The tap loop takes each window with a strided
    # `lax.slice` of the in-VMEM image, and Mosaic refuses a stride-2
    # `vector.extract_strided_slice` ("expected strides to be confined to
    # [1, 2)", jax 0.9.0 / libtpu 0.0.34).  The ref-side alternative
    # (`pl.ds(..., stride=2)`) compiles for 32-bit data only ("Strided load
    # with non 32-bit data" for bf16/int8), so stride 2 stays on XLA
    # (ROADMAP S1).
    if (sh, sw) != (1, 1):
        return False
    out_h, out_w = _out_hw(h, kh, sh, ph), _out_hw(w, kw, sw, pw)
    if out_h <= 0 or out_w <= 0:
        return False
    vmem = _conv_vmem_bytes(h + 2 * ph, w + 2 * pw, c, kh, kw, o, out_h,
                            out_w, x.dtype.itemsize)
    return vmem <= VMEM_CAP_BYTES


def conv2d_bn_act(x, w, a, b, *, stride=(1, 1), padding=(0, 0), act=""):
    """``act(conv2d(x, w) * a + b)`` — x NHWC, w OIHW, a/b fp32 ``(O,)``
    per-channel epilogue scale/bias (use ``a = ones`` and ``b = conv bias``
    for a plain conv+bias+act)."""
    n, h, wd, c = x.shape
    o, _, kh, kw = w.shape
    sh, sw = stride
    ph, pw = padding
    out_h, out_w = _out_hw(h, kh, sh, ph), _out_hw(wd, kw, sw, pw)
    xp = jnp.pad(x, ((0, 0), (ph, ph), (pw, pw), (0, 0)))
    hp, wp = h + 2 * ph, wd + 2 * pw
    wk = jnp.transpose(w, (2, 3, 1, 0))  # (kh, kw, C, O)
    kernel = functools.partial(_conv_bn_act_kernel, kh=kh, kw=kw, sh=sh,
                               sw=sw, out_h=out_h, out_w=out_w, act=act)
    _cfg.record_call("conv2d_bn_act")
    with jax.named_scope("pallas.conv2d_bn_act"):
        return pl.pallas_call(
            kernel,
            grid=(n,),
            in_specs=[
                pl.BlockSpec((1, hp, wp, c), lambda i: (i, 0, 0, 0)),
                pl.BlockSpec((kh, kw, c, o), lambda i: (0, 0, 0, 0)),
                pl.BlockSpec((1, o), lambda i: (0, 0)),
                pl.BlockSpec((1, o), lambda i: (0, 0)),
            ],
            out_specs=pl.BlockSpec((1, out_h, out_w, o),
                                   lambda i: (i, 0, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((n, out_h, out_w, o), x.dtype),
            interpret=_cfg.interpret(),
        )(xp, wk, a.reshape(1, -1).astype(jnp.float32),
          b.reshape(1, -1).astype(jnp.float32))


def conv_cost(n, out_h, out_w, c, o, kh, kw, itemsize=4,
              in_h=None, in_w=None) -> Tuple[float, float]:
    """(flops, hbm bytes) model for one fused conv+BN+act call."""
    flops = 2.0 * n * out_h * out_w * o * c * kh * kw \
        + 3.0 * n * out_h * out_w * o  # epilogue mul/add/act
    in_h = in_h if in_h is not None else out_h
    in_w = in_w if in_w is not None else out_w
    bytes_ = (n * in_h * in_w * c + n * out_h * out_w * o
              + kh * kw * c * o) * itemsize + 2 * o * 4
    return flops, bytes_


def _conv_instr_flops(instr) -> float:
    """xprof cost: operands are (x_padded, w, a, b) per `conv2d_bn_act`."""
    if len(instr.operand_shapes) < 2 or not instr.out_shapes:
        return 0.0
    out = instr.out_shapes[0][1]
    wsh = instr.operand_shapes[1][1]
    if len(out) != 4 or len(wsh) != 4:
        return 0.0
    n, oh, ow, o = out
    kh, kw, c, _ = wsh
    return 2.0 * n * oh * ow * o * c * kh * kw + 3.0 * n * oh * ow * o


_cfg.register_cost("pallas.conv2d_bn_act", _conv_instr_flops)


# ---------------------------------------------------------------------------
# Training: fused BN-stats + scale/shift + activation (around XLA's conv)
# ---------------------------------------------------------------------------

def _stats_kernel(x_ref, s_ref, ss_ref):
    # x_ref (block_rows, C) -> per-block partial sum / sum-of-squares tiles
    # (1, 8, C): payload in row 0, zeros elsewhere (layer_norm bwd idiom).
    xf = x_ref[...].astype(jnp.float32)
    s = jnp.sum(xf, axis=0)
    ss = jnp.sum(xf * xf, axis=0)
    row = jax.lax.broadcasted_iota(jnp.int32, (8, xf.shape[1]), 0)
    s_ref[0] = jnp.where(row == 0, s[None, :], 0.0)
    ss_ref[0] = jnp.where(row == 0, ss[None, :], 0.0)


def _scale_act_kernel(x_ref, a_ref, b_ref, o_ref, *, act):
    xf = x_ref[...].astype(jnp.float32)
    out = _apply_act(xf * a_ref[0][None, :] + b_ref[0][None, :], act)
    o_ref[...] = out.astype(o_ref.dtype)


def _batch_stats(x2, block_rows):
    """Per-channel (sum, sum_sq) of a (rows, C) array via one Pallas pass."""
    n, c = x2.shape
    grid = n // block_rows
    s, ss = pl.pallas_call(
        _stats_kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((block_rows, c), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((1, 8, c), lambda i: (i, 0, 0)),
                   pl.BlockSpec((1, 8, c), lambda i: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((grid, 8, c), jnp.float32),
                   jax.ShapeDtypeStruct((grid, 8, c), jnp.float32)],
        interpret=_cfg.interpret(),
    )(x2)
    return s.sum(axis=(0, 1)), ss.sum(axis=(0, 1))


def scale_act(x2, a, b, act, block_rows, out_dtype):
    """One-pass ``act(x * a + b)`` over a (rows, C) array."""
    n, c = x2.shape
    kernel = functools.partial(_scale_act_kernel, act=act)
    return pl.pallas_call(
        kernel,
        grid=(n // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, c), lambda i: (i, 0)),
                  pl.BlockSpec((1, c), lambda i: (0, 0)),
                  pl.BlockSpec((1, c), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((block_rows, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, c), out_dtype),
        interpret=_cfg.interpret(),
    )(x2, a.reshape(1, -1), b.reshape(1, -1))


def train_supported(x, act="", data_format="NHWC") -> bool:
    if data_format != "NHWC" or getattr(x, "ndim", 0) != 4:
        return False
    if act not in TRAIN_ACTS:
        return False
    if x.dtype not in (jnp.float32, jnp.bfloat16):
        return False
    n, h, w, c = x.shape
    rows = n * h * w
    return c % 128 == 0 and rows % 8 == 0


def _bn_act_fwd_impl(x2, gamma, beta, eps, act, block_rows):
    rows = x2.shape[0]
    s, ss = _batch_stats(x2, block_rows)
    mean = s / rows
    var = jnp.maximum(ss / rows - mean * mean, 0.0)
    inv = jax.lax.rsqrt(var + eps)
    a = gamma.astype(jnp.float32) * inv
    b = beta.astype(jnp.float32) - mean * a
    y2 = scale_act(x2, a, b, act, block_rows, x2.dtype)
    return y2, mean, var


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _bn_act_train(x2, gamma, beta, eps, act, block_rows):
    return _bn_act_fwd_impl(x2, gamma, beta, eps, act, block_rows)


def _bn_act_train_fwd(x2, gamma, beta, eps, act, block_rows):
    y2, mean, var = _bn_act_fwd_impl(x2, gamma, beta, eps, act, block_rows)
    return (y2, mean, var), (x2, gamma, mean, var, y2)


def _bn_act_train_bwd(eps, act, block_rows, res, cts):
    # Cotangents for the mean/var outputs are ignored: they feed the
    # (detached) running-stat updates only.
    dy2 = cts[0]
    x2, gamma, mean, var, y2 = res
    rows = x2.shape[0]
    xf = x2.astype(jnp.float32)
    dyf = dy2.astype(jnp.float32)
    if act == "relu":
        dz = jnp.where(y2 > 0, dyf, 0.0)
    else:
        dz = dyf
    inv = jax.lax.rsqrt(var + eps)
    xhat = (xf - mean[None, :]) * inv[None, :]
    dbeta = jnp.sum(dz, axis=0)
    dgamma = jnp.sum(dz * xhat, axis=0)
    g = gamma.astype(jnp.float32) * inv
    dx = g[None, :] * (dz - dbeta[None, :] / rows
                       - xhat * dgamma[None, :] / rows)
    return (dx.astype(x2.dtype), dgamma.astype(gamma.dtype),
            dbeta.astype(gamma.dtype))


_bn_act_train.defvjp(_bn_act_train_fwd, _bn_act_train_bwd)


def fused_bn_act_train(x, gamma, beta, eps=1e-5, act=""):
    """Training-mode fused BatchNorm + activation over an NHWC tensor.

    Returns ``(y, batch_mean, batch_var)`` with y differentiable in
    (x, gamma, beta); mean/var are fp32 ``(C,)`` batch statistics for the
    caller's running-stat update (treated as detached by the VJP).
    """
    n, h, w, c = x.shape
    x2 = x.reshape(n * h * w, c)
    block_rows = _rows_block(x2.shape[0])
    _cfg.record_call("bn_act_train")
    with jax.named_scope("pallas.bn_act_train"):
        y2, mean, var = _bn_act_train(x2, gamma, beta, float(eps), act,
                                      block_rows)
    return y2.reshape(n, h, w, c), mean, var


def bn_act_cost(rows, c, itemsize=4) -> Tuple[float, float]:
    """(flops, hbm bytes) for the fused train fwd (stats + apply)."""
    flops = rows * c * 3.0 + rows * c * 3.0  # stats pass + apply pass
    bytes_ = rows * c * itemsize * 3 + 4 * c * 4
    return flops, bytes_


def _elementwise_instr_flops(instr) -> float:
    if not instr.out_shapes:
        return 0.0
    out_elems = 1
    for d in instr.out_shapes[0][1]:
        out_elems *= d
    return 3.0 * out_elems


_cfg.register_cost("pallas.bn_act_train", _elementwise_instr_flops)
