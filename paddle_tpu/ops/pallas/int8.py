"""int8 inference Pallas kernels: conv / matmul with int32 accumulation
and an fp32 per-channel dequant epilogue.

The PTQ story: ``slim/quant_static.py`` calibrates a program and leaves
``weight_scale``/``weight_bits`` attrs on conv/mul ops plus fixed-scale
fake-quant ops on their activations; the ``quant_infer`` pass
(static/passes.py) folds each such pair into a ``quant_conv2d`` /
``quant_mul`` op.  These kernels execute those ops: operands arrive
already quantized to int8 (symmetric, zero-point 0), the MXU accumulates
in int32 (``preferred_element_type``), and the epilogue applies the
combined per-output-channel scale ``step_in * step_w`` — the one place
the computation returns to fp32, so the fp32 bias add and activation ride
in the same output tile.

Scale-axis contract (shared with slim/quant.py — see
``quant.conv_quant_axis``): per-channel scales are always indexed by the
*output-channel* axis, which is the NHWC minor (lane) axis of the conv
output — scale ``(O,)`` broadcasts over output tiles with no transpose.

The error model: int32 accumulation is exact, so the only divergence from
the fake-quant (dequantize + fp32 op) semantics the pass rewrote is fp32
summation rounding — parity holds to ~1e-3 relative on calibrated
ranges, asserted by golden-parity tests.  Off-TPU runs interpret mode.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from paddle_tpu.ops.pallas import config as _cfg


DEFAULT_BLOCK_ROWS = 256
VMEM_CAP_BYTES = 12 * 1024 * 1024

EPILOGUE_ACTS = ("", "relu", "relu6", "sigmoid", "tanh")


def _apply_act(out, act):
    if act == "relu":
        return jax.nn.relu(out)
    if act == "relu6":
        return jax.nn.relu6(out)
    if act == "sigmoid":
        return jax.nn.sigmoid(out)
    if act == "tanh":
        return jnp.tanh(out)
    return out


def _rows_block(n_rows: int) -> int:
    block = min(DEFAULT_BLOCK_ROWS, n_rows)
    while n_rows % block:
        block //= 2
    return max(block, 1)


def _out_hw(size: int, k: int, stride: int, pad: int) -> int:
    return (size + 2 * pad - k) // stride + 1


# ---------------------------------------------------------------------------
# int8 matmul
# ---------------------------------------------------------------------------

def _int8_matmul_kernel(x_ref, w_ref, s_ref, b_ref, o_ref, *, act):
    acc = jnp.dot(x_ref[...], w_ref[...],
                  preferred_element_type=jnp.int32)
    out = acc.astype(jnp.float32) * s_ref[0][None, :] + b_ref[0][None, :]
    o_ref[...] = _apply_act(out, act).astype(o_ref.dtype)


def matmul_supported(x_q, w_shape, act="") -> bool:
    if getattr(x_q, "ndim", 0) != 2 or x_q.dtype != jnp.int8:
        return False
    if act not in EPILOGUE_ACTS:
        return False
    k, n = w_shape
    m = x_q.shape[0]
    return (x_q.shape[1] == k and k % 128 == 0 and n % 128 == 0
            and m % 8 == 0)


def int8_matmul_dequant(x_q, w_q, scale, bias=None, act="",
                        out_dtype=jnp.float32):
    """``act((x_q @ w_q) * scale + bias)`` — x_q (M, K) int8, w_q (K, N)
    int8, scale fp32 (N,) combined in*weight step, bias fp32 (N,) or None."""
    m, k = x_q.shape
    n = w_q.shape[1]
    block_m = _rows_block(m)
    b = (jnp.zeros((n,), jnp.float32) if bias is None
         else bias.astype(jnp.float32))
    kernel = functools.partial(_int8_matmul_kernel, act=act)
    _cfg.record_call("int8_matmul")
    with jax.named_scope("pallas.int8_matmul"):
        return pl.pallas_call(
            kernel,
            grid=(m // block_m,),
            in_specs=[pl.BlockSpec((block_m, k), lambda i: (i, 0)),
                      pl.BlockSpec((k, n), lambda i: (0, 0)),
                      pl.BlockSpec((1, n), lambda i: (0, 0)),
                      pl.BlockSpec((1, n), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((block_m, n), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
            interpret=_cfg.interpret(),
        )(x_q, w_q, scale.reshape(1, -1).astype(jnp.float32),
          b.reshape(1, -1))


# ---------------------------------------------------------------------------
# int8 conv (direct, tap-loop — same layout as conv_fused)
# ---------------------------------------------------------------------------

def _int8_conv_kernel(x_ref, w_ref, s_ref, b_ref, o_ref, *, kh, kw, sh, sw,
                      out_h, out_w, act):
    c = x_ref.shape[3]
    o = w_ref.shape[3]
    x = x_ref[0]  # (Hp, Wp, C) int8
    acc = jnp.zeros((out_h * out_w, o), jnp.int32)
    for i in range(kh):
        for j in range(kw):
            win = jax.lax.slice(
                x, (i, j, 0),
                (i + (out_h - 1) * sh + 1, j + (out_w - 1) * sw + 1, c),
                (sh, sw, 1))
            acc = acc + jnp.dot(win.reshape(out_h * out_w, c), w_ref[i, j],
                                preferred_element_type=jnp.int32)
    out = acc.astype(jnp.float32) * s_ref[0][None, :] + b_ref[0][None, :]
    out = _apply_act(out, act)
    o_ref[0] = out.reshape(out_h, out_w, o).astype(o_ref.dtype)


def conv_supported(x_q, w_shape, stride, padding, dilation=(1, 1), groups=1,
                   act="", data_format="NHWC") -> bool:
    """x_q the int8 NHWC input; w_shape the OIHW filter shape."""
    if data_format != "NHWC" or getattr(x_q, "ndim", 0) != 4:
        return False
    if x_q.dtype != jnp.int8 or groups != 1 or tuple(dilation) != (1, 1):
        return False
    if act not in EPILOGUE_ACTS:
        return False
    o, c_in, kh, kw = w_shape
    n, h, w, c = x_q.shape
    if c != c_in or c % 128 or o % 128 or kh > 7 or kw > 7:
        return False
    sh, sw = stride
    ph, pw = padding
    # stride 1 only: Mosaic refuses the tap loop's stride-2 window slice
    # (see conv_fused.supported for the two refusals; ROADMAP S1)
    if (sh, sw) != (1, 1):
        return False
    out_h, out_w = _out_hw(h, kh, sh, ph), _out_hw(w, kw, sw, pw)
    if out_h <= 0 or out_w <= 0:
        return False
    vmem = ((h + 2 * ph) * (w + 2 * pw) * c + kh * kw * c * o
            + 4 * out_h * out_w * o * 2 + out_h * out_w * o * 4)
    return vmem <= VMEM_CAP_BYTES


def int8_conv2d_dequant(x_q, w_q, scale, bias=None, *, stride=(1, 1),
                        padding=(0, 0), act="", out_dtype=jnp.float32):
    """``act(conv2d(x_q, w_q) * scale + bias)`` — x_q NHWC int8, w_q OIHW
    int8, scale fp32 (O,) combined step, bias fp32 (O,) or None.  Padding
    is with 0 = the symmetric zero-point, so it matches fp32 zero pad."""
    n, h, wd, c = x_q.shape
    o, _, kh, kw = w_q.shape
    sh, sw = stride
    ph, pw = padding
    out_h, out_w = _out_hw(h, kh, sh, ph), _out_hw(wd, kw, sw, pw)
    xp = jnp.pad(x_q, ((0, 0), (ph, ph), (pw, pw), (0, 0)))
    hp, wp = h + 2 * ph, wd + 2 * pw
    wk = jnp.transpose(w_q, (2, 3, 1, 0))  # (kh, kw, C, O)
    b = (jnp.zeros((o,), jnp.float32) if bias is None
         else bias.astype(jnp.float32))
    kernel = functools.partial(_int8_conv_kernel, kh=kh, kw=kw, sh=sh, sw=sw,
                               out_h=out_h, out_w=out_w, act=act)
    _cfg.record_call("int8_conv2d")
    with jax.named_scope("pallas.int8_conv2d"):
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
            out_shape=jax.ShapeDtypeStruct((n, out_h, out_w, o), out_dtype),
            interpret=_cfg.interpret(),
        )(xp, wk, scale.reshape(1, -1).astype(jnp.float32), b.reshape(1, -1))


def int8_cost(n, out_h, out_w, c, o, kh, kw, in_h=None, in_w=None
              ) -> Tuple[float, float]:
    """(flops, hbm bytes) — int8 operands read 1 byte/elem, fp32 out."""
    flops = 2.0 * n * out_h * out_w * o * c * kh * kw \
        + 3.0 * n * out_h * out_w * o
    in_h = in_h if in_h is not None else out_h
    in_w = in_w if in_w is not None else out_w
    bytes_ = (n * in_h * in_w * c + kh * kw * c * o
              + 4 * n * out_h * out_w * o + 8 * o)
    return flops, float(bytes_)


def _int8_conv_instr_flops(instr) -> float:
    if len(instr.operand_shapes) < 2 or not instr.out_shapes:
        return 0.0
    out = instr.out_shapes[0][1]
    wsh = instr.operand_shapes[1][1]
    if len(out) != 4 or len(wsh) != 4:
        return 0.0
    n, oh, ow, o = out
    kh, kw, c, _ = wsh
    return 2.0 * n * oh * ow * o * c * kh * kw + 3.0 * n * oh * ow * o


def _int8_matmul_instr_flops(instr) -> float:
    if len(instr.operand_shapes) < 2 or not instr.out_shapes:
        return 0.0
    out = instr.out_shapes[0][1]
    wsh = instr.operand_shapes[1][1]
    if len(out) != 2 or len(wsh) != 2:
        return 0.0
    return 2.0 * out[0] * out[1] * wsh[0] + 3.0 * out[0] * out[1]


_cfg.register_cost("pallas.int8_conv2d", _int8_conv_instr_flops)
_cfg.register_cost("pallas.int8_matmul", _int8_matmul_instr_flops)
