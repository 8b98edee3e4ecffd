"""NHWC-native max/avg pooling Pallas kernels.

``layout_nhwc`` propagation (static/passes.py) rewrites vision programs
so conv/pool compute happens in NHWC; these kernels finish the story by
making the pooling itself layout-native — one HBM pass per pool with the
channel dim on the lane axis, where ``lax.reduce_window`` costs XLA a
windowed reduce it cannot fuse with neighbors.

Kernel layout mirrors conv_fused: one padded batch image per grid step
(block ``(1, Hp, Wp, C)`` in, ``(1, Ho, Wo, C)`` out), looping the
``kh*kw`` window taps as strided slices combined on the VPU.  Max pads
with -inf (bf16: its finite min is not used — jnp.pad with -inf stays
representable) so padded positions never win; avg is supported when the
divisor is the constant ``kh*kw`` (padding == 0, or ``exclusive=False``
which divides by the full window size everywhere) — the
exclusive-with-padding case needs per-position counts and falls back to
the XLA lowering.

`supported()` mirrors the conv gates: NHWC, lane-aligned channels,
stride 1/2, small windows, VMEM budget.  Off-TPU runs in interpret mode
for CPU CI parity tests.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from paddle_tpu.ops.pallas import config as _cfg


VMEM_CAP_BYTES = 12 * 1024 * 1024


def _out_hw(size: int, k: int, stride: int, pad: int) -> int:
    return (size + 2 * pad - k) // stride + 1


def _pool_kernel(x_ref, o_ref, *, kh, kw, sh, sw, out_h, out_w, mode,
                 inv_count):
    x = x_ref[0].astype(jnp.float32)  # (Hp, Wp, C)
    c = x.shape[-1]
    if mode == "max":
        acc = jnp.full((out_h, out_w, c), -jnp.inf, jnp.float32)
    else:
        acc = jnp.zeros((out_h, out_w, c), jnp.float32)
    for i in range(kh):
        for j in range(kw):
            win = jax.lax.slice(
                x, (i, j, 0),
                (i + (out_h - 1) * sh + 1, j + (out_w - 1) * sw + 1, c),
                (sh, sw, 1))
            acc = jnp.maximum(acc, win) if mode == "max" else acc + win
    if mode == "avg":
        acc = acc * inv_count
    o_ref[0] = acc.astype(o_ref.dtype)


def supported(x, kernel, stride, padding, mode="max", exclusive=True,
              data_format="NHWC") -> bool:
    if data_format != "NHWC" or getattr(x, "ndim", 0) != 4:
        return False
    if x.dtype not in (jnp.float32, jnp.bfloat16):
        return False
    kh, kw = kernel
    sh, sw = stride
    ph, pw = padding
    # stride 1 only: Mosaic refuses the tap loop's stride-2 window slice
    # (see conv_fused.supported for the two refusals; ROADMAP S1) — which
    # leaves every ResNet/YOLO pool on lax.reduce_window
    if kh > 8 or kw > 8 or (sh, sw) != (1, 1):
        return False
    if mode == "avg" and exclusive and (ph or pw):
        return False  # needs per-position counts — XLA fallback
    n, h, w, c = x.shape
    if c % 128:
        return False
    out_h, out_w = _out_hw(h, kh, sh, ph), _out_hw(w, kw, sw, pw)
    if out_h <= 0 or out_w <= 0:
        return False
    itemsize = x.dtype.itemsize
    vmem = ((h + 2 * ph) * (w + 2 * pw) * c * 4
            + out_h * out_w * c * (4 + itemsize))
    return vmem <= VMEM_CAP_BYTES


def _pool2d_nhwc(x, kernel, stride, padding, mode, name):
    n, h, w, c = x.shape
    kh, kw = kernel
    sh, sw = stride
    ph, pw = padding
    out_h, out_w = _out_hw(h, kh, sh, ph), _out_hw(w, kw, sw, pw)
    pad_value = -jnp.inf if mode == "max" else 0.0
    xp = jnp.pad(x, ((0, 0), (ph, ph), (pw, pw), (0, 0)),
                 constant_values=pad_value)
    hp, wp = h + 2 * ph, w + 2 * pw
    kernel_fn = functools.partial(
        _pool_kernel, kh=kh, kw=kw, sh=sh, sw=sw, out_h=out_h, out_w=out_w,
        mode=mode, inv_count=1.0 / (kh * kw))
    _cfg.record_call(name)
    with jax.named_scope(f"pallas.{name}"):
        return pl.pallas_call(
            kernel_fn,
            grid=(n,),
            in_specs=[pl.BlockSpec((1, hp, wp, c), lambda i: (i, 0, 0, 0))],
            out_specs=pl.BlockSpec((1, out_h, out_w, c),
                                   lambda i: (i, 0, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((n, out_h, out_w, c), x.dtype),
            interpret=_cfg.interpret(),
        )(xp)


def max_pool2d_nhwc(x, kernel, stride, padding):
    return _pool2d_nhwc(x, kernel, stride, padding, "max", "max_pool2d")


def avg_pool2d_nhwc(x, kernel, stride, padding):
    """Mean over the full ``kh*kw`` window (padding contributes zeros) —
    exactly `_pool2d(..., lax.add) / prod(kernel)`; the caller gates the
    exclusive-with-padding case out via `supported()`."""
    return _pool2d_nhwc(x, kernel, stride, padding, "avg", "avg_pool2d")


def pool_cost(n, out_h, out_w, c, kh, kw, itemsize=4,
              in_h=None, in_w=None) -> Tuple[float, float]:
    """(flops, hbm bytes) for one pooling call — one compare/add per tap."""
    flops = float(n * out_h * out_w * c * kh * kw)
    in_h = in_h if in_h is not None else out_h
    in_w = in_w if in_w is not None else out_w
    return flops, float((n * in_h * in_w * c + n * out_h * out_w * c)
                        * itemsize)


def _pool_instr_flops(instr) -> float:
    # operand (n, hp, wp, c), output (n, oh, ow, c): taps from shape ratio
    if not instr.out_shapes or not instr.operand_shapes:
        return 0.0
    out = instr.out_shapes[0][1]
    if len(out) != 4:
        return 0.0
    n, oh, ow, c = out
    inp = instr.operand_shapes[0][1]
    taps = 9.0  # window size is not in the HLO; a 3x3 default keeps O(right)
    if len(inp) == 4 and oh and ow:
        taps = max(1.0, round((inp[1] * inp[2]) / float(oh * ow)))
    return n * oh * ow * c * taps


_cfg.register_cost("pallas.max_pool2d", _pool_instr_flops)
_cfg.register_cost("pallas.avg_pool2d", _pool_instr_flops)
