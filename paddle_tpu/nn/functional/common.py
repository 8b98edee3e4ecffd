"""Common functional ops: linear, dropout, pad, interpolate (ref: python/
paddle/nn/functional/common.py; operators/dropout_op.cc, pad_op.cc,
interpolate_v2)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core import random as _random


def linear(x, weight, bias=None, out_dtype=None):
    """ref: mul/matmul+elementwise_add fusion (fc op). weight: (in, out).
    `out_dtype`: keep the product's sum + bias in that dtype (float32 for an
    activation that rounds once itself) instead of the operands'."""
    out = jnp.matmul(x, weight, preferred_element_type=out_dtype)
    if bias is not None:
        out = out + bias
    return out if out_dtype is None else out.astype(out_dtype)


def dropout(x, p=0.5, training=True, mode="upscale_in_train"):
    """ref: operators/dropout_op.cc — two modes match the reference:
    upscale_in_train (default, inverted dropout) and downscale_in_infer."""
    if p == 0.0:
        return x
    if not training:
        return x if mode == "upscale_in_train" else x * (1.0 - p)
    keep = jax.random.bernoulli(_random.next_key(), 1.0 - p, x.shape)
    if mode == "upscale_in_train":
        return jnp.where(keep, x / (1.0 - p), jnp.zeros((), x.dtype))
    return jnp.where(keep, x, jnp.zeros((), x.dtype))


def dropout2d(x, p=0.5, training=True):
    """Channel-wise dropout for NCHW."""
    if p == 0.0 or not training:
        return x
    keep = jax.random.bernoulli(_random.next_key(), 1.0 - p,
                                x.shape[:2] + (1,) * (x.ndim - 2))
    return jnp.where(keep, x / (1.0 - p), jnp.zeros((), x.dtype))


def pad(x, pad, mode="constant", value=0.0, data_format="NCHW"):
    """ref: paddle.nn.functional.pad (common.py:1127) / pad2d/pad3d ops.

    Partial specs follow paddle's LAST-DIM-FIRST pair order: 4-D NCHW input
    with pad=(l, r, t, b) pads W by (l, r) and H by (t, b); a full
    2*ndim spec is per-dim in dim order."""
    if len(pad) == 2 * x.ndim:
        cfg = [(pad[2 * i], pad[2 * i + 1]) for i in range(x.ndim)]
    else:
        n_spatial = len(pad) // 2
        pairs = [(pad[2 * i], pad[2 * i + 1]) for i in range(n_spatial)]
        cfg = [(0, 0)] * (x.ndim - n_spatial) + pairs[::-1]
        if data_format.endswith("C"):  # channels-last: spatial dims before C
            cfg = ([(0, 0)] + cfg[2:] + [(0, 0)])[: x.ndim]
    jmode = {"constant": "constant", "reflect": "reflect", "replicate": "edge",
             "circular": "wrap"}[mode]
    if jmode == "constant":
        return jnp.pad(x, cfg, mode=jmode, constant_values=value)
    return jnp.pad(x, cfg, mode=jmode)


def _axis_coords(out_n, in_n, align_corners, clip=True):
    if align_corners and out_n > 1:
        return jnp.linspace(0, in_n - 1, out_n)
    cs = (jnp.arange(out_n) + 0.5) * in_n / out_n - 0.5
    # bicubic keeps raw (possibly negative) coords: the kernel weights come
    # from the unclipped fraction, only tap *indices* clamp to the edge
    return jnp.clip(cs, 0, in_n - 1) if clip else cs


def _cubic_weights(t, a=-0.75):
    """Keys cubic-convolution weights for the 4 taps around t (ref
    bicubic_interp_v2_op.h cubic_interp1d)."""
    d = t - jnp.floor(t)
    x1, x0, xm1, xm2 = 1 + d, d, 1 - d, 2 - d
    w0 = a * x1 ** 3 - 5 * a * x1 ** 2 + 8 * a * x1 - 4 * a
    w1 = (a + 2) * x0 ** 3 - (a + 3) * x0 ** 2 + 1
    w2 = (a + 2) * xm1 ** 3 - (a + 3) * xm1 ** 2 + 1
    w3 = a * xm2 ** 3 - 5 * a * xm2 ** 2 + 8 * a * xm2 - 4 * a
    return (w0, w1, w2, w3)


def interpolate(x, size=None, scale_factor=None, mode="nearest",
                align_corners=False, data_format="NCHW"):
    """ref: operators/interpolate_v2_op.cc (nearest/linear/bilinear/bicubic
    on NCHW; trilinear on NCDHW)."""
    if mode == "trilinear":
        n, c, d, h, w = x.shape
        if size is None:
            sf = scale_factor if isinstance(scale_factor, (tuple, list)) \
                else (scale_factor,) * 3
            size = (int(d * sf[0]), int(h * sf[1]), int(w * sf[2]))
        od, oh, ow = size
        out = x
        for axis, (o, i) in zip((2, 3, 4), ((od, d), (oh, h), (ow, w))):
            cs = _axis_coords(o, i, align_corners)
            c0 = jnp.floor(cs).astype(jnp.int32)
            c1 = jnp.clip(c0 + 1, 0, i - 1)
            frac = (cs - c0).reshape((1,) * axis + (-1,) +
                                     (1,) * (4 - axis))
            out = (jnp.take(out, c0, axis=axis) * (1 - frac) +
                   jnp.take(out, c1, axis=axis) * frac)
        return out.astype(x.dtype)
    if data_format == "NHWC":
        x = jnp.transpose(x, (0, 3, 1, 2))
    n, c, h, w = x.shape
    if mode == "bicubic":
        if size is None:
            sf = scale_factor if isinstance(scale_factor, (tuple, list)) \
                else (scale_factor, scale_factor)
            size = (int(h * sf[0]), int(w * sf[1]))
        oh, ow = size
        out = x
        for axis, (o, i) in zip((2, 3), ((oh, h), (ow, w))):
            cs = _axis_coords(o, i, align_corners, clip=False)
            base = jnp.floor(cs).astype(jnp.int32)
            ws = _cubic_weights(cs)
            acc = 0.0
            for tap, wgt in zip((-1, 0, 1, 2), ws):
                idx = jnp.clip(base + tap, 0, i - 1)
                shape = (1,) * axis + (-1,) + (1,) * (3 - axis)
                acc = acc + jnp.take(out, idx, axis=axis) * wgt.reshape(shape)
            out = acc
        if data_format == "NHWC":
            out = jnp.transpose(out, (0, 2, 3, 1))
        return out.astype(x.dtype)
    if size is None:
        sf = scale_factor if isinstance(scale_factor, (tuple, list)) else (
            scale_factor, scale_factor)
        size = (int(h * sf[0]), int(w * sf[1]))
    oh, ow = size
    if mode == "nearest":
        if align_corners and oh > 1 and ow > 1:
            # corner-aligned grid (ref interpolate_v2 nearest w/ align_corners)
            ridx = jnp.round(jnp.arange(oh) * (h - 1) / (oh - 1)).astype(
                jnp.int32)
            cidx = jnp.round(jnp.arange(ow) * (w - 1) / (ow - 1)).astype(
                jnp.int32)
        else:
            ridx = (jnp.arange(oh) * (h / oh)).astype(jnp.int32)
            cidx = (jnp.arange(ow) * (w / ow)).astype(jnp.int32)
        out = x[:, :, ridx][:, :, :, cidx]
    elif mode in ("bilinear", "linear"):
        if align_corners and oh > 1 and ow > 1:
            rs = jnp.linspace(0, h - 1, oh)
            cs = jnp.linspace(0, w - 1, ow)
        else:
            rs = jnp.clip((jnp.arange(oh) + 0.5) * h / oh - 0.5, 0, h - 1)
            cs = jnp.clip((jnp.arange(ow) + 0.5) * w / ow - 0.5, 0, w - 1)
        r0 = jnp.floor(rs).astype(jnp.int32)
        c0 = jnp.floor(cs).astype(jnp.int32)
        r1 = jnp.clip(r0 + 1, 0, h - 1)
        c1 = jnp.clip(c0 + 1, 0, w - 1)
        wr = (rs - r0)[None, None, :, None]
        wc = (cs - c0)[None, None, None, :]
        g = lambda ri, ci: x[:, :, ri][:, :, :, ci]
        out = (g(r0, c0) * (1 - wr) * (1 - wc) + g(r1, c0) * wr * (1 - wc) +
               g(r0, c1) * (1 - wr) * wc + g(r1, c1) * wr * wc).astype(x.dtype)
    else:
        raise NotImplementedError(f"interpolate mode {mode!r}")
    if data_format == "NHWC":
        out = jnp.transpose(out, (0, 2, 3, 1))
    return out


def upsample(x, size=None, scale_factor=None, mode="nearest", align_corners=False):
    return interpolate(x, size=size, scale_factor=scale_factor, mode=mode,
                       align_corners=align_corners)


def cosine_similarity(x1, x2, axis=1, eps=1e-8):
    dot = jnp.sum(x1 * x2, axis=axis)
    n1 = jnp.linalg.norm(x1, axis=axis)
    n2 = jnp.linalg.norm(x2, axis=axis)
    return dot / jnp.maximum(n1 * n2, eps)


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1):
    """ref: operators/unfold_op.cc (im2col).  x: (N, C, H, W) ->
    (N, C*kh*kw, L)."""
    from jax import lax

    kh, kw = (kernel_sizes if isinstance(kernel_sizes, (list, tuple))
              else (kernel_sizes, kernel_sizes))
    sh, sw = (strides if isinstance(strides, (list, tuple)) else (strides, strides))
    ph, pw = (paddings if isinstance(paddings, (list, tuple)) else (paddings, paddings))
    dh, dw = (dilations if isinstance(dilations, (list, tuple)) else (dilations, dilations))
    patches = lax.conv_general_dilated_patches(
        x, (kh, kw), (sh, sw), [(ph, ph), (pw, pw)], rhs_dilation=(dh, dw),
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    n, ckk, oh, ow = patches.shape
    return patches.reshape(n, ckk, oh * ow)
