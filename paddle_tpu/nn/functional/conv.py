"""Convolutions (ref: python/paddle/nn/functional/conv.py; operators/
conv_op.cc + conv_cudnn_op.cu).  TPU-native: lax.conv_general_dilated lowers
straight to XLA convolution, which the TPU compiler maps onto the MXU —
the reference's cuDNN algo-search machinery has no equivalent here.
Data layout follows the reference default NCHW.
"""
from __future__ import annotations

from typing import Sequence, Union

import jax.numpy as jnp
from jax import lax


def _pair(v, n=2):
    if isinstance(v, (list, tuple)):
        return tuple(v)
    return (v,) * n


def _padding(padding, spatial_dims):
    if isinstance(padding, str):
        return padding.upper()  # SAME / VALID
    p = _pair(padding, spatial_dims)
    if len(p) == spatial_dims:
        return [(int(x), int(x)) for x in p]
    # ((before, after), ...) form
    return [tuple(map(int, x)) for x in p]


def conv2d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCHW"):
    """x: (N, C, H, W) or (N, H, W, C); weight: (out_c, in_c/groups, kh, kw)
    — ref layouts.  NHWC is a NATIVE path (dimension_numbers carry the
    layout straight into XLA, no transposes): channels-last keeps C on the
    128-lane minor dimension the TPU vector units and MXU feeds want, so
    the compiler stops materializing layout conversions around every conv."""
    if data_format == "NHWC":
        out = lax.conv_general_dilated(
            x, weight,
            window_strides=_pair(stride),
            padding=_padding(padding, 2),
            rhs_dilation=_pair(dilation),
            feature_group_count=groups,
            dimension_numbers=("NHWC", "OIHW", "NHWC"),
        )
        if bias is not None:
            out = out + bias.reshape(1, 1, 1, -1)
        return out
    out = lax.conv_general_dilated(
        x, weight,
        window_strides=_pair(stride),
        padding=_padding(padding, 2),
        rhs_dilation=_pair(dilation),
        feature_group_count=groups,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
    )
    if bias is not None:
        out = out + bias.reshape(1, -1, 1, 1)
    return out


def conv1d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1):
    """x: (N, C, L); weight: (out_c, in_c/groups, k)."""
    out = lax.conv_general_dilated(
        x, weight,
        window_strides=_pair(stride, 1),
        padding=_padding(padding, 1),
        rhs_dilation=_pair(dilation, 1),
        feature_group_count=groups,
        dimension_numbers=("NCH", "OIH", "NCH"),
    )
    if bias is not None:
        out = out + bias.reshape(1, -1, 1)
    return out


def conv3d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1):
    out = lax.conv_general_dilated(
        x, weight,
        window_strides=_pair(stride, 3),
        padding=_padding(padding, 3),
        rhs_dilation=_pair(dilation, 3),
        feature_group_count=groups,
        dimension_numbers=("NCDHW", "OIDHW", "NCDHW"),
    )
    if bias is not None:
        out = out + bias.reshape(1, -1, 1, 1, 1)
    return out


def conv2d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, dilation=1, groups=1):
    """ref: operators/conv_transpose_op.cc. weight: (in_c, out_c/groups, kh, kw)."""
    stride = _pair(stride)
    padding = _pair(padding)
    dilation = _pair(dilation)
    output_padding = _pair(output_padding)
    kh = (weight.shape[2] - 1) * dilation[0] + 1
    kw = (weight.shape[3] - 1) * dilation[1] + 1
    pad = [
        (kh - 1 - padding[0], kh - 1 - padding[0] + output_padding[0]),
        (kw - 1 - padding[1], kw - 1 - padding[1] + output_padding[1]),
    ]
    if groups != 1:
        xs = jnp.split(x, groups, axis=1)
        ws = jnp.split(weight, groups, axis=0)
        outs = [_deconv_single(xi, wi, stride, pad, dilation) for xi, wi in zip(xs, ws)]
        out = jnp.concatenate(outs, axis=1)
    else:
        out = _deconv_single(x, weight, stride, pad, dilation)
    if bias is not None:
        out = out + bias.reshape(1, -1, 1, 1)
    return out


def _deconv_single(x, weight, stride, pad, dilation):
    # flip spatial dims and swap in/out channels -> regular conv with lhs dilation
    w = jnp.flip(weight, axis=(2, 3)).swapaxes(0, 1)
    return lax.conv_general_dilated(
        x, w,
        window_strides=(1, 1),
        padding=pad,
        lhs_dilation=stride,
        rhs_dilation=dilation,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
    )


def conv3d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, dilation=1, groups=1):
    """ref: conv3d_transpose (conv_transpose_op.cc); weight layout
    (in_c, out_c/groups, kd, kh, kw) like conv2d_transpose."""
    stride = _pair(stride, 3)
    padding = _pair(padding, 3)
    dilation = _pair(dilation, 3)
    output_padding = _pair(output_padding, 3)

    def one(x, w):
        wf = jnp.flip(w, axis=(2, 3, 4)).swapaxes(0, 1)
        ks = [(w.shape[2 + i] - 1) * dilation[i] + 1 for i in range(3)]
        pads = [(ks[i] - 1 - padding[i],
                 ks[i] - 1 - padding[i] + output_padding[i])
                for i in range(3)]
        return lax.conv_general_dilated(
            x, wf, window_strides=(1, 1, 1), padding=pads,
            lhs_dilation=stride, rhs_dilation=dilation,
            dimension_numbers=("NCDHW", "OIDHW", "NCDHW"))

    if groups > 1:
        xs = jnp.split(x, groups, axis=1)
        ws = jnp.split(weight, groups, axis=0)
        out = jnp.concatenate([one(xi, wi) for xi, wi in zip(xs, ws)], axis=1)
    else:
        out = one(x, weight)
    if bias is not None:
        out = out + bias.reshape(1, -1, 1, 1, 1)
    return out
