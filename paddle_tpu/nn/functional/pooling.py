"""Pooling ops (ref: operators/pool_op.cc; python/paddle/nn/functional/
pooling.py).  lax.reduce_window lowers to XLA ReduceWindow (VPU-friendly)."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
from jax import lax


def _pair(v, n=2):
    return tuple(v) if isinstance(v, (list, tuple)) else (v,) * n


def _use_pallas_pool(x, kernel, stride, pads, mode, exclusive,
                     data_format) -> bool:
    """Gate for the NHWC-native Pallas pooling kernels: flag + TPU backend
    (ops.pallas.config, patched by tests) + per-shape support.  Off or
    unsupported: the lax.reduce_window path below, bitwise identical."""
    from ...ops.pallas import config as _pcfg

    if not _pcfg.kernel_enabled("use_pallas_pool"):
        return False
    from ...ops.pallas import pooling as _pool

    return _pcfg.counted(
        f"{mode}_pool2d", _pool.supported(x, kernel, stride, pads, mode,
                                          exclusive, data_format))


def _pool2d(x, kernel, stride, padding, init, op, norm=None,
            data_format="NCHW"):
    kernel = _pair(kernel)
    stride = _pair(stride if stride is not None else kernel)
    pads = _pair(padding)
    if data_format == "NHWC":
        window = (1,) + kernel + (1,)
        strides = (1,) + stride + (1,)
        padding_cfg = [(0, 0), (pads[0], pads[0]), (pads[1], pads[1]), (0, 0)]
    else:
        window = (1, 1) + kernel
        strides = (1, 1) + stride
        padding_cfg = [(0, 0), (0, 0), (pads[0], pads[0]), (pads[1], pads[1])]
    out = lax.reduce_window(x, init, op, window, strides, padding_cfg)
    if norm is not None:
        out = norm(out, kernel, stride, pads, x.shape)
    return out


def max_pool2d(x, kernel_size, stride=None, padding=0, return_mask=False,
               data_format="NCHW"):
    if return_mask:
        # index mask (ref: max_pool2d_with_index) computed via broadcast compare
        raise NotImplementedError("return_mask is not supported yet")
    kernel = _pair(kernel_size)
    strides = _pair(stride if stride is not None else kernel)
    pads = _pair(padding)
    if _use_pallas_pool(x, kernel, strides, pads, "max", True, data_format):
        from ...ops.pallas import pooling as _pool

        return _pool.max_pool2d_nhwc(x, kernel, strides, pads)
    return _pool2d(x, kernel_size, stride, padding, -jnp.inf, lax.max,
                   data_format=data_format)


def avg_pool2d(x, kernel_size, stride=None, padding=0, exclusive=True,
               data_format="NCHW"):
    kernel = _pair(kernel_size)
    strides = _pair(stride if stride is not None else kernel)
    pads = _pair(padding)
    if _use_pallas_pool(x, kernel, strides, pads, "avg", exclusive,
                        data_format):
        from ...ops.pallas import pooling as _pool

        return _pool.avg_pool2d_nhwc(x, kernel, strides, pads)
    if padding == 0 or not exclusive:
        out = _pool2d(x, kernel_size, stride, padding, 0.0, lax.add,
                      data_format=data_format)
        return out / float(np.prod(kernel))
    # exclusive: divide by actual window size (count non-pad elements)
    s = _pool2d(x, kernel_size, stride, padding, 0.0, lax.add,
                data_format=data_format)
    ones = jnp.ones_like(x)
    cnt = _pool2d(ones, kernel_size, stride, padding, 0.0, lax.add,
                  data_format=data_format)
    return s / cnt


def max_pool1d(x, kernel_size, stride=None, padding=0):
    out = max_pool2d(x[..., None], (_pair(kernel_size, 1)[0], 1),
                     None if stride is None else (_pair(stride, 1)[0], 1),
                     (_pair(padding, 1)[0], 0))
    return out[..., 0]


def avg_pool1d(x, kernel_size, stride=None, padding=0, exclusive=True):
    out = avg_pool2d(x[..., None], (_pair(kernel_size, 1)[0], 1),
                     None if stride is None else (_pair(stride, 1)[0], 1),
                     (_pair(padding, 1)[0], 0), exclusive=exclusive)
    return out[..., 0]


def _adaptive_pool2d(x, output_size, reduce_fn, data_format):
    """Divisible dims: one reshape+reduce.  General case: per-output-bin
    slices (reference AdaptivePool bin edges (i*h)//oh .. ceil((i+1)h/oh)),
    axes parameterized by layout."""
    oh, ow = _pair(output_size)
    if data_format == "NHWC":
        n, h, w, c = x.shape
        if h % oh == 0 and w % ow == 0:
            return reduce_fn(x.reshape(n, oh, h // oh, ow, w // ow, c),
                             (2, 4))
        ha, wa = 1, 2
    else:
        n, c, h, w = x.shape
        if h % oh == 0 and w % ow == 0:
            return reduce_fn(x.reshape(n, c, oh, h // oh, ow, w // ow),
                             (3, 5))
        ha, wa = 2, 3
    # each bin reduces to (n, c); spatial axes re-enter at `ha` so the
    # result is (n, c, oh, ow) for NCHW and (n, oh, ow, c) for NHWC
    rows = [lax.slice_in_dim(x, (i * h) // oh, -(-((i + 1) * h) // oh),
                             axis=ha) for i in range(oh)]
    out_rows = []
    for r in rows:
        cols = [reduce_fn(
            lax.slice_in_dim(r, (j * w) // ow, -(-((j + 1) * w) // ow),
                             axis=wa), (ha, wa)) for j in range(ow)]
        out_rows.append(jnp.stack(cols, axis=ha))
    return jnp.stack(out_rows, axis=ha)


def adaptive_avg_pool2d(x, output_size, data_format="NCHW"):
    return _adaptive_pool2d(x, output_size, jnp.mean, data_format)


def adaptive_max_pool2d(x, output_size, data_format="NCHW"):
    return _adaptive_pool2d(x, output_size, jnp.max, data_format)


def _pool3d(x, kernel, stride, padding, init, op):
    kernel = _pair(kernel, 3)
    stride = _pair(stride if stride is not None else kernel, 3)
    pads = _pair(padding, 3)
    window = (1, 1) + kernel
    strides = (1, 1) + stride
    padding_cfg = [(0, 0), (0, 0)] + [(p, p) for p in pads]
    return lax.reduce_window(x, init, op, window, strides, padding_cfg)


def max_pool3d(x, kernel_size, stride=None, padding=0):
    """ref operators/pool_op.cc pool3d (max): NCDHW reduce_window."""
    return _pool3d(x, kernel_size, stride, padding, -jnp.inf, lax.max)


def avg_pool3d(x, kernel_size, stride=None, padding=0, exclusive=True):
    """ref pool3d (avg); ``exclusive`` divides by the non-pad window count."""
    s = _pool3d(x, kernel_size, stride, padding, 0.0, lax.add)
    if padding == 0 or (isinstance(padding, (list, tuple))
                        and not any(padding)) or not exclusive:
        kernel = _pair(kernel_size, 3)
        return s / float(np.prod(kernel))
    cnt = _pool3d(jnp.ones_like(x), kernel_size, stride, padding, 0.0,
                  lax.add)
    return s / cnt


def adaptive_avg_pool3d(x, output_size):
    od, oh, ow = _pair(output_size, 3)
    n, c, d, h, w = x.shape
    if d % od == 0 and h % oh == 0 and w % ow == 0:
        return jnp.mean(
            x.reshape(n, c, od, d // od, oh, h // oh, ow, w // ow),
            axis=(3, 5, 7))
    raise NotImplementedError(
        "adaptive_avg_pool3d requires divisible spatial dims")
