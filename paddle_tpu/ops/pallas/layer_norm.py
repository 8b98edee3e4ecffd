"""Pallas TPU fused LayerNorm: forward + backward kernels.

Reference parity: operators/layer_norm_op.cc (the reference's fused CUDA
LayerNorm kernel); on TPU the XLA lowering of the jnp composition costs ~3
HBM passes forward (f32 upcast + mean + var reduces) and ~5 backward.  This
kernel does one pass each way:

* Forward: grid over row blocks; each (block_rows, dim) tile is read once,
  mean/variance come from a single fused sum/sum-of-squares pair in f32
  registers, the normalized output is written in the input dtype, and the
  per-row (mean, rstd) statistics are saved for backward.
* Backward: one pass re-deriving x_hat from (x, mean, rstd) and emitting
  dx plus PER-BLOCK partial reductions for dweight/dbias; the tiny
  (n_blocks, dim) partials are summed outside the kernel.  dx uses the
  standard row-local identity
      dx = rstd * (g - mean_row(g) - x_hat * mean_row(g * x_hat)),
  g = dy * weight.

Matmul-free, so the only wins are HBM passes — measured on the ERNIE-base
flagship this halves LayerNorm's step share.  Stats are always f32
regardless of input dtype (the jnp path's "f32 stability" contract).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from paddle_tpu.ops.pallas import config as _cfg

DEFAULT_BLOCK_ROWS = 256


def _ln_fwd_kernel(x_ref, w_ref, b_ref, o_ref, mean_ref, rstd_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)  # (block_rows, dim)
    dim = x.shape[-1]
    mean = jnp.sum(x, axis=-1, keepdims=True) / dim
    # Two-pass variance: E[x^2]-E[x]^2 cancels catastrophically for
    # large-mean rows (|x|~1e3 wipes out an O(1) variance in f32).  The
    # tile is already in VMEM so the second reduction costs no HBM pass.
    centered = x - mean
    var = jnp.sum(centered * centered, axis=-1, keepdims=True) / dim
    rstd = jax.lax.rsqrt(var + eps)
    xhat = (x - mean) * rstd
    out = xhat * w_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    o_ref[...] = out.astype(o_ref.dtype)
    mean_ref[...] = mean[:, 0][None, :]
    rstd_ref[...] = rstd[:, 0][None, :]


def _ln_bwd_kernel(x_ref, w_ref, mean_ref, rstd_ref, dy_ref, dx_ref, dw_ref,
                   db_ref):
    x = x_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    dim = x.shape[-1]
    mean = mean_ref[0][:, None]
    rstd = rstd_ref[0][:, None]
    xhat = (x - mean) * rstd
    g = dy * w
    g_mean = jnp.sum(g, axis=-1, keepdims=True) / dim
    gx_mean = jnp.sum(g * xhat, axis=-1, keepdims=True) / dim
    dx = rstd * (g - g_mean - xhat * gx_mean)
    dx_ref[...] = dx.astype(dx_ref.dtype)
    # Partial dweight/dbias for this row block.  Mosaic requires the last
    # two block dims to be (8, 128)-divisible, so the (dim,) partial is
    # written into row 0 of an (8, dim) tile (rows 1-7 zero).
    row = jax.lax.broadcasted_iota(jnp.int32, (8, dim), 0)
    dw = jnp.sum(dy * xhat, axis=0, keepdims=True)
    db = jnp.sum(dy, axis=0, keepdims=True)
    dw_ref[0] = jnp.where(row == 0, dw, 0.0)
    db_ref[0] = jnp.where(row == 0, db, 0.0)


def _rows_block(n_rows: int) -> int:
    block = min(DEFAULT_BLOCK_ROWS, n_rows)
    while n_rows % block:
        block //= 2
    return max(block, 1)


def _fwd(x2, w, b, eps, block_rows, out_dtype):
    n, dim = x2.shape
    grid = (n // block_rows,)
    return pl.pallas_call(
        functools.partial(_ln_fwd_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, dim), lambda i: (i, 0)),
            pl.BlockSpec((1, dim), lambda i: (0, 0)),
            pl.BlockSpec((1, dim), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, dim), lambda i: (i, 0)),
            pl.BlockSpec((1, block_rows), lambda i: (0, i)),
            pl.BlockSpec((1, block_rows), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, dim), out_dtype),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
        ],
        interpret=_cfg.interpret(),
        name="ln_fwd",
    )(x2, w.reshape(1, dim), b.reshape(1, dim))


def _bwd(x2, w, mean, rstd, dy2, block_rows):
    n, dim = x2.shape
    n_blocks = n // block_rows
    dx, dw_part, db_part = pl.pallas_call(
        _ln_bwd_kernel,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((block_rows, dim), lambda i: (i, 0)),
            pl.BlockSpec((1, dim), lambda i: (0, 0)),
            pl.BlockSpec((1, block_rows), lambda i: (0, i)),
            pl.BlockSpec((1, block_rows), lambda i: (0, i)),
            pl.BlockSpec((block_rows, dim), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, dim), lambda i: (i, 0)),
            pl.BlockSpec((1, 8, dim), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 8, dim), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, dim), x2.dtype),
            jax.ShapeDtypeStruct((n_blocks, 8, dim), jnp.float32),
            jax.ShapeDtypeStruct((n_blocks, 8, dim), jnp.float32),
        ],
        interpret=_cfg.interpret(),
        name="ln_bwd",
    )(x2, w.reshape(1, dim), mean, rstd, dy2)
    return dx, dw_part.sum(axis=(0, 1)), db_part.sum(axis=(0, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _fused_ln(x2, w, b, eps, block_rows, out_dtype):
    out, _, _ = _fwd(x2, w, b, eps, block_rows, out_dtype)
    return out


def _fused_ln_fwd(x2, w, b, eps, block_rows, out_dtype):
    out, mean, rstd = _fwd(x2, w, b, eps, block_rows, out_dtype)
    return out, (x2, w, mean, rstd)


def _fused_ln_bwd(eps, block_rows, out_dtype, res, dy2):
    x2, w, mean, rstd = res
    dx, dw, db = _bwd(x2, w, mean, rstd, dy2, block_rows)
    return dx, dw.astype(w.dtype), db.astype(w.dtype)


_fused_ln.defvjp(_fused_ln_fwd, _fused_ln_bwd)


def supported(x, normalized_shape) -> bool:
    """Last-dim-only norm with a lane-aligned dim and a row count divisible
    by the 256-row block (keeps every Mosaic block (8,128)-tileable: the
    per-row stats outputs are (1, block_rows) tiles)."""
    if len(normalized_shape) != 1 or x.shape[-1] != normalized_shape[0]:
        return False
    dim = x.shape[-1]
    n = 1
    for s in x.shape[:-1]:
        n *= s
    return (dim % 128 == 0 and n % DEFAULT_BLOCK_ROWS == 0
            and x.dtype in (jnp.bfloat16, jnp.float32))


def fused_layer_norm(x, weight, bias, epsilon=1e-5):
    """LayerNorm over the last axis with weight and bias, via the fused
    kernel.  Callers must check ``supported()`` first.  The output dtype
    matches the jnp composition's promotion (x normalized, then scaled by
    weight/bias): result_type(x, weight, bias)."""
    orig_shape = x.shape
    dim = orig_shape[-1]
    n = x.size // dim
    out_dtype = jnp.result_type(x.dtype, weight.dtype, bias.dtype)
    x2 = x.reshape(n, dim)
    block_rows = _rows_block(n)
    _cfg.record_call("fused_layer_norm")
    with jax.named_scope("pallas.fused_layer_norm"):
        out = _fused_ln(x2, weight, bias, float(epsilon), block_rows,
                        out_dtype)
    return out.reshape(orig_shape)


# -- fused residual + dropout + LayerNorm ------------------------------------
#
# The post-LN transformer sublayer epilogue  out = LN(residual + dropout(x))
# costs XLA ~5 HBM passes forward and more backward (dropout mask
# materialization, the sum, LN stats, then the chain in reverse).  This
# kernel does forward in ONE pass (read x + residual, write out + stats) and
# backward in one (recompute the sum h and the keep mask in-register from
# the replayable per-tile hardware PRNG stream -- nothing but (x, residual)
# is re-read, no mask or h tensor ever hits HBM).

def _keep_tile(seed, tile_idx, shape, rate):
    """Keep-mask for one (block_rows, dim) tile; hardware PRNG on TPU
    (re-seeded per tile => replayable in backward), position hash in
    interpret mode (same contract as flash_attention's dropout)."""
    if not _cfg.interpret():
        from .flash_attention import _keep_from_hw_bits

        return _keep_from_hw_bits((seed, tile_idx), shape, rate)
    from .flash_attention import _dropout_keep

    rows = jax.lax.broadcasted_iota(jnp.int32, shape, 0) + tile_idx * shape[0]
    cols = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return _dropout_keep(seed, jnp.int32(0), rows, cols, rate)


def _rdln_fwd_kernel(seed_ref, x_ref, res_ref, w_ref, b_ref, o_ref, mean_ref,
                     rstd_ref, *, eps, rate):
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)
    res = res_ref[...].astype(jnp.float32)
    if rate > 0.0:
        keep = _keep_tile(seed_ref[0], i, x.shape, rate)
        x = jnp.where(keep, x / (1.0 - rate), 0.0)
    h = res + x
    dim = h.shape[-1]
    mean = jnp.sum(h, axis=-1, keepdims=True) / dim
    centered = h - mean
    var = jnp.sum(centered * centered, axis=-1, keepdims=True) / dim
    rstd = jax.lax.rsqrt(var + eps)
    out = (centered * rstd * w_ref[...].astype(jnp.float32)
           + b_ref[...].astype(jnp.float32))
    o_ref[...] = out.astype(o_ref.dtype)
    mean_ref[...] = mean[:, 0][None, :]
    rstd_ref[...] = rstd[:, 0][None, :]


def _rdln_bwd_kernel(seed_ref, x_ref, res_ref, w_ref, mean_ref, rstd_ref,
                     dy_ref, dx_ref, dres_ref, dw_ref, db_ref, *, rate):
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)
    res = res_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    if rate > 0.0:
        keep = _keep_tile(seed_ref[0], i, x.shape, rate)
        x = jnp.where(keep, x / (1.0 - rate), 0.0)
    h = res + x
    dim = h.shape[-1]
    mean = mean_ref[0][:, None]
    rstd = rstd_ref[0][:, None]
    xhat = (h - mean) * rstd
    g = dy * w
    g_mean = jnp.sum(g, axis=-1, keepdims=True) / dim
    gx_mean = jnp.sum(g * xhat, axis=-1, keepdims=True) / dim
    dh = rstd * (g - g_mean - xhat * gx_mean)
    dres_ref[...] = dh.astype(dres_ref.dtype)
    if rate > 0.0:
        dx = jnp.where(keep, dh / (1.0 - rate), 0.0)
    else:
        dx = dh
    dx_ref[...] = dx.astype(dx_ref.dtype)
    row = jax.lax.broadcasted_iota(jnp.int32, (8, dim), 0)
    dw = jnp.sum(dy * xhat, axis=0, keepdims=True)
    db = jnp.sum(dy, axis=0, keepdims=True)
    dw_ref[0] = jnp.where(row == 0, dw, 0.0)
    db_ref[0] = jnp.where(row == 0, db, 0.0)


def _rdln_fwd(x2, res2, w, b, seed, eps, rate, block_rows, out_dtype):
    n, dim = x2.shape
    return pl.pallas_call(
        functools.partial(_rdln_fwd_kernel, eps=eps, rate=rate),
        grid=(n // block_rows,),
        in_specs=[
            pl.BlockSpec(memory_space=_smem_space()),
            pl.BlockSpec((block_rows, dim), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, dim), lambda i: (i, 0)),
            pl.BlockSpec((1, dim), lambda i: (0, 0)),
            pl.BlockSpec((1, dim), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, dim), lambda i: (i, 0)),
            pl.BlockSpec((1, block_rows), lambda i: (0, i)),
            pl.BlockSpec((1, block_rows), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, dim), out_dtype),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
        ],
        interpret=_cfg.interpret(),
        name="rdln_fwd",
    )(seed, x2, res2, w.reshape(1, dim), b.reshape(1, dim))


def _rdln_bwd(x2, res2, w, mean, rstd, seed, dy2, rate, block_rows):
    n, dim = x2.shape
    n_blocks = n // block_rows
    dx, dres, dw_part, db_part = pl.pallas_call(
        functools.partial(_rdln_bwd_kernel, rate=rate),
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec(memory_space=_smem_space()),
            pl.BlockSpec((block_rows, dim), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, dim), lambda i: (i, 0)),
            pl.BlockSpec((1, dim), lambda i: (0, 0)),
            pl.BlockSpec((1, block_rows), lambda i: (0, i)),
            pl.BlockSpec((1, block_rows), lambda i: (0, i)),
            pl.BlockSpec((block_rows, dim), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, dim), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, dim), lambda i: (i, 0)),
            pl.BlockSpec((1, 8, dim), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 8, dim), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, dim), x2.dtype),
            jax.ShapeDtypeStruct((n, dim), res2.dtype),
            jax.ShapeDtypeStruct((n_blocks, 8, dim), jnp.float32),
            jax.ShapeDtypeStruct((n_blocks, 8, dim), jnp.float32),
        ],
        interpret=_cfg.interpret(),
        name="rdln_bwd",
    )(seed, x2, res2, w.reshape(1, dim), mean, rstd, dy2)
    return dx, dres, dw_part.sum(axis=(0, 1)), db_part.sum(axis=(0, 1))


def _smem_space():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.SMEM


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _fused_rdln(x2, res2, w, b, seed, eps, rate, block_rows, out_dtype):
    out, _, _ = _rdln_fwd(x2, res2, w, b, seed, eps, rate, block_rows,
                          out_dtype)
    return out


def _fused_rdln_vjp_fwd(x2, res2, w, b, seed, eps, rate, block_rows,
                        out_dtype):
    out, mean, rstd = _rdln_fwd(x2, res2, w, b, seed, eps, rate, block_rows,
                                out_dtype)
    return out, (x2, res2, w, mean, rstd, seed)


def _fused_rdln_vjp_bwd(eps, rate, block_rows, out_dtype, resids, dy2):
    x2, res2, w, mean, rstd, seed = resids
    dx, dres, dw, db = _rdln_bwd(x2, res2, w, mean, rstd, seed, dy2, rate,
                                 block_rows)
    return dx, dres, dw.astype(w.dtype), db.astype(w.dtype), None


_fused_rdln.defvjp(_fused_rdln_vjp_fwd, _fused_rdln_vjp_bwd)


def fused_residual_dropout_layer_norm(x, residual, weight, bias,
                                      dropout_rate=0.0, seed=None,
                                      epsilon=1e-5):
    """out = LayerNorm(residual + dropout(x)) in one HBM pass per direction.
    Callers must check ``supported()`` (same shape contract as
    fused_layer_norm).  ``seed`` is an int32 scalar array driving the
    in-kernel keep mask when ``dropout_rate > 0``."""
    orig_shape = x.shape
    dim = orig_shape[-1]
    n = x.size // dim
    out_dtype = jnp.result_type(x.dtype, residual.dtype, weight.dtype,
                                bias.dtype)
    if seed is None:
        seed = jnp.zeros((1,), jnp.int32)
    else:
        seed = jnp.asarray(seed, jnp.int32).reshape((1,))
    _cfg.record_call("fused_rdln")
    with jax.named_scope("pallas.fused_rdln"):
        out = _fused_rdln(x.reshape(n, dim), residual.reshape(n, dim),
                          weight, bias, seed, float(epsilon),
                          float(dropout_rate), _rows_block(n), out_dtype)
    return out.reshape(orig_shape)
