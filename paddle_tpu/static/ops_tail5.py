"""Static-op long tail, batch 5: v1 aliases + the remaining numeric tail
from the registry audit (tests/test_registry_exhaustive.py enforces that
everything NOT here or in earlier batches has a recorded rationale in
static/op_coverage.py).

Reference parity targets: reshape_op.cc / transpose_op.cc v1 forms,
allclose_op.cc, bernoulli (distribution ops), eye_op.cc, fill_op.cc,
diag_v2/diag_embed, histogram_op.cc, randint/randperm, sampling_id_op.h,
seed_op.cc, modified_huber_loss_op.h, add_position_encoding_op.h,
amp/check_finite_and_unscale + update_loss_scaling (+ the v1
amp_check_finite_and_scale), fake_init, bilinear_tensor_product_op.h,
*_batch_size_like random ops, flatten_contiguous_range (flatten_op.cc),
the dequantize family (fake_dequantize_op.cc, dequantize_abs_max_op.cc,
dequantize_log_op.cc), fake_quantize_moving_average_abs_max
(fake_quantize_op.cc), average_accumulates_op.h (ModelAverage),
precision_recall_op.h, spp_op.h, polygon_box_transform_op.cc,
random_crop_op.h, hsigmoid (hierarchical_sigmoid_op.h +
math/matrix_bit_code.h), and the SSD training-assignment trio
bipartite_match_op.cc / target_assign_op.h / mine_hard_examples_op.cc.

TPU-native notes:
- Dynamic-size outputs keep the padded + valid-count contract of batch 4
  (mine_hard_examples' NegIndices is (B, P) padded with -1).
- bipartite_match's greedy global-argmax loop runs as a lax.fori_loop
  over ROWS (#gt, small) with a full (rows, cols) mask update per step —
  the data-dependent `while (row_pool)` of the reference is a fixed
  row-count loop here because each iteration always matches exactly one
  remaining row (or none when no positive dist remains).
- hierarchical_sigmoid implements the default complete-binary-tree code
  (ref math/matrix_bit_code.h SimpleCode) vectorized over a static
  max-code-length; the custom-tree (PathTable/PathCode) inputs are
  accepted and used when present.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core import dtype as _dtype_mod
from ..core import random as _random
from .registry import get_lowering, register_op


def _one(ins, slot):
    vs = ins.get(slot, [])
    return vs[0] if vs else None


# =========================================================================
# v1 aliases: the v2 rule already implements the math; extra output slots
# (XShape) are bound only when declared
# =========================================================================

for _v1, _v2 in [("reshape", "reshape2"), ("transpose", "transpose2"),
                 ("sequence_softmax", "sequence_softmax_padded"),
                 ("multiclass_nms2", "multiclass_nms"),
                 ("merge_lod_tensor_infer", "merge_lod_tensor")]:
    register_op(_v1)(get_lowering(_v2))


@register_op("allreduce")
def _allreduce(ins, attrs, op):
    """ref collective/allreduce_op.h: red_type 0..3 = sum/prod/max/min."""
    red = {0: "c_allreduce_sum", 1: "c_allreduce_prod",
           2: "c_allreduce_max", 3: "c_allreduce_min"}[
        int(attrs.get("reduce_type", 0))]
    return get_lowering(red)(ins, attrs, op)


register_op("broadcast")(lambda ins, attrs, op:
                         get_lowering("c_broadcast")(ins, attrs, op))


# =========================================================================
# easy numeric tail
# =========================================================================

@register_op("allclose")
def _allclose(ins, attrs, op):
    x, y = _one(ins, "Input"), _one(ins, "Other")
    rtol = float(attrs.get("rtol", 1e-5))
    atol = float(attrs.get("atol", 1e-8))
    close = jnp.abs(x - y) <= atol + rtol * jnp.abs(y)
    if attrs.get("equal_nan", False):
        close = close | (jnp.isnan(x) & jnp.isnan(y))
    return {"Out": [jnp.all(close)]}


@register_op("bernoulli")
def _bernoulli(ins, attrs, op):
    x = _one(ins, "X")
    u = jax.random.uniform(_random.next_key(), x.shape)
    return {"Out": [(u < x).astype(x.dtype)]}


@register_op("eye")
def _eye(ins, attrs, op):
    rows = int(attrs["num_rows"])
    cols = int(attrs.get("num_columns", -1))
    dtype = _dtype_mod.convert_dtype(attrs.get("dtype", "float32"))
    return {"Out": [jnp.eye(rows, cols if cols > 0 else rows, dtype=dtype)]}


@register_op("fill")
def _fill(ins, attrs, op):
    """ref fill_op.cc: tensor from an attr value list + shape."""
    dtype = _dtype_mod.convert_dtype(attrs.get("dtype", "float32"))
    vals = jnp.asarray(np.asarray(attrs["value"], np.float64), dtype)
    return {"Out": [vals.reshape(tuple(attrs["shape"]))]}


@register_op("fill_zeros_like2")
def _fill_zeros_like2(ins, attrs, op):
    return {"Out": [jnp.zeros_like(_one(ins, "X"))]}


@register_op("diag_v2")
def _diag_v2(ins, attrs, op):
    x = _one(ins, "X")
    offset = int(attrs.get("offset", 0))
    if x.ndim == 1:
        n = x.shape[0] + abs(offset)
        base = jnp.full((n, n),
                        jnp.asarray(attrs.get("padding_value", 0), x.dtype))
        i = jnp.arange(x.shape[0])
        r, c = (i, i + offset) if offset >= 0 else (i - offset, i)
        return {"Out": [base.at[r, c].set(x)]}
    return {"Out": [jnp.diagonal(x, offset)]}


@register_op("diag_embed")
def _diag_embed(ins, attrs, op):
    x = _one(ins, "X")
    offset = int(attrs.get("offset", 0))
    dim1 = int(attrs.get("dim1", -2))
    dim2 = int(attrs.get("dim2", -1))
    n = x.shape[-1] + abs(offset)
    i = jnp.arange(x.shape[-1])
    r, c = (i, i + offset) if offset >= 0 else (i - offset, i)
    # the (n, n) buffer IS the output  # proglint: dense-intermediate-ok
    out = jnp.zeros(x.shape[:-1] + (n, n), x.dtype).at[..., r, c].set(x)
    nd = out.ndim
    d1, d2 = dim1 % nd, dim2 % nd
    if (d1, d2) != (nd - 2, nd - 1):
        out = jnp.moveaxis(out, (nd - 2, nd - 1), (d1, d2))
    return {"Out": [out]}


@register_op("histogram")
def _histogram(ins, attrs, op):
    x = _one(ins, "X").ravel().astype(jnp.float32)
    bins = int(attrs.get("bins", 100))
    lo = float(attrs.get("min", 0))
    hi = float(attrs.get("max", 0))
    if lo == hi == 0:
        lo_t, hi_t = jnp.min(x), jnp.max(x)
        hi_t = jnp.where(hi_t == lo_t, lo_t + 1, hi_t)
    else:
        lo_t, hi_t = jnp.asarray(lo, x.dtype), jnp.asarray(hi, x.dtype)
    idx = jnp.clip(((x - lo_t) / (hi_t - lo_t) * bins).astype(jnp.int32),
                   0, bins - 1)
    inside = (x >= lo_t) & (x <= hi_t)
    counts = jnp.zeros((bins,), jnp.int64).at[
        jnp.where(inside, idx, bins)].add(1, mode="drop")
    return {"Out": [counts]}


@register_op("randint")
def _randint(ins, attrs, op):
    dtype = _dtype_mod.convert_dtype(attrs.get("dtype", "int64"))
    return {"Out": [jax.random.randint(
        _random.next_key(), tuple(attrs["shape"]),
        int(attrs.get("low", 0)), int(attrs.get("high", 100))).astype(dtype)]}


@register_op("randperm")
def _randperm(ins, attrs, op):
    dtype = _dtype_mod.convert_dtype(attrs.get("dtype", "int64"))
    return {"Out": [jax.random.permutation(
        _random.next_key(), int(attrs["n"])).astype(dtype)]}


@register_op("sampling_id")
def _sampling_id(ins, attrs, op):
    """ref sampling_id_op.h: per row, inverse-CDF sample over the prob
    vector (uniform draw in [min, max))."""
    x = _one(ins, "X")
    u = jax.random.uniform(_random.next_key(), (x.shape[0], 1), x.dtype,
                           float(attrs.get("min", 0.0)),
                           float(attrs.get("max", 1.0)))
    cdf = jnp.cumsum(x, axis=1)
    idx = jnp.sum(cdf < u, axis=1)  # first j with cdf >= u
    return {"Out": [jnp.minimum(idx, x.shape[1] - 1).astype(jnp.int64)]}


@register_op("seed")
def _seed(ins, attrs, op):
    """ref seed_op.cc: emit the dropout seed scalar (attr seed, or a
    fresh random one when 0)."""
    s = int(attrs.get("seed", 0))
    if s != 0:
        return {"Out": [jnp.asarray([s], jnp.int32)]}
    return {"Out": [jax.random.randint(
        _random.next_key(), (1,), 1, 2 ** 31 - 1).astype(jnp.int32)]}


@register_op("modified_huber_loss")
def _modified_huber_loss(ins, attrs, op):
    """ref modified_huber_loss_op.h: z = x*(2y-1); loss = -4z (z<-1),
    (1-z)^2 (z<1), 0 otherwise."""
    x, y = _one(ins, "X"), _one(ins, "Y")
    z = x * (2.0 * y - 1.0)
    loss = jnp.where(z < -1.0, -4.0 * z,
                     jnp.where(z < 1.0, (1.0 - z) ** 2, 0.0))
    return {"IntermediateVal": [z], "Out": [loss]}


@register_op("add_position_encoding")
def _add_position_encoding(ins, attrs, op):
    """ref add_position_encoding_op.h: out = alpha*x + beta*PE with the
    half-sin/half-cos layout (first half sin, second half cos, shared
    frequency index k/(half-1))."""
    x = _one(ins, "X")
    alpha = float(attrs.get("alpha", 1.0))
    beta = float(attrs.get("beta", 1.0))
    B, T, D = x.shape
    half = D // 2
    pos = jnp.arange(T, dtype=jnp.float32)[:, None]
    denom = (jnp.power(10000.0, jnp.arange(half, dtype=jnp.float32)
                       / max(half - 1, 1)) if half > 1
             else jnp.full((1,), 10000.0))
    val = pos / denom[None, :]
    pe = jnp.concatenate([jnp.sin(val), jnp.cos(val)], axis=1)
    if 2 * half < D:  # odd enc size: last channel has no PE pair
        pe = jnp.pad(pe, ((0, 0), (0, 1)))
    return {"Out": [alpha * x + beta * pe[None].astype(x.dtype)]}


@register_op("amp_check_finite_and_scale")
def _amp_check_finite_and_scale(ins, attrs, op):
    """ref amp/check_finite_and_scale (v1 name): Out_i = X_i * Scale;
    FoundInfinite = any nonfinite across all inputs."""
    xs = ins.get("X", [])
    scale = jnp.reshape(_one(ins, "Scale"), ())
    found = jnp.zeros((), bool)
    outs = []
    for x in xs:
        found = found | ~jnp.all(jnp.isfinite(x))
        outs.append(x * scale.astype(x.dtype))
    return {"Out": outs, "FoundInfinite": [found.reshape(1)]}


@register_op("fake_init")
def _fake_init(ins, attrs, op):
    dtype = _dtype_mod.convert_dtype(attrs.get("dtype", "float32"))
    return {"Out": [jnp.zeros(tuple(attrs["shape"]), dtype)]}


@register_op("bilinear_tensor_product")
def _bilinear_tensor_product(ins, attrs, op):
    """ref bilinear_tensor_product_op.h: out[b,k] = x[b] W[k] y[b]^T."""
    x, y, w = _one(ins, "X"), _one(ins, "Y"), _one(ins, "Weight")
    out = jnp.einsum("bi,kij,bj->bk", x, w, y)
    b = _one(ins, "Bias")
    if b is not None:
        out = out + b.reshape(1, -1)
    return {"Out": [out]}


def _batch_size_like_shape(ins, attrs):
    ref_shape = _one(ins, "Input").shape
    shape = list(attrs["shape"])
    in_idx = int(attrs.get("input_dim_idx", 0))
    out_idx = int(attrs.get("output_dim_idx", 0))
    shape[out_idx] = ref_shape[in_idx]
    return tuple(shape)


@register_op("gaussian_random_batch_size_like")
def _gaussian_random_bsl(ins, attrs, op):
    dtype = _dtype_mod.convert_dtype(attrs.get("dtype", "float32"))
    out = attrs.get("mean", 0.0) + attrs.get("std", 1.0) * jax.random.normal(
        _random.next_key(), _batch_size_like_shape(ins, attrs), dtype)
    return {"Out": [out]}


@register_op("uniform_random_batch_size_like")
def _uniform_random_bsl(ins, attrs, op):
    dtype = _dtype_mod.convert_dtype(attrs.get("dtype", "float32"))
    return {"Out": [jax.random.uniform(
        _random.next_key(), _batch_size_like_shape(ins, attrs), dtype,
        attrs.get("min", -1.0), attrs.get("max", 1.0))]}


@register_op("flatten_contiguous_range")
def _flatten_contiguous_range(ins, attrs, op):
    x = _one(ins, "X")
    start = int(attrs.get("start_axis", 1)) % x.ndim
    stop = int(attrs.get("stop_axis", -1)) % x.ndim
    shape = x.shape[:start] + (-1,) + x.shape[stop + 1:]
    out = x.reshape(shape)
    return {"Out": [out], "XShape": [jnp.zeros((0,) + x.shape, x.dtype)]}


@register_op("sequence_expand_as")
def _sequence_expand_as(ins, attrs, op):
    """ref sequence_expand_as_op.cc, dense re-scope: X row b repeats
    across timesteps < Length[b] of the (B, T, ...) output (Y provides
    the target T and lengths)."""
    x = _one(ins, "X")
    y = _one(ins, "Y")
    lengths = _one(ins, "Length")
    T = y.shape[1]
    out = jnp.repeat(x[:, None], T, axis=1)
    if lengths is not None:
        mask = jnp.arange(T)[None, :] < lengths.astype(jnp.int32)[:, None]
        out = jnp.where(mask.reshape(mask.shape + (1,) * (out.ndim - 2)),
                        out, jnp.zeros_like(out))
    return {"Out": [out]}


# =========================================================================
# dequantize family (slim/int8 deploy path)
# =========================================================================

@register_op("fake_dequantize_max_abs")
def _fake_dequantize_max_abs(ins, attrs, op):
    """ref fake_dequantize_op.cc: Out = X * Scale / max_range."""
    x = _one(ins, "X").astype(jnp.float32)
    scale = jnp.reshape(_one(ins, "Scale"), ()).astype(jnp.float32)
    return {"Out": [x * scale / float(attrs["max_range"])]}


register_op("dequantize_abs_max")(_fake_dequantize_max_abs)


@register_op("fake_channel_wise_dequantize_max_abs")
def _fake_cw_dequantize_max_abs(ins, attrs, op):
    """ref fake_dequantize_op.cc channel-wise form: one scale per output
    channel (axis quant_axis), optional second scale for activations."""
    x = _one(ins, "X").astype(jnp.float32)
    scales = ins.get("Scales", [])
    qaxis = int(attrs.get("quant_axis", 0))
    bits = attrs.get("quant_bits", [8])
    s0 = scales[0].astype(jnp.float32)
    shape = [1] * x.ndim
    shape[qaxis] = -1
    out = x * s0.reshape(shape) / (2 ** (int(bits[0]) - 1) - 1)
    if len(scales) > 1 and scales[1] is not None:
        out = out * jnp.reshape(scales[1], ()).astype(jnp.float32) \
            / (2 ** (int(bits[1]) - 1) - 1)
    return {"Out": [out]}


@register_op("dequantize_log")
def _dequantize_log(ins, attrs, op):
    """ref dequantize_log_op.cc: int8 codes index a 128-entry dict;
    negative codes mirror with a sign flip."""
    x = _one(ins, "X").astype(jnp.int32)
    table = _one(ins, "Dict").astype(jnp.float32)
    neg = x < 0
    out = jnp.where(neg, -table[(x + 128) % 128], table[x % 128])
    return {"Out": [out]}


@register_op("fake_quantize_moving_average_abs_max")
def _fake_quantize_moving_avg_abs_max(ins, attrs, op):
    """ref fake_quantize_op.cc FakeQuantizeMovingAverageAbsMax: EMA of
    |x|_max drives the quantization scale; round(x/scale*bin_cnt)."""
    x = _one(ins, "X")
    in_scale = jnp.reshape(_one(ins, "InScale"), ())
    rate = float(attrs.get("moving_rate", 0.9))
    bits = int(attrs.get("bit_length", 8))
    bin_cnt = 2 ** (bits - 1) - 1
    cur = jnp.max(jnp.abs(x)).astype(in_scale.dtype)
    state = _one(ins, "InState")
    accum = _one(ins, "InAccum")
    if attrs.get("is_test", False):
        scale = in_scale
        new_state, new_accum = state, accum
    else:
        new_state = (rate * jnp.reshape(state, ()) + 1
                     if state is not None else jnp.asarray(1.0))
        new_accum = (rate * jnp.reshape(accum, ()) + cur
                     if accum is not None else cur)
        scale = new_accum / new_state
    inv = bin_cnt / jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) * inv), -bin_cnt, bin_cnt)
    out = {"Out": [(q / inv).astype(x.dtype)],
           "OutScale": [scale.reshape(1)]}
    if state is not None:
        out["OutState"] = [jnp.reshape(new_state, state.shape)]
    if accum is not None:
        out["OutAccum"] = [jnp.reshape(new_accum, accum.shape)]
    return out


# =========================================================================
# ModelAverage support + metric ops
# =========================================================================

@register_op("average_accumulates")
def _average_accumulates(ins, attrs, op):
    """ref average_accumulates_op.h: three-tier sum accumulation with
    precision-preserving rollover every 16384 updates and window restart
    when the average window outgrows num_updates*average_window.  The
    data-dependent branches become jnp.where over the traced counters."""
    kmax = 16384.0
    p = _one(ins, "param")
    s1 = _one(ins, "in_sum_1")
    s2 = _one(ins, "in_sum_2")
    s3 = _one(ins, "in_sum_3")
    nu = jnp.reshape(_one(ins, "in_num_updates"), ()).astype(jnp.int64) + 1
    na = jnp.reshape(_one(ins, "in_num_accumulates"),
                     ()).astype(jnp.int64) + 1
    ona = jnp.reshape(_one(ins, "in_old_num_accumulates"),
                      ()).astype(jnp.int64)
    avg_win = float(attrs.get("average_window", 0.0))
    max_win = int(attrs.get("max_average_window", 2 ** 62))
    min_win = int(attrs.get("min_average_window", 10000))

    o1, o2, o3 = s1 + p, s2, s3
    roll = (nu % int(kmax)) == 0
    o2 = jnp.where(roll, o2 + o1, o2)
    o1 = jnp.where(roll, jnp.zeros_like(o1), o1)
    restart = (na >= min_win) & (
        na >= jnp.minimum(jnp.asarray(max_win, jnp.float64),
                          nu.astype(jnp.float64) * avg_win).astype(jnp.int64))
    o3 = jnp.where(restart, o1 + o2, o3)
    o1 = jnp.where(restart, jnp.zeros_like(o1), o1)
    o2 = jnp.where(restart, jnp.zeros_like(o2), o2)
    ona = jnp.where(restart, na, ona)
    na = jnp.where(restart, jnp.zeros_like(na), na)
    dt = _one(ins, "in_num_updates").dtype
    return {"out_sum_1": [o1], "out_sum_2": [o2], "out_sum_3": [o3],
            "out_num_updates": [nu.astype(dt).reshape(1)],
            "out_num_accumulates": [na.astype(dt).reshape(1)],
            "out_old_num_accumulates": [ona.astype(dt).reshape(1)]}


@register_op("precision_recall")
def _precision_recall(ins, attrs, op):
    """ref precision_recall_op.h: per-class TP/FP/TN/FN stats from
    argmax predictions vs labels (+ optional per-sample weights), macro-
    and micro-averaged precision/recall/F1, with running accumulation."""
    cls = int(attrs["class_number"])
    idx = _one(ins, "Indices").reshape(-1).astype(jnp.int32)
    labels = _one(ins, "Labels").reshape(-1).astype(jnp.int32)
    w = _one(ins, "Weights")
    w = (w.reshape(-1).astype(jnp.float32) if w is not None
         else jnp.ones_like(idx, jnp.float32))
    onehot_p = jax.nn.one_hot(idx, cls, dtype=jnp.float32)
    onehot_l = jax.nn.one_hot(labels, cls, dtype=jnp.float32)
    tp = jnp.einsum("nc,nc,n->c", onehot_p, onehot_l, w)
    fp = jnp.einsum("nc,n->c", onehot_p, w) - tp
    fn = jnp.einsum("nc,n->c", onehot_l, w) - tp
    tn = jnp.sum(w) - tp - fp - fn
    batch_states = jnp.stack([tp, fp, tn, fn], axis=1)  # (C, 4)
    acc = _one(ins, "StatesInfo")
    accum_states = (batch_states + acc.astype(jnp.float32)
                    if acc is not None else batch_states)

    def metrics(states):
        tp_, fp_, tn_, fn_ = (states[:, 0], states[:, 1],
                              states[:, 2], states[:, 3])
        prec = jnp.where(tp_ + fp_ > 0, tp_ / (tp_ + fp_ + 1e-12), 0.0)
        rec = jnp.where(tp_ + fn_ > 0, tp_ / (tp_ + fn_ + 1e-12), 0.0)
        f1 = jnp.where(prec + rec > 0, 2 * prec * rec / (prec + rec + 1e-12),
                       0.0)
        macro = jnp.stack([prec.mean(), rec.mean(), f1.mean()])
        stp, sfp, sfn = tp_.sum(), fp_.sum(), fn_.sum()
        mp = jnp.where(stp + sfp > 0, stp / (stp + sfp + 1e-12), 0.0)
        mr = jnp.where(stp + sfn > 0, stp / (stp + sfn + 1e-12), 0.0)
        mf = jnp.where(mp + mr > 0, 2 * mp * mr / (mp + mr + 1e-12), 0.0)
        return jnp.concatenate([macro, jnp.stack([mp, mr, mf])])

    return {"BatchMetrics": [metrics(batch_states)],
            "AccumMetrics": [metrics(accum_states)],
            "AccumStatesInfo": [accum_states]}


# =========================================================================
# vision tail
# =========================================================================

@register_op("spp")
def _spp(ins, attrs, op):
    """ref spp_op.h: pyramid of 2^p x 2^p poolings, each flattened and
    concatenated along the feature dim (ceil kernel + centering pad)."""
    from ..nn.functional import pooling as P

    x = _one(ins, "X")
    height = int(attrs["pyramid_height"])
    ptype = attrs.get("pooling_type", "max")
    N, C, H, W = x.shape
    outs = []
    for p in range(height):
        bins = 2 ** p
        kh, kw = -(-H // bins), -(-W // bins)
        ph, pw = (kh * bins - H + 1) // 2, (kw * bins - W + 1) // 2
        if ptype == "max":
            lvl = P.max_pool2d(x, (kh, kw), (kh, kw), (ph, pw))
        else:
            lvl = P.avg_pool2d(x, (kh, kw), (kh, kw), (ph, pw),
                               exclusive=False)
        outs.append(lvl.reshape(N, -1))
    return {"Out": [jnp.concatenate(outs, axis=1)]}


@register_op("polygon_box_transform")
def _polygon_box_transform(ins, attrs, op):
    """ref detection/polygon_box_transform_op.cc: even geo channels are
    x-offsets (out = 4*w_idx - in), odd are y-offsets (out = 4*h_idx -
    in)."""
    x = _one(ins, "Input")
    N, G, H, W = x.shape
    wi = jnp.arange(W, dtype=x.dtype).reshape(1, 1, 1, W)
    hi = jnp.arange(H, dtype=x.dtype).reshape(1, 1, H, 1)
    even = (jnp.arange(G) % 2 == 0).reshape(1, G, 1, 1)
    return {"Output": [jnp.where(even, 4.0 * wi - x, 4.0 * hi - x)]}


@register_op("random_crop")
def _random_crop(ins, attrs, op):
    """ref random_crop_op.h: crop the trailing dims to attr shape at a
    random offset (batch dims keep their extent)."""
    x = _one(ins, "X")
    shape = tuple(attrs["shape"])
    nbatch = x.ndim - len(shape)
    key = _random.next_key()
    starts = []
    for i, s in enumerate(shape):
        key, sub = jax.random.split(key)
        hi = x.shape[nbatch + i] - s
        starts.append(jax.random.randint(sub, (), 0, hi + 1)
                      if hi > 0 else jnp.zeros((), jnp.int32))
    start_idx = [jnp.zeros((), jnp.int32)] * nbatch \
        + [s.astype(jnp.int32) for s in starts]
    out = jax.lax.dynamic_slice(x, start_idx, x.shape[:nbatch] + shape)
    # SeedOut is a threading artifact of the reference's per-op RNG; the
    # rng_scope key stream owns randomness here (int32: x64 is off)
    return {"Out": [out], "SeedOut": [jnp.zeros((1,), jnp.int32)]}


# =========================================================================
# hierarchical sigmoid (ref hierarchical_sigmoid_op.h +
# math/matrix_bit_code.h SimpleCode)
# =========================================================================

@register_op("hierarchical_sigmoid")
def _hierarchical_sigmoid(ins, attrs, op):
    x = _one(ins, "X")                        # (B, D)
    w = _one(ins, "W")                        # (C-1, D)
    label = _one(ins, "Label").reshape(-1)    # (B,)
    bias = _one(ins, "Bias")                  # (C-1,) or (C-1, 1)
    path = _one(ins, "PathTable")
    code = _one(ins, "PathCode")
    B = x.shape[0]
    if path is not None and code is not None:
        # custom tree: per-sample node ids (-1 pad) + bits
        node = path.astype(jnp.int32)
        bits = code.astype(jnp.float32)
        valid = node >= 0
        node = jnp.maximum(node, 0)
    else:
        C = int(attrs["num_classes"])
        # SimpleCode (ref matrix_bit_code.h:106): c = label + C; for bit
        # position j (leaf->root), weight index = (c >> (j+1)) - 1 (the
        # prefix) and the branch bit = (c >> j) & 1 (the suffix); the
        # path ends when the prefix hits the root (index < 0).
        L = max((2 * C - 1).bit_length() - 1, 1)
        c = label.astype(jnp.int32) + C
        j = jnp.arange(L)[None, :]
        node = (c[:, None] >> (j + 1)) - 1
        bits = ((c[:, None] >> j) & 1).astype(jnp.float32)
        valid = node >= 0
        node = jnp.where(valid, node, 0)
    pre = jnp.einsum("bd,bld->bl", x, w[node])          # (B, L)
    if bias is not None:
        pre = pre + bias.reshape(-1)[node]
    # sum over path of softplus(pre) - bit*pre  (sigmoid cross-entropy
    # with bit targets, the matrix_bit_code sum)
    lossb = jax.nn.softplus(pre) - bits * pre
    loss = jnp.sum(jnp.where(valid, lossb, 0.0), axis=1, keepdims=True)
    return {"Out": [loss], "PreOut": [pre]}


# =========================================================================
# SSD training-assignment trio
# =========================================================================

@register_op("bipartite_match")
def _bipartite_match(ins, attrs, op):
    """ref detection/bipartite_match_op.cc: greedy global-argmax matching
    of rows (gt) to cols (priors) by descending DistMat, then optional
    per_prediction argmax completion above overlap_threshold.

    Dense layout: DistMat (B, R, C) (the reference's LoD batch of (R, C)
    mats); outputs ColToRowMatchIndices / ColToRowMatchDist (B, C)."""
    dist = _one(ins, "DistMat")
    if dist.ndim == 2:
        dist = dist[None]
    B, R, C = dist.shape
    mtype = attrs.get("match_type", "bipartite")
    thresh = float(attrs.get("dist_threshold", 0.5))

    def one(dmat):
        def body(_, carry):
            md, mi, used_r = carry  # (C,), (C,), (R,)
            # mask already-matched rows and cols
            col_free = mi < 0
            m = dmat * used_r[:, None] * col_free[None, :]
            flat = jnp.argmax(m)
            r, c = flat // C, flat % C
            ok = m[r, c] > 0
            mi = jnp.where(ok, mi.at[c].set(r.astype(jnp.int32)), mi)
            md = jnp.where(ok, md.at[c].set(dmat[r, c]), md)
            used_r = jnp.where(ok, used_r.at[r].set(0.0), used_r)
            return md, mi, used_r

        init = (jnp.zeros((C,), dist.dtype), jnp.full((C,), -1, jnp.int32),
                jnp.ones((R,), dist.dtype))
        md, mi, _ = jax.lax.fori_loop(0, R, body, init)
        if mtype == "per_prediction":
            best_r = jnp.argmax(dmat, axis=0).astype(jnp.int32)
            best_d = jnp.max(dmat, axis=0)
            take = (mi < 0) & (best_d >= thresh)
            mi = jnp.where(take, best_r, mi)
            md = jnp.where(take, best_d, md)
        return mi, md

    mi, md = jax.vmap(one)(dist)
    return {"ColToRowMatchIndices": [mi], "ColToRowMatchDis": [md],
            "ColToRowMatchDist": [md]}


@register_op("target_assign")
def _target_assign(ins, attrs, op):
    """ref detection/target_assign_op.h, dense layout: X (B, P, K)
    per-image candidate rows, MatchIndices (B, M) -> Out (B, M, K) +
    OutWeight (B, M, 1); optional NegIndices (B, M) (-1 padded) overrides
    matched-away entries with mismatch_value/weight 1."""
    x = _one(ins, "X")
    if x.ndim == 2:
        x = x[:, :, None]
    match = _one(ins, "MatchIndices").astype(jnp.int32)
    mismatch = float(attrs.get("mismatch_value", 0))
    B, M = match.shape
    K = x.shape[2]
    b_idx = jnp.arange(B)[:, None]
    gathered = x[b_idx, jnp.maximum(match, 0)]           # (B, M, K)
    matched = (match >= 0)[..., None]
    out = jnp.where(matched, gathered,
                    jnp.asarray(mismatch, x.dtype))
    wt = matched.astype(jnp.float32)
    neg = _one(ins, "NegIndices")
    if neg is not None:
        neg = neg.astype(jnp.int32)
        negmask = jnp.zeros((B, M), bool).at[
            jnp.arange(B)[:, None],
            jnp.where(neg >= 0, neg, M)].set(True, mode="drop")
        out = jnp.where(negmask[..., None],
                        jnp.asarray(mismatch, x.dtype), out)
        wt = jnp.where(negmask[..., None], 1.0, wt)
    return {"Out": [out], "OutWeight": [wt]}


@register_op("mine_hard_examples")
def _mine_hard_examples(ins, attrs, op):
    """ref detection/mine_hard_examples_op.cc.  max_negative (default):
    candidates are unmatched priors, ranked by ClsLoss desc, keep
    min(num_pos*neg_pos_ratio, #candidates); hard_example: candidates
    have MatchDist < neg_dist_threshold, loss = cls+loc, keep sample_size
    and un-match positives that don't survive.  NegIndices is (B, P)
    ascending, -1 padded (the reference's ragged LoD output)."""
    cls_loss = _one(ins, "ClsLoss")
    loc_loss = _one(ins, "LocLoss")
    match = _one(ins, "MatchIndices").astype(jnp.int32)
    match_dist = _one(ins, "MatchDist")
    ratio = float(attrs.get("neg_pos_ratio", 1.0))
    thresh = float(attrs.get("neg_dist_threshold", 0.5))
    sample_size = int(attrs.get("sample_size", 0))
    mining = attrs.get("mining_type", "max_negative")
    B, P = match.shape

    if mining == "hard_example":
        eligible = match_dist < thresh
        loss = cls_loss + (loc_loss if loc_loss is not None else 0.0)
        neg_sel = jnp.minimum(sample_size, eligible.sum(axis=1))
    else:
        eligible = match < 0
        loss = cls_loss
        num_pos = (match >= 0).sum(axis=1)
        neg_sel = jnp.minimum((num_pos * ratio).astype(jnp.int32),
                              eligible.sum(axis=1).astype(jnp.int32))

    masked = jnp.where(eligible, loss, -jnp.inf)
    order = jnp.argsort(-masked, axis=1)
    rank = jnp.argsort(order, axis=1)                   # rank of each prior
    selected = eligible & (rank < neg_sel[:, None])

    upd = match
    if mining == "hard_example":
        upd = jnp.where((match > -1) & ~selected, -1, match)
        neg_mask = (match < 0) & selected
    else:
        neg_mask = selected
    # ascending compaction of selected indices, -1 pad
    tgt = jnp.cumsum(neg_mask, axis=1) - 1
    neg_idx = jnp.full((B, P), -1, jnp.int32).at[
        jnp.arange(B)[:, None],
        jnp.where(neg_mask, tgt, P)].set(
        jnp.broadcast_to(jnp.arange(P, dtype=jnp.int32), (B, P)),
        mode="drop")
    return {"NegIndices": [neg_idx], "UpdatedMatchIndices": [upd]}


@register_op("fc")
def _fc_op(ins, attrs, op):
    """ref fc_op.h: the fused inference-pass mul+bias(+relu) op —
    flatten leading in_num_col_dims dims, x @ W + b, optional relu."""
    x = _one(ins, "Input")
    w = _one(ins, "W")
    b = _one(ins, "Bias")
    ncol = int(attrs.get("in_num_col_dims", 1))
    lead = x.shape[:ncol]
    out = x.reshape((int(np.prod(lead)) if lead else 1, -1)) @ w
    if b is not None:
        out = out + b.reshape(1, -1)
    if attrs.get("activation_type", "") == "relu":
        out = jax.nn.relu(out)
    return {"Out": [out.reshape(lead + (w.shape[1],))]}


@register_op("assert")
def _assert_op(ins, attrs, op):
    """ref controlflow/assert_op.cc: abort the run when Cond is false,
    printing the attached data vars.  Host-side check via ordered
    io_callback (same contract as the print op, see the module docstring
    of ops_tail2)."""
    from jax.experimental import io_callback

    cond = _one(ins, "Cond")
    data = ins.get("Data", [])
    summarize = int(attrs.get("summarize", -1))

    def host_check(c, *arrs):
        # ALL elements must hold (assert_op.cc checks the full tensor)
        if not bool(np.asarray(c).all()):
            shown = [np.asarray(a).ravel()[:summarize if summarize > 0
                                           else None] for a in arrs]
            raise AssertionError(
                f"assert_op failed; data: {shown}")
        return np.zeros((), np.int32)

    io_callback(host_check, jax.ShapeDtypeStruct((), jnp.int32),
                cond, *data, ordered=True)
    return {}
