"""Lowerings for the fused ops emitted by the graph-rewrite passes.

Reference parity: the `framework/ir` fusion passes materialize fused op
types (conv_bn_fuse_pass -> conv2d with folded weights, fc_fuse_pass ->
`fc`, conv_elementwise_add_act_fuse_pass -> `conv2d_fusion`).  Here the
pass manager (static/passes.py) rewrites op *patterns* into these two op
types; their lowerings fold at trace time, so XLA sees one region:

- ``fused_conv2d_bn_act``: conv2d -> batch_norm -> act collapsed into one
  op.  Inference mode has two executions of the same math: when the
  Pallas gate holds (NHWC, lane-aligned channels, TPU backend — see
  ops/pallas/conv_fused.py) the conv runs as a Pallas kernel with the
  per-channel BN transform ``a·x + b`` fused as an epilogue on its output
  tiles; otherwise BN is folded INTO THE FILTER (``w' = w * a`` per
  output channel, ``b' = conv_bias * a + b`` — the weight-space fold)
  and XLA runs one unfused conv.  Training mode (is_test=False) keeps
  XLA's conv and fuses the BN-stats reduction + scale/shift + activation
  via nn.functional.norm.batch_norm_act (Pallas when gated, jnp
  otherwise), emitting MeanOut/VarianceOut running-stat updates like the
  unfused batch_norm op — this is what lets fuse_conv_bn_act fire inside
  programs with a backward_region.
- ``fused_matmul_bias_act``: mul -> elementwise_add(1-D bias) -> act (the
  `fc`/transformer-MLP pattern, gelu included) as one op.
- ``quant_conv2d`` / ``quant_mul``: the int8 inference ops minted by the
  quant_infer pass from PTQ artifacts (weight_scale attrs + fixed-scale
  activation quant ops).  Flag-on they run the ops/pallas/int8 kernels
  (int8 MXU dots, int32 accumulate, fp32 per-channel dequant epilogue);
  flag-off or unsupported they run the *simulate* fallback — quantize +
  dequantize + fp32 op — which is bitwise the pre-rewrite fake-quant
  graph, so parity tests can pin the rewrite exactly.

The float lowerings reproduce the unfused op chain's math (same primitive
sequence modulo the weight-space refactor), so golden parity holds bitwise
for ints and within float tolerance for the BN fold; the int8 kernels hold
parity to calibrated tolerance (int32 accumulation vs fp32 rounding).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..nn import functional as F
from ..nn.functional.norm import batch_norm_act, bn_inference_scale_bias
from .registry import get_lowering, register_op
from .ops import _one


def _pair(v):
    if isinstance(v, (list, tuple)):
        return (int(v[0]), int(v[1])) if len(v) >= 2 \
            else (int(v[0]), int(v[0]))
    return (int(v), int(v))

# Activations a fusion pattern may absorb: value-wise, attr-free in the
# emitted-by-layers form, with a registered X->Out lowering.
FUSABLE_ACTS = frozenset({
    "relu", "gelu", "sigmoid", "tanh", "relu6", "silu", "swish",
    "leaky_relu", "hard_swish", "softplus", "mish", "elu",
})


def _apply_act(out, act, attrs, op):
    if not act:
        return out
    return get_lowering(act)({"X": [out]}, attrs, op)["Out"][0]


def _use_pallas_conv(x, w, stride, padding, dilation, groups, act,
                     data_format) -> bool:
    """Gate for the fused conv+BN+act epilogue kernel (flag + TPU backend
    via ops.pallas.config — tests patch `config.kernel_enabled` — plus the
    kernel's own shape gates).  String paddings (SAME/VALID) stay on XLA."""
    if not (isinstance(padding, tuple) and data_format == "NHWC"):
        return False
    from ..ops.pallas import config as _pcfg

    if not _pcfg.kernel_enabled("use_pallas_conv_fused"):
        return False
    from ..ops.pallas import conv_fused as _cf

    return _pcfg.counted(
        "conv2d_bn_act", _cf.supported(x, w.shape, stride, padding, dilation,
                                       groups, act, data_format))


@register_op("fused_conv2d_bn_act")
def _fused_conv2d_bn_act(ins, attrs, op):
    x = _one(ins, "Input")
    w = _one(ins, "Filter")
    conv_bias = _one(ins, "Bias")
    act = attrs.get("act", "")
    data_format = attrs.get("data_format", "NCHW")
    stride = _pair(attrs.get("strides", 1))
    dilation = _pair(attrs.get("dilations", 1))
    groups = attrs.get("groups", 1)
    raw_padding = attrs.get("paddings", 0)
    padding = raw_padding if isinstance(raw_padding, str) \
        else _pair(raw_padding)

    if not attrs.get("is_test", True):
        # training mode: XLA's conv + fused BN-stats/scale-shift/act with
        # running-stat outputs (differentiable — safe under backward_region)
        out = F.conv2d(x, w, bias=conv_bias, stride=stride,
                       padding=raw_padding, dilation=dilation, groups=groups,
                       data_format=data_format)
        y, new_rm, new_rv = batch_norm_act(
            out, _one(ins, "Mean"), _one(ins, "Variance"),
            weight=_one(ins, "Scale"), bias=_one(ins, "BnBias"),
            momentum=attrs.get("momentum", 0.9),
            epsilon=attrs.get("epsilon", 1e-5), act=act,
            data_format=data_format)
        return {"Output": [y], "MeanOut": [new_rm], "VarianceOut": [new_rv]}

    a, b = bn_inference_scale_bias(
        _one(ins, "Mean"), _one(ins, "Variance"),
        _one(ins, "Scale"), _one(ins, "BnBias"),
        attrs.get("epsilon", 1e-5))
    if conv_bias is not None:
        b = b + conv_bias.astype(jnp.float32) * a

    if _use_pallas_conv(x, w, stride, padding, dilation, groups, act,
                        data_format):
        from ..ops.pallas import conv_fused as _cf

        out = _cf.conv2d_bn_act(x, w, a, b, stride=stride, padding=padding,
                                act=act)
        return {"Output": [out]}

    # weight-space fold: scale each OUTPUT channel's filter (OIHW axis 0)
    w = w * a.astype(w.dtype).reshape(-1, 1, 1, 1)
    out = F.conv2d(x, w, bias=b.astype(x.dtype),
                   stride=stride, padding=raw_padding, dilation=dilation,
                   groups=groups, data_format=data_format)
    return {"Output": [_apply_act(out, act, attrs, op)]}


def _qmax(bits: int) -> float:
    return float(2 ** (int(bits) - 1) - 1)


def _quantize_int8(x, scale, qmax):
    """Symmetric zero-point quantization matching the
    fake_quantize_dequantize_fixed_scale lowering's rounding exactly:
    ``round(clip(x/scale, -1, 1) * qmax)`` as int8."""
    return jnp.round(jnp.clip(x / scale, -1.0, 1.0) * qmax).astype(jnp.int8)


def _simulate_qdq(x, in_scale, in_bits, op):
    """The bitwise flag-off path: replay the exact fixed-scale fake-quant
    lowering the quant_infer pass removed (NOT a reimplementation — the
    STE form ``x + stop_gradient(q - x)`` must match to the last ulp)."""
    return get_lowering("fake_quantize_dequantize_fixed_scale")(
        {"X": [x]}, {"bit_length": in_bits, "scale": in_scale}, op)["Out"][0]


@register_op("quant_conv2d")
def _quant_conv2d(ins, attrs, op):
    x = _one(ins, "Input")
    w = _one(ins, "Filter")
    bias = _one(ins, "Bias")
    act = attrs.get("act", "")
    data_format = attrs.get("data_format", "NCHW")
    stride = _pair(attrs.get("strides", 1))
    dilation = _pair(attrs.get("dilations", 1))
    groups = attrs.get("groups", 1)
    raw_padding = attrs.get("paddings", 0)
    padding = raw_padding if isinstance(raw_padding, str) \
        else _pair(raw_padding)
    in_scale = float(attrs["in_scale"])
    in_bits = int(attrs.get("in_bits", 8))
    w_scale = jnp.asarray(attrs["weight_scale"], jnp.float32)   # (O,)
    w_bits = int(attrs.get("weight_bits", 8))

    use_pallas = False
    if isinstance(padding, tuple) and data_format == "NHWC" \
            and w_scale.shape[0] == w.shape[0]:
        from ..ops.pallas import config as _pcfg

        if _pcfg.kernel_enabled("use_pallas_int8"):
            from ..ops.pallas import int8 as _int8

            use_pallas = _pcfg.counted("int8_conv2d", _int8.conv_supported(
                jax.ShapeDtypeStruct(x.shape, jnp.int8), w.shape, stride,
                padding, dilation, groups, act, data_format))
    if use_pallas:
        from ..ops.pallas import int8 as _int8

        qm_in, qm_w = _qmax(in_bits), _qmax(w_bits)
        x_q = _quantize_int8(x, in_scale, qm_in)
        # the weight in scope is already int8-SIMULATED (q/qmax*scale, q
        # integral — the freeze/PTQ pass wrote it), so dividing by the
        # step recovers the exact int8 grid point
        step_w = w_scale / qm_w
        w_q = jnp.round(w / step_w.reshape(-1, 1, 1, 1)).astype(jnp.int8)
        out = _int8.int8_conv2d_dequant(
            x_q, w_q, (in_scale / qm_in) * step_w, bias=bias,
            stride=stride, padding=padding, act=act, out_dtype=x.dtype)
        return {"Output": [out]}

    # simulate fallback: bitwise the pre-rewrite fake-quant graph
    xq = _simulate_qdq(x, in_scale, in_bits, op)
    out = F.conv2d(xq, w, bias=bias, stride=stride, padding=raw_padding,
                   dilation=dilation, groups=groups, data_format=data_format)
    return {"Output": [_apply_act(out, act, attrs, op)]}


@register_op("quant_mul")
def _quant_mul(ins, attrs, op):
    x, y = _one(ins, "X"), _one(ins, "Y")
    act = attrs.get("act", "")
    xd = attrs.get("x_num_col_dims", 1)
    yd = attrs.get("y_num_col_dims", 1)
    xs, ys = x.shape, y.shape
    in_scale = float(attrs["in_scale"])
    in_bits = int(attrs.get("in_bits", 8))
    w_scale = jnp.asarray(attrs["weight_scale"], jnp.float32)   # (out,)
    w_bits = int(attrs.get("weight_bits", 8))
    x2_shape = (int(np.prod(xs[:xd])), int(np.prod(xs[xd:])))
    y2_shape = (int(np.prod(ys[:yd])), int(np.prod(ys[yd:])))

    use_pallas = False
    # per-channel scales only line up with the flattened output dim when
    # the weight's quant axis IS the flattened minor axis
    if w_scale.shape[0] == y2_shape[1]:
        from ..ops.pallas import config as _pcfg

        if _pcfg.kernel_enabled("use_pallas_int8"):
            from ..ops.pallas import int8 as _int8

            use_pallas = _pcfg.counted("int8_matmul", _int8.matmul_supported(
                jax.ShapeDtypeStruct(x2_shape, jnp.int8), y2_shape, act))
    if use_pallas:
        from ..ops.pallas import int8 as _int8

        qm_in, qm_w = _qmax(in_bits), _qmax(w_bits)
        x_q = _quantize_int8(x.reshape(x2_shape), in_scale, qm_in)
        step_w = w_scale / qm_w
        w_q = jnp.round(y.reshape(y2_shape) / step_w[None, :]) \
            .astype(jnp.int8)
        out2 = _int8.int8_matmul_dequant(
            x_q, w_q, (in_scale / qm_in) * step_w, act=act,
            out_dtype=x.dtype)
        return {"Out": [out2.reshape(xs[:xd] + ys[yd:])]}

    xq = _simulate_qdq(x, in_scale, in_bits, op)
    out = (xq.reshape(x2_shape) @ y.reshape(y2_shape)) \
        .reshape(xs[:xd] + ys[yd:])
    return {"Out": [_apply_act(out, act, attrs, op)]}


@register_op("fused_matmul_bias_act")
def _fused_matmul_bias_act(ins, attrs, op):
    x, y = _one(ins, "X"), _one(ins, "Y")
    xd = attrs.get("x_num_col_dims", 1)
    yd = attrs.get("y_num_col_dims", 1)
    xs, ys = x.shape, y.shape
    # identical math to the mul lowering (ops.py _mul)
    x2 = x.reshape(int(np.prod(xs[:xd])), int(np.prod(xs[xd:])))
    y2 = y.reshape(int(np.prod(ys[:yd])), int(np.prod(ys[yd:])))
    out = (x2 @ y2).reshape(xs[:xd] + ys[yd:])
    bias = _one(ins, "Bias")
    if bias is not None:
        out = out + bias          # 1-D bias broadcasts on the last axis
    return {"Out": [_apply_act(out, attrs.get("act", ""), attrs, op)]}
