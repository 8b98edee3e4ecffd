"""Compiled parity, on the chip, for the Pallas kernel families that only
ever ran in interpret mode before PR 21: fused conv+BN+act, the training
BN-stats+act epilogue, NHWC pooling, int8 matmul/conv, paged attention.

Each case goes through the product's dispatch gate at one gate-passing TPU
shape, with the family's flag on (Mosaic) and off (the XLA lowering it
replaces) — `pallas.kernel_calls{kernel}` proves which one ran.  Shapes
Mosaic refuses (stride 2, see conv_fused.supported) must be gated out and
counted in `pallas.fallbacks`, never attempted."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.nn.functional as F
from paddle_tpu.core import flags
from paddle_tpu.nn.functional.norm import batch_norm_act
from paddle_tpu.ops.pallas import config as pcfg
from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.static.registry import get_lowering

pytestmark = pytest.mark.skipif(
    not pcfg.backend_is_tpu(), reason="compiles Mosaic kernels: TPU only")

RNG = np.random.default_rng(21)


def _calls(kernel):
    return pcfg._m_calls.value(kernel=kernel)


def _fallbacks(kernel):
    return pcfg._m_fallbacks.value(kernel=kernel, reason="unsupported")


def _kernel_vs_xla(flag, kernel, fn, *args):
    """``fn(*args)`` jitted with the family's flag on, then off (dispatch is
    decided at trace time, so each gets its own trace).  Asserts the first
    trace really took the Pallas branch and the second did not."""
    saved = flags.get_flags([flag])
    try:
        flags.set_flags({flag: True})
        before = _calls(kernel)
        got = jax.block_until_ready(jax.jit(lambda *a: fn(*a))(*args))
        assert _calls(kernel) > before, f"{kernel}: Pallas branch not taken"
        flags.set_flags({flag: False})
        before = _calls(kernel)
        want = jax.block_until_ready(jax.jit(lambda *a: fn(*a))(*args))
        assert _calls(kernel) == before
    finally:
        flags.set_flags(saved)
    return got, want


def _close(got, want, rel):
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    assert np.isfinite(got).all()
    scale = float(np.abs(want).max()) or 1.0
    assert float(np.abs(got - want).max()) / scale < rel


def _conv_ins(dtype, c=128, hw=16):
    return {
        "Input": [jnp.asarray(RNG.normal(size=(2, hw, hw, c)), dtype)],
        "Filter": [jnp.asarray(RNG.normal(size=(c, c, 3, 3)) * 0.05, dtype)],
        "Bias": [],
        "Scale": [jnp.asarray(RNG.uniform(0.5, 1.5, c), jnp.float32)],
        "BnBias": [jnp.asarray(RNG.normal(size=c), jnp.float32)],
        "Mean": [jnp.asarray(RNG.normal(size=c) * 0.1, jnp.float32)],
        "Variance": [jnp.asarray(RNG.uniform(0.5, 1.5, c), jnp.float32)],
    }


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_conv_bn_act_inference(dtype):
    ins = _conv_ins(dtype)
    names = sorted(k for k, v in ins.items() if v)
    attrs = {"act": "relu", "data_format": "NHWC", "strides": 1,
             "paddings": 1, "is_test": True}

    def fn(*arrs):
        d = {k: [] for k in ins}
        d.update({k: [a] for k, a in zip(names, arrs)})
        return get_lowering("fused_conv2d_bn_act")(d, attrs, None)[
            "Output"][0]

    got, want = _kernel_vs_xla("use_pallas_conv_fused", "conv2d_bn_act", fn,
                               *[ins[k][0] for k in names])
    _close(got, want, 2e-2)  # default-precision MXU passes on both sides


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_bn_act_train_forward_and_grads(dtype):
    c = 128
    x = jnp.asarray(RNG.normal(size=(4, 16, 16, c)), dtype)
    gamma = jnp.asarray(RNG.uniform(0.5, 1.5, c), jnp.float32)
    beta = jnp.asarray(RNG.normal(size=c), jnp.float32)
    rm, rv = jnp.zeros((c,), jnp.float32), jnp.ones((c,), jnp.float32)

    def fn(x, gamma, beta):
        def loss(x, gamma, beta):
            y, _rm, _rv = batch_norm_act(x, rm, rv, gamma, beta, act="relu",
                                         data_format="NHWC")
            return jnp.sum(y.astype(jnp.float32) ** 2), y
        (_val, y), grads = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                             has_aux=True)(x, gamma, beta)
        return (y,) + grads

    got, want = _kernel_vs_xla("use_pallas_conv_fused", "bn_act_train", fn,
                               x, gamma, beta)
    for g, w in zip(got, want):
        _close(g, w, 2e-2 if dtype == jnp.bfloat16 else 1e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("mode", ["max", "avg"])
def test_nhwc_pooling_stride1(mode, dtype):
    x = jnp.asarray(RNG.normal(size=(2, 16, 16, 128)), dtype)
    pool = F.max_pool2d if mode == "max" else F.avg_pool2d
    kw = {} if mode == "max" else {"exclusive": False}
    got, want = _kernel_vs_xla(
        "use_pallas_pool", f"{mode}_pool2d",
        lambda x: pool(x, 3, stride=1, padding=1, data_format="NHWC", **kw),
        x)
    # the kernel sums in fp32 and rounds once; reduce_window sums in bf16
    _close(got, want, 2e-2 if dtype == jnp.bfloat16 else 1e-5)


def test_stride2_is_gated_out_and_counted():
    """Mosaic refuses the stride-2 window slice: the gates must say no
    before any compile, and `pallas.fallbacks` must show it."""
    x = jnp.asarray(RNG.normal(size=(2, 16, 16, 128)), jnp.bfloat16)
    calls, fb = _calls("max_pool2d"), _fallbacks("max_pool2d")
    got = jax.jit(lambda x: F.max_pool2d(x, 3, stride=2, padding=1,
                                         data_format="NHWC"))(x)
    assert got.shape == (2, 8, 8, 128)
    assert _calls("max_pool2d") == calls and _fallbacks("max_pool2d") > fb

    ins = _conv_ins(jnp.bfloat16)
    attrs = {"act": "relu", "data_format": "NHWC", "strides": 2,
             "paddings": 1, "is_test": True}
    calls, fb = _calls("conv2d_bn_act"), _fallbacks("conv2d_bn_act")
    out = jax.jit(lambda: get_lowering("fused_conv2d_bn_act")(
        ins, attrs, None)["Output"][0])()
    assert out.shape == (2, 8, 8, 128)
    assert _calls("conv2d_bn_act") == calls
    assert _fallbacks("conv2d_bn_act") > fb


def _quantized_weight(shape, axis):
    """An int8-SIMULATED float weight (q / 127 * scale, q integral) and its
    per-output-channel scale — what the PTQ/freeze pass leaves in scope."""
    q = RNG.integers(-127, 128, size=shape).astype(np.float32)
    n_out = shape[axis]
    scale = RNG.uniform(0.05, 0.2, n_out).astype(np.float32)
    bshape = [1] * len(shape)
    bshape[axis] = n_out
    return jnp.asarray(q / 127.0 * scale.reshape(bshape)), scale.tolist()


def test_int8_matmul_through_quant_mul():
    x = jnp.asarray(RNG.uniform(-1, 1, (256, 256)), jnp.float32)
    y, w_scale = _quantized_weight((256, 256), 1)
    attrs = {"in_scale": 1.0, "weight_scale": w_scale, "act": "relu"}
    got, want = _kernel_vs_xla(
        "use_pallas_int8", "int8_matmul",
        lambda x, y: get_lowering("quant_mul")(
            {"X": [x], "Y": [y]}, attrs, None)["Out"][0], x, y)
    _close(got, want, 2e-2)  # int8 MXU exact; the fp32 simulate side is not


def test_int8_conv_through_quant_conv2d():
    x = jnp.asarray(RNG.uniform(-1, 1, (2, 16, 16, 128)), jnp.float32)
    w, w_scale = _quantized_weight((128, 128, 3, 3), 0)
    attrs = {"in_scale": 1.0, "weight_scale": w_scale, "act": "relu",
             "data_format": "NHWC", "strides": 1, "paddings": 1}
    got, want = _kernel_vs_xla(
        "use_pallas_int8", "int8_conv2d",
        lambda x, w: get_lowering("quant_conv2d")(
            {"Input": [x], "Filter": [w], "Bias": []}, attrs, None)[
                "Output"][0], x, w)
    _close(got, want, 2e-2)


@pytest.mark.parametrize("kv_dtype", ["float32", "bfloat16", "int8"])
def test_paged_attention_decode(kv_dtype):
    num_seqs, max_blocks, block_size, d = 8, 4, 16, 128
    num_blocks = num_seqs * max_blocks + 1
    kw = {}
    if kv_dtype == "int8":
        k_cache = jnp.asarray(RNG.integers(
            -127, 128, (num_blocks, block_size, d)), jnp.int8)
        v_cache = jnp.asarray(RNG.integers(
            -127, 128, (num_blocks, block_size, d)), jnp.int8)
        kw["kv_scales"] = jnp.asarray(
            RNG.uniform(0.01, 0.1, (num_blocks, 2)), jnp.float32)
        q_dtype = jnp.float32
    else:
        q_dtype = jnp.dtype(kv_dtype)
        k_cache = jnp.asarray(RNG.normal(
            size=(num_blocks, block_size, d)), q_dtype)
        v_cache = jnp.asarray(RNG.normal(
            size=(num_blocks, block_size, d)), q_dtype)
    q = jnp.asarray(RNG.normal(size=(num_seqs, d)), q_dtype)
    tables = jnp.asarray(RNG.permutation(np.arange(1, num_blocks)).reshape(
        num_seqs, max_blocks), jnp.int32)
    # empty row, partial block, exact block, full table, and in between
    lens = jnp.asarray([0, 3, block_size, max_blocks * block_size,
                        17, 33, 50, 1], jnp.int32)
    got, want = _kernel_vs_xla(
        "use_paged_attention", "paged_attention",
        lambda q, k, v, t, l, *s: pa.paged_attention(
            q, k, v, t, l, sm_scale=0.088,
            **({"kv_scales": s[0]} if s else {})),
        q, k_cache, v_cache, tables, lens, *kw.values())
    _close(got, want, 2e-2)
    # a row that has seen no tokens comes back exactly zero, not NaN
    assert np.all(np.asarray(got, np.float32)[0] == 0.0)
