"""Pallas vision kernels + int8 inference path (ops/pallas/conv_fused.py,
pooling.py, int8.py + the quant_infer pass and dispatch wiring).

The PR-13 contract pinned here:
  * fused conv+BN+act and the training-mode BN-stats+act kernel match the
    unfused XLA reference (forward AND gradients) in interpret mode on CPU
    CI — the same code path a TPU runs compiled;
  * NHWC pooling kernels match lax.reduce_window on odd spatial shapes and
    with padding; the exclusive-avg-with-padding case is gated OUT of the
    kernel (`supported()` false) and the functional layer falls back;
  * the graph-level conv+BN+act fusion now fires in TRAINING graphs
    (backward_region references only Loss+Params, never intermediates)
    with golden parity through the optimizer step;
  * the `quant_infer` pass folds PTQ artifacts into `quant_conv2d` /
    `quant_mul`: flag-off lowering is BITWISE the pre-rewrite fake-quant
    graph, the Pallas int8 path stays within a bounded error of it, and a
    quantized residual block holds golden parity end to end;
  * per-channel weight scales live on the OUTPUT-channel axis — conv OIHW
    axis 0, mul/matmul LAST axis (axis 0 is the contraction dim; reducing
    over the wrong axis silently breaks per-channel dequant);
  * the kernel-config fingerprint rides both executor cache layers: zero
    steady-state retraces, a kernel-flag flip is exactly one clean
    recompile (and flipping back re-traces nothing);
  * xprof prices the custom-calls Pallas kernels lower to (>= 90% flops
    attribution coverage on a representative synthetic HLO);
  * a PTQ'd tenant registered with ``add_tenant(quantize=True)`` serves
    through the rewritten program with parity;
  * every registered kernel family prices a call above zero, and the
    metricsdump known-names lint passes in a child process.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.nn.functional as F
import paddle_tpu.static as static
from paddle_tpu.core import flags
from paddle_tpu.ops.pallas import config as pcfg
from paddle_tpu.ops.pallas import conv_fused as cf
from paddle_tpu.ops.pallas import int8 as pint8
from paddle_tpu.ops.pallas import paged_attention as ppaged
from paddle_tpu.ops.pallas import pooling as ppool
from paddle_tpu.slim import quant_static
from paddle_tpu.slim.quant import weight_quant_axis
from paddle_tpu.static import layers as L
from paddle_tpu.static import passes as P
from paddle_tpu.utils import monitor, xprof

REPO = Path(__file__).resolve().parents[1]
RNG = np.random.default_rng(7)


@pytest.fixture(autouse=True)
def _fresh_programs():
    main, startup = static.Program(), static.Program()
    scope = static.Scope()
    with static.program_guard(main, startup), static.scope_guard(scope):
        yield main, startup


@pytest.fixture
def _flags_guard():
    saved = flags.get_flags(["metrics", "opt_passes", "compile_cache_dir",
                             "use_pallas_conv_fused", "use_pallas_pool",
                             "use_pallas_int8"])
    yield
    flags.set_flags(saved)


@pytest.fixture
def _tpu_gate(monkeypatch):
    """Force `kernel_enabled` open on CPU CI: kernels run in Pallas
    interpret mode, exercising the exact code a TPU compiles."""
    monkeypatch.setattr(pcfg, "kernel_enabled",
                        lambda name: bool(flags.get_flag(name)))


def _init_state(startup):
    scope = static.Scope()
    with static.scope_guard(scope):
        static.Executor().run(startup)
        return {k: np.asarray(scope.find_var(k)) for k in scope.keys()}


def _op_types(program):
    return [op.type for op in program.global_block().ops]


def _scope_state(scope):
    return {k: np.asarray(scope.find_var(k)) for k in scope.keys()}


# ---------------------------------------------------------------------------
# kernel parity: fused conv+BN+act (inference epilogue)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("stride,padding,act", [
    ((1, 1), (1, 1), "relu"),
    ((2, 2), (0, 0), ""),
    ((1, 1), (2, 2), "sigmoid"),
])
def test_conv2d_bn_act_kernel_parity(stride, padding, act):
    x = RNG.normal(size=(2, 8, 8, 8)).astype(np.float32)
    w = (RNG.normal(size=(16, 8, 3, 3)) * 0.2).astype(np.float32)
    a = RNG.uniform(0.5, 1.5, size=(16,)).astype(np.float32)
    b = RNG.normal(size=(16,)).astype(np.float32)

    got = cf.conv2d_bn_act(x, w, a, b, stride=stride, padding=padding,
                           act=act)
    ref = jax.lax.conv_general_dilated(
        x, jnp.transpose(w, (2, 3, 1, 0)), stride,
        [(padding[0], padding[0]), (padding[1], padding[1])],
        dimension_numbers=("NHWC", "HWIO", "NHWC")) * a + b
    if act == "relu":
        ref = jax.nn.relu(ref)
    elif act == "sigmoid":
        ref = jax.nn.sigmoid(ref)
    assert got.shape == ref.shape
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


def test_fused_bn_act_train_parity_and_grads():
    x = RNG.normal(size=(2, 4, 4, 8)).astype(np.float32)
    gamma = RNG.uniform(0.5, 1.5, size=(8,)).astype(np.float32)
    beta = RNG.normal(size=(8,)).astype(np.float32)
    eps = 1e-5

    def ref_fn(x, gamma, beta):
        x2 = x.reshape(-1, x.shape[-1])
        mean = x2.mean(0)
        var = x2.var(0)
        y = (x2 - mean) / jnp.sqrt(var + eps) * gamma + beta
        return jax.nn.relu(y).reshape(x.shape), mean, var

    y, mean, var = cf.fused_bn_act_train(x, gamma, beta, eps, act="relu")
    ry, rmean, rvar = ref_fn(x, gamma, beta)
    np.testing.assert_allclose(y, ry, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(mean, rmean, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(var, rvar, rtol=1e-5, atol=1e-6)

    # the custom VJP must match AD through the unfused reference
    fused = lambda x, g, b: cf.fused_bn_act_train(x, g, b, eps, act="relu")
    loss = lambda fn: lambda *args: jnp.sum(fn(*args)[0] ** 2)
    g = jax.grad(loss(fused), argnums=(0, 1, 2))(x, gamma, beta)
    rg = jax.grad(loss(ref_fn), argnums=(0, 1, 2))(x, gamma, beta)
    for got, want in zip(g, rg):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# NHWC pooling: odd shapes, padding, and the gated-out fallback
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape,kernel,stride,padding", [
    ((2, 7, 9, 8), (3, 3), (2, 2), (1, 1)),   # odd spatial + padding
    ((1, 5, 5, 4), (2, 2), (1, 1), (0, 0)),   # unit stride
    ((2, 8, 6, 8), (3, 2), (2, 1), (0, 1)),   # asymmetric everything
])
def test_pooling_kernel_parity(shape, kernel, stride, padding):
    x = RNG.normal(size=shape).astype(np.float32)
    window = (1,) + kernel + (1,)
    strides = (1,) + stride + (1,)
    pads = [(0, 0), (padding[0], padding[0]), (padding[1], padding[1]),
            (0, 0)]

    got_max = ppool.max_pool2d_nhwc(x, kernel, stride, padding)
    ref_max = jax.lax.reduce_window(x, -np.inf, jax.lax.max, window,
                                    strides, pads)
    np.testing.assert_array_equal(got_max, ref_max)

    # inclusive avg: padding contributes zeros, denominator is kh*kw
    got_avg = ppool.avg_pool2d_nhwc(x, kernel, stride, padding)
    ref_avg = jax.lax.reduce_window(x, 0.0, jax.lax.add, window, strides,
                                    pads) / float(np.prod(kernel))
    np.testing.assert_allclose(got_avg, ref_avg, rtol=1e-6, atol=1e-6)


def test_avg_pool_exclusive_with_padding_is_gated_out(_tpu_gate):
    x = jnp.zeros((1, 8, 8, 128), jnp.float32)
    assert ppool.supported(x, (2, 2), (1, 1), (0, 0), "avg", True)
    # exclusive + padding needs per-position counts: XLA fallback
    assert not ppool.supported(x, (3, 3), (1, 1), (1, 1), "avg", True)
    assert ppool.supported(x, (3, 3), (1, 1), (1, 1), "avg", False)
    # stride 2: Mosaic refuses the strided window slice (gated, not tried)
    assert not ppool.supported(x, (2, 2), (2, 2), (0, 0), "max", True)

    xr = RNG.normal(size=(1, 8, 8, 128)).astype(np.float32)
    got = F.avg_pool2d(xr, 3, stride=1, padding=1, exclusive=True,
                       data_format="NHWC")
    flags.set_flags({"use_pallas_pool": False})
    try:
        want = F.avg_pool2d(xr, 3, stride=1, padding=1, exclusive=True,
                            data_format="NHWC")
    finally:
        flags.set_flags({"use_pallas_pool": True})
    np.testing.assert_array_equal(got, want)


def test_functional_pool_dispatch_parity(_flags_guard, _tpu_gate):
    """With the gate open the functional layer routes NHWC pools through
    Pallas; the result must match the flag-off reduce_window path."""
    x = RNG.normal(size=(2, 9, 9, 128)).astype(np.float32)
    reg = monitor.default_registry()
    flags.set_flags({"metrics": True})
    base = reg.get("pallas.kernel_calls")
    calls0 = sum(v for _l, v in base.samples()) if base is not None else 0

    got = F.max_pool2d(x, 2, stride=1, data_format="NHWC")
    calls = reg.get("pallas.kernel_calls")
    calls1 = sum(v for _l, v in calls.samples()) if calls is not None else 0
    assert calls1 > calls0  # the Pallas branch actually ran
    # stride 2 is gated out (a Mosaic refusal): same entry, XLA lowering
    got2 = F.max_pool2d(x, 2, stride=2, data_format="NHWC")
    assert sum(v for _l, v in calls.samples()) == calls1
    flags.set_flags({"use_pallas_pool": False})
    np.testing.assert_array_equal(
        got, F.max_pool2d(x, 2, stride=1, data_format="NHWC"))
    np.testing.assert_array_equal(
        got2, F.max_pool2d(x, 2, stride=2, data_format="NHWC"))


# ---------------------------------------------------------------------------
# graph fusion in TRAINING graphs
# ---------------------------------------------------------------------------

def test_fuse_conv_bn_act_train_mode_golden_parity(_fresh_programs):
    """backward_region references only Loss+Params, so the conv+BN+act
    triple fuses in training graphs too — parity through the SGD step,
    optimizer state included."""
    main, startup = _fresh_programs
    img = L.data("img", [4, 8, 8])
    c = L.conv2d(img, 4, 3, padding=1)
    out = L.batch_norm(c, act="relu")        # training-mode BN
    loss = L.mean(out)
    static.optimizer.SGD(learning_rate=0.1).minimize(loss)
    assert "backward_region" in _op_types(main)

    rewritten, report = P.PassManager(("fuse_conv_bn_act",)).apply(
        main, feed_names={"img"}, fetch_names=[loss.name])
    assert "fused_conv2d_bn_act" in _op_types(rewritten)
    assert "batch_norm" not in _op_types(rewritten)
    fused = next(op for op in rewritten.global_block().ops
                 if op.type == "fused_conv2d_bn_act")
    assert fused.attrs["is_test"] is False
    # running-stat writebacks survive (they alias the Mean/Variance inputs)
    assert fused.outputs["MeanOut"] == fused.inputs["Mean"]
    assert fused.outputs["VarianceOut"] == fused.inputs["Variance"]

    feed = {"img": RNG.normal(size=(4, 4, 8, 8)).astype(np.float32)}
    parity = P.golden_parity(main, rewritten, feed, [loss.name],
                             state=_init_state(startup), rtol=1e-4,
                             atol=1e-5)
    assert parity.ok, parity.to_text()


# ---------------------------------------------------------------------------
# int8 inference path: quant_infer pass + quant op lowerings
# ---------------------------------------------------------------------------

def _resnet_block(scope):
    """conv-BN-relu -> conv-BN -> +residual -> relu, PTQ'd in place."""
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup), static.scope_guard(scope):
        img = L.data("img", [8, 6, 6])
        c1 = L.conv2d(img, 8, 3, padding=1)
        b1 = L.batch_norm(c1, act="relu", is_test=True)
        c2 = L.conv2d(b1, 8, 3, padding=1)
        b2 = L.batch_norm(c2, is_test=True)
        out = L.relu(L.elementwise_add(b2, img))
        exe = static.Executor()
        exe.run(startup)
    return main, out, exe


def _ptq(main, out, exe, scope, feed):
    with static.scope_guard(scope):
        ptq = quant_static.PostTrainingQuantization(
            exe, program=main, feed_names=list(feed),
            batch_generator=lambda: iter([feed]), batch_nums=1, scope=scope)
        return ptq.quantize()


def test_quant_infer_resnet_block_golden_parity():
    scope = static.Scope()
    main, out, exe = _resnet_block(scope)
    feed = {"img": RNG.normal(size=(2, 8, 6, 6)).astype(np.float32)}
    qprog = _ptq(main, out, exe, scope, feed)
    assert "fake_quantize_dequantize_fixed_scale" in _op_types(qprog)

    rewritten, report = P.PassManager(P.QUANT_INFER_PIPELINE).apply(
        qprog, feed_names={"img"}, fetch_names=[out.name])
    types = _op_types(rewritten)
    assert types.count("quant_conv2d") == 2
    assert "conv2d" not in types
    # both convs' activation qdq ops folded into the quant op's in_scale
    assert "fake_quantize_dequantize_fixed_scale" not in types
    q = next(op for op in rewritten.global_block().ops
             if op.type == "quant_conv2d")
    assert q.attrs["in_scale"] > 0 and len(q.attrs["weight_scale"]) == 8

    parity = P.golden_parity(qprog, rewritten, feed, [out.name],
                             state=_scope_state(scope), rtol=1e-4,
                             atol=1e-5)
    assert parity.ok, parity.to_text()


def test_quant_conv_flag_off_is_bitwise_fallback():
    """Off-gate the quant ops must replay the exact fake-quant graph —
    the simulate path calls the same fixed-scale lowering, so parity is
    bitwise, not approximate."""
    scope = static.Scope()
    main, out, exe = _resnet_block(scope)
    feed = {"img": RNG.normal(size=(2, 8, 6, 6)).astype(np.float32)}
    qprog = _ptq(main, out, exe, scope, feed)
    rewritten, _report = P.PassManager(("quant_infer",)).apply(
        qprog, feed_names={"img"}, fetch_names=[out.name])
    assert "quant_conv2d" in _op_types(rewritten)

    parity = P.golden_parity(qprog, rewritten, feed, [out.name],
                             state=_scope_state(scope), rtol=0.0, atol=0.0)
    assert parity.ok, parity.to_text()


def _fc128(scope):
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup), static.scope_guard(scope):
        x = L.data("x", [128])
        y = L.fc(x, 128, act="relu")
        exe = static.Executor()
        exe.run(startup)
    return main, y, exe


def test_quant_mul_pallas_int8_error_bound(_flags_guard, _tpu_gate):
    """The int8 Pallas matmul (interpret mode) must stay within a tight
    bound of the simulate path — the int8 grid recovery is exact, so the
    only drift is the fp32 dequant epilogue's summation order — and
    within the coarse PTQ error bound of the float program."""
    scope = static.Scope()
    main, y, exe = _fc128(scope)
    feed = {"x": RNG.normal(size=(8, 128)).astype(np.float32)}
    with static.scope_guard(scope):
        float_out, = exe.run(main, feed=feed, fetch_list=[y])

    qprog = _ptq(main, y, exe, scope, feed)
    rewritten, _report = P.PassManager(("quant_infer",)).apply(
        qprog, feed_names={"x"}, fetch_names=[y.name])
    assert "quant_mul" in _op_types(rewritten)

    with static.scope_guard(scope):
        sim_out, = exe.run(qprog, feed=feed, fetch_list=[y.name])
        flags.set_flags({"metrics": True})
        pal_out, = exe.run(rewritten, feed=feed, fetch_list=[y.name])
    np.testing.assert_allclose(pal_out, sim_out, rtol=1e-4, atol=1e-4)
    scale = np.abs(float_out).max()
    assert np.abs(pal_out - float_out).max() <= 0.05 * scale + 1e-3


def test_weight_quant_axis_contract():
    """Per-channel scales index the OUTPUT-channel axis: OIHW axis 0 for
    conv, the LAST axis for (in, out) mul weights.  Axis 0 of a mul
    weight is the contraction dim — a scale per *input* channel cannot be
    applied after the accumulation, so that reduction is the regression
    this test pins out."""
    assert weight_quant_axis("conv2d", 4) == 0
    assert weight_quant_axis("mul", 2) == 1
    assert weight_quant_axis("matmul", 2) == 1
    assert weight_quant_axis("unknown_op", 4) == 0

    scope = static.Scope()
    main, y, exe = _fc128(scope)
    with static.scope_guard(scope):
        wname = next(n for n in main.global_block().vars
                     if isinstance(main.global_block().vars[n],
                                   static.framework.Parameter)
                     and len(main.global_block().vars[n].shape) == 2)
        w_before = np.asarray(scope.find_var(wname)).copy()
    feed = {"x": RNG.normal(size=(8, 128)).astype(np.float32)}
    qprog = _ptq(main, y, exe, scope, feed)
    mul = next(op for op in qprog.global_block().ops if op.type == "mul")
    ws = np.asarray(mul.attrs["weight_scale"])
    assert ws.shape == (128,)
    np.testing.assert_allclose(
        ws, np.maximum(np.abs(w_before).max(axis=0), 1e-8), rtol=1e-6)


def test_qat_freeze_records_mul_quant_axis():
    """The QAT transform records quant_axis on the weight-qdq op so the
    freeze pass reduces over the right axes for mul weights too."""
    main, startup = static.Program(), static.Program()
    scope = static.Scope()
    with static.program_guard(main, startup), static.scope_guard(scope):
        x = L.data("x", [16])
        y = L.fc(x, 4)
        quant_static.QuantizationTransformPass().apply(main, startup)
        qdq = next(op for op in main.global_block().ops
                   if op.type ==
                   "fake_channel_wise_quantize_dequantize_abs_max")
        assert qdq.attrs["quant_axis"] == 1    # (in, out) weight: last axis
        scale_var = main.global_block().var(qdq.outputs["OutScale"][0])
        assert tuple(scale_var.shape) == (4,)  # one scale per OUTPUT unit


# ---------------------------------------------------------------------------
# executor cache identity: zero retraces, flag flip = one clean recompile
# ---------------------------------------------------------------------------

def test_kernel_fingerprint_zero_retraces_and_flag_flip(_flags_guard,
                                                        monkeypatch):
    flags.set_flags({"metrics": True})
    reg = monitor.default_registry()
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        x = L.data("x", [8])
        y = L.fc(x, 4, act="relu")
    feed = {"x": RNG.normal(size=(4, 8)).astype(np.float32)}
    scope = static.Scope()
    with static.scope_guard(scope):
        exe = static.Executor()
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[y])
        t0 = reg.get("executor.traces").value()
        for _ in range(3):
            base_out, = exe.run(main, feed=feed, fetch_list=[y])
        assert reg.get("executor.traces").value() == t0  # steady state

        # flag flip (gate opens) -> different executable -> ONE recompile
        monkeypatch.setattr(pcfg, "kernel_enabled",
                            lambda name: bool(flags.get_flag(name)))
        assert pcfg.cache_key_part() != ""
        gated_out, = exe.run(main, feed=feed, fetch_list=[y])
        t1 = reg.get("executor.traces").value()
        assert t1 == t0 + 1
        exe.run(main, feed=feed, fetch_list=[y])
        assert reg.get("executor.traces").value() == t1

        # flip back: the pre-flip executable is still cold-cached — no
        # retrace, and no stale cross-config hit either direction
        monkeypatch.setattr(pcfg, "kernel_enabled", lambda name: False)
        assert pcfg.cache_key_part() == ""
        back_out, = exe.run(main, feed=feed, fetch_list=[y])
        assert reg.get("executor.traces").value() == t1
        np.testing.assert_array_equal(base_out, back_out)
        np.testing.assert_allclose(gated_out, base_out, rtol=1e-5,
                                   atol=1e-6)


def test_kernel_fingerprint_rides_disk_cache_key(_tpu_gate):
    from paddle_tpu.static import compile_cache as cc

    main, _startup = static.Program(), static.Program()
    with static.program_guard(main, _startup):
        x = L.data("x", [8])
        y = L.fc(x, 4)
    feed = {"x": np.zeros((2, 8), np.float32)}
    common = dict(seed=0, fetch_names=[y.name], feed_arrays=feed,
                  donated={}, carried={}, donate=False,
                  plan_fingerprint=None)
    base = cc.build_cache_key(main, **common)
    assert cc.build_cache_key(main, **common, kernel="") == base
    fp = pcfg.cache_key_part()
    assert fp.startswith("pk") and "conv=1" in fp
    assert cc.build_cache_key(main, **common, kernel=fp) != base


# ---------------------------------------------------------------------------
# xprof: custom-call attribution coverage
# ---------------------------------------------------------------------------

_SYNTH_HLO = """\
ENTRY %main (p0: f32[2,10,10,64]) -> f32[2,8,8,64] {
  %p0 = f32[2,10,10,64]{3,2,1,0} parameter(0)
  %p1 = f32[3,3,64,64]{3,2,1,0} parameter(1)
  %p2 = f32[1,64]{1,0} parameter(2)
  %p3 = f32[1,64]{1,0} parameter(3)
  %q0 = s8[2,10,10,64]{3,2,1,0} parameter(4)
  %q1 = s8[3,3,64,64]{3,2,1,0} parameter(5)
  %m0 = s8[8,128]{1,0} parameter(6)
  %m1 = s8[128,128]{1,0} parameter(7)
  %cc0 = f32[2,8,8,64]{3,2,1,0} custom-call(f32[2,10,10,64]{3,2,1,0} %p0, f32[3,3,64,64]{3,2,1,0} %p1, f32[1,64]{1,0} %p2, f32[1,64]{1,0} %p3), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/fused_conv2d_bn_act.b0.i2/pallas.conv2d_bn_act"}
  %cc1 = f32[2,4,4,64]{3,2,1,0} custom-call(f32[2,8,8,64]{3,2,1,0} %cc0), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/pool2d.b0.i3/pallas.max_pool2d"}
  %cc2 = f32[2,8,8,64]{3,2,1,0} custom-call(s8[2,10,10,64]{3,2,1,0} %q0, s8[3,3,64,64]{3,2,1,0} %q1, f32[1,64]{1,0} %p2, f32[1,64]{1,0} %p3), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/quant_conv2d.b0.i4/pallas.int8_conv2d"}
  %cc3 = f32[8,128]{1,0} custom-call(s8[8,128]{1,0} %m0, s8[128,128]{1,0} %m1), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/quant_mul.b0.i5/pallas.int8_matmul"}
  %cc4 = f32[2,8,8,64]{3,2,1,0} custom-call(f32[2,8,8,64]{3,2,1,0} %cc0, f32[1,64]{1,0} %p2, f32[1,64]{1,0} %p3), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/fused_conv2d_bn_act.b0.i6/pallas.bn_act_train"}
  ROOT %out = f32[2,8,8,64]{3,2,1,0} add(f32[2,8,8,64]{3,2,1,0} %cc2, f32[2,8,8,64]{3,2,1,0} %cc4)
}
"""


def test_xprof_prices_pallas_custom_calls():
    """Every Pallas kernel family's custom-call is priced by its
    registered cost model (acceptance: >= 90% flops attribution coverage
    on a program dominated by Pallas custom-calls)."""
    report = xprof.build_report(_SYNTH_HLO, peaks=xprof.resolve_peaks(
        device_kind="test-device", peak_flops=200e9,
        peak_bytes_per_sec=40e9))
    regions = {r["region"]: r for r in report["regions"]}

    conv_flops = 2.0 * 2 * 8 * 8 * 64 * 64 * 3 * 3 + 3.0 * 2 * 8 * 8 * 64
    assert regions["fused_conv2d_bn_act.b0.i2"]["flops"] == conv_flops
    assert regions["quant_conv2d.b0.i4"]["flops"] == conv_flops
    assert regions["pool2d.b0.i3"]["flops"] > 0
    mm_flops = 2.0 * 8 * 128 * 128 + 3.0 * 8 * 128
    assert regions["quant_mul.b0.i5"]["flops"] == mm_flops
    assert regions["fused_conv2d_bn_act.b0.i6"]["flops"] == \
        3.0 * 2 * 8 * 8 * 64
    for key, r in regions.items():
        if key != "<unattributed>":
            assert r["attributed"], key
    assert report["totals"]["attribution_coverage"] >= 0.9


def test_unregistered_custom_call_prices_zero_not_crash():
    hlo = """\
ENTRY %main (p0: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8]{1,0} parameter(0)
  ROOT %cc = f32[8,8]{1,0} custom-call(f32[8,8]{1,0} %p0), custom_call_target="mystery", metadata={op_name="jit(f)/mystery_op"}
}
"""
    report = xprof.build_report(hlo)
    assert report["totals"]["flops_modeled"] == 0.0


def _instr(operands, out):
    f32 = lambda shapes: [("f32", s) for s in shapes]
    return xprof.HloInstr("cc", "custom-call", f32([out]), f32(operands),
                          "jit(f)/k", "")


_CONV = _instr([(2, 10, 10, 16), (3, 3, 16, 32)], (2, 8, 8, 32))
_POOL = (_instr([(2, 8, 8, 16)], (2, 4, 4, 16)),
         ppool.pool_cost(2, 4, 4, 16, 2, 2, in_h=8, in_w=8))
# tag -> (a call of that family as its registered pricer sees it, the
# family's own (flops, bytes) model at the same sizes)
_FAMILY_CALLS = {
    "pallas.conv2d_bn_act": (
        _CONV, cf.conv_cost(2, 8, 8, 16, 32, 3, 3, in_h=10, in_w=10)),
    "pallas.bn_act_train": (
        _instr([(128, 16)], (128, 16)), cf.bn_act_cost(128, 16)),
    "pallas.max_pool2d": _POOL,
    "pallas.avg_pool2d": _POOL,
    "pallas.int8_conv2d": (
        _CONV, pint8.int8_cost(2, 8, 8, 16, 32, 3, 3, in_h=10, in_w=10)),
    "pallas.int8_matmul": (
        _instr([(8, 128), (128, 64)], (8, 64)),
        pint8.int8_cost(8, 1, 1, 128, 64, 1, 1)),
    "pallas.paged_attention": (
        _instr([(4, 3), (4,), (4, 1, 128), (16, 8, 128), (16, 8, 128)],
               (4, 1, 128)),
        ppaged.paged_attention_cost(num_seqs=4, max_blocks=3, block_size=8,
                                    head_dim=128)),
}


@pytest.mark.parametrize("tag", sorted(_FAMILY_CALLS))
def test_every_kernel_family_prices_a_call_above_zero(tag):
    """A family whose cost reads 0 drops out of xprof's attribution and of
    every roofline built on it, silently."""
    instr, (flops, bytes_) = _FAMILY_CALLS[tag]
    assert pcfg.registered_costs()[tag](instr) > 0
    assert flops > 0 and bytes_ > 0


def test_every_registered_kernel_family_is_priced_above():
    assert sorted(pcfg.registered_costs()) == sorted(_FAMILY_CALLS)


# ---------------------------------------------------------------------------
# serving: quantized tenant registration
# ---------------------------------------------------------------------------

def test_serving_quantized_tenant_parity():
    from paddle_tpu.serving import Server

    scope = static.Scope()
    main, out, exe = _resnet_block(scope)
    feed = {"img": RNG.normal(size=(2, 8, 6, 6)).astype(np.float32)}
    qprog = _ptq(main, out, exe, scope, feed)
    with static.scope_guard(scope):
        ref, = exe.run(qprog, feed=feed, fetch_list=[out.name])

    srv = Server(bucket_edges=(1, 2, 4), max_wait_ms=2.0).start()
    try:
        srv.add_tenant("q", qprog, ["img"], [out], scope, quantize=True)
        tenant_types = _op_types(srv.tenants.get("q").program)
        assert "quant_conv2d" in tenant_types
        got = srv.submit("q", feed).result(timeout=120)[0]
    finally:
        srv.close()
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# tools ride tier-1
# ---------------------------------------------------------------------------

def _child_env():
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    return env


def test_metricsdump_lint_knows_pallas_names():
    out = subprocess.run(
        [sys.executable, "-m", "tools.metricsdump", "--lint"],
        cwd=REPO, env=_child_env(), capture_output=True, text=True,
        timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
