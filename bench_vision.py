"""Vision benchmarks: ResNet-50 (BASELINE config 2) and YOLOv3 (config 4,
single-chip part) training throughput in images/sec/chip, plus the r06
static-graph INFERENCE ladder:

* ``conv_infer`` — a conv/BN/pool tower served through the Executor with
  ``opt_passes=default`` ON (the r06 default for inference benches),
  reporting the traced-op-count delta from the rewrite pipeline and the
  first-step compile-time delta vs the unoptimized program;
* ``int8_infer`` — the same tower PTQ'd (slim/quant_static.py) and folded
  to int8 ops by the ``quant_infer`` pass (static/passes.py
  QUANT_INFER_PIPELINE), reporting quantized throughput vs float and the
  int8-vs-float error.  The quant ops dispatch to the ops/pallas/int8
  kernels where their `supported()` gates pass.

Reference configs: PaddleClas ResNet-50 dygraph (224x224, momentum SGD) and
PaddleDetection YOLOv3-DarkNet53 (416x416, yolo_loss over 3 heads).  Kept
for the benchmark PR (ROADMAP S0) to absorb.  No chip, no number: exits
non-zero off-TPU.

Usage: python bench_vision.py [resnet50|yolov3|conv_infer|int8_infer|all]
Prints one JSON line per model (same schema as bench.py).
"""
from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu import autograd
from paddle_tpu.autograd import parameters_dict
from paddle_tpu.core.jax_cache import configure_compile_cache
from paddle_tpu.optimizer import Momentum
from paddle_tpu.utils import xprof
import paddle_tpu.nn.functional as F

# fwd FLOPs per image (2 x MACs, the convention behind the usual
# "ResNet-50 = 4.1 GFLOPs @224", "YOLOv3 = 65.9 BFLOPs @416" numbers);
# training ~= 3x forward (fwd + dW + dX)
_FWD_FLOPS = {"resnet50": 4.09e9, "yolov3": 65.86e9}


def _cast_tree(p, dtype):
    return jax.tree_util.tree_map(
        lambda x: x.astype(dtype)
        if jnp.issubdtype(x.dtype, jnp.floating) else x, p)


def _aot_step(step, example):
    """AOT-compile the jitted step against the bench inputs (the same
    compile the first jit dispatch would do) so the artifact the loop runs
    is also the xprof attribution source (BENCH_PROFILE=0 skips)."""
    if os.environ.get("BENCH_PROFILE", "1") == "0":
        return step, None
    aot = step.lower(*example).compile()
    return aot, aot


def _roofline_block(aot, measured_ms):
    """Condensed xprof block for the bench JSON line: per-layer regions
    (Layer named scopes), MFU, and the top memory-bound regions by name —
    the ResNet MFU-gap diagnosis the ROADMAP asks for."""
    report = xprof.profile_aot(aot, measured_ms=measured_ms)
    return xprof.summarize(report, top=5)


def _bench_loop(step, params, opt_state, feed, warmup, iters):
    for _ in range(warmup):
        params, opt_state, loss = step(params, opt_state, *feed)
    jax.block_until_ready(loss)
    # steps chain through the donated params; one wait ends the window
    t0 = time.perf_counter()
    for _ in range(iters):
        params, opt_state, loss = step(params, opt_state, *feed)
    jax.block_until_ready(loss)
    return time.perf_counter() - t0, float(loss)


def bench_resnet50():
    from paddle_tpu.vision import models as M

    batch = int(os.environ.get("BENCH_RESNET_BATCH", "256"))
    size = 224
    # NHWC is the TPU-native layout (channels on the 128-lane minor dim;
    # measured r05 ladder) — overridable for A/B via BENCH_RESNET_LAYOUT
    layout = os.environ.get("BENCH_RESNET_LAYOUT", "NHWC")
    warmup, iters = 3, int(os.environ.get("BENCH_ITERS", "30"))
    model = M.resnet50(num_classes=1000, data_format=layout)
    model.train()
    opt = Momentum(learning_rate=0.1, momentum=0.9)
    params = parameters_dict(model)
    opt_state = opt.init(params)
    compute_dtype = jnp.bfloat16

    def train_step(p, s, images, labels):
        def loss_fn(p_):
            logits = autograd.functional_call(
                model, _cast_tree(p_, compute_dtype), (images,))
            with jax.named_scope("loss"):
                return jnp.mean(F.cross_entropy(logits.astype(jnp.float32),
                                                labels))

        loss, grads = jax.value_and_grad(loss_fn)(p)
        with jax.named_scope("optimizer"):
            p, s = opt.update(grads, s, p)
        return p, s, loss

    step = jax.jit(train_step, donate_argnums=(0, 1))
    rng = np.random.default_rng(0)
    shape = ((batch, 3, size, size) if layout == "NCHW"
             else (batch, size, size, 3))
    images = jnp.asarray(rng.standard_normal(shape), compute_dtype)
    labels = jnp.asarray(rng.integers(0, 1000, (batch, 1)), jnp.int32)
    step, aot = _aot_step(step, (params, opt_state, images, labels))
    dt, loss = _bench_loop(step, params, opt_state, (images, labels),
                           warmup, iters)
    return dict(metric="resnet50_train_throughput", batch=batch,
                imgs_per_sec=batch * iters / dt, iters=iters, loss=loss,
                model="resnet50", size=size, layout=layout, _aot=aot)


def bench_yolov3():
    from paddle_tpu.vision.models.yolov3 import yolov3_darknet53

    # b64 amortizes the step's fixed costs that bound b32 (r05 ladder,
    # earlier setup)
    batch = int(os.environ.get("BENCH_YOLO_BATCH", "64"))
    size = 416
    n_gt = 16
    warmup, iters = 3, int(os.environ.get("BENCH_ITERS", "20"))
    model = yolov3_darknet53(num_classes=80)
    model.train()
    opt = Momentum(learning_rate=1e-4, momentum=0.9)
    params = parameters_dict(model)
    opt_state = opt.init(params)
    compute_dtype = jnp.bfloat16

    # bf16 head inputs to the loss measured NEUTRAL on throughput (r05
    # ladder) and yolo_loss promotes its grid math to fp32 either way,
    # so feed fp32 heads; BENCH_YOLO_LOSS_DTYPE remains for A/B
    loss_dtype = jnp.dtype(os.environ.get("BENCH_YOLO_LOSS_DTYPE", "")
                           or jnp.float32)

    def train_step(p, s, images, gt_box, gt_label):
        def loss_fn(p_):
            heads = autograd.functional_call(
                model, _cast_tree(p_, compute_dtype), (images,))
            heads = [h.astype(loss_dtype) for h in heads]
            with jax.named_scope("loss"):
                return model.loss(heads, gt_box, gt_label)

        loss, grads = jax.value_and_grad(loss_fn)(p)
        with jax.named_scope("optimizer"):
            p, s = opt.update(grads, s, p)
        return p, s, loss

    step = jax.jit(train_step, donate_argnums=(0, 1))
    rng = np.random.default_rng(0)
    images = jnp.asarray(rng.standard_normal((batch, 3, size, size)),
                         compute_dtype)
    # normalized cx/cy/w/h gt boxes (the yolo_loss contract)
    wh = rng.uniform(0.05, 0.4, (batch, n_gt, 2))
    cxy = rng.uniform(0.2, 0.8, (batch, n_gt, 2))
    gt_box = jnp.asarray(np.concatenate([cxy, wh], -1), jnp.float32)
    gt_label = jnp.asarray(rng.integers(0, 80, (batch, n_gt)), jnp.int32)
    step, aot = _aot_step(step, (params, opt_state, images, gt_box, gt_label))
    dt, loss = _bench_loop(step, params, opt_state,
                           (images, gt_box, gt_label), warmup, iters)
    return dict(metric="yolov3_train_throughput", batch=batch,
                imgs_per_sec=batch * iters / dt, iters=iters, loss=loss,
                model="yolov3", size=size, _aot=aot)


# ---------------------------------------------------------------------------
# r06 inference ladder: opt_passes-on conv tower + int8 PTQ path
# ---------------------------------------------------------------------------

def _conv_tower():
    """Static conv/BN(relu)/pool x2 + fc head at the width the Pallas gates
    need (C=128 lanes)."""
    import paddle_tpu.static as static
    from paddle_tpu.static import layers as L

    ch = 128
    size = 32
    main, startup = static.Program(), static.Program()
    main.random_seed = startup.random_seed = 11
    with static.program_guard(main, startup):
        img = L.data("img", [3, size, size])
        h = L.conv2d(img, ch, 3, padding=1)
        h = L.batch_norm(h, act="relu", is_test=True)
        h = L.pool2d(h, 2, "max", 2)
        h = L.conv2d(h, ch, 3, padding=1)
        h = L.batch_norm(h, act="relu", is_test=True)
        h = L.pool2d(h, 2, "max", 2)
        out = L.fc(L.flatten(h), 10)
    return main, startup, out, size


def _infer_loop(exe, program, feed, fetch, scope, warmup, iters):
    """(first-step ms, steady imgs/sec) for one Executor config."""
    import paddle_tpu.static as static

    with static.scope_guard(scope):
        t0 = time.perf_counter()
        exe.run(program, feed=feed, fetch_list=fetch)
        first_ms = (time.perf_counter() - t0) * 1e3
        for _ in range(warmup):
            exe.run(program, feed=feed, fetch_list=fetch)
        t0 = time.perf_counter()
        for _ in range(iters):
            out, = exe.run(program, feed=feed, fetch_list=fetch)
        dt = time.perf_counter() - t0
    batch = next(iter(feed.values())).shape[0]
    return first_ms, batch * iters / dt, out


def bench_conv_infer():
    import paddle_tpu.static as static
    from paddle_tpu.core import flags
    from paddle_tpu.static import passes as P

    batch = 64
    warmup, iters = 3, int(os.environ.get("BENCH_ITERS", "30"))
    main, startup, out, size = _conv_tower()
    rng = np.random.default_rng(0)
    feed = {"img": rng.standard_normal(
        (batch, 3, size, size)).astype(np.float32)}

    # traced-op-count delta straight from the pipeline the flag runs
    _rw, report = P.PassManager(P.DEFAULT_PIPELINE).apply(
        main, feed_names={"img"}, fetch_names=[out.name])

    saved = flags.get_flags(["opt_passes"])
    results = {}
    try:
        for mode in ("", "default"):
            flags.set_flags({"opt_passes": mode})
            scope = static.Scope()
            with static.scope_guard(scope):
                exe = static.Executor()
                exe.run(startup)
            results[mode or "off"] = _infer_loop(
                exe, main, feed, [out], scope, warmup, iters)
    finally:
        flags.set_flags(saved)
    first_off, ips_off, ref = results["off"]
    first_on, ips_on, got = results["default"]
    err = float(np.abs(np.asarray(got) - np.asarray(ref)).max())
    return dict(metric="conv_infer_throughput", imgs_per_sec=ips_on,
                model="conv_infer", batch=batch, size=size, iters=iters,
                ops_traced_before=report.ops_before,
                ops_traced_after=report.ops_after,
                compile_ms={"opt_off": round(first_off, 1),
                            "opt_on": round(first_on, 1)},
                vs_opt_off=round(ips_on / ips_off, 4),
                opt_abs_err=err)


def bench_int8_infer():
    import paddle_tpu.static as static
    from paddle_tpu.slim import quant_static
    from paddle_tpu.static import passes as P

    batch = 64
    warmup, iters = 3, int(os.environ.get("BENCH_ITERS", "30"))
    main, startup, out, size = _conv_tower()
    rng = np.random.default_rng(0)
    feed = {"img": rng.standard_normal(
        (batch, 3, size, size)).astype(np.float32)}

    scope = static.Scope()
    with static.scope_guard(scope):
        exe = static.Executor()
        exe.run(startup)
    # float baseline BEFORE PTQ mutates the weights in scope
    _first, ips_f32, float_out = _infer_loop(exe, main, feed, [out], scope,
                                             warmup, iters)
    with static.scope_guard(scope):
        ptq = quant_static.PostTrainingQuantization(
            exe, program=main, feed_names=["img"],
            batch_generator=lambda: iter([feed]), batch_nums=1, scope=scope)
        qprog = ptq.quantize()
    rewritten, _report = P.PassManager(P.QUANT_INFER_PIPELINE).apply(
        qprog, feed_names={"img"}, fetch_names=[out.name])
    quant_ops = sum(1 for op in rewritten.global_block().ops
                    if op.type.startswith("quant_"))
    first_ms, ips_q, q_out = _infer_loop(exe, rewritten, feed, [out.name],
                                         scope, warmup, iters)
    scale = float(np.abs(np.asarray(float_out)).max()) or 1.0
    err = float(np.abs(np.asarray(q_out)
                       - np.asarray(float_out)).max()) / scale
    return dict(metric="int8_infer_throughput", imgs_per_sec=ips_q,
                model="int8_infer", batch=batch, size=size, iters=iters,
                quant_ops=quant_ops, compile_ms=round(first_ms, 1),
                vs_f32=round(ips_q / ips_f32, 4),
                int8_rel_err=round(err, 5))


def main():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"bench_vision.py needs a TPU; JAX found platform "
                 f"{dev.platform!r}")
    configure_compile_cache()
    peaks = xprof.resolve_peaks()  # keyed by device_kind; unknown raises
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    runs = {"resnet50": bench_resnet50, "yolov3": bench_yolov3,
            "conv_infer": bench_conv_infer, "int8_infer": bench_int8_infer}
    if which != "all" and which not in runs:
        sys.exit(f"usage: bench_vision.py [{'|'.join(runs)}|all] "
                 f"(got {which!r})")
    targets = list(runs) if which == "all" else [which]
    for name in targets:
        r = runs[name]()
        ips = r.pop("imgs_per_sec")
        mfu = None
        if name in _FWD_FLOPS:
            mfu = round(ips * 3 * _FWD_FLOPS[name] / peaks.flops_per_sec, 4)
        loss = r.pop("loss", None)
        aot = r.pop("_aot", None)
        roofline = (_roofline_block(aot, measured_ms=1000.0 * r["batch"] / ips)
                    if aot is not None else None)
        line = {
            "metric": r.pop("metric"),
            "value": round(ips, 2),
            "unit": "imgs/sec/chip",
            "device": device,
            "mfu_est": mfu,
            **r,
        }
        if name in _FWD_FLOPS:
            # NaN would break the one-JSON-line contract
            line["loss"] = round(loss, 4) \
                if loss is not None and np.isfinite(loss) else None
            line["roofline"] = roofline
        print(json.dumps(line))


if __name__ == "__main__":
    main()
