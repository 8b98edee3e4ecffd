"""Micro-benchmark of the Pallas vision kernels (ops/pallas/).

Runs each kernel directly (not through the static-graph dispatch gates) on
one representative shape, checks it against the plain-XLA reference, and
prints exactly ONE JSON line::

    {"backend": "cpu", "interpret": true, "iters": 5, "kernels": [
      {"kernel": "conv2d_bn_act", "shape": "...", "ms": ..,
       "flops": .., "bytes": .., "gflops_s": .., "gb_s": ..,
       "intensity": .., "max_abs_err": .., "tol": ..}, ...]}

* ``flops``/``bytes`` come from the SAME cost models the kernels register
  with ops/pallas/config.register_cost — so xprof attribution, roofline
  analysis and this tool can never disagree about what a call "should"
  cost.  ``intensity`` is flops/byte (compare against the TPU ridge).
* Off-TPU the kernels run in Pallas interpret mode: wall times then
  measure the interpreter, not the hardware — the modeled numbers are the
  portable output, the measured ones are only meaningful on a real TPU.
* ``max_abs_err`` is the deviation from the unfused XLA reference; every
  row carries its ``tol`` and the tool exits non-zero when any row is out
  of bound, so the bench doubles as a numerics canary.

Usage:
    python -m tools.kernelbench [--iters K] [--batch N] [--hw H] [--ch C]
    python -m tools.kernelbench --selfcheck     # tiny shapes: rides tier-1
"""
from __future__ import annotations

import argparse
import functools
import json
import statistics
import sys
import time


def _bench(fn, iters: int):
    """(result, median wall ms) — first call outside the clock (compile)."""
    import jax

    out = jax.block_until_ready(fn())
    times = []
    for _ in range(max(1, iters)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append((time.perf_counter() - t0) * 1e3)
    return out, statistics.median(times)


def _row(name, shape, ms, flops, bytes_, err, tol):
    return {
        "kernel": name,
        "shape": shape,
        "ms": round(ms, 4),
        "flops": float(flops),
        "bytes": float(bytes_),
        "gflops_s": round(flops / (ms * 1e6), 3) if ms > 0 else 0.0,
        "gb_s": round(bytes_ / (ms * 1e6), 3) if ms > 0 else 0.0,
        "intensity": round(flops / bytes_, 3) if bytes_ else 0.0,
        "max_abs_err": float(err),
        "tol": float(tol),
    }


def run_bench(iters: int, n: int, hw: int, ch: int, mk: int):
    """All kernel rows for one (batch, spatial, channel, matmul-dim) size."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.pallas import conv_fused as _cf
    from paddle_tpu.ops.pallas import int8 as _int8
    from paddle_tpu.ops.pallas import pooling as _pool

    rng = np.random.default_rng(0)
    rows = []
    dn = ("NHWC", "OIHW", "NHWC")

    # -- fused conv + BN + act (inference epilogue) ---------------------------
    kh = kw = 3
    x = rng.normal(size=(n, hw, hw, ch)).astype(np.float32)
    w = (rng.normal(size=(ch, ch, kh, kw)) * 0.1).astype(np.float32)
    a = rng.uniform(0.5, 1.5, size=(ch,)).astype(np.float32)
    b = rng.normal(size=(ch,)).astype(np.float32)
    fused = jax.jit(functools.partial(
        _cf.conv2d_bn_act, stride=(1, 1), padding=(1, 1), act="relu"))
    got, ms = _bench(lambda: fused(x, w, a, b), iters)
    ref = jax.nn.relu(jax.lax.conv_general_dilated(
        x, jnp.transpose(w, (2, 3, 1, 0)), (1, 1), [(1, 1), (1, 1)],
        dimension_numbers=("NHWC", "HWIO", "NHWC")) * a + b)
    flops, bytes_ = _cf.conv_cost(n, hw, hw, ch, ch, kh, kw,
                                  in_h=hw, in_w=hw)
    rows.append(_row("conv2d_bn_act", f"{n}x{hw}x{hw}x{ch} k{kh}", ms,
                     flops, bytes_, jnp.abs(got - ref).max(), 1e-3))

    # -- fused BN-stats + scale/shift + act (training mode) -------------------
    gamma = rng.uniform(0.5, 1.5, size=(ch,)).astype(np.float32)
    beta = rng.normal(size=(ch,)).astype(np.float32)
    bn = jax.jit(functools.partial(_cf.fused_bn_act_train, eps=1e-5,
                                   act="relu"))
    (y, mean, var), ms = _bench(lambda: bn(x, gamma, beta), iters)
    x2 = x.reshape(-1, ch)
    rmean = x2.mean(0)
    rvar = x2.var(0)
    ref = np.maximum((x2 - rmean) / np.sqrt(rvar + 1e-5) * gamma + beta, 0.0)
    err = max(float(jnp.abs(y.reshape(-1, ch) - ref).max()),
              float(jnp.abs(mean - rmean).max()),
              float(jnp.abs(var - rvar).max()))
    flops, bytes_ = _cf.bn_act_cost(n * hw * hw, ch)
    rows.append(_row("bn_act_train", f"{n}x{hw}x{hw}x{ch}", ms,
                     flops, bytes_, err, 1e-3))

    # -- NHWC pooling ---------------------------------------------------------
    for mode, fn, init, red in (
            ("max_pool2d", _pool.max_pool2d_nhwc, -np.inf, jax.lax.max),
            ("avg_pool2d", _pool.avg_pool2d_nhwc, 0.0, jax.lax.add)):
        pooled = jax.jit(functools.partial(fn, kernel=(2, 2), stride=(2, 2),
                                           padding=(0, 0)))
        got, ms = _bench(lambda: pooled(x), iters)
        ref = jax.lax.reduce_window(x, init, red, (1, 2, 2, 1),
                                    (1, 2, 2, 1), "VALID")
        if mode == "avg_pool2d":
            ref = ref / 4.0
        oh = hw // 2
        flops, bytes_ = _pool.pool_cost(n, oh, oh, ch, 2, 2, in_h=hw,
                                        in_w=hw)
        rows.append(_row(mode, f"{n}x{hw}x{hw}x{ch} k2s2", ms, flops,
                         bytes_, jnp.abs(got - ref).max(), 1e-5))

    # -- int8 matmul with fp32 per-channel dequant epilogue -------------------
    xq = rng.integers(-127, 128, size=(mk, mk), dtype=np.int8)
    wq = rng.integers(-127, 128, size=(mk, mk), dtype=np.int8)
    scale = rng.uniform(1e-4, 1e-3, size=(mk,)).astype(np.float32)
    bias = rng.normal(size=(mk,)).astype(np.float32)
    mm = jax.jit(functools.partial(_int8.int8_matmul_dequant, act="relu"))
    got, ms = _bench(lambda: mm(xq, wq, scale, bias), iters)
    ref = np.maximum(
        (xq.astype(np.int64) @ wq.astype(np.int64)) * scale + bias, 0.0)
    flops = 2.0 * mk * mk * mk + 3.0 * mk * mk
    bytes_ = float(2 * mk * mk + 4 * mk * mk + 8 * mk)
    rows.append(_row("int8_matmul", f"{mk}x{mk}x{mk}", ms, flops, bytes_,
                     jnp.abs(got - ref).max(), 1e-2))

    # -- int8 conv with fp32 per-channel dequant epilogue ---------------------
    xq4 = rng.integers(-127, 128, size=(n, hw, hw, ch), dtype=np.int8)
    wq4 = rng.integers(-127, 128, size=(ch, ch, kh, kw), dtype=np.int8)
    cscale = rng.uniform(1e-4, 1e-3, size=(ch,)).astype(np.float32)
    conv8 = jax.jit(functools.partial(_int8.int8_conv2d_dequant,
                                      stride=(1, 1), padding=(1, 1),
                                      act="relu"))
    got, ms = _bench(lambda: conv8(xq4, wq4, cscale, bias[:ch]), iters)
    ref = jax.nn.relu(jax.lax.conv_general_dilated(
        xq4.astype(np.float32),
        jnp.transpose(wq4, (2, 3, 1, 0)).astype(jnp.float32),
        (1, 1), [(1, 1), (1, 1)],
        dimension_numbers=("NHWC", "HWIO", "NHWC")) * cscale + bias[:ch])
    flops, bytes_ = _int8.int8_cost(n, hw, hw, ch, ch, kh, kw, in_h=hw,
                                    in_w=hw)
    rows.append(_row("int8_conv2d", f"{n}x{hw}x{hw}x{ch} k{kh}", ms,
                     flops, bytes_, jnp.abs(got - ref).max(), 1e-2))
    return rows


def _selfcheck(result) -> int:
    keys = {"kernel", "shape", "ms", "flops", "bytes", "gflops_s", "gb_s",
            "intensity", "max_abs_err", "tol"}
    names = {r["kernel"] for r in result["kernels"]}
    want = {"conv2d_bn_act", "bn_act_train", "max_pool2d", "avg_pool2d",
            "int8_matmul", "int8_conv2d"}
    if names != want:
        print(f"kernelbench selfcheck: kernel set {sorted(names)} != "
              f"{sorted(want)}", file=sys.stderr)
        return 1
    for r in result["kernels"]:
        if set(r) != keys:
            print(f"kernelbench selfcheck: bad row keys in {r['kernel']}",
                  file=sys.stderr)
            return 1
        if not (r["flops"] > 0 and r["bytes"] > 0 and r["ms"] >= 0):
            print(f"kernelbench selfcheck: non-positive cost in "
                  f"{r['kernel']}", file=sys.stderr)
            return 1
    print("kernelbench selfcheck: OK")
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m tools.kernelbench", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument("--iters", type=int, default=5,
                        help="timed reps per kernel (median reported)")
    parser.add_argument("--batch", type=int, default=2)
    parser.add_argument("--hw", type=int, default=16,
                        help="spatial size of the conv/pool inputs")
    parser.add_argument("--ch", type=int, default=32,
                        help="channel count (conv C=O)")
    parser.add_argument("--mk", type=int, default=128,
                        help="int8 matmul M=K=N")
    parser.add_argument("--selfcheck", action="store_true",
                        help="tiny shapes + schema/parity gate; rides tier-1")
    args = parser.parse_args(argv)

    if args.selfcheck:
        args.iters, args.batch, args.hw, args.ch, args.mk = 1, 1, 8, 8, 16

    import jax

    from paddle_tpu.core.jax_cache import configure_compile_cache
    from paddle_tpu.ops.pallas import config as _pcfg

    configure_compile_cache()
    rows = run_bench(args.iters, args.batch, args.hw, args.ch, args.mk)
    result = {
        "backend": jax.default_backend(),
        "interpret": not _pcfg.backend_is_tpu(),
        "iters": args.iters,
        "kernels": rows,
    }
    if args.selfcheck:
        rc = _selfcheck(result)
    else:
        rc = 0
    print(json.dumps(result, sort_keys=True))
    bad = [r["kernel"] for r in result["kernels"]
           if r["max_abs_err"] > r["tol"]]
    if bad:
        print(f"kernelbench: parity FAILED for {bad}", file=sys.stderr)
        return 1
    return rc


if __name__ == "__main__":
    sys.exit(main())
