"""Collective-communication benchmark: gradient allreduce GB/s and
end-to-end training throughput for {none, int8, fp8} payloads x
{flat, hierarchical} schedules (parallel/compress.py).

Prints exactly ONE JSON line:

  * ``configs`` — per (compress, schedule) pair: median wall ms of one
    allreduce of ``--mb`` MB of fp32 gradients, achieved wire GB/s, the
    wire-byte accounting (`compress.wire_bytes`: 2*(n-1)/n * payload, where
    a quantized payload is 1 byte/element + one fp32 scale per block) and
    its ratio to the fp32 baseline.  On forced-host CPU devices the wall
    times measure scheduling, not ICI — the wire accounting is the
    portable number (cost_analysis does not model inter-device traffic).
  * ``parity`` — correctness gates against plain ``lax.psum``: the
    unquantized path (flat AND hierarchical) must be **bitwise** equal on
    integer-valued fp32 data (any summation order is exact there); the
    quantized paths must land within a bounded relative error.
  * ``train`` — a toy data-parallel regression trained through
    ``fleet.distributed_optimizer`` with ``DistributedStrategy.
    comm_quantize`` in {"", "none", "int8", "fp8"}: rows/sec ("tok_s") per
    mode plus the final-loss delta of each quantized run vs the exact one.

Usage:
    python -m tools.collbench [--devices N] [--mb MB] [--iters K]
                              [--steps S] [--block-size B]
    python -m tools.collbench --selfcheck     # smoke: rides tier-1
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time


def _ensure_cpu_devices(n: int) -> None:
    """Must run BEFORE jax imports: on CPU-only hosts, force enough virtual
    XLA devices for an N-way mesh (no-op if jax is already in, e.g. when a
    harness exported its own XLA_FLAGS)."""
    if "jax" in sys.modules:
        return
    env = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in env:
        os.environ["XLA_FLAGS"] = (
            env + f" --xla_force_host_platform_device_count={n}").strip()


def _mesh(devices: int):
    import numpy as np
    import jax
    from jax.sharding import Mesh

    if len(jax.devices()) < devices:
        raise SystemExit(
            f"need {devices} devices, have {len(jax.devices())} "
            "(run before other jax users or set XLA_FLAGS)")
    return Mesh(np.asarray(jax.devices()[:devices]), ("dp",))


def _hier(schedule: str, devices: int):
    """Hierarchy spec for a schedule name.  On a single forced host
    jax.local_device_count()==devices so "auto" degrades to flat; the
    hierarchical rows pin an explicit 2-way intra split to exercise the
    intra-RS -> inter-AR -> intra-AG lowering."""
    if schedule == "flat":
        return None
    return 2 if devices % 2 == 0 and devices > 2 else None


def _allreduce_bench(kind, schedule, nelem, iters, devices, block_size):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.parallel import compress as C
    from paddle_tpu.parallel.collective import shard_map

    m = _mesh(devices)
    hier = _hier(schedule, devices)

    def ar(v):
        return C.optimized_all_reduce(v, "dp", compress=kind,
                                      block_size=block_size, hierarchy=hier,
                                      mean=False)

    f = jax.jit(shard_map(ar, mesh=m, in_specs=(P("dp"),),
                          out_specs=P("dp")))
    x = jnp.asarray(
        np.random.RandomState(0).randn(devices, nelem).astype(np.float32))
    jax.block_until_ready(f(x))  # compile
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(f(x))
        times.append((time.perf_counter() - t0) * 1e3)
    ms = statistics.median(times)
    wire = C.wire_bytes(nelem, kind, block_size, devices)
    raw = C.wire_bytes(nelem, None, block_size, devices)
    return {
        "compress": kind or "none",
        "schedule": schedule,
        "ms": round(ms, 4),
        "gbps": round(wire / (ms / 1e3) / 1e9, 3) if ms > 0 else None,
        "wire_bytes": wire,
        "wire_ratio": round(wire / raw, 4),
    }


def _parity(nelem, devices, block_size):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.parallel import compress as C
    from paddle_tpu.parallel.collective import shard_map

    m = _mesh(devices)
    hier = _hier("hier", devices)

    def run(fn, x):
        return shard_map(fn, mesh=m, in_specs=(P("dp"),),
                         out_specs=P("dp"))(x)

    # integer-valued fp32: every summation order is exact, so bitwise
    # equality across schedules is a meaningful check
    xi = jnp.asarray(np.random.RandomState(1).randint(
        -8, 9, (devices, nelem)).astype(np.float32))
    exact_i = run(lambda v: jax.lax.psum(v, "dp"), xi)
    flat_i = run(lambda v: C.optimized_all_reduce(
        v, "dp", compress=None, hierarchy=None, mean=False), xi)
    hier_i = run(lambda v: C.optimized_all_reduce(
        v, "dp", compress=None, hierarchy=hier, mean=False), xi)
    bitwise = bool(jnp.all(exact_i == flat_i)) and \
        bool(jnp.all(exact_i == hier_i))

    xf = jnp.asarray(
        np.random.RandomState(2).randn(devices, nelem).astype(np.float32))
    exact = run(lambda v: jax.lax.psum(v, "dp"), xf)
    scale = float(jnp.max(jnp.abs(exact)))

    def rel_err(kind, hr):
        out = run(lambda v: C.optimized_all_reduce(
            v, "dp", compress=kind, block_size=block_size, hierarchy=hr,
            mean=False), xf)
        return round(float(jnp.max(jnp.abs(out - exact))) / scale, 6)

    report = {
        "unquantized_bitwise": bitwise,
        "int8_rel_err": rel_err("int8", None),
        "int8_hier_rel_err": rel_err("int8", hier),
    }
    if hasattr(jnp, "float8_e4m3fn"):
        report["fp8_rel_err"] = rel_err("fp8", None)
    return report


def _train_run(comm_quantize, steps, batch, dim, devices):
    """Toy dp regression through fleet.distributed_optimizer: returns
    (rows/sec in steady state, final loss)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.optimizer import SGD
    from paddle_tpu.parallel import mesh as mesh_mod
    from paddle_tpu.parallel.collective import shard_map
    from paddle_tpu.parallel.fleet import (DistributedOptimizer,
                                           DistributedStrategy)

    m = _mesh(devices)
    mesh_mod.set_mesh(m)
    try:
        strategy = DistributedStrategy()
        strategy.comm_quantize = comm_quantize
        strategy.comm_configs.hierarchical = _hier("hier", devices) or "off"
        opt = DistributedOptimizer(SGD(0.05), strategy)

        rng = np.random.RandomState(0)
        w_true = rng.randn(dim, 1).astype(np.float32)
        xs = jnp.asarray(rng.randn(batch, dim).astype(np.float32))
        ys = jnp.asarray((np.asarray(xs) @ w_true).astype(np.float32))
        params = {"w": jnp.zeros((dim, 1), jnp.float32)}
        state = opt.init(params)

        def step_fn(x, y, p, s):
            def loss_fn(p_):
                return jnp.mean((x @ p_["w"] - y) ** 2)

            loss, grads = jax.value_and_grad(loss_fn)(p)
            if not comm_quantize:
                # builder-owned sync (legacy contract when comm_quantize="")
                grads = jax.tree_util.tree_map(
                    lambda g: jax.lax.pmean(g, "dp"), grads)
            new_p, new_s = opt.update(grads, s, p)
            return jax.lax.pmean(loss, "dp"), new_p, new_s

        f = jax.jit(shard_map(
            step_fn, mesh=m, in_specs=(P("dp"), P("dp"), P(), P()),
            out_specs=(P(), P(), P())))
        loss, params, state = f(xs, ys, params, state)  # compile + step 1
        jax.block_until_ready(loss)
        t0 = time.perf_counter()
        for _ in range(steps - 1):
            loss, params, state = f(xs, ys, params, state)
        loss = jax.block_until_ready(loss)
        dt = time.perf_counter() - t0
        tok_s = batch * max(steps - 1, 1) / dt if dt > 0 else None
        return (round(tok_s) if tok_s else None), float(loss)
    finally:
        mesh_mod.set_mesh(None)


def run_bench(args) -> dict:
    nelem = max(1024, int(args.mb * (1 << 20) / 4))
    result = {
        "bench": "collbench",
        "devices": args.devices,
        "tensor_mb": round(nelem * 4 / (1 << 20), 3),
        "block_size": args.block_size,
        "schema": 1,
    }
    import jax.numpy as jnp
    kinds = [None, "int8"] + (["fp8"] if hasattr(jnp, "float8_e4m3fn") else [])
    result["configs"] = [
        _allreduce_bench(kind, schedule, nelem, args.iters, args.devices,
                         args.block_size)
        for kind in kinds for schedule in ("flat", "hier")]
    result["parity"] = _parity(min(nelem, 1 << 15), args.devices,
                               args.block_size)
    train = {}
    losses = {}
    for mode in ("", "none", "int8") + (
            ("fp8",) if hasattr(jnp, "float8_e4m3fn") else ()):
        tok_s, loss = _train_run(mode, args.steps, args.batch, args.dim,
                                 args.devices)
        name = mode or "builder"
        train[f"tok_s_{name}"] = tok_s
        losses[name] = loss
        train[f"loss_{name}"] = round(loss, 6)
    for q in ("int8", "fp8"):
        if q in losses:
            train[f"loss_delta_{q}"] = round(
                abs(losses[q] - losses["builder"]), 6)
    result["train"] = train
    return result


def _selfcheck(result) -> int:
    """Acceptance gates (ISSUE 7): schema fields, unquantized bitwise
    parity, int8 wire ratio <= 30% of fp32, bounded quantization error,
    quantized final loss within tolerance of the exact run."""
    errors = []
    for field in ("configs", "parity", "train", "devices"):
        if field not in result:
            errors.append(f"missing field {field!r}")
    if not result.get("parity", {}).get("unquantized_bitwise"):
        errors.append("unquantized path is not bitwise-equal to lax.psum")
    int8_rows = [c for c in result.get("configs", [])
                 if c["compress"] == "int8"]
    if not int8_rows:
        errors.append("no int8 config rows")
    for c in int8_rows:
        if c["wire_ratio"] > 0.30:
            errors.append(
                f"int8 {c['schedule']} wire_ratio {c['wire_ratio']} > 0.30")
    par = result.get("parity", {})
    if par.get("int8_rel_err", 1.0) > 0.05:
        errors.append(f"int8 rel err {par.get('int8_rel_err')} > 0.05")
    if par.get("int8_hier_rel_err", 1.0) > 0.05:
        errors.append(
            f"int8 hier rel err {par.get('int8_hier_rel_err')} > 0.05")
    if "fp8_rel_err" in par and par["fp8_rel_err"] > 0.2:
        errors.append(f"fp8 rel err {par['fp8_rel_err']} > 0.2")
    train = result.get("train", {})
    if abs(train.get("loss_none", 0.0)
           - train.get("loss_builder", 1.0)) > 1e-4:
        errors.append("owned unquantized sync diverges from builder sync")
    if train.get("loss_delta_int8", 1.0) > 0.05:
        errors.append(
            f"int8 final-loss delta {train.get('loss_delta_int8')} > 0.05")
    if errors:
        print("SELFCHECK FAIL:", "; ".join(errors), file=sys.stderr)
        return 1
    print("selfcheck ok", file=sys.stderr)
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="collbench", description=__doc__)
    p.add_argument("--devices", type=int, default=8)
    p.add_argument("--mb", type=float, default=16.0,
                   help="gradient tensor size in MB (fp32)")
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--batch", type=int, default=256)
    p.add_argument("--dim", type=int, default=64)
    p.add_argument("--block-size", type=int, default=256)
    p.add_argument("--selfcheck", action="store_true",
                   help="small sizes + acceptance gates; exit 0/1")
    args = p.parse_args(argv)
    _ensure_cpu_devices(args.devices)
    from paddle_tpu.core.jax_cache import configure_compile_cache

    configure_compile_cache()
    if args.selfcheck:
        args.mb, args.iters, args.steps = 0.25, 3, 12
        args.batch, args.dim = 64, 16
    result = run_bench(args)
    print(json.dumps(result))
    if args.selfcheck:
        return _selfcheck(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
