"""Steady-state Executor step micro-benchmark: host overhead of the
dispatch path, donation+async fast path vs today's copy+sync path.

Builds a tiny static program (one hidden fc + SGD step), runs N
steady-state steps in two modes, and prints exactly ONE JSON line:

  * ``fast``  — ``donate_state=1`` + ``return_numpy=False``: the state
    pytree stays device-resident and chained step to step, the PRNG fold
    happens inside the compiled function, and the fetch comes back as an
    unmaterialized ``jax.Array``, so ``Executor.run`` returns as soon as
    XLA has the step enqueued.  Host cost = the Python rim only.  (On
    TPU/GPU the flag additionally donates the state buffers; on CPU
    donation is skipped because XLA:CPU executes donated computations
    synchronously — see ``executor._donation_async_safe``.)
  * ``sync``  — ``donate_state=0`` + ``return_numpy=True``: every step
    round-trips a fresh copy of the state and forces the fetch through
    ``np.asarray`` (a blocking device sync), today's default-copy
    semantics.

``host_ms_*`` is the median wall time of one ``Executor.run`` call in
steady state (after warmup, compile excluded).  ``speedup`` is
``host_ms_sync / host_ms_fast`` — the per-step host overhead reduction the
fast path buys.  ``parity`` confirms both modes produced identical losses
(donation does not change math).  The ``metrics`` flag is forced off inside
the timed region so the instrumented step_time sync (see
``executor.step_time_ms``) does not serialize the fast path.

Two optional extra modes ride the same JSON line:

  * ``--mesh N`` — run the SHARDED fast path too: the same program compiled
    through ``CompiledProgram.with_sharding`` on an N-device dp mesh (feeds
    batch-sharded, state donated where the platform allows), reporting
    ``host_ms_sharded`` — the per-step host rim of the multi-device dispatch
    — next to the single-device numbers.  On CPU hosts the virtual device
    count is forced up before jax imports.
  * ``--cache [DIR]`` — measure the persistent AOT executable cache
    (``static/compile_cache.py``): first run against an empty DIR compiles
    and stores (``cold_start_ms``), a second run from a fresh Executor
    deserializes the stored executable (``warm_start_ms``, ``cache_hits``),
    skipping Python tracing/lowering entirely.  DIR defaults to a
    temp directory.  Both runs share ONE Program object: the global
    unique-name counter makes a rebuilt program fingerprint-different
    within a process (fresh processes regenerate identical names, which is
    the real cross-process warm-start story — see tests).

Usage:
    python -m tools.stepbench [--steps N] [--batch B] [--hidden H]
                              [--mesh N] [--cache [DIR]]
    python -m tools.stepbench --selfcheck     # smoke: rides tier-1
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import tempfile
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


def _run_mode(donate: bool, async_dispatch: bool, steps: int, batch: int,
              hidden: int):
    """Fresh program + scope per mode; returns (median_host_ms, losses)."""
    import numpy as np

    import paddle_tpu.static as static
    from paddle_tpu.core import flags
    from paddle_tpu.static import layers as L

    main, startup = static.Program(), static.Program()
    main.random_seed = 7
    startup.random_seed = 7
    scope = static.Scope()
    saved = flags.get_flags(["donate_state", "metrics"])
    try:
        flags.set_flags({"donate_state": donate, "metrics": False})
        with static.program_guard(main, startup), static.scope_guard(scope):
            x = L.data("x", [hidden])
            y = L.data("y", [1])
            h = L.fc(x, hidden, act="relu")
            pred = L.fc(h, 1)
            loss = L.mean(L.square(L.elementwise_sub(pred, y)))
            static.optimizer.SGD(learning_rate=0.01).minimize(loss)

            exe = static.Executor()
            exe.run(startup)
            rng = np.random.default_rng(0)
            feed = {"x": rng.normal(0, 1, (batch, hidden)).astype(np.float32),
                    "y": rng.normal(0, 1, (batch, 1)).astype(np.float32)}
            fetch = [loss]
            return_numpy = not async_dispatch
            for _ in range(3):  # warmup: compile + settle the caches
                out = exe.run(main, feed=feed, fetch_list=fetch,
                              return_numpy=return_numpy)
            np.asarray(out[0])  # drain warmup dispatches

            host_ms, losses = [], []
            for _ in range(steps):
                t0 = time.perf_counter()
                out = exe.run(main, feed=feed, fetch_list=fetch,
                              return_numpy=return_numpy)
                host_ms.append((time.perf_counter() - t0) * 1000.0)
                losses.append(out[0])
            # materialize at the end only — the async mode's device work
            # drains here, off the per-call host clock
            losses = [float(np.asarray(l)) for l in losses]
        return statistics.median(host_ms), losses
    finally:
        flags.set_flags(saved)


def _run_sharded(steps: int, batch: int, hidden: int, n_dev: int):
    """Sharded fast path on an N-device dp mesh (global batch, feeds
    batch-sharded, state replicated); returns (median_host_ms, losses)."""
    import jax
    import numpy as np

    import paddle_tpu.static as static
    from paddle_tpu.core import flags
    from paddle_tpu.parallel.mesh import DP_AXIS
    from paddle_tpu.static import layers as L

    devs = jax.devices()[:n_dev]
    if len(devs) < n_dev:
        raise SystemExit(
            f"--mesh {n_dev}: only {len(devs)} device(s) visible "
            "(set XLA_FLAGS=--xla_force_host_platform_device_count=N "
            "before python starts, or lower --mesh)")
    mesh = jax.sharding.Mesh(np.asarray(devs), (DP_AXIS,))

    main, startup = static.Program(), static.Program()
    main.random_seed = 7
    startup.random_seed = 7
    scope = static.Scope()
    saved = flags.get_flags(["donate_state", "metrics"])
    try:
        flags.set_flags({"donate_state": True, "metrics": False})
        with static.program_guard(main, startup), static.scope_guard(scope):
            x = L.data("x", [hidden])
            y = L.data("y", [1])
            h = L.fc(x, hidden, act="relu")
            pred = L.fc(h, 1)
            loss = L.mean(L.square(L.elementwise_sub(pred, y)))
            static.optimizer.SGD(learning_rate=0.01).minimize(loss)

            exe = static.Executor()
            exe.run(startup)
            compiled = static.CompiledProgram(main).with_sharding(mesh=mesh)
            rng = np.random.default_rng(0)
            feed = {"x": rng.normal(0, 1, (batch, hidden)).astype(np.float32),
                    "y": rng.normal(0, 1, (batch, 1)).astype(np.float32)}
            for _ in range(3):
                out = exe.run(compiled, feed=feed, fetch_list=[loss],
                              return_numpy=False)
            np.asarray(out[0])

            host_ms, losses = [], []
            for _ in range(steps):
                t0 = time.perf_counter()
                out = exe.run(compiled, feed=feed, fetch_list=[loss],
                              return_numpy=False)
                host_ms.append((time.perf_counter() - t0) * 1000.0)
                losses.append(out[0])
            losses = [float(np.asarray(l)) for l in losses]
        return statistics.median(host_ms), losses
    finally:
        flags.set_flags(saved)


def _cache_bench(steps: int, batch: int, hidden: int, cache_dir: str) -> dict:
    """Cold vs warm start through the persistent executable cache.  ONE
    Program object, fresh Scope+Executor per run: run 1 populates the cache
    (miss), run 2 deserializes it (hit) without re-tracing."""
    import numpy as np

    import paddle_tpu.static as static
    from paddle_tpu.core import flags
    from paddle_tpu.static import layers as L
    from paddle_tpu.utils import monitor

    main, startup = static.Program(), static.Program()
    main.random_seed = 7
    startup.random_seed = 7
    with static.program_guard(main, startup):
        x = L.data("x", [hidden])
        y = L.data("y", [1])
        h = L.fc(x, hidden, act="relu")
        pred = L.fc(h, 1)
        loss = L.mean(L.square(L.elementwise_sub(pred, y)))
        static.optimizer.SGD(learning_rate=0.01).minimize(loss)

    rng = np.random.default_rng(0)
    feed = {"x": rng.normal(0, 1, (batch, hidden)).astype(np.float32),
            "y": rng.normal(0, 1, (batch, 1)).astype(np.float32)}
    reg = monitor.default_registry()

    def counter(name):
        m = reg.get(name)
        return m.value() if m is not None else 0

    def one_run():
        scope = static.Scope()
        with static.scope_guard(scope):
            exe = static.Executor()
            exe.run(startup)
            t0 = time.perf_counter()
            out = exe.run(main, feed=feed, fetch_list=[loss],
                          return_numpy=False)
            start_ms = (time.perf_counter() - t0) * 1000.0
            losses = [float(np.asarray(out[0]))]
            for _ in range(max(0, steps - 1)):
                out = exe.run(main, feed=feed, fetch_list=[loss],
                              return_numpy=False)
                losses.append(float(np.asarray(out[0])))
        return start_ms, losses

    saved = flags.get_flags(["donate_state", "metrics", "compile_cache_dir"])
    try:
        # metrics must be on for the hit/miss counters; only first-run wall
        # time (compile-dominated) is reported, so the per-step metric sync
        # does not pollute the numbers
        flags.set_flags({"donate_state": True, "metrics": True,
                         "compile_cache_dir": cache_dir})
        cold_ms, cold_losses = one_run()
        hits0 = counter("executor.compile_cache_hit")
        traces0 = counter("executor.traces")
        warm_ms, warm_losses = one_run()
        hits = counter("executor.compile_cache_hit") - hits0
        traces = counter("executor.traces") - traces0
    finally:
        flags.set_flags(saved)
    return {
        "cold_start_ms": round(cold_ms, 2),
        "warm_start_ms": round(warm_ms, 2),
        "cold_warm_ratio": round(cold_ms / warm_ms, 2) if warm_ms > 0 else None,
        "cache_hits": hits,
        "warm_traces": traces,  # 0 = the warm run never re-traced python
        "cache_parity": cold_losses == warm_losses,
        "cache_dir": cache_dir,
    }


def _run_autoplan(steps: int, batch: int, hidden: int, n_dev: int) -> dict:
    """Cost-model plan search over the bench program (parallel/autoplan.py):
    searches an N-device mesh, then measures the chosen plan's steady-state
    host step time next to the hand dp baseline.  Returned as flat numeric
    scalars so ``record.autoplan.*`` flows straight through benchdiff."""
    import jax
    import numpy as np

    import paddle_tpu.static as static
    from paddle_tpu.core import flags
    from paddle_tpu.parallel import autoplan
    from paddle_tpu.parallel.sharding import ShardingPlan
    from paddle_tpu.static import layers as L

    devs = list(jax.devices()[:n_dev])
    if len(devs) < n_dev:
        raise SystemExit(
            f"--autoplan over {n_dev} devices: only {len(devs)} visible "
            "(set XLA_FLAGS=--xla_force_host_platform_device_count=N "
            "before python starts)")

    main, startup = static.Program(), static.Program()
    main.random_seed = 7
    startup.random_seed = 7
    with static.program_guard(main, startup):
        x = L.data("x", [hidden])
        y = L.data("y", [1])
        h = L.fc(x, hidden, act="relu")
        pred = L.fc(h, 1)
        loss = L.mean(L.square(L.elementwise_sub(pred, y)))
        static.optimizer.SGD(learning_rate=0.01).minimize(loss)

    rng = np.random.default_rng(0)
    feed = {"x": rng.normal(0, 1, (batch, hidden)).astype(np.float32),
            "y": rng.normal(0, 1, (batch, 1)).astype(np.float32)}

    choice = autoplan.search(
        main, devices=devs,
        feed_shapes={k: v.shape for k, v in feed.items()},
        fetch_names=(loss.name,))
    best = choice.ranked[0]

    def run_plan(plan):
        scope = static.Scope()
        with static.scope_guard(scope):
            exe = static.Executor()
            exe.run(startup)
            compiled = static.CompiledProgram(main).with_sharding(plan=plan)
            for _ in range(3):
                out = exe.run(compiled, feed=feed, fetch_list=[loss],
                              return_numpy=False)
            np.asarray(out[0])
            host_ms = []
            for _ in range(steps):
                t0 = time.perf_counter()
                out = exe.run(compiled, feed=feed, fetch_list=[loss],
                              return_numpy=False)
                host_ms.append((time.perf_counter() - t0) * 1000.0)
            final = float(np.asarray(out[0]))
        return statistics.median(host_ms), final

    saved = flags.get_flags(["donate_state", "metrics"])
    try:
        flags.set_flags({"donate_state": True, "metrics": False})
        auto_ms, _ = run_plan(best.plan)
        dp_ms, _ = run_plan(ShardingPlan(devices=devs, donate=False))
    finally:
        flags.set_flags(saved)

    return {
        "search_ms": round(choice.search_ms, 2),
        "candidates_ok": len(choice.ranked),
        "candidates_pruned": len(choice.pruned),
        "best_score_ms": round(best.score, 6),
        "best_comm_kb": round(
            best.corrected.get("comm_bytes", 0.0) / 1024.0, 3),
        "step_ms_auto": round(auto_ms, 4),
        "step_ms_dp": round(dp_ms, 4),
    }


def _run_profile(steps: int, batch: int, hidden: int) -> dict:
    """xprof roofline block for the bench program: a separate short run
    with metrics ON (the timed modes force metrics off, so this pass owns
    the step_time_ms anchor), condensed via ``xprof.summarize`` — coverage,
    MFU, drift, top regions and the memory-bound ones by name."""
    import numpy as np

    import paddle_tpu.static as static
    from paddle_tpu.core import flags
    from paddle_tpu.static import layers as L
    from paddle_tpu.utils import xprof

    main, startup = static.Program(), static.Program()
    main.random_seed = 7
    startup.random_seed = 7
    scope = static.Scope()
    saved = flags.get_flags(["metrics"])
    try:
        flags.set_flags({"metrics": True})
        with static.program_guard(main, startup), static.scope_guard(scope):
            x = L.data("x", [hidden])
            y = L.data("y", [1])
            h = L.fc(x, hidden, act="relu")
            pred = L.fc(h, 1)
            loss = L.mean(L.square(L.elementwise_sub(pred, y)))
            static.optimizer.SGD(learning_rate=0.01).minimize(loss)

            exe = static.Executor()
            exe.run(startup)
            rng = np.random.default_rng(0)
            feed = {"x": rng.normal(0, 1, (batch, hidden)).astype(np.float32),
                    "y": rng.normal(0, 1, (batch, 1)).astype(np.float32)}
            for _ in range(max(2, min(steps, 8))):
                exe.run(main, feed=feed, fetch_list=[loss])
            report = exe.xprof_report(main)
        return xprof.summarize(report)
    finally:
        flags.set_flags(saved)


def run_bench(steps: int = 50, batch: int = 64, hidden: int = 256,
              mesh: int = 0, cache_dir=None, profile: bool = False,
              autoplan: int = 0) -> dict:
    import jax

    fast_ms, fast_losses = _run_mode(donate=True, async_dispatch=True,
                                     steps=steps, batch=batch, hidden=hidden)
    sync_ms, sync_losses = _run_mode(donate=False, async_dispatch=False,
                                     steps=steps, batch=batch, hidden=hidden)
    result = {
        "metric": "executor_step_host_overhead",
        "unit": "ms/step (median host time in Executor.run)",
        "host_ms_fast": round(fast_ms, 4),
        "host_ms_sync": round(sync_ms, 4),
        "speedup": round(sync_ms / fast_ms, 3) if fast_ms > 0 else None,
        "parity": fast_losses == sync_losses,
        "loss_final": fast_losses[-1] if fast_losses else None,
        "steps": steps, "batch": batch, "hidden": hidden,
        "platform": jax.devices()[0].platform,
    }
    if mesh and mesh > 1:
        sharded_ms, sharded_losses = _run_sharded(
            steps=steps, batch=batch, hidden=hidden, n_dev=mesh)
        result["host_ms_sharded"] = round(sharded_ms, 4)
        result["mesh_devices"] = mesh
        # different XLA executables (GSPMD vs single-device) differ in ulps;
        # assert closeness at the DP tolerance, not bitwise
        result["sharded_parity"] = all(
            abs(a - b) <= 2e-4 * max(1.0, abs(b))
            for a, b in zip(sharded_losses, fast_losses))
    if cache_dir is not None:
        result.update(_cache_bench(steps=min(steps, 8), batch=batch,
                                   hidden=hidden, cache_dir=cache_dir))
    if profile:
        result["roofline"] = _run_profile(steps=steps, batch=batch,
                                          hidden=hidden)
    if autoplan and autoplan > 1:
        # under "record" so benchdiff's nested-scalar extractor picks the
        # block up as autoplan.* metrics (see tools/benchdiff.py)
        result["record"] = {"autoplan": _run_autoplan(
            steps=steps, batch=batch, hidden=hidden, n_dev=autoplan)}
    return result


def selfcheck() -> int:
    """Smoke for tier-1: tiny run covering all three modes — donation
    parity, a 2-device sharded pass, and a cache cold/warm round-trip."""
    _ensure_cpu_devices(2)
    with tempfile.TemporaryDirectory(prefix="pdtpu_stepbench_cc_") as cc:
        r = run_bench(steps=8, batch=8, hidden=32, mesh=2, cache_dir=cc,
                      profile=True, autoplan=2)
    ok = True
    ap = (r.get("record") or {}).get("autoplan") or {}
    if not (ap.get("candidates_ok", 0) > 0 and ap.get("step_ms_auto", 0) > 0
            and ap.get("search_ms", 0) > 0):
        print(f"selfcheck: bad autoplan block {ap!r}", file=sys.stderr)
        ok = False
    roof = r.get("roofline") or {}
    if not (roof.get("attribution_coverage", 0) >= 0.9):
        print(f"selfcheck: roofline attribution coverage "
              f"{roof.get('attribution_coverage')} < 0.9", file=sys.stderr)
        ok = False
    if not roof.get("top_regions"):
        print("selfcheck: roofline block has no top_regions",
              file=sys.stderr)
        ok = False
    for k in ("host_ms_fast", "host_ms_sync", "speedup", "parity",
              "host_ms_sharded", "sharded_parity", "cold_start_ms",
              "warm_start_ms", "cache_parity"):
        if r.get(k) is None:
            print(f"selfcheck: missing/None field {k!r}", file=sys.stderr)
            ok = False
    if not r.get("parity"):
        print("selfcheck: donated and undonated losses diverged",
              file=sys.stderr)
        ok = False
    if not r.get("sharded_parity"):
        print("selfcheck: sharded losses diverged from single-device "
              "fast path beyond tolerance", file=sys.stderr)
        ok = False
    if not r.get("cache_parity"):
        print("selfcheck: warm-cache losses diverged from cold run",
              file=sys.stderr)
        ok = False
    if not r.get("cache_hits"):
        print("selfcheck: warm run produced no compile-cache hits",
              file=sys.stderr)
        ok = False
    if r.get("warm_traces"):
        print(f"selfcheck: warm run re-traced python "
              f"({r['warm_traces']} traces)", file=sys.stderr)
        ok = False
    if ok and not (r["host_ms_fast"] > 0 and r["host_ms_sync"] > 0
                   and r["host_ms_sharded"] > 0):
        print("selfcheck: non-positive timings", file=sys.stderr)
        ok = False
    print(f"stepbench selfcheck: {'OK' if ok else 'FAILED'} "
          f"(fast={r['host_ms_fast']}ms sync={r['host_ms_sync']}ms "
          f"sharded={r['host_ms_sharded']}ms speedup={r['speedup']}x "
          f"parity={r['parity']} cold={r['cold_start_ms']}ms "
          f"warm={r['warm_start_ms']}ms hits={r['cache_hits']})")
    return 0 if ok else 1


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m tools.stepbench",
        description="Steady-state Executor step host-overhead benchmark "
                    "(donation + async dispatch on vs off).")
    parser.add_argument("--steps", type=int, default=50)
    parser.add_argument("--batch", type=int, default=64)
    parser.add_argument("--hidden", type=int, default=256)
    parser.add_argument("--mesh", type=int, default=0, metavar="N",
                        help="also run the sharded fast path on an N-device "
                             "dp mesh (reports host_ms_sharded)")
    parser.add_argument("--cache", nargs="?", const="", default=None,
                        metavar="DIR",
                        help="also measure the persistent executable cache: "
                             "cold vs warm start against DIR (default: a "
                             "temp directory)")
    parser.add_argument("--profile", action="store_true",
                        help="also attach an xprof roofline block (coverage, "
                             "MFU, top regions; see tools/xprof.py)")
    parser.add_argument("--autoplan", type=int, default=0, metavar="N",
                        help="also run the cost-model plan search over an "
                             "N-device mesh and measure the chosen plan "
                             "(benchdiff-consumable record.autoplan block)")
    parser.add_argument("--selfcheck", action="store_true",
                        help="tiny smoke run with field/parity checks")
    args = parser.parse_args(argv)
    n_dev = 2 if args.selfcheck else max(args.mesh, args.autoplan)
    if n_dev > 1:
        _ensure_cpu_devices(n_dev)  # before anything imports jax
    from paddle_tpu.core.jax_cache import configure_compile_cache

    configure_compile_cache()
    if args.selfcheck:
        return selfcheck()
    if args.cache == "":
        with tempfile.TemporaryDirectory(prefix="pdtpu_stepbench_cc_") as cc:
            r = run_bench(steps=args.steps, batch=args.batch,
                          hidden=args.hidden, mesh=args.mesh, cache_dir=cc,
                          profile=args.profile, autoplan=args.autoplan)
    else:
        r = run_bench(steps=args.steps, batch=args.batch, hidden=args.hidden,
                      mesh=args.mesh, cache_dir=args.cache,
                      profile=args.profile, autoplan=args.autoplan)
    print(json.dumps(r))
    return 0


if __name__ == "__main__":
    sys.exit(main())
