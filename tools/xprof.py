"""Op-level cost attribution + roofline/MFU + device-memory report CLI.

The TPU-native answer to the reference's tools/timeline.py over CUPTI
device-tracer protos (platform/device_tracer.h): instead of joining kernel
timestamps to ops after the fact, the Executor plants per-op
``jax.named_scope`` markers at trace time, and ``paddle_tpu/utils/xprof.py``
joins XLA's own cost/memory model back to those source ops from the
optimized HLO of the artifact that actually runs.

Usage::

    python -m tools.xprof                        # toy fc model, table view
    python -m tools.xprof --model mlp --steps 8 --batch 64 --hidden 256
    python -m tools.xprof --format json --out report.json
    python -m tools.xprof --input report.json --top 5        # re-render a dump
    python -m tools.xprof --selfcheck            # CI assertion mode (tier-1)

The toy models (fc regression / deeper mlp) run a
few measured steps first, so the report's MFU and modeled-vs-measured drift
are anchored by the real ``executor.step_time_ms`` median — on CPU CI the
absolute MFU is meaningless (fallback peaks), but attribution coverage,
compute/memory classification, and the ranked region list are exactly what
a TPU run produces.

``--selfcheck`` asserts the acceptance contract: attribution coverage
>= 90% of modeled flops on the toy model, every region carries a roofline
class + MFU, the memory breakdown sums match ``memory_analysis()``, a
synthetic compute-bound/memory-bound pair classifies correctly, and both
render formats produce output.  Exits non-zero on any violation.
"""
from __future__ import annotations

import argparse
import json
import sys


def _ensure_cpu_devices() -> None:
    """Default JAX to CPU when no flag is set: the tool must run on a
    build box without TPUs attached."""
    import os

    os.environ.setdefault("JAX_PLATFORMS", "cpu")


def build_toy(model: str, batch: int, hidden: int):
    """A toy training program: (main, startup, loss, feeds)."""
    import numpy as np

    import paddle_tpu.static as static
    from paddle_tpu.static import layers as L

    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        x = L.data("x", [hidden // 2])
        y = L.data("y", [1])
        h = L.fc(x, hidden, act="relu")
        if model == "mlp":
            h = L.fc(h, hidden, act="relu")
            h = L.fc(h, hidden // 2, act="relu")
        pred = L.fc(h, 1)
        loss = L.mean(L.square_error_cost(pred, y))
        static.optimizer.SGD(learning_rate=0.01).minimize(loss)

    rng = np.random.default_rng(0)
    feeds = {
        "x": rng.normal(size=(batch, hidden // 2)).astype(np.float32),
        "y": rng.normal(size=(batch, 1)).astype(np.float32),
    }
    return main, startup, loss, feeds


def run_and_profile(model: str = "fc", steps: int = 4, batch: int = 32,
                    hidden: int = 128, top=None):
    """Build the toy model, run ``steps`` measured Executor steps (metrics
    on, so step_time_ms anchors the report), and return the xprof report."""
    import paddle_tpu.static as static
    from paddle_tpu.core import flags as _flags

    _flags.set_flags({"metrics": True})
    main, startup, loss, feeds = build_toy(model, batch, hidden)
    exe = static.Executor()
    scope = static.Scope()
    with static.scope_guard(scope):
        exe.run(startup)
        for _ in range(max(2, steps)):
            exe.run(main, feed=feeds, fetch_list=[loss])
    return exe.xprof_report(main, top=top), exe


def render(report: dict, fmt: str, top: int) -> str:
    from paddle_tpu.utils import xprof

    if fmt == "json":
        return json.dumps(report, indent=2, sort_keys=True)
    return xprof.render_table(report, top=top)


def selfcheck() -> int:
    """Assert the xprof acceptance contract end to end; 0 on success."""
    from paddle_tpu.utils import xprof

    failures = []

    def check(cond: bool, what: str) -> None:
        (failures.append(what) if not cond else None)

    # 1) attribution on the toy model: >= 90% of modeled flops land on
    #    named source ops, every region is classified, MFU present
    report, exe = run_and_profile(model="fc", steps=4)
    t = report["totals"]
    check(t["attribution_coverage"] >= 0.9,
          f"attribution coverage {t['attribution_coverage']} < 0.9")
    check(t["flops_modeled"] > 0, "no modeled flops")
    check(t["measured_ms"] is not None and t["measured_ms"] > 0,
          "no measured step time anchored the report")
    check(t["mfu_measured"] is not None and t["mfu_measured"] >= 0,
          "no measured MFU")
    for row in report["regions"]:
        check(row["bound"] in ("compute", "memory"),
              f"region {row['region']} unclassified")
        check(row["mfu"] >= 0, f"region {row['region']} has no MFU")
    named = [r for r in report["regions"]
             if xprof.OP_SCOPE_RE.match(r["region"])]
    check(len(named) >= 3, f"only {len(named)} op-scope regions survived")

    # 2) the memory breakdown is internally consistent and matches the
    #    executable's memory_analysis() via Executor.memory_stats()
    mem = report.get("memory")
    check(bool(mem), "report has no memory block")
    if mem:
        check(mem["total_bytes"] == mem["args_bytes"] + mem["out_bytes"]
              + mem["temp_bytes"] + mem["code_bytes"],
              "memory breakdown does not sum to total")
        agg = exe.memory_stats()
        check(agg["programs"] >= 1, "Executor.memory_stats saw no entries")
        check(agg["total_bytes"] >= mem["total_bytes"],
              "Executor.memory_stats lost the profiled entry's bytes")

    # 3) telemetry rode along: coverage/MFU gauges + report counter (checked
    #    before the synthetic profiles below overwrite the last-report
    #    gauges with their scope-less coverage)
    from paddle_tpu.utils import monitor

    reg = monitor.default_registry()
    check(reg.get("xprof.reports").value() >= 1, "xprof.reports never inc'd")
    check(reg.get("xprof.attribution_coverage").value() >= 0.9,
          "xprof.attribution_coverage gauge not set")

    # 4) roofline classification: a big matmul is compute-bound, an
    #    elementwise add is memory-bound (ridge holds on every peak table
    #    entry, CPU fallback included)
    import jax.numpy as jnp
    import numpy as np

    a = np.zeros((512, 512), np.float32)
    cb = xprof.profile_jit(lambda p, q: p @ q, a, a)
    check(cb["regions"][0]["bound"] == "compute",
          f"512x512 matmul classified {cb['regions'][0]['bound']}")
    mb = xprof.profile_jit(lambda p, q: jnp.add(p, q), a, a)
    check(mb["regions"][0]["bound"] == "memory",
          f"elementwise add classified {mb['regions'][0]['bound']}")

    # 5) every render format produces non-empty output
    for fmt in ("table", "json"):
        check(bool(render(report, fmt, top=5).strip()),
              f"{fmt} render came back empty")

    if failures:
        for f in failures:
            print(f"xprof selfcheck FAIL: {f}", file=sys.stderr)
        return 1
    cov = report["totals"]["attribution_coverage"]
    print(f"xprof selfcheck: OK (coverage {cov:.1%}, "
          f"{len(report['regions'])} regions, "
          f"drift x{report['totals']['measured_vs_modeled']})")
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m tools.xprof", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument("--model", choices=("fc", "mlp"), default="fc",
                        help="toy program to profile (default: fc)")
    parser.add_argument("--steps", type=int, default=4,
                        help="measured Executor steps anchoring MFU")
    parser.add_argument("--batch", type=int, default=32)
    parser.add_argument("--hidden", type=int, default=128)
    parser.add_argument("--format", choices=("table", "json"),
                        default="table")
    parser.add_argument("--top", type=int, default=20,
                        help="regions shown in the table view")
    parser.add_argument("--out", default=None,
                        help="write to this file instead of stdout")
    parser.add_argument("--input", default=None,
                        help="re-render a saved JSON report instead of "
                        "running a model")
    parser.add_argument("--selfcheck", action="store_true",
                        help="assert the acceptance contract (CI mode)")
    args = parser.parse_args(argv)

    _ensure_cpu_devices()
    if args.selfcheck:
        return selfcheck()

    if args.input:
        with open(args.input) as f:
            report = json.load(f)
        if report.get("schema") != "xprof.report.v1":
            print(f"xprof: {args.input} is not an xprof report "
                  f"(schema {report.get('schema')!r})", file=sys.stderr)
            return 1
    else:
        report, _ = run_and_profile(args.model, args.steps, args.batch,
                                    args.hidden)

    text = render(report, args.format, args.top)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
        print(f"xprof: wrote {args.format} report to {args.out}")
    else:
        print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
