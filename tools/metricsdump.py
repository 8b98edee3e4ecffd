"""Run a small static-graph workload and dump the runtime metric registry.

The observability analogue of proglint: a one-command answer to "is the
telemetry layer wired up, and what does it report?"  Builds a tiny fc
regression program, runs the Executor a few steps (one compile + N cached
runs), then prints the process-wide `MetricRegistry` as Prometheus text or
JSON — so `executor.cache_miss/.cache_hit`, the compile/run histograms,
`registry.lowering_calls{op=...}` and friends are all populated.

Usage::

    python -m tools.metricsdump                    # prometheus text
    python -m tools.metricsdump --format json
    python -m tools.metricsdump --steps 10 --out metrics.prom
    python -m tools.metricsdump --chrome trace.json   # spans + counter track
    python -m tools.metricsdump --lint             # metric-name lint only

`--lint` checks every registered metric name against ``^[a-z0-9_.]+$``
(the registry enforces this at registration; the lint is the CI backstop
that keeps exporter output Prometheus-legal), then against the KNOWN-NAMES
inventory below — dashboards and alerts key on these exact strings, so a
new instrumented module must add its names here (the lint failing is the
review prompt) and a typo'd registration fails instead of silently
splitting a time series.  Exits non-zero on any violation.
"""
from __future__ import annotations

import argparse
import json
import re
import sys

_NAME_RE = re.compile(r"^[a-z0-9_.]+$")

# The metric-name inventory: every name any instrumented module registers.
# Grouped by family; keep sorted within each group.
_KNOWN_NAMES = frozenset({
    # static/analysis.py + static/shardcheck.py + static/memcheck.py
    # (the three-tier verifier)
    "analysis.mem_checks",
    "analysis.mem_violations",
    "analysis.plans_checked",
    "analysis.programs_checked",
    "analysis.violations",
    # parallel/autoplan.py (plan-search telemetry)
    "autoplan.candidates",
    "autoplan.replans",
    "autoplan.search_ms",
    "autoplan.searches",
    "debug.nan_events",
    # parallel/collective.py + parallel/compress.py
    "comm.allreduce_bytes",
    "comm.allreduce_ms",
    "comm.compress_ratio",
    # parallel/embedding.py (vocab-sharded embedding exchange + serving)
    "emb.exchange_bytes",
    "emb.lookup_ms",
    "emb.unique_ratio",
    # elastic/ (checkpoint.py, membership.py, failover.py)
    "elastic.checkpoint_ms",
    "elastic.failovers",
    "elastic.resharded_leaves",
    "elastic.restore_ms",
    "elastic.worker_deaths",
    # static/executor.py + static/compile_cache.py
    "executor.cache_hit",
    "executor.cache_miss",
    "executor.cold_start_ms",
    "executor.compile_cache_hit",
    "executor.compile_cache_miss",
    "executor.compile_time_ms",
    "executor.cost_bytes_accessed",
    "executor.cost_flops",
    "executor.device_mem_args_bytes",
    "executor.device_mem_code_bytes",
    "executor.device_mem_live_arrays",
    "executor.device_mem_live_bytes",
    "executor.device_mem_out_bytes",
    "executor.device_mem_temp_bytes",
    "executor.device_mem_total_bytes",
    "executor.dispatch_time_ms",
    "executor.donated_bytes",
    "executor.predicted_peak_bytes",
    "executor.program_ops",
    "executor.state_size_bytes",
    "executor.step_time_ms",
    "executor.traces",
    # tools/fleetview.py (the job-level aggregator's own instruments)
    "fleet.ranks",
    "fleet.scrape_errors",
    "fleet.scrapes",
    # ops/delta_rule.py (the gated delta rule's traces; labels pass, chunk)
    "gdn.delta_calls",
    # io/prefetch.py
    "io.prefetch_batches",
    "io.prefetch_depth",
    # utils/ledger.py (measured-vs-predicted calibration)
    "ledger.drift_alarms",
    "ledger.drift_ratio",
    "ledger.records",
    # ops/pallas/config.py (kernel dispatch telemetry); kernel= label
    # values: flash_attention, flash_attention_packed, fused_layer_norm,
    # fused_rdln, conv2d_bn_act, bn_act_train, max_pool2d, avg_pool2d,
    # int8_matmul, int8_conv2d, paged_attention, grouped_matmul
    "pallas.fallbacks",
    "pallas.flash.tiles",
    "pallas.kernel_calls",
    # ops/ssd.py (the state-space scan's dispatch; labels impl, chunk)
    "ssm.scan_calls",
    # text/pretrainer.py routing_stats (nn.DroplessMoE; label layer)
    "moe.buffer_rows",
    "moe.held_load_max_over_mean",
    "moe.pairs_dropped",
    "moe.pairs_held",
    "moe.pairs_routed",
    # static/passes.py (graph-rewrite pipeline)
    "passes.ops_fused",
    "passes.ops_removed",
    "passes.pipeline_ms",
    "passes.rollbacks",
    "passes.runs",
    # static/passes.py quant_infer (int8 inference rewrite)
    "quant.ops_rewritten",
    # distributed/ps_server.py
    "ps.heartbeat_age_seconds",
    "ps.rpc_count",
    "ps.rpc_errors",
    "ps.rpc_latency_ms",
    "registry.lowering_calls",
    # serving/ (slo.py, tenancy.py, continuous.py, paged.py)
    "serve.batch_occupancy",
    "serve.batch_size",
    "serve.decode_active_slots",
    "serve.kv_blocks_free",
    "serve.kv_cache_bytes",
    "serve.kv_prefill_chunks",
    "serve.kv_prefix_hits",
    "serve.live_programs",
    "serve.live_temp_bytes",
    "serve.load_shed",
    "serve.peak_temp_bytes",
    "serve.program_evictions",
    "serve.projected_p99_ms",
    "serve.queue_depth",
    "serve.request_ms",
    "serve.requests",
    "serve.ttft_batch_ms",
    "serve.ttft_compile_ms",
    "serve.ttft_execute_ms",
    "serve.ttft_ms",
    "serve.ttft_p50_ms",
    "serve.ttft_p99_ms",
    "serve.ttft_queue_ms",
    # utils/slo.py (the SLO engine's own instruments)
    "slo.alerts_firing",
    "slo.burn_rate",
    "slo.evaluations",
    # utils/telemetry.py (the HTTP exposition plane)
    "telemetry.port",
    "telemetry.requests",
    "telemetry.scrape_ms",
    # hapi/callbacks.py MetricsLogger + utils/watchdog.py goodput
    "train.epochs",
    "train.goodput_pct",
    "train.samples_per_sec",
    "train.step_time_ms",
    "train.steps",
    # utils/watchdog.py (anomaly detection)
    "watchdog.anomalies",
    "watchdog.checkpoints",
    "watchdog.time_ms",
    # utils/xprof.py
    "xprof.attribution_coverage",
    "xprof.mfu",
    "xprof.reports",
})


def run_workload(steps: int = 3) -> None:
    """One compile + (steps - 1) cached Executor runs of a tiny fc model."""
    import numpy as np

    import paddle_tpu.static as static
    from paddle_tpu.static import layers as L
    from paddle_tpu.utils import profiler

    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        x = L.data("x", [8])
        y = L.data("y", [1])
        hidden = L.fc(x, 16, act="relu")
        pred = L.fc(hidden, 1)
        loss = L.mean(L.square_error_cost(pred, y))
        static.optimizer.SGD(learning_rate=0.01).minimize(loss)

    rng = np.random.default_rng(0)
    xv = rng.normal(size=(16, 8)).astype(np.float32)
    yv = rng.normal(size=(16, 1)).astype(np.float32)

    exe = static.Executor()
    scope = static.Scope()
    with static.scope_guard(scope):
        exe.run(startup)
        for _ in range(max(1, steps)):
            with profiler.RecordEvent("metricsdump::step"):
                exe.run(main, feed={"x": xv, "y": yv}, fetch_list=[loss])


def _register_instrumented_modules() -> None:
    """Import every instrumented layer so its metrics are registered even
    when the workload doesn't exercise it (PS server, hapi loop)."""
    import paddle_tpu.distributed.ps_server  # noqa: F401
    import paddle_tpu.elastic  # noqa: F401 — the elastic.* family
    import paddle_tpu.parallel.autoplan  # noqa: F401 — the autoplan.* family
    import paddle_tpu.parallel.embedding  # noqa: F401 — the emb.* family
    import paddle_tpu.serving  # noqa: F401 — the serve.* family
    import paddle_tpu.static.analysis  # noqa: F401 — analysis.* counters
    import paddle_tpu.static.shardcheck  # noqa: F401 — analysis.plans_checked
    import paddle_tpu.static.compile_cache  # noqa: F401
    import paddle_tpu.static.executor  # noqa: F401 — executor.* + registry.*
    import paddle_tpu.ops.pallas.config  # noqa: F401 — the pallas.* family
    import paddle_tpu.static.passes  # noqa: F401 — passes.* + quant.*
    import paddle_tpu.utils.debug  # noqa: F401
    import paddle_tpu.utils.ledger  # noqa: F401 — the ledger.* family
    import paddle_tpu.utils.slo  # noqa: F401 — the slo.* family
    import paddle_tpu.utils.telemetry  # noqa: F401 — the telemetry.* family
    import paddle_tpu.utils.watchdog  # noqa: F401 — watchdog.* + goodput
    import paddle_tpu.utils.xprof  # noqa: F401 — the xprof.* family
    import tools.fleetview  # noqa: F401 — the fleet.* family
    from paddle_tpu.hapi.callbacks import MetricsLogger

    MetricsLogger()  # registers the train.* family


def lint_names(registry) -> list:
    """(name, problem) pairs: names the exporters would reject or that are
    missing from the _KNOWN_NAMES inventory."""
    bad = []
    for n in registry.names():
        if not _NAME_RE.match(n):
            bad.append((n, f"must match {_NAME_RE.pattern}"))
        elif n not in _KNOWN_NAMES and not n.startswith("t."):
            # "t." is the reserved scratch namespace (tests, ad-hoc probes)
            bad.append((n, "not in the metricsdump known-names inventory; "
                           "add it to _KNOWN_NAMES"))
    return bad


def lint_objectives(path: str) -> list:
    """(name, problem) pairs for an SLO objective file: parse failures and
    objectives whose metric is missing from the known-names inventory —
    an alert rule keying on a metric nothing registers would silently
    never fire."""
    from paddle_tpu.utils import slo as _slo

    try:
        objectives = _slo.load_objectives(path)
    except (OSError, ValueError) as e:
        return [(path, f"objective file failed to load: {e}")]
    bad = []
    for s in objectives:
        if s.metric not in _KNOWN_NAMES and not s.metric.startswith("t."):
            bad.append((s.metric,
                        f"SLO {s.name!r} references a metric not in the "
                        "metricsdump known-names inventory"))
    return bad


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m tools.metricsdump", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument("--format", choices=("prom", "json"), default="prom",
                        help="export format (default: prometheus text)")
    parser.add_argument("--steps", type=int, default=3,
                        help="Executor.run steps (first one compiles)")
    parser.add_argument("--out", default=None,
                        help="write to this file instead of stdout")
    parser.add_argument("--chrome", default=None,
                        help="also export a chrome trace (spans + counter "
                        "track) to this path")
    parser.add_argument("--lint", action="store_true",
                        help="lint registered metric names instead of "
                        "running the workload dump")
    parser.add_argument("--objectives", default=None, metavar="FILE",
                        help="with --lint: also validate this SLO objective "
                        "file (utils/slo.py format) — fails on objectives "
                        "referencing metrics missing from the inventory")
    args = parser.parse_args(argv)

    from paddle_tpu.utils import monitor, profiler

    registry = monitor.default_registry()
    _register_instrumented_modules()

    if args.lint:
        bad = lint_names(registry)
        if args.objectives:
            bad.extend(lint_objectives(args.objectives))
        if bad:
            for name, problem in bad:
                print(f"metricsdump: bad metric name {name!r}: {problem}",
                      file=sys.stderr)
            return 1
        print(f"metricsdump: {len(registry.names())} metric names OK"
              + (f" (+ objectives {args.objectives} OK)"
                 if args.objectives else ""))
        return 0

    profiler.start_profiler()
    run_workload(args.steps)
    if args.chrome:
        profiler.export_chrome_tracing(args.chrome)
    # event summary goes to stderr so stdout stays pure prom/json payload
    profiler.stop_profiler(sorted_key="total", stream=sys.stderr)

    if args.format == "json":
        text = json.dumps(registry.to_json(), indent=2, sort_keys=True)
    else:
        text = registry.to_prometheus_text()
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
    else:
        print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
