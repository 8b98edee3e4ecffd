"""One run of one cell: set-up, the measured window, the traced steps, the
reference and the comparison.  Holds no configuration's, cell's or metric's
name: all of that comes from `BENCHMARK.json` and the files it names.
"""
from __future__ import annotations

import collections
import contextlib
import gc
import json
import os
import pathlib
import shutil
import sys
import time

import jax
import numpy as np

from . import compare, peaks, trafficgen, weights, xplane
from .manifest import Manifest


class BenchError(RuntimeError):
    """The run cannot be made (no chip, bad manifest): no result is printed."""


# ---------------------------------------------------------------------------
# spans, compile events
# ---------------------------------------------------------------------------
class Spans:
    """The benchmark's own host spans: kept in memory by name, and written
    into the profiler's trace under `bench.<name>` while it is on."""

    def __init__(self):
        self.durations = collections.defaultdict(list)

    @contextlib.contextmanager
    def __call__(self, name: str):
        with jax.profiler.TraceAnnotation(xplane.HOST_SPAN_PREFIX + name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.durations[name].append(time.perf_counter() - t0)

    def clear(self):
        self.durations.clear()


class CompileLog:
    """Every XLA compile request of this process, from jax.monitoring
    (copied from chip_smoke.py): (function name, served from the cache?)."""

    def __init__(self):
        self.events, self._hit = [], False
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self._hit = True

    def _on_duration(self, event, _secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((kw.get("fun_name", "?"), self._hit))
            self._hit = False

    def __len__(self):
        return len(self.events)


def configure_compile_cache(root: pathlib.Path) -> str:
    """JAX's persistent compilation cache: `JAX_COMPILATION_CACHE_DIR` where
    it is set, else the fixed path `<checkout>/.jax_cache`.  Every program
    is kept, however quick its compile, so that a cell's second run finds
    all of them."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(root / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


# ---------------------------------------------------------------------------
# the loop that set-up and the window share
# ---------------------------------------------------------------------------
class Loop:
    """Feeds a fresh host batch through `jax.device_put` and dispatches the
    step; keeps `in_flight` steps queued on the device and takes a step's
    completion time when its loss is ready."""

    def __init__(self, training, pool, in_flight: int, spans: Spans):
        self.t, self.pool, self.in_flight, self.spans = \
            training, pool, in_flight, spans
        self.index = 0
        self.step, self.temp_bytes = training.step, 0
        if hasattr(training.step, "lower"):
            # compile once, ahead: the program that runs is the one whose
            # temporary memory is read (the runtime's counters leave it out)
            batch = jax.device_put(pool[0], training.data_shardings)
            self.step = training.step.lower(
                training.params, training.opt_state, batch,
                training.key).compile()
            mem = self.step.memory_analysis()
            self.temp_bytes = int(getattr(mem, "temp_size_in_bytes", 0) or 0)

    def dispatch(self):
        with self.spans("device_put"):
            batch = jax.device_put(self.pool[self.index % len(self.pool)],
                                   self.t.data_shardings)
        with self.spans("dispatch"):
            self.t.params, self.t.opt_state, loss = self.step(
                self.t.params, self.t.opt_state, batch, self.t.key)
        self.index += 1
        return loss

    def one(self) -> float:
        """One step, waited for (set-up's checked steps)."""
        loss = self.dispatch()
        with self.spans("fetch"):
            return float(loss)

    def run(self, seconds=None, steps=None) -> dict:
        """Dispatch until `seconds` have passed (or `steps` are out), then
        wait for what is in flight."""
        queue, done_at, losses = collections.deque(), [], []

        def retire():
            loss = queue.popleft()
            with self.spans("fetch"):
                loss.block_until_ready()
            done_at.append(time.perf_counter())
            losses.append(loss)

        t0 = time.perf_counter()
        while True:
            queue.append(self.dispatch())
            if len(queue) > self.in_flight:
                retire()
            sent = len(done_at) + len(queue)
            if (steps is not None and sent >= steps) or (
                    seconds is not None
                    and time.perf_counter() - t0 >= seconds):
                break
        while queue:
            retire()
        return {"t0": t0, "done_at": done_at,
                "losses": [float(x) for x in losses]}


# ---------------------------------------------------------------------------
# a run
# ---------------------------------------------------------------------------
def _devices(chips: int, require_tpu: bool):
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX found platform "
                         f"{devices[0].platform!r} ({devices[0].device_kind})")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chip(s), JAX found "
                         f"{len(devices)}")
    return devices[:chips]


def _memory(devices, key: str) -> int:
    """A counter of `device.memory_stats()` on the fullest device."""
    return max(int((d.memory_stats() or {}).get(key, 0)) for d in devices)


def whole_sharding(devices):
    """Every leaf whole on each of `devices`: where the reference's
    weights are drawn."""
    return jax.sharding.NamedSharding(
        jax.sharding.Mesh(np.array(devices), ("rows",)),
        jax.sharding.PartitionSpec())


def _profiler_options():
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def program_readings(loop: Loop, training, make_params, key, check_steps):
    """Drive the window's own loop through its first steps and read what
    the comparison needs from the program's state."""
    out = {"losses": []}
    for n in range(check_steps):
        out["losses"].append(loop.one())
        if n == 0:
            grad = training.first_gradient(training.opt_state)
            out["grad_norms"] = np.asarray(compare.leaf_norms(grad))
            # to the host: the device has no room to keep it beside the step
            out["first_grad"] = jax.device_get(grad)
            del grad
    change = jax.jit(lambda p, k: compare.leaf_norms(
        jax.tree_util.tree_map(lambda a, b: a - b, p, make_params(k))))
    out["change_norms"] = np.asarray(change(training.params, key))
    return out


def reference_readings(reference, config, params, batches, devices,
                       yardstick: bool = False, **kw):
    """The reference's readings of the checked steps.  With `yardstick`,
    where the configuration names a `reference_yardstick` (the precision it
    states, in the reference's terms), the reference also takes the first
    step in that precision: how far that moves the first gradient is what
    the program's own distance is measured in (`compare.numbers`,
    `grad_diff_ratio`)."""
    model, opt = config["model"], config["train"]["optimizer"]
    ref = reference.run(model, opt, params, batches, devices=devices, **kw)
    out = {"losses": ref["losses"], "first_grad": ref["first_grad"],
           "grad_norms": np.asarray(compare.leaf_norms(ref["first_grad"])),
           "change_norms": np.asarray(compare.leaf_norms(ref["param_change"]))}
    del ref
    if yardstick and config.get("reference_yardstick"):
        rounded = reference.run(
            model, opt, params, batches[:1], devices=devices,
            **{**kw, "precision": config["reference_yardstick"]})
        out["grad_diff_yardstick"] = compare.diff_rel(rounded["first_grad"],
                                                      out["first_grad"])
    return out


def run(manifest_path, workload: str, seed: int, seconds: float, trace: bool,
        *, t_start: float = None, search=(), require_tpu: bool = True,
        scratch: str = None, compile_cache: bool = True, keep_trace: bool = False,
        err=sys.stderr) -> dict:
    t_start = time.perf_counter() if t_start is None else t_start
    marks = {}

    def mark(name):          # where set-up's seconds go (in the notes)
        marks[name] = time.perf_counter() - t_start

    mark("imports")
    man = Manifest(manifest_path, search)
    cell = man.cell(workload)
    config = man.config(cell["config"])
    mix = trafficgen.load(man.find("traffic", f"{cell['traffic']}.json"))
    if mix["chips"] != cell["chips"]:
        raise BenchError(f"{workload}: the manifest says {cell['chips']} "
                         f"chip(s), the traffic mix {mix['chips']}")
    limits = man.json_of("limits", workload)["numbers"]
    devices = _devices(cell["chips"], require_tpu)
    mark("devices")
    kind = devices[0].device_kind
    peak_table = peaks.lookup(kind) if devices[0].platform == "tpu" else None
    cache_dir = configure_compile_cache(man.root) if compile_cache else None
    log = CompileLog()

    entry = man.module("entries", config["entry"])
    reference = man.module("references", config["reference"])
    spec = reference.param_spec(config["model"])
    pool = trafficgen.make_pool(mix, config["model"], seed)
    training = entry.build(config, mix, devices)
    mark("entry_built")
    make_params = weights.maker(
        spec, training.param_shardings(weights.shapes(spec)))
    key = weights.seed_key(seed)
    training.params = make_params(key)
    training.opt_state = training.init_opt_state(training.params)
    mark("state_made")

    spans = Spans()
    loop = Loop(training, pool, mix["in_flight"], spans)
    mark("step_compiled")
    check_steps = mix["check_steps"]
    program = program_readings(loop, training, make_params, key, check_steps)
    mark("steps_checked")
    loop.run(steps=mix["warm_steps"])
    spans.clear()
    compiles_before = len(log)
    setup_s = time.perf_counter() - t_start

    # ---- the measured window -------------------------------------------
    window = loop.run(seconds=seconds)
    window_spans = {k: list(v) for k, v in spans.durations.items()}
    losses = list(window["losses"])
    steps, window_s = len(window["done_at"]), window["done_at"][-1] - window["t0"]
    items_per_step = trafficgen.global_batch(mix) * mix["seq"]
    ctx = {
        "model": config["model"], "mix": mix, "chips": cell["chips"],
        "peaks": peak_table, "setup_s": setup_s,
        "steps": steps, "window_s": window_s,
        "items_per_s_per_chip": steps * items_per_step / window_s / cell["chips"],
        "done_at": [t - window["t0"] for t in window["done_at"]],
        "spans": window_spans, "manifest": man,
        "trace": None, "host_spans": [],
    }

    # ---- the traced steps (a window of their own, after the timed one) --
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices)}
    breakdown = None
    if trace:
        log_dir = pathlib.Path(scratch or man.root / ".bench_trace") / \
            f"{workload}.{seed}"
        shutil.rmtree(log_dir, ignore_errors=True)
        jax.profiler.start_trace(str(log_dir),
                                 profiler_options=_profiler_options())
        try:
            traced = loop.run(steps=mix["trace_steps"])
        finally:
            jax.profiler.stop_trace()
        losses += traced["losses"]
        read = xplane.load(str(log_dir))
        ws = xplane.windows(read, training.step_module)
        if not keep_trace:
            shutil.rmtree(log_dir, ignore_errors=True)
        if require_tpu and not ws:
            raise BenchError("the trace holds no run of the step's program "
                             f"({training.step_module!r}) on a device")
        if ws:
            ctx["trace"], ctx["host_spans"] = ws, read.host_spans
            device["busy_s"], device["window_s"] = xplane.busy_and_window_s(ws)
            breakdown = {"device_ops": xplane.top_ops(ws),
                         "idle_gaps": xplane.idle_gaps(ws[0], read.host_spans)}
    compiles_in_window = len(log) - compiles_before
    # The runtime's `peak_bytes_in_use` counts the buffers it hands out
    # (weights, optimizer state, batches) and not a running program's
    # temporary memory (read on the chip, PR 25: 2.05 GB where the step needs
    # 13.9).  While a step runs, what is live is what is in use now, with
    # the state and the batches in flight, plus the step's temporaries as
    # XLA sized them for the compiled program that the window drove.
    device["memory_peak_bytes"] = ctx["memory_peak_bytes"] = max(
        _memory(devices, "peak_bytes_in_use"),
        _memory(devices, "bytes_in_use") + loop.temp_bytes)
    counters = training.counters()
    memory_stats = {k: v for k, v in (devices[0].memory_stats() or {}).items()
                    if isinstance(v, (int, float))}
    memory_stats["step_temp_bytes"] = loop.temp_bytes

    # ---- free the program's state, then the reference -------------------
    names = compare.leaf_names(training.params)
    training.params = training.opt_state = None
    del loop, training
    gc.collect()
    t_ref = time.perf_counter()
    ref = reference_readings(
        reference, config, weights.maker(spec, whole_sharding(devices))(key),
        pool[:check_steps], devices, yardstick=True,
        rows_per_block=config.get("reference_rows_per_block", 8))
    reference_s = time.perf_counter() - t_ref

    values, worst = compare.numbers(program, ref, names)
    values["nonfinite_losses"] = float(sum(not np.isfinite(x) for x in losses))
    values["compiles_in_window"] = float(compiles_in_window)
    judged, only_read = compare.judge(values, limits)

    metrics = {}
    for m in man.metrics_of(workload, "per_layer" if trace else "end_to_end"):
        kind_dir = "layer_metrics" if trace else "e2e_metrics"
        how = man.json_of(kind_dir, m["name"])
        value = man.module("readers", how["reader"]).read(
            ctx, how.get("params", {}))
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    result = {"correct": compare.correct(judged), "attempted": len(losses),
              "failed": int(values["nonfinite_losses"]), "metrics": metrics,
              "device": device}
    if breakdown:
        result["breakdown"] = breakdown
    gaps_ms = np.diff(np.asarray([0.0] + ctx["done_at"])) * 1e3
    slowest = np.argsort(gaps_ms)[::-1][:3]
    result["notes"] = {
        "step_ms": {"median": float(np.median(gaps_ms)),
                    "slowest": [[int(i), float(gaps_ms[i])] for i in slowest]},
        "span_ms_max": {k: [int(np.argmax(v)), float(np.max(v) * 1e3)]
                        for k, v in window_spans.items()},
        "setup_s": setup_s, "setup_marks_s": marks, "reference_s": reference_s,
        "steps_in_window": ctx["steps"], "window_s": ctx["window_s"],
        "compile_cache_dir": cache_dir,
        "compiles": len(log), "compile_cache_hits": sum(h for _, h in log.events),
        "counters": counters, "worst_leaf": worst, "not_compared": only_read,
        "memory_stats": memory_stats,
        "losses": {"program": program["losses"], "reference": ref["losses"]},
    }
    result["compared"] = judged
    for name, j in judged.items():
        print(f"compared {name} = {j['value']:.6g} (limit {j['limit']:.6g})"
              f"{'' if j['value'] <= j['limit'] else '  <-- over'}", file=err)
    return result


def main(argv, t_start: float, manifest_path) -> int:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(manifest_path, args.workload, args.seed, args.seconds,
                     bool(args.trace), t_start=t_start)
    except BenchError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
