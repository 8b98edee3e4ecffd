"""Op-level cost attribution, roofline/MFU analyzer and device-memory
profiler (utils/xprof.py + static/executor.py integration): named-scope
round-trips through optimized HLO, roofline classification, memory
breakdowns, and the must-not-regress invariants — profiling changes
neither compile-cache keys nor steady-state retrace counts."""
import json
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.nn as nn
import paddle_tpu.static as static
from paddle_tpu.core import flags
from paddle_tpu.static import layers as L
from paddle_tpu.static.compile_cache import build_cache_key, \
    program_fingerprint
from paddle_tpu.utils import monitor, trace, xprof


@pytest.fixture
def _flags_guard():
    saved = flags.get_flags(["donate_state", "metrics", "xprof_scopes",
                             "compile_cache_dir"])
    yield
    flags.set_flags(saved)


def _sgd_net():
    x = L.data("x", [8])
    y = L.data("y", [1])
    pred = L.fc(L.fc(x, 16, act="relu"), 1)
    loss = L.mean(L.square(L.elementwise_sub(pred, y)))
    static.optimizer.SGD(learning_rate=0.05).minimize(loss)
    return loss


def _feed(batch=16, seed=0):
    rng = np.random.default_rng(seed)
    return {"x": rng.normal(size=(batch, 8)).astype(np.float32),
            "y": rng.normal(size=(batch, 1)).astype(np.float32)}


# CPU-independent peaks with ridge at AI = 5 flop/byte, so the synthetic
# pairs below classify deterministically on any host
_PEAKS = xprof.resolve_peaks(device_kind="test-device",
                             peak_flops=200e9, peak_bytes_per_sec=40e9)


# ---------------------------------------------------------------------------
# attribution: named scopes survive into optimized HLO and get the flops
# ---------------------------------------------------------------------------
def test_named_scope_attribution_roundtrip():
    def f(a, b):
        with jax.named_scope(xprof.op_scope_name("matmul", 0, 0)):
            c = a @ b
        with jax.named_scope(xprof.op_scope_name("relu", 0, 1)):
            return jnp.maximum(c, 0.0)

    a = jnp.ones((32, 64), jnp.float32)
    b = jnp.ones((64, 16), jnp.float32)
    report = xprof.profile_jit(f, a, b, peaks=_PEAKS)
    regions = {r["region"]: r for r in report["regions"]}
    assert "matmul.b0.i0" in regions, sorted(regions)
    mm = regions["matmul.b0.i0"]
    assert mm["attributed"] and mm["op_type"] == "matmul"
    # the dot itself: 2 * M * N * K
    assert mm["flops"] >= 2 * 32 * 16 * 64
    assert report["totals"]["attribution_coverage"] >= 0.9
    # every region got a roofline class + modeled time + MFU
    for r in report["regions"]:
        assert r["bound"] in ("compute", "memory")
        assert r["modeled_ms"] >= 0 and 0.0 <= r["mfu"] <= 1.0


def test_backward_flops_fold_into_forward_scopes():
    # jvp(scope)/transpose(jvp(scope)) path components unwrap to the
    # forward source op, so a grad step's flops land on the op that
    # caused them, not in <unattributed>
    w = jnp.ones((64, 64), jnp.float32)
    x = jnp.ones((8, 64), jnp.float32)

    def loss(w_):
        with jax.named_scope(xprof.op_scope_name("mul", 0, 0)):
            h = x @ w_
        return jnp.sum(h * h)

    fwd = xprof.profile_jit(lambda w_: loss(w_), w, peaks=_PEAKS)
    grad = xprof.profile_jit(jax.grad(loss), w, peaks=_PEAKS)
    get = lambda rep: next(r["flops"] for r in rep["regions"]
                           if r["region"] == "mul.b0.i0")
    assert get(grad) > get(fwd)  # fwd + dW + dX on the same region
    assert grad["totals"]["attribution_coverage"] >= 0.5


def test_dygraph_layer_scopes_name_regions():
    # Layer.__call__ wraps forward in named_scope(attribute name), so a
    # jitted dygraph model attributes per-layer without manual scopes
    class Net(nn.Layer):
        def __init__(self):
            super().__init__()
            self.proj = nn.Linear(8, 32)
            self.head = nn.Linear(32, 4)

        def forward(self, x):
            return self.head(jnp.tanh(self.proj(x)))

    model = Net()
    report = xprof.profile_jit(lambda x: model(x),
                               jnp.ones((16, 8), jnp.float32), peaks=_PEAKS)
    names = [r["region"] for r in report["regions"] if r["attributed"]]
    assert any("proj" in n for n in names), names
    assert any("head" in n for n in names), names


# ---------------------------------------------------------------------------
# roofline classification + peaks
# ---------------------------------------------------------------------------
def test_roofline_classifies_compute_vs_memory_bound():
    n = 512
    m = jnp.ones((n, n), jnp.float32)
    # big matmul: AI ~ n/6 flop/byte >> ridge 5 -> compute-bound
    mat = xprof.profile_jit(lambda a, b: a @ b, m, m, peaks=_PEAKS)
    # elementwise add: AI ~ 1/12 flop/byte << ridge -> memory-bound
    add = xprof.profile_jit(lambda a, b: a + b, m, m, peaks=_PEAKS)
    top = lambda rep: max(rep["regions"], key=lambda r: r["flops"])
    assert top(mat)["bound"] == "compute", top(mat)
    assert top(add)["bound"] == "memory", top(add)
    assert mat["totals"]["mfu_modeled"] > add["totals"]["mfu_modeled"]
    # measured anchor: slower-than-modeled wall time caps measured MFU
    modeled = mat["totals"]["modeled_ms"]
    anchored = xprof.profile_jit(lambda a, b: a @ b, m, m, peaks=_PEAKS,
                                 measured_ms=modeled * 10)
    t = anchored["totals"]
    assert t["mfu_measured"] == pytest.approx(t["mfu_modeled"] / 10, rel=0.01)
    assert t["measured_vs_modeled"] == pytest.approx(10.0, rel=0.01)


def test_peak_table_and_overrides():
    v5e = xprof.resolve_peaks(device_kind="TPU v5e")
    assert v5e.kind == "TPU v5e" and v5e.flops_per_sec == 197e12
    over = xprof.resolve_peaks(device_kind="x", peak_flops=1e12,
                               peak_bytes_per_sec=1e11)
    assert over.source == "override" and over.ridge == 10.0
    cpu = xprof.resolve_peaks(device_kind="epyc rome 9000", platform="cpu")
    assert cpu.kind == "epyc rome 9000"  # unknown CPU -> order-of-magnitude
    assert cpu.flops_per_sec > 0 and cpu.bytes_per_sec > 0
    assert xprof.resolve_peaks().source == "fallback"  # the local CPU
    with pytest.raises(ValueError, match="no peak spec"):
        xprof.resolve_peaks(device_kind="TPU v9 mega", platform="tpu")
    assert xprof.resolve_peaks(device_kind="TPU v5 lite",
                               platform="tpu").source == "table"


# ---------------------------------------------------------------------------
# memory: breakdown sums, executor gauges, live census
# ---------------------------------------------------------------------------
def test_memory_breakdown_sums_and_executor_gauges(_flags_guard):
    flags.set_flags({"metrics": True, "donate_state": True})
    reg = monitor.default_registry()
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup), \
            static.scope_guard(static.Scope()):
        loss = _sgd_net()
        exe = static.Executor()
        exe.run(startup)
        for _ in range(2):
            exe.run(main, feed=_feed(), fetch_list=[loss],
                    return_numpy=False)
        report = exe.xprof_report(main)
        mem = report["memory"]
        assert mem["total_bytes"] == (mem["args_bytes"] + mem["out_bytes"]
                                      + mem["temp_bytes"]
                                      + mem["code_bytes"])
        assert mem["args_bytes"] > 0 and mem["out_bytes"] > 0
        # the same breakdown rides the per-program executor gauges
        tok = str(main._exec_cache_token)
        assert reg.get("executor.device_mem_args_bytes").value(
            program=tok) == mem["args_bytes"]
        assert reg.get("executor.device_mem_total_bytes").value(
            program=tok) == mem["total_bytes"]
        # aggregate across the hot cache covers at least this entry
        agg = exe.memory_stats()
        assert agg["programs"] >= 1
        assert agg["total_bytes"] >= mem["total_bytes"]
        # live-array census is a collect-time callback: any live jax.Array
        # (parameters at minimum) makes it nonzero
        assert reg.get("executor.device_mem_live_arrays").value() > 0
        assert reg.get("executor.device_mem_live_bytes").value() > 0


def test_executor_report_attributes_static_ops(_flags_guard):
    flags.set_flags({"metrics": True, "donate_state": True})
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup), \
            static.scope_guard(static.Scope()):
        loss = _sgd_net()
        exe = static.Executor()
        exe.run(startup)
        exe.run(main, feed=_feed(), fetch_list=[loss], return_numpy=False)
        report = exe.xprof_report(main, measured_ms=1.0)
        assert report["totals"]["attribution_coverage"] >= 0.9
        scoped = [r for r in report["regions"]
                  if xprof.OP_SCOPE_RE.match(r["region"])]
        assert len(scoped) >= 3  # fc/mul/sgd... each a <type>.b<i>.i<j>
        assert report["totals"]["mfu_measured"] is not None


# ---------------------------------------------------------------------------
# invariants: cache key + retrace counts unchanged by profiling
# ---------------------------------------------------------------------------
def test_scopes_change_neither_fingerprint_nor_cache_key(_flags_guard):
    flags.set_flags({"metrics": True, "donate_state": True})
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup), \
            static.scope_guard(static.Scope()):
        loss = _sgd_net()
        feed = _feed()

        def aot_text(scoped):
            flags.set_flags({"xprof_scopes": scoped})
            exe = static.Executor()
            exe.run(startup)
            exe.run(main, feed=feed, fetch_list=[loss], return_numpy=False)
            entry = next(e for e in exe._hot.values() if e.aot is not None)
            text = entry.aot.as_text()
            exe.close()
            return text

        def key_of():
            return build_cache_key(main, 7, [loss.name], feed, {}, {},
                                   donate=True, plan_fingerprint=None)

        scoped_re = static.Executor._SCOPED_META_RE
        flags.set_flags({"xprof_scopes": True})
        k_on, fp_on = key_of(), program_fingerprint(main)
        assert scoped_re.search(aot_text(True))  # the flag does something...
        flags.set_flags({"xprof_scopes": False})
        k_off, fp_off = key_of(), program_fingerprint(main)
        aot_text(False)  # compiles; metadata absence is NOT asserted — jax's
        # metadata-blind compilation cache may legally serve the scoped twin
        # ...but scopes live only in HLO metadata: program content and the
        # persistent compile-cache key are identical with profiling on/off
        assert fp_on == fp_off
        assert k_on == k_off


def test_zero_retrace_with_profiling_enabled(_flags_guard):
    # the fast-path contract of test_fastpath.py, re-pinned with the full
    # profiling stack on: scopes, AOT cost/memory extraction, gauges
    flags.set_flags({"donate_state": True, "metrics": True,
                     "xprof_scopes": True})
    reg = monitor.default_registry()
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup), \
            static.scope_guard(static.Scope()):
        loss = _sgd_net()
        exe = static.Executor()
        exe.run(startup)
        feed = _feed()
        miss0 = reg.get("executor.cache_miss").value()
        hit0 = reg.get("executor.cache_hit").value()
        tr0 = reg.get("executor.traces").value()
        n = 6
        for _ in range(n):
            exe.run(main, feed=feed, fetch_list=[loss], return_numpy=False)
        assert reg.get("executor.cache_miss").value() - miss0 == 1
        assert reg.get("executor.cache_hit").value() - hit0 == n - 1
        assert reg.get("executor.traces").value() - tr0 == 1
        exe.xprof_report(main)  # profiling an entry is free of retraces too
        assert reg.get("executor.traces").value() - tr0 == 1


def test_cost_and_memory_gauges_set_on_compile_cache_hit(_flags_guard,
                                                         tmp_path):
    # regression (satellite 3): the hit path used to skip cost extraction,
    # so a warm-started process reported cost_flops == 0 forever
    flags.set_flags({"donate_state": True, "metrics": True,
                     "compile_cache_dir": str(tmp_path)})
    reg = monitor.default_registry()
    main, startup = static.Program(), static.Program()
    main.random_seed = 7
    startup.random_seed = 7
    with static.program_guard(main, startup):
        loss = _sgd_net()

    def run_once():
        with static.scope_guard(static.Scope()):
            exe = static.Executor()
            exe.run(startup)
            exe.run(main, feed=_feed(), fetch_list=[loss],
                    return_numpy=False)
            return exe

    run_once()  # cold: compiles + stores
    assert sorted(tmp_path.glob("*.pdtc")), "cold run stored no executables"
    tok = str(main._exec_cache_token)
    # wipe the gauges the cold run set, then warm-start a fresh Executor
    reg.get("executor.cost_flops").set(0.0, program=tok)
    reg.get("executor.device_mem_total_bytes").set(0.0, program=tok)
    h0 = reg.get("executor.compile_cache_hit").value()
    tr0 = reg.get("executor.traces").value()
    exe = run_once()
    assert reg.get("executor.compile_cache_hit").value() - h0 >= 1
    assert reg.get("executor.traces").value() - tr0 == 0  # still zero-trace
    assert reg.get("executor.cost_flops").value(program=tok) > 0
    assert reg.get("executor.device_mem_total_bytes").value(program=tok) > 0
    exe.xprof_report(main, measured_ms=1.0)  # attributable after warm start


# ---------------------------------------------------------------------------
# flight recorder + tenancy + CLI riders
# ---------------------------------------------------------------------------
def test_flight_dump_carries_xprof_summary(tmp_path):
    m = jnp.ones((64, 64), jnp.float32)
    xprof.profile_jit(lambda a: a @ a, m, peaks=_PEAKS)  # -> _remember()
    out = tmp_path / "flight.json"
    trace.flight_recorder().dump(str(out))
    doc = json.loads(out.read_text())
    ev = [e for e in doc["events"] if e.get("kind") == "xprof.summary"]
    assert ev, "post-mortem dump missing the xprof.summary event"
    info = ev[-1]["info"]
    assert "attribution_coverage" in info and "top_regions" in info


def test_tenancy_temp_gauges(_flags_guard):
    from paddle_tpu.serving.tenancy import Tenant, TenantManager

    flags.set_flags({"metrics": True, "donate_state": True})
    reg = monitor.default_registry()
    main, startup = static.Program(), static.Program()
    scope = static.Scope()
    with static.program_guard(main, startup), static.scope_guard(scope):
        x = L.data("x", [8])
        y = L.fc(x, 4)
    mgr = TenantManager(max_live_programs=2)
    t = mgr.register(Tenant("a", main, ["x"], [y], scope))
    with static.scope_guard(scope):
        t.executor.run(startup)
        t.executor.run(main, feed={"x": np.ones((2, 8), np.float32)},
                       fetch_list=[y])
    mgr.acquire("a")
    assert t.executor.memory_stats()["programs"] >= 1
    live = reg.get("serve.live_temp_bytes").value()
    peak = reg.get("serve.peak_temp_bytes").value()
    assert live >= 0 and peak >= live
    mgr.evict_all()
    assert reg.get("serve.live_temp_bytes").value() == 0
    assert reg.get("serve.peak_temp_bytes").value() == peak  # high-water


# ---------------------------------------------------------------------------
# tools/xprof rides tier-1 via --selfcheck (the CI gate of satellite 6)
# ---------------------------------------------------------------------------
def test_xprof_cli_selfcheck():
    repo = Path(__file__).resolve().parents[1]
    proc = subprocess.run(
        [sys.executable, "-m", "tools.xprof", "--selfcheck"],
        cwd=repo, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "xprof selfcheck: OK" in proc.stdout


def test_xprof_cli_report_formats(tmp_path):
    repo = Path(__file__).resolve().parents[1]
    out = tmp_path / "report.json"
    proc = subprocess.run(
        [sys.executable, "-m", "tools.xprof", "--steps", "2",
         "--format", "json", "--out", str(out)],
        cwd=repo, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(out.read_text())
    assert report["schema"] == "xprof.report.v1"
    assert report["totals"]["attribution_coverage"] >= 0.9
