"""Steady-state step fast path: donation parity + safety guard, async
dispatch, compile-cache stability, and the host→device prefetch stage
(io/prefetch.py DeviceFeeder wired through DataLoader and Model.fit)."""
import threading
import time

import jax
import numpy as np
import pytest

import paddle_tpu as pd
import paddle_tpu.nn as nn
import paddle_tpu.static as static
from paddle_tpu.core import flags
from paddle_tpu.core.errors import StaleScopeValueError
from paddle_tpu.io import DataLoader, DeviceFeeder, TensorDataset
from paddle_tpu.io.prefetch import device_prefetch
from paddle_tpu.static import executor as executor_mod
from paddle_tpu.static import layers as L
from paddle_tpu.utils import monitor


@pytest.fixture
def _flags_guard():
    saved = flags.get_flags(["donate_state", "metrics"])
    yield
    flags.set_flags(saved)


def _sgd_net():
    x = L.data("x", [8])
    y = L.data("y", [1])
    pred = L.fc(L.fc(x, 16, act="relu"), 1)
    loss = L.mean(L.square(L.elementwise_sub(pred, y)))
    static.optimizer.SGD(learning_rate=0.05).minimize(loss)
    return loss


def _train_losses(donate: bool, steps: int = 5, return_numpy: bool = True):
    """Fresh program/scope/executor; returns per-step losses as floats."""
    flags.set_flags({"donate_state": donate})
    main, startup = static.Program(), static.Program()
    main.random_seed = 7
    startup.random_seed = 7
    scope = static.Scope()
    with static.program_guard(main, startup), static.scope_guard(scope):
        loss = _sgd_net()
        exe = static.Executor()
        exe.run(startup)
        rng = np.random.default_rng(3)
        feed = {"x": rng.normal(size=(16, 8)).astype(np.float32),
                "y": rng.normal(size=(16, 1)).astype(np.float32)}
        out = [exe.run(main, feed=feed, fetch_list=[loss],
                       return_numpy=return_numpy)[0] for _ in range(steps)]
        return [float(np.asarray(l)) for l in out]


# ---------------------------------------------------------------------------
# donation: parity, the flag contract, and the stale-read guard
# ---------------------------------------------------------------------------
def test_donation_parity_flag_on_vs_off(_flags_guard):
    # PDTPU_FLAGS_donate_state=0 restores copy semantics bit-for-bit: the
    # compiled math is identical, donation only changes buffer ownership
    on = _train_losses(donate=True, return_numpy=False)
    off = _train_losses(donate=False, return_numpy=True)
    assert on == off
    assert on[-1] < on[0]  # and training actually trains


def test_forced_donation_parity_and_buffer_consumption(
        _flags_guard, monkeypatch):
    # CPU gates real donation off (_donation_async_safe: XLA:CPU runs
    # donated computations synchronously); force it to cover the
    # donate_argnums path and prove parity holds there too
    off = _train_losses(donate=False)
    monkeypatch.setattr(executor_mod, "_FORCE_DONATION", True)
    on = _train_losses(donate=True, return_numpy=False)
    assert on == off

    # and donation really consumes the input buffers: a reference captured
    # before a donated step is deleted afterwards
    flags.set_flags({"donate_state": True})
    main, startup = static.Program(), static.Program()
    scope = static.Scope()
    with static.program_guard(main, startup), static.scope_guard(scope):
        loss = _sgd_net()
        exe = static.Executor()
        exe.run(startup)
        feed = {"x": np.ones((4, 8), np.float32),
                "y": np.ones((4, 1), np.float32)}
        exe.run(main, feed=feed, fetch_list=[loss], return_numpy=False)
        w_name = next(n for n in scope.keys() if n.startswith("param"))
        held = scope.find_var(w_name)
        assert isinstance(held, jax.Array) and not held.is_deleted()
        exe.run(main, feed=feed, fetch_list=[loss], return_numpy=False)
        assert held.is_deleted()           # donated into the second step
        # ...while the scope's own entry was pointer-swapped to the update
        fresh = scope.find_var(w_name)
        assert fresh is not held and not fresh.is_deleted()


def test_stale_scope_read_raises_legible_error(_flags_guard, monkeypatch):
    monkeypatch.setattr(executor_mod, "_FORCE_DONATION", True)
    flags.set_flags({"donate_state": True})
    main, startup = static.Program(), static.Program()
    scope = static.Scope()
    with static.program_guard(main, startup), static.scope_guard(scope):
        loss = _sgd_net()
        exe = static.Executor()
        exe.run(startup)
        feed = {"x": np.ones((4, 8), np.float32),
                "y": np.ones((4, 1), np.float32)}
        exe.run(main, feed=feed, fetch_list=[loss], return_numpy=False)
        w_name = next(n for n in scope.keys() if n.startswith("param"))
        stale = static.Scope()
        stale.set(w_name, scope.find_var(w_name))  # alias, not a copy
        exe.run(main, feed=feed, fetch_list=[loss], return_numpy=False)
        # the aliased buffer was donated: reading it must fail with the
        # typed, actionable error — not XLA's 'Array has been deleted'
        with pytest.raises(StaleScopeValueError, match="donate"):
            stale.find_var(w_name)
        # the run scope itself is fine (write-back replaced the entry)
        assert not scope.find_var(w_name).is_deleted()


def test_donation_skips_parent_scope_values(_flags_guard, monkeypatch):
    # fall-through reads from a parent scope are never donated — the
    # reference's scope semantics (framework/scope.h): children must not
    # clobber ancestors
    monkeypatch.setattr(executor_mod, "_FORCE_DONATION", True)
    flags.set_flags({"donate_state": True})
    main, startup = static.Program(), static.Program()
    root = static.Scope()
    with static.program_guard(main, startup), static.scope_guard(root):
        loss = _sgd_net()
        exe = static.Executor()
        exe.run(startup)
        feed = {"x": np.ones((4, 8), np.float32),
                "y": np.ones((4, 1), np.float32)}
        exe.run(main, feed=feed, fetch_list=[loss], return_numpy=False)
        parent_vals = {n: root.find_var(n) for n in root.keys()}
        kid = root.new_scope()
        exe.run(main, feed=feed, fetch_list=[loss], scope=kid,
                return_numpy=False)
        for n, v in parent_vals.items():
            if isinstance(v, jax.Array):
                assert not v.is_deleted(), n   # parent buffers untouched
            assert root.local_var(n) is v      # and still the same objects


# ---------------------------------------------------------------------------
# async dispatch + cache stability
# ---------------------------------------------------------------------------
def test_return_numpy_false_returns_device_arrays(_flags_guard):
    flags.set_flags({"donate_state": True})
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup), \
            static.scope_guard(static.Scope()):
        loss = _sgd_net()
        exe = static.Executor()
        exe.run(startup)
        feed = {"x": np.ones((4, 8), np.float32),
                "y": np.ones((4, 1), np.float32)}
        out = exe.run(main, feed=feed, fetch_list=[loss], return_numpy=False)
        assert isinstance(out[0], jax.Array)
        sync = exe.run(main, feed=feed, fetch_list=[loss])
        assert isinstance(sync[0], np.ndarray)


def test_jax_array_feeds_accepted(_flags_guard):
    # DeviceFeeder hands the executor device-resident batches; they must be
    # passed through without a host round-trip and give identical results
    main, startup = static.Program(), static.Program()
    main.random_seed = 7
    startup.random_seed = 7
    with static.program_guard(main, startup), \
            static.scope_guard(static.Scope()):
        x = L.data("x", [8])
        out_v = L.fc(x, 4)
        exe = static.Executor()
        exe.run(startup)
        xv = np.random.default_rng(0).normal(size=(4, 8)).astype(np.float32)
        host = exe.run(main, feed={"x": xv}, fetch_list=[out_v])[0]
        dev = exe.run(main, feed={"x": jax.device_put(xv)},
                      fetch_list=[out_v])[0]
        np.testing.assert_array_equal(host, dev)


def test_fast_path_zero_retraces(_flags_guard):
    # steady state on the fast path = ONE compile then cache hits only;
    # the step counter (PRNG fold) and chained device state must not
    # change the cache key
    flags.set_flags({"donate_state": True, "metrics": True})
    reg = monitor.default_registry()
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup), \
            static.scope_guard(static.Scope()):
        loss = _sgd_net()
        exe = static.Executor()
        exe.run(startup)
        feed = {"x": np.ones((16, 8), np.float32),
                "y": np.ones((16, 1), np.float32)}
        miss0 = reg.get("executor.cache_miss").value()
        hit0 = reg.get("executor.cache_hit").value()
        disp0 = reg.get("executor.dispatch_time_ms").count()
        step0 = reg.get("executor.step_time_ms").count()
        n = 6
        for _ in range(n):
            exe.run(main, feed=feed, fetch_list=[loss], return_numpy=False)
        assert reg.get("executor.cache_miss").value() - miss0 == 1
        assert reg.get("executor.cache_hit").value() - hit0 == n - 1
        # satellite contract: dispatch_time_ms is the host rim, recorded on
        # every hit; step_time_ms (one blocking sync) only while metrics on
        assert reg.get("executor.dispatch_time_ms").count() - disp0 == n - 1
        assert reg.get("executor.step_time_ms").count() - step0 == n - 1


# ---------------------------------------------------------------------------
# DeviceFeeder: ordering, backpressure, errors, cleanup
# ---------------------------------------------------------------------------
def _feeder_threads():
    return [t for t in threading.enumerate()
            if t.name == "pdtpu-device-feeder" and t.is_alive()]


def test_device_feeder_orders_and_places_batches():
    batches = [{"x": np.full((2, 3), i, np.float32)} for i in range(7)]
    got = list(DeviceFeeder(batches, depth=2))
    assert len(got) == 7
    for i, b in enumerate(got):
        assert isinstance(b["x"], jax.Array)
        np.testing.assert_array_equal(np.asarray(b["x"]), batches[i]["x"])
    assert not _feeder_threads()


def test_device_feeder_backpressure_bounds_readahead():
    pulled = []

    def source():
        for i in range(100):
            pulled.append(i)
            yield np.full((2,), i, np.float32)

    feeder = DeviceFeeder(source(), depth=2)
    it = iter(feeder)
    next(it)
    time.sleep(0.3)  # consumer stalls; feeder may stage at most depth+1
    assert len(pulled) <= feeder.depth + 2
    feeder.close()
    assert not _feeder_threads()


def test_device_feeder_propagates_source_errors():
    def source():
        yield np.zeros((2,), np.float32)
        raise RuntimeError("bad shard")

    with pytest.raises(RuntimeError, match="bad shard"):
        for _ in DeviceFeeder(source()):
            pass
    assert not _feeder_threads()


def test_device_feeder_early_break_stops_thread():
    feeder = DeviceFeeder(
        (np.full((2,), i, np.float32) for i in range(1000)), depth=2)
    for b in feeder:
        break  # abandon mid-stream
    deadline = time.time() + 5.0
    while _feeder_threads() and time.time() < deadline:
        time.sleep(0.01)
    assert not _feeder_threads()


def test_device_feeder_rejects_bad_depth():
    with pytest.raises(ValueError, match="depth"):
        DeviceFeeder([], depth=0)


# ---------------------------------------------------------------------------
# DataLoader integration + prefetch_factor regression
# ---------------------------------------------------------------------------
def test_dataloader_prefetch_to_device_matches_host_loader():
    xs = np.arange(40, dtype=np.float32).reshape(10, 4)
    plain = DataLoader(TensorDataset([xs]), batch_size=3)
    staged = DataLoader(TensorDataset([xs]), batch_size=3,
                        prefetch_to_device=True)
    host = [b[0] for b in plain]
    dev = [b[0] for b in staged]
    assert len(host) == len(dev)
    for h, d in zip(host, dev):
        assert isinstance(d, jax.Array)
        np.testing.assert_array_equal(np.asarray(d), np.asarray(h))
    assert not _feeder_threads()


def test_dataloader_prefetch_factor_one_honored():
    # regression: prefetch_factor used to be silently clamped to >= 2
    xs = np.arange(24, dtype=np.float32).reshape(12, 2)
    dl = DataLoader(TensorDataset([xs]), batch_size=4, num_workers=2,
                    prefetch_factor=1)
    assert dl.prefetch_factor == 1
    got = np.concatenate([np.asarray(b[0]) for b in dl])
    np.testing.assert_array_equal(got, xs)


def test_dataloader_prefetch_factor_below_one_raises():
    with pytest.raises(ValueError, match="prefetch_factor"):
        DataLoader(TensorDataset([np.zeros((4, 2), np.float32)]),
                   batch_size=2, prefetch_factor=0)


# ---------------------------------------------------------------------------
# hapi: prefetch wiring + lazy batch logs
# ---------------------------------------------------------------------------
def test_model_fit_with_device_prefetch():
    from paddle_tpu.hapi import Model

    rng = np.random.default_rng(0)
    xs = rng.normal(size=(64, 4)).astype(np.float32)
    ys = (xs @ np.array([[1.0], [-2.0], [0.5], [3.0]],
                        np.float32)).astype(np.float32)
    ds = TensorDataset([xs, ys])
    model = Model(nn.Linear(4, 1))
    model.prepare(optimizer=pd.optimizer.SGD(learning_rate=0.1),
                  loss=nn.MSELoss())
    logs0 = model.evaluate(ds, batch_size=16, verbose=0)
    model.fit(ds, batch_size=16, epochs=4, verbose=0,
              prefetch_to_device=True)
    logs1 = model.evaluate(ds, batch_size=16, verbose=0)
    assert logs1["loss"] < logs0["loss"] * 0.5, (logs0, logs1)
    assert not _feeder_threads()


def test_lazy_logs_defer_materialization():
    from paddle_tpu.hapi.model import _LazyLogs

    calls = []
    logs = _LazyLogs(step=3)
    logs.set_lazy("loss", lambda: calls.append("loss") or 1.25)
    assert logs["step"] == 3
    assert calls == []              # nothing forced yet
    assert "loss" in logs           # membership does not force either
    assert logs["loss"] == 1.25     # reading forces the device sync
    assert calls == ["loss"]
    assert logs["loss"] == 1.25 and calls == ["loss"]  # forced once
    assert dict(logs.materialize()) == {"step": 3, "loss": 1.25}
