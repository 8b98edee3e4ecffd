"""Serving subsystem (paddle_tpu/serving/): bucketed coalescing frontend,
continuous-batching decode, tenant LRU + quotas, SLO load shed, and the
capi worker's pipelined request-id framing.

The two load-bearing contracts pinned bitwise here:

* PADDING PARITY — the real rows of a padded bucket batch are bitwise
  identical to running each request alone.  Holds for row-independent
  graphs whose matmul shapes are not degenerate (contraction dim >= 8 and
  output dim >= 2 on XLA:CPU; tinier gemms can take batch-size-dependent
  kernel strategies — a kernel-choice property, not a padding artifact).
* DECODE PARITY — a sequence's generated tokens are identical no matter
  which slot it decodes in, who its neighbors are, or when it joins.

Plus zero steady-state retraces per bucket (``executor.traces``).
"""
import os
import struct
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu.static as static
from paddle_tpu import serving
from paddle_tpu.core import flags
from paddle_tpu.core.errors import NotFoundError
from paddle_tpu.serving import (AdmissionError, ContinuousBatcher,
                                QuotaExceededError, SLOPolicy, Server,
                                make_toy_lm)
from paddle_tpu.static import layers as L
from paddle_tpu.utils import monitor, trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _metrics_on():
    saved = flags.get_flags(["metrics"])
    flags.set_flags({"metrics": True})
    yield
    flags.set_flags(saved)


def _mlp_tenant(seed=3, in_dim=8, out_dim=4):
    """fc(8 -> 16 tanh -> 4): row-independent, batch-invariant dims."""
    main, startup = static.Program(), static.Program()
    main.random_seed = seed
    startup.random_seed = seed
    scope = static.Scope()
    with static.program_guard(main, startup), static.scope_guard(scope):
        x = L.data("x", [in_dim])
        y = L.fc(L.fc(x, 16, act="tanh"), out_dim)
        exe = static.Executor()
        exe.run(startup, scope=scope)
    return main, y, scope


def _int_tenant():
    """int32 in, int32 out (x*x + x): parity must hold exactly."""
    main, startup = static.Program(), static.Program()
    scope = static.Scope()
    with static.program_guard(main, startup), static.scope_guard(scope):
        x = L.data("x", [6], dtype="int32")
        y = L.elementwise_add(L.elementwise_mul(x, x), x)
        exe = static.Executor()
        exe.run(startup, scope=scope)
    return main, y, scope


def _bitwise_equal(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return a.dtype == b.dtype and a.shape == b.shape and np.array_equal(
        a.view(np.uint8), b.view(np.uint8))


# ---------------------------------------------------------------------------
# frontend: coalescing, padding parity, concurrency, zero retraces
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("rows", [1, 3, 5, 8])
def test_bucket_padding_bitwise_parity_f32(rows):
    """What padding owes a request: its rows come back bitwise as a batch of
    the bucket's size computes them, whatever the other rows hold.  (Against
    a batch of ONE they differ in the last ulp on XLA:CPU, whose product for
    one row is another kernel than for several, at any optimization level:
    that is the backend's, and was this test's oracle until PR 30.)"""
    main, y, scope = _mlp_tenant()
    rng = np.random.default_rng(0)
    x = rng.normal(size=(rows, 8)).astype(np.float32)
    with Server(bucket_edges=(1, 2, 4, 8), max_wait_ms=5.0) as srv:
        srv.add_tenant("m", main, ["x"], [y], scope)
        out = srv.submit("m", {"x": x}).result(timeout=60)[0]
        bucket = srv._bucket_for(rows)
    filler = rng.normal(size=(bucket - rows, 8)).astype(np.float32)
    ref = static.Executor().run(
        main, feed={"x": np.concatenate([x, filler])}, fetch_list=[y],
        scope=scope)[0][:rows]
    assert _bitwise_equal(out, ref)


def test_bucket_padding_bitwise_parity_int32():
    main, y, scope = _int_tenant()
    with Server(bucket_edges=(1, 4, 8), max_wait_ms=5.0) as srv:
        srv.add_tenant("m", main, ["x"], [y], scope)
        xs = [np.arange(6, dtype=np.int32).reshape(1, 6) + i
              for i in range(10)]
        outs = [f.result(timeout=60)[0]
                for f in [srv.submit("m", {"x": x}) for x in xs]]
    for x, out in zip(xs, outs):
        assert _bitwise_equal(out, x * x + x)


def test_multi_row_requests_coalesce_and_slice_correctly():
    main, y, scope = _mlp_tenant()
    ref_exe = static.Executor()
    rng = np.random.default_rng(1)
    sizes = [3, 1, 2, 5, 1, 4]
    xs = [rng.normal(size=(n, 8)).astype(np.float32) for n in sizes]
    with Server(bucket_edges=(1, 2, 4, 8, 16), max_wait_ms=5.0) as srv:
        srv.add_tenant("m", main, ["x"], [y], scope)
        outs = [f.result(timeout=60)[0]
                for f in [srv.submit("m", {"x": x}) for x in xs]]
    for x, out in zip(xs, outs):
        assert out.shape == (x.shape[0], 4)
        ref = ref_exe.run(main, feed={"x": x}, fetch_list=[y],
                          scope=scope)[0]
        # XLA:CPU's products are not batch-invariant to the last ulp;
        # bitwise f32 parity at the bucket's own size is pinned above
        np.testing.assert_allclose(out, ref, rtol=0, atol=1e-5)


def test_concurrent_submit_8_threads():
    # int32 elementwise model: results are exact, so 8 racing submitter
    # threads x arbitrary coalescing must still produce bitwise answers
    main, y, scope = _int_tenant()
    rng = np.random.default_rng(2)
    per_thread = 10
    xs = {(t, i): rng.integers(-50, 50, size=(1 + (t + i) % 3, 6)
                               ).astype(np.int32)
          for t in range(8) for i in range(per_thread)}
    results, errs = {}, []
    with Server(bucket_edges=(1, 2, 4, 8), max_wait_ms=1.0) as srv:
        srv.add_tenant("m", main, ["x"], [y], scope)

        def client(t):
            try:
                for i in range(per_thread):
                    out = srv.submit(
                        "m", {"x": xs[(t, i)]}).result(timeout=60)[0]
                    results[(t, i)] = out
            except Exception as e:  # noqa: BLE001 — surface in main thread
                errs.append(e)

        threads = [threading.Thread(target=client, args=(t,))
                   for t in range(8)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
    assert not errs, errs
    assert len(results) == 8 * per_thread
    for key, x in xs.items():
        assert _bitwise_equal(results[key], x * x + x)


def test_zero_steady_state_retraces_per_bucket():
    main, y, scope = _mlp_tenant()
    reg = monitor.default_registry()
    rng = np.random.default_rng(3)
    with Server(bucket_edges=(1, 2, 4), max_wait_ms=0.0) as srv:
        srv.add_tenant("m", main, ["x"], [y], scope)
        # warm every bucket once (each compiles its own entry)
        for n in (1, 2, 4):
            srv.submit("m", {"x": rng.normal(size=(n, 8)).astype(
                np.float32)}).result(timeout=60)
        traces0 = reg.get("executor.traces").value()
        hot0 = len(srv.tenants.get("m").executor._hot)
        for _ in range(5):
            for n in (1, 2, 4):
                srv.submit("m", {"x": rng.normal(size=(n, 8)).astype(
                    np.float32)}).result(timeout=60)
        assert reg.get("executor.traces").value() == traces0
        # the buckets keep distinct pinned hot slots, none evicted another
        assert len(srv.tenants.get("m").executor._hot) == hot0 == 3


def test_submit_validation_and_error_propagation():
    main, y, scope = _mlp_tenant()
    with Server(bucket_edges=(1, 2), max_wait_ms=0.0) as srv:
        srv.add_tenant("m", main, ["x"], [y], scope)
        with pytest.raises(ValueError):  # wrong feed names
            srv.submit("m", {"wrong": np.zeros((1, 8), np.float32)})
        with pytest.raises(ValueError):  # rows > largest bucket
            srv.submit("m", {"x": np.zeros((3, 8), np.float32)})
        with pytest.raises(ValueError):  # scalar feed
            srv.submit("m", {"x": np.float32(1.0)})
        with pytest.raises(NotFoundError):
            srv.submit("nope", {"x": np.zeros((1, 8), np.float32)})
        # an executor failure surfaces on the FUTURE, not the dispatcher:
        # same feed name, wrong trailing shape compiles into a shape error
        fut = srv.submit("m", {"x": np.zeros((1, 5), np.float32)})
        with pytest.raises(Exception):
            fut.result(timeout=60)
        # ...and the server keeps serving afterwards
        out = srv.submit("m", {"x": np.zeros((1, 8), np.float32)}).result(
            timeout=60)[0]
        assert out.shape == (1, 4)


def test_closed_server_rejects_and_drains():
    main, y, scope = _mlp_tenant()
    srv = Server(bucket_edges=(1,), max_wait_ms=0.0)
    srv.add_tenant("m", main, ["x"], [y], scope)
    srv.start()
    fut = srv.submit("m", {"x": np.zeros((1, 8), np.float32)})
    srv.close()  # drain=True: queued work completes
    assert fut.result(timeout=60)[0].shape == (1, 4)
    with pytest.raises(AdmissionError):
        srv.submit("m", {"x": np.zeros((1, 8), np.float32)})


# ---------------------------------------------------------------------------
# tenancy: LRU eviction, recompile on return, quotas
# ---------------------------------------------------------------------------
def test_tenant_lru_eviction_and_recompile_on_return():
    reg = monitor.default_registry()
    tenants = [(f"t{i}",) + _mlp_tenant(seed=i) for i in range(3)]
    with Server(bucket_edges=(1,), max_wait_ms=0.0,
                max_live_programs=2) as srv:
        for name, main, y, scope in tenants:
            srv.add_tenant(name, main, ["x"], [y], scope)
        x = np.ones((1, 8), np.float32)
        ev0 = reg.get("serve.program_evictions").value(tenant="t0")
        out0 = srv.submit("t0", {"x": x}).result(timeout=60)[0]
        srv.submit("t1", {"x": x}).result(timeout=60)
        assert srv.tenants.live() == ["t0", "t1"]
        assert len(srv.tenants.get("t0").executor._cache) == 1
        # t2 arrives -> LRU victim t0 is evicted: compiled state dropped,
        # flight-recorded, counted
        srv.submit("t2", {"x": x}).result(timeout=60)
        assert srv.tenants.live() == ["t1", "t2"]
        assert len(srv.tenants.get("t0").executor._cache) == 0
        assert len(srv.tenants.get("t0").executor._hot) == 0
        assert (reg.get("serve.program_evictions").value(tenant="t0")
                == ev0 + 1)
        events = [e for e in trace.flight_recorder().events()
                  if e.get("kind") == "serve_program_evicted"
                  and e.get("name") == "t0"]
        assert events, "eviction was not flight-recorded"
        # t0 returns: transparently recompiles, same bits, evicts t1 (LRU)
        miss0 = reg.get("executor.cache_miss").value()
        out0b = srv.submit("t0", {"x": x}).result(timeout=60)[0]
        assert reg.get("executor.cache_miss").value() == miss0 + 1
        assert _bitwise_equal(out0, out0b)
        assert srv.tenants.live() == ["t2", "t0"]


def test_tenant_isolation_distinct_params():
    main_a, y_a, scope_a = _mlp_tenant(seed=1)
    main_b, y_b, scope_b = _mlp_tenant(seed=2)
    x = np.ones((1, 8), np.float32)
    with Server(bucket_edges=(1,), max_wait_ms=0.0) as srv:
        srv.add_tenant("a", main_a, ["x"], [y_a], scope_a)
        srv.add_tenant("b", main_b, ["x"], [y_b], scope_b)
        oa = srv.submit("a", {"x": x}).result(timeout=60)[0]
        ob = srv.submit("b", {"x": x}).result(timeout=60)[0]
    assert not np.array_equal(oa, ob)  # different seeds, different params


def test_per_tenant_quota_sheds_typed_error():
    main, y, scope = _mlp_tenant()
    srv = Server(bucket_edges=(1,), max_wait_ms=0.0)
    srv.add_tenant("m", main, ["x"], [y], scope, quota=2)
    # server NOT started: submits queue up and hold quota
    f1 = srv.submit("m", {"x": np.zeros((1, 8), np.float32)})
    f2 = srv.submit("m", {"x": np.zeros((1, 8), np.float32)})
    with pytest.raises(QuotaExceededError):
        srv.submit("m", {"x": np.zeros((1, 8), np.float32)})
    reg = monitor.default_registry()
    assert reg.get("serve.load_shed").value(reason="quota") >= 1
    srv.start()  # dispatcher drains the two queued requests
    assert f1.result(timeout=60) and f2.result(timeout=60)
    # quota released on completion — a new submit is admitted again
    assert srv.submit("m", {"x": np.zeros((1, 8), np.float32)}).result(
        timeout=60)
    srv.close()


# ---------------------------------------------------------------------------
# SLO admission
# ---------------------------------------------------------------------------
def test_slo_policy_projection_and_shed():
    slo = SLOPolicy(p99_ms=None, min_samples=5)
    for _ in range(10):
        slo.observe("t", "4", 10.0)
    p99 = slo.observed_p99("t")
    assert 9.0 <= p99 <= 11.0
    # disabled policy admits anything
    slo.admit("t", queue_depth=1000, max_batch=4)
    slo.p99_ms = 15.0
    slo.admit("t", queue_depth=0, max_batch=4)  # projection ~=p99 < 15
    with pytest.raises(AdmissionError):
        # 4 full dispatches queued ahead -> projected ~5x observed p99
        slo.admit("t", queue_depth=16, max_batch=4)
    reg = monitor.default_registry()
    assert reg.get("serve.load_shed").value(reason="slo") >= 1


def test_slo_policy_needs_min_samples():
    slo = SLOPolicy(p99_ms=0.001, min_samples=50)
    for _ in range(10):
        slo.observe("t", "1", 99.0)
    # immature cell: no shed even though observations dwarf the SLO
    slo.admit("t", queue_depth=100, max_batch=1)


def test_server_load_shed_end_to_end():
    main, y, scope = _mlp_tenant()
    slo = SLOPolicy(p99_ms=0.5, min_samples=1)
    srv = Server(bucket_edges=(1,), max_wait_ms=0.0, slo=slo)
    srv.add_tenant("mshed", main, ["x"], [y], scope)
    # no mature latency data -> first submit admitted (server not started,
    # so it just queues)
    fut = srv.submit("mshed", {"x": np.zeros((1, 8), np.float32)})
    # now the observed p99 dwarfs the SLO -> the next submit sheds
    for _ in range(5):
        slo.observe("mshed", "1", 50.0)
    with pytest.raises(AdmissionError):
        srv.submit("mshed", {"x": np.zeros((1, 8), np.float32)})
    srv.close(drain=False)
    with pytest.raises(AdmissionError):
        fut.result(timeout=60)  # drain=False fails the queued future too


# ---------------------------------------------------------------------------
# continuous batching
# ---------------------------------------------------------------------------
def _toy(seed=5, max_len=24):
    return make_toy_lm(vocab=48, hidden=16, max_len=max_len, seed=seed)


def _sequential_reference(prompts, new_tokens, seed=5, max_len=24):
    step_fn, init_fn = _toy(seed, max_len)
    out = []
    for p in prompts:
        cb = ContinuousBatcher(step_fn, init_fn, num_slots=1,
                               max_len=max_len)
        out.append(cb.decode([p], max_new_tokens=new_tokens)[0])
    return out


def test_continuous_join_evict_mid_decode_parity():
    step_fn, init_fn = _toy()
    cb = ContinuousBatcher(step_fn, init_fn, num_slots=3, max_len=24)
    h1 = cb.join([1, 2, 3], max_new_tokens=8)
    h2 = cb.join([4, 5], max_new_tokens=8)
    for _ in range(4):
        cb.step()
    h3 = cb.join([7, 8, 9, 10], max_new_tokens=8)  # joins mid-decode
    for _ in range(3):
        cb.step()
    cb.evict(h2)  # evicted mid-decode: keeps partial output
    assert h2.done and h2.evicted
    partial = list(h2.tokens)
    assert 0 < len(partial) < 8
    cb.run_until_idle()
    assert h1.done and h3.done and not h1.evicted
    ref = _sequential_reference([[1, 2, 3], [4, 5], [7, 8, 9, 10]], 8)
    assert h1.tokens == ref[0]
    assert partial == ref[1][:len(partial)]  # prefix parity up to eviction
    assert h3.tokens == ref[2]


def test_continuous_decode_parity_many_sequences():
    prompts = [[(3 * i + j) % 48 for j in range(1 + i % 6)]
               for i in range(12)]
    step_fn, init_fn = _toy()
    cb = ContinuousBatcher(step_fn, init_fn, num_slots=4, max_len=24)
    multi = cb.decode(prompts, max_new_tokens=10)
    assert multi == _sequential_reference(prompts, 10)


def test_continuous_zero_retraces_across_join_evict():
    reg = monitor.default_registry()
    step_fn, init_fn = _toy()
    cb = ContinuousBatcher(step_fn, init_fn, num_slots=4, max_len=24)
    cb.decode([[1, 2]], max_new_tokens=4)  # warm: one trace
    traces0 = reg.get("executor.traces").value()
    h = cb.join([3, 4, 5], max_new_tokens=12)
    cb.step()
    cb.join([6], max_new_tokens=6)
    cb.step()
    cb.evict(h)
    cb.run_until_idle()
    cb.decode([[7, 8], [9]], max_new_tokens=8)
    assert reg.get("executor.traces").value() == traces0


def test_continuous_admission_and_bounds():
    step_fn, init_fn = _toy()
    cb = ContinuousBatcher(step_fn, init_fn, num_slots=2, max_len=24)
    cb.join([1], max_new_tokens=4)
    cb.join([2], max_new_tokens=4)
    with pytest.raises(AdmissionError):
        cb.join([3], max_new_tokens=4)
    with pytest.raises(ValueError):  # prompt + new tokens > max_len
        ContinuousBatcher(step_fn, init_fn, num_slots=1, max_len=8).join(
            [1, 2, 3, 4, 5], max_new_tokens=8)
    with pytest.raises(ValueError):
        cb.join([], max_new_tokens=4)


# ---------------------------------------------------------------------------
# capi worker: legacy + pipelined PDID framing
# ---------------------------------------------------------------------------
def _child_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    existing = env.get("PYTHONPATH", "")
    env["PYTHONPATH"] = ROOT + (os.pathsep + existing if existing else "")
    return env


_WIRE_DTYPES = {0: np.float32, 1: np.int32, 2: np.int64, 3: np.float64}
_WIRE_CODES = {np.dtype(v): k for k, v in _WIRE_DTYPES.items()}


def _enc_req(feed):
    out = b"PDRQ" + struct.pack("<i", len(feed))
    for name, arr in feed.items():
        nb = name.encode()
        out += struct.pack("<i", len(nb)) + nb
        out += struct.pack("<ii", _WIRE_CODES[arr.dtype], arr.ndim)
        out += struct.pack(f"<{arr.ndim}q", *arr.shape)
        out += arr.tobytes()
    return out


class _WorkerClient:
    def __init__(self, model_dir):
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "paddle_tpu.inference.capi_worker",
             model_dir], stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            env=_child_env())
        assert self._rd(4) == b"PDOK"

    def _rd(self, n):
        buf = b""
        while len(buf) < n:
            chunk = self.proc.stdout.read(n - len(buf))
            assert chunk, "worker EOF"
            buf += chunk
        return buf

    def send(self, feed, req_id=None):
        frame = _enc_req(feed)
        if req_id is not None:
            frame = b"PDID" + struct.pack("<Q", req_id) + frame
        self.proc.stdin.write(frame)
        self.proc.stdin.flush()

    def read_response(self):
        magic, rid = self._rd(4), None
        if magic == b"PDID":
            (rid,) = struct.unpack("<Q", self._rd(8))
            magic = self._rd(4)
        if magic == b"PDER":
            (n,) = struct.unpack("<i", self._rd(4))
            return rid, RuntimeError(self._rd(n).decode())
        assert magic == b"PDRS", magic
        (n,) = struct.unpack("<i", self._rd(4))
        outs = {}
        for _ in range(n):
            (nl,) = struct.unpack("<i", self._rd(4))
            name = self._rd(nl).decode()
            code, ndim = struct.unpack("<ii", self._rd(8))
            dims = struct.unpack(f"<{ndim}q", self._rd(8 * ndim))
            dt = np.dtype(_WIRE_DTYPES[code])
            raw = self._rd(int(np.prod(dims)) * dt.itemsize)
            outs[name] = np.frombuffer(raw, dt).reshape(dims)
        return rid, outs

    def close(self):
        self.proc.stdin.close()
        self.proc.wait(timeout=60)


@pytest.fixture(scope="module")
def _capi_model(tmp_path_factory):
    # int32 elementwise model (x*x + x): results are exact, so bitwise
    # assertions hold under ANY XLA flag set the child inherits (the f32
    # wire path is covered by tests/test_capi.py, f32 padding parity by
    # test_bucket_padding_bitwise_parity_f32 above)
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        x = L.data("x", [6], dtype="int32")
        y = L.elementwise_add(L.elementwise_mul(x, x), x)
    exe = static.Executor()
    exe.run(startup)
    model_dir = str(tmp_path_factory.mktemp("serve_capi") / "m")
    static.save_inference_model(model_dir, ["x"], [y], exe,
                                main_program=main)
    return model_dir


def test_capi_worker_legacy_framing_unchanged(_capi_model):
    client = _WorkerClient(_capi_model)
    try:
        rng = np.random.default_rng(0)
        for _ in range(3):
            x = rng.integers(-50, 50, size=(2, 6)).astype(np.int32)
            client.send({"x": x})
            rid, outs = client.read_response()
            assert rid is None  # legacy responses carry no id frame
            assert _bitwise_equal(list(outs.values())[0], x * x + x)
    finally:
        client.close()


def test_capi_worker_pipelined_id_framing(_capi_model):
    client = _WorkerClient(_capi_model)
    try:
        rng = np.random.default_rng(1)
        xs = {i: rng.integers(-50, 50, size=(1, 6)).astype(np.int32)
              for i in range(8)}
        for i in range(8):  # pipeline: no waiting between sends
            client.send({"x": xs[i]}, req_id=i)
        got = {}
        for _ in range(8):
            rid, outs = client.read_response()
            assert rid is not None
            got[rid] = list(outs.values())[0]
        assert sorted(got) == list(range(8))
        for i, x in xs.items():
            assert _bitwise_equal(got[i], x * x + x)
        # id-less request after id'd traffic = drain barrier + strict order
        xl = rng.integers(-50, 50, size=(3, 6)).astype(np.int32)
        client.send({"x": xl})
        rid, outs = client.read_response()
        assert rid is None
        assert _bitwise_equal(list(outs.values())[0], xl * xl + xl)
    finally:
        client.close()


def test_capi_inproc_echoes_id_frame(_capi_model):
    from paddle_tpu.inference import capi_inproc

    h = capi_inproc.create(_capi_model)
    try:
        x = np.ones((1, 6), np.int32)
        resp = capi_inproc.run(h, b"PDID" + struct.pack("<Q", 77)
                               + _enc_req({"x": x}))
        assert resp[:4] == b"PDID"
        (rid,) = struct.unpack("<Q", resp[4:12])
        assert rid == 77 and resp[12:16] == b"PDRS"
        # id-less stays byte-compatible
        resp2 = capi_inproc.run(h, _enc_req({"x": x}))
        assert resp2[:4] == b"PDRS"
        assert resp[16:] == resp2[4:]
    finally:
        capi_inproc.destroy(h)


# ---------------------------------------------------------------------------
# servebench rides tier-1 through its self-check
# ---------------------------------------------------------------------------
def test_servebench_selfcheck():
    out = subprocess.run(
        [sys.executable, "-m", "tools.servebench", "--selfcheck"],
        cwd=ROOT, env=_child_env(), capture_output=True, text=True,
        timeout=570)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "servebench selfcheck: OK" in out.stdout


# ---------------------------------------------------------------------------
# per-request TTFT decomposition: queue / batch / compile / execute
# ---------------------------------------------------------------------------
def test_ttft_decomposition_histograms_and_flight_spans():
    from paddle_tpu.serving import slo

    reg = monitor.default_registry()
    fr = trace.flight_recorder()

    def counts():
        return {n: reg.get(n).count()
                for n in ("serve.ttft_queue_ms", "serve.ttft_batch_ms",
                          "serve.ttft_compile_ms", "serve.ttft_execute_ms")}

    main, y, scope = _mlp_tenant()
    c0 = counts()
    seq0 = fr.last_seq
    with Server(bucket_edges=(1, 2, 4), max_wait_ms=0.0) as srv:
        srv.add_tenant("m", main, ["x"], [y], scope)
        # cold request: pays the bucket compile
        srv.submit("m", {"x": np.ones((1, 8), np.float32)}).result(timeout=60)
        # hot request, same bucket: compile segment must be 0
        srv.submit("m", {"x": np.ones((1, 8), np.float32)}).result(timeout=60)
    c1 = counts()
    assert all(c1[n] - c0[n] == 2 for n in c1), (c0, c1)

    evs = fr.events_since(seq0)
    reqs = [e for e in evs if e["kind"] == "serve_request"]
    assert len(reqs) == 2
    cold, hot = reqs
    # every request carries the full decomposition + its own trace context
    for r in reqs:
        assert {"queue_ms", "batch_ms", "compile_ms", "execute_ms",
                "total_ms", "trace_id", "span_id"} <= set(r)
        assert r["total_ms"] >= r["execute_ms"] >= 0.0
    assert cold["compile_ms"] > 0.0          # first b1 dispatch compiled
    assert hot["compile_ms"] == 0.0          # hot cache: pure execute
    assert hot["execute_ms"] > 0.0
    # the dispatch span tree is in the ring for tracecat: dispatch parents
    # assemble + execute, and itself parents under the request context
    begins = [e for e in evs if e["kind"] == "span_begin"]
    assert {"serve::dispatch", "serve::batch_assemble",
            "serve::execute"} <= {e["name"] for e in begins}
    # each dispatch parents under ITS head request's context (cold and hot
    # were separate single-request batches)
    dispatch, = [e for e in begins if e["name"] == "serve::dispatch"
                 and e.get("parent_id") == cold["span_id"]]
    assert dispatch["trace_id"] == cold["trace_id"]
    execute, = [e for e in begins if e["name"] == "serve::execute"
                and e.get("parent_id") == dispatch["span_id"]]
    assert execute["trace_id"] == cold["trace_id"]
    assert any(e["name"] == "serve::dispatch"
               and e.get("parent_id") == hot["span_id"] for e in begins)
    # histograms agree with the flight attribution: compile seen once
    assert reg.get("serve.ttft_compile_ms").sum() >= cold["compile_ms"] - 1.0
    # the percentile gauges are live now (real numbers, not nan)
    assert not np.isnan(slo.TTFT_P50.value())
    assert not np.isnan(slo.TTFT_P99.value())


def test_submit_inside_span_parents_request_context():
    main, y, scope = _mlp_tenant()
    fr = trace.flight_recorder()
    seq0 = fr.last_seq
    with Server(bucket_edges=(1,), max_wait_ms=0.0) as srv:
        srv.add_tenant("m", main, ["x"], [y], scope)
        with trace.span("client::call") as sp:
            srv.submit("m", {"x": np.ones((1, 8), np.float32)}
                       ).result(timeout=60)
            client_ctx = sp.context
    req, = [e for e in fr.events_since(seq0) if e["kind"] == "serve_request"]
    # the request context is a child of the caller's span: same trace,
    # parented under it — tracecat stitches client -> server causality
    assert req["trace_id"] == client_ctx.trace_id
    assert req["parent_id"] == client_ctx.span_id


# ---------------------------------------------------------------------------
# slow stress variants (excluded from tier-1; run with `-m slow`)
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_stress_many_threads_sustained():
    main, y, scope = _mlp_tenant()
    rng = np.random.default_rng(4)
    errs = []
    with Server(bucket_edges=(1, 2, 4, 8, 16), max_wait_ms=1.0) as srv:
        srv.add_tenant("m", main, ["x"], [y], scope)

        def client():
            try:
                for _ in range(200):
                    n = int(rng.integers(1, 5))
                    srv.submit("m", {"x": np.ones((n, 8), np.float32)}
                               ).result(timeout=120)
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        threads = [threading.Thread(target=client) for _ in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    assert not errs, errs


@pytest.mark.slow
def test_stress_continuous_churn_parity():
    step_fn, init_fn = _toy(max_len=40)
    cb = ContinuousBatcher(step_fn, init_fn, num_slots=6, max_len=40)
    prompts = [[(5 * i + j) % 48 for j in range(1 + i % 8)]
               for i in range(64)]
    multi = cb.decode(prompts, max_new_tokens=16)
    assert multi == _sequential_reference(prompts, 16, max_len=40)


def test_projected_p99_gauge_tracks_queue_backlog():
    """serve.projected_p99_ms{tenant} is a collect-time function gauge over
    SLOPolicy.projected_p99: equal to the observed p99 on an empty queue,
    inflated by the queued-dispatch factor under backlog."""
    slo = SLOPolicy(p99_ms=None, min_samples=1)
    depth = {"n": 0}
    slo.bind_queue(lambda: depth["n"], 8)
    for _ in range(20):
        slo.observe("t_proj", "4", 10.0)
    gauge = monitor.default_registry().get("serve.projected_p99_ms")
    observed = slo.observed_p99("t_proj")
    assert 9.0 <= observed <= 11.0
    # empty queue: the projection IS the observed p99
    assert gauge.value(tenant="t_proj") == pytest.approx(observed)
    # backlog: 64 queued rows / max_batch 8 -> 8 full dispatches ahead
    depth["n"] = 64
    assert gauge.value(tenant="t_proj") == pytest.approx(observed * 9.0)
    assert slo.projected_p99("t_proj", 64, 8) == \
        pytest.approx(observed * 9.0)
    # the gauge rides the normal exposition (history sampler's food)
    labels = dict(
        next(labels for labels, _ in gauge.samples()
             if labels.get("tenant") == "t_proj"))
    assert labels == {"tenant": "t_proj"}


def test_frontend_binds_projection_to_live_queue_depth():
    """Server wires its own queue into the policy at construction, so the
    exported projection reflects real backlog without any polling."""
    main, y, scope = _mlp_tenant()
    slo = SLOPolicy(p99_ms=None, min_samples=1)
    srv = Server(bucket_edges=(1,), max_wait_ms=0.0, slo=slo)
    srv.add_tenant("t_bind", main, ["x"], [y], scope)
    gauge = monitor.default_registry().get("serve.projected_p99_ms")
    # server NOT started: submits queue up and hold queued rows
    f1 = srv.submit("t_bind", {"x": np.zeros((1, 8), np.float32)})
    f2 = srv.submit("t_bind", {"x": np.zeros((1, 8), np.float32)})
    slo.observe("t_bind", "1", 10.0)
    backlog = gauge.value(tenant="t_bind")
    assert backlog == pytest.approx(
        slo.projected_p99("t_bind", 2, srv.max_batch))
    assert backlog > slo.observed_p99("t_bind")
    srv.start()                       # drain; projection falls back to p99
    assert f1.result(timeout=60) and f2.result(timeout=60)
    assert gauge.value(tenant="t_bind") == \
        pytest.approx(slo.observed_p99("t_bind"))
    srv.close()
