"""Quantized, bucketed, topology-aware gradient allreduce (parallel/compress.py).

Covers the PR-7 contract:
  * blockwise int8/fp8 quantization round-trips within the per-block error
    bound at every block size, and zero blocks round-trip exactly;
  * bucket assignment and the bucket signature are deterministic — the
    signature is byte-identical in a SECOND PROCESS;
  * the unquantized bucketed/hierarchical paths are parity-exact with
    lax.psum/pmean (bitwise on integer-valued data), and the quantized
    path lands within the blockwise error bound;
  * fleet's `DistributedStrategy.comm_quantize` gradient sync trains a toy
    problem to the same loss as the builder-owned pmean (exact for
    "none", tolerance-bounded for "int8"/"fp8");
  * dygraph `DataParallel(comm_buffer_size=...)` rides the same bucketer
    and rejects non-positive buffer sizes;
  * eager `collective.all_reduce` records comm.allreduce_bytes/_ms and
    comm.compress_ratio;
  * the Executor keeps zero steady-state retraces and a working persistent
    compile cache under `with_sharding(comm_quantize=...)` (the comm
    options ride the plan fingerprint into the cache key).
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import paddle_tpu.distributed as dist
import paddle_tpu.static as static
from paddle_tpu.core import flags
from paddle_tpu.optimizer import SGD
from paddle_tpu.parallel import collective as coll
from paddle_tpu.parallel import compress
from paddle_tpu.parallel import mesh as mesh_mod
from paddle_tpu.parallel.data_parallel import DataParallel
from paddle_tpu.parallel.fleet import DistributedOptimizer, DistributedStrategy
from paddle_tpu.parallel.mesh import DP_AXIS
from paddle_tpu.parallel.sharding import ShardingPlan
from paddle_tpu.static import layers as L
from paddle_tpu.utils import monitor


needs_devices = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs the 8-device virtual CPU mesh")


@pytest.fixture(autouse=True)
def _reset_mesh():
    yield
    mesh_mod.set_mesh(None)


@pytest.fixture
def _flags_guard():
    saved = flags.get_flags(["donate_state", "metrics", "compile_cache_dir"])
    yield
    flags.set_flags(saved)


def _mesh(n: int) -> Mesh:
    return Mesh(np.asarray(jax.devices()[:n]), (DP_AXIS,))


def _shard_map(fn, mesh, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# ---------------------------------------------------------------------------
# blockwise quantization round trip
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("block_size", [64, 256, 1024])
def test_int8_roundtrip_error_bound(block_size):
    """Per element the int8 error is at most half a quantization step:
    amax(block)/(2*127)."""
    rng = np.random.default_rng(0)
    x = rng.normal(size=(4 * block_size,)).astype(np.float32) * 3.0
    q, s = compress.quantize_blockwise(x, "int8", block_size)
    assert q.dtype == jnp.int8
    assert s.shape == (4,)
    back = np.asarray(compress.dequantize_blockwise(q, s, block_size))
    amax = np.abs(x.reshape(4, block_size)).max(axis=1, keepdims=True)
    bound = np.broadcast_to(amax / (2 * 127.0) + 1e-7,
                            (4, block_size)).reshape(-1)
    assert np.all(np.abs(back - x) <= bound)


@pytest.mark.parametrize("block_size", [64, 256])
def test_fp8_roundtrip_error_bound(block_size):
    if not hasattr(jnp, "float8_e4m3fn"):
        pytest.skip("no fp8 dtype in this jaxlib")
    rng = np.random.default_rng(1)
    x = rng.normal(size=(2 * block_size,)).astype(np.float32)
    q, s = compress.quantize_blockwise(x, "fp8", block_size)
    back = np.asarray(compress.dequantize_blockwise(q, s, block_size))
    # e4m3 keeps ~3 mantissa bits: relative error per element <~ 2^-3 / 2
    assert np.all(np.abs(back - x) <= np.abs(x) * 0.0725 + 1e-6)


def test_quantize_zero_block_exact():
    x = np.zeros((512,), np.float32)
    x[256:] = np.linspace(-1, 1, 256)
    q, s = compress.quantize_blockwise(x, "int8", 256)
    assert float(s[0]) == 0.0
    back = np.asarray(compress.dequantize_blockwise(q, s, 256))
    assert np.all(back[:256] == 0.0)


def test_quantize_rejects_ragged_input():
    with pytest.raises(ValueError, match="block_size"):
        compress.quantize_blockwise(np.ones((100,), np.float32), "int8", 256)
    with pytest.raises(ValueError, match="unknown compression kind"):
        compress.quantize_blockwise(np.ones((256,), np.float32), "int4", 256)


def test_wire_bytes_accounting():
    n, nelem = 8, 1 << 20
    raw = compress.wire_bytes(nelem, None, 256, n)
    q = compress.wire_bytes(nelem, "int8", 256, n)
    assert raw == int(2 * (n - 1) / n * nelem * 4)
    # the acceptance gate: quantized wire traffic <= 30% of fp32
    assert q / raw <= 0.30
    assert compress.wire_bytes(nelem, "int8", 256, 1) == 0


# ---------------------------------------------------------------------------
# bucketing determinism
# ---------------------------------------------------------------------------

def test_bucket_assignment_greedy_and_deterministic():
    cap_mb = 1024 / (1 << 20)  # a 1 KB cap expressed in MB
    sizes = [400, 400, 400, 2048, 100]
    b1 = compress.bucket_assignment(sizes, cap_mb)
    b2 = compress.bucket_assignment(list(sizes), cap_mb)
    assert b1 == b2
    assert b1 == [[0, 1], [2], [3], [4]]  # oversized leaf gets its own bucket


def _grad_tree():
    rng = np.random.default_rng(7)
    return {
        "fc1": {"w": jnp.asarray(rng.normal(size=(64, 32)), jnp.float32),
                "b": jnp.asarray(rng.normal(size=(32,)), jnp.float32)},
        "fc2": {"w": jnp.asarray(rng.normal(size=(32, 8)), jnp.float32),
                "b": jnp.asarray(rng.normal(size=(8,)), jnp.float32)},
    }


def test_bucket_signature_stable_in_process():
    g = _grad_tree()
    sig1 = compress.bucket_signature(g, 25.0)
    sig2 = compress.bucket_signature(_grad_tree(), 25.0)
    assert sig1 == sig2
    assert compress.bucket_signature(g, 1e-4) != sig1  # cap feeds the digest


_SIG_CHILD = r"""
import json
import jax.numpy as jnp
import numpy as np
from paddle_tpu.parallel import compress
rng = np.random.default_rng(7)
g = {
    "fc1": {"w": jnp.asarray(rng.normal(size=(64, 32)), jnp.float32),
            "b": jnp.asarray(rng.normal(size=(32,)), jnp.float32)},
    "fc2": {"w": jnp.asarray(rng.normal(size=(32, 8)), jnp.float32),
            "b": jnp.asarray(rng.normal(size=(8,)), jnp.float32)},
}
print(json.dumps({"sig": compress.bucket_signature(g, 25.0)}))
"""


def test_bucket_signature_cross_process(tmp_path):
    """The signature is safe for the persistent compile-cache key: a second
    process computes the identical digest."""
    script = tmp_path / "sig_child.py"
    script.write_text(_SIG_CHILD)
    repo = Path(__file__).resolve().parents[1]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(repo) + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, str(script)], cwd=repo,
                          capture_output=True, text=True, timeout=300,
                          env=env)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    child_sig = json.loads(proc.stdout.strip().splitlines()[-1])["sig"]
    assert child_sig == compress.bucket_signature(_grad_tree(), 25.0)


# ---------------------------------------------------------------------------
# allreduce parity on the 8-device mesh
# ---------------------------------------------------------------------------

def _per_shard(seed, shape=(8, 1024)):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=shape), jnp.float32)


@needs_devices
def test_bucketed_unquantized_matches_pmean():
    m = _mesh(8)
    xs = _per_shard(0)

    def both(x_local):
        x = x_local[0]
        g = {"a": x[:600], "b": x[600:].reshape(53, 8)}
        bucketed = compress.bucketed_all_reduce(
            g, DP_AXIS, buffer_mb=1e-3, hierarchy=None, mean=True)
        plain = jax.tree_util.tree_map(
            lambda v: jax.lax.pmean(v, DP_AXIS), g)
        return bucketed, plain

    with m:
        (bk, pl) = _shard_map(both, m, (P(DP_AXIS),), (P(), P()))(xs)
    for k in ("a", "b"):
        np.testing.assert_array_equal(np.asarray(bk[k]), np.asarray(pl[k]))


@needs_devices
@pytest.mark.parametrize("kind, bound", [("int8", 0.05), ("fp8", 0.2)])
def test_quantized_allreduce_error_bound(kind, bound):
    """Quantized allreduce vs exact psum: relative L2 error stays small
    (each element is off by at most a quantization step of its block,
    twice)."""
    if kind == "fp8" and not hasattr(jnp, "float8_e4m3fn"):
        pytest.skip("no fp8 dtype in this jaxlib")
    m = _mesh(8)
    xs = _per_shard(1)

    def both(x_local):
        x = x_local[0]
        exact = jax.lax.psum(x, DP_AXIS)
        q = compress.all_reduce_compressed(x, DP_AXIS, compress=kind,
                                           block_size=256)
        return exact, q

    with m:
        exact, q = _shard_map(both, m, (P(DP_AXIS),), (P(), P()))(xs)
    exact, q = np.asarray(exact), np.asarray(q)
    rel = np.linalg.norm(q - exact) / np.linalg.norm(exact)
    assert rel <= bound, rel


@needs_devices
def test_hierarchical_matches_flat_bitwise_on_integer_data():
    """On integer-valued fp32 data every partial sum is exact, so the
    hierarchical schedule (intra reduce-scatter -> inter allreduce -> intra
    all-gather) must equal flat psum bit-for-bit."""
    m = _mesh(8)
    rng = np.random.default_rng(3)
    xs = jnp.asarray(rng.integers(-64, 64, size=(8, 4096)), jnp.float32)

    def both(x_local):
        x = x_local[0]
        flat = compress.optimized_all_reduce(x, DP_AXIS, hierarchy=None)
        hier = compress.optimized_all_reduce(x, DP_AXIS, hierarchy=2)
        return flat, hier

    with m:
        flat, hier = _shard_map(both, m, (P(DP_AXIS),), (P(), P()))(xs)
    np.testing.assert_array_equal(np.asarray(flat), np.asarray(hier))


@needs_devices
def test_hierarchical_quantized_error_bound():
    m = _mesh(8)
    xs = _per_shard(4)

    def both(x_local):
        x = x_local[0]
        exact = jax.lax.psum(x, DP_AXIS)
        q = compress.optimized_all_reduce(x, DP_AXIS, compress="int8",
                                          hierarchy=2)
        return exact, q

    with m:
        exact, q = _shard_map(both, m, (P(DP_AXIS),), (P(), P()))(xs)
    exact, q = np.asarray(exact), np.asarray(q)
    rel = np.linalg.norm(q - exact) / np.linalg.norm(exact)
    assert rel <= 0.05, rel


def test_resolve_hierarchy_normalization():
    assert compress.resolve_hierarchy(None, 8) is None
    assert compress.resolve_hierarchy("off", 8) is None
    assert compress.resolve_hierarchy(2, 8) == (2, 4)
    assert compress.resolve_hierarchy((4, 2), 8) == (4, 2)
    assert compress.resolve_hierarchy(8, 8) is None  # degenerate: one group
    with pytest.raises(ValueError, match="does not divide"):
        compress.resolve_hierarchy(3, 8)
    with pytest.raises(ValueError, match="does not factor"):
        compress.resolve_hierarchy((3, 2), 8)


def test_hierarchical_groups_host_major():
    intra, inter = compress.hierarchical_groups(8, 4)
    assert intra == [[0, 1, 2, 3], [4, 5, 6, 7]]
    assert inter == [[0, 4], [1, 5], [2, 6], [3, 7]]


def test_dp_hierarchy_factors_by_local_devices():
    assert mesh_mod.dp_hierarchy(8, local=4) == (4, 2)
    assert mesh_mod.dp_hierarchy(8, local=8) is None   # single host
    assert mesh_mod.dp_hierarchy(8, local=1) is None   # one device per host
    assert mesh_mod.dp_hierarchy(8, local=3) is None   # does not divide


# ---------------------------------------------------------------------------
# collective.all_reduce front door
# ---------------------------------------------------------------------------

@needs_devices
def test_all_reduce_compress_traced():
    m = dist.init_parallel_env(dp=8)
    xs = _per_shard(5, (8, 512))

    def f(x_local):
        x = x_local[0]
        return coll.all_reduce(x, compress="int8"), jax.lax.psum(x, DP_AXIS)

    with m:
        q, exact = _shard_map(f, m, (P(DP_AXIS),), (P(), P()))(xs)
    rel = (np.linalg.norm(np.asarray(q) - np.asarray(exact))
           / np.linalg.norm(np.asarray(exact)))
    assert rel <= 0.05, rel


@needs_devices
def test_all_reduce_compress_scope_inherited():
    """compress=None inherits the ambient comm_scope; "none" opts out."""
    m = dist.init_parallel_env(dp=8)
    xs = _per_shard(6, (8, 512))
    opts = compress.CommOptions(quantize="int8", hierarchy=None)

    def f(x_local):
        x = x_local[0]
        with compress.comm_scope(opts):
            ambient = coll.all_reduce(x)            # quantized via scope
            exact = coll.all_reduce(x, compress="none")  # forced exact
        return ambient, exact, jax.lax.psum(x, DP_AXIS)

    with m:
        ambient, exact, psum = _shard_map(
            f, m, (P(DP_AXIS),), (P(), P(), P()))(xs)
    np.testing.assert_array_equal(np.asarray(exact), np.asarray(psum))
    assert not np.array_equal(np.asarray(ambient), np.asarray(psum))
    rel = (np.linalg.norm(np.asarray(ambient) - np.asarray(psum))
           / np.linalg.norm(np.asarray(psum)))
    assert rel <= 0.05


def test_all_reduce_rejects_bad_compress():
    with pytest.raises(ValueError, match="compress="):
        coll.all_reduce(jnp.ones((4,)), compress="int4")


@needs_devices
def test_eager_all_reduce_records_metrics(_flags_guard):
    flags.set_flags({"metrics": True})
    reg = monitor.default_registry()
    dist.init_parallel_env(dp=8)
    x = jnp.asarray(np.arange(512, dtype=np.float32))

    def _snap():
        by_ = reg.get("comm.allreduce_bytes")
        if by_ is None:
            return 0, 0
        return (by_.count(axis=DP_AXIS, dtype="int8"),
                by_.sum(axis=DP_AXIS, dtype="int8"))

    c0, s0 = _snap()
    out = coll.all_reduce(x)                      # fp32 eager
    np.testing.assert_allclose(np.asarray(out), np.asarray(x) * 8, rtol=1e-6)
    qout = coll.all_reduce(x, compress="int8")    # quantized eager
    rel = (np.linalg.norm(np.asarray(qout) - np.asarray(x) * 8)
           / max(np.linalg.norm(np.asarray(x) * 8), 1e-9))
    assert rel <= 0.05

    by = reg.get("comm.allreduce_bytes")
    ms = reg.get("comm.allreduce_ms")
    ratio = reg.get("comm.compress_ratio")
    assert by is not None and ms is not None and ratio is not None
    assert by.count(axis=DP_AXIS, dtype="float32") >= 1
    c1, s1 = _snap()
    wire = compress.wire_bytes(512, "int8", 256, 8)
    assert c1 - c0 >= 1                 # the eager quantized call landed
    assert (s1 - s0) >= wire and (s1 - s0) % wire == 0
    assert ms.count(axis=DP_AXIS) >= 2
    assert 0 < ratio.value() <= 0.30


# ---------------------------------------------------------------------------
# fleet comm_quantize end-to-end
# ---------------------------------------------------------------------------

def _toy_problem(seed=0):
    rng = np.random.RandomState(seed)
    w_true = rng.randn(8, 1).astype(np.float32)
    xs = rng.randn(64, 8).astype(np.float32)
    ys = xs @ w_true
    return jnp.asarray(xs), jnp.asarray(ys)


def _fleet_train(comm_quantize: str, steps: int = 15):
    """Toy dp=8 regression; comm_quantize="" means builder-owned pmean."""
    m = dist.init_parallel_env(dp=8)
    strategy = DistributedStrategy()
    strategy.comm_quantize = comm_quantize
    strategy.comm_configs.hierarchical = None
    opt = DistributedOptimizer(SGD(0.05), strategy)
    xs, ys = _toy_problem()
    params = {"w": jnp.zeros((8, 1), jnp.float32)}
    state = opt.init(params)

    def step(x_l, y_l, p, s):
        def loss_fn(p_):
            return jnp.mean((x_l @ p_["w"] - y_l) ** 2)
        loss, grads = jax.value_and_grad(loss_fn)(p)
        if not comm_quantize:  # legacy contract: the builder syncs
            grads = jax.tree_util.tree_map(
                lambda g: jax.lax.pmean(g, "dp"), grads)
        p2, s2 = opt.update(grads, s, p)
        return jax.lax.pmean(loss, "dp"), p2, s2

    losses = []
    with m:
        f = _shard_map(step, m, (P("dp"), P("dp"), P(), P()),
                       (P(), P(), P()))
        for _ in range(steps):
            loss, params, state = f(xs, ys, params, state)
            losses.append(float(loss))
    return losses


@needs_devices
def test_fleet_owned_sync_matches_builder_sync():
    base = _fleet_train("")
    owned = _fleet_train("none")
    assert owned == pytest.approx(base, rel=1e-5, abs=1e-7)


@needs_devices
@pytest.mark.parametrize("kind", ["int8", "fp8"])
def test_fleet_quantized_training_converges(kind):
    if kind == "fp8" and not hasattr(jnp, "float8_e4m3fn"):
        pytest.skip("no fp8 dtype in this jaxlib")
    base = _fleet_train("")
    q = _fleet_train(kind)
    assert q[-1] < 0.1 * q[0]                    # it actually trains
    assert abs(q[-1] - base[-1]) <= 0.05         # and lands near the exact run


def test_fleet_rejects_unknown_comm_quantize():
    strategy = DistributedStrategy()
    strategy.comm_quantize = "int4"
    with pytest.raises(ValueError, match="comm_quantize"):
        DistributedOptimizer(SGD(0.05), strategy)


# ---------------------------------------------------------------------------
# dygraph DataParallel face
# ---------------------------------------------------------------------------

def test_data_parallel_rejects_nonpositive_buffer():
    from paddle_tpu.nn import Linear
    with pytest.raises(ValueError, match="comm_buffer_size"):
        DataParallel(Linear(4, 4), comm_buffer_size=0)
    with pytest.raises(ValueError, match="comm_buffer_size"):
        DataParallel(Linear(4, 4), comm_buffer_size=-3)
    with pytest.raises(ValueError, match="comm_buffer_size"):
        DataParallel(Linear(4, 4), comm_buffer_size=None)


@needs_devices
def test_data_parallel_bucketed_grads_match_pmean():
    from paddle_tpu.distributed import env as dist_env

    m = dist.init_parallel_env(dp=8)
    from paddle_tpu.nn import Linear
    model = DataParallel(Linear(4, 4), comm_buffer_size=25)
    xs = _per_shard(9, (8, 256))

    def f(x_local):
        x = x_local[0]
        g = {"w": x.reshape(16, 16), "b": x[:16]}
        with dist_env.data_axis_scope(DP_AXIS):
            synced = model.apply_collective_grads(g)
        ref = jax.tree_util.tree_map(lambda v: jax.lax.pmean(v, DP_AXIS), g)
        return synced, ref

    with m:
        got, ref = _shard_map(f, m, (P(DP_AXIS),), (P(), P()))(xs)
    for k in ("w", "b"):
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(ref[k]))


# ---------------------------------------------------------------------------
# executor: zero retraces + compile cache under comm options
# ---------------------------------------------------------------------------

def _build_net(seed: int = 7):
    main, startup = static.Program(), static.Program()
    main.random_seed = seed
    startup.random_seed = seed
    with static.program_guard(main, startup):
        x = L.data("x", [8])
        y = L.data("y", [1])
        pred = L.fc(L.fc(x, 16, act="relu"), 1)
        loss = L.mean(L.square(L.elementwise_sub(pred, y)))
        static.optimizer.SGD(learning_rate=0.05).minimize(loss)
    return main, startup, loss


def _feed(batch: int = 16):
    rng = np.random.default_rng(3)
    return {"x": rng.normal(size=(batch, 8)).astype(np.float32),
            "y": rng.normal(size=(batch, 1)).astype(np.float32)}


def _train(run_target, main, startup, loss, steps: int = 5):
    scope = static.Scope()
    with static.scope_guard(scope):
        exe = static.Executor()
        exe.run(startup)
        feed = _feed()
        out = [exe.run(run_target, feed=feed, fetch_list=[loss],
                       return_numpy=False)[0] for _ in range(steps)]
        return [float(np.asarray(l)) for l in out], scope


def test_plan_fingerprint_carries_comm_options():
    m = _mesh(min(8, jax.device_count()))
    base = ShardingPlan(mesh=m).fingerprint()
    quant = ShardingPlan(mesh=m, comm_quantize="int8").fingerprint()
    quant2 = ShardingPlan(mesh=m, comm_quantize="int8",
                          comm_buffer_mb=4.0).fingerprint()
    assert base != quant
    assert quant != quant2
    assert ShardingPlan(mesh=m, comm_quantize="int8").fingerprint() == quant


@needs_devices
def test_sharded_zero_retraces_under_comm_quantize(_flags_guard):
    """Acceptance: comm_quantize/bucketing must not break the steady-state
    fast path — one compile, zero retraces after the first step."""
    flags.set_flags({"donate_state": True, "metrics": True})
    reg = monitor.default_registry()
    main, startup, loss = _build_net(seed=7)
    compiled = static.CompiledProgram(main).with_sharding(
        mesh=_mesh(8), comm_quantize="int8", comm_buffer_mb=4.0)

    scope = static.Scope()
    with static.scope_guard(scope):
        exe = static.Executor()
        exe.run(startup)
        feed = _feed()
        miss0 = reg.get("executor.cache_miss").value()
        exe.run(compiled, feed=feed, fetch_list=[loss], return_numpy=False)
        traces1 = reg.get("executor.traces").value()
        for _ in range(5):
            exe.run(compiled, feed=feed, fetch_list=[loss],
                    return_numpy=False)
        assert reg.get("executor.cache_miss").value() - miss0 == 1
        assert reg.get("executor.traces").value() == traces1


def _cc_counters(reg):
    def val(name):
        m = reg.get(name)
        return m.value() if m is not None else 0
    return (val("executor.compile_cache_hit"),
            val("executor.compile_cache_miss"),
            val("executor.traces"))


@needs_devices
def test_compile_cache_warm_start_under_comm_quantize(_flags_guard, tmp_path):
    """Acceptance: the persistent AOT cache still round-trips when the plan
    carries comm options (they feed the key via the plan fingerprint), and
    a warm run deserializes without re-tracing."""
    flags.set_flags({"donate_state": True, "metrics": True,
                     "compile_cache_dir": str(tmp_path)})
    reg = monitor.default_registry()
    main, startup, loss = _build_net(seed=7)
    compiled = static.CompiledProgram(main).with_sharding(
        mesh=_mesh(8), comm_quantize="int8")

    cold, _ = _train(compiled, main, startup, loss)
    assert sorted(tmp_path.glob("*.pdtc")), "cold run stored no executables"
    h0, m0, t0 = _cc_counters(reg)
    warm, _ = _train(compiled, main, startup, loss)
    h1, m1, t1 = _cc_counters(reg)
    assert warm == cold                      # bitwise: same executable bytes
    assert h1 - h0 >= 1
    assert t1 - t0 == 0                      # deserialization never re-traces

    # a different comm config must MISS, not replay the quantized executable
    other = static.CompiledProgram(main).with_sharding(
        mesh=_mesh(8), comm_quantize="fp8")
    h0, m0, _ = _cc_counters(reg)
    _train(other, main, startup, loss, steps=1)
    _, m1, _ = _cc_counters(reg)
    assert m1 - m0 >= 1
