"""Vocab-sharded embeddings (parallel/embedding.py) — the ISSUE-15 suite.

Covers the subsystem end to end:
  * the dedup'd ``is_sparse`` gradient path: bitwise grad parity with the
    dense lookup and xprof-modeled backward flops that scale with the id
    batch, not the vocab (the SelectedRows contract);
  * ``padding_idx``: zero forward rows AND a zero gradient row (the
    padding row survives an SGD step bit-for-bit);
  * the sharded exchange: forward and backward bitwise vs the dense
    single-device reference on a pure-tp mesh and on dp×tp (the dp case
    pins shard_map's replicated-cotangent psum — a double count here is
    exactly 2×), int8-quantized backward wire within tolerance;
  * capacity / exchange-byte accounting;
  * end-to-end static training under ``ShardingPlan(embedding_shard=)``:
    token rows bitwise, losses within rtol 1e-6, zero steady-state
    retraces;
  * elastic checkpoints: a vocab-sharded table saved on tp=4 restores
    onto tp=2 bitwise (dict-form ``embedding_shard`` — no program);
  * shardcheck SC010 (indivisible vocab, batch-axis conflict, annotation
    conflict, dense-fallback warning);
  * serving: ``add_embedding_tenant`` submit-side dedup returns rows in
    token order bitwise;
  * fleet strategy plumbing, the ShardedEmbedding class, PS host-table
    interop, plan-fingerprint coverage.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu.static as static
import paddle_tpu.static.shardcheck as sc
from paddle_tpu.elastic import checkpoint as eckpt
from paddle_tpu.parallel import embedding as pemb
from paddle_tpu.parallel import fleet
from paddle_tpu.parallel.mesh import DP_AXIS, TP_AXIS
from paddle_tpu.parallel.sharding import ShardingPlan
from paddle_tpu.static import layers as L
from paddle_tpu.utils import monitor, xprof

needs_devices = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs the 8-device virtual CPU mesh")


def _mesh(dp: int, tp: int) -> Mesh:
    devs = np.asarray(jax.devices()[:dp * tp]).reshape(dp, tp)
    return Mesh(devs, (DP_AXIS, TP_AXIS))


def _table(vocab: int, dim: int, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.normal(size=(vocab, dim)).astype(np.float32)


def _dup_ids(vocab: int, n: int, seed: int = 1) -> np.ndarray:
    """Duplicate-heavy id batch (the CTR shape the dedup exists for)."""
    rng = np.random.default_rng(seed)
    return rng.integers(0, max(2, vocab // 4), size=(n,)).astype(np.int32)


# ---------------------------------------------------------------------------
# sparse_lookup: the is_sparse segment-sum gradient
# ---------------------------------------------------------------------------

def test_sparse_lookup_forward_and_grad_bitwise():
    w = _table(64, 8)
    ids = _dup_ids(64, 32)
    coef = _table(32, 8, seed=2)

    assert np.array_equal(pemb.sparse_lookup(w, ids), w[ids])

    def dense(wa):
        return jnp.sum(jnp.take(wa, ids, axis=0) * coef)

    def sparse(wa):
        return jnp.sum(pemb.sparse_lookup(wa, ids) * coef)

    g_dense = np.asarray(jax.grad(dense)(jnp.asarray(w)))
    g_sparse = np.asarray(jax.grad(sparse)(jnp.asarray(w)))
    assert np.array_equal(g_dense, g_sparse)
    # rows never looked up get exactly zero
    untouched = np.setdiff1d(np.arange(64), ids)
    assert not g_sparse[untouched].any()


def test_sparse_lookup_backward_flops_scale_with_batch_not_vocab():
    """xprof-modeled flops of the sparse backward follow the id batch:
    4x the ids ≥ 2x the flops, while 8x the vocab stays under 1.5x."""
    def make(vocab, n):
        w = jnp.asarray(_table(vocab, 16))
        ids = jnp.asarray(_dup_ids(vocab, n))

        def loss(wa):
            return jnp.sum(pemb.sparse_lookup(wa, ids))

        rep = xprof.profile_jit(jax.grad(loss), w)
        return rep["totals"]["flops_modeled"]

    base = make(256, 64)
    more_ids = make(256, 256)
    more_vocab = make(2048, 64)
    assert more_ids >= 2.0 * base
    assert more_vocab <= 1.5 * base


def test_is_sparse_static_training_parity():
    """A lookup_table with is_sparse=True trains bit-identically to the
    dense gradient path (same program, same init, 3 SGD steps)."""
    def build(is_sparse):
        main, startup = static.Program(), static.Program()
        with static.program_guard(main, startup):
            ids = L.data("ids", [], dtype="int64")
            y = L.data("y", [1])
            emb = L.embedding(ids, size=[64, 8], name="emb",
                              is_sparse=is_sparse)
            pred = L.fc(emb, 1)
            loss = L.mean(L.square_error_cost(pred, y))
            static.optimizer.SGD(learning_rate=0.1).minimize(loss)
        return main, startup, loss

    rng = np.random.default_rng(0)
    ids = _dup_ids(64, 16).astype(np.int64)
    yv = rng.normal(size=(16, 1)).astype(np.float32)

    runs = []
    init = None
    for is_sparse in (False, True):
        main, startup, loss = build(is_sparse)
        exe = static.Executor()
        scope = static.Scope()
        with static.scope_guard(scope):
            exe.run(startup)
            if init is None:
                init = [np.array(scope.find_var(p.name))
                        for p in main.all_parameters()]
            else:
                for p, v in zip(main.all_parameters(), init):
                    scope.set(p.name, v)
            losses = [np.array(exe.run(main, feed={"ids": ids, "y": yv},
                                       fetch_list=[loss])[0])
                      for _ in range(3)]
            table = np.array(scope.find_var(
                main.all_parameters()[0].name))
        runs.append((losses, table))
    (l_dense, t_dense), (l_sparse, t_sparse) = runs
    assert all(np.array_equal(a, b) for a, b in zip(l_dense, l_sparse))
    assert np.array_equal(t_dense, t_sparse)


# ---------------------------------------------------------------------------
# padding_idx
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("is_sparse", [False, True])
def test_padding_idx_zero_rows_and_zero_gradient(is_sparse):
    pad = 3
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        ids = L.data("ids", [], dtype="int64")
        y = L.data("y", [1])
        emb = L.embedding(ids, size=[32, 4], name="pademb",
                          padding_idx=pad, is_sparse=is_sparse)
        pred = L.fc(emb, 1)
        loss = L.mean(L.square_error_cost(pred, y))
        static.optimizer.SGD(learning_rate=0.5).minimize(loss)

    ids_v = np.array([1, 3, 3, 7, 3, 0, 5, 3], dtype=np.int64)
    yv = np.ones((8, 1), np.float32)
    exe = static.Executor()
    scope = static.Scope()
    with static.scope_guard(scope):
        exe.run(startup)
        wname = "pademb.w"
        before = np.array(scope.find_var(wname))
        out = exe.run(main, feed={"ids": ids_v, "y": yv},
                      fetch_list=[emb, loss])
        rows = np.asarray(out[0])
        after = np.array(scope.find_var(wname))
    # forward: padding rows are exact zeros, others are the table rows
    assert not rows[ids_v == pad].any()
    assert np.array_equal(rows[ids_v != pad], before[ids_v[ids_v != pad]])
    # backward: the padding row took a zero gradient through the SGD step
    assert np.array_equal(after[pad], before[pad])
    touched = [i for i in np.unique(ids_v) if i != pad]
    assert not np.array_equal(after[touched], before[touched])


# ---------------------------------------------------------------------------
# the sharded exchange
# ---------------------------------------------------------------------------

@needs_devices
@pytest.mark.parametrize("dp,tp", [(1, 8), (2, 4)])
def test_sharded_lookup_forward_bitwise(dp, tp):
    mesh = _mesh(dp, tp)
    w = _table(64, 8)
    ids = _dup_ids(64, 32)
    out = pemb.sharded_lookup(jnp.asarray(w), jnp.asarray(ids), mesh=mesh,
                              axis=TP_AXIS, batch_axes=(DP_AXIS,))
    assert np.array_equal(np.asarray(out), w[ids])


@needs_devices
@pytest.mark.parametrize("dp,tp", [(1, 8), (2, 4)])
def test_sharded_lookup_backward_bitwise(dp, tp):
    """dp>1 is the double-count canary: shard_map transposition psums the
    replicated table's cotangent over dp, so a body-side psum would make
    the gradient exactly dp× the dense one.  With an integer-valued
    cotangent every summation order is exact, so parity is bitwise (and a
    2× error still lands exactly on 2×); with a real-valued cotangent the
    two XLA programs may reassociate the duplicate-row sums at the last
    ulp, so that leg pins rtol 1e-6 plus the explicit 2× canary."""
    mesh = _mesh(dp, tp)
    w = jnp.asarray(_table(64, 8))
    ids = jnp.asarray(_dup_ids(64, 32))
    coef = jnp.asarray(_table(32, 8, seed=2))

    def dense(wa, c):
        return jnp.sum(jnp.take(wa, ids, axis=0) * c)

    def sharded(wa, c):
        out = pemb.sharded_lookup(wa, ids, mesh=mesh, axis=TP_AXIS,
                                  batch_axes=(DP_AXIS,))
        return jnp.sum(out * c)

    ones = jnp.ones_like(coef)
    g_dense_i = np.asarray(jax.grad(dense)(w, ones))
    g_sharded_i = np.asarray(jax.grad(sharded)(w, ones))
    assert np.array_equal(g_dense_i, g_sharded_i)

    g_dense = np.asarray(jax.grad(dense)(w, coef))
    g_sharded = np.asarray(jax.grad(sharded)(w, coef))
    np.testing.assert_allclose(g_sharded, g_dense, rtol=1e-6, atol=1e-7)
    assert not np.allclose(g_sharded, 2.0 * g_dense, rtol=1e-3, atol=1e-7)


@needs_devices
def test_sharded_lookup_quantized_backward_close():
    """int8 backward wire: forward stays bitwise, the gradient lands
    within blockwise-quantization tolerance of the exact one."""
    mesh = _mesh(1, 8)
    w = jnp.asarray(_table(64, 8))
    ids = jnp.asarray(_dup_ids(64, 32))

    def loss(wa, q):
        return jnp.sum(pemb.sharded_lookup(
            wa, ids, mesh=mesh, axis=TP_AXIS, quantize=q) ** 2)

    out_q = pemb.sharded_lookup(w, ids, mesh=mesh, axis=TP_AXIS,
                                quantize="int8")
    assert np.array_equal(np.asarray(out_q), np.asarray(w)[np.asarray(ids)])
    g_exact = np.asarray(jax.grad(loss)(w, ""))
    g_q = np.asarray(jax.grad(loss)(w, "int8"))
    assert np.all(np.isfinite(g_q))
    scale = np.abs(g_exact).max()
    assert np.abs(g_q - g_exact).max() <= 0.05 * scale


@needs_devices
def test_sharded_lookup_capacity_factor_uniform_ids_exact():
    """With near-uniform ids a trimmed capacity still drops nothing."""
    mesh = _mesh(1, 8)
    w = _table(64, 8)
    ids = np.arange(32, dtype=np.int32) * 2  # exactly 4 uniques per shard
    out = pemb.sharded_lookup(jnp.asarray(w), jnp.asarray(ids), mesh=mesh,
                              axis=TP_AXIS, capacity_factor=1.0)
    assert np.array_equal(np.asarray(out), w[ids])


def test_sharded_lookup_indivisible_vocab_raises():
    mesh = _mesh(1, 8)
    with pytest.raises(ValueError, match="SC010"):
        pemb.sharded_lookup(jnp.asarray(_table(63, 8)),
                            jnp.zeros((4,), jnp.int32),
                            mesh=mesh, axis=TP_AXIS)


def test_capacity_and_exchange_byte_accounting():
    assert pemb.unique_capacity(32, 8) == 32            # exact mode
    assert pemb.unique_capacity(32, 8, 1.5) == 6        # ceil(32/8*1.5)
    assert pemb.exchange_bytes(32, 8, 1) == 0           # no off-chip axis
    plain = pemb.exchange_bytes(24, 8, 4)
    quant = pemb.exchange_bytes(24, 8, 4, quantize="int8")
    # off=3, C=24: ids 3*24*4 + fwd rows 3*24*32 + bwd rows 3*24*32
    assert plain == 3 * 24 * 4 + 2 * (3 * 24 * 32)
    # int8 bwd: 8 payload bytes + one fp32 scale per row
    assert quant == 3 * 24 * 4 + 3 * 24 * 32 + 3 * 24 * 12
    assert quant < plain


# ---------------------------------------------------------------------------
# end-to-end static training under ShardingPlan(embedding_shard=)
# ---------------------------------------------------------------------------

def _ctr(vocab=64, dim=8):
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        ids = L.data("ids", [], dtype="int64")
        y = L.data("y", [1])
        emb = L.embedding(ids, size=[vocab, dim], name="ctr_emb")
        pred = L.fc(emb, 1)
        loss = L.mean(L.square_error_cost(pred, y))
        static.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, startup, loss, emb


@needs_devices
def test_executor_embedding_shard_token_parity_and_no_retrace():
    rng = np.random.default_rng(0)
    ids = _dup_ids(64, 16).astype(np.int64)
    yv = rng.normal(size=(16, 1)).astype(np.float32)

    main, startup, loss, emb = _ctr()
    exe = static.Executor()
    scope = static.Scope()
    with static.scope_guard(scope):
        exe.run(startup)
        init = [np.array(scope.find_var(p.name))
                for p in main.all_parameters()]
        ref = [exe.run(main, feed={"ids": ids, "y": yv},
                       fetch_list=[loss, emb]) for _ in range(3)]

    mesh = _mesh(1, 8)
    main2, startup2, loss2, emb2 = _ctr()
    comp = static.CompiledProgram(main2).with_sharding(
        mesh=mesh, embedding_shard=TP_AXIS)
    exe2 = static.Executor()
    scope2 = static.Scope()
    traces = monitor.default_registry().get("executor.traces")
    with static.scope_guard(scope2):
        exe2.run(startup2)
        for p, v in zip(main2.all_parameters(), init):
            scope2.set(p.name, v)
        first = exe2.run(comp, feed={"ids": ids, "y": yv},
                         fetch_list=[loss2, emb2])
        # the table really lives vocab-sharded on the mesh
        table = scope2.find_var("ctr_emb.w")
        assert table.sharding.is_equivalent_to(
            NamedSharding(mesh, P(TP_AXIS, None)), table.ndim)
        warm = traces.value()
        rest = [exe2.run(comp, feed={"ids": ids, "y": yv},
                         fetch_list=[loss2, emb2]) for _ in range(2)]
        assert traces.value() == warm  # zero steady-state retraces
    sh = [first] + rest
    # token-level parity: step-0 embedding rows bitwise
    assert np.array_equal(np.asarray(ref[0][1]), np.asarray(sh[0][1]))
    # whole-step fusion may reassociate fp32 sums at the last ulp
    np.testing.assert_allclose(
        [float(np.asarray(r[0])) for r in ref],
        [float(np.asarray(s[0])) for s in sh], rtol=1e-6, atol=0.0)


# ---------------------------------------------------------------------------
# elastic checkpoints: vocab-shards reshard 4 -> 2
# ---------------------------------------------------------------------------

@needs_devices
def test_checkpoint_reshard_vocab_shards_4_to_2_bitwise(tmp_path):
    w = _table(64, 8)
    plan4 = ShardingPlan(mesh=_mesh(1, 4),
                         embedding_shard={"emb": TP_AXIS}, donate=False)
    sharded = jax.device_put(
        w, NamedSharding(plan4.resolve_mesh(), P(TP_AXIS, None)))
    state = {"emb.w": sharded, "fc.b": np.zeros((4,), np.float32)}
    # dict-form patterns match state names with no program in sight
    assert plan4.embedding_axis_for("emb.w") == TP_AXIS
    assert plan4.state_shardings(state)["emb.w"].is_equivalent_to(
        NamedSharding(plan4.resolve_mesh(), P(TP_AXIS, None)), 2)
    eckpt.save_checkpoint(str(tmp_path), state, 7, plan=plan4)

    plan2 = ShardingPlan(mesh=_mesh(1, 2),
                         embedding_shard={"emb": TP_AXIS}, donate=False)
    restored, meta = eckpt.restore_checkpoint(str(tmp_path), plan=plan2)
    assert meta["resharded_leaves"] >= 1
    got = restored["emb.w"]
    assert np.array_equal(np.asarray(got), w)
    assert got.sharding.is_equivalent_to(
        NamedSharding(plan2.resolve_mesh(), P(TP_AXIS, None)), got.ndim)


# ---------------------------------------------------------------------------
# shardcheck SC010
# ---------------------------------------------------------------------------

def _codes(diags, severity=None):
    return [d.code for d in diags
            if severity is None or d.severity == severity]


@needs_devices
def test_sc010_indivisible_vocab_error():
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        ids = L.data("ids", [], dtype="int64")
        L.embedding(ids, size=[63, 8], name="bad")
    plan = ShardingPlan(mesh=_mesh(1, 8), embedding_shard=TP_AXIS)
    report = sc.verify_plan(main, plan, feed_shapes={"ids": (16,)})
    assert "SC010" in _codes(report.errors)
    assert any("63" in d.message for d in report.errors
               if d.code == "SC010")


@needs_devices
def test_sc010_batch_axis_conflict_error():
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        ids = L.data("ids", [], dtype="int64")
        L.embedding(ids, size=[64, 8], name="emb")
    plan = ShardingPlan(mesh=_mesh(8, 1), embedding_shard=DP_AXIS,
                        batch_axes=(DP_AXIS,))
    report = sc.verify_plan(main, plan, feed_shapes={"ids": (16,)})
    assert "SC010" in _codes(report.errors)


@needs_devices
def test_sc010_annotation_conflict_error():
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        ids = L.data("ids", [], dtype="int64")
        L.embedding(ids, size=[64, 8], name="emb")
    plan = ShardingPlan(mesh=_mesh(1, 8), embedding_shard=TP_AXIS,
                        annotations={"emb.w": (None, TP_AXIS)})
    report = sc.verify_plan(main, plan, feed_shapes={"ids": (16,)})
    assert "SC010" in _codes(report.errors)


@needs_devices
def test_sc010_uncovered_huge_table_warns():
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        ids = L.data("ids", [], dtype="int64")
        L.embedding(ids, size=[1 << 17, 8], name="huge")
    plan = ShardingPlan(mesh=_mesh(8, 1))
    report = sc.verify_plan(main, plan, feed_shapes={"ids": (16,)})
    warn = [d for d in report.warnings if d.code == "SC010"]
    assert warn and "is_sparse" in (warn[0].hint or "")
    assert report.errors == []
    # covered or is_sparse tables don't warn
    main2, startup2 = static.Program(), static.Program()
    with static.program_guard(main2, startup2):
        ids2 = L.data("ids", [], dtype="int64")
        L.embedding(ids2, size=[1 << 17, 8], name="huge2", is_sparse=True)
    report2 = sc.verify_plan(main2, plan, feed_shapes={"ids": (16,)})
    assert not [d for d in report2.warnings if d.code == "SC010"]


# ---------------------------------------------------------------------------
# serving: embedding tenant with submit-side dedup
# ---------------------------------------------------------------------------

def test_serving_embedding_tenant_dedup_parity():
    from paddle_tpu.serving.frontend import Server

    w = _table(64, 8)
    ids = np.array([5, 9, 5, 5, 31, 9, 0, 5], dtype=np.int64)
    with Server(bucket_edges=(16,), max_wait_ms=0.5) as srv:
        srv.add_embedding_tenant("rec", w)
        out = srv.submit("rec", {"ids": ids}).result(timeout=60)
    rows = np.asarray(out[0], np.float32)
    # duplicates restored in token order, rows bitwise
    assert rows.shape == (8, 8)
    assert np.array_equal(rows, w[ids])
    g = monitor.default_registry().get("emb.unique_ratio")
    assert g is not None and 0.0 < g.value() < 1.0  # 5 uniques / 8 ids


def test_serving_embedding_tenant_padding_idx():
    from paddle_tpu.serving.frontend import Server

    w = _table(32, 4)
    ids = np.array([1, 2, 1, 4], dtype=np.int64)
    with Server(bucket_edges=(8,), max_wait_ms=0.5) as srv:
        srv.add_embedding_tenant("pad", w, padding_idx=2)
        rows = np.asarray(
            srv.submit("pad", {"ids": ids}).result(timeout=60)[0])
    expect = w[ids].copy()
    expect[ids == 2] = 0.0
    assert np.array_equal(rows, expect)


# ---------------------------------------------------------------------------
# fleet strategy + the ShardedEmbedding class + PS interop
# ---------------------------------------------------------------------------

def test_fleet_embedding_plan_kwargs():
    strat = fleet.DistributedStrategy()
    assert fleet.embedding_plan_kwargs(strat) == {}
    strat.sharded_embedding = True
    strat.embedding_configs.capacity_factor = 1.5
    strat.embedding_configs.quantize = "int8"
    kw = fleet.embedding_plan_kwargs(strat)
    assert kw == {"embedding_shard": TP_AXIS,
                  "embedding_capacity": 1.5,
                  "embedding_quantize": "int8"}
    plan = ShardingPlan(mesh=_mesh(1, 8), **kw)
    assert plan.embedding_axis_for("anything.w", lookup=True) == TP_AXIS
    assert "int8" in plan.fingerprint()


@needs_devices
def test_sharded_embedding_class_lookup_and_grad():
    mesh = _mesh(1, 8)
    w = _table(64, 8)
    emb = pemb.ShardedEmbedding(64, 8, axis=TP_AXIS, mesh=mesh, weight=w)
    assert emb.spec() == (TP_AXIS, None)
    assert emb.weight.sharding.is_equivalent_to(
        NamedSharding(mesh, P(TP_AXIS, None)), 2)
    ids = np.array([[3, 3], [17, 60]], np.int32)
    out = np.asarray(emb(ids))
    assert out.shape == (2, 2, 8)
    assert np.array_equal(out, w[ids])

    def loss(wa):
        return jnp.sum(emb.lookup(ids, weight=wa))

    g = np.asarray(jax.grad(loss)(emb.weight))
    expect = np.zeros_like(w)
    np.add.at(expect, ids.reshape(-1), 1.0)
    assert np.array_equal(g, expect)
    with pytest.raises(ValueError, match="divisible"):
        pemb.ShardedEmbedding(63, 8, axis=TP_AXIS, mesh=mesh)


def test_to_host_table_ps_pull_parity():
    from paddle_tpu.distributed.ps import SparseTable

    w = _table(48, 6)
    table = pemb.to_host_table(w, num_shards=3)
    assert isinstance(table, SparseTable)
    ids = np.array([0, 7, 7, 47, 13], np.int64)
    assert np.array_equal(table.pull(ids), w[ids])


def test_plan_fingerprint_carries_embedding_config():
    base = ShardingPlan(mesh=_mesh(1, 8))
    covered = ShardingPlan(mesh=_mesh(1, 8), embedding_shard=TP_AXIS)
    tuned = ShardingPlan(mesh=_mesh(1, 8), embedding_shard=TP_AXIS,
                         embedding_capacity=1.2, embedding_quantize="int8")
    prints = {p.fingerprint() for p in (base, covered, tuned)}
    assert len(prints) == 3
