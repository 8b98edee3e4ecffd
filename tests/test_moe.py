"""MoE / expert parallelism (new TPU capability — SURVEY.md §2.2 EP row)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu
import paddle_tpu.distributed as dist
import paddle_tpu.nn as nn
from paddle_tpu.nn.layer.moe import switch_gating, top2_gating
from paddle_tpu.parallel import mesh as mesh_mod, shard_layer
from paddle_tpu.parallel.sharding import layer_annotations


@pytest.fixture(autouse=True)
def _reset_mesh():
    yield
    mesh_mod.set_mesh(None)


def _gates(b=2, s=8, e=4, seed=0):
    rng = np.random.default_rng(seed)
    return jax.nn.softmax(
        jnp.asarray(rng.normal(0, 1, (b, s, e)), jnp.float32), axis=-1)


def test_switch_gating_invariants():
    gates = _gates()
    dispatch, combine, aux = switch_gating(gates, capacity=8)
    # each token goes to at most one (expert, slot)
    assert np.all(np.asarray(dispatch.sum(axis=(2, 3))) <= 1 + 1e-6)
    # no slot is double-booked
    assert np.all(np.asarray(dispatch.sum(axis=1)) <= 1 + 1e-6)
    # combine weight equals the token's top gate when kept
    kept = np.asarray(dispatch.sum(axis=(2, 3))) > 0
    top_gate = np.asarray(gates.max(axis=-1))
    np.testing.assert_allclose(
        np.asarray(combine.sum(axis=(2, 3)))[kept], top_gate[kept], rtol=1e-5)
    assert float(aux) > 0


def test_switch_gating_capacity_drops():
    # all tokens pick expert 0 -> only `capacity` of them survive
    gates = jnp.tile(jnp.asarray([[0.97, 0.01, 0.01, 0.01]]), (1, 8, 1))
    dispatch, combine, _ = switch_gating(gates, capacity=3)
    assert float(dispatch.sum()) == 3.0
    # the first three tokens in sequence order are the ones kept
    np.testing.assert_allclose(
        np.asarray(dispatch.sum(axis=(2, 3))[0]), [1, 1, 1, 0, 0, 0, 0, 0])


def test_top2_gating_invariants():
    gates = _gates(seed=3)
    dispatch, combine, aux = top2_gating(gates, capacity=8)
    counts = np.asarray(dispatch.sum(axis=(2, 3)))
    assert np.all(counts <= 2 + 1e-6)   # at most two experts per token
    assert np.all(np.asarray(dispatch.sum(axis=1)) <= 1 + 1e-6)  # slots unique
    # combine weights are normalized over the two experts
    np.testing.assert_allclose(np.asarray(combine.sum(axis=(2, 3))),
                               np.ones((2, 8)), rtol=1e-4)


def test_moe_ffn_forward_and_aux():
    layer = nn.MoEFFN(16, 32, num_experts=4, top_k=2, capacity_factor=2.0)
    x = jnp.asarray(np.random.default_rng(0).normal(0, 1, (2, 8, 16)),
                    jnp.float32)
    y = layer(x)
    assert y.shape == (2, 8, 16)
    assert float(layer.aux_loss) > 0
    # with huge capacity nothing is dropped: outputs differ from zeros
    assert float(jnp.abs(y).sum()) > 0


def test_moe_matches_dense_expert_computation():
    # top-1, capacity >= S: MoE == routing each token through its argmax
    # expert's FFN scaled by its gate.
    layer = nn.MoEFFN(8, 16, num_experts=2, top_k=1, capacity_factor=8.0)
    x = jnp.asarray(np.random.default_rng(1).normal(0, 1, (1, 6, 8)),
                    jnp.float32)
    y = layer(x)
    logits = jnp.einsum("bsd,de->bse", x, layer.gate_weight.value)
    gates = jax.nn.softmax(logits, axis=-1)
    idx = np.asarray(jnp.argmax(gates, -1))[0]
    ref = np.zeros((6, 8), np.float32)
    for t in range(6):
        e = idx[t]
        h = np.tanh(0)  # placeholder
        hin = np.asarray(x)[0, t] @ np.asarray(layer.wi.value)[e]
        act = np.asarray(layer.activation(jnp.asarray(hin)))
        ref[t] = float(gates[0, t, e]) * (act @ np.asarray(layer.wo.value)[e])
    np.testing.assert_allclose(np.asarray(y)[0], ref, rtol=1e-4, atol=1e-5)


def test_moe_ep_sharded_matches_single_device():
    layer = nn.MoEFFN(8, 16, num_experts=4, top_k=2, capacity_factor=4.0)
    x = jnp.asarray(np.random.default_rng(2).normal(0, 1, (2, 8, 8)),
                    jnp.float32)
    ref = np.asarray(layer(x))
    m = dist.init_parallel_env(dp=1, ep=4, tp=2)
    ann = layer_annotations(layer)
    assert any("wi" in k for k in ann)
    shard_layer(layer, m)
    out = jax.jit(lambda inp: layer(inp))(x)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# the dropless layer's router: the normalisation's epsilon, no shared expert
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("norm_eps", [1e-20, 1e-6, 0.5])
def test_sigmoid_router_adds_its_epsilon_to_the_selected_sum(norm_eps):
    from paddle_tpu.nn.layer.moe import sigmoid_topk_routing
    x = jax.random.normal(jax.random.PRNGKey(0), (9, 16))
    w = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
    bias = jnp.zeros((8,)).at[5].set(10.0)
    ids, weights = sigmoid_topk_routing(x, w, bias, 3, norm_eps=norm_eps)
    scores = np.asarray(jax.nn.sigmoid(x @ w))
    picked = np.take_along_axis(scores, np.asarray(ids), axis=1)
    assert (np.asarray(ids) == 5).any(axis=1).all()   # the bias selects
    np.testing.assert_allclose(                       # and does not weigh
        weights, picked / (picked.sum(1, keepdims=True) + norm_eps),
        rtol=1e-5)


def test_sigmoid_router_default_epsilon_is_deepseeks():
    import inspect
    from paddle_tpu.nn.layer.moe import sigmoid_topk_routing
    sig = inspect.signature(sigmoid_topk_routing)
    assert sig.parameters["norm_eps"].default == 1e-20
    assert inspect.signature(nn.DroplessMoE).parameters[
        "norm_eps"].default == 1e-20


def test_dropless_layer_hands_its_epsilon_to_the_router():
    layer = nn.DroplessMoE(16, 8, 8, 3, norm_eps=0.5)
    x = jax.random.normal(jax.random.PRNGKey(2), (12, 16))
    _, weights = layer.route(x)
    _, plain = nn.DroplessMoE(16, 8, 8, 3).route(x)
    assert float(jnp.max(weights.sum(1))) < 0.9      # sum / (sum + 0.5)
    np.testing.assert_allclose(plain.sum(1), 1.0, rtol=1e-5)


def test_dropless_layer_without_shared_experts_plants_no_shared_scope():
    """`n_shared_experts = 0`: no parameter, no `shared` scope in the
    traced step, and the result is the held experts' sum alone."""
    import re
    layer = nn.DroplessMoE(16, 8, 8, 3, held=(0, 4), n_shared_experts=0)
    assert layer.shared_mlp is None
    assert sorted(n for n, _ in layer.named_parameters()) == [
        "router_bias", "router_weight", "w_in", "w_out"]
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 6, 16))
    text = jax.jit(layer).lower(x).as_text(debug_info=True)
    scopes = set(re.findall(r"\b(router|experts|shared)\b", text))
    assert scopes == {"router", "experts"}, scopes
    with_shared = nn.DroplessMoE(16, 8, 8, 3, held=(0, 4),
                                 n_shared_experts=1)
    text = jax.jit(with_shared).lower(x).as_text(debug_info=True)
    assert "shared" in text


# ---------------------------------------------------------------------------
# the dropless layer's passes over the sorted rows follow the held pairs
# ---------------------------------------------------------------------------
PD, PF, PE, PK, PT, CHUNK = 16, 8, 8, 2, 32, 16     # 64 pairs, 4 chunks
NEAR = CHUNK, 2 * CHUNK     # leading rows that the per-token gathers read


@pytest.fixture
def chunked(monkeypatch):
    """Chunks of 16 rows, 16 and 32 leading rows of float32 [·, 16] "near",
    and a buffer's unwritten rows hold NaN (off the TPU `lax.empty` gives
    zeros): whatever reads one is poisoned."""
    from paddle_tpu.nn.layer import moe
    monkeypatch.setattr(moe, "CHUNK_ROWS", CHUNK)
    monkeypatch.setattr(moe, "NEAR_BYTES", tuple(n * PD * 4 for n in NEAR))
    monkeypatch.setattr(jax.lax, "empty",
                        lambda shape, dtype: jnp.full(shape, jnp.nan, dtype))
    return moe


def _routed(held_pairs, tokens=PT, seed=0):
    """(layer, parameters, x [1, tokens, PD]): x's leading PE channels pick
    each token's two experts through a near-identity router, so that
    exactly `held_pairs` of the tokens × 2 pairs go to the held experts
    0..3, scattered over the tokens; None holds all 8 experts."""
    from paddle_tpu.autograd import parameters_dict
    rng = np.random.default_rng(seed)
    layer = nn.DroplessMoE(PD, PF, PE, PK,
                           held=None if held_pairs is None else (0, 4))
    held = np.zeros(tokens * PK, bool)
    held[rng.permutation(tokens * PK)[:held_pairs or 0]] = True
    held = held.reshape(tokens, PK)
    # slot 0 picks among experts {0, 1 | 4, 5}, slot 1 among {2, 3 | 6, 7}
    ids = np.where(held, 0, 4) + 2 * np.arange(PK) + rng.integers(
        0, 2, (tokens, PK))
    x = rng.normal(0, 1, (tokens, PD)).astype(np.float32)
    x[:, :PE] = -8.0
    np.put_along_axis(x, ids, 8.0, axis=1)
    p = {k: jnp.asarray(rng.normal(0, 0.3, v.shape), jnp.float32)
         for k, v in parameters_dict(layer).items()}
    p["router_weight"] = 0.01 * p["router_weight"] + jnp.eye(PD, PE)
    p["router_bias"] = jnp.zeros_like(p["router_bias"])
    return layer, p, jnp.asarray(x)[None]


def _value_and_grads(layer, p, x):
    from paddle_tpu.autograd import functional_call
    g = jnp.asarray(np.random.default_rng(9).normal(0, 1, x.shape),
                    jnp.float32)
    return jax.jit(jax.value_and_grad(
        lambda p, x: jnp.sum(functional_call(layer, p, (x,)) * g),
        (0, 1)))(p, x)


def _whole_buffer(moe, monkeypatch):
    """Every pass over every row and every gather from the whole buffer,
    whatever is held."""
    monkeypatch.setattr(
        moe, "_buffer_rows",
        lambda held, rows: (-(-rows // min(CHUNK, rows)), min(CHUNK, rows)))
    monkeypatch.setattr(moe, "NEAR_BYTES", ())


@pytest.mark.parametrize("held_pairs, rows", [
    (0, 0), (1, CHUNK), (CHUNK, CHUNK), (CHUNK + 1, 2 * CHUNK),
    (2 * CHUNK, 2 * CHUNK), (2 * CHUNK + 1, 3 * CHUNK),
    (3 * CHUNK, 3 * CHUNK), (3 * CHUNK + 1, 4 * CHUNK), (None, 4 * CHUNK)])
def test_passes_over_the_sorted_rows_follow_the_held_pairs(
        chunked, monkeypatch, held_pairs, rows):
    """Output and gradients (x, w_in, w_out, router_weight) with the
    passes cut to the chunks that hold the held pairs, and the per-token
    gathers reading the least of the 16 or 32 leading rows that holds
    them (the whole buffer beyond 32 held pairs), are the whole-buffer
    path's to the last bit (XLA:CPU; a chunk's rows are computed as the
    whole buffer's are), the rows never written holding NaN; the chunks
    taken are the fewest that hold the pairs; no pair is dropped."""
    layer, p, x = _routed(held_pairs)
    with paddle_tpu.autograd._swapped(layer, p):
        stats = jax.device_get(layer.routing_stats(x))
    pairs = PT * PK
    assert stats["pairs_routed"] == pairs and stats["pairs_dropped"] == 0
    assert stats["pairs_held"] == (pairs if held_pairs is None
                                   else held_pairs)
    assert stats["buffer_rows"] == rows
    got = _value_and_grads(layer, p, x)
    _whole_buffer(chunked, monkeypatch)
    # a layer of its own: `jax.checkpoint` keeps what it traced for one
    want = _value_and_grads(*_routed(held_pairs))
    assert not np.asarray(got[1][0]["router_bias"]).any()
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.isfinite(a).all()
        np.testing.assert_array_equal(a, b)
    if held_pairs:      # and the held experts do move the result
        assert np.asarray(got[1][0]["w_in"]).any()


@pytest.mark.parametrize("held, written", [
    (0, 0), (1, 16), (16, 16), (17, 32), (40, 40), (33, 40)])
def test_a_pass_writes_the_fewest_chunks_that_hold_the_rows(
        chunked, held, written):
    """`_leading_rows` over 40 rows in chunks of 16: whole chunks up to the
    held rows, the last chunk of a ragged length started early, nothing
    past them."""
    a = jnp.arange(40.0)
    out = jax.jit(lambda n: chunked._leading_rows(
        lambda a, b: a + b, n, a, 2 * a))(jnp.int32(held))
    np.testing.assert_array_equal(out[:written], 3 * a[:written])
    assert np.isnan(out[written:]).all()


def test_passes_follow_each_data_parallel_shard_s_own_held_pairs(
        chunked, monkeypatch):
    """Under `per_batch_shard` every shard sorts its own tokens' pairs and
    its passes follow its own held pairs (5 of 32 and 20 of 32 here: one
    chunk and two): result and gradients are the whole-buffer path's under
    the same sharding to the last bit, and the one-device path's to 1e-5
    (XLA:CPU multiplies 32 rows by another kernel than 64)."""
    import functools
    if jax.device_count() < 2:
        pytest.skip("needs two devices")
    # of a shard's 32 rows 16 are near: one shard gathers from those, one
    # from its whole buffer
    monkeypatch.setattr(chunked, "NEAR_BYTES", (CHUNK * PD * 4,))
    rows = [_routed(n, tokens=16, seed=s) for s, n in enumerate((5, 20))]
    p = rows[0][1]
    x = jnp.concatenate([r[2] for r in rows])             # [2, 16, PD]
    mesh = mesh_mod.build_mesh(dp=2, devices=jax.devices()[:2])

    def loss(layer, sharded, p, x):
        with paddle_tpu.autograd._swapped(layer, p):
            ids, weights = layer.route(x.reshape(-1, PD))
            batched = tuple(t.reshape(2, 16, -1) for t in (x, ids, weights))
            held = functools.partial(layer._held_experts, False)
            y = (mesh_mod.per_batch_shard(held, 2, batched,
                                          (p["w_in"], p["w_out"]))
                 if sharded else held(*batched, p["w_in"], p["w_out"]))
        return jnp.sum(y * jnp.cos(jnp.arange(y.size).reshape(y.shape)))

    def run(sharded):
        # a layer of its own: `jax.checkpoint` keeps what it traced for one
        layer = _routed(0, tokens=16)[0]
        with mesh_mod.mesh_scope(mesh if sharded else None):
            return jax.tree_util.tree_leaves(jax.jit(jax.value_and_grad(
                functools.partial(loss, layer, sharded), (0, 1)))(p, x))

    got = run(True)
    _whole_buffer(chunked, monkeypatch)
    for a, b, c in zip(got, run(True), run(False)):
        assert np.isfinite(a).all()
        np.testing.assert_array_equal(a, b)
        np.testing.assert_allclose(a, c, rtol=1e-5, atol=1e-5)
