"""MoE / expert parallelism (new TPU capability — SURVEY.md §2.2 EP row)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

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
