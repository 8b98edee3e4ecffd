"""Operations and bytes the DeepSeek-V3-class algorithms need, from shapes
(`model_type: deepseek_v3`; the configuration's `model` holds one chip's
share: `held_experts` of `router_experts`).

A per-layer metric's file names a function here as "deepseek_v3:<function>".
These are the yardstick: a later PR cannot change them.  Recomputed work
never counts; causal attention counts the pairs it needs, s(s+1)/2 of the
square; the held experts count the pairs a uniform router sends them,
tokens × num_experts_per_tok × held / router_experts.
"""
from __future__ import annotations


def _sizes(model: dict):
    H, heads = model["hidden_size"], model["num_attention_heads"]
    qk = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    dense = min(model["first_k_dense_replace"], model["num_hidden_layers"])
    return H, heads, qk, model["v_head_dim"], dense, \
        model["num_hidden_layers"] - dense


def _expert_matrices(model: dict) -> float:
    """Elements of one routed expert's two matrices (gate|up, down)."""
    return 3.0 * model["hidden_size"] * model["moe_intermediate_size"]


def expected_pairs_per_token(model: dict) -> float:
    return model["num_experts_per_tok"] * model["held_experts"][1] \
        / model["router_experts"]


def train_flops_per_item(model: dict, mix: dict) -> float:
    """Model FLOPs of one training step per token, 3 x forward.  Forward, a
    layer: the attention's projections 2·(H·heads·qk + H·(rank + rope) +
    rank·heads·(nope + v) + heads·v·H); the causal core at the pairs it
    needs, (s + 1)/2 keys a query on average: (s + 1)·heads·(qk + v); the
    FFN: dense 6·H·I, or router 2·H·E + shared experts 6·H·n_shared·F + the
    held experts at the expected pairs a token × 6·H·F.  Once: the untied
    head 2·H·V (every position has logits; the embedding is a lookup)."""
    H, heads, qk, v, dense, expert = _sizes(model)
    rank, rope, nope = (model["kv_lora_rank"], model["qk_rope_head_dim"],
                        model["qk_nope_head_dim"])
    F, s = model["moe_intermediate_size"], mix["seq"]
    attn = 2.0 * (H * heads * qk + H * (rank + rope)
                  + rank * heads * (nope + v) + heads * v * H) \
        + (s + 1.0) * heads * (qk + v)
    dense_ffn = 6.0 * H * model["intermediate_size"]
    expert_ffn = 2.0 * H * model["router_experts"] \
        + 6.0 * H * model["n_shared_experts"] * F \
        + expected_pairs_per_token(model) * 2.0 * _expert_matrices(model)
    forward = dense * (attn + dense_ffn) + expert * (attn + expert_ffn) \
        + 2.0 * H * model["vocab_size"]
    return 3.0 * forward


def mla_core_train(model: dict, mix: dict) -> dict:
    """The attention core of every layer for one step on ONE chip, forward
    and backward, causal: s(s+1)/2 query-key pairs a head.  Operations:
    forward q·kᵀ (qk) and P·v (v); backward dV (v), dP (v), dQ (qk), dK (qk):
    2 · pairs · (3·qk + 3·v) a head.  Bytes, bf16, each once: q, k, dq, dk at
    qk; v, o, do, dv at v.  (Row statistics are left out.)"""
    _, heads, qk, v, dense, expert = _sizes(model)
    b, s, L = mix["batch_per_chip"], mix["seq"], dense + expert
    pairs = s * (s + 1) / 2.0
    return {"ops": 2.0 * b * heads * pairs * (3 * qk + 3 * v) * L,
            "bytes": 2.0 * b * heads * s * (4 * qk + 4 * v) * L}


def held_experts_train(model: dict, mix: dict) -> dict:
    """The held routed experts of every expert layer for one step on ONE
    chip at the expected pairs: two grouped products forward, four backward
    (both operands' gradients of each): 3 · 2 · pairs · (3·H·F).  Bytes,
    bf16: the held matrices read by the forward and by the backward's input
    gradients and their gradients written (3 passes over them); every pair's
    activations (the gathered input H, gate|up 2F, their product F, the
    output H) written once, read once by the backward, and their gradients
    once."""
    H, *_, expert = _sizes(model)
    F = model["moe_intermediate_size"]
    tokens = mix["batch_per_chip"] * mix["seq"]
    pairs = tokens * expected_pairs_per_token(model)
    matrices = model["held_experts"][1] * _expert_matrices(model)
    return {"ops": 3.0 * 2.0 * pairs * _expert_matrices(model) * expert,
            "bytes": 2.0 * (3.0 * matrices
                            + 3.0 * pairs * (2 * H + 3 * F)) * expert}
