"""Operations and bytes the LFM2-MoE-class algorithms need, from shapes
(`model_type: lfm2_moe`; the configuration's `model` holds one chip's
share: `held_experts` of `router_experts`).

A per-layer metric's file names a function here as "lfm2_moe:<function>".
These are the yardstick: a later PR cannot change them.  Recomputed work
never counts; causal attention counts the pairs it needs, s(s+1)/2 of the
square; the held experts count the pairs a uniform router sends them,
tokens × num_experts_per_tok × held / router_experts; the convolution's
taps and both gates (a handful of operations a channel) are left out of
the operations, as every elementwise pass is.
"""
from __future__ import annotations

CONV = "conv"


def _layers(model: dict):
    """(convolution mixers, attention mixers, dense FFNs, expert FFNs)."""
    kinds = model["layer_types"]
    conv = sum(k == CONV for k in kinds)
    dense = min(model["num_dense_layers"], len(kinds))
    return conv, len(kinds) - conv, dense, len(kinds) - dense


def _conv_matrices(model: dict) -> float:
    """Elements of a convolution mixer's two matrices (in, out)."""
    H = model["hidden_size"]
    return 3.0 * H * H + H * H


def expected_pairs_per_token(model: dict) -> float:
    return model["num_experts_per_tok"] * model["held_experts"][1] \
        / model["router_experts"]


def train_flops_per_item(model: dict, mix: dict) -> float:
    """Model FLOPs of one training step per token, 3 x forward.  Forward: a
    convolution mixer's two products 2·(H·3H + H·H); an attention mixer's
    projections 2·(H·(heads + 2·kv)·d + heads·d·H) and its causal core at
    the pairs it needs, (s + 1)/2 keys a query on average:
    (s + 1)·heads·2d; a dense FFN 6·H·I; an expert FFN's router 2·H·E and
    the held experts at the expected pairs a token × 6·H·F.  Once: the
    logits 2·H·V (every position has them; the embedding is a lookup)."""
    H, d = model["hidden_size"], model["head_dim"]
    heads, kv = model["num_attention_heads"], model["num_key_value_heads"]
    conv, attn, dense, expert = _layers(model)
    attention = 2.0 * (H * (heads + 2 * kv) * d + heads * d * H) \
        + (mix["seq"] + 1.0) * heads * 2 * d
    expert_ffn = 2.0 * H * model["router_experts"] \
        + expected_pairs_per_token(model) * 6.0 * H \
        * model["moe_intermediate_size"]
    forward = conv * 2.0 * _conv_matrices(model) + attn * attention \
        + dense * 6.0 * H * model["intermediate_size"] \
        + expert * expert_ffn + 2.0 * H * model["vocab_size"]
    return 3.0 * forward


def gqa_core_train(model: dict, mix: dict) -> dict:
    """The attention core of every attention layer for one step on ONE
    chip, forward and backward, causal: s(s+1)/2 query-key pairs a query
    head.  Operations: forward q·kᵀ and P·v; backward dV, dP, dQ, dK: six
    products of 2 · pairs · d a query head.  Bytes, bf16, each once: q, o,
    dq, do at the query heads; k, v, dk, dv at the key/value heads.  (Row
    statistics are left out.)"""
    d, heads = model["head_dim"], model["num_attention_heads"]
    b, s, L = mix["batch_per_chip"], mix["seq"], _layers(model)[1]
    pairs = s * (s + 1) / 2.0
    return {"ops": 2.0 * b * heads * pairs * 6 * d * L,
            "bytes": 2.0 * b * s * d
            * (4 * heads + 4 * model["num_key_value_heads"]) * L}


def conv_mixer_train(model: dict, mix: dict) -> dict:
    """Every convolution mixer for one step on ONE chip: its two products
    forward and their four backward (both operands' gradients of each),
    6 · tokens · (H·3H + H·H) a mixer.  Bytes, bf16: the two matrices read
    by the forward and by the backward's input gradients and their
    gradients written (3 passes over them); a token's x (H), the projection
    (3H), the gated convolution's result (H) and the output (H) once each,
    and their gradients once each."""
    H = model["hidden_size"]
    tokens = mix["batch_per_chip"] * mix["seq"]
    L = _layers(model)[0]
    return {"ops": 6.0 * tokens * _conv_matrices(model) * L,
            "bytes": 2.0 * (3.0 * _conv_matrices(model)
                            + 2.0 * tokens * 6 * H) * L}
