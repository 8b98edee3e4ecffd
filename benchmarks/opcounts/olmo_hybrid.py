"""Operations and bytes the Olmo-Hybrid-class algorithms need, from shapes
(`model_type: olmo_hybrid`; the configuration's `model` holds one pipeline
stage's layers and one slice of the vocabulary, every layer whole).

A per-layer metric's file names a function here as
"olmo_hybrid:<function>".  These are the yardstick: a later PR cannot
change them.  Recomputed work never counts, though the cell's step
recomputes every block; causal attention counts the pairs it needs,
s(s+1)/2 of the square; the gated delta rule counts the chunked
algorithm's products **at the configuration's chunk** (`linear_chunk_size`),
whatever implements it, and within a chunk the causal pairs each needs;
exponentials, the triangular solve, the convolution's taps, the norms, the
gates and every other elementwise pass are left out of the operations, as
everywhere here.
"""
from __future__ import annotations

LINEAR = "linear_attention"


def _layers(model: dict):
    """(delta-rule mixers, attention mixers)."""
    kinds = model["layer_types"]
    linear = sum(k == LINEAR for k in kinds)
    return linear, len(kinds) - linear


def _widths(model: dict):
    """(heads, q and k channels, v channels, the in-projection's width) of
    a Gated DeltaNet mixer: q | k | v | gate | a | b."""
    heads = model["linear_num_key_heads"]
    keys = heads * model["linear_key_head_dim"]
    values = heads * model["linear_value_head_dim"]
    return heads, keys, values, 2 * keys + 2 * values + 2 * heads


def _mixer_matrices(model: dict) -> float:
    """Elements of a delta-rule mixer's two matrices (in, out)."""
    _, _, values, proj = _widths(model)
    return float(model["hidden_size"]) * proj + values * model["hidden_size"]


def delta_flops_per_token(model: dict) -> float:
    """One layer's chunked delta rule, forward, a token: with C the chunk
    and dk, dv a head's sizes, per head — `Q Kᵀ` at the (C + 1)/2 pairs a
    position needs on average (the diagonal with them) and `K Kᵀ` at the
    (C − 1)/2 below it, 2·dk each; `T·(βK)` and `T·(βV)`, T lower
    triangular, (C + 1)/2 × 2·dk and × 2·dv; the masked, decayed scores
    times the written values, (C + 1)/2 × 2·dv; the carried state's three
    products `Q S`, `W S` and `K̃ᵀ Δ`, 2·dk·dv each."""
    C = model["linear_chunk_size"]
    dk, dv = model["linear_key_head_dim"], model["linear_value_head_dim"]
    per_head = (C + 1) / 2.0 * 2 * dk + (C - 1) / 2.0 * 2 * dk \
        + (C + 1) / 2.0 * 2 * dk + 2 * (C + 1) / 2.0 * 2 * dv \
        + 3 * 2.0 * dk * dv
    return model["linear_num_key_heads"] * per_head


def train_flops_per_item(model: dict, mix: dict) -> float:
    """Model FLOPs of one training step per token, 3 x forward.  Forward: a
    delta-rule mixer's two products 2·(H·proj + values·H) and its rule
    (`delta_flops_per_token`); an attention mixer's projections
    2·(H·(heads + 2·kv)·d + heads·d·H) and its causal core at the pairs it
    needs, (s + 1)/2 keys a query on average: (s + 1)·heads·2d; every
    layer's SwiGLU 6·H·I.  Once: the logits 2·H·V (every position has them;
    the embedding is a lookup)."""
    H, d = model["hidden_size"], model["head_dim"]
    heads, kv = model["num_attention_heads"], model["num_key_value_heads"]
    linear, attn = _layers(model)
    swiglu = 6.0 * H * model["intermediate_size"]
    linear_layer = 2.0 * _mixer_matrices(model) \
        + delta_flops_per_token(model) + swiglu
    attn_layer = 2.0 * (H * (heads + 2 * kv) * d + heads * d * H) \
        + (mix["seq"] + 1.0) * heads * 2 * d + swiglu
    forward = linear * linear_layer + attn * attn_layer \
        + 2.0 * H * model["vocab_size"]
    return 3.0 * forward


def delta_train(model: dict, mix: dict) -> dict:
    """The gated delta rule of every Gated DeltaNet mixer for one step on
    ONE chip, forward and backward: 3 × the forward's products.  Bytes,
    each once, and their gradients once each: a token's q, k, v and o
    (bf16, heads·dk, heads·dk, heads·dv, heads·dv) and g and β (float32, a
    head each)."""
    heads, keys, values, _ = _widths(model)
    tokens = mix["batch_per_chip"] * mix["seq"]
    L = _layers(model)[0]
    a_token = 2.0 * (2 * keys + 2 * values) + 4.0 * 2 * heads
    return {"ops": 3.0 * tokens * delta_flops_per_token(model) * L,
            "bytes": 2.0 * tokens * a_token * L}


def gdn_mixer_train(model: dict, mix: dict) -> dict:
    """Every Gated DeltaNet mixer whole for one step on ONE chip: its two
    products forward and their four backward (both operands' gradients of
    each), 6 · tokens · (H·proj + values·H) a mixer, plus the rule's
    (`delta_train`).  Bytes, bf16: the two matrices read by the forward and
    by the backward's input gradients and their gradients written (3 passes
    over them); a token's x (H), the projection (proj), the convolution's
    output (q | k | v), the rule's output (values) and the result (H) once
    each, and their gradients once each."""
    H = model["hidden_size"]
    _, keys, values, proj = _widths(model)
    tokens = mix["batch_per_chip"] * mix["seq"]
    L = _layers(model)[0]
    a_token = H + proj + (2 * keys + values) + values + H
    return {"ops": 6.0 * tokens * _mixer_matrices(model) * L
            + delta_train(model, mix)["ops"],
            "bytes": 2.0 * (3.0 * _mixer_matrices(model)
                            + 2.0 * tokens * a_token) * L}
