"""Operations and bytes the Granite-4.0-H-class algorithms need, from shapes
(`model_type: granitemoehybrid`; the configuration's `model` holds one
pipeline stage's layers and one slice of the vocabulary, every layer whole).

A per-layer metric's file names a function here as
"granite_hybrid:<function>".  These are the yardstick: a later PR cannot
change them.  Recomputed work never counts, though the cell's step
recomputes every block; causal attention counts the pairs it needs,
s(s+1)/2 of the square; the state-space scan counts the chunked algorithm's
products **at the published chunk** (`mamba_chunk_size`), whatever
implements it, and within a chunk the causal pairs it needs, Q(Q+1)/2 of the
square; exponentials, the convolution's taps, the gates and every other
elementwise pass are left out of the operations, as everywhere here.
"""
from __future__ import annotations

MAMBA = "mamba"


def _layers(model: dict):
    """(Mamba-2 mixers, attention mixers)."""
    kinds = model["layer_types"]
    mamba = sum(k == MAMBA for k in kinds)
    return mamba, len(kinds) - mamba


def _widths(model: dict):
    """(d_inner, the in-projection's width) of a Mamba-2 mixer."""
    inner = model["mamba_n_heads"] * model["mamba_d_head"]
    state = model["mamba_n_groups"] * model["mamba_d_state"]
    return inner, 2 * inner + 2 * state + model["mamba_n_heads"]


def _mixer_matrices(model: dict) -> float:
    """Elements of a Mamba-2 mixer's two matrices (in, out)."""
    inner, proj = _widths(model)
    return float(model["hidden_size"]) * proj + inner * model["hidden_size"]


def scan_flops_per_token(model: dict) -> float:
    """One layer's chunked scan, forward, a token: with Q the chunk, N the
    state size and d_inner = heads × head size — `C·Bᵀ` (one group: once for
    all heads) at the (Q + 1)/2 pairs a position needs on average, 2·N each;
    the masked, decayed scores times x, (Q + 1)/2 pairs × 2·d_inner; the
    chunk's state `Bᵀ(decay ∘ dt ∘ x)`, 2·N·d_inner; its read-out
    `C · state`, 2·N·d_inner."""
    inner, _ = _widths(model)
    pairs = (model["mamba_chunk_size"] + 1) / 2.0
    N = model["mamba_n_groups"] * model["mamba_d_state"]
    return pairs * 2 * N + pairs * 2 * inner + 2.0 * N * inner \
        + 2.0 * N * inner


def train_flops_per_item(model: dict, mix: dict) -> float:
    """Model FLOPs of one training step per token, 3 x forward.  Forward: a
    Mamba-2 mixer's two products 2·(H·proj + d_inner·H) and its scan
    (`scan_flops_per_token`); an attention mixer's projections
    2·(H·(heads + 2·kv)·d + heads·d·H) and its causal core at the pairs it
    needs, (s + 1)/2 keys a query on average: (s + 1)·heads·2d; every
    layer's SwiGLU 6·H·I.  Once: the logits 2·H·V (every position has them;
    the embedding is a lookup)."""
    H, d = model["hidden_size"], model["head_dim"]
    heads, kv = model["num_attention_heads"], model["num_key_value_heads"]
    mamba, attn = _layers(model)
    swiglu = 6.0 * H * model["shared_intermediate_size"]
    mamba_layer = 2.0 * _mixer_matrices(model) + scan_flops_per_token(model) \
        + swiglu
    attn_layer = 2.0 * (H * (heads + 2 * kv) * d + heads * d * H) \
        + (mix["seq"] + 1.0) * heads * 2 * d + swiglu
    forward = mamba * mamba_layer + attn * attn_layer \
        + 2.0 * H * model["vocab_size"]
    return 3.0 * forward


def ssd_train(model: dict, mix: dict) -> dict:
    """The state-space scan of every Mamba-2 mixer for one step on ONE chip,
    forward and backward: 3 × the forward's products.  Bytes, bf16, each
    once: a token's x (d_inner), B and C (N each), dt (heads) and y
    (d_inner), and their gradients."""
    inner, _ = _widths(model)
    N = model["mamba_n_groups"] * model["mamba_d_state"]
    tokens = mix["batch_per_chip"] * mix["seq"]
    L = _layers(model)[0]
    return {"ops": 3.0 * tokens * scan_flops_per_token(model) * L,
            "bytes": 2.0 * 2 * tokens
            * (2 * inner + 2 * N + model["mamba_n_heads"]) * L}


def ssm_mixer_train(model: dict, mix: dict) -> dict:
    """Every Mamba-2 mixer whole for one step on ONE chip: its two products
    forward and their four backward (both operands' gradients of each),
    6 · tokens · (H·proj + d_inner·H) a mixer, plus the scan's
    (`ssd_train`).  Bytes, bf16: the two matrices read by the forward and by
    the backward's input gradients and their gradients written (3 passes
    over them); a token's x (H), the projection (proj), the convolution's
    output (the scan's input: d_inner + 2·N), the scan's output (d_inner)
    and the result (H) once each, and their gradients once each."""
    H = model["hidden_size"]
    inner, proj = _widths(model)
    N = model["mamba_n_groups"] * model["mamba_d_state"]
    tokens = mix["batch_per_chip"] * mix["seq"]
    L = _layers(model)[0]
    a_token = H + proj + (inner + 2 * N) + inner + H
    return {"ops": 6.0 * tokens * _mixer_matrices(model) * L
            + ssd_train(model, mix)["ops"],
            "bytes": 2.0 * (3.0 * _mixer_matrices(model)
                            + 2.0 * tokens * a_token) * L}
