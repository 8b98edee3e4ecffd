"""Operations and bytes the ERNIE/BERT-class algorithms need, from shapes.

A per-layer metric's file names a function here as "ernie:<function>"; a
later configuration family brings a module of its own beside this one.

These are the yardstick: a later PR cannot change them.  Recomputed work
never counts (flash attention's backward recomputes the scores; the count
holds the scores once).
"""
from __future__ import annotations

from benchmarks.harness.trafficgen import n_masked


def train_flops_per_item(model: dict, mix: dict) -> float:
    """Model FLOPs of one training step per token (copied from bench.py's
    arithmetic, the sound part of it): per layer the QKV + output
    projections 8H^2, the FFN 4HI, attention scores + values 4sH; the MLM
    head (transform 2H^2 + tied decoder 2HV) on the masked share only; the
    pooler + NSP once per sequence; training = 3 x forward."""
    H, I, L, V = (model["hidden_size"], model["intermediate_size"],
                  model["num_hidden_layers"], model["vocab_size"])
    s = mix["seq"]
    masked = n_masked(mix) / s
    forward = (L * (8 * H * H + 4 * H * I + 4 * s * H)
               + masked * (2 * H * H + 2 * H * V)
               + (2 * H * H + 4 * H) / s)
    return 3.0 * forward


def flash_attention_train(model: dict, mix: dict) -> dict:
    """Self-attention of every layer for one step on ONE chip, forward and
    backward.  Operations: forward QK^T and PV, 2 products of 2*b*s*s*H;
    backward dV, dP, dQ, dK, 4 products: 12*b*s^2*H per layer.  Bytes, bf16:
    forward reads q, k, v and writes o; backward reads q, k, v, o, do and
    writes dq, dk, dv: 12 tensors of b*s*H.  (Row statistics are s/H of
    that and left out.)"""
    b, s = mix["batch_per_chip"], mix["seq"]
    H, L = model["hidden_size"], model["num_hidden_layers"]
    return {"ops": 12.0 * b * s * s * H * L,
            "bytes": 12.0 * b * s * H * 2 * L}

