"""Plain reference for ERNIE/BERT-class MLM + NSP pretraining steps.

Straightforward `jax.numpy` in float32 with every matrix product at
`highest` precision: embeddings (word + position + sentence type, LayerNorm),
post-LN encoder blocks (multi-head softmax attention, exact-erf GELU FFN),
pooler + NSP head, MLM head on the masked positions with the decoder tied to
the word embeddings, mean cross-entropies added, bias-corrected Adam.  No
kernels, no cache, no batching tricks.  It imports nothing of the program
under test and takes nothing the program made: the benchmark hands it the
configuration's sizes, the batches and the weights, all made from the seed
by the benchmark's own generators.

Rows of a batch only meet in the loss's mean, so a step is computed in
blocks of rows whose gradients add up: that is what lets the real batch fit
next to float32 attention probabilities.  Each block is spread over the
devices it is given (rows sharded, weights replicated), nothing more.

`precision` is the control's switch (see PERF.md, "How correct is
decided"): "float32" is the reference; "bfloat16" rounds both operands of
every matrix product to bfloat16; "fp8" computes every matrix product as an
fp8 training recipe does (operands in e4m3, the backward's incoming gradient
in e5m2, per-tensor scales, float32 accumulation), the step below the
bfloat16 the configurations state.  `row_share` plants a fault: only the leading
share of each batch's rows is used and the mean is taken over them.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

MATRIX, BIAS, SCALE = "matrix", "bias", "scale"
_HI = lax.Precision.HIGHEST


def param_spec(model: dict) -> dict:
    """{group: {leaf name: (shape, kind)}} in the layout the step builders
    use: encoder blocks stacked on a leading layer axis."""
    H, I, L = (model["hidden_size"], model["intermediate_size"],
               model["num_hidden_layers"])
    V, P, T = (model["vocab_size"], model["max_position_embeddings"],
               model["type_vocab_size"])
    blocks = {
        "linear1.weight": ((L, H, I), MATRIX), "linear1.bias": ((L, I), BIAS),
        "linear2.weight": ((L, I, H), MATRIX), "linear2.bias": ((L, H), BIAS),
    }
    for n in ("norm1", "norm2"):
        blocks[f"{n}.weight"] = ((L, H), SCALE)
        blocks[f"{n}.bias"] = ((L, H), BIAS)
    for n in ("q_proj", "k_proj", "v_proj", "out_proj"):
        blocks[f"self_attn.{n}.weight"] = ((L, H, H), MATRIX)
        blocks[f"self_attn.{n}.bias"] = ((L, H), BIAS)
    return {
        "embed": {
            "word_embeddings.weight": ((V, H), MATRIX),
            "position_embeddings.weight": ((P, H), MATRIX),
            "token_type_embeddings.weight": ((T, H), MATRIX),
            "layer_norm.weight": ((H,), SCALE),
            "layer_norm.bias": ((H,), BIAS),
        },
        "blocks": blocks,
        "head": {
            "pooler.dense.weight": ((H, H), MATRIX),
            "pooler.dense.bias": ((H,), BIAS),
            "cls.seq_relationship.weight": ((H, 2), MATRIX),
            "cls.seq_relationship.bias": ((2,), BIAS),
            "cls.predictions.transform.weight": ((H, H), MATRIX),
            "cls.predictions.transform.bias": ((H,), BIAS),
            "cls.predictions.layer_norm.weight": ((H,), SCALE),
            "cls.predictions.layer_norm.bias": ((H,), BIAS),
            "cls.predictions.decoder_bias": ((V,), BIAS),
        },
    }


# ---------------------------------------------------------------------------
# the arithmetic
# ---------------------------------------------------------------------------
def _rounded(x, dtype, largest):
    """x as `dtype` holds it under a per-tensor scale (the tensor's largest
    magnitude on the type's largest), back in float32."""
    scale = largest / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mm_fp8(spec, a, b):
    """A matrix product as an fp8 training recipe computes it: operands
    rounded to e4m3 forward; backward, the incoming gradient rounded to e5m2
    against the same rounded operands; float32 accumulation throughout."""
    return _mm_fp8_fwd(spec, a, b)[0]


def _mm_fp8_fwd(spec, a, b):
    a = _rounded(a, jnp.float8_e4m3fn, 448.0)
    b = _rounded(b, jnp.float8_e4m3fn, 448.0)
    return jnp.einsum(spec, a, b, precision=_HI,
                      preferred_element_type=jnp.float32), (a, b)


def _mm_fp8_bwd(spec, operands, g):
    _, vjp = jax.vjp(lambda a, b: jnp.einsum(
        spec, a, b, precision=_HI, preferred_element_type=jnp.float32),
        *operands)
    return vjp(_rounded(g, jnp.float8_e5m2, 57344.0))


_mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)


def _mm(spec, a, b, precision):
    if precision == "fp8":
        return _mm_fp8(spec, a, b)
    if precision == "bfloat16":
        a, b = a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
    elif precision != "float32":
        raise ValueError(f"unknown precision {precision!r}")
    return jnp.einsum(spec, a, b, precision=_HI,
                      preferred_element_type=jnp.float32)


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def _gelu(x):
    return 0.5 * x * (1.0 + lax.erf(x / math.sqrt(2.0)))


def _block(x, p, heads, eps, precision):
    mm = functools.partial(_mm, precision=precision)
    b, s, hidden = x.shape
    d = hidden // heads

    def proj(n):
        y = mm("bsh,hk->bsk", x, p[f"self_attn.{n}.weight"]) \
            + p[f"self_attn.{n}.bias"]
        return y.reshape(b, s, heads, d)

    q, k, v = proj("q_proj"), proj("k_proj"), proj("v_proj")
    scores = mm("bqnd,bknd->bnqk", q, k) / math.sqrt(d)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = mm("bnqk,bknd->bqnd", probs, v).reshape(b, s, hidden)
    out = mm("bsh,hk->bsk", ctx, p["self_attn.out_proj.weight"]) \
        + p["self_attn.out_proj.bias"]
    x = _layer_norm(x + out, p["norm1.weight"], p["norm1.bias"], eps)
    h = _gelu(mm("bsh,hi->bsi", x, p["linear1.weight"]) + p["linear1.bias"])
    y = mm("bsi,ih->bsh", h, p["linear2.weight"]) + p["linear2.bias"]
    return _layer_norm(x + y, p["norm2.weight"], p["norm2.bias"], eps)


def _xent_sum(logits, labels):
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(logz - picked)


def block_loss(params, rows, model, total_rows, precision):
    """These rows' part of the batch's loss: MLM cross-entropy summed over
    their masked positions / (total_rows * n_mask) + NSP cross-entropy
    summed over them / total_rows."""
    mm = functools.partial(_mm, precision=precision)
    eps = model["layer_norm_epsilon"]
    e, hd = params["embed"], params["head"]
    ids, tt = rows["input_ids"], rows["token_type_ids"]
    s = ids.shape[1]
    x = (e["word_embeddings.weight"][ids]
         + e["position_embeddings.weight"][jnp.arange(s)][None]
         + e["token_type_embeddings.weight"][tt])
    x = _layer_norm(x, e["layer_norm.weight"], e["layer_norm.bias"], eps)

    layer = jax.checkpoint(functools.partial(
        _block, heads=model["num_attention_heads"], eps=eps,
        precision=precision))
    x, _ = lax.scan(lambda h, p: (layer(h, p), None), x, params["blocks"])

    pooled = jnp.tanh(mm("bh,hk->bk", x[:, 0], hd["pooler.dense.weight"])
                      + hd["pooler.dense.bias"])
    nsp = mm("bh,hk->bk", pooled, hd["cls.seq_relationship.weight"]) \
        + hd["cls.seq_relationship.bias"]
    picked = jnp.take_along_axis(
        x, rows["masked_positions"][..., None], axis=1)
    t = _gelu(mm("bmh,hk->bmk", picked,
                 hd["cls.predictions.transform.weight"])
              + hd["cls.predictions.transform.bias"])
    t = _layer_norm(t, hd["cls.predictions.layer_norm.weight"],
                    hd["cls.predictions.layer_norm.bias"], eps)
    logits = mm("bmh,vh->bmv", t, e["word_embeddings.weight"]) \
        + hd["cls.predictions.decoder_bias"]
    n_mask = rows["masked_positions"].shape[1]
    return (_xent_sum(logits, rows["mlm_labels"]) / (total_rows * n_mask)
            + _xent_sum(nsp, rows["nsp_labels"]) / total_rows)


def adam_update(params, grads, m, v, t, opt):
    """Bias-corrected Adam (Paddle's adam_op), float32 throughout."""
    b1, b2, eps, lr = opt["beta1"], opt["beta2"], opt["epsilon"], opt["lr"]

    def one(p, g, m_, v_):
        m_ = b1 * m_ + (1 - b1) * g
        v_ = b2 * v_ + (1 - b2) * jnp.square(g)
        upd = lr * (m_ / (1 - b1 ** t)) / (jnp.sqrt(v_ / (1 - b2 ** t)) + eps)
        return p - upd, m_, v_

    out = jax.tree_util.tree_map(one, params, grads, m, v)
    pick = lambda i: jax.tree_util.tree_map(  # noqa: E731
        lambda _, o: o[i], params, out)
    return pick(0), pick(1), pick(2)


# ---------------------------------------------------------------------------
# the steps
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _programs(model_json: str, opt_json: str, total_rows: int,
              precision: str):
    model, opt = json.loads(model_json), json.loads(opt_json)

    def accumulate(acc, params, rows):
        loss, grads = jax.value_and_grad(block_loss)(
            params, rows, model, total_rows, precision)
        return (acc[0] + loss,
                jax.tree_util.tree_map(jnp.add, acc[1], grads))

    def update(params, grads, m, v, t):
        return adam_update(params, grads, m, v, t, opt)

    return (jax.jit(accumulate, donate_argnums=(0,)),
            jax.jit(update, donate_argnums=(0, 2, 3)))


def run(model: dict, optimizer: dict, params, batches, *,
        precision: str = "float32", devices=None, rows_per_block: int = 8,
        row_share: float = 1.0) -> dict:
    """Follow `len(batches)` optimizer steps from `params` (a float32 tree
    in `param_spec`'s layout; not consumed).  Returns the loss of every
    step, the first step's gradient and the parameters' change over all the
    steps, as trees on the devices."""
    devices = list(devices or jax.devices()[:1])
    rows_total = int(round(len(batches[0]["input_ids"]) * row_share))
    if rows_total % (rows_per_block * len(devices)):
        # too few rows for a block on every device: one device, smaller blocks
        devices, rows_per_block = devices[:1], math.gcd(rows_total,
                                                        rows_per_block)
    mesh = Mesh(np.array(devices), ("rows",))
    whole = NamedSharding(mesh, PartitionSpec())
    by_row = NamedSharding(mesh, PartitionSpec("rows"))
    per_call = rows_per_block * len(devices)
    accumulate, update = _programs(
        json.dumps(model, sort_keys=True), json.dumps(optimizer, sort_keys=True),
        rows_total, precision)
    zeros = jax.jit(lambda t: jax.tree_util.tree_map(jnp.zeros_like, t),
                    out_shardings=whole)

    start = jax.device_put(params, whole)
    p = jax.tree_util.tree_map(jnp.copy, start)
    m, v = zeros(p), zeros(p)
    losses, first_grad = [], None
    for t, batch in enumerate(batches, start=1):
        acc = (jax.device_put(jnp.zeros((), jnp.float32), whole), zeros(p))
        for lo in range(0, rows_total, per_call):
            rows = {k: jax.device_put(np.asarray(a[lo:lo + per_call]), by_row)
                    for k, a in batch.items()}
            acc = accumulate(acc, p, rows)
        loss, grads = acc
        losses.append(float(loss))
        if t == 1:
            first_grad = grads
        p, m, v = update(p, grads, m, v, jnp.float32(t))
    change = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.subtract, a, b))(
        p, start)
    return {"losses": losses, "first_grad": first_grad,
            "param_change": change}
