"""Plain reference for LFM2-MoE-class causal-LM training steps
(`model_type: lfm2_moe`: LFM2-24B-A2B), one chip's share of an
expert-parallel deployment.

Straightforward `jax.numpy` in float32 with every matrix product at
`highest` precision.  `x` is `[b, s, H]`; `RMSNorm(x) = x / sqrt(mean(x²) +
norm_eps) ∘ w`.  Every layer is `h = x + Mixer(RMSNorm_op(x))`,
`y = h + FFN(RMSNorm_ffn(h))`; `layer_types[i]` names layer i's mixer:

* `"conv"`, the gated short convolution: `[B | C | u] = x·W_in` (split in
  that order), `z = B ∘ u`, `c_t = Σ_j k_j·z_{t−(L−1−j)}` per channel with
  `z` before position 0 nought (a depthwise causal `Conv1d(kernel L, padding
  L − 1)` cut to the first s outputs; `taps` holds `k` as `[L, H]`),
  `out = (C ∘ c)·W_out`; no activation, no bias;
* `"full_attention"`, grouped-query attention: `[q | k | v] = x·W_qkv`
  (`num_attention_heads`, `num_key_value_heads`, `num_key_value_heads` heads
  of `head_dim`); q and k each an RMSNorm over every head's channels (one
  weight of `head_dim` each); the rotary on all channels, halves form
  (`[a | b] → [a·cos − b·sin | b·cos + a·sin]`, angle `t·θ^(−2i/d)`); query
  head j attends key/value head `j // group`; scores `/ sqrt(head_dim)`,
  causal, softmax, `·v`, `W_o`.

The FFN of layers `< num_dense_layers` is a SwiGLU at `intermediate_size`;
of the rest, the expert layer: `s = sigmoid(x·W_r)`, the
`num_experts_per_tok` largest of `s + b` selected (`b` takes no gradient),
weights the selected `s` over (their sum + `router_norm_eps`) times
`routed_scaling_factor`, `FFN(x) = Σ_{selected ∩ held} w_i·E_i(x)`, each
`E_i` a SwiGLU at `moe_intermediate_size`; no shared expert.  After the
last layer one RMSNorm, the logits through the embedding matrix
(`tie_word_embeddings`), mean next-token cross-entropy over rows × (seq - 1)
positions; bias-corrected Adam.  No kernels, no cache, no sorting or
grouping: every held expert runs over every token and the router's weight
(0 for a token that did not select it) multiplies its result.

`model["held_experts"] = [first, count]` is the share: the router keeps its
published width `model["router_experts"]`, the weights are normalised over
all the selected experts, and what the experts held elsewhere would have
added is left out.  With `count == router_experts` it is the whole layer.

The parameters come grouped as the step builder groups the layers: one
group a maximal run of one kind of layer (mixer, FFN), named
`run<index>_<conv|attention>_<dense|expert>` and stacked on a leading axis
(`runs`).

It imports nothing of the program under test and takes nothing the program
made.  Rows of a batch only meet in the loss's mean, so a step is computed in
blocks of rows whose gradients add up; inside a block, attention runs in
blocks of queries under `jax.checkpoint`, and each layer is checkpointed, so
that 8192 positions in float32 fit beside 16 bytes a parameter.  Adam's
moments and the first gradient wait on the host between the steps.

`precision` is the control's switch, as in `deepseek_v3_lm.py`: "float32" is
the reference; "bfloat16" rounds both operands of every matrix product to
bfloat16 (the yardstick); "fp8" computes every matrix product as an fp8
training recipe does.  `row_share` plants a fault: **the share of the step's
tokens whose loss terms are kept, the mean taken over them** — whole leading
rows where the batch has that many (`rows × share ≥ 1`), else the leading
`rows × seq × share` positions of the first row (the model being causal,
what those positions read is unchanged).
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
QUERY_BLOCK = 512
CONV, ATTENTION = "conv", "full_attention"


def runs(model: dict) -> list:
    """[(group name, mixer, expert FFN?, layers)]: the layers in order as
    maximal runs of one kind."""
    out = []
    for i, mixer in enumerate(model["layer_types"]):
        kind = (mixer, i >= model["num_dense_layers"])
        if out and out[-1][:2] == kind:
            out[-1] = kind + (out[-1][2] + 1,)
        else:
            out.append(kind + (1,))
    return [(f"run{i:02d}_{'conv' if mixer == CONV else 'attention'}"
             f"_{'expert' if expert else 'dense'}", mixer, expert, n)
            for i, (mixer, expert, n) in enumerate(out)]


def param_spec(model: dict) -> dict:
    """{group: {leaf name: (shape, kind)}} in the layout the step builder
    uses: each run stacked on a leading layer axis, q | k | v as one matrix,
    the gate and up projections of a SwiGLU as one matrix (gate first), the
    held experts stacked [held, ...], the head's matrix the embedding's."""
    H, V = model["hidden_size"], model["vocab_size"]
    d = model["head_dim"]
    heads, kv = model["num_attention_heads"], model["num_key_value_heads"]
    F, held = model["moe_intermediate_size"], model["held_experts"][1]
    mixers = {
        CONV: {"operator.in_proj.weight": ((H, 3 * H), MATRIX),
               "operator.taps": ((model["conv_L_cache"], H), MATRIX),
               "operator.out_proj.weight": ((H, H), MATRIX)},
        ATTENTION: {"operator.qkv_proj.weight":
                    ((H, (heads + 2 * kv) * d), MATRIX),
                    "operator.q_norm.weight": ((d,), SCALE),
                    "operator.k_norm.weight": ((d,), SCALE),
                    "operator.out_proj.weight": ((heads * d, H), MATRIX)}}
    ffns = {
        False: {"feed_forward.gate_up.weight":
                ((H, 2 * model["intermediate_size"]), MATRIX),
                "feed_forward.down.weight":
                ((model["intermediate_size"], H), MATRIX)},
        True: {"feed_forward.router_weight":
               ((H, model["router_experts"]), MATRIX),
               "feed_forward.router_bias":
               ((model["router_experts"],), BIAS),
               "feed_forward.w_in": ((held, H, 2 * F), MATRIX),
               "feed_forward.w_out": ((held, F, H), MATRIX)}}
    if not model["tie_word_embeddings"]:
        raise ValueError("this reference ties the head to the embedding")
    spec = {"embed": {"word_embeddings.weight": ((V, H), MATRIX)},
            "head": {"final_norm.weight": ((H,), SCALE)}}
    for name, mixer, expert, n in runs(model):
        leaves = {"operator_norm.weight": ((H,), SCALE),
                  "ffn_norm.weight": ((H,), SCALE),
                  **mixers[mixer], **ffns[expert]}
        spec[name] = {k: ((n,) + shape, kind)
                      for k, (shape, kind) in leaves.items()}
    return spec


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


def _rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * w


def _rotary(x, theta):
    """x [..., s, d]: [a | b] -> [a·cos − b·sin | b·cos + a·sin], channel i
    of each half turned by position × theta^(-2i/d)."""
    s, d = x.shape[-2], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _swiglu(x, gate_up, down, mm):
    gate, up = jnp.split(mm("bsh,hf->bsf", x, gate_up), 2, axis=-1)
    return mm("bsf,fh->bsh", jax.nn.silu(gate) * up, down)


def short_conv(x, p, model, mm):
    s, taps = x.shape[1], p["operator.taps"]
    gate_b, gate_c, u = jnp.split(
        mm("bsh,hk->bsk", x, p["operator.in_proj.weight"]), 3, axis=-1)
    z = gate_b * u
    c = jnp.zeros_like(z)
    for j in range(taps.shape[0]):
        back = taps.shape[0] - 1 - j        # tap j weighs z_{t - back}
        shifted = jnp.concatenate(
            [jnp.zeros_like(z[:, :back]), z[:, :s - back]], axis=1)
        c = c + taps[j] * shifted
    return mm("bsh,hk->bsk", gate_c * c, p["operator.out_proj.weight"])


def attention(x, p, model, mm):
    b, s, _ = x.shape
    heads, kv, d = (model["num_attention_heads"],
                    model["num_key_value_heads"], model["head_dim"])
    group, eps, theta = heads // kv, model["norm_eps"], model["rope_theta"]
    qkv = mm("bsh,hk->bsk", x, p["operator.qkv_proj.weight"])
    q = qkv[..., :heads * d].reshape(b, s, kv, group, d)
    k = qkv[..., heads * d:(heads + kv) * d].reshape(b, s, kv, d)
    v = qkv[..., (heads + kv) * d:].reshape(b, s, kv, d)
    # [b, kv, group, s, d] and [b, kv, s, d]: query head j = (j // group,
    # j % group) reads key/value head j // group
    q = _rotary(_rms_norm(q, p["operator.q_norm.weight"], eps)
                .transpose(0, 2, 3, 1, 4), theta)
    k = _rotary(_rms_norm(k, p["operator.k_norm.weight"], eps)
                .transpose(0, 2, 1, 3), theta)
    v = v.transpose(0, 2, 1, 3)

    block = math.gcd(s, QUERY_BLOCK)

    @jax.checkpoint
    def queries(q_blk, first):
        scores = mm("bngqd,bnkd->bngqk", q_blk, k) / math.sqrt(d)
        seen = (first + jnp.arange(block))[:, None] >= jnp.arange(s)[None, :]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return mm("bngqk,bnkd->bngqd", probs, v)

    q_blocks = q.reshape(b, kv, group, s // block, block, d)
    out = lax.map(lambda a: queries(*a), (jnp.moveaxis(q_blocks, 3, 0),
                                          jnp.arange(0, s, block)))
    out = jnp.moveaxis(out, 0, 3).reshape(b, heads, s, d)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, heads * d)
    return mm("bsk,kh->bsh", out, p["operator.out_proj.weight"])


def routing_weights(x, router_weight, router_bias, model, mm):
    """[b, s, router_experts]: the weight of every expert for every token,
    0 where the token did not select it."""
    scores = jax.nn.sigmoid(mm("bsh,he->bse", x, router_weight))
    _, ids = lax.top_k(lax.stop_gradient(scores + router_bias),
                       model["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, ids, axis=-1)
    if model["norm_topk_prob"]:
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True)
                           + model["router_norm_eps"])
    picked = picked * model["routed_scaling_factor"]
    chosen = jax.nn.one_hot(ids, scores.shape[-1], dtype=picked.dtype)
    return jnp.einsum("bsk,bske->bse", picked, chosen)


def expert_layer(x, p, model, mm, prefix="feed_forward."):
    """Σ_{selected ∩ held} w_i·E_i(x)."""
    first, count = model["held_experts"]
    weights = routing_weights(x, p[prefix + "router_weight"],
                              p[prefix + "router_bias"], model, mm)
    held = jnp.moveaxis(weights[..., first:first + count], -1, 0)

    def one(acc, e):
        w, w_in, w_out = e
        return acc + w[..., None] * _swiglu(x, w_in, w_out, mm), None

    return lax.scan(one, jnp.zeros_like(x),
                    (held, p[prefix + "w_in"], p[prefix + "w_out"]))[0]


def _block(x, p, model, precision, mixer, expert):
    mm = functools.partial(_mm, precision=precision)
    eps = model["norm_eps"]
    mix = short_conv if mixer == CONV else attention
    x = x + mix(_rms_norm(x, p["operator_norm.weight"], eps), p, model, mm)
    h = _rms_norm(x, p["ffn_norm.weight"], eps)
    if expert:
        return x + expert_layer(h, p, model, mm)
    return x + _swiglu(h, p["feed_forward.gate_up.weight"],
                       p["feed_forward.down.weight"], mm)


def block_loss(params, rows, model, total_terms, precision):
    """These rows' part of the batch's loss: next-token cross-entropy summed
    over their first seq - 1 positions / total_terms."""
    ids = rows["input_ids"]
    x = params["embed"]["word_embeddings.weight"][ids]
    for name, mixer, expert, _ in runs(model):
        layer = jax.checkpoint(functools.partial(
            _block, model=model, precision=precision, mixer=mixer,
            expert=expert))
        x, _ = lax.scan(lambda h, p: (layer(h, p), None), x, params[name])
    hd = params["head"]
    x = _rms_norm(x, hd["final_norm.weight"], model["norm_eps"])
    logits = _mm("bsh,vh->bsv", x, params["embed"]["word_embeddings.weight"],
                 precision)[:, :-1]
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, ids[:, 1:, None], axis=-1)[..., 0]
    return jnp.sum(logz - picked) / total_terms


def adam_leaf(p, g, m, v, t, opt):
    """Bias-corrected Adam (Paddle's adam_op) of one leaf, float32."""
    b1, b2, eps, lr = opt["beta1"], opt["beta2"], opt["epsilon"], opt["lr"]
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * jnp.square(g)
    upd = lr * (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
    return p - upd, m, v


def kept(rows: int, seq: int, row_share: float):
    """(rows, positions) whose loss terms `row_share` keeps: whole leading
    rows where there are that many, else the first row's leading
    positions."""
    if rows * row_share >= 1:
        return int(round(rows * row_share)), seq
    return 1, int(round(rows * seq * row_share))


# ---------------------------------------------------------------------------
# the steps
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _programs(model_json: str, opt_json: str, total_terms: int,
              precision: str):
    model, opt = json.loads(model_json), json.loads(opt_json)

    def accumulate(acc, params, rows):
        loss, grads = jax.value_and_grad(block_loss)(
            params, rows, model, total_terms, precision)
        return (acc[0] + loss,
                jax.tree_util.tree_map(jnp.add, acc[1], grads))

    return (jax.jit(accumulate, donate_argnums=(0,)),
            jax.jit(functools.partial(adam_leaf, opt=opt),
                    donate_argnums=(0, 2, 3)))


def run(model: dict, optimizer: dict, params, batches, *,
        precision: str = "float32", devices=None, rows_per_block: int = 1,
        row_share: float = 1.0) -> dict:
    """Follow `len(batches)` optimizer steps from `params` (a float32 tree
    in `param_spec`'s layout; not consumed).  Returns the loss of every
    step, the first step's gradient and the parameters' change over all the
    steps, as trees on the devices.  On the devices while a step's gradient
    is computed: `params`, the stepped parameters and the gradient; Adam's
    moments are brought a leaf at a time for the update."""
    devices = list(devices or jax.devices()[:1])
    rows_total, positions = kept(*np.shape(batches[0]["input_ids"]),
                                 row_share)
    if rows_total % (rows_per_block * len(devices)):
        # too few rows for a block on every device: one device, smaller blocks
        devices, rows_per_block = devices[:1], math.gcd(rows_total,
                                                        rows_per_block)
    mesh = Mesh(np.array(devices), ("rows",))
    whole = NamedSharding(mesh, PartitionSpec())
    by_row = NamedSharding(mesh, PartitionSpec("rows"))
    per_call = rows_per_block * len(devices)
    accumulate, update_leaf = _programs(
        json.dumps(model, sort_keys=True), json.dumps(optimizer, sort_keys=True),
        rows_total * (positions - 1), precision)
    zeros = jax.jit(lambda t: jax.tree_util.tree_map(jnp.zeros_like, t),
                    out_shardings=whole)

    start = jax.device_put(params, whole)
    leaves, treedef = jax.tree_util.tree_flatten(
        jax.tree_util.tree_map(jnp.copy, start))
    moments = [None] * len(leaves)          # per leaf (m, v) on the host
    losses, first_grad = [], None
    for t, batch in enumerate(batches, start=1):
        p = jax.tree_util.tree_unflatten(treedef, leaves)
        acc = (jax.device_put(jnp.zeros((), jnp.float32), whole), zeros(p))
        for lo in range(0, rows_total, per_call):
            rows = {"input_ids": jax.device_put(np.asarray(
                batch["input_ids"][lo:lo + per_call, :positions]), by_row)}
            acc = accumulate(acc, p, rows)
        losses.append(float(acc[0]))
        grads = jax.tree_util.tree_leaves(acc[1])
        del p, acc
        if t == 1:
            first_grad = [np.asarray(g) for g in grads]
        for i, g in enumerate(grads):
            m, v = moments[i] or (np.zeros(g.shape, np.float32),) * 2
            leaves[i], m, v = update_leaf(
                leaves[i], g, jax.device_put(m, whole),
                jax.device_put(v, whole), jnp.float32(t))
            moments[i] = (np.asarray(m), np.asarray(v))
            grads[i] = None
    del moments
    change = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.subtract, a, b))(
        jax.tree_util.tree_unflatten(treedef, leaves), start)
    del leaves
    return {"losses": losses,
            "first_grad": jax.tree_util.tree_unflatten(
                treedef, [jax.device_put(g, whole) for g in first_grad]),
            "param_change": change}
