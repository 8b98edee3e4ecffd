"""Plain reference for DeepSeek-V3-class causal-LM training steps
(`model_type: deepseek_v3`: kanana-2-30b-a3b), one chip's share of an
expert-parallel deployment.

Straightforward `jax.numpy` in float32 with every matrix product at
`highest` precision: token embedding; pre-norm RMSNorm residual blocks
`h = x + Attn(RMSNorm(x))`, `y = h + FFN(RMSNorm(h))`; multi-head latent
attention (`q = x·W_q` split `q_nope | q_rope`; `x·W_kva` split `c | k_rope`,
one rotary key for all heads; `RMSNorm(c)·W_kvb` split `k_nope | v` per head;
rotary on pairs (2i, 2i+1) of `q_rope` and `k_rope`; scores
`q·[k_nope | k_rope]ᵀ / sqrt(192)`, causal, softmax, `P·v`, `W_o`); a leading
dense SwiGLU layer, then expert layers: `s = sigmoid(x·W_r)`, the
`num_experts_per_tok` largest of `s + b` selected (`b` takes no gradient),
weights the selected `s` over their sum times `routed_scaling_factor`,
`FFN(x) = Σ_{selected ∩ held} w_i·E_i(x) + Shared(x)`; final RMSNorm, untied
head, mean next-token cross-entropy over rows × (seq - 1) positions;
bias-corrected Adam.  No kernels, no cache, no sorting or grouping: every
held expert runs over every token and the router's weight (0 for a token
that did not select it) multiplies its result.

`model["held_experts"] = [first, count]` is the share: the router keeps its
published width `model["router_experts"]`, the weights are normalised over
all the selected experts, and what the experts held elsewhere would have
added is left out.  With `count == router_experts` it is the whole layer.

It imports nothing of the program under test and takes nothing the program
made.  Rows of a batch only meet in the loss's mean, so a step is computed in
blocks of rows whose gradients add up; inside a block, attention runs in
blocks of queries under `jax.checkpoint`, and each layer is checkpointed, so
that 4096 positions in float32 fit beside 16 bytes a parameter.  Adam's
moments and the first gradient wait on the host between the steps.

`precision` is the control's switch, as in `ernie_pretrain.py`: "float32" is
the reference; "bfloat16" rounds both operands of every matrix product to
bfloat16 (the yardstick); "fp8" computes every matrix product as an fp8
training recipe does.  `row_share` plants a fault: only the leading share of
each batch's rows is used and the mean is taken over them.
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


def param_spec(model: dict) -> dict:
    """{group: {leaf name: (shape, kind)}} in the layout the step builder
    uses: each group of uniform blocks stacked on a leading layer axis, the
    gate and up projections of a SwiGLU as one matrix (gate first), the held
    experts stacked [held, ...]."""
    H, V, heads = (model["hidden_size"], model["vocab_size"],
                   model["num_attention_heads"])
    qk = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    rank, F = model["kv_lora_rank"], model["moe_intermediate_size"]
    dense = min(model["first_k_dense_replace"], model["num_hidden_layers"])
    held = model["held_experts"][1]

    def block(L, ffn):
        out = {
            "input_norm.weight": ((L, H), SCALE),
            "post_norm.weight": ((L, H), SCALE),
            "self_attn.q_proj.weight": ((L, H, heads * qk), MATRIX),
            "self_attn.kv_a_proj.weight":
                ((L, H, rank + model["qk_rope_head_dim"]), MATRIX),
            "self_attn.kv_a_norm.weight": ((L, rank), SCALE),
            "self_attn.kv_b_proj.weight":
                ((L, rank, heads * (model["qk_nope_head_dim"]
                                    + model["v_head_dim"])), MATRIX),
            "self_attn.o_proj.weight":
                ((L, heads * model["v_head_dim"], H), MATRIX),
        }
        out.update({k: ((L,) + shape, kind)
                    for k, (shape, kind) in ffn.items()})
        return out

    def swiglu(prefix, width):
        return {f"{prefix}gate_up.weight": ((H, 2 * width), MATRIX),
                f"{prefix}down.weight": ((width, H), MATRIX)}

    spec = {"embed": {"word_embeddings.weight": ((V, H), MATRIX)},
            "head": {"final_norm.weight": ((H,), SCALE),
                     "lm_proj.weight": ((H, V), MATRIX)}}
    if dense:
        spec["dense_blocks"] = block(
            dense, swiglu("mlp.", model["intermediate_size"]))
    if model["num_hidden_layers"] > dense:
        spec["expert_blocks"] = block(model["num_hidden_layers"] - dense, {
            "mlp.router_weight": ((H, model["router_experts"]), MATRIX),
            "mlp.router_bias": ((model["router_experts"],), BIAS),
            "mlp.w_in": ((held, H, 2 * F), MATRIX),
            "mlp.w_out": ((held, F, H), MATRIX),
            **swiglu("mlp.shared_mlp.", model["n_shared_experts"] * F)})
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
    """x [..., s, d]: pairs (2i, 2i+1) rotated by position × theta^(-2i/d)."""
    s, d = x.shape[-2], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def _swiglu(x, gate_up, down, mm):
    gate, up = jnp.split(mm("bsh,hf->bsf", x, gate_up), 2, axis=-1)
    return mm("bsf,fh->bsh", jax.nn.silu(gate) * up, down)


def _attention(x, p, model, mm):
    b, s, _ = x.shape
    heads, nope, rope = (model["num_attention_heads"],
                         model["qk_nope_head_dim"], model["qk_rope_head_dim"])
    rank, theta = model["kv_lora_rank"], model["rope_theta"]
    q = mm("bsh,hk->bsk", x, p["self_attn.q_proj.weight"]) \
        .reshape(b, s, heads, nope + rope).transpose(0, 2, 1, 3)
    kva = mm("bsh,hk->bsk", x, p["self_attn.kv_a_proj.weight"])
    latent = _rms_norm(kva[..., :rank], p["self_attn.kv_a_norm.weight"],
                       model["rms_norm_eps"])
    kv = mm("bsr,rk->bsk", latent, p["self_attn.kv_b_proj.weight"]) \
        .reshape(b, s, heads, nope + model["v_head_dim"]).transpose(0, 2, 1, 3)
    k_rope = _rotary(kva[..., rank:], theta)[:, None]         # [b, 1, s, rope]
    q = jnp.concatenate([q[..., :nope], _rotary(q[..., nope:], theta)], -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (b, heads, s, rope))], -1)
    v = kv[..., nope:]

    block = math.gcd(s, QUERY_BLOCK)

    @jax.checkpoint
    def queries(q_blk, first):
        scores = mm("bnqd,bnkd->bnqk", q_blk, k) / math.sqrt(nope + rope)
        seen = (first + jnp.arange(block))[:, None] >= jnp.arange(s)[None, :]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return mm("bnqk,bnkd->bnqd", probs, v)

    q_blocks = q.reshape(b, heads, s // block, block, nope + rope)
    out = lax.map(lambda a: queries(*a), (jnp.moveaxis(q_blocks, 2, 0),
                                          jnp.arange(0, s, block)))
    out = jnp.moveaxis(out, 0, 2).reshape(b, heads, s, model["v_head_dim"])
    out = out.transpose(0, 2, 1, 3).reshape(b, s, -1)
    return mm("bsk,kh->bsh", out, p["self_attn.o_proj.weight"])


def routing_weights(x, router_weight, router_bias, model, mm):
    """[b, s, router_experts]: the weight of every expert for every token,
    0 where the token did not select it."""
    scores = jax.nn.sigmoid(mm("bsh,he->bse", x, router_weight))
    _, ids = lax.top_k(lax.stop_gradient(scores + router_bias),
                       model["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, ids, axis=-1)
    if model["norm_topk_prob"]:
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    picked = picked * model["routed_scaling_factor"]
    chosen = jax.nn.one_hot(ids, scores.shape[-1], dtype=picked.dtype)
    return jnp.einsum("bsk,bske->bse", picked, chosen)


def expert_layer(x, p, model, mm, prefix="mlp."):
    """Σ_{selected ∩ held} w_i·E_i(x) + Shared(x)."""
    first, count = model["held_experts"]
    weights = routing_weights(x, p[prefix + "router_weight"],
                              p[prefix + "router_bias"], model, mm)
    held = jnp.moveaxis(weights[..., first:first + count], -1, 0)

    def one(acc, e):
        w, w_in, w_out = e
        return acc + w[..., None] * _swiglu(x, w_in, w_out, mm), None

    y, _ = lax.scan(one, jnp.zeros_like(x),
                    (held, p[prefix + "w_in"], p[prefix + "w_out"]))
    return y + _swiglu(x, p[prefix + "shared_mlp.gate_up.weight"],
                       p[prefix + "shared_mlp.down.weight"], mm)


def _block(x, p, model, precision, expert):
    mm = functools.partial(_mm, precision=precision)
    eps = model["rms_norm_eps"]
    x = x + _attention(_rms_norm(x, p["input_norm.weight"], eps), p, model, mm)
    h = _rms_norm(x, p["post_norm.weight"], eps)
    if expert:
        return x + expert_layer(h, p, model, mm)
    return x + _swiglu(h, p["mlp.gate_up.weight"], p["mlp.down.weight"], mm)


def block_loss(params, rows, model, total_rows, precision):
    """These rows' part of the batch's loss: next-token cross-entropy summed
    over their first seq - 1 positions / (total_rows * (seq - 1))."""
    ids = rows["input_ids"]
    s = ids.shape[1]
    x = params["embed"]["word_embeddings.weight"][ids]
    for group, expert in (("dense_blocks", False), ("expert_blocks", True)):
        if group in params:
            layer = jax.checkpoint(functools.partial(
                _block, model=model, precision=precision, expert=expert))
            x, _ = lax.scan(lambda h, p: (layer(h, p), None), x, params[group])
    hd = params["head"]
    x = _rms_norm(x, hd["final_norm.weight"], model["rms_norm_eps"])
    logits = _mm("bsh,hv->bsv", x, hd["lm_proj.weight"], precision)[:, :-1]
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, ids[:, 1:, None], axis=-1)[..., 0]
    return jnp.sum(logz - picked) / (total_rows * (s - 1))


def adam_leaf(p, g, m, v, t, opt):
    """Bias-corrected Adam (Paddle's adam_op) of one leaf, float32."""
    b1, b2, eps, lr = opt["beta1"], opt["beta2"], opt["epsilon"], opt["lr"]
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * jnp.square(g)
    upd = lr * (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
    return p - upd, m, v


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
    rows_total = int(round(len(batches[0]["input_ids"]) * row_share))
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
        rows_total, precision)
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
            rows = {"input_ids": jax.device_put(
                np.asarray(batch["input_ids"][lo:lo + per_call]), by_row)}
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
