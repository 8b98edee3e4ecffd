"""Plain reference for Olmo-Hybrid-class causal-LM training steps
(`model_type: olmo_hybrid`: Olmo-Hybrid-7B), one pipeline stage's layers and
one slice of the vocabulary.

Straightforward `jax.numpy` in float32 with every matrix product at
`highest` precision.  `x` is `[b, s, H]`; `RMSNorm(x) = x / sqrt(mean(x²) +
rms_norm_eps) ∘ w`.  `h₀ = E[ids]`; every layer puts the norm after its
sublayer, inside the branch: `h ← h + RMSNorm_mixer(Mixer(h))`, `h ← h +
RMSNorm_mlp(MLP(h))`; the MLP a SwiGLU at `intermediate_size` (`[g | u] =
x·W_in`, `W_out(silu(g) ∘ u)`); `layer_types[i]` names layer i's mixer:

* `"linear_attention"`, the Gated DeltaNet: `[q | k | v | gate | a | b] =
  x·W_in` (widths heads·dk, heads·dk, heads·dv, heads·dv, heads, heads, in
  that order); `[q | k | v] ← silu(Σ_j taps_j · [q | k | v]_{t−(L−1−j)})`
  per channel, before position 0 nought and no bias (a depthwise causal
  `Conv1d(kernel L, padding L − 1)` cut to the first s outputs; `taps`
  holds the kernels as `[L, channels]`); q and k divided by `sqrt(Σ x² +
  1e-6)` a head; `β = 2σ(b)` (`linear_allow_neg_eigval`), `g =
  −exp(a_log) · softplus(a + dt_bias)` a head; **the recurrence itself,
  token by token**: `S_t = exp(g_t)(S_{t−1} − β_t k_t (k_tᵀ S_{t−1})) +
  β_t k_t v_tᵀ` a head (`S ∈ ℝ^{dk × dv}`, `S_0 = 0`), `o_t = S_tᵀ q_t /
  √dk`; then RMSNorm over each head's dv channels (one weight of dv) and
  only then the gate, `· silu(gate)`; `W_out`;
* `"full_attention"`: `[q | k | v] = x·W_qkv` (`num_attention_heads`,
  `num_key_value_heads`, `num_key_value_heads` heads of `head_dim`); q and
  k each through an RMSNorm over the whole projection, before the heads are
  split; **no positional term of any kind**; query head j attends key/value
  head `j // group`; scores `/ √head_dim`, causal, softmax, `·v`, `W_o`.

After the last layer one RMSNorm, the untied output matrix, mean next-token
cross-entropy over rows × (seq - 1) positions; bias-corrected Adam.  No
kernels, no cache, no chunking of the recurrence: it shares no algebra with
the chunked form the program runs, which is the point.

The parameters come grouped as the step builder groups the layers: one
group a layer, named `run<place>_<layer type>`, with a leading axis of one
(`runs`).

It imports nothing of the program under test and takes nothing the program
made.  Departures from "one forward pass, one backward pass", all of them to
fit 12 bytes a parameter of float32 copies and float32 activations of 4096
positions on one chip, none of them arithmetic: rows of a batch only meet in
the loss's mean, so a step is computed in blocks of rows whose gradients add
up; each layer is checkpointed; attention runs in blocks of queries under
`jax.checkpoint`; the recurrence runs in blocks of `SCAN_BLOCK` positions,
each block checkpointed, so that a state (2.2 MB a row at the published
sizes) is kept once a block and not once a position; Adam's moments wait on
the host between the steps, and the first gradient is returned on the
host's CPU device where JAX has one (3.7 GB that the harness would
otherwise hold on the chip beside the program's 11 GB of state while it
rebuilds the step for its text).

`precision` is the control's switch, as in `granite_hybrid_lm.py`:
"float32" is the reference; "bfloat16" rounds both operands of every matrix
product to bfloat16 (the yardstick); "fp8" computes every matrix product as
an fp8 training recipe does.  The recurrence's own products (a vector by
the state, an outer product) are float32 under every `precision`.
`row_share` plants a fault: **the share of the step's tokens whose loss
terms are kept, the mean taken over them** — whole leading rows where the
batch has that many (`rows × share ≥ 1`), else the leading `rows × seq ×
share` positions of the first row (the model being causal, what those
positions read is unchanged).
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
SCAN_BLOCK = 64
LINEAR, FULL = "linear_attention", "full_attention"


def runs(model: dict) -> list:
    """[(group name, mixer, layers)]: the layers in order, one group a layer
    (as the step builder groups them)."""
    return [(f"run{i:02d}_{mixer}", mixer, 1)
            for i, mixer in enumerate(model["layer_types"])]


def linear_widths(model: dict):
    """(heads, q and k channels, v channels, convolution channels) of a
    Gated DeltaNet mixer."""
    heads = model["linear_num_key_heads"]
    keys = heads * model["linear_key_head_dim"]
    values = heads * model["linear_value_head_dim"]
    return heads, keys, values, 2 * keys + values


def param_spec(model: dict) -> dict:
    """{group: {leaf name: (shape, kind)}} in the layout the step builder
    uses: each run stacked on a leading layer axis; a delta-rule mixer's
    six projections as one matrix (q | k | v | gate | a | b) and its
    convolution's kernels [taps, channels]; q | k | v of attention as one
    matrix; the gate and up projections of the SwiGLU as one matrix (gate
    first).  `a_log` and `dt_bias` are drawn as biases (the configuration's
    `departures` says what that makes of the decay)."""
    H, V, d = model["hidden_size"], model["vocab_size"], model["head_dim"]
    heads, kv = model["num_attention_heads"], model["num_key_value_heads"]
    I = model["intermediate_size"]
    n, keys, values, channels = linear_widths(model)
    if model["tie_word_embeddings"] or model["attention_bias"] \
            or model["rope_theta"] is not None \
            or not model["linear_allow_neg_eigval"] \
            or model["linear_num_value_heads"] != n:
        raise ValueError("this reference has an untied head, no biases, no "
                         "positions, beta up to 2 and as many value heads "
                         "as key heads")
    mixers = {
        LINEAR: {"mixer.in_proj.weight":
                 ((H, channels + values + 2 * n), MATRIX),
                 "mixer.taps": ((model["linear_conv_kernel_dim"], channels),
                                MATRIX),
                 "mixer.dt_bias": ((n,), BIAS),
                 "mixer.a_log": ((n,), BIAS),
                 "mixer.out_norm.weight":
                 ((model["linear_value_head_dim"],), SCALE),
                 "mixer.out_proj.weight": ((values, H), MATRIX)},
        FULL: {"mixer.qkv_proj.weight": ((H, (heads + 2 * kv) * d), MATRIX),
               "mixer.q_norm.weight": ((heads * d,), SCALE),
               "mixer.k_norm.weight": ((kv * d,), SCALE),
               "mixer.out_proj.weight": ((heads * d, H), MATRIX)}}
    spec = {"embed": {"word_embeddings.weight": ((V, H), MATRIX)},
            "head": {"final_norm.weight": ((H,), SCALE),
                     "lm_proj.weight": ((H, V), MATRIX)}}
    for name, mixer, layers in runs(model):
        leaves = {"mixer_norm.weight": ((H,), SCALE),
                  "mlp_norm.weight": ((H,), SCALE),
                  "mlp.gate_up.weight": ((H, 2 * I), MATRIX),
                  "mlp.down.weight": ((I, H), MATRIX), **mixers[mixer]}
        spec[name] = {k: ((layers,) + shape, kind)
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


def _swiglu(x, gate_up, down, mm):
    gate, up = jnp.split(mm("bsh,hf->bsf", x, gate_up), 2, axis=-1)
    return mm("bsf,fh->bsh", jax.nn.silu(gate) * up, down)


def causal_conv(x, taps):
    """x [b, s, channels]: Σ_j taps[j] · x_{t − (L − 1 − j)}."""
    s, out = x.shape[1], jnp.zeros_like(x)
    for j in range(taps.shape[0]):
        back = taps.shape[0] - 1 - j        # tap j weighs x_{t - back}
        shifted = jnp.concatenate(
            [jnp.zeros_like(x[:, :back]), x[:, :s - back]], axis=1)
        out = out + taps[j] * shifted
    return out


def recurrence(q, k, v, g, beta):
    """The gated delta rule, one position at a time.  q and k [b, s, h, dk],
    v [b, s, h, dv], g and beta [b, s, h] -> o [b, s, h, dv]:
    S_t = exp(g_t)(S_{t−1} − β_t k_t (k_tᵀ S_{t−1})) + β_t k_t v_tᵀ,
    o_t = S_tᵀ q_t / √dk."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]

    def position(S, at):
        q_t, k_t, v_t, g_t, b_t = at
        read = jnp.einsum("bhk,bhkv->bhv", k_t, S, precision=_HI)
        S = jnp.exp(g_t)[..., None, None] * (
            S - (b_t[..., None] * k_t)[..., None] * read[..., None, :]) \
            + (b_t[..., None] * k_t)[..., None] * v_t[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t, precision=_HI) \
            / math.sqrt(dk)

    @jax.checkpoint
    def block(S, at):
        return lax.scan(position, S, at)

    size = math.gcd(s, SCAN_BLOCK)
    along = [jnp.moveaxis(t, 1, 0).reshape(s // size, size, *t.shape[:1],
                                           *t.shape[2:])
             for t in (q, k, v, g, beta)]
    _, o = lax.scan(block, jnp.zeros((b, h, dk, dv), q.dtype), tuple(along))
    return jnp.moveaxis(o.reshape(s, b, h, dv), 0, 1)


def _l2(x):
    return x * lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                         + 1e-6)


def delta_net(x, p, model, mm):
    b, s, _ = x.shape
    n, keys, values, channels = linear_widths(model)
    dk, dv = model["linear_key_head_dim"], model["linear_value_head_dim"]
    proj = mm("bsh,hk->bsk", x, p["mixer.in_proj.weight"])
    qkv = jax.nn.silu(causal_conv(proj[..., :channels], p["mixer.taps"]))
    gate = proj[..., channels:channels + values]
    a = proj[..., channels + values:channels + values + n]
    w = proj[..., channels + values + n:]
    q = _l2(qkv[..., :keys].reshape(b, s, n, dk))
    k = _l2(qkv[..., keys:2 * keys].reshape(b, s, n, dk))
    v = qkv[..., 2 * keys:].reshape(b, s, n, dv)
    beta = 2.0 * jax.nn.sigmoid(w)
    g = -jnp.exp(p["mixer.a_log"]) * jax.nn.softplus(a + p["mixer.dt_bias"])
    o = _rms_norm(recurrence(q, k, v, g, beta), p["mixer.out_norm.weight"],
                  model["rms_norm_eps"])
    o = o * jax.nn.silu(gate.reshape(b, s, n, dv))
    return mm("bsk,kh->bsh", o.reshape(b, s, values),
              p["mixer.out_proj.weight"])


def attention(x, p, model, mm):
    b, s, _ = x.shape
    heads, kv, d = (model["num_attention_heads"],
                    model["num_key_value_heads"], model["head_dim"])
    group, eps = heads // kv, model["rms_norm_eps"]
    qkv = mm("bsh,hk->bsk", x, p["mixer.qkv_proj.weight"])
    q = _rms_norm(qkv[..., :heads * d], p["mixer.q_norm.weight"], eps)
    k = _rms_norm(qkv[..., heads * d:(heads + kv) * d],
                  p["mixer.k_norm.weight"], eps)
    # [b, kv, group, s, d] and [b, kv, s, d]: query head j = (j // group,
    # j % group) reads key/value head j // group
    q = q.reshape(b, s, kv, group, d).transpose(0, 2, 3, 1, 4)
    k = k.reshape(b, s, kv, d).transpose(0, 2, 1, 3)
    v = qkv[..., (heads + kv) * d:].reshape(b, s, kv, d).transpose(0, 2, 1, 3)

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
    return mm("bsk,kh->bsh", out, p["mixer.out_proj.weight"])


def _block(x, p, model, precision, mixer):
    mm = functools.partial(_mm, precision=precision)
    eps = model["rms_norm_eps"]
    mix = delta_net if mixer == LINEAR else attention
    x = x + _rms_norm(mix(x, p, model, mm), p["mixer_norm.weight"], eps)
    return x + _rms_norm(_swiglu(x, p["mlp.gate_up.weight"],
                                 p["mlp.down.weight"], mm),
                         p["mlp_norm.weight"], eps)


def block_loss(params, rows, model, total_terms, precision):
    """These rows' part of the batch's loss: next-token cross-entropy summed
    over their first seq - 1 positions / total_terms."""
    ids = rows["input_ids"]
    x = params["embed"]["word_embeddings.weight"][ids]
    for name, mixer, _ in runs(model):
        layer = jax.checkpoint(functools.partial(
            _block, model=model, precision=precision, mixer=mixer))
        x, _ = lax.scan(lambda h, p: (layer(h, p), None), x, params[name])
    hd = params["head"]
    x = _rms_norm(x, hd["final_norm.weight"], model["rms_norm_eps"])
    logits = _mm("bsh,hv->bsv", x, hd["lm_proj.weight"], precision)[:, :-1]
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

    def first(params, rows):
        return jax.value_and_grad(block_loss)(
            params, rows, model, total_terms, precision)

    def accumulate(acc, params, rows):
        loss, grads = first(params, rows)
        return (acc[0] + loss,
                jax.tree_util.tree_map(jnp.add, acc[1], grads))

    return (jax.jit(first), jax.jit(accumulate, donate_argnums=(0,)),
            jax.jit(functools.partial(adam_leaf, opt=opt),
                    donate_argnums=(0, 2, 3)))


def _host():
    """The host's CPU device, or None where this process sees none."""
    try:
        return jax.devices("cpu")[0]
    except RuntimeError:
        return None


def run(model: dict, optimizer: dict, params, batches, *,
        precision: str = "float32", devices=None, rows_per_block: int = 1,
        row_share: float = 1.0) -> dict:
    """Follow `len(batches)` optimizer steps from `params` (a float32 tree
    in `param_spec`'s layout; not consumed).  Returns the loss of every
    step, the first step's gradient (a tree on the host's CPU device, on
    `devices` where there is none) and the parameters' change over all the
    steps (a tree on `devices`).  On the devices while a step's gradient is
    computed: `params`, from the second step on the stepped parameters, and
    the gradient (one more of it while a second block of rows adds to it);
    Adam's moments are brought a leaf at a time for the update."""
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
    first, accumulate, update_leaf = _programs(
        json.dumps(model, sort_keys=True), json.dumps(optimizer, sort_keys=True),
        rows_total * (positions - 1), precision)

    start = jax.device_put(params, whole)
    leaves, treedef = jax.tree_util.tree_flatten(start)
    moments = [None] * len(leaves)          # per leaf (m, v) on the host
    losses, first_grad = [], None
    for t, batch in enumerate(batches, start=1):
        p = jax.tree_util.tree_unflatten(treedef, leaves)
        acc = None
        for lo in range(0, rows_total, per_call):
            rows = {"input_ids": jax.device_put(np.asarray(
                batch["input_ids"][lo:lo + per_call, :positions]), by_row)}
            acc = first(p, rows) if acc is None else accumulate(acc, p, rows)
        losses.append(float(acc[0]))
        grads = jax.tree_util.tree_leaves(acc[1])
        del p, acc
        if t == 1:
            # straight to the host's CPU device, one host copy and not two
            # (3.7 GB each at the cell's size, twice a run with the
            # yardstick's step: the host has 40 GiB)
            first_grad = [jax.device_put(g, _host() or whole) for g in grads]
            # `params` is not consumed: the first update writes a copy
            leaves = list(leaves)
        for i, g in enumerate(grads):
            m, v = moments[i] or (np.zeros(g.shape, np.float32),) * 2
            leaves[i], m, v = update_leaf(
                jnp.copy(leaves[i]) if t == 1 else leaves[i], g,
                jax.device_put(m, whole), jax.device_put(v, whole),
                jnp.float32(t))
            moments[i] = (np.asarray(m), np.asarray(v))
            grads[i] = None
    del moments
    change = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.subtract, a, b))(
        jax.tree_util.tree_unflatten(treedef, leaves), start)
    del leaves
    return {"losses": losses,
            "first_grad": jax.tree_util.tree_unflatten(treedef, first_grad),
            "param_change": change}
