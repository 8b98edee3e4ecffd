"""Mixture-of-experts FFN with expert parallelism over the `ep` mesh axis.

The reference has no MoE (SURVEY.md §2.2: expert parallelism ABSENT — design
fresh).  TPU-native design is the GShard/Switch formulation: gating +
capacity-bounded dispatch expressed as dense einsums over one-hot dispatch/
combine tensors — static shapes, MXU-friendly, and when the expert dim of
`wi`/`wo` is sharded over `ep` (set via Parameter.sharding_axes, consumed by
parallel.sharding.infer_sharding) GSPMD lowers the dispatch einsums to
all-to-all over ICI automatically; no hand-written token routing.

`DroplessMoE` beside it is the expert layer as today's open models deploy
it (DeepSeek-V3 and kin): sigmoid scores with a selection bias, k of E, no
capacity and so no dropped token, gated experts, shared experts, and a layer
that is told which experts it holds — one chip's share of an
expert-parallel group computes its own experts' part of the result.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ...core import dtype as _dtype_mod
from ...ops.pallas import config as _pcfg
from ...ops.pallas import grouped_matmul as _gm
from ...utils import xprof as _xprof
from .. import functional as F
from .. import initializer as init
from ..layer.base import Layer, Parameter

__all__ = ["MoEFFN", "switch_gating", "top2_gating", "DroplessMoE", "SwiGLU",
           "sigmoid_topk_routing"]


def _one_hot(x, n, dtype=jnp.float32):
    return jax.nn.one_hot(x, n, dtype=dtype)


def switch_gating(gates, capacity: int):
    """Switch-Transformer top-1 gating.

    gates: [B, S, E] softmax outputs.  Returns (dispatch [B,S,E,C] one-hot,
    combine [B,S,E,C] weights, aux load-balancing loss)."""
    b, s, e = gates.shape
    idx1 = jnp.argmax(gates, axis=-1)                       # [B,S]
    mask1 = _one_hot(idx1, e)                               # [B,S,E]
    # position of each token in its expert's buffer (order = sequence order)
    pos1 = jnp.cumsum(mask1, axis=1) * mask1 - mask1        # [B,S,E]
    keep1 = mask1 * (pos1 < capacity)
    gate1 = jnp.sum(gates * keep1, axis=-1)                 # [B,S]
    # aux loss (Switch eq. 4): E * mean_e(frac_tokens_e * mean_gate_e)
    density = jnp.mean(mask1, axis=(0, 1))
    density_proxy = jnp.mean(gates, axis=(0, 1))
    aux = e * jnp.sum(density * density_proxy)
    pos_in_expert = _one_hot(jnp.sum(pos1, -1).astype(jnp.int32),
                             capacity)                      # [B,S,C]
    dispatch = keep1[..., None] * pos_in_expert[:, :, None, :]  # [B,S,E,C]
    combine = dispatch * gate1[..., None, None]
    return dispatch, combine, aux


def top2_gating(gates, capacity: int):
    """GShard top-2 gating with capacity overflow drop.

    gates: [B, S, E].  Returns (dispatch, combine, aux) like switch_gating;
    second-choice tokens queue behind first-choice traffic."""
    b, s, e = gates.shape
    idx1 = jnp.argmax(gates, axis=-1)
    mask1 = _one_hot(idx1, e)
    gates2 = gates * (1.0 - mask1)
    idx2 = jnp.argmax(gates2, axis=-1)
    mask2 = _one_hot(idx2, e)

    pos1 = jnp.cumsum(mask1, axis=1) * mask1 - mask1
    # second-choice tokens start after all first-choice tokens of that expert
    used1 = jnp.sum(mask1, axis=1, keepdims=True)           # [B,1,E]
    pos2 = (jnp.cumsum(mask2, axis=1) * mask2 - mask2) + used1 * mask2
    keep1 = mask1 * (pos1 < capacity)
    keep2 = mask2 * (pos2 < capacity)

    g1 = jnp.sum(gates * keep1, axis=-1)
    g2 = jnp.sum(gates * keep2, axis=-1)
    denom = jnp.maximum(g1 + g2, 1e-9)
    g1, g2 = g1 / denom, g2 / denom

    density = jnp.mean(mask1, axis=(0, 1))
    density_proxy = jnp.mean(gates, axis=(0, 1))
    aux = e * jnp.sum(density * density_proxy)

    p1 = _one_hot(jnp.sum(pos1, -1).astype(jnp.int32), capacity)
    p2 = _one_hot(jnp.sum(pos2, -1).astype(jnp.int32), capacity)
    d1 = keep1[..., None] * p1[:, :, None, :]
    d2 = keep2[..., None] * p2[:, :, None, :]
    dispatch = jnp.maximum(d1, d2)
    combine = d1 * g1[..., None, None] + d2 * g2[..., None, None]
    return dispatch, combine, aux


class MoEFFN(Layer):
    """Expert-parallel FFN block: y = combine · expert_ffn(dispatch · x).

    Weight layout: wi [E, D, F], wo [E, F, D] with the expert dim annotated
    for `ep` sharding (and the ff dim for `tp`, Megatron-style, so MoE and
    tensor parallelism compose)."""

    def __init__(self, d_model: int, d_ff: int, num_experts: int,
                 top_k: int = 2, capacity_factor: float = 1.25,
                 activation: str = "gelu", name=None):
        super().__init__()
        if top_k not in (1, 2):
            raise ValueError("top_k must be 1 (Switch) or 2 (GShard)")
        self.d_model, self.d_ff = d_model, d_ff
        self.num_experts = num_experts
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        self.activation = getattr(F, activation)
        dtype = _dtype_mod.get_default_dtype()
        xavier = init.XavierUniform()
        self.gate_weight = Parameter(
            xavier((d_model, num_experts), dtype), initializer=xavier)
        self.wi = Parameter(xavier((num_experts, d_model, d_ff), dtype),
                            initializer=xavier)
        self.wo = Parameter(xavier((num_experts, d_ff, d_model), dtype),
                            initializer=xavier)
        from ...parallel.mesh import EP_AXIS, TP_AXIS
        self.wi.sharding_axes = (EP_AXIS, None, TP_AXIS)
        self.wo.sharding_axes = (EP_AXIS, TP_AXIS, None)
        self.aux_loss = jnp.zeros(())  # last computed load-balance loss

    def capacity(self, seq_len: int) -> int:
        c = int(self.top_k * seq_len * self.capacity_factor /
                self.num_experts)
        return max(c, 1)

    def forward(self, x):
        """x: [B, S, D] -> [B, S, D].  In eager use the load-balancing aux
        loss is available as `self.aux_loss` afterwards; inside scans/jit use
        `forward_with_aux` to thread it functionally (a stored tracer must
        never escape its trace)."""
        y, _ = self.forward_with_aux(x)
        return y

    def forward_with_aux(self, x):
        b, s, dm = x.shape
        cap = self.capacity(s)
        logits = jnp.einsum("bsd,de->bse", x, self.gate_weight.value)
        gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        gating = switch_gating if self.top_k == 1 else top2_gating
        dispatch, combine, aux = gating(gates, cap)
        if not isinstance(aux, jax.core.Tracer):
            self.aux_loss = aux
        dispatch = dispatch.astype(x.dtype)
        combine = combine.astype(x.dtype)
        # route: [B,S,E,C] x [B,S,D] -> [E, B, C, D]
        expert_in = jnp.einsum("bsec,bsd->ebcd", dispatch, x)
        h = self.activation(jnp.einsum("ebcd,edf->ebcf", expert_in,
                                       self.wi.value))
        expert_out = jnp.einsum("ebcf,efd->ebcd", h, self.wo.value)
        return jnp.einsum("bsec,ebcd->bsd", combine, expert_out), aux


# ---------------------------------------------------------------------------
# the dropless layer
# ---------------------------------------------------------------------------
class SwiGLU(Layer):
    """Gated FFN without biases: W_down(silu(x·W_gate) ⊙ x·W_up), the gate
    and up projections held as one matrix [d_model, 2·d_ff] (gate first)."""

    def __init__(self, d_model: int, d_ff: int, weight_attr=None):
        super().__init__()
        from .common import Linear
        self.d_ff = d_ff
        self.gate_up = Linear(d_model, 2 * d_ff, weight_attr, bias_attr=False)
        self.down = Linear(d_ff, d_model, weight_attr, bias_attr=False)

    def forward(self, x):
        return _gated_down(self.gate_up(x), self.down.weight.value)


@jax.checkpoint
def _gated_down(gate_up, w_down):
    """silu(gate) ⊙ up, then the down projection.  The backward multiplies
    the halves again from the one product that is kept: the activation, its
    derivative's factors and the down projection's input are four more
    tensors of the FFN's width in every layer of a scanned stack."""
    gate, up = jnp.split(gate_up, 2, axis=-1)
    return F.linear(F.silu(gate) * up, w_down)


def sigmoid_topk_routing(x, router_weight, selection_bias, top_k: int,
                         scaling: float = 1.0, normalize: bool = True,
                         norm_eps: float = 1e-20):
    """DeepSeek-V3's router without groups (`n_group` = `topk_group` = 1):
    scores s = sigmoid(x·W_r) in float32; the `top_k` largest of s + b are
    selected (b takes no gradient); the weights are the selected s (without
    b), divided by their sum + `norm_eps` (DeepSeek-V3's published code adds
    1e-20, the LFM2 family 1e-6), times `scaling`.  x: [T, D] -> (expert ids
    [T, k] int32, weights [T, k] float32)."""
    logits = jnp.dot(x.astype(jnp.float32), router_weight.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    biased = scores + lax.stop_gradient(selection_bias.astype(jnp.float32))
    _, ids = lax.top_k(lax.stop_gradient(biased), top_k)
    weights = jnp.take_along_axis(scores, ids, axis=-1)
    if normalize:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + norm_eps)
    return ids.astype(jnp.int32), weights * scaling


# The pairs (token, slot) sorted by expert are a permutation `order` of
# t·top_k + j with inverse `inverse`, the held pairs first.  A grouped
# product writes the rows of its groups and leaves the rest of its result as
# it found them, forward and transposed alike, so rows that belong to no
# held pair are no result: nothing below lets one into a sum (a `select`,
# not a product with 0), which costs nothing — the select fuses into the sum
# over the slots — where zeroing the rows themselves is a pass over
# tokens × top_k rows each time.
#
# The buffers keep tokens × top_k rows whatever the router does (no pair is
# dropped, and the grouped products' row tiles stay where they are), but a
# pass reads and writes the rows of held pairs only, as the grouped products
# do: a pass over the sorted rows is a loop over chunks of `CHUNK_ROWS` rows
# whose trip count is read from the call's own held pairs
# (`_leading_rows`), and a gather per token reads the least of the buffer's
# leading `NEAR_BYTES` that holds every held pair (`_over_slots`).
CHUNK_ROWS = 2048
# of a buffer's leading rows, what XLA:TPU holds in VMEM beside a pass's own
# buffers, least first (a v5e's compiler places 112 MiB: a gather of 8,192
# rows from 96 MiB there took 0.05 ms, from any buffer in HBM 0.29; beside
# the layer's own [T, D] input it placed 64 MiB and not 96: chip, PR 33)
NEAR_BYTES = (64 * 2 ** 20, 96 * 2 ** 20)


def _buffer_rows(held, rows: int):
    """(chunks, rows a chunk) that cover the leading `held` of `rows`."""
    chunk = min(CHUNK_ROWS, rows)
    return (held + chunk - 1) // chunk, chunk


def _leading_rows(fn, held, *operands):
    """`fn` over the leading `held` rows of `operands` (arrays of one
    length), a chunk at a time: chunks [c, ·] in, a chunk [c, ·] (or a
    tuple of them) out, into buffers of the operands' length whose rows
    from `held` (rounded up to a whole chunk) on are never written.  The
    last chunk of a length that is no multiple of the chunk starts early
    and writes some rows twice."""
    rows = operands[0].shape[0]
    chunks, chunk = _buffer_rows(held, rows)
    out = jax.eval_shape(fn, *(jax.ShapeDtypeStruct(
        (chunk,) + a.shape[1:], a.dtype) for a in operands))

    def body(i, buffers):
        start = jnp.minimum(i * chunk, rows - chunk)
        parts = fn(*(lax.dynamic_slice_in_dim(a, start, chunk)
                     for a in operands))
        return jax.tree_util.tree_map(
            lambda b, p: lax.dynamic_update_slice_in_dim(b, p, start, 0),
            buffers, parts)

    return lax.fori_loop(0, chunks, body, jax.tree_util.tree_map(
        lambda o: lax.empty((rows,) + o.shape[1:], o.dtype), out))


def _over_slots(term, rows, inverse, held, top_k):
    """Σ_j term(j, the rows of every token's pair j): [T, D] in float32,
    the rows gathered slot by slot in float32.  (One gather reshaped to
    [T, top_k, D] would put top_k on the tiled sublanes: a padded copy of
    tokens × top_k rows.)  Only the `held` leading rows are any pair's
    result: the gathers read the least of `NEAR_BYTES` of leading rows
    that holds them all, a buffer that XLA:TPU keeps in VMEM, and the
    whole buffer where none does."""
    def over(rows, inverse):
        inverse = inverse.reshape(-1, top_k)
        return sum(term(j, rows[inverse[:, j]].astype(jnp.float32))
                   for j in range(top_k))

    row_bytes = rows.shape[1] * rows.dtype.itemsize
    near = [n for n in (b // row_bytes for b in NEAR_BYTES)
            if n < rows.shape[0]]
    if not near:
        return over(rows, inverse)
    return lax.switch(
        jnp.sum(held > jnp.asarray(near)),
        [lambda n=n: over(rows[:n], jnp.minimum(inverse, n - 1))
         for n in near] + [lambda: over(rows, inverse)])


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _dispatch(x, order, inverse, pair_held, held, top_k):
    """Row r of the sorted pairs is token order[r] // top_k's activation.
    The transpose of a gather with repeats is a scatter-add; pairs being a
    permutation, it is a gather through the inverse and a sum over the
    slots of the held pairs (`pair_held` [T, top_k])."""
    return x[order // top_k]


def _dispatch_fwd(x, order, inverse, pair_held, held, top_k):
    return x[order // top_k], (inverse, pair_held, held)


def _dispatch_bwd(top_k, res, g):
    inverse, pair_held, held = res
    dx = _over_slots(lambda j, slot: jnp.where(pair_held[:, j, None], slot, 0),
                     g, inverse, held, top_k)
    return dx.astype(g.dtype), None, None, None, None


def _swiglu(gate_up):
    gate, up = jnp.split(gate_up, 2, axis=-1)
    return F.silu(gate) * up


@jax.custom_vjp
def _gated(gate_up, held):
    """silu(gate) ⊙ up of the `held` leading rows of [rows, 2·d_expert]
    (gate first)."""
    return _leading_rows(_swiglu, held, gate_up)


def _gated_fwd(gate_up, held):
    return _gated(gate_up, held), (gate_up, held)


def _gated_bwd(res, g):
    gate_up, held = res
    return _leading_rows(lambda gate_up, g: jax.vjp(_swiglu, gate_up)[1](g)[0],
                         held, gate_up, g), None


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _combine(rows, pair_weight, order, inverse, held, top_k):
    """Token t's result: Σ_j pair_weight[t, j] · (the row of its pair j),
    over the pairs whose weight is not 0 (the held ones), summed in
    float32."""
    y = _over_slots(
        lambda j, slot: jnp.where(pair_weight[:, j, None] != 0,
                                  slot * pair_weight[:, j, None], 0),
        rows, inverse, held, top_k)
    return y.astype(rows.dtype)


def _combine_fwd(rows, pair_weight, order, inverse, held, top_k):
    return (_combine(rows, pair_weight, order, inverse, held, top_k),
            (rows, pair_weight, order, inverse, held))


def _combine_bwd(top_k, res, g):
    """Both gradients row by row of the sorted pairs: row r's is g's row of
    its token times its pair's weight, its weight's the product of the two
    rows summed in float32 (brought to [T, top_k] through the inverse)."""
    rows, pair_weight, order, inverse, held = res

    def back(order, rows):
        g_rows = g[order // top_k].astype(jnp.float32)
        weight = pair_weight.reshape(-1)[order][:, None]
        return ((g_rows * weight).astype(rows.dtype),
                jnp.sum(rows.astype(jnp.float32) * g_rows, axis=-1))

    d_rows, d_weight = _leading_rows(back, held, order, rows)
    return (d_rows, jnp.where(pair_weight != 0,
                              d_weight[inverse].reshape(pair_weight.shape), 0),
            None, None, None)


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)
_gated.defvjp(_gated_fwd, _gated_bwd)
_combine.defvjp(_combine_fwd, _combine_bwd)


class DroplessMoE(Layer):
    """FFN(x) = Σ_selected∩held w_i·E_i(x) + Shared(x), no token dropped.

    `held=(first, count)` says which routed experts this layer holds: the
    router keeps its width `n_routed_experts` and its `top_k`, the weights
    are normalised over all the selected, and the pairs whose expert lies
    elsewhere are another chip's work: they are left out of the sort and
    add nothing here (no exchange, no stand-in).  With
    `count == n_routed_experts` it is the whole layer.  The shared experts
    (one SwiGLU at n_shared_experts × d_expert) are computed by every
    holder alike; with `n_shared_experts = 0` the layer has none, holds no
    parameter for them and plants no `shared` scope.  `norm_eps` is what
    the router adds to the selected scores' sum (`sigmoid_topk_routing`).

    Token-expert pairs are sorted by expert (held first, in order), the
    held experts run as one grouped product each way over
    [held, d_model, 2·d_expert] and [held, d_expert, d_model] (the Pallas
    kernel of `ops/pallas/grouped_matmul.py` on the TPU, `lax.ragged_dot`
    elsewhere), and the weighted rows are summed back per token."""

    def __init__(self, d_model: int, d_expert: int, n_routed_experts: int,
                 top_k: int, *, held=None, n_shared_experts: int = 0,
                 routed_scaling_factor: float = 1.0,
                 norm_topk_prob: bool = True, norm_eps: float = 1e-20,
                 weight_attr=None):
        super().__init__()
        first, count = held if held is not None else (0, n_routed_experts)
        if not (0 <= first and count >= 1
                and first + count <= n_routed_experts):
            raise ValueError(f"held={held!r} is not a range of the "
                             f"{n_routed_experts} routed experts")
        if top_k > n_routed_experts:
            raise ValueError("top_k exceeds the number of routed experts")
        self.d_model, self.d_expert = d_model, d_expert
        self.n_routed_experts, self.top_k = n_routed_experts, top_k
        self.held = (first, count)
        self.routed_scaling_factor = routed_scaling_factor
        self.norm_topk_prob = norm_topk_prob
        self.norm_eps = norm_eps
        dtype = _dtype_mod.get_default_dtype()
        w_init = getattr(weight_attr, "initializer", None) or \
            init.XavierUniform()
        zeros = init.Constant(0.0)
        self.router_weight = Parameter(
            w_init((d_model, n_routed_experts), dtype), initializer=w_init)
        # e_score_correction_bias: moves the selection, takes no gradient
        self.router_bias = Parameter(zeros((n_routed_experts,), dtype),
                                     initializer=zeros)
        self.w_in = Parameter(w_init((count, d_model, 2 * d_expert), dtype),
                              initializer=w_init)
        self.w_out = Parameter(w_init((count, d_expert, d_model), dtype),
                               initializer=w_init)
        self.shared_mlp = SwiGLU(d_model, n_shared_experts * d_expert,
                                 weight_attr) if n_shared_experts else None

    def route(self, x):
        """x: [T, D] -> (ids [T, k], weights [T, k] float32).  The
        backward computes the scores again from x as it came: its float32
        copy is not kept."""
        return jax.checkpoint(functools.partial(
            sigmoid_topk_routing, top_k=self.top_k,
            scaling=self.routed_scaling_factor,
            normalize=self.norm_topk_prob, norm_eps=self.norm_eps))(
                x, self.router_weight.value, self.router_bias.value)

    def _pair_held(self, ids):
        first, count = self.held
        return (ids >= first) & (ids < first + count)

    def _plan(self, ids):
        """The sort: (order [T·k] of the pairs by held expert, the rest
        last; its inverse; group sizes [count])."""
        first, count = self.held
        local = jnp.where(self._pair_held(ids), ids - first,
                          count).reshape(-1)
        order = jnp.argsort(local, stable=True).astype(jnp.int32)
        inverse = jnp.argsort(order).astype(jnp.int32)
        sizes = jnp.sum(local[:, None] == jnp.arange(count)[None, :],
                        axis=0, dtype=jnp.int32)
        return order, inverse, sizes

    def forward(self, x):
        from ...parallel import mesh as _mesh

        lead, d = x.shape[:-1], x.shape[-1]
        tokens = x.reshape(-1, d)
        with jax.named_scope(_xprof.SCOPE_ROUTER):
            ids, weights = self.route(tokens)
        with jax.named_scope(_xprof.SCOPE_EXPERTS):
            # every data-parallel shard sorts and computes its own tokens'
            # pairs against the held experts (replicated over dp)
            rows = x.shape[0] if x.ndim == 3 else 1
            batched = tuple(t.reshape(rows, -1, t.shape[-1])
                            for t in (tokens, ids, weights))
            kernel = _pcfg.backend_is_tpu() and _gm.supported(
                tokens.shape[0] * self.top_k, d, self.d_expert)
            n = _mesh.batch_shards(rows) if kernel else 0
            if kernel and not n:
                _pcfg.record_fallback("grouped_matmul", "partial_manual_mesh")
            held = functools.partial(self._held_experts, bool(n))
            weights_ = (self.w_in.value, self.w_out.value)
            # the kernel runs once per data-parallel shard (a Mosaic call
            # has no partitioning rule); XLA's own products are GSPMD's
            y = (_mesh.per_batch_shard(held, n, batched, weights_) if n
                 else held(*batched, *weights_)).reshape(-1, d)
        if self.shared_mlp is not None:
            with jax.named_scope(_xprof.SCOPE_SHARED):
                y = y + self.shared_mlp(tokens)
        return y.reshape(*lead, d)

    @functools.partial(jax.checkpoint, static_argnums=(0, 1))
    def _held_experts(self, kernel, tokens, ids, weights, w_in, w_out):
        """These tokens' pairs sorted by held expert, through the held
        experts and back, per token ([b, s, ·] in and out).  Its rows are
        tokens × top_k of which the held are a share (an eighth on one chip
        of eight), and all of them would wait as residuals of every layer:
        the backward sorts, gathers and multiplies again instead (a third
        more of the grouped products, the smallest of the layer).  Every
        pass but the gather of the tokens' rows follows the held pairs
        (that one reads [T, D] from VMEM at the buffer's write rate, and a
        loop was no faster: chip, PR 33)."""
        shape = tokens.shape
        tokens = tokens.reshape(-1, shape[-1])
        ids, weights = (t.reshape(-1, self.top_k) for t in (ids, weights))
        pair_held = self._pair_held(ids)
        order, inverse, sizes = self._plan(ids)
        held = jnp.sum(sizes)
        dot = _gm.grouped_matmul if kernel else lax.ragged_dot
        # each pass under its own scope, planted round the call: a
        # `custom_vjp`'s backward rule is traced under its call's scopes
        with jax.named_scope(_xprof.SCOPE_DISPATCH):
            rows = _dispatch(tokens, order, inverse, pair_held, held,
                             self.top_k)
        with jax.named_scope(_xprof.SCOPE_PRODUCTS):
            rows = dot(rows, w_in, sizes)
        with jax.named_scope(_xprof.SCOPE_GATED):
            rows = _gated(rows, held)
        with jax.named_scope(_xprof.SCOPE_PRODUCTS):
            rows = dot(rows, w_out, sizes)
        with jax.named_scope(_xprof.SCOPE_COMBINE):
            return _combine(rows, jnp.where(pair_held, weights, 0.0), order,
                            inverse, held, self.top_k).reshape(shape)

    def routing_stats(self, x):
        """Counts of one call's routing, as int32/float32 scalars: pairs
        routed (tokens × top_k), pairs whose expert is held here, the held
        experts' largest load over their mean load, pairs dropped (held
        pairs that no group of the grouped product covers: 0 by
        construction), and the rows of the sorted buffers that a call which
        sorts these tokens together passes over (`_buffer_rows`)."""
        ids, _ = self.route(x.reshape(-1, x.shape[-1]))
        sizes = self._plan(ids)[2]
        held = jnp.sum(self._pair_held(ids), dtype=jnp.int32)
        mean = jnp.maximum(jnp.mean(sizes.astype(jnp.float32)), 1e-9)
        chunks, chunk = _buffer_rows(held, ids.size)
        return {"pairs_routed": jnp.int32(ids.size), "pairs_held": held,
                "held_load_max_over_mean": jnp.max(sizes) / mean,
                "pairs_dropped": held - jnp.sum(sizes),
                "buffer_rows": jnp.minimum(chunks * chunk, ids.size)}
