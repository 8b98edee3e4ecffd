"""Packed-layout Pallas flash attention: q/k/v in (batch, seq, heads*dim).

The standard kernel (flash_attention.py) consumes (batch*heads, seq, dim),
which forces the model to materialize (b, s, h, d) -> (b, h, s, d)
transposes around every attention call: a layout copy of q, k, v and the
output forward, and of their gradients backward, in every layer.  This
variant reads the projection output LAYOUT DIRECTLY: blocks are
(1, block, 128) slices of the (b, s, h*d) array covering 128 lanes of
heads (Mosaic requires 128-divisible lane blocks): a PAIR of 64-wide heads
(BERT/ERNIE family) or ONE 128-wide head (LLaMA-class models).  No
transpose of the operands ever exists in the program, and the backward
runs no arithmetic outside its two kernels (see _backward).

**The tile.**  All three kernels hold a score tile as the standard ones
do, `(block_k, block_q)`: keys along sublanes, queries along lanes
(Sᵀ = K·Qᵀ, dPᵀ = V·dOᵀ by `_dot_nt`, no operand transposed for them).
What a query row owns — running max and sum, logsumexp, delta — is a
`(1, block_q)` row as it is stored, `(b, groups, heads_per_group, seq)`,
and broadcasts down sublanes as it lies; a reduction over the keys is an
elementwise pass over the tile's sublane groups.  The accumulators follow
the tile: `flash_packed_fwd` sums Oᵀ `(d, block_q)` += Vᵀ·Pᵀ and
`flash_packed_dq` dQᵀ += Kᵀ·dSᵀ, with the `(block_k, 128)` V or K block
turned once a tile for every head of the group (a head is then a slice of
sublanes) and the group's heads joined along sublanes, turned once a grid
cell and stored as one unmasked 128-lane block; `flash_packed_dkdv`'s
dV += Pᵀ·dO and dK += dSᵀ·Q consume the tile as it is.  The scores' scale
lives on the resident block (`_scaled`), the factor of dS on the
accumulator at its store.

**The heads of a group share one loop body** (`_walk`): a tile of every
head in one basic block, so that one head's exponentials run beside the
other's products and a grid cell pays one loop's fill and drain, or none
— a trip count known at trace time (a non-causal call) that fits one
iteration of `_for_tiles` is unrolled.  On one v5e at (64, 12, 512, 64)
with a padding bias (cell 1's call; PERF.md §6, PR 35) forward / dkdv / dq
took 1.166 / 1.407 / 0.996 ms a call with queries along sublanes and a
loop a head, 0.783 / 1.163 / 0.899 as they stand.

Numerics, bias handling and the matmul dtype policy are those of
flash_attention.py, whose helpers the kernels share; causal masking is
supported the same way.  Dropout draws tile (qi, kv_idx) of GLOBAL head
`group * heads_per_group + head` through `_keep_scale`, at the one shape
all three kernels hold it, so forward, dkdv and dq replay one mask
(tests_tpu/test_packed_attention_tpu.py holds them to it on the chip).
Non-pair-divisible head counts fall back to the standard kernel at the
dispatch layer (ops/attention.py).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from paddle_tpu.ops.pallas import config as _cfg

from .flash_attention import (
    DEFAULT_BLOCK_K,
    DEFAULT_BLOCK_Q,
    NEG_INF,
    _TILES_PER_ITERATION,
    _dot,
    _dot_nt,
    _for_tiles,
    _keep_scale,
    _kv_end,
    _normalize_bias_seed,
    _scaled,
    _smem,
    _under_diagonal,
)


def _rows(idx, block):
    """Rows `[idx * block, (idx + 1) * block)` of a ref: a traced index is
    declared aligned, a Python int (an unrolled walk) is a static slice."""
    if isinstance(idx, int):
        return pl.dslice(idx * block, block)
    return pl.dslice(pl.multiple_of(idx * block, block), block)


def _walk(tile, start, stop, carry):
    """`carry = tile(i, carry)` for i in [start, stop).  Bounds known at
    trace time (a non-causal call) that fit one iteration of `_for_tiles`
    run with no loop at all — the ERNIE cells have ONE tile a grid cell, and
    at two the unrolled walk took a forward call 0.347 -> 0.302 ms
    ((8, 8, 1024, 128), PERF.md §6, PR 35); everything else is `_for_tiles`."""
    static = isinstance(start, int) and isinstance(stop, int)
    if static and stop - start <= _TILES_PER_ITERATION:
        for i in range(start, stop):
            carry = tile(i, carry)
        return carry
    return _for_tiles(tile, start, stop, carry)


def _head_lanes(head_dim):
    """The lane slice of each head of a 128-lane group."""
    return [slice(lo, lo + head_dim) for lo in range(0, 128, head_dim)]


def _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref, *,
                sm_scale, causal, dropout_rate, block_q, block_k, seq_len,
                head_dim):
    """One q-block of one 128-lane group: per head the online softmax over
    `(block_k, block_q)` tiles, Oᵀ `(head_dim, block_q)` accumulated; every
    head's tile of a k-block in one body."""
    group = pl.program_id(0)
    qi = pl.program_id(1)
    heads = _head_lanes(head_dim)
    q = _scaled(q_ref[0], sm_scale)     # (block_q, 128), the scores' scale on it

    def tile(kv_idx, carry):
        rows = _rows(kv_idx, block_k)
        k = k_ref[0, rows, :]                             # (block_k, 128)
        vt = v_ref[0, rows, :].T        # (128, block_k): a head is sublanes
        bias = bias_ref[0, 0, rows][:, None]
        if causal:
            under = _under_diagonal(qi, kv_idx, block_q, block_k)
        out = []
        for head, (lanes, (acc, m_prev, l_prev)) in enumerate(zip(heads,
                                                                  carry)):
            st = _dot_nt(k[:, lanes], q[:, lanes]) + bias  # (block_k, block_q)
            if causal:
                st = jnp.where(under, st, NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(st, axis=0, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            pt = jnp.exp(st - m_new)
            l_new = l_prev * alpha + jnp.sum(pt, axis=0, keepdims=True)
            if dropout_rate > 0.0:
                pt = pt * _keep_scale(seed_ref[0], group * len(heads) + head,
                                      qi, kv_idx, block_q, block_k,
                                      dropout_rate)
            acc = acc * alpha + _dot(vt[lanes], pt.astype(vt.dtype))
            out.append((acc, m_new, l_new))
        return tuple(out)

    state = _walk(tile, 0, _kv_end(qi, block_q, block_k, seq_len, causal), (
        (jnp.zeros((head_dim, block_q), jnp.float32),
         jnp.full((1, block_q), NEG_INF, jnp.float32),
         jnp.zeros((1, block_q), jnp.float32)),) * len(heads))
    l_safe = [jnp.maximum(l, 1e-30) for _, _, l in state]
    # the heads' Oᵀ joined along sublanes, turned once: one 128-lane store
    o_ref[0] = jnp.concatenate(
        [acc / l for (acc, _, _), l in zip(state, l_safe)],
        axis=0).T.astype(o_ref.dtype)
    lse_ref[0, 0] = jnp.concatenate(
        [m + jnp.log(l) for (_, m, _), l in zip(state, l_safe)], axis=0)


def _bwd_dkdv_kernel(seed_ref, q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref,
                     delta_ref, dk_ref, dv_ref, *, sm_scale, causal,
                     dropout_rate, block_q, block_k, seq_len, head_dim):
    group = pl.program_id(0)
    kv_idx = pl.program_id(1)
    heads = _head_lanes(head_dim)
    k = _scaled(k_ref[0], sm_scale)     # (block_k, 128), the scores' scale on it
    v = v_ref[0]
    bias = bias_ref[0, 0][:, None]      # this k-block's, turned once a cell

    def tile(qi, carry):
        rows = _rows(qi, block_q)
        q = q_ref[0, rows, :]                             # (block_q, 128)
        do = do_ref[0, rows, :]
        if causal:
            under = _under_diagonal(qi, kv_idx, block_q, block_k)
        out = []
        for head, (lanes, (dk_acc, dv_acc)) in enumerate(zip(heads, carry)):
            lse = lse_ref[0, 0, pl.dslice(head, 1), rows]     # (1, block_q)
            delta = delta_ref[0, 0, pl.dslice(head, 1), rows]
            st = _dot_nt(k[:, lanes], q[:, lanes]) + bias  # (block_k, block_q)
            pt = jnp.exp(st - lse)      # true softmax probabilities
            if causal:
                pt = jnp.where(under, pt, 0.0)
            dpt = _dot_nt(v[:, lanes], do[:, lanes])
            if dropout_rate > 0.0:
                keep = _keep_scale(seed_ref[0], group * len(heads) + head, qi,
                                   kv_idx, block_q, block_k, dropout_rate)
                dv_acc = dv_acc + _dot((pt * keep).astype(do.dtype),
                                       do[:, lanes])
                dpt = dpt * keep
            else:
                dv_acc = dv_acc + _dot(pt.astype(do.dtype), do[:, lanes])
            dst = pt * (dpt - delta)
            dk_acc = dk_acc + _dot(dst.astype(q.dtype), q[:, lanes])
            out.append((dk_acc, dv_acc))
        return tuple(out)

    # q-blocks from the first that reaches this k-block's diagonal upwards
    zeros = jnp.zeros((block_k, head_dim), jnp.float32)
    lo = (kv_idx * block_k) // block_q if causal else 0
    state = _walk(tile, lo, seq_len // block_q,
                  ((zeros, zeros),) * len(heads))
    for lanes, (dk, dv) in zip(heads, state):
        dk_ref[0, :, lanes] = (dk * sm_scale).astype(dk_ref.dtype)
        dv_ref[0, :, lanes] = dv.astype(dv_ref.dtype)


def _head_rowsums(a, b, head_dim):
    """rowsum(a * b) over each head's lanes of a (rows, 128) tile, in float32:
    (8, rows), row h for head h, rows lane-major as lse is stored.  The lane
    reduction runs on the matrix unit (a 0/1 selector times the transposed
    product): jnp.sum's cross-lane reduce on the vector unit costs the dq
    kernel several times as much (PERF.md, PR 29).  The float32 product
    goes in as bf16 pieces that hold it exactly — two for bf16 operands
    (8 + 8 significant bits), three for float32 — summed in float32."""
    prod = a.astype(jnp.float32) * b.astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 1)
    head = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 0)
    selector = jnp.where(lane // head_dim == head, 1.0, 0.0).astype(
        jnp.bfloat16)
    both_bf16 = a.dtype == jnp.bfloat16 and b.dtype == jnp.bfloat16
    pieces = []
    for _ in range(2 if both_bf16 else 3):
        pieces.append(prod.astype(jnp.bfloat16))
        prod = prod - pieces[-1].astype(jnp.float32)
    return sum(_dot_nt(selector, piece) for piece in pieces)


def _bwd_dq_kernel(seed_ref, q_ref, k_ref, v_ref, bias_ref, do_ref, o_ref,
                   lse_ref, dq_ref, delta_ref, *, sm_scale, causal,
                   dropout_rate, block_q, block_k, seq_len, head_dim):
    group = pl.program_id(0)
    qi = pl.program_id(1)
    heads = _head_lanes(head_dim)
    q = _scaled(q_ref[0], sm_scale)     # (block_q, 128), the scores' scale on it
    do = do_ref[0]
    # delta = rowsum(do * o) of this q-block per head, in float32 from the
    # blocks already in VMEM; written out for the dkdv kernel as fwd writes lse
    deltas = _head_rowsums(do, o_ref[0], head_dim)
    delta_ref[0, 0] = deltas[:len(heads)]
    # lse rides a full-seq block (shared spec with the dkdv kernel); this
    # cell only needs its q-block slice
    lses = lse_ref[0, 0, :, pl.dslice(qi * block_q, block_q)]

    def tile(kv_idx, carry):            # carry: a head's dqᵀ, (d, block_q)
        rows = _rows(kv_idx, block_k)
        k = k_ref[0, rows, :]                             # (block_k, 128)
        v = v_ref[0, rows, :]
        kt = k.T                        # (128, block_k): a head is sublanes
        bias = bias_ref[0, 0, rows][:, None]
        if causal:
            under = _under_diagonal(qi, kv_idx, block_q, block_k)
        out = []
        for head, (lanes, dq_acc) in enumerate(zip(heads, carry)):
            st = _dot_nt(k[:, lanes], q[:, lanes]) + bias  # (block_k, block_q)
            pt = jnp.exp(st - lses[head:head + 1])
            if causal:
                pt = jnp.where(under, pt, 0.0)
            dpt = _dot_nt(v[:, lanes], do[:, lanes])
            if dropout_rate > 0.0:
                dpt = dpt * _keep_scale(seed_ref[0],
                                        group * len(heads) + head, qi, kv_idx,
                                        block_q, block_k, dropout_rate)
            dst = (pt * (dpt - deltas[head:head + 1])).astype(k.dtype)
            out.append(dq_acc + _dot(kt[lanes], dst))
        return tuple(out)

    dqs = _walk(tile, 0, _kv_end(qi, block_q, block_k, seq_len, causal),
                (jnp.zeros((head_dim, block_q), jnp.float32),) * len(heads))
    dq_ref[0] = (jnp.concatenate(dqs, axis=0) * sm_scale).T.astype(
        dq_ref.dtype)


def _specs(seq_len, pairs, block=None):
    """BlockSpec over the packed (b, seq, h*d) array: dim2 indexed by the
    128-lane head group; block=None takes the full sequence."""
    if block is None:
        return pl.BlockSpec((1, seq_len, 128),
                            lambda p, i: (p // pairs, 0, p % pairs))
    return pl.BlockSpec((1, block, 128),
                        lambda p, i: (p // pairs, i, p % pairs))


def _row_stat_spec(pairs, hpg, block_q):
    """Output spec of a per-(row, head) float32 statistic (the forward's lse,
    the backward's delta): (b, groups, heads_per_group, seq) by q-block."""
    return pl.BlockSpec((1, 1, hpg, block_q),
                        lambda p, i: (p // pairs, p % pairs, 0, i))


def _forward(q, k, v, bias, seed, num_heads, sm_scale, causal, dropout_rate,
             block_q, block_k):
    b, seq_len, packed = q.shape
    hd = packed // num_heads
    pairs = packed // 128               # 128-lane head groups
    hpg = 128 // hd                     # heads per group
    grid = (b * pairs, seq_len // block_q)
    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal,
        dropout_rate=dropout_rate, block_q=block_q, block_k=block_k,
        seq_len=seq_len, head_dim=hd)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=_smem()),
            _specs(seq_len, pairs, block_q),
            _specs(seq_len, pairs),
            _specs(seq_len, pairs),
            pl.BlockSpec((1, 1, seq_len), lambda p, i: (p // pairs, 0, 0)),
        ],
        out_specs=[
            _specs(seq_len, pairs, block_q),
            _row_stat_spec(pairs, hpg, block_q),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((b, pairs, hpg, seq_len), jnp.float32),
        ],
        interpret=_cfg.interpret(),
        name="flash_packed_fwd",
    )(seed, q, k, v, bias.reshape(b, 1, seq_len))


def _backward(q, k, v, bias, seed, num_heads, o, lse, do, sm_scale, causal,
              dropout_rate, block_q, block_k):
    """dq, dk, dv.  `o` and `do` go to the kernels as they come: the row sums
    delta = rowsum(do * o) per head are made inside the dq kernel, in float32
    from the bf16 blocks it holds in VMEM anyway, and written as
    (b, groups, heads_per_group, seq) like the forward's lse for the dkdv
    kernel to read.  Made here in jax.numpy they cost XLA two float32 copies
    of (b, s, h*d) in a row-minor layout, a reduce and two re-tilings a
    layer: ~0.5 GB of HBM traffic for one number per (row, head)."""
    b, seq_len, packed = q.shape
    hd = packed // num_heads
    pairs = packed // 128
    hpg = 128 // hd
    bias3 = bias.reshape(b, 1, seq_len)

    common = dict(sm_scale=sm_scale, causal=causal, dropout_rate=dropout_rate,
                  block_q=block_q, block_k=block_k, seq_len=seq_len,
                  head_dim=hd)
    lse_spec = pl.BlockSpec((1, 1, hpg, seq_len),
                            lambda p, i: (p // pairs, p % pairs, 0, 0))
    dq, delta = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **common),
        grid=(b * pairs, seq_len // block_q),
        in_specs=[
            pl.BlockSpec(memory_space=_smem()),
            _specs(seq_len, pairs, block_q),                  # q
            _specs(seq_len, pairs),   # k
            _specs(seq_len, pairs),   # v
            pl.BlockSpec((1, 1, seq_len), lambda p, i: (p // pairs, 0, 0)),
            _specs(seq_len, pairs, block_q),                  # do
            _specs(seq_len, pairs, block_q),                  # o
            lse_spec,
        ],
        out_specs=[
            _specs(seq_len, pairs, block_q),
            _row_stat_spec(pairs, hpg, block_q),
        ],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(lse.shape, jnp.float32)],
        interpret=_cfg.interpret(),
        name="flash_packed_dq",
    )(seed, q, k, v, bias3, do, o, lse)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkdv_kernel, **common),
        grid=(b * pairs, seq_len // block_k),
        in_specs=[
            pl.BlockSpec(memory_space=_smem()),
            _specs(seq_len, pairs),   # q
            _specs(seq_len, pairs, block_k),                  # k
            _specs(seq_len, pairs, block_k),                  # v
            pl.BlockSpec((1, 1, block_k), lambda p, i: (p // pairs, 0, i)),
            _specs(seq_len, pairs),   # do
            lse_spec,
            lse_spec,
        ],
        out_specs=[
            _specs(seq_len, pairs, block_k),
            _specs(seq_len, pairs, block_k),
        ],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        interpret=_cfg.interpret(),
        name="flash_packed_dkdv",
    )(seed, q, k, v, bias3, do, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash_packed(q, k, v, bias, seed, num_heads, sm_scale, causal,
                  dropout_rate, block_q, block_k):
    out, _ = _forward(q, k, v, bias, seed, num_heads, sm_scale, causal,
                      dropout_rate, block_q, block_k)
    return out


def _vjp_fwd(q, k, v, bias, seed, num_heads, sm_scale, causal, dropout_rate,
             block_q, block_k):
    out, lse = _forward(q, k, v, bias, seed, num_heads, sm_scale, causal,
                        dropout_rate, block_q, block_k)
    return out, (q, k, v, bias, seed, out, lse)


def _vjp_bwd(num_heads, sm_scale, causal, dropout_rate, block_q, block_k,
             res, g):
    q, k, v, bias, seed, out, lse = res
    dq, dk, dv = _backward(q, k, v, bias, seed, num_heads, out, lse, g,
                           sm_scale, causal, dropout_rate, block_q, block_k)
    return dq, dk, dv, jnp.zeros_like(bias), None


_flash_packed.defvjp(_vjp_fwd, _vjp_bwd)


def supported(seq_len: int, num_heads: int, head_dim: int) -> bool:
    """128-lane head groups: pairs of 64-wide heads or single 128-wide
    heads."""
    if head_dim == 64:
        heads_ok = num_heads % 2 == 0
    elif head_dim == 128:
        heads_ok = True
    else:
        heads_ok = False
    return heads_ok and seq_len % 128 == 0 and seq_len >= 128


def flash_attention_packed(q, k, v, num_heads, bias=None, sm_scale=None,
                           causal=False, dropout_rate=0.0, seed=None,
                           block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K):
    """Flash attention over PACKED (batch, seq, heads*head_dim) inputs —
    the projection layout, no head transposes.  Same contract as
    flash_attention otherwise (bias is a non-differentiable (b, s_k)
    padding bias; seed drives in-kernel dropout)."""
    b, s, packed = q.shape
    if packed % num_heads:
        raise ValueError(f"packed width {packed} not divisible by "
                         f"num_heads {num_heads}")
    hd = packed // num_heads
    heads_ok = (hd == 64 and num_heads % 2 == 0) or hd == 128
    if not heads_ok:
        raise ValueError(
            f"flash_attention_packed: unsupported head layout "
            f"(num_heads={num_heads}, head_dim={hd}); 128-lane groups need "
            f"head_dim 64 with even heads, or head_dim 128")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(hd)
    bq = min(block_q, s)
    bk = min(block_k, s)
    while s % bq:
        bq //= 2
    while s % bk:
        bk //= 2
    if not _cfg.interpret():
        if s % 128:
            raise ValueError(
                f"flash_attention_packed requires seq_len % 128 == 0 on "
                f"TPU, got {s}")
        bq, bk = max(bq, 128), max(bk, 128)
    bias, seed = _normalize_bias_seed(bias, seed, b, s)
    _cfg.record_call("flash_attention_packed")
    with jax.named_scope("pallas.flash_attention_packed"):
        return _flash_packed(q, k, v, bias, seed, int(num_heads), sm_scale,
                             causal, float(dropout_rate), bq, bk)
