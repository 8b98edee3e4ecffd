"""Packed-layout Pallas flash attention: q/k/v in (batch, seq, heads*dim).

The standard kernel (flash_attention.py) consumes (batch*heads, seq, dim),
which forces the model to materialize (b, s, h, d) -> (b, h, s, d)
transposes around every attention call: a layout copy of q, k, v and the
output forward, and of their gradients backward, in every layer.  This
variant reads the projection output LAYOUT DIRECTLY: blocks are
(1, block_q, 2*dim) slices of the (b, s, h*d) array covering 128 lanes of
heads (Mosaic requires 128-divisible lane blocks): a PAIR of 64-wide heads
(BERT/ERNIE family) or ONE 128-wide head (LLaMA-class models); each grid
cell runs the online-softmax recursion for its heads back to back.  No
transpose ever exists in the program, and the backward runs no arithmetic
outside its two kernels (see _backward).

Numerics, dropout (hardware-PRNG per-tile reseed keyed by the GLOBAL head
index, replayable in both backward kernels), bias handling, and the matmul
dtype policy are identical to flash_attention.py; causal masking is
supported the same way.  Non-pair-divisible head counts fall back to the
standard kernel at the dispatch layer (ops/attention.py).
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
    _keep_mask,
    _normalize_bias_seed,
    _smem,
)


def _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref, *,
                sm_scale, causal, dropout_rate, block_q, block_k, seq_len,
                head_dim):
    pair = pl.program_id(0)
    qi = pl.program_id(1)
    q2 = q_ref[0]                       # (block_q, 2*head_dim)

    num_kv = seq_len // block_k
    if causal:
        num_kv_iter = (qi * block_q) // block_k + pl.cdiv(block_q, block_k)
        num_kv_iter = jnp.minimum(num_kv_iter, num_kv)
    else:
        num_kv_iter = num_kv

    for head in range(128 // head_dim):
        lo = head * head_dim
        q = q2[:, lo:lo + head_dim]
        bh_global = pair * (128 // head_dim) + head  # dropout stream key

        def body(kv_idx, carry, q=q, bh_global=bh_global, lo=lo):
            acc, m_prev, l_prev = carry
            k = k_ref[0, pl.dslice(kv_idx * block_k, block_k),
                      lo:lo + head_dim]
            v = v_ref[0, pl.dslice(kv_idx * block_k, block_k),
                      lo:lo + head_dim]
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
            bias = bias_ref[0, 0, pl.dslice(kv_idx * block_k, block_k)]
            s = s + bias.astype(jnp.float32)[None, :]
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            k_pos = kv_idx * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            if causal:
                s = jnp.where(q_pos >= k_pos, s, NEG_INF)
            m_cur = jnp.max(s, axis=-1)
            m_new = jnp.maximum(m_prev, m_cur)
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new[:, None])
            l_new = l_prev * alpha + jnp.sum(p, axis=-1)
            if dropout_rate > 0.0:
                keep = _keep_mask(seed_ref[0], jnp.int32(bh_global), qi,
                                  kv_idx, q_pos, k_pos, dropout_rate)
                p = jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
            acc = acc * alpha[:, None] + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            return acc, m_new, l_new

        acc0 = jnp.zeros((block_q, head_dim), jnp.float32)
        m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
        l0 = jnp.zeros((block_q,), jnp.float32)
        acc, m, l = jax.lax.fori_loop(0, num_kv_iter, body, (acc0, m0, l0))
        l_safe = jnp.maximum(l, 1e-30)
        o_ref[0, :, lo:lo + head_dim] = (
            acc / l_safe[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0, head] = m + jnp.log(l_safe)


def _bwd_dkdv_kernel(seed_ref, q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref,
                     delta_ref, dk_ref, dv_ref, *, sm_scale, causal,
                     dropout_rate, block_q, block_k, seq_len, head_dim):
    pair = pl.program_id(0)
    kv_idx = pl.program_id(1)
    bias = bias_ref[0, 0].astype(jnp.float32)   # (block_k,)
    num_q = seq_len // block_q
    qi_start = (kv_idx * block_k) // block_q if causal else 0

    for head in range(128 // head_dim):
        lo = head * head_dim
        k = k_ref[0, :, lo:lo + head_dim]       # (block_k, d)
        v = v_ref[0, :, lo:lo + head_dim]
        bh_global = pair * (128 // head_dim) + head

        def body(qi, carry, k=k, v=v, bh_global=bh_global, lo=lo, head=head):
            dk_acc, dv_acc = carry
            q = q_ref[0, pl.dslice(qi * block_q, block_q), lo:lo + head_dim]
            do = do_ref[0, pl.dslice(qi * block_q, block_q), lo:lo + head_dim]
            lse = lse_ref[0, 0, head, pl.dslice(qi * block_q, block_q)]
            delta = delta_ref[0, 0, head, pl.dslice(qi * block_q, block_q)]
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
            s = s + bias[None, :]
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            k_pos = kv_idx * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            p = jnp.exp(s - lse[:, None])
            if causal:
                p = jnp.where(q_pos >= k_pos, p, 0.0)
            if dropout_rate > 0.0:
                keep = _keep_mask(seed_ref[0], jnp.int32(bh_global), qi,
                                  kv_idx, q_pos, k_pos, dropout_rate)
                inv = 1.0 / (1.0 - dropout_rate)
                p_d = jnp.where(keep, p * inv, 0.0)
            else:
                p_d = p
            dv_acc = dv_acc + jnp.dot(p_d.astype(do.dtype).T, do,
                                      preferred_element_type=jnp.float32)
            dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
            if dropout_rate > 0.0:
                dp = jnp.where(keep, dp * inv, 0.0)
            ds = p * (dp - delta[:, None]) * sm_scale
            dk_acc = dk_acc + jnp.dot(ds.astype(q.dtype).T, q,
                                      preferred_element_type=jnp.float32)
            return dk_acc, dv_acc

        zeros = jnp.zeros((block_k, head_dim), jnp.float32)
        dk, dv = jax.lax.fori_loop(qi_start, num_q, body, (zeros, zeros))
        dk_ref[0, :, lo:lo + head_dim] = dk.astype(dk_ref.dtype)
        dv_ref[0, :, lo:lo + head_dim] = dv.astype(dv_ref.dtype)


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
    return sum(jax.lax.dot_general(selector, piece, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)
               for piece in pieces)


def _bwd_dq_kernel(seed_ref, q_ref, k_ref, v_ref, bias_ref, do_ref, o_ref,
                   lse_ref, dq_ref, delta_ref, *, sm_scale, causal,
                   dropout_rate, block_q, block_k, seq_len, head_dim):
    pair = pl.program_id(0)
    qi = pl.program_id(1)
    num_kv = seq_len // block_k
    if causal:
        num_kv_iter = (qi * block_q) // block_k + pl.cdiv(block_q, block_k)
        num_kv_iter = jnp.minimum(num_kv_iter, num_kv)
    else:
        num_kv_iter = num_kv
    # delta = rowsum(do * o) of this q-block per head, in float32 from the
    # blocks already in VMEM; written out for the dkdv kernel as fwd writes lse
    deltas = _head_rowsums(do_ref[0], o_ref[0], head_dim)
    delta_ref[0, 0] = deltas[:128 // head_dim]

    for head in range(128 // head_dim):
        lo = head * head_dim
        q = q_ref[0, :, lo:lo + head_dim]
        do = do_ref[0, :, lo:lo + head_dim]
        delta = deltas[head][:, None]
        # lse rides a full-seq block (shared spec with the dkdv kernel);
        # this cell only needs its q-block slice
        lse = lse_ref[0, 0, head, pl.dslice(qi * block_q, block_q)]
        bh_global = pair * (128 // head_dim) + head

        def body(kv_idx, dq_acc, q=q, do=do, lse=lse, delta=delta,
                 bh_global=bh_global, lo=lo):
            k = k_ref[0, pl.dslice(kv_idx * block_k, block_k),
                      lo:lo + head_dim]
            v = v_ref[0, pl.dslice(kv_idx * block_k, block_k),
                      lo:lo + head_dim]
            bias = bias_ref[0, 0, pl.dslice(kv_idx * block_k, block_k)]
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
            s = s + bias.astype(jnp.float32)[None, :]
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            k_pos = kv_idx * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            p = jnp.exp(s - lse[:, None])
            if causal:
                p = jnp.where(q_pos >= k_pos, p, 0.0)
            dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
            if dropout_rate > 0.0:
                keep = _keep_mask(seed_ref[0], jnp.int32(bh_global), qi,
                                  kv_idx, q_pos, k_pos, dropout_rate)
                dp = jnp.where(keep, dp / (1.0 - dropout_rate), 0.0)
            ds = (p * (dp - delta) * sm_scale).astype(k.dtype)
            return dq_acc + jnp.dot(ds, k, preferred_element_type=jnp.float32)

        dq = jax.lax.fori_loop(0, num_kv_iter, body,
                               jnp.zeros((q_ref.shape[1], head_dim),
                                         jnp.float32))
        dq_ref[0, :, lo:lo + head_dim] = dq.astype(dq_ref.dtype)


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
