"""Pallas TPU paged-attention: decode attention over a block-pooled KV cache.

The reference serves autoregressive decode from dense per-sequence caches
(DecoderCache in the beam-search op family — every sequence owns a
``max_len`` slab whether it uses 3 tokens or 3000).  The paged rebuild
stores K/V in a pool of fixed-size **blocks** (``block_size`` tokens each);
a sequence's cache is a *block table* — the list of physical block ids that
hold its tokens — so HBM follows live sequence length and identical
prefixes can alias the same physical blocks (serving/paged.py).

The kernel computes, for every sequence slot ``s`` with one query token::

    out[s] = softmax(q[s] · K[s]ᵀ / √d) · V[s]

where ``K[s]``/``V[s]`` are gathered block-by-block through the table.  The
gather is free at the grid level: the block table rides as a
**scalar-prefetch** operand (SMEM), and the K/V ``BlockSpec`` index maps
read ``tables[s, j]`` to pick WHICH physical cache block the next grid step
DMAs into VMEM — no materialized (seqs, max_len, d) gather ever exists.
Softmax is the online (streaming max/sum) form over the ``j`` grid axis
with float32 accumulators in scratch, exactly the flash-attention recipe
restricted to a 1-token query.

Chunked prefill reuses THIS kernel: a chunk of C prompt tokens is laid out
as C query rows sharing one table with per-row context lengths
``start+1 … start+C`` — causal attention inside the chunk falls out of the
length mask (serving/paged.py writes the chunk's K/V before attending).

int8 KV blocks: when the caches are int8, a per-block fp32 scale pair
(k_scale, v_scale) rides a third gathered operand and the dequantize runs
in-kernel next to the dot — HBM traffic is the compressed bytes.

Rows with ``context_len == 0`` (empty slots) produce exact zeros.
Off-TPU the kernel runs in interpret mode (CI); production CPU dispatch
takes the jit-friendly ``paged_attention_reference`` path instead (same
math, one fused XLA gather) via the ``use_paged_attention`` flag gate in
``ops/pallas/config.py`` — the kernel fingerprint rides the compile-cache
key, so a flag flip is exactly one recompile.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from paddle_tpu.ops.pallas import config as _cfg

NEG_INF = -1e30


def supported(num_seqs: int, block_size: int, head_dim: int,
              dtype) -> bool:
    """Shapes the kernel handles on real TPUs: lane-aligned head_dim,
    sublane-aligned block_size (int8 packs 32/sublane but 8 keeps the
    masked tail cheap), f32/bf16/int8 caches.  Interpret mode (CI) accepts
    the same shapes so the gate is exercised identically."""
    if head_dim % 128 != 0 or block_size % 8 != 0:
        return False
    return jnp.dtype(dtype) in (jnp.dtype(jnp.float32),
                                jnp.dtype(jnp.bfloat16),
                                jnp.dtype(jnp.int8))


def _paged_attn_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, scale_ref,
                       o_ref, acc_ref, m_ref, l_ref, *, block_size,
                       max_blocks, sm_scale, quantized):
    s = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[0, 0] = NEG_INF    # SMEM takes scalar stores only
        l_ref[0, 0] = 0.0

    q = q_ref[0]                       # (1, d) native dtype
    k = k_ref[0]                       # (block_size, d)
    v = v_ref[0]
    if quantized:
        blk = tbl_ref[s, j]
        k = k.astype(jnp.float32) * scale_ref[2 * blk]
        v = v.astype(jnp.float32) * scale_ref[2 * blk + 1]
        q = q.astype(jnp.float32)
    scores = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
    pos = j * block_size + jax.lax.broadcasted_iota(jnp.int32, scores.shape,
                                                    1)
    valid = pos < len_ref[s]
    scores = jnp.where(valid, scores, NEG_INF)

    m_prev = m_ref[0, 0]
    l_prev = l_ref[0, 0]
    m_cur = jnp.maximum(m_prev, jnp.max(scores))
    alpha = jnp.exp(m_prev - m_cur)
    # Explicit zero on masked lanes: when a row has seen no valid token yet
    # m_cur is still NEG_INF and exp(NEG_INF - NEG_INF) would be 1.
    p = jnp.where(valid, jnp.exp(scores - m_cur), 0.0)  # (1, bs) fp32
    l_ref[0, 0] = l_prev * alpha + jnp.sum(p)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p.astype(v.dtype) if not quantized else p, v,
        preferred_element_type=jnp.float32)
    m_ref[0, 0] = m_cur

    @pl.when(j == max_blocks - 1)
    def _finish():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[0, 0], 1e-30)).astype(o_ref.dtype)


def paged_attention_kernel(q, k_cache, v_cache, block_tables, context_lens,
                           sm_scale: float,
                           kv_scales: Optional[jax.Array] = None):
    """The Pallas path.  ``q`` (num_seqs, d); caches (num_blocks,
    block_size, d); ``block_tables`` (num_seqs, max_blocks) int32 —
    every entry must be a valid block id (masked rows still DMA);
    ``context_lens`` (num_seqs,) int32; ``kv_scales`` (num_blocks, 2)
    fp32 when the caches are int8.  Returns (num_seqs, d) in q's dtype."""
    from jax.experimental.pallas import tpu as pltpu

    num_seqs, d = q.shape
    num_blocks, block_size, _ = k_cache.shape
    max_blocks = block_tables.shape[1]
    quantized = k_cache.dtype == jnp.int8
    if kv_scales is None:
        kv_scales = jnp.ones((num_blocks, 2), jnp.float32)

    kernel = functools.partial(
        _paged_attn_kernel, block_size=block_size, max_blocks=max_blocks,
        sm_scale=sm_scale, quantized=quantized)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block_tables, context_lens
        grid=(num_seqs, max_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, d), lambda s, j, tbl, lens: (s, 0, 0)),
            pl.BlockSpec((1, block_size, d),
                         lambda s, j, tbl, lens: (tbl[s, j], 0, 0)),
            pl.BlockSpec((1, block_size, d),
                         lambda s, j, tbl, lens: (tbl[s, j], 0, 0)),
            # per-block (k, v) scales, flat in SMEM and indexed by the
            # physical block id: a (1, 2) VMEM block of the (num_blocks, 2)
            # array is refused by Mosaic (second-minor block of 1)
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, d), lambda s, j, tbl, lens: (s, 0, 0)),
        scratch_shapes=[pltpu.VMEM((1, d), jnp.float32),
                        pltpu.SMEM((1, 1), jnp.float32),
                        pltpu.SMEM((1, 1), jnp.float32)],
    )
    _cfg.record_call("paged_attention")
    with jax.named_scope("pallas.paged_attention"):
        out = pl.pallas_call(
            kernel, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((num_seqs, 1, d), q.dtype),
            interpret=_cfg.interpret(),
        )(block_tables, context_lens, q.reshape(num_seqs, 1, d),
          k_cache, v_cache, kv_scales.reshape(-1))
    return out.reshape(num_seqs, d)


def paged_attention_reference(q, k_cache, v_cache, block_tables,
                              context_lens, sm_scale: float,
                              kv_scales: Optional[jax.Array] = None):
    """jnp fallback with identical semantics: one fused gather + masked
    softmax.  This is the production CPU path (jit-compiles into the
    serving step) and the parity oracle for the kernel."""
    num_seqs, d = q.shape
    block_size = k_cache.shape[1]
    max_blocks = block_tables.shape[1]
    k = k_cache[block_tables]          # (S, max_blocks, bs, d)
    v = v_cache[block_tables]
    if k_cache.dtype == jnp.int8:
        if kv_scales is None:
            raise ValueError("int8 KV caches require kv_scales")
        s_kv = kv_scales[block_tables]  # (S, max_blocks, 2)
        k = k.astype(jnp.float32) * s_kv[..., 0][:, :, None, None]
        v = v.astype(jnp.float32) * s_kv[..., 1][:, :, None, None]
    span = max_blocks * block_size
    k = k.reshape(num_seqs, span, d)
    v = v.reshape(num_seqs, span, d)
    scores = jnp.einsum("sd,smd->sm", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * sm_scale
    pos = jnp.arange(span, dtype=jnp.int32)[None, :]
    scores = jnp.where(pos < context_lens[:, None], scores, NEG_INF)
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores - m)
    p = jnp.where(pos < context_lens[:, None], p, 0.0)
    l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("sm,smd->sd", p / l, v.astype(jnp.float32))
    return out.astype(q.dtype)


def paged_attention(q, k_cache, v_cache, block_tables, context_lens,
                    sm_scale: Optional[float] = None,
                    kv_scales: Optional[jax.Array] = None):
    """Gated dispatch: the Pallas kernel when the ``use_paged_attention``
    flag is on, the backend is TPU (tests monkeypatch
    ``config.kernel_enabled`` to exercise interpret mode on CPU CI) and
    the shapes pass :func:`supported`; the jnp reference otherwise."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if (_cfg.kernel_enabled("use_paged_attention")
            and supported(q.shape[0], k_cache.shape[1], q.shape[-1],
                          k_cache.dtype)):
        return paged_attention_kernel(q, k_cache, v_cache, block_tables,
                                      context_lens, sm_scale,
                                      kv_scales=kv_scales)
    _cfg.record_fallback("paged_attention")
    return paged_attention_reference(q, k_cache, v_cache, block_tables,
                                     context_lens, sm_scale,
                                     kv_scales=kv_scales)


def paged_attention_cost(num_seqs: int, max_blocks: int, block_size: int,
                         head_dim: int,
                         kv_bytes_per_elem: int = 4) -> Tuple[float, float]:
    """(flops, HBM bytes) for one kernel call — the same model the xprof
    instr pricer uses, exported for kernelbench/servebench."""
    span = num_seqs * max_blocks * block_size
    flops = span * (4.0 * head_dim + 5.0)   # qk + pv dots, online softmax
    bytes_ = (2.0 * span * head_dim * kv_bytes_per_elem     # K and V blocks
              + 2.0 * num_seqs * head_dim * 4               # q in, out
              + num_seqs * max_blocks * 4 + num_seqs * 4)   # table + lens
    return flops, float(bytes_)


def _paged_attn_instr_flops(instr) -> float:
    """xprof custom-call pricer: operands are (tables, lens, q, k_cache,
    v_cache, scales); out (S, 1, d)."""
    shapes = [s for _, s in instr.operand_shapes]
    if not instr.out_shapes or len(shapes) < 5:
        return 0.0
    out = instr.out_shapes[0][1]
    tables = shapes[0]
    caches = [s for s in shapes if len(s) == 3 and s[-1] == out[-1]]
    if len(out) != 3 or len(tables) != 2 or not caches:
        return 0.0
    num_seqs, max_blocks = tables
    block_size = caches[0][1]
    d = out[-1]
    return num_seqs * max_blocks * block_size * (4.0 * d + 5.0)


_cfg.register_cost("pallas.paged_attention", _paged_attn_instr_flops)
