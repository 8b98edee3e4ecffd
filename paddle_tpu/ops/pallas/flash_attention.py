"""Pallas TPU flash-attention: forward + backward kernels.

The reference has no flash attention (SURVEY.md §5.7 — its transformer is
plain full attention, python/paddle/nn/layer/transformer.py); this is a new
TPU-native capability.  Design:

* Forward: block-wise online-softmax in VMEM with float32 accumulators (MXU
  matmuls via jnp.dot with preferred_element_type), grid over
  (batch*heads, q_blocks); K/V stream through a fori_loop of VMEM dynamic
  slices.  Emits the per-row logsumexp for the backward pass.
* Matmul dtype policy: every dot runs in the INPUT dtype (bf16 on the
  flagship) with fp32 accumulation — softmax statistics and probabilities
  are fp32, and probabilities are rounded back to the input dtype for the
  PV / dV / dK / dQ matmuls.  An fp32 upcast before the dot (the r02
  design) forced multi-pass fp32 MXU matmuls at a fraction of bf16 peak;
  fp32 inputs still take the exact-fp32 path end-to-end (the CPU tests).
* Backward: two kernels — dK/dV over a (batch*heads, k_blocks) grid and dQ
  over (batch*heads, q_blocks) — recomputing probabilities from the stored
  logsumexp (no S matrix ever materialized in HBM).
* Head sizes: q and k share one (the contraction of the scores), v, o and
  their cotangents another: latent attention trains at q·k 192 / v 128, and
  padding v to 192 would add a third to the P·V, dV and dP work.
* Padding mask: an additive k-position bias of shape (batch, seq_k) streams
  through both passes, which covers the BERT/ERNIE padding-mask case without
  falling back to the O(S^2) jnp path.
* Dropout: applied inside the kernel with no mask tensor in HBM.  On real
  TPUs the keep mask comes from the hardware PRNG re-seeded per
  (seed, batch*head, q_block, k_block) tile — tile-local streams are
  replayable across the forward and both backward kernels even though they
  visit tiles in different orders.  Interpret mode (CPU tests) uses a
  murmur3-style position hash instead (identical property, but ~10 ms/step
  slower on TPU where int32 multiplies are VPU-emulated).

Numerics: probabilities use softmax-then-dropout semantics; sum `l` is taken
over the *undropped* probabilities, matching the jnp reference path.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from paddle_tpu.ops.pallas import config as _cfg

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
NEG_INF = -1e30

import numpy as np

# murmur3 fmix32 constants for the dropout hash (numpy scalars embed as
# literals inside pallas kernels; jnp constants would be captured consts)
_M1 = np.uint32(0x85EBCA6B)
_M2 = np.uint32(0xC2B2AE35)
_P1 = np.uint32(0x9E3779B1)  # golden-ratio primes to decorrelate axes
_P2 = np.uint32(0x85EBCA77)
_P3 = np.uint32(0xC2B2AE3D)


def _dropout_keep(seed, bh, q_pos, k_pos, rate):
    """Deterministic keep-mask: murmur3-finalizer hash of global positions.

    Identical values in forward and both backward kernels for the same
    (seed, bh, q_pos, k_pos), independent of block sizes.  Used in interpret
    mode (CPU tests); on real TPUs _dropout_keep_hw replaces it — int32
    multiplies are emulated on the VPU and the 5-multiply hash costs ~10 ms
    per flagship step (measured r03).
    """
    h = (seed.astype(jnp.uint32)
         + bh.astype(jnp.uint32) * _P3
         + q_pos.astype(jnp.uint32) * _P1
         + k_pos.astype(jnp.uint32) * _P2)
    h = h ^ (h >> 16)
    h = h * _M1
    h = h ^ (h >> 13)
    h = h * _M2
    h = h ^ (h >> 16)
    threshold = np.uint32(min(int(rate * 2**32), 2**32 - 1))
    return h >= threshold  # keep with prob (1 - rate)


def _keep_from_hw_bits(seed_words, shape, rate):
    """Draw a keep mask from the hardware PRNG seeded with up to two int32
    words (the Mosaic limit).  Shared by the flash-attention and fused-LN
    dropout paths so the threshold/seeding convention cannot drift."""
    from jax.experimental.pallas import tpu as pltpu

    pltpu.prng_seed(*seed_words)
    bits = pltpu.prng_random_bits(shape)  # int32 tile
    threshold = np.int32(min(int(rate * 2**32), 2**32 - 1) - 2**31)
    return bits >= threshold  # keep with prob (1 - rate)


def _dropout_keep_hw(seed, bh, qi, kv_idx, shape, rate):
    """Hardware-PRNG keep-mask for one (block_q, block_k) tile.

    The generator is RE-SEEDED per (seed, bh, q_block, k_block) tile, so the
    stream drawn for a tile depends only on its coordinates — the forward,
    dK/dV, and dQ kernels visit tiles in different orders yet replay
    identical masks.  (A single kernel-wide stream would not be replayable:
    the two backward kernels iterate the S matrix along different axes.)
    Requires block sizes to agree across forward and backward, which
    flash_attention() guarantees.
    """
    # Mosaic takes at most two 32-bit seed words: fold (seed, bh) into one
    # (odd-constant multiply is injective in bh mod 2^32) and (qi, kv) into
    # the other (block indices are far below 2^16).
    return _keep_from_hw_bits(
        (seed + bh * jnp.int32(_P3), qi * jnp.int32(65536) + kv_idx),
        shape, rate)


def _keep_mask(seed, bh, qi, kv_idx, q_pos, k_pos, rate):
    """Dispatch: hardware PRNG on real TPUs, position hash in interpret."""
    if _cfg.interpret():
        return _dropout_keep(seed, bh, q_pos, k_pos, rate)
    return _dropout_keep_hw(seed, bh, qi, kv_idx, q_pos.shape, rate)


def _flash_fwd_kernel(seed_ref, q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref,
                      *, sm_scale, causal, dropout_rate, block_q, block_k,
                      seq_len):
    # MXU policy: matmuls run in the INPUT dtype with float32 accumulation
    # (preferred_element_type).  bf16 inputs hit the MXU at full rate; an
    # fp32 upcast before the dot would force multi-pass fp32 matmuls at a
    # fraction of peak.  Softmax/logsumexp stay fp32; probabilities are cast
    # back to the input dtype for the PV matmul (fp32 inputs therefore keep
    # exact fp32 numerics end-to-end — the CPU/interpret test path).
    bh_idx = pl.program_id(0)
    qi = pl.program_id(1)
    q = q_ref[0]  # (block_q, d), native dtype

    num_kv = seq_len // block_k
    if causal:
        num_kv_iter = (qi * block_q) // block_k + pl.cdiv(block_q, block_k)
        num_kv_iter = jnp.minimum(num_kv_iter, num_kv)
    else:
        num_kv_iter = num_kv

    def body(kv_idx, carry):
        acc, m_prev, l_prev = carry
        k = k_ref[0, pl.dslice(kv_idx * block_k, block_k), :]
        v = v_ref[0, pl.dslice(kv_idx * block_k, block_k), :]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
        bias = bias_ref[0, 0, pl.dslice(kv_idx * block_k, block_k)]
        s = s + bias.astype(jnp.float32)[None, :]
        q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = kv_idx * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        if causal:
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        if dropout_rate > 0.0:
            keep = _keep_mask(seed_ref[0], bh_idx, qi, kv_idx, q_pos, k_pos,
                              dropout_rate)
            p = jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
        acc = acc * alpha[:, None] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        return acc, m_new, l_new

    acc0 = jnp.zeros((block_q, v_ref.shape[-1]), jnp.float32)
    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, num_kv_iter, body, (acc0, m0, l0))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    lse_ref[0] = (m + jnp.log(l_safe))[None, :]


def _flash_forward(q, k, v, bias, seed, sm_scale, causal, dropout_rate,
                   block_q, block_k):
    """q,k: (bh, seq, d); v: (bh, seq, d_v); bias: (b, seq); seed: int32
    scalar array."""
    bh, seq_len, d = q.shape
    d_v = v.shape[-1]
    b = bias.shape[0]
    h = bh // b
    grid = (bh, seq_len // block_q)
    kernel = functools.partial(
        _flash_fwd_kernel, sm_scale=sm_scale, causal=causal,
        dropout_rate=dropout_rate, block_q=block_q, block_k=block_k,
        seq_len=seq_len)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=_smem()),
            pl.BlockSpec((1, block_q, d), lambda bh_i, i: (bh_i, i, 0)),
            pl.BlockSpec((1, seq_len, d), lambda bh_i, i: (bh_i, 0, 0)),
            pl.BlockSpec((1, seq_len, d_v), lambda bh_i, i: (bh_i, 0, 0)),
            pl.BlockSpec((1, 1, seq_len), lambda bh_i, i: (bh_i // h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d_v), lambda bh_i, i: (bh_i, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda bh_i, i: (bh_i, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq_len, d_v), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, seq_len), jnp.float32),
        ],
        interpret=_cfg.interpret(),
        name="flash_fwd",
    )(seed, q, k, v, bias.reshape(b, 1, seq_len))


def _flash_bwd_dkdv_kernel(seed_ref, q_ref, k_ref, v_ref, bias_ref, do_ref,
                           lse_ref, delta_ref, dk_ref, dv_ref, *, sm_scale,
                           causal, dropout_rate, block_q, block_k, seq_len):
    bh_idx = pl.program_id(0)
    kv_idx = pl.program_id(1)
    k = k_ref[0]  # (block_k, d), native dtype (matmuls run in input dtype)
    v = v_ref[0]
    bias = bias_ref[0, 0].astype(jnp.float32)  # (block_k,)

    num_q = seq_len // block_q
    qi_start = (kv_idx * block_k) // block_q if causal else 0

    def body(qi, carry):
        dk_acc, dv_acc = carry
        q = q_ref[0, pl.dslice(qi * block_q, block_q), :]
        do = do_ref[0, pl.dslice(qi * block_q, block_q), :]
        lse = lse_ref[0, 0, pl.dslice(qi * block_q, block_q)]
        delta = delta_ref[0, 0, pl.dslice(qi * block_q, block_q)]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
        s = s + bias[None, :]
        q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = kv_idx * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        p = jnp.exp(s - lse[:, None])  # true softmax probs (block_q, block_k)
        if causal:
            p = jnp.where(q_pos >= k_pos, p, 0.0)
        if dropout_rate > 0.0:
            keep = _keep_mask(seed_ref[0], bh_idx, qi, kv_idx, q_pos, k_pos,
                              dropout_rate)
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

    dk, dv = jax.lax.fori_loop(
        qi_start, num_q, body,
        (jnp.zeros(k.shape, jnp.float32), jnp.zeros(v.shape, jnp.float32)))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(seed_ref, q_ref, k_ref, v_ref, bias_ref, do_ref,
                         lse_ref, delta_ref, dq_ref, *, sm_scale, causal,
                         dropout_rate, block_q, block_k, seq_len):
    bh_idx = pl.program_id(0)
    qi = pl.program_id(1)
    q = q_ref[0]  # (block_q, d), native dtype (matmuls run in input dtype)
    do = do_ref[0]
    lse = lse_ref[0, 0]
    delta = delta_ref[0, 0]

    num_kv = seq_len // block_k
    if causal:
        num_kv_iter = (qi * block_q) // block_k + pl.cdiv(block_q, block_k)
        num_kv_iter = jnp.minimum(num_kv_iter, num_kv)
    else:
        num_kv_iter = num_kv

    def body(kv_idx, dq_acc):
        k = k_ref[0, pl.dslice(kv_idx * block_k, block_k), :]
        v = v_ref[0, pl.dslice(kv_idx * block_k, block_k), :]
        bias = bias_ref[0, 0, pl.dslice(kv_idx * block_k, block_k)]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
        s = s + bias.astype(jnp.float32)[None, :]
        q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = kv_idx * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        p = jnp.exp(s - lse[:, None])
        if causal:
            p = jnp.where(q_pos >= k_pos, p, 0.0)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        if dropout_rate > 0.0:
            keep = _keep_mask(seed_ref[0], bh_idx, qi, kv_idx, q_pos, k_pos,
                              dropout_rate)
            dp = jnp.where(keep, dp / (1.0 - dropout_rate), 0.0)
        ds = (p * (dp - delta[:, None]) * sm_scale).astype(k.dtype)
        return dq_acc + jnp.dot(ds, k, preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, num_kv_iter, body,
                           jnp.zeros(q.shape, jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _flash_backward(q, k, v, bias, seed, o, lse, do, sm_scale, causal,
                    dropout_rate, block_q, block_k):
    bh, seq_len, d = q.shape
    d_v = v.shape[-1]
    b = bias.shape[0]
    h = bh // b
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta = delta.reshape(bh, 1, seq_len)
    bias3 = bias.reshape(b, 1, seq_len)

    common = dict(sm_scale=sm_scale, causal=causal, dropout_rate=dropout_rate,
                  block_q=block_q, block_k=block_k, seq_len=seq_len)
    seq_spec = lambda d: pl.BlockSpec((1, seq_len, d), lambda bh_i, i: (bh_i, 0, 0))
    blk_spec = lambda n, d: pl.BlockSpec((1, n, d), lambda bh_i, i: (bh_i, i, 0))
    row_spec = lambda: pl.BlockSpec((1, 1, seq_len), lambda bh_i, i: (bh_i, 0, 0))

    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkdv_kernel, **common),
        grid=(bh, seq_len // block_k),
        in_specs=[
            pl.BlockSpec(memory_space=_smem()),
            seq_spec(d),  # q
            blk_spec(block_k, d),  # k
            blk_spec(block_k, d_v),  # v
            pl.BlockSpec((1, 1, block_k), lambda bh_i, i: (bh_i // h, 0, i)),  # bias
            seq_spec(d_v),  # do
            row_spec(),  # lse
            row_spec(),  # delta
        ],
        out_specs=[blk_spec(block_k, d), blk_spec(block_k, d_v)],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        interpret=_cfg.interpret(),
        name="flash_dkdv",
    )(seed, q, k, v, bias3, do, lse, delta)

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, **common),
        grid=(bh, seq_len // block_q),
        in_specs=[
            pl.BlockSpec(memory_space=_smem()),
            blk_spec(block_q, d),  # q
            seq_spec(d),  # k
            seq_spec(d_v),  # v
            pl.BlockSpec((1, 1, seq_len), lambda bh_i, i: (bh_i // h, 0, 0)),  # bias
            blk_spec(block_q, d_v),  # do
            pl.BlockSpec((1, 1, block_q), lambda bh_i, i: (bh_i, 0, i)),  # lse
            pl.BlockSpec((1, 1, block_q), lambda bh_i, i: (bh_i, 0, i)),  # delta
        ],
        out_specs=blk_spec(block_q, d),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=_cfg.interpret(),
        name="flash_dq",
    )(seed, q, k, v, bias3, do, lse, delta)
    return dq, dk, dv


def _smem():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.SMEM


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash_attention_bhsd(q, k, v, bias, seed, sm_scale, causal, dropout_rate,
                          block_q, block_k):
    out, _ = _flash_forward(q, k, v, bias, seed, sm_scale, causal,
                            dropout_rate, block_q, block_k)
    return out


def _fwd(q, k, v, bias, seed, sm_scale, causal, dropout_rate, block_q, block_k):
    out, lse = _flash_forward(q, k, v, bias, seed, sm_scale, causal,
                              dropout_rate, block_q, block_k)
    return out, (q, k, v, bias, seed, out, lse)


def _bwd(sm_scale, causal, dropout_rate, block_q, block_k, res, g):
    q, k, v, bias, seed, out, lse = res
    dq, dk, dv = _flash_backward(q, k, v, bias, seed, out, lse, g, sm_scale,
                                 causal, dropout_rate, block_q, block_k)
    # Padding bias carries no trainable state; seed is integer (no cotangent).
    return dq, dk, dv, jnp.zeros_like(bias), None


_flash_attention_bhsd.defvjp(_fwd, _bwd)


def _normalize_bias_seed(bias, seed, b, s):
    """Shared by the standard and packed wrappers: pad-bias broadcast with
    the non-differentiable contract, and int32 seed normalization."""
    if bias is None:
        bias = jnp.zeros((b, s), jnp.float32)
    else:
        bias = jax.lax.stop_gradient(
            jnp.broadcast_to(bias.astype(jnp.float32), (b, s)))
    if seed is None:
        seed = jnp.zeros((1,), jnp.int32)
    else:
        seed = jnp.asarray(seed, jnp.int32).reshape((1,))
    return bias, seed


def supported(seq_len: int, head_dim: int, v_head_dim: int = None) -> bool:
    """Shapes the kernel handles: sublane-aligned head sizes (64 covers the
    BERT/ERNIE family, 192/128 latent attention; Mosaic pads lanes),
    block-divisible seq."""
    v_head_dim = head_dim if v_head_dim is None else v_head_dim
    return head_dim % 64 == 0 and v_head_dim % 64 == 0 \
        and seq_len % 128 == 0 and seq_len >= 128


def flash_attention(q, k, v, bias=None, sm_scale=None, causal=False,
                    dropout_rate=0.0, seed=None,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K):
    """Flash attention over (batch, heads, seq, head_dim) inputs; ``v`` may
    have a head size of its own (the output's).

    ``bias`` is an optional additive k-position bias of shape (batch, seq_k)
    — the padding-mask case.  ``bias`` is treated as NON-DIFFERENTIABLE:
    it passes through ``stop_gradient``, so a learned bias (ALiBi-style)
    passed here silently receives zero gradient.  Use the composable
    ``ops.attention`` path for trainable biases.  ``seed`` (int32 scalar
    array) drives in-kernel dropout when ``dropout_rate > 0``.
    """
    b, h, s, d = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    bq = min(block_q, s)
    bk = min(block_k, s)
    while s % bq:
        bq //= 2
    while s % bk:
        bk //= 2
    if not _cfg.interpret() and (bq < 128 or bk < 128):
        # Mosaic lane constraint: the (1, 1, block) lse/bias/delta blocks
        # need block % 128 == 0.  supported() guarantees s % 128 == 0, so
        # 128 always divides s here; reject explicit smaller blocks.
        if s % 128:
            raise ValueError(
                f"flash_attention requires seq_len % 128 == 0 on TPU, got {s}")
        bq, bk = max(bq, 128), max(bk, 128)
    # bias is non-differentiable (padding masks carry no trainable state;
    # the docstring carries the learned-bias warning)
    bias, seed = _normalize_bias_seed(bias, seed, b, s)
    merged = lambda x: x.reshape(b * h, s, x.shape[-1])
    _cfg.record_call("flash_attention")
    with jax.named_scope("pallas.flash_attention"):
        out = _flash_attention_bhsd(merged(q), merged(k), merged(v), bias,
                                    seed, sm_scale, causal,
                                    float(dropout_rate), bq, bk)
    return out.reshape(b, h, s, v.shape[-1])
