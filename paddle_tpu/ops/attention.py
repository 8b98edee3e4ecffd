"""Attention ops: reference jnp implementation + TPU flash-attention dispatch.

Reference parity: fused/multihead_matmul (inference-only fusion in the
reference, SURVEY.md §5.7); here attention is a first-class training op.
Inputs follow the (batch, num_heads, seq, head_dim) convention.  What the
entry points take:

* `scaled_dot_product_attention`, `flash_attention`: q `(b, h, s, d)`,
  k `(b, h_kv, s, d)`, v `(b, h_kv, s, d_v)` with `h % h_kv == 0` (query
  head j attends key/value head `j // (h / h_kv)`; `h_kv == h` is plain
  multi-head attention) and any `d_v` (the output's head size).
* `flash_attention_packed`: q, k, v all `(b, s, h·d)`, one head count and
  one head size.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .pallas import config as _pcfg


def scaled_dot_product_attention(q, k, v, attn_mask=None, dropout_p=0.0,
                                 is_causal=False, scale=None, training=True):
    """Reference attention: q (b, h, s, d), k (b, h_kv, s, d),
    v (b, h_kv, s, d_v) -> (b, h, s, d_v); with fewer key/value heads than
    query heads each is repeated for the query heads that share it.

    ``attn_mask`` is additive (float, broadcastable to (b, h, sq, sk)) or
    boolean (True = keep).
    """
    d = q.shape[-1]
    if k.shape[1] != q.shape[1]:
        k, v = (jnp.repeat(t, q.shape[1] // t.shape[1], axis=1)
                for t in (k, v))
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if is_causal:
        sq, sk = s.shape[-2], s.shape[-1]
        causal = jnp.tril(jnp.ones((sq, sk), dtype=bool))
        s = jnp.where(causal, s, -1e30)
    if attn_mask is not None:
        if attn_mask.dtype == jnp.bool_:
            s = jnp.where(attn_mask, s, -1e30)
        else:
            s = s + attn_mask.astype(jnp.float32)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    if dropout_p > 0.0 and training:
        from ..core import random as _random

        keep = jax.random.bernoulli(_random.next_key(), 1.0 - dropout_p, p.shape)
        p = jnp.where(keep, p / (1.0 - dropout_p), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _as_padding_bias(attn_mask, b, s):
    """If ``attn_mask`` is a k-position-only mask — shape broadcastable to
    (b, 1, 1, s) — return the equivalent additive (b, s) bias; else None.
    This is the BERT/ERNIE padding-mask shape the Pallas kernel streams
    in-kernel instead of materializing an O(S^2) score mask."""
    if attn_mask is None:
        return jnp.zeros((b, s), jnp.float32)
    if attn_mask.ndim != 4 or attn_mask.shape[1] != 1 or attn_mask.shape[2] != 1:
        return None
    if attn_mask.shape[0] not in (1, b) or attn_mask.shape[3] != s:
        return None
    m = attn_mask[:, 0, 0, :]
    if m.dtype == jnp.bool_:
        m = jnp.where(m, 0.0, -1e30)
    return jnp.broadcast_to(m.astype(jnp.float32), (b, s))


def draw_dropout_seed(n_shards: int = 1, rate: float = 1.0):
    """int32 seeds from the framework key stream for in-kernel dropout, one
    per data-parallel shard the kernel is dispatched over (the kernels key
    their streams on shard-LOCAL coordinates, so shards sharing a seed
    would draw the same masks); zeros, and no key drawn, when ``rate`` is
    0.  Single definition so the seeding convention used by the flash and
    fused-LN kernels cannot drift between call sites."""
    from ..core import random as _random

    if rate <= 0.0:
        return jnp.zeros((n_shards,), jnp.int32)
    return jax.random.randint(_random.next_key(), (n_shards,),
                              jnp.iinfo(jnp.int32).min,
                              jnp.iinfo(jnp.int32).max, jnp.int32)


def flash_attention_packed(q, k, v, num_heads, attn_mask=None,
                           dropout_p=0.0, is_causal=False, scale=None,
                           training=True):
    """Packed-layout dispatch: q/k/v are (batch, seq, heads*head_dim) —
    the projection output, no head transposes (see
    pallas/flash_attention_packed.py).  Returns (batch, seq, heads*head_dim)
    or None when the kernel path is not eligible (caller falls back to the
    standard split-head path)."""
    from ..parallel import mesh as _mesh
    from .pallas import flash_attention_packed as fap

    b, s, packed = q.shape
    hd = packed // num_heads
    # cheap gates first: every eager fallback call would otherwise build
    # and discard the mask conversion
    if not (_pcfg.kernel_enabled("use_flash_attention")
            and q.shape == k.shape == v.shape
            and fap.supported(s, num_heads, hd)):
        return None
    bias = _as_padding_bias(attn_mask, b, s)
    if bias is None:
        return None
    n = _mesh.batch_shards(b)
    if not n:
        _pcfg.record_fallback("flash_attention_packed", "partial_manual_mesh")
        return None
    rate = float(dropout_p) if training else 0.0
    seed = draw_dropout_seed(n, rate)

    def kernel(q, k, v, bias, seed):
        return fap.flash_attention_packed(q, k, v, num_heads, bias=bias,
                                          sm_scale=scale, causal=is_causal,
                                          dropout_rate=rate, seed=seed)

    return _mesh.per_batch_shard(kernel, n, (q, k, v, bias, seed))


def flash_attention(q, k, v, attn_mask=None, dropout_p=0.0, is_causal=False,
                    scale=None, training=True):
    """Dispatch to the Pallas flash-attention kernel when the backend/shape
    allow; otherwise fall back to the jnp reference implementation.

    q is (b, h, s, d), k (b, h_kv, s, d), v (b, h_kv, s, d_v): ``v`` may
    differ from ``q`` and ``k`` in its head size (latent attention: q·k at
    192, v at 128), and ``k`` and ``v`` may have fewer heads than ``q``, a
    divisor of its count (grouped-query attention: the kernels read a
    key/value head once for the query heads that share it).  Anything else
    is counted as ``shapes``.  Kernel-eligible masks are k-position padding
    masks (shape (b,1,1,s)); arbitrary (b,h,sq,sk) masks fall back.
    Dropout runs in-kernel with a replayable position-keyed RNG.  A causal,
    mask-free call that misses the enabled kernel is counted in
    ``pallas.fallbacks`` with its reason, never silent: at long sequences
    the fallback holds the whole float32 score square."""
    from ..parallel import mesh as _mesh
    from .pallas import flash_attention as fa

    b, h, s, d = q.shape
    rate = float(dropout_p) if training else 0.0
    bias = _as_padding_bias(attn_mask, b, s)
    reason = None
    if not _pcfg.kernel_enabled("use_flash_attention"):
        pass        # no kernel on this backend, or switched off: nothing missed
    elif bias is None:
        reason = "mask"
    elif not (k.shape == (b, k.shape[1], s, d) and h % k.shape[1] == 0
              and v.shape[:-1] == k.shape[:-1]):
        reason = "shapes"
    elif not fa.supported(s, d, v.shape[-1]):
        reason = "unsupported"
    else:
        n = _mesh.batch_shards(b)
        if n:
            seed = draw_dropout_seed(n, rate)

            def kernel(q, k, v, seed, bias=None):
                return fa.flash_attention(q, k, v, bias=bias, sm_scale=scale,
                                          causal=is_causal, dropout_rate=rate,
                                          seed=seed)

            # no mask is no bias operand: the kernels then neither stream
            # nor add one (a zero bias costs a pass over every score tile)
            operands = (q, k, v, seed) + (() if attn_mask is None else (bias,))
            return _mesh.per_batch_shard(kernel, n, operands)
        reason = "partial_manual_mesh"
    if reason == "partial_manual_mesh" or (
            reason and is_causal and attn_mask is None):
        _pcfg.record_fallback("flash_attention", reason)
    return scaled_dot_product_attention(q, k, v, attn_mask=attn_mask,
                                        dropout_p=dropout_p, is_causal=is_causal,
                                        scale=scale, training=training)
