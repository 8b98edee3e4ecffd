"""The state-space scan of a Mamba-2 mixer (SSD, "state space duality"),
in its chunked form.

Per head, with `x_t ∈ ℝ^p`, `B_t, C_t ∈ ℝ^n` (shared by every head: one
group), a step `dt_t > 0` and a decay rate `A < 0`:

    S_t = a_t · S_{t−1} + dt_t · x_t B_tᵀ      a_t = exp(dt_t · A),  S_0 = 0
    y_t = S_t C_t + D · x_t

A state `S ∈ ℝ^{p×n}` a head is carried along the sequence.  The chunked
form cuts the sequence into chunks of `chunk` positions.  With `cs_i` the
running sum of `dt · A` inside a chunk (inclusive), a chunk whose carried-in
state is `S_in` gives

    y_i = Σ_{j ≤ i} (C_i · B_j) · exp(cs_i − cs_j) · dt_j · x_j      (within)
          + exp(cs_i) · S_in C_i                                     (carried)
    S_out = exp(cs_last) · S_in + Σ_j exp(cs_last − cs_j) · dt_j · x_j B_jᵀ

so the work inside a chunk is matrix products (`C·Bᵀ` once for all heads,
the masked and decayed scores times `x`, the chunk's state and its
read-out).  Between chunks the state is passed by one product too, not by a
loop over chunks (the state passing of Dao & Gu 2024, "Transformers are
SSMs", Listing 1).  With `λ_k` chunk k's decay sum (`cs_last` of chunk k)
and `local_z` chunk z's own term of its `S_out`,

    seg_cz   = Σ_{z < k < c} λ_k                     for z < c
    S_in_c   = Σ_{z < c} exp(seg_cz) · local_z       one product over z

a `[nc, nc]` matrix of decays a head against every chunk's local state:
16² · 64 · 64 · 128 · 2 = 268 MFLOP at 16 chunks of a 4,096-position row,
275 GFLOP at 512 chunks, where the layers over those positions need some
625 TFLOP; so one form serves every length.  Its operands stay float32 and
the product runs at `HIGHEST` precision: on the TPU a float32 product at
default precision rounds its operands to bfloat16, a coarser result than the
float32 multiply-add of the recurrence it replaces.

Two things are part of the mathematics, not of tuning.  The decay between
two positions is formed from the **difference** `cs_i − cs_j ≤ 0`, never as
`exp(cs_i) · exp(−cs_j)`: a chunk's sum reaches −200 and beyond, where the
factored form is `0 · inf`.  Between chunks `seg` is a masked **sum** of
the chunks' own decay sums, never a difference of running totals, which
reach −3,200 over 16 chunks and would lose the small segments.  And the
decay sums and every exponential of them are float32 whatever the operands'
dtype is; the products within a chunk take the operands' dtype with float32
accumulation, the one between chunks float32 operands.

Written in `jax.numpy` and differentiated by JAX.  One implementation: no
flag, option or environment variable selects another.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..utils import monitor
from ..utils import xprof as _xprof

_m_calls = monitor.counter(
    "ssm.scan_calls",
    "state_space_scan calls (trace-time), labeled by implementation and "
    "chunk length.", labelnames=("impl", "chunk"))


def _gaps(n: int):
    """[n, n]: i − j, how many steps row i lies past column j."""
    return jnp.arange(n)[:, None] - jnp.arange(n)


def state_space_scan(x, dt, A, B, C, D, chunk: int):
    """x [b, s, h, p], dt [b, s, h] (positive: after the softplus),
    A [h] (negative), B and C [b, s, n], D [h] -> y [b, s, h, p] in x's
    dtype.  `chunk` must divide s.  Everything lies under the scope `ssd`."""
    b, s, h, p = x.shape
    if s % chunk:
        raise ValueError(f"chunk {chunk} does not divide the sequence {s}")
    _m_calls.inc(impl="xla", chunk=str(chunk))
    nc, f32 = s // chunk, jnp.float32
    with jax.named_scope(_xprof.SCOPE_SSD):
        xc = x.reshape(b, nc, chunk, h, p)
        Bc, Cc = (t.reshape(b, nc, chunk, -1) for t in (B, C))
        # [b, nc, h, chunk]: heads before positions, as the scores have them
        dth = dt.astype(f32).reshape(b, nc, chunk, h).transpose(0, 1, 3, 2)
        cs = jnp.cumsum(dth * A.astype(f32)[:, None], axis=-1)
        last = cs[..., -1:]

        # within a chunk: L_ij = exp(cs_i − cs_j) for i > j, 1 for i = j,
        # else 0 (the difference masked before the exponential: above the
        # diagonal it is positive without bound, and its gradient would be
        # 0 · inf; on it the difference is 0 by definition, and formed, its
        # gradient would be a row's sum less a column's, both as large as
        # the steepest step's term: all round-off)
        gap = _gaps(chunk)
        decay = jnp.exp(jnp.where(gap > 0, cs[..., :, None] - cs[..., None, :],
                                  jnp.where(gap == 0, 0.0, -jnp.inf)))
        scores = jnp.einsum("bcin,bcjn->bcij", Cc, Bc,
                            preferred_element_type=f32)
        weights = scores[:, :, None] * decay * dth[..., None, :]
        y = jnp.einsum("bchij,bcjhp->bcihp", weights.astype(x.dtype), xc,
                       preferred_element_type=f32)

        # each chunk's own contribution to its end state, heads first
        # (turned after the product: XLA:CPU has no bf16 product that writes
        # the heads-first order itself)
        to_end = (jnp.exp(last - cs) * dth).transpose(0, 1, 3, 2)
        local = jnp.einsum("bcjhp,bcjn->bchpn",
                           (xc * to_end[..., None]).astype(x.dtype), Bc,
                           preferred_element_type=f32).transpose(0, 2, 1, 3, 4)
        # seg_cz = Σ_{z<k<c} λ_k, a running sum down c of λ_{c−1} kept
        # where z + 1 < c: only the segment's own terms are ever added
        lam = last[..., 0].transpose(0, 2, 1)               # [b, h, nc]
        before = jnp.pad(lam, ((0, 0), (0, 0), (1, 0)))[..., :-1, None]
        gap = _gaps(nc)
        seg = jnp.cumsum(jnp.where(gap >= 2, before, 0.0), axis=-2)
        passed = jnp.exp(jnp.where(gap >= 1, seg, -jnp.inf))    # [b, h, c, z]
        # the state each chunk starts from, S_in_c = Σ_{z<c} exp(seg_cz) ·
        # local_z: [c, z] × [z, p·n] a head, one batch of b·h products
        state = Bc.shape[-1]
        carried = lax.dot_general(
            passed.reshape(b * h, nc, nc), local.reshape(b * h, nc, p * state),
            (((2,), (1,)), ((0,), (0,))), precision=lax.Precision.HIGHEST,
            preferred_element_type=f32).reshape(b, h, nc, p, state)
        read = jnp.einsum("bcin,bhcpn->bcihp", Cc, carried.astype(x.dtype),
                          preferred_element_type=f32)
        y = y + read * jnp.exp(cs).transpose(0, 1, 3, 2)[..., None]
        y = y + D.astype(f32)[:, None] * xc.astype(f32)
        return y.astype(x.dtype).reshape(b, s, h, p)
