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
read-out) and only `S_out → S_in` runs along the sequence, one step a chunk.

Two things are part of the mathematics, not of tuning.  The decay between
two positions is formed from the **difference** `cs_i − cs_j ≤ 0`, never as
`exp(cs_i) · exp(−cs_j)`: a chunk's sum reaches −200 and beyond, where the
factored form is `0 · inf`.  And the decay sums and every exponential of
them are float32 whatever the operands' dtype is; the products take the
operands' dtype with float32 accumulation.

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

        # within a chunk: L_ij = exp(cs_i − cs_j) for i ≥ j, else 0 (the
        # difference masked before the exponential: above the diagonal it is
        # positive without bound, and its gradient would be 0 · inf)
        seen = jnp.tril(jnp.ones((chunk, chunk), bool))
        decay = jnp.exp(jnp.where(seen, cs[..., :, None] - cs[..., None, :],
                                  -jnp.inf))
        scores = jnp.einsum("bcin,bcjn->bcij", Cc, Bc,
                            preferred_element_type=f32)
        weights = scores[:, :, None] * decay * dth[..., None, :]
        y = jnp.einsum("bchij,bcjhp->bcihp", weights.astype(x.dtype), xc,
                       preferred_element_type=f32)

        # each chunk's own contribution to its end state, then the carry:
        # S_in of chunk c from S_in and the contribution of chunk c − 1
        to_end = (jnp.exp(last - cs) * dth).transpose(0, 1, 3, 2)
        local = jnp.einsum("bcjhp,bcjn->bchpn",
                           (xc * to_end[..., None]).astype(x.dtype), Bc,
                           preferred_element_type=f32)
        whole = jnp.exp(last)[..., None]            # [b, nc, h, 1, 1]

        def carry(state, step):
            keep, add = step
            return keep * state + add, state

        _, carried = lax.scan(
            carry, jnp.zeros((b, h, p, Bc.shape[-1]), f32),
            (jnp.moveaxis(whole, 1, 0), jnp.moveaxis(local, 1, 0)))
        carried = jnp.moveaxis(carried, 0, 1)       # [b, nc, h, p, n]: S_in
        read = jnp.einsum("bcin,bchpn->bcihp", Cc, carried.astype(x.dtype),
                          preferred_element_type=f32)
        y = y + read * jnp.exp(cs).transpose(0, 1, 3, 2)[..., None]
        y = y + D.astype(f32)[:, None] * xc.astype(f32)
        return y.astype(x.dtype).reshape(b, s, h, p)
