"""The gated delta rule of a Gated DeltaNet mixer (Yang, Kautz &
Hatamizadeh 2024, "Gated Delta Networks"), in its chunked form.

Per head, with keys and queries `k_t, q_t ∈ ℝ^{dk}`, values `v_t ∈ ℝ^{dv}`,
a log decay `g_t ≤ 0` (`α_t = exp(g_t)`) and a write strength `β_t`:

    S_t = α_t (I − β_t k_t k_tᵀ) S_{t−1} + β_t k_t v_tᵀ      S_0 = 0
    o_t = S_tᵀ q_t / √dk

a state `S ∈ ℝ^{dk×dv}` a head carried along the sequence.  The transition
is not diagonal (unlike `ops/ssd.py`'s), and with β up to 2 it has negative
eigenvalues.  Written as `S_t = α_t S_{t−1} + k_t δ_tᵀ` with the value it
writes `δ_t = β_t (v_t − α_t S_{t−1}ᵀ k_t)`, a chunk of C positions whose
carried-in state is `S` has, with `G_i` the running sum of g inside the
chunk (inclusive) and `Γ_ij = exp(G_i − G_j)`,

    (I + A) Δ = diag(β) V − diag(β) (K ∘ e^G) S
    A = strict_lower(diag(β) (K Kᵀ ∘ Γ))

so `Δ = U − W S` with `T = (I + A)⁻¹`, `W = T diag(β)(K ∘ e^G)` and
`U = T diag(β) V` (the UT / WY form), and

    O      = (Q ∘ e^G) S + (Q Kᵀ ∘ Γ ∘ causal) Δ
    S_next = e^{G_C} S + K̃ᵀ Δ                 K̃_j = e^{G_C − G_j} k_j

`T` is solved exactly, in float32 at `HIGHEST` precision, by doubling the
blocks of a blocked triangular inverse (log₂C steps of two products; its
entries pass 1 where β does).  The nilpotent series `(I − A)(I + A²)(I +
A⁴)…` is exact too but cancels terms that grow as C choose n: with keys
alike and β near 2 it loses what float32 holds.

Everything but the passing of the state from chunk to chunk is one batch of
products over all chunks; the passing is a loop over the chunks of two
products a step, `W S` and `K̃ᵀ Δ`, since the dense transitions do not
commute.

As in `ops/ssd.py`, the decay between two positions is formed from the
**difference** `G_i − G_j`, masked before its exponential, never as
`e^{G_i} · e^{−G_j}`; the sums, the exponentials, A, T and the carried
state are float32 whatever the operands' dtype; the products take the
operands' dtype with float32 accumulation (the state and T rounded to it
where they enter a product, as the family's published kernels do).

Written in `jax.numpy`; the gradient is JAX's, held in a `custom_vjp` only
so that the forward and the backward pass are each counted where they are
traced.  One implementation: no flag, option or environment variable
selects another.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from ..utils import monitor
from ..utils import xprof as _xprof

# the carried state's dtype; float32 (`tests/test_olmo_hybrid.py` shows a
# bfloat16 state falls outside the recurrence's tolerance)
STATE_DTYPE = jnp.float32

_m_calls = monitor.counter(
    "gdn.delta_calls",
    "gated_delta_rule traces, labeled by pass (fwd, bwd) and chunk length.",
    labelnames=("pass", "chunk"))


def _gaps(n: int):
    """[n, n]: i − j, how many steps row i lies past column j."""
    return jnp.arange(n)[:, None] - jnp.arange(n)


def unit_lower_inverse(a):
    """(I + a)⁻¹ of `a` [..., C, C] strictly lower triangular, float32, by
    doubling: the inverses of the diagonal blocks of m rows, two at a time,
    give those of 2m rows, `[[T₁, 0], [−T₂ a₂₁ T₁, T₂]]` (the blocked
    triangular inverse, as stable as substitution; C padded to a power of
    two with rows and columns of I)."""
    c = a.shape[-1]
    size = 1 << (c - 1).bit_length()
    pad = [(0, 0)] * (a.ndim - 2) + [(0, size - c)] * 2
    a = jnp.pad(a, pad)
    lead = a.shape[:-2]
    mm = functools.partial(jnp.matmul, precision=lax.Precision.HIGHEST)
    inv = jnp.ones(lead + (size, 1, 1), a.dtype)     # the 1 x 1 blocks
    m = 1
    while m < size:
        n = size // (2 * m)
        # the diagonal blocks of 2m rows of a, and the lower-left m x m of each
        blocks = jnp.moveaxis(jnp.diagonal(
            a.reshape(lead + (n, 2 * m, n, 2 * m)), axis1=-4, axis2=-2),
            -1, -3)                                  # [..., n, 2m, 2m]
        first, second = inv[..., 0::2, :, :], inv[..., 1::2, :, :]
        low = -mm(mm(second, blocks[..., m:, :m]), first)
        zero = jnp.zeros_like(low)
        inv = jnp.concatenate([jnp.concatenate([first, zero], -1),
                               jnp.concatenate([low, second], -1)], -2)
        m *= 2
    return inv.reshape(lead + (size, size))[..., :c, :c]


def _chunked(q, k, v, g, beta, chunk: int):
    b, s, h, dk = q.shape
    nc, f32, dt = s // chunk, jnp.float32, q.dtype

    def by_chunk(t):            # [b, s, h, ...] -> [b, h, nc, C, ...]
        t = t.reshape(b, nc, chunk, h, *t.shape[3:])
        return jnp.moveaxis(t, 3, 1)

    qc, kc, vc = by_chunk(q), by_chunk(k), by_chunk(v)
    gc, bc = by_chunk(g.astype(f32)), by_chunk(beta.astype(f32))
    G = jnp.cumsum(gc, axis=-1)                     # [b, h, nc, C]
    gap = _gaps(chunk)
    # Γ_ij for i ≥ j, else 0 (the difference masked before the
    # exponential: above the diagonal it is positive without bound)
    decay = jnp.exp(jnp.where(gap > 0, G[..., :, None] - G[..., None, :],
                              jnp.where(gap == 0, 0.0, -jnp.inf)))
    kk = jnp.einsum("bhcid,bhcjd->bhcij", kc, kc, preferred_element_type=f32)
    a = jnp.where(gap > 0, kk * decay, 0.0) * bc[..., None]
    t = unit_lower_inverse(a).astype(dt)
    w = jnp.einsum("bhcij,bhcjd->bhcid", t,
                   (kc * (bc * jnp.exp(G))[..., None]).astype(dt),
                   preferred_element_type=f32).astype(dt)
    u = jnp.einsum("bhcij,bhcjd->bhcid", t, (vc * bc[..., None]).astype(dt),
                   preferred_element_type=f32)
    k_end = (kc * jnp.exp(G[..., -1:] - G)[..., None]).astype(dt)
    fade = jnp.exp(G[..., -1])                      # [b, h, nc]

    def one_chunk(state, at):
        w_c, u_c, k_c, fade_c = at
        delta = u_c - jnp.einsum("bhid,bhdv->bhiv", w_c, state.astype(dt),
                                 preferred_element_type=f32)
        written = jnp.einsum("bhid,bhiv->bhdv", k_c, delta.astype(dt),
                             preferred_element_type=f32)
        return (fade_c[..., None, None] * state + written).astype(
            STATE_DTYPE), (state, delta)

    # the state each chunk starts from and the values it writes, chunks first
    _, (states, deltas) = lax.scan(
        one_chunk, jnp.zeros((b, h, dk, v.shape[-1]), STATE_DTYPE),
        tuple(jnp.moveaxis(x, 2, 0) for x in (w, u, k_end, fade)))
    states, deltas = (jnp.moveaxis(x, 0, 2).astype(dt)
                      for x in (states, deltas))
    qs = (qc * (jnp.exp(G) / math.sqrt(dk))[..., None]).astype(dt)
    scores = jnp.einsum("bhcid,bhcjd->bhcij", qc, kc,
                        preferred_element_type=f32) * decay / math.sqrt(dk)
    o = jnp.einsum("bhcid,bhcdv->bhciv", qs, states,
                   preferred_element_type=f32) \
        + jnp.einsum("bhcij,bhcjv->bhciv", scores.astype(dt), deltas,
                     preferred_element_type=f32)
    return jnp.moveaxis(o, 1, 3).reshape(b, s, h, -1).astype(dt)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _rule(q, k, v, g, beta, chunk):
    _m_calls.inc(**{"pass": "fwd", "chunk": str(chunk)})
    return _chunked(q, k, v, g, beta, chunk)


def _rule_fwd(q, k, v, g, beta, chunk):
    _m_calls.inc(**{"pass": "fwd", "chunk": str(chunk)})
    return jax.vjp(functools.partial(_chunked, chunk=chunk), q, k, v, g, beta)


def _rule_bwd(chunk, vjp, grad):
    _m_calls.inc(**{"pass": "bwd", "chunk": str(chunk)})
    with jax.named_scope(_xprof.SCOPE_DELTA):
        return vjp(grad)


_rule.defvjp(_rule_fwd, _rule_bwd)


def gated_delta_rule(q, k, v, g, beta, chunk: int):
    """q and k [b, s, h, dk], v [b, s, h, dv], g and beta [b, s, h] (g the
    log decay, ≤ 0) -> o [b, s, h, dv] in q's dtype, the 1/√dk inside.
    `chunk` must divide s.  Everything lies under the scope `delta`."""
    s = q.shape[1]
    if s % chunk:
        raise ValueError(f"chunk {chunk} does not divide the sequence {s}")
    with jax.named_scope(_xprof.SCOPE_DELTA):
        return _rule(q, k, v, g, beta, chunk)
