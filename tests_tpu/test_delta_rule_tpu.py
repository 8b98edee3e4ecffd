"""TPU-only: the chunked gated delta rule (`ops/delta_rule.py:
gated_delta_rule`) at `olmo-hybrid-7b.s4096`'s shape — one row of 4,096
positions, 30 heads of 96 keys and 192 values, chunks of 64, bfloat16 q, k
and v, float32 decay and beta — against the recurrence itself, token by
token in float32 on the chip.  On the TPU a float32 product at default
precision rounds its operands to bfloat16, so the recurrence's products
run at `HIGHEST`: what is compared is the chunked rule's own rounding, its
bfloat16 operands, and nothing of the reference's.

The rule's forward and forward + backward milliseconds a call are printed
(`-s`), the rule apart from the step.
"""
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from paddle_tpu.ops.delta_rule import gated_delta_rule

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="the TPU's own rounding of products is what is measured")

B_, S, H, DK, DV, CHUNK = 1, 4096, 30, 96, 192, 64
BLOCK = 64          # positions in a checkpointed block of the recurrence
HIGHEST = lax.Precision.HIGHEST


def recurrence(q, k, v, g, beta):
    """S_t = exp(g_t)(S_{t-1} - beta_t k_t (k_tᵀ S_{t-1})) + beta_t k_t
    v_tᵀ, o_t = S_tᵀ q_t / √dk in float32, one position at a time; blocks
    of `BLOCK` positions under `jax.checkpoint`, so that a 2.2 MB state is
    kept once a block."""
    b, s, h, dk = q.shape

    def position(state, at):
        q_t, k_t, v_t, g_t, b_t = at
        read = jnp.einsum("bhk,bhkv->bhv", k_t, state, precision=HIGHEST)
        wk = (b_t[..., None] * k_t)[..., None]
        state = jnp.exp(g_t)[..., None, None] * (
            state - wk * read[..., None, :]) + wk * v_t[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t,
                                 precision=HIGHEST) / math.sqrt(dk)

    @jax.checkpoint
    def block(state, at):
        return lax.scan(position, state, at)

    along = tuple(jnp.moveaxis(t, 1, 0).reshape(s // BLOCK, BLOCK, b,
                                                *t.shape[2:])
                  for t in (q, k, v, g, beta))
    _, o = lax.scan(block, jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32),
                    along)
    return jnp.moveaxis(o.reshape(s, b, h, -1), 0, 1)


def inputs(decay: float, seed: int):
    """bfloat16 unit q and k and v as the mixer hands them over; float32 g
    (about `decay` a position) and beta in (0, 2)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (B_, S, H, DK))).astype(jnp.bfloat16)
    k = unit(jax.random.normal(ks[1], (B_, S, H, DK))).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (B_, S, H, DV)).astype(jnp.bfloat16)
    g = decay * jax.nn.softplus(jax.random.normal(ks[3], (B_, S, H))) / 0.69
    beta = 2.0 * jax.nn.sigmoid(2.0 * jax.random.normal(ks[4], (B_, S, H)))
    return q, k, v, g, beta


def _ms_a_call(fn, x, *rest, calls: int = 10) -> float:
    """Device milliseconds a call: `calls` calls chained through x inside
    one program, best of three."""
    def chained(x, *rest):
        return lax.fori_loop(0, calls, lambda _, x: fn(x, *rest), x)
    run = jax.jit(chained)
    jax.block_until_ready(run(x, *rest))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(run(x, *rest))
        best = min(best, (time.perf_counter() - t0) / calls * 1e3)
    return best


@pytest.mark.parametrize("decay", [-0.7, -0.01])
def test_the_rule_is_the_recurrence_at_the_cells_shape(decay):
    """A log decay of -0.7 a position is the cell's (a_log and dt_bias drawn
    near 0); -0.01 carries a state across all 64 chunks.  Values and the
    gradient of every input within bfloat16 rounding of the float32
    recurrence on the same (bfloat16-valued) operands."""
    args = inputs(decay, seed=int(-100 * decay))
    up = tuple(a.astype(jnp.float32) for a in args)
    w = jax.random.normal(jax.random.PRNGKey(7), (B_, S, H, DV))

    # the cotangent is an argument: as a closure it would be a constant the
    # compiler folds for seconds
    def rule_vjp(w, *a):
        o, back = jax.vjp(lambda *x: gated_delta_rule(*x, CHUNK), *a)
        return (o,) + back(w.astype(o.dtype))

    def ref_vjp(w, *a):
        o, back = jax.vjp(recurrence, *a)
        return (o,) + back(w)

    got = jax.jit(rule_vjp)(w, *args)
    want = jax.jit(ref_vjp)(w, *up)
    assert got[0].dtype == jnp.bfloat16
    for name, a, b in zip("o dq dk dv dg dbeta".split(), got, want):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        assert np.isfinite(a).all(), name
        err = np.linalg.norm(a - b) / np.linalg.norm(b)
        worst = np.abs(a - b).max() / np.abs(b).max()
        print(f"decay {decay}: {name} relative error {err:.2e} "
              f"(worst element {worst:.2e} of the largest)")
        assert err < 2e-2 and worst < 5e-2, name


def test_the_rules_own_time_at_the_cells_shape():
    q, k, v, g, beta = inputs(-0.7, seed=70)
    w = jax.random.normal(jax.random.PRNGKey(7), (B_, S, H, DV)).astype(
        jnp.bfloat16)

    def forward(v, q, k, g, beta):
        return gated_delta_rule(q, k, v, g, beta, CHUNK)

    def both(v, w, q, k, g, beta):
        o, back = jax.vjp(lambda *x: gated_delta_rule(*x, CHUNK),
                          q, k, v, g, beta)
        grads = back(w)
        rest = sum(jnp.sum(t.astype(jnp.float32)) for t in (o,) + grads)
        return (v.astype(jnp.float32) + 1e-3 * grads[2].astype(jnp.float32)
                + 1e-30 * rest).astype(v.dtype)

    fwd = _ms_a_call(forward, v, q, k, g, beta)
    fwd_bwd = _ms_a_call(both, v, w, q, k, g, beta)
    print(f"gated_delta_rule at b {B_}, s {S}, {H} heads of {DK}/{DV}, "
          f"chunk {CHUNK}, bf16: forward {fwd:.3f} ms a call, forward + "
          f"backward {fwd_bwd:.3f} ms a call")
    assert 0 < fwd < fwd_bwd
