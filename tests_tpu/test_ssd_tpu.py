"""TPU-only: the chunked state-space scan (`ops/ssd.py:state_space_scan`) at
`granite-4.0-h-micro.s4096`'s shape — one row of 4,096 positions, 64 heads
of 64, a state of 128, chunks of 256, bfloat16 operands — against the
recurrence itself, token by token in float32 on the chip.  On the TPU a
float32 product at default precision rounds its operands to bfloat16, so
the recurrence's one product runs at `HIGHEST`: what is compared is the
scan's own rounding, its bfloat16 operands, and nothing of the reference's.

The scan's forward and forward + backward milliseconds a call are printed
(`-s`), the scan apart from the step.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from paddle_tpu.ops.ssd import state_space_scan

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="the TPU's own rounding of products is what is measured")

B_, S, H, P, N, CHUNK = 1, 4096, 64, 64, 128, 256
BLOCK = 64          # positions in a checkpointed block of the recurrence
HIGHEST = lax.Precision.HIGHEST


def recurrence(x, dt, A, B, C, D):
    """S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_tᵀ, y_t = S_t C_t + D x_t in
    float32, one position at a time; blocks of `BLOCK` positions under
    `jax.checkpoint`, so that a 2 MB state is kept once a block."""
    b, s, h, p = x.shape

    def position(state, at):
        x_t, dt_t, B_t, C_t = at
        state = jnp.exp(dt_t * A)[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., None] * B_t[:, None, None, :]
        return state, jnp.einsum("bhpn,bn->bhp", state, C_t,
                                 precision=HIGHEST) + D[:, None] * x_t

    @jax.checkpoint
    def block(state, at):
        return lax.scan(position, state, at)

    along = tuple(jnp.moveaxis(t, 1, 0).reshape(s // BLOCK, BLOCK, b,
                                                *t.shape[2:])
                  for t in (x, dt, B, C))
    _, y = lax.scan(block, jnp.zeros((b, h, p, B.shape[-1]), jnp.float32),
                    along)
    return jnp.moveaxis(y.reshape(s, b, h, p), 0, 1)


def inputs(chunk_sum: float, seed: int):
    """bfloat16 x, B, C as the mixer hands them over; float32 dt, A, D;
    `dt·A` adds up to about `chunk_sum` a chunk."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (B_, S, H, P)).astype(jnp.bfloat16)
    B = (jax.random.normal(ks[1], (B_, S, N)) / 4).astype(jnp.bfloat16)
    C = (jax.random.normal(ks[2], (B_, S, N)) / 4).astype(jnp.bfloat16)
    A = -jnp.exp(0.02 * jax.random.normal(ks[3], (H,)))
    dt = jax.nn.softplus(jax.random.normal(ks[4], (B_, S, H)))
    dt = dt * (-chunk_sum / CHUNK / jnp.mean(dt * -A))
    D = 1.0 + 0.02 * jax.random.normal(ks[5], (H,))
    return x, dt, A, B, C, D


def _ms_a_call(fn, x, *rest, calls: int = 10) -> float:
    """Device milliseconds a call: `calls` calls chained through x (the
    result, or its gradient, has x's shape and is the next call's x) inside
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


@pytest.mark.parametrize("chunk_sum", [-180.0, -2.0])
def test_the_scan_is_the_recurrence_at_the_cells_shape(chunk_sum):
    """A chunk's decay sum of -180 is the cell's (a token's decay 0.3-0.7);
    -2 carries a state across all 16 chunks.  Values and the gradient of
    every input within bfloat16 rounding of the float32 recurrence on the
    same (bfloat16-valued) operands."""
    args = inputs(chunk_sum, seed=int(-chunk_sum))
    up = tuple(a.astype(jnp.float32) for a in args)
    g = jax.random.normal(jax.random.PRNGKey(7), (B_, S, H, P))

    # the cotangent is an argument: as a closure it would be a constant of
    # 64 MB, which the compiler folds for seconds
    def scan_vjp(g, *a):
        y, back = jax.vjp(lambda *q: state_space_scan(*q, CHUNK), *a)
        return (y,) + back(g.astype(y.dtype))

    def ref_vjp(g, *a):
        y, back = jax.vjp(recurrence, *a)
        return (y,) + back(g)

    got = jax.jit(scan_vjp)(g, *args)
    want = jax.jit(ref_vjp)(g, *up)
    assert got[0].dtype == jnp.bfloat16
    for name, a, b in zip("y dx ddt dA dB dC dD".split(), got, want):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        assert np.isfinite(a).all(), name
        err = np.linalg.norm(a - b) / np.linalg.norm(b)
        worst = np.abs(a - b).max() / np.abs(b).max()
        print(f"chunk sum {chunk_sum}: {name} relative error {err:.2e} "
              f"(worst element {worst:.2e} of the largest)")
        assert err < 1e-2 and worst < 3e-2, name


def test_the_scans_own_time_at_the_cells_shape():
    x, dt, A, B, C, D = inputs(-180.0, seed=180)
    g = jax.random.normal(jax.random.PRNGKey(7), x.shape).astype(x.dtype)

    def forward(x, *rest):
        return state_space_scan(x, *rest, CHUNK)

    def both(x, g, *rest):
        y, back = jax.vjp(lambda *q: state_space_scan(*q, CHUNK), x, *rest)
        grads = back(g)
        # the result and every gradient feed the next call, so that the
        # compiler drops none of them
        rest = sum(jnp.sum(t.astype(jnp.float32)) for t in (y,) + grads[1:])
        return (x.astype(jnp.float32) + 1e-3 * grads[0].astype(jnp.float32)
                + 1e-30 * rest).astype(x.dtype)

    fwd = _ms_a_call(forward, x, dt, A, B, C, D)
    fwd_bwd = _ms_a_call(both, x, g, dt, A, B, C, D)
    print(f"state_space_scan at b {B_}, s {S}, {H} heads of {P}, state {N}, "
          f"chunk {CHUNK}, bf16: forward {fwd:.3f} ms a call, forward + "
          f"backward {fwd_bwd:.3f} ms a call")
    assert 0 < fwd < fwd_bwd
