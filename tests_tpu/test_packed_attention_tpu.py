"""TPU-only gradient validation of the PACKED-layout flash attention
against the jnp reference (run `pytest tests_tpu/` on a TPU host).

Methodology note (learned the hard way): when the loss packs (b, h, s, d)
inputs internally, jax.grad already returns cotangents in the ORIGINAL
(b, h, s, d) space — do NOT "unpack" them again.  A harness that did
produced bit-stable garbage comparisons that perfectly impersonated a
Mosaic miscompile across five kernel rewrites.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.attention import scaled_dot_product_attention as sdpa
from paddle_tpu.ops.pallas.flash_attention_packed import flash_attention_packed

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="validates the real-TPU lowering of the packed kernel")


@pytest.mark.parametrize("b,h,s,d,blocks", [
    (2, 4, 512, 64, None),     # head pairs
    (8, 12, 512, 64, None),    # flagship shape (batch slice)
    (2, 2, 512, 128, None),    # single 128-wide heads
    (2, 4, 1024, 64, 256),     # multi-block
])
def test_packed_grads_match_jnp_reference(b, h, s, d, blocks):
    rng = np.random.default_rng(0)
    q4, k4, v4 = (jnp.asarray(rng.normal(0, 1, (b, h, s, d)), jnp.float32)
                  for _ in range(3))

    def pack(t):
        return jnp.moveaxis(t, 1, 2).reshape(b, s, h * d)

    kw = {} if blocks is None else {"block_q": blocks, "block_k": blocks}
    g_ref = jax.grad(lambda t: (sdpa(t[0], t[1], t[2], training=False) ** 2
                                ).sum())((q4, k4, v4))
    g_pk = jax.grad(lambda t: (flash_attention_packed(
        pack(t[0]), pack(t[1]), pack(t[2]), h, **kw) ** 2).sum())(
        (q4, k4, v4))
    # grads are w.r.t. the (b, h, s, d) inputs — compare DIRECTLY
    for name, a, r in zip("qkv", g_pk, g_ref):
        rel = float(jnp.abs(a - r).max() / jnp.abs(r).max())
        assert rel < 0.02, (name, rel)  # TPU default matmul precision


def _packed_vjp(h):
    """(q, k, v, do) -> (dq, dk, dv) of the packed kernel, all in the packed
    layout and the inputs' dtype: the program holds the attention and nothing
    of a loss around it."""
    def run(q, k, v, do):
        return jax.vjp(lambda *t: flash_attention_packed(*t, h), q, k, v)[1](
            do)
    return jax.jit(run)


def test_flagship_shape_bf16_grads_and_what_the_compiled_backward_holds():
    """Cell 1's attention (64 x 512 x 768, 12 heads, bf16): dq, dk, dv
    against the jnp reference, and the compiled gradient is the three
    kernels alone — the row sums rowsum(dO * O) are made inside
    flash_packed_dq, so no float32 copy of a (b, s, h*d) tensor exists."""
    b, h, s, d = 64, 12, 512, 64
    rng = np.random.default_rng(0)
    q, k, v, do = (jnp.asarray(rng.normal(0, 1, (b, s, h * d)), jnp.bfloat16)
                   for _ in range(4))

    def heads(t):
        return jnp.moveaxis(t.astype(jnp.float32).reshape(b, s, h, d), 2, 1)

    def pack(t):
        return jnp.moveaxis(t, 1, 2).reshape(b, s, h * d)

    fn = _packed_vjp(h)
    text = fn.lower(q, k, v, do).compile().as_text()
    calls = re.findall(r"= .* custom-call\(.*custom_call_target=\"(\w+)\"",
                       text)
    assert calls == ["tpu_custom_call"] * 3, calls
    assert sorted(set(re.findall(r"/(\w+)/pallas_call", text))) == [
        "flash_packed_dkdv", "flash_packed_dq", "flash_packed_fwd"]
    assert "f32[64,512,768]" not in text

    g_pk = fn(q, k, v, do)
    g_ref = jax.vjp(lambda *t: sdpa(*t, training=False),
                    heads(q), heads(k), heads(v))[1](heads(do))
    for name, a, r in zip("qkv", g_pk, g_ref):
        r = pack(r)
        rel = float(jnp.abs(a.astype(jnp.float32) - r).max()
                    / jnp.abs(r).max())
        assert rel < 0.02, (name, rel)  # TPU default matmul precision


# -- dropout: one mask in all three kernels -----------------------------------

RATE = 0.1


@pytest.mark.parametrize("h,d,s,block_q,block_k,causal", [
    (4, 64, 1024, 512, 512, False),     # head pairs, four tiles a head
    (4, 64, 1024, 256, 512, True),      # a tile that is not square
    (2, 128, 512, 512, 512, False),     # one head a group, one tile
])
def test_dropout_mask_is_one_mask_in_forward_dkdv_and_dq(h, d, s, block_q,
                                                         block_k, causal):
    """On the chip the keep mask of tile (qi, kv_idx) comes from the hardware
    PRNG at the tile's shape, so only the chip can say that the three kernels
    replay ONE mask.  With the cotangent dO fixed: the forward is linear in v
    under a fixed mask, so <dO, out> = <dV, v> only if `flash_packed_dkdv`
    redraws the forward's mask; and the central difference of <dO, out> along
    a direction in q (in k) equals <dq, direction> (<dk, direction>) only if
    `flash_packed_dq` (`flash_packed_dkdv`) does.  The direction is the one
    in which a wrong mask shows most — the difference between this seed's
    gradient and another seed's — and that difference is the yardstick: a
    gradient of another mask misses by all of it, the right one by rounding
    and the difference's truncation."""
    b = 2
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(0, 1, (b, s, h * d)), jnp.float32)
               for _ in range(3))
    kw = dict(causal=causal, dropout_rate=RATE, block_q=block_q,
              block_k=block_k)

    @jax.jit
    def forward(q, k, v, seed):
        return flash_attention_packed(q, k, v, h, seed=seed, **kw)

    @jax.jit
    def grads(q, k, v, do, seed):
        return jax.vjp(lambda *t: forward(*t, seed), q, k, v)[1](do)

    seed, other = (jnp.asarray([n], jnp.int32) for n in (5, 6))
    f64 = lambda t: np.asarray(t, np.float64)  # noqa: E731
    dot = lambda a, b: float(np.vdot(f64(a), f64(b)))  # noqa: E731
    do = forward(q, k, v, seed)         # the cotangent of |out|^2 / 2, fixed
    value = lambda q, k: dot(do, forward(q, k, v, seed))  # noqa: E731
    dq, dk, dv = grads(q, k, v, do, seed)
    dq_o, dk_o, dv_o = grads(q, k, v, do, other)

    missed = {"dv": abs(value(q, k) - dot(dv, v)) / abs(dot(dv - dv_o, v))}
    # a small step: the direction is a combination of the keys (queries), so
    # the scores move coherently and <dO, out> bends within a step of 0.02
    # (off the chip, in exact float32, the difference misses by 0.04 of the
    # yardstick at 0.02 and by 0.001 at 0.002: PR 35)
    step = 0.002
    for name, g, g_o, at in (("dq", dq, dq_o, lambda e: (q + e, k)),
                             ("dk", dk, dk_o, lambda e: (q, k + e))):
        along = g - g_o
        along = along * (np.sqrt(along.size) / np.linalg.norm(f64(along)))
        fd = (value(*at(step * along)) - value(*at(-step * along))) / (2 * step)
        missed[name] = abs(fd - dot(g, along)) / abs(dot(g - g_o, along))
    print(f"\ndropout, share of another mask's miss: {missed}")
    assert max(missed.values()) < 0.1, missed


def test_packed_and_standard_kernels_draw_one_mask_for_one_seed():
    """Both families draw tile (qi, kv_idx) of global head b * h + head at
    `(block_k, block_q)` from one seeding, so under dropout the packed call
    equals the standard call on the same heads, forward and gradients (off
    the chip tests/test_flash_attention.py says so of the position hash)."""
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    b, h, s, d = 2, 4, 1024, 64
    rng = np.random.default_rng(1)
    q4, k4, v4, do4 = (jnp.asarray(rng.normal(0, 1, (b, h, s, d)),
                                   jnp.float32) for _ in range(4))
    pack = lambda t: jnp.moveaxis(t, 1, 2).reshape(b, s, h * d)  # noqa: E731
    unpack = lambda t: jnp.moveaxis(t.reshape(b, s, h, d), 2, 1)  # noqa: E731
    kw = dict(causal=True, dropout_rate=RATE, seed=jnp.asarray([9], jnp.int32),
              block_q=256, block_k=512)
    std, vjp_std = jax.vjp(lambda *t: flash_attention(*t, **kw), q4, k4, v4)
    pk, vjp_pk = jax.vjp(lambda *t: unpack(flash_attention_packed(
        *map(pack, t), h, **kw)), q4, k4, v4)
    for name, a, r in zip(("o", "dq", "dk", "dv"), (pk,) + vjp_pk(do4),
                          (std,) + vjp_std(do4)):
        rel = float(jnp.abs(a - r).max() / jnp.abs(r).max())
        assert rel < 1e-2, (name, rel)    # a wrong mask misses by order 1
