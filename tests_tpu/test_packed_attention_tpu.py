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
