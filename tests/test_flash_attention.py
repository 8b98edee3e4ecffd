"""Pallas flash-attention kernel: interpret-mode numerics vs the jnp
reference (ops/attention.py), including padding bias, causal, dropout replay,
and the backward kernels.

The reference framework has no flash attention (SURVEY.md §5.7); the oracle
here is the O(S^2) reference implementation the kernel must agree with.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.core import flags
from paddle_tpu.ops import attention as attn_ops
from paddle_tpu.ops.attention import scaled_dot_product_attention as sdpa
from paddle_tpu.ops.pallas import config as pcfg
from paddle_tpu.ops.pallas import flash_attention as fa

B, H, S, D = 2, 3, 128, 64


@pytest.fixture
def qkv():
    rng = np.random.default_rng(0)
    mk = lambda: jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    return mk(), mk(), mk()


@pytest.fixture
def pad_bias():
    bias = np.zeros((B, S), np.float32)
    bias[0, 100:] = -1e4  # batch 0: 100 valid tokens
    return jnp.asarray(bias)


def _mask4d(bias):
    return bias[:, None, None, :]


def _hash_keep(seed, b, h, s, rate):
    """The keep mask the kernels draw off the chip, `(b, h, s_q, s_k)`: the
    position hash of every global head."""
    qpos = jax.lax.broadcasted_iota(jnp.int32, (s, s), 0)
    kpos = jax.lax.broadcasted_iota(jnp.int32, (s, s), 1)
    return jnp.stack([fa._dropout_keep(seed[0], jnp.int32(i), qpos, kpos, rate)
                      for i in range(b * h)]).reshape(b, h, s, s)


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_reference(qkv, pad_bias, causal):
    q, k, v = qkv
    out = fa.flash_attention(q, k, v, bias=pad_bias, causal=causal,
                             block_q=64, block_k=64)
    ref = sdpa(q, k, v, attn_mask=_mask4d(pad_bias), is_causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_forward_no_bias_uneven_blocks(qkv):
    q, k, v = qkv
    out = fa.flash_attention(q, k, v, block_q=128, block_k=32)
    ref = sdpa(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_backward_matches_reference(qkv, pad_bias, causal):
    q, k, v = qkv

    def loss_k(q, k, v):
        return (fa.flash_attention(q, k, v, bias=pad_bias, causal=causal,
                                   block_q=64, block_k=64) ** 2).sum()

    def loss_r(q, k, v):
        return (sdpa(q, k, v, attn_mask=_mask4d(pad_bias),
                     is_causal=causal) ** 2).sum()

    gk = jax.grad(loss_k, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        scale = float(jnp.abs(b).max())
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=scale * 1e-5)


def test_dropout_deterministic_and_block_independent(qkv, pad_bias):
    q, k, v = qkv
    seed = jnp.array([1234], jnp.int32)
    args = dict(bias=pad_bias, dropout_rate=0.3, seed=seed)
    o1 = fa.flash_attention(q, k, v, block_q=64, block_k=64, **args)
    o2 = fa.flash_attention(q, k, v, block_q=64, block_k=64, **args)
    assert bool((o1 == o2).all())
    o3 = fa.flash_attention(q, k, v, block_q=32, block_k=128, **args)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o3),
                               rtol=1e-5, atol=1e-5)
    o4 = fa.flash_attention(q, k, v, block_q=64, block_k=64, bias=pad_bias,
                            dropout_rate=0.3, seed=jnp.array([9], jnp.int32))
    assert bool((o1 != o4).any())


def test_dropout_grads_match_same_mask_reference(qkv, pad_bias):
    """Backward with dropout replays the identical keep mask: compare against
    a jnp attention using the hash-derived mask computed outside the kernel."""
    q, k, v = qkv
    seed = jnp.array([77], jnp.int32)
    rate = 0.3
    keeps = _hash_keep(seed, B, H, S, rate)

    def ref(q, k, v):
        sm = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
        sm = sm + pad_bias[:, None, None, :]
        p = jax.nn.softmax(sm, -1)
        p = jnp.where(keeps, p / (1 - rate), 0.0)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v)

    def loss_k(*a):
        return (fa.flash_attention(*a, bias=pad_bias, dropout_rate=rate,
                                   seed=seed, block_q=64, block_k=64) ** 2).sum()

    out_k = fa.flash_attention(q, k, v, bias=pad_bias, dropout_rate=rate,
                               seed=seed, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(ref(q, k, v)),
                               rtol=1e-5, atol=1e-5)
    gk = jax.grad(loss_k, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: (ref(*a) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        scale = float(jnp.abs(b).max())
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=scale * 1e-5)


def test_dropout_keep_rate():
    qpos = jax.lax.broadcasted_iota(jnp.int32, (512, 512), 0)
    kpos = jax.lax.broadcasted_iota(jnp.int32, (512, 512), 1)
    keep = fa._dropout_keep(jnp.int32(42), jnp.int32(0), qpos, kpos, 0.3)
    rate = 1.0 - float(keep.mean())
    assert abs(rate - 0.3) < 0.01


class TestDispatch:
    def test_padding_bias_extraction(self):
        b, s = 2, 128
        add = jnp.zeros((b, 1, 1, s), jnp.float32)
        assert attn_ops._as_padding_bias(add, b, s).shape == (b, s)
        boolm = jnp.ones((1, 1, 1, s), bool)
        out = attn_ops._as_padding_bias(boolm, b, s)
        assert out.shape == (b, s) and float(out.max()) == 0.0
        # full (b, h, sq, sk) masks are not kernel-eligible
        assert attn_ops._as_padding_bias(
            jnp.zeros((b, 1, s, s)), b, s) is None
        assert attn_ops._as_padding_bias(
            jnp.zeros((b, 4, 1, s)), b, s) is None

    def test_none_mask_gives_zero_bias(self):
        out = attn_ops._as_padding_bias(None, 3, 64)
        assert out.shape == (3, 64) and float(jnp.abs(out).max()) == 0.0

    def test_flash_fallback_matches_sdpa_with_general_mask(self):
        rng = np.random.default_rng(1)
        q = jnp.asarray(rng.normal(size=(1, 2, 64, 32)), jnp.float32)
        mask = jnp.asarray(rng.normal(size=(1, 2, 64, 64)), jnp.float32)
        out = attn_ops.flash_attention(q, q, q, attn_mask=mask)
        ref = sdpa(q, q, q, attn_mask=mask)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-6, atol=1e-6)


# -- packed layout (no head transposes) --------------------------------------

class TestPackedLayout:
    def _data(self, b=2, h=4, s=256, d=64, dtype=jnp.float32):
        rng = np.random.default_rng(0)
        q4, k4, v4 = (jnp.asarray(rng.normal(0, 1, (b, h, s, d)), dtype)
                      for _ in range(3))
        bias = jnp.asarray(rng.normal(0, 1, (b, s)), jnp.float32)
        pack = lambda t: jnp.moveaxis(t, 1, 2).reshape(b, s, h * d)
        return q4, k4, v4, bias, pack

    def test_packed_matches_standard_kernel_fwd_and_grads(self):
        from paddle_tpu.ops.pallas.flash_attention import flash_attention as std
        from paddle_tpu.ops.pallas.flash_attention_packed import (
            flash_attention_packed as packed,
        )

        q4, k4, v4, bias, pack = self._data()
        b, h, s, d = q4.shape
        ref = std(q4, k4, v4, bias=bias)
        out = packed(pack(q4), pack(k4), pack(v4), h, bias=bias)
        out4 = jnp.moveaxis(out.reshape(b, s, h, d), 2, 1)
        np.testing.assert_allclose(np.asarray(out4), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        g_ref = jax.grad(lambda t: (std(t[0], t[1], t[2], bias=bias) ** 2
                                    ).sum())((q4, k4, v4))
        g_pk = jax.grad(lambda t: (packed(pack(t[0]), pack(t[1]), pack(t[2]),
                                          h, bias=bias) ** 2).sum())(
            (q4, k4, v4))
        for name, a, r in zip("qkv", g_pk, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                       rtol=1e-5, atol=1e-5, err_msg=name)

    def test_packed_multi_block_and_head_dim_128(self):
        """seq > block (lse/delta slicing regression) and 128-wide heads."""
        from paddle_tpu.ops.pallas.flash_attention import flash_attention as std
        from paddle_tpu.ops.pallas.flash_attention_packed import (
            flash_attention_packed as packed,
        )

        for h, d, s in ((2, 64, 1024), (3, 128, 512)):
            b = 1
            rng = np.random.default_rng(0)
            q4, k4, v4 = (jnp.asarray(rng.normal(0, 1, (b, h, s, d)),
                                      jnp.float32) for _ in range(3))
            pack = lambda t: jnp.moveaxis(t, 1, 2).reshape(b, s, h * d)
            ref = std(q4, k4, v4, block_q=256, block_k=256)
            g_ref = jax.grad(lambda t: (std(t[0], t[1], t[2], block_q=256,
                                            block_k=256) ** 2).sum())(
                (q4, k4, v4))
            out = packed(pack(q4), pack(k4), pack(v4), h, block_q=256,
                         block_k=256)
            g_pk = jax.grad(lambda t: (packed(pack(t[0]), pack(t[1]),
                                              pack(t[2]), h, block_q=256,
                                              block_k=256) ** 2).sum())(
                (q4, k4, v4))
            out4 = jnp.moveaxis(out.reshape(b, s, h, d), 2, 1)
            np.testing.assert_allclose(np.asarray(out4), np.asarray(ref),
                                       rtol=1e-5, atol=1e-5)
            for a, r in zip(g_pk, g_ref):
                np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                           rtol=1e-5, atol=1e-5)

    def test_packed_causal_and_dropout_replay(self):
        from paddle_tpu.ops.pallas.flash_attention import flash_attention as std
        from paddle_tpu.ops.pallas.flash_attention_packed import (
            flash_attention_packed as packed,
        )

        q4, k4, v4, bias, pack = self._data(s=128)
        b, h, s, d = q4.shape
        ref = std(q4, k4, v4, bias=bias, causal=True)
        out = packed(pack(q4), pack(k4), pack(v4), h, bias=bias, causal=True)
        out4 = jnp.moveaxis(out.reshape(b, s, h, d), 2, 1)
        np.testing.assert_allclose(np.asarray(out4), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        # causal MULTI-BLOCK bounds (num_kv_iter clamp / qi_start) incl grads
        q4, k4, v4, bias, pack = self._data(s=1024)
        b, h, s, d = q4.shape
        ref = std(q4, k4, v4, bias=bias, causal=True, block_q=256,
                  block_k=256)
        g_ref = jax.grad(lambda t: (std(t[0], t[1], t[2], bias=bias,
                                        causal=True, block_q=256,
                                        block_k=256) ** 2).sum())((q4, k4, v4))
        out = packed(pack(q4), pack(k4), pack(v4), h, bias=bias, causal=True,
                     block_q=256, block_k=256)
        g_pk = jax.grad(lambda t: (packed(pack(t[0]), pack(t[1]), pack(t[2]),
                                          h, bias=bias, causal=True,
                                          block_q=256, block_k=256) ** 2
                                   ).sum())((q4, k4, v4))
        out4 = jnp.moveaxis(out.reshape(b, s, h, d), 2, 1)
        np.testing.assert_allclose(np.asarray(out4), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        for a, r in zip(g_pk, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                       rtol=1e-5, atol=1e-5)
        with pytest.raises(ValueError):
            packed(pack(q4)[..., :q4.shape[1] * 96 // 64], pack(k4), pack(v4),
                   h)  # head_dim 96: unsupported layout must raise
        seed = jnp.asarray([5], jnp.int32)
        a1 = packed(pack(q4), pack(k4), pack(v4), h, dropout_rate=0.2,
                    seed=seed)
        a2 = packed(pack(q4), pack(k4), pack(v4), h, dropout_rate=0.2,
                    seed=seed)
        assert np.array_equal(np.asarray(a1), np.asarray(a2))

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["f32", "bf16"])
    @pytest.mark.parametrize("h,d,s,block", [
        (4, 64, 128, 128),      # one block, head pairs
        (4, 64, 512, 128),      # four q-blocks
        (2, 128, 128, 128),     # one block, single 128-wide heads
        (2, 128, 512, 128),
        (6, 64, 256, 128),      # an odd count of lane groups
    ])
    def test_packed_delta_is_float32_rowsum_of_do_times_o(self, monkeypatch,
                                                          h, d, s, block,
                                                          dtype):
        """The row sums the dq kernel makes and hands the dkdv kernel are
        rowsum(dO * O) per head, in float32 from the inputs as they come."""
        from paddle_tpu.ops.pallas import flash_attention_packed as fp

        b = 2
        rng = np.random.default_rng(3)
        q, k, v, do = (jnp.asarray(rng.normal(0, 1, (b, s, h * d)), dtype)
                       for _ in range(4))
        bias = jnp.zeros((b, s), jnp.float32)
        seed = jnp.zeros((1,), jnp.int32)
        args = (1.0 / np.sqrt(d), False, 0.0, block, block)
        o, lse = fp._forward(q, k, v, bias, seed, h, *args)
        seen = _spy_pallas_calls(monkeypatch)   # fp.pl is fa.pl
        fp._backward(q, k, v, bias, seed, h, o, lse, do, *args)
        want = jnp.sum((do.astype(jnp.float32) * o.astype(jnp.float32)
                        ).reshape(b, s, h, d), axis=-1)          # (b, s, h)
        want = jnp.moveaxis(want, 1, 2).reshape(b, h * d // 128, 128 // d, s)
        made = seen["flash_packed_dq"][1][1]
        assert made.dtype == jnp.float32 and made.shape == want.shape
        np.testing.assert_allclose(np.asarray(made), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        # and that array, not another, is what the dkdv kernel reads
        assert seen["flash_packed_dkdv"][0][-1] is made

    @pytest.mark.parametrize("h,d", [(4, 64), (2, 128), (6, 64)])
    def test_packed_grad_runs_nothing_full_size_outside_its_kernels(self, h,
                                                                    d):
        """No equation of the gradient's jaxpr outside a pallas_call may touch
        a (b, s, h*d)-sized operand, save reshape/broadcast: the row sums
        cannot drift back out of the kernel unnoticed."""
        from paddle_tpu.ops.pallas.flash_attention_packed import (
            flash_attention_packed as packed,
        )

        b, s = 2, 256
        x = jnp.zeros((b, s, h * d), jnp.bfloat16)
        jaxpr = jax.make_jaxpr(jax.vjp(
            lambda q, k, v: packed(q, k, v, h), x, x, x)[1])(x)
        kernels, outside = _pallas_calls_and_outside(jaxpr, b * s * h * d)
        assert not outside, outside
        assert list(kernels) == ["flash_packed_dq", "flash_packed_dkdv"]

    @pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
    @pytest.mark.parametrize("rate", [0.0, 0.1], ids=["nodrop", "drop0.1"])
    @pytest.mark.parametrize("h,d", [(4, 64), (2, 128)])
    def test_packed_grads_match_standard_kernel_multi_block(self, h, d, rate,
                                                            causal):
        """causal x dropout x more than one q-block: the packed backward (its
        delta output's block spec included) against the standard kernel,
        which replays the same per-head dropout streams."""
        from paddle_tpu.ops.pallas.flash_attention import flash_attention as std
        from paddle_tpu.ops.pallas.flash_attention_packed import (
            flash_attention_packed as packed,
        )

        q4, k4, v4, bias, pack = self._data(b=1, h=h, s=512, d=d)
        seed = jnp.asarray([11], jnp.int32)
        kw = dict(bias=bias, causal=causal, dropout_rate=rate, seed=seed,
                  block_q=128, block_k=128)
        g_ref = jax.grad(lambda t: (std(*t, **kw) ** 2).sum())((q4, k4, v4))
        g_pk = jax.grad(lambda t: (packed(*map(pack, t), h, **kw) ** 2
                                   ).sum())((q4, k4, v4))
        for name, a, r in zip("qkv", g_pk, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                       rtol=1e-5, atol=1e-5, err_msg=name)

    @pytest.mark.parametrize("rate", [0.0, 0.1], ids=["nodrop", "drop0.1"])
    @pytest.mark.parametrize("h,d,s,block_q,block_k,causal", [
        (4, 64, 512, 128, 128, False),  # four key blocks, each its own bias
        (4, 64, 512, 256, 128, True),   # a tile wider along the queries
        (4, 64, 512, 128, 256, True),   # ... and along the keys
        (2, 128, 512, 256, 128, True),  # one head a lane group
        (3, 128, 256, 128, 256, False),
        (6, 64, 256, 128, 128, False),  # an odd count of lane groups
        (6, 64, 256, 64, 128, True),
    ], ids=["keyblocks", "wide_q", "wide_k", "d128", "d128_full", "groups3",
            "groups3_causal"])
    def test_packed_forward_lse_and_gradients_match_plain_reference(
            self, h, d, s, block_q, block_k, causal, rate):
        """What a score tile held keys-along-sublanes can get wrong: out, the
        stored logsumexp `(b, groups, heads_per_group, seq)` and dq, dk, dv
        against plain jax.numpy under the kernel's own dropout draw, with a
        bias that differs at every key."""
        from paddle_tpu.ops.pallas import flash_attention_packed as fp

        b = 2
        rng = np.random.default_rng(7)
        q4, k4, v4, do4 = (jnp.asarray(rng.normal(0, 1, (b, h, s, d)),
                                       jnp.float32) for _ in range(4))
        bias = jnp.asarray(rng.normal(0, 2, (b, s)), jnp.float32)
        pack = lambda t: jnp.moveaxis(t, 1, 2).reshape(b, s, h * d)
        unpack = lambda t: jnp.moveaxis(t.reshape(b, s, h, d), 2, 1)
        seed = jnp.asarray([13], jnp.int32)
        keep = None
        if rate:
            keep = _hash_keep(seed, b, h, s, rate)

        def kernel(q, k, v):
            return unpack(fp.flash_attention_packed(
                pack(q), pack(k), pack(v), h, bias=bias, causal=causal,
                dropout_rate=rate, seed=seed, block_q=block_q,
                block_k=block_k))

        def plain(q, k, v):
            return _plain_attention(q, k, v, bias, causal, keep, rate)

        out, vjp = jax.vjp(kernel, q4, k4, v4)
        ref, vjp_ref = jax.vjp(plain, q4, k4, v4)
        pairs = zip(("o", "dq", "dk", "dv"), (out,) + vjp(do4),
                    (ref,) + vjp_ref(do4))
        for name, a, r in pairs:
            scale = max(float(jnp.abs(r).max()), 1.0)
            np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                       rtol=1e-4, atol=1e-5 * scale,
                                       err_msg=name)
        _, lse = fp._forward(pack(q4), pack(k4), pack(v4), bias, seed, h,
                             1.0 / np.sqrt(d), causal, rate, block_q, block_k)
        scores = jnp.einsum("bhqd,bhkd->bhqk", q4, k4) / np.sqrt(d) \
            + bias[:, None, None, :]
        if causal:
            scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores,
                               -jnp.inf)
        want = jax.nn.logsumexp(scores, -1).reshape(b, h * d // 128,
                                                    128 // d, s)
        assert lse.dtype == jnp.float32 and lse.shape == want.shape
        np.testing.assert_allclose(np.asarray(lse), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_mha_packed_dispatch(self, monkeypatch):
        """MultiHeadAttention takes the transpose-free path when the gate
        opens and matches the split-head fallback."""
        import paddle_tpu.nn as nn
        from paddle_tpu.autograd import functional_call, parameters_dict
        from paddle_tpu.ops import attention as attn_mod

        mha = nn.MultiHeadAttention(128, 2)
        mha.eval()
        p = parameters_dict(mha)
        x = jnp.asarray(np.random.default_rng(1).normal(0, 1, (2, 128, 128)),
                        jnp.float32)
        ref = functional_call(mha, p, (x,))
        calls = []
        orig = attn_mod.flash_attention_packed

        def spy(*a, **k):
            out = orig(*a, **k)
            calls.append(out is not None)
            return out

        monkeypatch.setattr(attn_mod, "flash_attention_packed", spy)
        monkeypatch.setattr(pcfg, "kernel_enabled",
                            lambda name: bool(flags.get_flag(name)))
        out = functional_call(mha, p, (x,))
        assert calls == [True], "packed path did not engage"
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


# -- the specialised tile (PR 31) ---------------------------------------------

def _plain_attention(q, k, v, bias, causal, keep, rate):
    """float32 reference with the kernel's own dropout draw (`keep`)."""
    q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    if bias is not None:
        s = s + bias[:, None, None, :]
    if causal:
        n = s.shape[-1]
        s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -1e30)
    p = jax.nn.softmax(s, -1)
    if keep is not None:
        p = jnp.where(keep, p / (1.0 - rate), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("rate", [0.0, 0.1], ids=["nodrop", "drop0.1"])
@pytest.mark.parametrize("d,d_v", [(64, 64), (192, 128)],
                         ids=["d64", "d192v128"])
@pytest.mark.parametrize("block_q,block_k", [
    (256, 256),     # one tile, on the diagonal
    (64, 64),       # several: one masked tile a q-block, the rest unmasked
    (128, 32),      # four tiles of a q-block straddle the diagonal
    (32, 128),      # four q-blocks straddle a k-block's diagonal
], ids=["one", "several", "wide_q", "wide_k"])
@pytest.mark.parametrize("masked", [False, True], ids=["nomask", "padbias"])
def test_causal_tile_variants_match_reference(masked, block_q, block_k, d, d_v,
                                              rate, dtype):
    """Forward and dq/dk/dv of every variant of the causal tile (mask on
    the diagonal tiles only, bias or none, dropout or none, q·k and v head
    sizes apart) against the plain reference under the same dropout draw."""
    b, h, s = 2, 2, 256
    rng = np.random.default_rng(5)
    q, k = (jnp.asarray(rng.normal(0, 1, (b, h, s, d)), dtype)
            for _ in range(2))
    v, do = (jnp.asarray(rng.normal(0, 1, (b, h, s, d_v)), dtype)
             for _ in range(2))
    bias = None
    if masked:
        bias = np.zeros((b, s), np.float32)
        bias[0, 200:] = -1e4
        bias = jnp.asarray(bias)
    seed = jnp.asarray([31], jnp.int32)
    keep = None
    if rate:
        keep = _hash_keep(seed, b, h, s, rate)

    def kernel(q, k, v):
        return fa.flash_attention(q, k, v, bias=bias, causal=True,
                                  dropout_rate=rate, seed=seed,
                                  block_q=block_q, block_k=block_k)

    def plain(q, k, v):
        return _plain_attention(q, k, v, bias, True, keep, rate)

    out, vjp = jax.vjp(kernel, q, k, v)
    ref, vjp_ref = jax.vjp(plain, q, k, v)
    # bf16: the kernel rounds p, dS and the scaled q/k block to bf16 where
    # the reference keeps float32
    tol = 1e-5 if dtype == jnp.float32 else 2.5e-2
    pairs = zip(("o", "dq", "dk", "dv"), (out,) + vjp(do),
                (ref,) + vjp_ref(do.astype(jnp.float32)))
    for name, a, r in pairs:
        assert a.dtype == dtype, name
        scale = max(float(jnp.abs(r).max()), 1.0)
        np.testing.assert_allclose(
            np.asarray(a.astype(jnp.float32)), np.asarray(r),
            rtol=tol * 10, atol=tol * scale, err_msg=name)


def test_no_mask_no_dropout_non_causal_tile(qkv):
    """The leanest tile: no bias, no mask, no positions at all."""
    q, k, v = qkv
    out = fa.flash_attention(q, k, v, block_q=64, block_k=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(sdpa(q, k, v)),
                               rtol=1e-5, atol=1e-5)
    g = jax.grad(lambda t: (fa.flash_attention(*t, block_q=64, block_k=32)
                            ** 2).sum())((q, k, v))
    g_ref = jax.grad(lambda t: (sdpa(*t) ** 2).sum())((q, k, v))
    for a, r in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=1e-4, atol=1e-4)


def _spy_pallas_calls(monkeypatch):
    """Every pallas_call of the standard kernel's module, by name: its
    operands and what it returned."""
    seen = {}
    orig = fa.pl.pallas_call

    def spy(kernel, **kw):
        call = orig(kernel, **kw)

        def run(*operands):
            out = call(*operands)
            seen[kw["name"]] = (operands, out)
            return out
        return run

    monkeypatch.setattr(fa.pl, "pallas_call", spy)
    return seen


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("d,d_v,s,block", [
    (64, 64, 128, 128),         # one q-block
    (64, 64, 512, 128),         # four: the delta output's block spec
    (192, 128, 256, 128),       # the latent-attention head sizes
])
def test_delta_is_float32_rowsum_of_do_times_o(monkeypatch, d, d_v, s, block,
                                               dtype):
    """The row sums `flash_dq` makes and hands `flash_dkdv` are
    rowsum(dO * O), in float32 from the inputs as they come."""
    bh = 4
    rng = np.random.default_rng(3)
    q, k = (jnp.asarray(rng.normal(0, 1, (bh, s, d)), dtype)
            for _ in range(2))
    v, do = (jnp.asarray(rng.normal(0, 1, (bh, s, d_v)), dtype)
             for _ in range(2))
    seed = jnp.zeros((1,), jnp.int32)
    args = (1.0 / np.sqrt(d), True, 0.0, block, block)
    o, res = fa._fwd(q, k, v, None, seed, *args)
    seen = _spy_pallas_calls(monkeypatch)
    fa._bwd(*args, res, do)
    want = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    made = seen["flash_dq"][1][1]
    assert made.dtype == jnp.float32 and made.shape == (bh, 1, s)
    np.testing.assert_allclose(np.asarray(made[:, 0]), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # and that array, not another, is what the dkdv kernel reads
    assert seen["flash_dkdv"][0][-1] is made


def _pallas_calls_and_outside(jaxpr, full):
    """The jaxpr's pallas_calls (name -> operand shapes) and the names of
    the equations outside them that touch an operand of `full` elements or
    more, reshape/broadcast aside."""
    allowed = {"pallas_call", "reshape", "broadcast_in_dim"}
    kernels, outside = {}, []

    def walk(jp):
        for eqn in jp.eqns:
            name = eqn.primitive.name
            if name == "pallas_call":
                kernels[eqn.params["name"]] = [
                    tuple(a.aval.shape) for a in eqn.invars]
                continue            # what a kernel does inside is its own
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
            sizes = [getattr(a.aval, "size", 0)
                     for a in list(eqn.invars) + list(eqn.outvars)]
            if name not in allowed and max(sizes, default=0) >= full:
                outside.append(name)

    walk(jaxpr.jaxpr)
    return kernels, outside


def test_grad_runs_nothing_full_size_outside_its_kernels_and_no_zero_bias(
        monkeypatch):
    """Through the dispatch site with `attn_mask=None`: the three kernels
    take no (b, s) bias operand, and no equation outside a pallas_call
    touches a (b*h, s, .) operand but reshape/broadcast — the row sums
    cannot drift back out of `flash_dq` unnoticed."""
    monkeypatch.setattr(pcfg, "kernel_enabled",
                        lambda name: bool(flags.get_flag(name)))
    b, h, s, d, d_v = 2, 3, 256, 192, 128
    q = jnp.zeros((b, h, s, d), jnp.bfloat16)
    v = jnp.zeros((b, h, s, d_v), jnp.bfloat16)

    def both(q, k, v, g):
        out, vjp = jax.vjp(lambda q, k, v: attn_ops.flash_attention(
            q, k, v, is_causal=True), q, k, v)
        return out, vjp(g)

    kernels, outside = _pallas_calls_and_outside(
        jax.make_jaxpr(both)(q, q, v, v), b * h * s * d_v)
    assert not outside, outside
    assert list(kernels) == ["flash_fwd", "flash_dq", "flash_dkdv"]
    assert [len(ops) for ops in kernels.values()] == [4, 7, 7]
    for name, shapes in kernels.items():
        assert not any(sh[0] == b and sh[-1] == s for sh in shapes), (
            name, shapes)
    # a given padding mask still streams, through all three
    mask = jnp.ones((b, 1, 1, s), bool)
    kernels, _ = _pallas_calls_and_outside(jax.make_jaxpr(
        lambda q, k, v: jax.grad(lambda q: attn_ops.flash_attention(
            q, k, v, attn_mask=mask, is_causal=True).astype(
                jnp.float32).sum())(q))(q, q, v), b * h * s * d_v)
    assert all((b, 1, s) in shapes for shapes in kernels.values()), kernels


@pytest.mark.parametrize("block_q,block_k,causal,want", [
    (512, 512, True, (28, 8, 28)),      # cell 4 as it runs
    (256, 512, True, (56, 16, 56)),
    (512, 256, True, (56, 16, 56)),
    (512, 512, False, (64, 0, 0)),
])
def test_flash_tiles_counter_at_cell_4s_shape(block_q, block_k, causal, want):
    """`pallas.flash.tiles{kind}` says which of a head's tiles the call
    computes and which it skips; without explicit blocks cell 4's call
    runs 512 x 512."""
    from paddle_tpu.utils import monitor

    assert fa.tile_counts(4096, block_q, block_k, causal) == want
    q = jax.ShapeDtypeStruct((2, 32, 4096, 192), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((2, 32, 4096, 128), jnp.bfloat16)
    blocks = {} if (block_q, block_k) == (512, 512) else dict(
        block_q=block_q, block_k=block_k)
    jax.eval_shape(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=causal, **blocks), q, q, v)
    gauge = monitor.default_registry().get("pallas.flash.tiles")
    read = {labels["kind"]: n for labels, n in gauge.samples()}
    assert tuple(read[kind] for kind in
                 ("under_diagonal", "on_diagonal", "skipped")) == want
    # the dkdv kernel walks q-blocks of a k-block: the same tiles
    computed = sum(
        4096 // block_q - (j * block_k // block_q if causal else 0)
        for j in range(4096 // block_k))
    assert computed == want[0] + want[1]


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("kv_heads", [4, 1])
@pytest.mark.parametrize("with_bias,rate", [(False, 0.0), (True, 0.25)])
def test_grouped_keys_forward_and_three_gradients(kv_heads, causal,
                                                  with_bias, rate):
    """k and v with `kv_heads` heads under 4 query heads (`h_kv` in {h,
    h/4}): query head j reads key/value head j // group, and dK, dV are
    summed over the group inside `flash_dkdv`.  Forward and dq, dk, dv
    against the plain path, which repeats the heads; with a padding bias
    and dropout too (the stream is keyed by the query head)."""
    b, h, s, d, d_v = 2, 4, 128, 64, 32
    rng = np.random.default_rng(7)
    mk = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    q, do = mk(b, h, s, d), mk(b, h, s, d_v)
    k, v = mk(b, kv_heads, s, d), mk(b, kv_heads, s, d_v)
    bias = None
    if with_bias:
        bias = jnp.zeros((b, s), jnp.float32).at[1, 96:].set(-1e4)
    seed = jnp.asarray([11], jnp.int32)
    keep = None
    if rate:
        keep = _hash_keep(seed, b, h, s, rate)

    def kernel(q, k, v):
        return fa.flash_attention(q, k, v, bias=bias, causal=causal,
                                  dropout_rate=rate, seed=seed,
                                  block_q=64, block_k=32)

    def plain(q, k, v):
        k, v = (jnp.repeat(t, h // kv_heads, axis=1) for t in (k, v))
        return _plain_attention(q, k, v, bias, causal, keep, rate)

    out, vjp = jax.vjp(kernel, q, k, v)
    ref, vjp_ref = jax.vjp(plain, q, k, v)
    for name, a, r in zip(("o", "dq", "dk", "dv"), (out,) + vjp(do),
                          (ref,) + vjp_ref(do)):
        assert a.shape == r.shape, name
        scale = max(float(jnp.abs(r).max()), 1.0)
        np.testing.assert_allclose(np.asarray(a), np.asarray(r), rtol=1e-4,
                                   atol=1e-5 * scale, err_msg=name)


def test_grouped_keys_through_the_dispatch(monkeypatch):
    """`ops.attention.flash_attention` hands a 4/2 call to the three
    kernels (no `shapes` fallback), k and v enter them with their own two
    heads, and the plain path computes the same thing; a head count that
    does not divide is still `shapes`."""
    monkeypatch.setattr(pcfg, "kernel_enabled",
                        lambda name: bool(flags.get_flag(name)))
    b, h, h_kv, s, d = 1, 4, 2, 128, 64
    rng = np.random.default_rng(3)
    mk = lambda heads: jnp.asarray(rng.normal(size=(b, heads, s, d)),
                                   jnp.float32)
    q, k, v = mk(h), mk(h_kv), mk(h_kv)

    def both(q, k, v, g):
        out, vjp = jax.vjp(lambda q, k, v: attn_ops.flash_attention(
            q, k, v, is_causal=True), q, k, v)
        return out, vjp(g)

    kernels, outside = _pallas_calls_and_outside(
        jax.make_jaxpr(both)(q, k, v, q), b * h * s * d)
    assert not outside, outside
    assert list(kernels) == ["flash_fwd", "flash_dq", "flash_dkdv"]
    for name, shapes in kernels.items():
        # q-sized and k-sized operands both: nothing was repeated
        assert (b * h, s, d) in shapes and (b * h_kv, s, d) in shapes, name
    out = attn_ops.flash_attention(q, k, v, is_causal=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(sdpa(q, k, v, is_causal=True)),
        rtol=1e-5, atol=1e-5)
    from paddle_tpu.utils import monitor

    def shapes_fallbacks():
        c = monitor.default_registry().get("pallas.fallbacks")
        return sum(n for labels, n in c.samples()
                   if labels == {"kernel": "flash_attention",
                                 "reason": "shapes"})

    before = shapes_fallbacks()
    three = mk(3)
    with pytest.raises((TypeError, ValueError)):
        # 4 query heads over 3: no kernel, and the plain path cannot either
        attn_ops.flash_attention(q, three, three, is_causal=True)
    assert shapes_fallbacks() == before + 1
