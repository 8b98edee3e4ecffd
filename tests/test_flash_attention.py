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
    qpos = jax.lax.broadcasted_iota(jnp.int32, (S, S), 0)
    kpos = jax.lax.broadcasted_iota(jnp.int32, (S, S), 1)
    keeps = jnp.stack([
        fa._dropout_keep(seed[0], jnp.int32(i), qpos, kpos, rate)
        for i in range(B * H)]).reshape(B, H, S, S)

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
        seen = {}
        orig = fp.pl.pallas_call

        def spy(kernel, **kw):
            call = orig(kernel, **kw)

            def run(*operands):
                out = call(*operands)
                seen[kw["name"]] = (operands, out)
                return out
            return run

        monkeypatch.setattr(fp.pl, "pallas_call", spy)
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

    @pytest.mark.parametrize("h,d", [(4, 64), (2, 128)])
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
        full = b * s * h * d
        allowed = {"pallas_call", "reshape", "broadcast_in_dim"}
        kernels, outside = [], []

        def walk(jp):
            for eqn in jp.eqns:
                name = eqn.primitive.name
                if name == "pallas_call":
                    kernels.append(eqn.params["name"])
                    continue        # what a kernel does inside is its own
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)
                sizes = [getattr(a.aval, "size", 0)
                         for a in list(eqn.invars) + list(eqn.outvars)]
                if name not in allowed and max(sizes, default=0) >= full:
                    outside.append(name)

        walk(jaxpr.jaxpr)
        assert not outside, outside
        assert kernels == ["flash_packed_dq", "flash_packed_dkdv"]

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
