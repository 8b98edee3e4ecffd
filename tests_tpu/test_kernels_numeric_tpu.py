"""TPU-gated numeric checks: the Pallas LayerNorm forward AND backward on the chip, the fused
sublayer epilogue's gradients at a second shape, one ResNet bottleneck
block forward/backward against an fp32 oracle, and a long-context (s2048)
flash-attention training step.  Everything else validates on the CPU
backend, which has not historically caught TPU-only layout/precision bugs
(the reference gates per-op tests on every place, op_test.py:948)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="on-device numeric checks need the real TPU backend")


def _ref_ln(x, w, b, eps=1e-5):
    xf = x.astype(jnp.float32)
    m = xf.mean(-1, keepdims=True)
    v = xf.var(-1, keepdims=True)
    return ((xf - m) / jnp.sqrt(v + eps)) * w + b


def test_pallas_layer_norm_forward_and_backward_on_device():
    from paddle_tpu.ops.pallas import layer_norm as fln

    N, D = 1024, 768
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 1, (N, D)), jnp.float32)
    w = jnp.asarray(rng.normal(1, 0.1, (D,)), jnp.float32)
    b = jnp.asarray(rng.normal(0, 0.1, (D,)), jnp.float32)
    dy = jnp.asarray(rng.normal(0, 1, (N, D)), jnp.float32)

    out = fln.fused_layer_norm(x, w, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_ref_ln(x, w, b)),
                               rtol=2e-2, atol=2e-3)

    def kernel_loss(x_, w_, b_):
        return jnp.sum(fln.fused_layer_norm(x_, w_, b_) * dy)

    def ref_loss(x_, w_, b_):
        return jnp.sum(_ref_ln(x_, w_, b_) * dy)

    gk = jax.grad(kernel_loss, argnums=(0, 1, 2))(x, w, b)
    gr = jax.grad(ref_loss, argnums=(0, 1, 2))(x, w, b)
    for a, e, name in zip(gk, gr, ("dx", "dw", "db")):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(e), rtol=3e-2, atol=5e-2,
            err_msg=f"LayerNorm backward {name} diverges on-device")


def test_fused_sublayer_epilogue_grads_second_shape():
    """r04 covered (2048, 768); pin a second, non-multiple-of-512 row
    count and wider feature dim so tile-edge paths get a device check."""
    from paddle_tpu.ops.pallas import layer_norm as fln

    N, D = 1536, 1024
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(0, 1, (N, D)), jnp.float32)
    res = jnp.asarray(rng.normal(0, 1, (N, D)), jnp.float32)
    w = jnp.asarray(rng.normal(1, 0.1, (D,)), jnp.float32)
    b = jnp.asarray(rng.normal(0, 0.1, (D,)), jnp.float32)
    dy = jnp.asarray(rng.normal(0, 1, (N, D)), jnp.float32)

    def kernel_loss(x_, res_, w_, b_):
        return jnp.sum(fln.fused_residual_dropout_layer_norm(
            x_, res_, w_, b_, 0.0) * dy)

    def ref_loss(x_, res_, w_, b_):
        return jnp.sum(_ref_ln(x_ + res_, w_, b_) * dy)

    gk = jax.grad(kernel_loss, argnums=(0, 1, 2, 3))(x, res, w, b)
    gr = jax.grad(ref_loss, argnums=(0, 1, 2, 3))(x, res, w, b)
    for a, e, name in zip(gk, gr, ("dx", "dres", "dw", "db")):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(e), rtol=3e-2, atol=5e-2,
            err_msg=f"fused epilogue {name} diverges at (1536, 1024)")


def test_resnet_bottleneck_block_fwd_bwd_vs_fp32_oracle():
    """One BottleneckBlock training step on-device in bf16 vs the same
    math in fp32 — catches TPU conv layout/precision regressions the CPU
    suite cannot see."""
    from paddle_tpu import autograd
    from paddle_tpu.autograd import parameters_dict
    from paddle_tpu.vision.models.resnet import BottleneckBlock

    rng = np.random.default_rng(2)
    blk = BottleneckBlock(64, 16)
    blk.train()
    params = parameters_dict(blk)
    x = rng.normal(0, 1, (4, 64, 16, 16)).astype(np.float32)

    def loss(p, dtype):
        cast = jax.tree_util.tree_map(
            lambda a: a.astype(dtype)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, p)
        out = autograd.functional_call(blk, cast,
                                       (jnp.asarray(x, dtype),))
        return jnp.mean(out.astype(jnp.float32) ** 2)

    l16, g16 = jax.value_and_grad(lambda p: loss(p, jnp.bfloat16))(params)
    l32, g32 = jax.value_and_grad(lambda p: loss(p, jnp.float32))(params)
    np.testing.assert_allclose(float(l16), float(l32), rtol=5e-2)
    flat16 = jax.tree_util.tree_leaves(g16)
    flat32 = jax.tree_util.tree_leaves(g32)
    for a, e in zip(flat16, flat32):
        denom = float(jnp.abs(e).max()) + 1e-6
        assert float(jnp.abs(a - e).max()) / denom < 0.15, \
            "bf16 block gradient diverges from fp32 oracle on-device"


def test_long_context_s2048_flash_training_step():
    """One s2048 flash-attention step with gradients on the chip: the
    long-context path (ROADMAP S4) gets an on-device
    numeric gate, not just a throughput entry."""
    from paddle_tpu.ops.pallas import flash_attention as fa

    B, H, S, D = 1, 4, 2048, 64
    rng = np.random.default_rng(3)
    q, k, v = (jnp.asarray(rng.normal(0, 1, (B, H, S, D)), jnp.float32)
               for _ in range(3))
    dy = jnp.asarray(rng.normal(0, 1, (B, H, S, D)), jnp.float32)

    def ref(q_, k_, v_):
        s = jnp.einsum("bhqd,bhkd->bhqk", q_, k_) / np.sqrt(D)
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v_)

    out_k = fa.flash_attention(q, k, v, causal=True)
    out_r = ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=2e-2, atol=2e-2)

    gk = jax.grad(lambda q_, k_, v_: jnp.sum(
        fa.flash_attention(q_, k_, v_, causal=True) * dy),
        argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q_, k_, v_: jnp.sum(ref(q_, k_, v_) * dy),
                  argnums=(0, 1, 2))(q, k, v)
    for a, e, name in zip(gk, gr, ("dq", "dk", "dv")):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(e), rtol=5e-2, atol=5e-2,
            err_msg=f"s2048 flash {name} diverges on-device")
