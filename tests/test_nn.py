"""Layer / functional tests (analogue of reference test_layers.py + per-op
grad checks via finite differences, ref unittests/op_test.py check_grad)."""
import numpy as np
import pytest

import paddle_tpu as pd
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu import autograd


def _np(x):
    return np.asarray(x)


class TestLayerBase:
    def test_parameter_registration(self):
        m = nn.Linear(4, 8)
        names = [n for n, _ in m.named_parameters()]
        assert names == ["weight", "bias"]
        assert m.weight.shape == (4, 8)

    def test_nested_traversal_and_state_dict(self):
        class Net(nn.Layer):
            def __init__(self):
                super().__init__()
                self.fc1 = nn.Linear(4, 8)
                self.block = nn.Sequential(nn.Linear(8, 8), nn.ReLU())

            def forward(self, x):
                return self.block(self.fc1(x))

        net = Net()
        names = [n for n, _ in net.named_parameters()]
        assert "fc1.weight" in names and "block.0.weight" in names
        sd = net.state_dict()
        net2 = Net()
        missing, unexpected = net2.set_state_dict(sd)
        assert not missing and not unexpected
        np.testing.assert_array_equal(_np(net2.fc1.weight.value),
                                      _np(net.fc1.weight.value))

    def test_train_eval_mode(self):
        m = nn.Sequential(nn.Linear(2, 2), nn.Dropout(0.5))
        assert m.training
        m.eval()
        assert not m.training and not m[1].training
        x = pd.ones([4, 2])
        y1, y2 = m(x), m(x)
        np.testing.assert_array_equal(_np(y1), _np(y2))  # dropout off

    def test_apply_and_to_dtype(self):
        m = nn.Linear(2, 2)
        m.to(dtype="bfloat16")
        assert m.weight.dtype == pd.bfloat16

    def test_hooks(self):
        m = nn.Linear(2, 2)
        calls = []
        h = m.register_forward_post_hook(lambda layer, inp, out: calls.append(1))
        m(pd.ones([1, 2]))
        assert calls == [1]
        h.remove()
        m(pd.ones([1, 2]))
        assert calls == [1]


class TestLayers:
    def test_linear_matches_numpy(self):
        m = nn.Linear(3, 5)
        x = np.random.rand(2, 3).astype(np.float32)
        expect = x @ _np(m.weight.value) + _np(m.bias.value)
        np.testing.assert_allclose(_np(m(pd.to_tensor(x))), expect, rtol=1e-5)

    def test_conv2d_matches_scipy_like(self):
        # 1x1 kernel degenerates to per-pixel linear map — easy oracle
        m = nn.Conv2D(3, 4, 1, bias_attr=False)
        x = np.random.rand(2, 3, 5, 5).astype(np.float32)
        out = _np(m(pd.to_tensor(x)))
        w = _np(m.weight.value).reshape(4, 3)
        expect = np.einsum("nchw,oc->nohw", x, w)
        np.testing.assert_allclose(out, expect, rtol=1e-4, atol=1e-5)

    def test_conv2d_padding_shape(self):
        m = nn.Conv2D(1, 1, 3, padding=1, stride=2)
        assert m(pd.zeros([1, 1, 8, 8])).shape == (1, 1, 4, 4)

    def test_conv_transpose_shape(self):
        m = nn.Conv2DTranspose(4, 2, 3, stride=2, padding=1)
        assert m(pd.zeros([1, 4, 8, 8])).shape == (1, 2, 15, 15)

    def test_batchnorm_normalizes(self):
        m = nn.BatchNorm2D(3, momentum=0.5)
        x = np.random.rand(8, 3, 4, 4).astype(np.float32) * 5 + 2
        y = _np(m(pd.to_tensor(x)))
        assert abs(y.mean()) < 1e-4 and abs(y.std() - 1) < 1e-2
        # running stats moved toward batch stats
        assert _np(m._buffers["_mean"].value).mean() > 0.5
        m.eval()
        y2 = m(pd.to_tensor(x))
        assert y2.shape == x.shape

    def test_layernorm(self):
        m = nn.LayerNorm(16)
        x = np.random.rand(4, 16).astype(np.float32) * 3
        y = _np(m(pd.to_tensor(x)))
        np.testing.assert_allclose(y.mean(-1), 0, atol=1e-5)
        np.testing.assert_allclose(y.std(-1), 1, atol=1e-2)

    def test_groupnorm_instancenorm_rmsnorm(self):
        x = pd.to_tensor(np.random.rand(2, 4, 4, 4).astype(np.float32))
        assert nn.GroupNorm(2, 4)(x).shape == (2, 4, 4, 4)
        assert nn.InstanceNorm2D(4)(x).shape == (2, 4, 4, 4)
        r = nn.RMSNorm(8)(pd.to_tensor(np.random.rand(2, 8).astype(np.float32)))
        assert r.shape == (2, 8)

    def test_embedding_padding_idx(self):
        m = nn.Embedding(10, 4, padding_idx=0)
        out = _np(m(pd.to_tensor(np.array([[0, 1]]))))
        np.testing.assert_array_equal(out[0, 0], np.zeros(4))
        assert np.abs(out[0, 1]).sum() > 0

    def test_pools(self):
        x = pd.to_tensor(np.arange(16, dtype=np.float32).reshape(1, 1, 4, 4))
        mp = nn.MaxPool2D(2, 2)(x)
        np.testing.assert_array_equal(_np(mp)[0, 0], [[5, 7], [13, 15]])
        ap = nn.AvgPool2D(2, 2)(x)
        np.testing.assert_allclose(_np(ap)[0, 0], [[2.5, 4.5], [10.5, 12.5]])
        ad = nn.AdaptiveAvgPool2D(1)(x)
        np.testing.assert_allclose(_np(ad)[0, 0, 0, 0], 7.5)

    def test_dropout_train_scale(self):
        pd.seed(0)
        x = pd.ones([1000])
        y = _np(F.dropout(x, p=0.5, training=True))
        assert set(np.unique(y)).issubset({0.0, 2.0})
        assert 0.3 < (y == 0).mean() < 0.7

    def test_activations_numeric(self):
        x = np.linspace(-3, 3, 13).astype(np.float32)
        t = pd.to_tensor(x)
        np.testing.assert_allclose(_np(F.relu(t)), np.maximum(x, 0))
        np.testing.assert_allclose(_np(F.sigmoid(t)), 1 / (1 + np.exp(-x)), rtol=1e-5)
        np.testing.assert_allclose(_np(F.leaky_relu(t, 0.1)),
                                   np.where(x > 0, x, 0.1 * x), rtol=1e-5)
        np.testing.assert_allclose(_np(F.softmax(t)).sum(), 1.0, rtol=1e-5)
        np.testing.assert_allclose(_np(F.hardswish(t)),
                                   x * np.clip(x / 6 + 0.5, 0, 1), rtol=1e-5)

    def test_interpolate(self):
        x = pd.to_tensor(np.arange(4, dtype=np.float32).reshape(1, 1, 2, 2))
        up = F.interpolate(x, size=(4, 4), mode="nearest")
        assert up.shape == (1, 1, 4, 4)
        bi = F.interpolate(x, scale_factor=2, mode="bilinear")
        assert bi.shape == (1, 1, 4, 4)


class TestLosses:
    def test_cross_entropy_matches_manual(self):
        logits = np.random.rand(4, 5).astype(np.float32)
        label = np.array([0, 2, 1, 4])
        out = float(F.cross_entropy(pd.to_tensor(logits), pd.to_tensor(label)))
        p = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
        expect = -np.log(p[np.arange(4), label]).mean()
        np.testing.assert_allclose(out, expect, rtol=1e-5)

    def test_cross_entropy_soft_label_and_ignore(self):
        logits = np.random.rand(4, 5).astype(np.float32)
        soft = np.full((4, 5), 0.2, np.float32)
        out = float(F.cross_entropy(pd.to_tensor(logits), pd.to_tensor(soft),
                                    soft_label=True))
        assert out > 0
        label = np.array([0, -100, 1, -100])
        li = float(F.cross_entropy(pd.to_tensor(logits), pd.to_tensor(label)))
        p = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
        expect = -np.log(p[[0, 2], [0, 1]]).mean()
        np.testing.assert_allclose(li, expect, rtol=1e-5)

    def test_mse_bce(self):
        a = np.random.rand(8).astype(np.float32)
        b = np.random.rand(8).astype(np.float32)
        np.testing.assert_allclose(float(F.mse_loss(pd.to_tensor(a), pd.to_tensor(b))),
                                   ((a - b) ** 2).mean(), rtol=1e-5)
        p = np.clip(np.random.rand(8).astype(np.float32), 0.05, 0.95)
        y = (np.random.rand(8) > 0.5).astype(np.float32)
        np.testing.assert_allclose(
            float(F.binary_cross_entropy(pd.to_tensor(p), pd.to_tensor(y))),
            -(y * np.log(p) + (1 - y) * np.log(1 - p)).mean(), rtol=1e-4)
        # logits version consistent with probability version
        logit = np.random.randn(8).astype(np.float32)
        np.testing.assert_allclose(
            float(F.binary_cross_entropy_with_logits(pd.to_tensor(logit), pd.to_tensor(y))),
            float(F.binary_cross_entropy(pd.to_tensor(1/(1+np.exp(-logit))), pd.to_tensor(y))),
            rtol=1e-4)


class TestAutogradBridge:
    def test_value_and_grad_linear_regression(self):
        m = nn.Linear(3, 1, bias_attr=False)
        x = np.random.rand(16, 3).astype(np.float32)
        y = x @ np.array([[1.0], [2.0], [3.0]], np.float32)

        def loss_fn(xb, yb):
            return F.mse_loss(m(xb), yb)

        params = autograd.parameters_dict(m)
        vag = autograd.value_and_grad(m, loss_fn)
        loss, grads = vag(params, pd.to_tensor(x), pd.to_tensor(y))
        assert set(grads) == {"weight"}
        # finite-difference check (ref: op_test.py get_numeric_gradient)
        eps = 1e-3
        w = _np(m.weight.value).copy()
        for idx in [(0, 0), (2, 0)]:
            wp = w.copy(); wp[idx] += eps
            wm = w.copy(); wm[idx] -= eps
            lp, _ = vag({"weight": pd.to_tensor(wp)}, pd.to_tensor(x), pd.to_tensor(y))
            lm, _ = vag({"weight": pd.to_tensor(wm)}, pd.to_tensor(x), pd.to_tensor(y))
            num = (float(lp) - float(lm)) / (2 * eps)
            np.testing.assert_allclose(_np(grads["weight"])[idx], num, rtol=2e-2)

    def test_functional_call_pure_wrt_params(self):
        m = nn.Linear(2, 2, bias_attr=False)
        x = pd.ones([1, 2])
        orig = _np(m.weight.value).copy()
        out = autograd.functional_call(m, {"weight": pd.zeros([2, 2])}, (x,))
        np.testing.assert_array_equal(_np(out), np.zeros((1, 2)))
        np.testing.assert_array_equal(_np(m.weight.value), orig)  # restored

    def test_jitted_train_step_converges(self):
        import jax

        m = nn.Sequential(nn.Linear(4, 16), nn.Tanh(), nn.Linear(16, 1))
        opt = pd.optimizer.Adam(learning_rate=0.05)
        params = autograd.parameters_dict(m)
        state = opt.init(params)
        rng = np.random.RandomState(0)
        X = rng.rand(64, 4).astype(np.float32)
        Y = (X.sum(1, keepdims=True) ** 2).astype(np.float32)

        def loss_fn(p, xb, yb):
            out = autograd.functional_call(m, p, (xb,))
            return F.mse_loss(out, yb)

        @jax.jit
        def step(p, s, xb, yb):
            loss, grads = jax.value_and_grad(loss_fn)(p, xb, yb)
            p, s = opt.update(grads, s, p)
            return p, s, loss

        losses = []
        for i in range(60):
            params, state, loss = step(params, state, X, Y)
            losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.1, losses[::20]


def test_linear_supports_forward_mode_ad_whatever_the_environment(
        monkeypatch):
    """`F.linear` reads nothing of the environment: with the variable of the
    deleted dW toggle set, forward-mode AD works through it (the toggle's
    path was a `custom_vjp`) and the gradients are the unset run's, bitwise."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu.nn.functional as F

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((4, 6, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((8,)), jnp.float32)

    def loss(x_, w_, b_):
        return jnp.sum(F.linear(x_, w_, b_) ** 2)

    monkeypatch.delenv("PDTPU_LINEAR_DW", raising=False)
    ref = jax.grad(loss, argnums=(0, 1, 2))(x, w, b)
    monkeypatch.setenv("PDTPU_LINEAR_DW", "transpose")
    got = jax.grad(loss, argnums=(0, 1, 2))(x, w, b)
    for r, g in zip(ref, got):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(g))
    out, tangent = jax.jvp(lambda w_: F.linear(x, w_, b), (w,),
                           (jnp.ones_like(w),))
    np.testing.assert_allclose(
        np.asarray(tangent),
        np.broadcast_to(np.asarray(x).sum(-1, keepdims=True), out.shape),
        rtol=1e-5, atol=1e-5)
