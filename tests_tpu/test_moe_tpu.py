"""TPU-only: `nn.DroplessMoE`'s passes over the sorted rows follow the held
pairs (`nn/layer/moe.py:_leading_rows`), at both expert cells' shapes with
the Pallas grouped product.  On the chip a buffer's unwritten rows are
whatever the memory held (`lax.empty` allocates and writes nothing), which
the CPU suite can only imitate: for every count of held pairs, the result
and the gradients are those of the same layer with every pass run over
every row.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.nn as nn
from paddle_tpu.nn.layer import moe

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="the Pallas grouped product and uninitialised buffers are the "
           "TPU's")

TOKENS, HIDDEN = 8192, 2048
# (d_expert, routed experts, top_k, held): kanana-2-30b-a3b, lfm2-24b-a2b
SHAPES = {"kanana": (768, 128, 6, 16), "lfm2": (1536, 64, 4, 8)}


def _case(shape, held_pairs):
    d_expert, routed, top_k, held_n = SHAPES[shape]
    rng = np.random.default_rng(held_pairs)
    layer = nn.DroplessMoE(HIDDEN, d_expert, routed, top_k, held=(0, held_n))
    pairs = TOKENS * top_k
    held = np.zeros(pairs, bool)
    held[rng.permutation(pairs)[:held_pairs]] = True
    ids = np.where(held, rng.integers(0, held_n, pairs),
                   rng.integers(held_n, routed, pairs))
    bf = lambda *s: jnp.asarray(rng.normal(0, 1, s), jnp.bfloat16)  # noqa: E731
    return layer, (
        bf(2, TOKENS // 2, HIDDEN),
        jnp.asarray(ids.reshape(2, TOKENS // 2, top_k), jnp.int32),
        jnp.asarray(rng.random((2, TOKENS // 2, top_k)) + 0.1, jnp.float32),
        0.02 * bf(held_n, HIDDEN, 2 * d_expert),
        0.02 * bf(held_n, d_expert, HIDDEN), bf(2, TOKENS // 2, HIDDEN))


def _run(layer, args):
    def fn(tokens, ids, weights, w_in, w_out, g):
        y, vjp = jax.vjp(
            lambda t, w, a, b: layer._held_experts(True, t, ids, w, a, b),
            tokens, weights, w_in, w_out)
        return (y,) + vjp(g)
    return [np.asarray(a.astype(jnp.float32)) for a in jax.jit(fn)(*args)]


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_passes_follow_the_held_pairs_at_the_cells_shapes(shape, monkeypatch):
    top_k = SHAPES[shape][2]
    pairs, chunk = TOKENS * top_k, moe.CHUNK_ROWS
    counts = (0, chunk, chunk + 1, pairs // 8, pairs // 3, pairs)
    got = {n: _run(*_case(shape, n)) for n in counts}
    monkeypatch.setattr(moe, "_buffer_rows", lambda held, rows: (
        -(-rows // min(chunk, rows)), min(chunk, rows)))
    monkeypatch.setattr(moe, "NEAR_BYTES", ())
    for n in counts:
        # a layer of its own: `jax.checkpoint` keeps what it traced for one
        for name, a, b in zip(("y", "d_tokens", "d_weights", "d_w_in",
                               "d_w_out"), got[n], _run(*_case(shape, n))):
            assert np.isfinite(a).all(), (shape, n, name)
            np.testing.assert_array_equal(a, b, err_msg=f"{shape} {n} {name}")
        if n:
            assert got[n][0].any() and got[n][3].any()
