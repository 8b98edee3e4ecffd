"""TPU-only validation of the hardware-PRNG dropout path in the Pallas
flash-attention kernel (tests/conftest.py forces the CPU interpret backend,
where `_keep_mask` routes to the murmur hash — so the production TPU path
needs its own gate; run `pytest tests_tpu/` from an
environment with a real TPU and no JAX_PLATFORMS override).

The load-bearing claim under test: per-(seed, bh, q_block, k_block) tile
reseeding makes the hardware PRNG stream replayable across the forward,
dK/dV, and dQ kernels even though they visit S-matrix tiles in different
orders.  We extract the actual keep mask with a dump kernel that uses the
identical seeding, recompute reference attention + grads WITH that exact
mask, and require the kernel's outputs/grads to match.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import flash_attention as fa

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="hardware-PRNG dropout only lowers on real TPUs")

B, H, S, D = 2, 3, 512, 64
RATE = 0.1


def _qkv():
    rng = np.random.default_rng(0)
    return tuple(jnp.asarray(rng.normal(0, 1, (B, H, S, D)), jnp.float32)
                 for _ in range(3))


def _dump_mask(seed, bq=512, bk=512, bh=B * H, s=S):
    """The keep mask the kernels draw, (bh, s_q, s_k): they hold a tile
    with the keys along sublanes, `(block_k, block_q)`, and draw it so."""
    from jax.experimental import pallas as pl

    def kernel(seed_ref, out_ref):
        bh_idx = pl.program_id(0)
        qi = pl.program_id(1)

        def body(kv, _):
            keep = fa._dropout_keep_hw(seed_ref[0], bh_idx, qi, kv,
                                       (bk, bq), RATE)
            out_ref[0, pl.dslice(kv * bk, bk), :] = keep
            return 0

        jax.lax.fori_loop(0, s // bk, body, 0)

    mask = pl.pallas_call(
        kernel, grid=(bh, s // bq),
        in_specs=[pl.BlockSpec(memory_space=fa._smem())],
        out_specs=pl.BlockSpec((1, s, bq), lambda bh_i, i: (bh_i, 0, i)),
        out_shape=jax.ShapeDtypeStruct((bh, s, s), jnp.bool_),
    )(seed)
    return np.swapaxes(np.asarray(mask), 1, 2)


def _ref_attn(q, k, v, mask):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) / (D ** 0.5)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(mask, p / (1 - RATE), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(q.dtype), v)


def test_hw_dropout_deterministic_and_rate():
    q, k, v = _qkv()
    seed = jnp.asarray([1234], jnp.int32)
    o1 = fa.flash_attention(q, k, v, dropout_rate=RATE, seed=seed)
    o2 = fa.flash_attention(q, k, v, dropout_rate=RATE, seed=seed)
    assert np.array_equal(np.asarray(o1), np.asarray(o2))
    mask = _dump_mask(seed).reshape(B, H, S, S)
    assert abs(mask.mean() - (1 - RATE)) < 0.01


def test_hw_dropout_fwd_bwd_mask_consistency():
    q, k, v = _qkv()
    seed = jnp.asarray([1234], jnp.int32)
    mask = _dump_mask(seed).reshape(B, H, S, S)

    out = fa.flash_attention(q, k, v, dropout_rate=RATE, seed=seed)
    ref = _ref_attn(q, k, v, mask)
    assert float(jnp.abs(out - ref).max()) < 1e-2  # TPU default dot precision

    g_kernel = jax.grad(lambda t: (fa.flash_attention(
        t[0], t[1], t[2], dropout_rate=RATE, seed=seed) ** 2).sum())((q, k, v))
    g_ref = jax.grad(lambda t: (_ref_attn(t[0], t[1], t[2], mask) ** 2).sum())(
        (q, k, v))
    for name, a, b in zip("qkv", g_kernel, g_ref):
        diff = float(jnp.abs(a - b).max())
        mag = float(jnp.abs(b).max())
        assert diff < 1e-2 * max(mag, 1.0), (name, diff, mag)


# -- cell 4's own call: (2, 32, 4096, 192 / 128), bfloat16, causal, no mask ----

CB, CH, CS, CD, CDV = 2, 32, 4096, 192, 128
HEADS_A_CHUNK = 8       # the float32 reference holds 8 heads' score squares


def _cell4_inputs():
    rng = np.random.default_rng(4)
    mk = lambda d: jnp.asarray(rng.normal(0, 1, (CB, CH, CS, d)),
                               jnp.bfloat16)
    return mk(CD), mk(CD), mk(CDV), mk(CDV)


@jax.jit
def _causal_reference_chunk(q, k, v, do, keep_scale):
    """o, dq, dk, dv of a chunk of heads in float32 (`highest`);
    `keep_scale` multiplies the probabilities (1 everywhere: no dropout)."""
    def attend(q, k, v):
        with jax.default_matmul_precision("highest"):
            s = jnp.einsum("hqd,hkd->hqk", q, k) / (CD ** 0.5)
            s = jnp.where(jnp.tril(jnp.ones((CS, CS), bool)), s, -1e30)
            p = jax.nn.softmax(s, axis=-1) * keep_scale
            return jnp.einsum("hqk,hkd->hqd", p, v)

    o, vjp = jax.vjp(attend, *(t.astype(jnp.float32) for t in (q, k, v)))
    return (o,) + vjp(do.astype(jnp.float32))


def _assert_matches_reference(got, inputs, keep):
    """`got` (o, dq, dk, dv) of the kernels against the chunked float32
    reference.  bfloat16 results: half an ulp of a value near 4 is 0.008,
    so the file's 1e-2 is taken relative to the largest magnitude, and
    twice (the kernels also round p, dS and the scaled block to bf16)."""
    flat = [t.reshape(CB * CH, CS, -1) for t in inputs]
    got = [t.reshape(CB * CH, CS, -1) for t in got]
    for lo in range(0, CB * CH, HEADS_A_CHUNK):
        sl = slice(lo, lo + HEADS_A_CHUNK)
        keep_scale = (jnp.ones((1, 1, 1), jnp.float32) if keep is None
                      else jnp.where(jnp.asarray(keep[sl]), 1 / (1 - RATE), 0))
        want = _causal_reference_chunk(*(t[sl] for t in flat), keep_scale)
        for name, a, r in zip(("o", "dq", "dk", "dv"), got, want):
            diff = float(jnp.abs(a[sl].astype(jnp.float32) - r).max())
            mag = float(jnp.abs(r).max())
            assert diff < 2e-2 * max(mag, 1.0), (name, lo, diff, mag)


def _kernel_o_and_grads(q, k, v, do, **kw):
    out, vjp = jax.vjp(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True, **kw), q, k, v)
    return (out,) + vjp(do)


def test_cell4_shape_matches_float32_reference_in_three_kernels():
    inputs = _cell4_inputs()
    run = jax.jit(_kernel_o_and_grads)
    text = run.lower(*inputs).compile().as_text()
    import re
    calls = re.findall(r'custom_call_target="tpu_custom_call"', text)
    names = set(re.findall(r"/(flash_\w+)/pallas_call", text))
    assert len(calls) == 3 and names == {
        "flash_fwd", "flash_dkdv", "flash_dq"}, (len(calls), names)
    _assert_matches_reference(run(*inputs), inputs, None)


def test_cell4_shape_dropout_replays_one_mask_across_the_three_kernels():
    """Dropout 0.1 at the blocks the call runs by itself (512 x 512): the
    forward and both backward kernels against the reference under the mask
    a dump kernel draws with the same seeding."""
    inputs = _cell4_inputs()
    seed = jnp.asarray([4321], jnp.int32)
    keep = _dump_mask(seed, fa.DEFAULT_BLOCK_Q, fa.DEFAULT_BLOCK_K,
                      bh=CB * CH, s=CS)
    assert abs(keep[:, 1024:].mean() - (1 - RATE)) < 0.01
    got = jax.jit(lambda *t: _kernel_o_and_grads(
        *t, dropout_rate=RATE, seed=seed))(*inputs)
    _assert_matches_reference(got, inputs, keep)


def test_grouped_keys_at_the_8k_cells_shape_match_float32():
    """(1, 32/8, 8192, 64) causal, bf16 (`lfm2-24b-a2b.s8192`): four query
    heads read one key/value head, dK and dV summed over them inside
    `flash_dkdv`.  Against float32 attention, one key/value head's group at
    a time (four 8192² score squares), its dk and dv summed over the
    group's query heads by the reference's own vjp."""
    h, h_kv, s, d = 32, 8, 8192, 64
    group = h // h_kv
    rng = np.random.default_rng(8)
    mk = lambda heads: jnp.asarray(rng.normal(0, 1, (1, heads, s, d)),
                                   jnp.bfloat16)
    q, k, v, do = mk(h), mk(h_kv), mk(h_kv), mk(h)
    run = jax.jit(_kernel_o_and_grads)
    import re
    names = set(re.findall(r"/(flash_\w+)/pallas_call",
                           run.lower(q, k, v, do).compile().as_text()))
    assert names == {"flash_fwd", "flash_dkdv", "flash_dq"}, names
    o, dq, dk, dv = run(q, k, v, do)
    assert dk.shape == k.shape and dv.shape == v.shape

    @jax.jit
    def reference(q, k, v, do):           # [group, s, d], [s, d] twice
        def attend(q, k, v):
            with jax.default_matmul_precision("highest"):
                sc = jnp.einsum("gqd,kd->gqk", q, k) / (d ** 0.5)
                sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -1e30)
                return jnp.einsum("gqk,kd->gqd", jax.nn.softmax(sc, -1), v)

        out, vjp = jax.vjp(attend, *(t.astype(jnp.float32)
                                     for t in (q, k, v)))
        return (out,) + vjp(do.astype(jnp.float32))

    for j in range(h_kv):
        mine = slice(j * group, (j + 1) * group)
        want = reference(q[0, mine], k[0, j], v[0, j], do[0, mine])
        got = (o[0, mine], dq[0, mine], dk[0, j], dv[0, j])
        for name, a, r in zip(("o", "dq", "dk", "dv"), got, want):
            diff = float(jnp.abs(a.astype(jnp.float32) - r).max())
            mag = float(jnp.abs(r).max())
            assert diff < 2e-2 * max(mag, 1.0), (name, j, diff, mag)
