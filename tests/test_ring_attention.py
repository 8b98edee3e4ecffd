"""Ring attention / Ulysses vs exact full attention on the sp mesh (new TPU
capability — SURVEY.md §5.7 rebuild guidance)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec

import paddle_tpu.distributed as dist
from paddle_tpu.parallel import mesh as mesh_mod
from paddle_tpu.parallel.collective import shard_map
from paddle_tpu.parallel.ring_attention import ring_attention, ulysses_attention


@pytest.fixture(autouse=True)
def _reset_mesh():
    yield
    mesh_mod.set_mesh(None)


def _full_attention(q, k, v, causal=False):
    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / (d ** 0.5)
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        s = jnp.where(jnp.tril(jnp.ones((sq, sk), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)


def _qkv(b=2, h=4, s=32, d=8, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.normal(0, 1, (b, h, s, d)), jnp.float32)
                 for _ in range(3))


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(causal):
    m = dist.init_parallel_env(sp=4)
    q, k, v = _qkv()
    ref = _full_attention(q, k, v, causal)

    f = shard_map(
        lambda q_, k_, v_: ring_attention(q_, k_, v_, axis="sp", causal=causal),
        mesh=m,
        in_specs=(PartitionSpec(None, None, "sp"),) * 3,
        out_specs=PartitionSpec(None, None, "sp"), check_vma=False)
    out = f(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_ring_attention_grads_match_full():
    # sp=2 keeps a real multi-hop ring (the fwd test covers sp=4) while
    # halving the unrolled-ring AD compile that dominated suite cold time
    m = dist.init_parallel_env(sp=2)
    q, k, v = _qkv(s=16)

    def ref_loss(q_, k_, v_):
        return jnp.sum(_full_attention(q_, k_, v_, True) ** 2)

    def ring_loss(q_, k_, v_):
        f = shard_map(
            lambda a, b, c: ring_attention(a, b, c, axis="sp", causal=True),
            mesh=m, in_specs=(PartitionSpec(None, None, "sp"),) * 3,
            out_specs=PartitionSpec(None, None, "sp"), check_vma=False)
        return jnp.sum(f(q_, k_, v_) ** 2)

    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    g_ring = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
    for gr, gg in zip(g_ref, g_ring):
        np.testing.assert_allclose(np.asarray(gg), np.asarray(gr),
                                   rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_full(causal):
    m = dist.init_parallel_env(sp=4)
    q, k, v = _qkv(h=8)
    ref = _full_attention(q, k, v, causal)
    f = shard_map(
        lambda q_, k_, v_: ulysses_attention(q_, k_, v_, axis="sp",
                                             causal=causal),
        mesh=m, in_specs=(PartitionSpec(None, None, "sp"),) * 3,
        out_specs=PartitionSpec(None, None, "sp"), check_vma=False)
    out = f(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_ulysses_rejects_indivisible_heads():
    m = dist.init_parallel_env(sp=4)
    q, k, v = _qkv(h=2)
    f = shard_map(
        lambda q_, k_, v_: ulysses_attention(q_, k_, v_, axis="sp"),
        mesh=m, in_specs=(PartitionSpec(None, None, "sp"),) * 3,
        out_specs=PartitionSpec(None, None, "sp"), check_vma=False)
    with pytest.raises(ValueError, match="not divisible"):
        f(q, k, v)
