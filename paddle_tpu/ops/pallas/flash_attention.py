"""Pallas TPU flash-attention: forward + backward kernels.

The reference has no flash attention (SURVEY.md §5.7 — its transformer is
plain full attention, python/paddle/nn/layer/transformer.py); this is a new
TPU-native capability.  Three kernels over (batch*heads, seq, head) operands;
no S matrix ever exists in HBM, and nothing of the gradient runs outside
the two backward kernels:

* `flash_fwd`, grid (batch*heads, q_blocks): block-wise online softmax in
  VMEM; K/V rows of the head stream through a loop of VMEM slices.  Emits
  the per-row logsumexp, lane-major `(bh, 1, seq)`, for the backward.
* `flash_dq`, grid (batch*heads, q_blocks): recomputes the probabilities of
  its q-block from the logsumexp, makes `delta = rowsum(dO ∘ O)` of the
  block from the dO and O it holds anyway, and writes it, lane-major like
  the logsumexp, as a second output.
* `flash_dkdv`, grid (batch*kv_heads, k_blocks, group), after `flash_dq`:
  reads those row sums.

**Grouped keys.**  k and v may have fewer heads than q: `(batch*kv_heads,
seq, head)` with `heads = kv_heads × group`, query head j reading key/value
head j // group (grouped-query attention; `group` 1 is the plain case, the
same code).  Nothing is repeated in HBM: `flash_fwd` and `flash_dq` pick a
query head's K/V rows by index map, and consecutive grid cells of one group
name the same block, so the pipeline fetches it once a group.  `flash_dkdv`
runs over the key/value heads with the group's query heads as its innermost
grid axis: the k-block's dK, dV go from member to member through float32
VMEM scratch and are stored at the last, so no dK of a repeated head is
ever summed outside the kernel; a group of one never touches the scratch
(chip, (2, 32, 4096, 192/128): 5.806 ms a call as before grouped keys;
zeroing and reading it every cell cost 5.868).  Timed against a loop over
the group's rows inside one grid cell at (1, 32/8, 8192, 64): 6.895 ms
against 6.821, with Q/dO blocks a quarter the size (PERF.md §6, PR 32).

**Every score tile is held transposed, `(block_k, block_q)`: keys along
sublanes, queries along lanes.**  Sᵀ = K·Qᵀ and dPᵀ = V·dOᵀ contract the
last dims of both operands (`_dot_nt`: no operand is transposed for them),
and everything a query row owns — running max, running sum, logsumexp,
delta — is a lane-major `(1, block_q)` row: it is stored in HBM that way,
broadcasts along sublanes as it lies, and a reduction over the keys is an
elementwise pass over the tile's sublane groups, not a cross-lane reduce
a row.  What the chip said (one v5e, (64, 4096, 192/128) causal, PERF.md §6
PR 31): with queries along sublanes the forward spends more time in its
two cross-lane reductions a tile than in anything else beside its
products (3.99 ms a call; 3.29 transposed), and turning the row statistics
from lanes to sublanes costs the backward more than any elementwise pass
over the tile does.  The accumulators follow the tile:

* `flash_fwd`: Oᵀ `(d_v, block_q)` += Vᵀ·Pᵀ, rescaled by a row; turned once
  a grid cell at the store.  Only the `(block_k, d_v)` V block is transposed
  a tile.
* `flash_dq`: dQᵀ `(d, block_q)` += Kᵀ·dSᵀ, turned at the store; with d = 192
  as the streamed side of that product, the matrix unit pads nothing (dS·K
  fills two 128-wide result columns half).
* `flash_dkdv`: dV += Pᵀ·dO and dK += dSᵀ·Q consume the tile as it is.

**What a tile does beside its products is decided at trace time from what
the call can observe** (`causal`, whether a bias was given, `dropout_rate`):

* *Causal:* tiles wholly above the diagonal are skipped (`tile_counts`,
  recorded in `pallas.flash.tiles`); every computed tile takes the mask —
  one subtract of two iotas, a compare with a scalar and a select.  Walking
  the unmasked tiles in a loop of their own was timed and lost (a second
  loop a grid cell costs more than the mask: the vector unit has room
  beside the matrix unit, a loop's fill and drain has none).  A non-causal
  call makes no positions at all.
* *Bias only where one was given.*  `bias=None` is "no bias": no operand,
  no add, no cotangent.  A padding bias `(batch, seq_k)` streams through
  all three kernels (the BERT/ERNIE padding-mask case without the O(S²)
  jnp path); along the keys it is a column, turned a tile in the kernels
  that stream keys and once a grid cell in `flash_dkdv`.
* *The scale lives on the resident block, not on the score tile.*  The
  q-block (`flash_fwd`, `flash_dq`) or k-block (`flash_dkdv`) is multiplied
  by `sm_scale` once a grid cell and rounded to the input dtype; the
  factor of dS is applied to the accumulator at its store.
* *Several tiles a loop iteration* (`_for_tiles`), so that one tile's
  products run beside another's vector work.
* *Dropout* runs inside the kernel with no mask tensor in HBM.  On real
  TPUs the keep mask comes from the hardware PRNG re-seeded per
  (seed, batch*head, q_block, k_block) tile — tile-local streams are
  replayable across the three kernels even though they visit tiles in
  different orders.  **The stream is keyed by block indices and drawn at
  the tile's shape `(block_k, block_q)`, so the three kernels must run the
  same blocks and hold the tile the same way**; `flash_attention()` gives
  them one `(block_q, block_k)`.  Interpret mode (CPU tests) uses a
  murmur3-style hash of global positions instead, which no block size
  moves (int32 multiplies are emulated on the TPU's vector unit, so the
  chip does not run it).

Matmul dtype policy: every product runs in the INPUT dtype (bf16 on the
chip) with float32 accumulation; softmax statistics, probabilities, the
row sums and dS are float32, and probabilities and dS are rounded to the
input dtype for the P·V / dV / dK / dQ products.  float32 inputs take the
float32 path end to end (the CPU tests).  Head sizes: q and k share one
(the contraction of the scores), v, o and their cotangents another: latent
attention trains at q·k 192 / v 128, and padding v to 192 would add a third
to the P·V, dV and dP work.

Numerics: probabilities use softmax-then-dropout semantics; the sum `l` is
taken over the *undropped* probabilities, matching the jnp reference path.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from paddle_tpu.ops.pallas import config as _cfg

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
NEG_INF = -1e30

# murmur3 fmix32 constants for the dropout hash (numpy scalars embed as
# literals inside pallas kernels; jnp constants would be captured consts)
_M1 = np.uint32(0x85EBCA6B)
_M2 = np.uint32(0xC2B2AE35)
_P1 = np.uint32(0x9E3779B1)  # golden-ratio primes to decorrelate axes
_P2 = np.uint32(0x85EBCA77)
_P3 = np.uint32(0xC2B2AE3D)


def _dropout_keep(seed, bh, q_pos, k_pos, rate):
    """Deterministic keep-mask: murmur3-finalizer hash of global positions.

    Identical values in forward and both backward kernels for the same
    (seed, bh, q_pos, k_pos), independent of block sizes.  Used in interpret
    mode (CPU tests); on real TPUs _dropout_keep_hw replaces it — int32
    multiplies are emulated on the vector unit.
    """
    h = (seed.astype(jnp.uint32)
         + bh.astype(jnp.uint32) * _P3
         + q_pos.astype(jnp.uint32) * _P1
         + k_pos.astype(jnp.uint32) * _P2)
    h = h ^ (h >> 16)
    h = h * _M1
    h = h ^ (h >> 13)
    h = h * _M2
    h = h ^ (h >> 16)
    threshold = np.uint32(min(int(rate * 2**32), 2**32 - 1))
    return h >= threshold  # keep with prob (1 - rate)


def _keep_from_hw_bits(seed_words, shape, rate):
    """Draw a keep mask from the hardware PRNG seeded with up to two int32
    words (the Mosaic limit).  Shared by the flash-attention and fused-LN
    dropout paths so the threshold/seeding convention cannot drift."""
    from jax.experimental.pallas import tpu as pltpu

    pltpu.prng_seed(*seed_words)
    bits = pltpu.prng_random_bits(shape)  # int32 tile
    threshold = np.int32(min(int(rate * 2**32), 2**32 - 1) - 2**31)
    return bits >= threshold  # keep with prob (1 - rate)


def _dropout_keep_hw(seed, bh, qi, kv_idx, shape, rate):
    """Hardware-PRNG keep-mask for the tile (q_block qi, k_block kv_idx), drawn
    at `shape`: every kernel, standard and packed, holds the tile
    `(block_k, block_q)` and draws it so through `_keep_scale`.

    The generator is RE-SEEDED per (seed, bh, q_block, k_block) tile, so the
    stream drawn for a tile depends only on its coordinates — the forward,
    dK/dV, and dQ kernels visit tiles in different orders yet replay
    identical masks.  (A single kernel-wide stream would not be replayable:
    the two backward kernels iterate the S matrix along different axes.)
    Requires the tile's shape (block sizes and which of them lies along
    sublanes) to agree across forward and backward, which flash_attention()
    guarantees.
    """
    # Mosaic takes at most two 32-bit seed words: fold (seed, bh) into one
    # (odd-constant multiply is injective in bh mod 2^32) and (qi, kv) into
    # the other (block indices are far below 2^16).
    return _keep_from_hw_bits(
        (seed + bh * jnp.int32(_P3), qi * jnp.int32(65536) + kv_idx),
        shape, rate)


def _keep_mask(seed, bh, qi, kv_idx, q_pos, k_pos, rate):
    """Dispatch: hardware PRNG on real TPUs, position hash in interpret."""
    if _cfg.interpret():
        return _dropout_keep(seed, bh, q_pos, k_pos, rate)
    return _dropout_keep_hw(seed, bh, qi, kv_idx, q_pos.shape, rate)


def _keep_scale(seed, bh, qi, kv_idx, block_q, block_k, rate):
    """The dropout multiplier of tile (qi, kv_idx) as the kernels hold a
    tile, `(block_k, block_q)`: 1/(1-rate) where kept, 0 where dropped.
    The positions feed the interpret-mode hash alone; on the chip nothing
    reads them."""
    shape = (block_k, block_q)
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    k_pos = kv_idx * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    keep = _keep_mask(seed, bh, qi, kv_idx, q_pos, k_pos, rate)
    return jnp.where(keep, 1.0 / (1.0 - rate), 0.0)


def _dot(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def _dot_nt(a, b):
    """a · bᵀ, contracting the last dims of both: the matrix unit takes the
    untransposed operand, no bᵀ is formed."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _scaled(x, sm_scale):
    """x · sm_scale in float32, rounded once to x's dtype: the scores'
    scale, applied to a `(block, d)` operand a grid cell instead of to
    every score tile."""
    return (x.astype(jnp.float32) * sm_scale).astype(x.dtype)


def _under_diagonal(qi, kv_idx, block_q, block_k):
    """`q_pos >= k_pos` over the `(block_k, block_q)` tile (qi, kv_idx):
    all true on a tile wholly under the diagonal."""
    shape = (block_k, block_q)
    rel = (jax.lax.broadcasted_iota(jnp.int32, shape, 1)
           - jax.lax.broadcasted_iota(jnp.int32, shape, 0))
    return rel >= kv_idx * block_k - qi * block_q


def _kv_end(qi, block_q, block_k, seq_len, causal):
    """q-block `qi` attends k-blocks `[0, _kv_end)`: under `causal` up to
    the last that reaches its diagonal, the rest is skipped.  Python ints
    in, a Python int out (`tile_counts`); a traced `qi` gives a traced end."""
    num_kv = seq_len // block_k
    if not causal:
        return num_kv
    end = ((qi + 1) * block_q + block_k - 1) // block_k
    return min(end, num_kv) if isinstance(end, int) else jnp.minimum(
        end, num_kv)


def tile_counts(seq_len, block_q, block_k, causal):
    """Score tiles a head at these blocks, by where they lie: (wholly under
    the causal diagonal, on it, wholly above it = skipped)."""
    under = on = 0
    for qi in range(seq_len // block_q):
        end = _kv_end(qi, block_q, block_k, seq_len, causal)
        first_on = min(qi * block_q // block_k, end) if causal else end
        under, on = under + first_on, on + end - first_on
    total = (seq_len // block_q) * (seq_len // block_k)
    return under, on, total - under - on


# Tiles one loop iteration computes.  Independent tiles in one basic block
# let the scheduler run one's matrix products beside another's vector work,
# and every loop costs its fill and drain once more a grid cell: on one v5e
# at (64, 4096, 192/128) the three kernels took 14.04 ms a call set at 1,
# 13.50 at 2, 13.35 at 4 (PERF.md §6, PR 31).
_TILES_PER_ITERATION = 4


def _for_tiles(tile, start, stop, carry):
    """`carry = tile(i, carry)` for i in [start, stop): `_TILES_PER_ITERATION`
    at a time, then the rest one by one."""
    n = (stop - start) // _TILES_PER_ITERATION

    def several(j, carry):
        for u in range(_TILES_PER_ITERATION):
            carry = tile(start + j * _TILES_PER_ITERATION + u, carry)
        return carry

    carry = jax.lax.fori_loop(0, n, several, carry)
    return jax.lax.fori_loop(start + n * _TILES_PER_ITERATION, stop, tile,
                             carry)


def _flash_fwd_kernel(seed_ref, q_ref, k_ref, v_ref, *rest, sm_scale, causal,
                      dropout_rate, block_q, block_k, seq_len, has_bias):
    bias_ref = rest[0] if has_bias else None
    o_ref, lse_ref = rest[-2:]
    bh_idx = pl.program_id(0)
    qi = pl.program_id(1)
    q = _scaled(q_ref[0], sm_scale)     # (block_q, d), the scores' scale on it

    def tile(kv_idx, carry):
        acc, m_prev, l_prev = carry     # (d_v, block_q), (1, block_q) twice
        start = pl.multiple_of(kv_idx * block_k, block_k)
        k = k_ref[0, pl.dslice(start, block_k), :]
        v = v_ref[0, pl.dslice(start, block_k), :]
        st = _dot_nt(k, q)                                # (block_k, block_q)
        if has_bias:
            st = st + bias_ref[0, 0, pl.dslice(start, block_k)][:, None]
        if causal:
            st = jnp.where(_under_diagonal(qi, kv_idx, block_q, block_k),
                           st, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(st, axis=0, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        pt = jnp.exp(st - m_new)
        l_new = l_prev * alpha + jnp.sum(pt, axis=0, keepdims=True)
        if dropout_rate > 0.0:
            pt = pt * _keep_scale(seed_ref[0], bh_idx, qi, kv_idx, block_q,
                                  block_k, dropout_rate)
        acc = acc * alpha + _dot(v.T, pt.astype(v.dtype))
        return acc, m_new, l_new

    acc, m, l = _for_tiles(tile, 0, _kv_end(qi, block_q, block_k, seq_len,
                                            causal), (
        jnp.zeros((v_ref.shape[-1], block_q), jnp.float32),
        jnp.full((1, block_q), NEG_INF, jnp.float32),
        jnp.zeros((1, block_q), jnp.float32)))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l_safe).T.astype(o_ref.dtype)
    lse_ref[0] = m + jnp.log(l_safe)


def _smem():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.SMEM


def _vmem():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM


def _specs(q, k, bias):
    """Block specs over `(bh, seq, d)` operands and `(bh, 1, seq)` row
    statistics — whole rows of a head, or the grid's block of them — and the
    bias as the calls take it: its `(b, 1, seq)` operand and its specs, each
    a list that is empty without one.

    `q_*` specs are by `flash_fwd`'s and `flash_dq`'s grid `(query head,
    q-block)`: `kv_rows` gives query head j the rows of key/value head
    j // group.  `kv_*` specs are by `flash_dkdv`'s grid `(key/value head,
    k-block, member of the group)`: `member_rows` gives the rows of query
    head `kv head × group + member`."""
    bh, seq_len, _ = q.shape
    group = bh // k.shape[0]
    if bias is None:
        bias_operand, q_bias_rows, kv_bias_block = [], [], lambda n: []
    else:
        h = bh // bias.shape[0]
        bias_operand = [bias.reshape(-1, 1, seq_len)]
        q_bias_rows = [pl.BlockSpec((1, 1, seq_len),
                                    lambda b, i: (b // h, 0, 0))]
        kv_bias_block = lambda n: [pl.BlockSpec(
            (1, 1, n), lambda b, i, g: (b * group // h, 0, i))]
    member = lambda b, i, g: (b * group + g, 0, 0)
    return dict(
        group=group,
        q_block=lambda n, d: pl.BlockSpec((1, n, d), lambda b, i: (b, i, 0)),
        q_stat_block=lambda n: pl.BlockSpec((1, 1, n),
                                            lambda b, i: (b, 0, i)),
        kv_rows=lambda d: pl.BlockSpec((1, seq_len, d),
                                       lambda b, i: (b // group, 0, 0)),
        kv_block=lambda n, d: pl.BlockSpec((1, n, d),
                                           lambda b, i, g: (b, i, 0)),
        member_rows=lambda d: pl.BlockSpec((1, seq_len, d), member),
        member_stat_rows=pl.BlockSpec((1, 1, seq_len), member),
        bias=bias_operand, q_bias_rows=q_bias_rows,
        kv_bias_block=kv_bias_block)


def _flash_forward(q, k, v, bias, seed, sm_scale, causal, dropout_rate,
                   block_q, block_k):
    """q: (bh, seq, d); k: (b·kv_heads, seq, d); v: (b·kv_heads, seq, d_v);
    bias: (b, seq) or None; seed: int32 scalar array.  Returns o and the
    logsumexp (bh, 1, seq)."""
    bh, seq_len, d = q.shape
    d_v = v.shape[-1]
    sp = _specs(q, k, bias)
    return pl.pallas_call(
        functools.partial(
            _flash_fwd_kernel, sm_scale=sm_scale, causal=causal,
            dropout_rate=dropout_rate, block_q=block_q, block_k=block_k,
            seq_len=seq_len, has_bias=bias is not None),
        grid=(bh, seq_len // block_q),
        in_specs=[pl.BlockSpec(memory_space=_smem()),
                  sp["q_block"](block_q, d), sp["kv_rows"](d),
                  sp["kv_rows"](d_v)]
        + sp["q_bias_rows"],
        out_specs=[sp["q_block"](block_q, d_v), sp["q_stat_block"](block_q)],
        out_shape=[jax.ShapeDtypeStruct((bh, seq_len, d_v), q.dtype),
                   jax.ShapeDtypeStruct((bh, 1, seq_len), jnp.float32)],
        interpret=_cfg.interpret(),
        name="flash_fwd",
    )(seed, q, k, v, *sp["bias"])


def _rowsum_lane_major(a, b):
    """rowsum(a ∘ b) of two (rows, d) blocks in float32, as one lane-major
    `(1, rows)` row like the logsumexp is stored.  The lane reduction runs on
    the matrix unit (a row of ones times the transposed product: +0.11 ms a
    call of the dq kernel at cell 4's shape); jnp.sum's cross-lane reduce on
    the vector unit costs +0.43 (PERF.md §6, PR 31; PR 29 found the same in
    the packed kernel).  The float32
    product goes in as bf16 pieces that hold it exactly — two for bf16
    operands (8 + 8 significant bits), three for float32 — summed in
    float32."""
    prod = a.astype(jnp.float32) * b.astype(jnp.float32)
    ones = jnp.ones((8, prod.shape[-1]), jnp.bfloat16)
    both_bf16 = a.dtype == jnp.bfloat16 and b.dtype == jnp.bfloat16
    total = None
    for _ in range(2 if both_bf16 else 3):
        piece = prod.astype(jnp.bfloat16)
        prod = prod - piece.astype(jnp.float32)
        part = _dot_nt(ones, piece)
        total = part if total is None else total + part
    return total[:1]


def _flash_bwd_dq_kernel(seed_ref, q_ref, k_ref, v_ref, *rest, sm_scale,
                         causal, dropout_rate, block_q, block_k, seq_len,
                         has_bias):
    bias_ref = rest[0] if has_bias else None
    do_ref, o_ref, lse_ref, dq_ref, delta_ref = rest[-5:]
    bh_idx = pl.program_id(0)
    qi = pl.program_id(1)
    q = _scaled(q_ref[0], sm_scale)     # (block_q, d), the scores' scale on it
    do = do_ref[0]
    lse = lse_ref[0]                                        # (1, block_q)
    # delta = rowsum(do * o) of this q-block, in float32 from the blocks in
    # VMEM; written out for the dkdv kernel as the forward writes lse
    delta = _rowsum_lane_major(do, o_ref[0])
    delta_ref[0] = delta

    def tile(kv_idx, dq_acc):           # dq_acc: dqᵀ, (d, block_q)
        start = pl.multiple_of(kv_idx * block_k, block_k)
        k = k_ref[0, pl.dslice(start, block_k), :]
        v = v_ref[0, pl.dslice(start, block_k), :]
        st = _dot_nt(k, q)                                # (block_k, block_q)
        if has_bias:
            st = st + bias_ref[0, 0, pl.dslice(start, block_k)][:, None]
        pt = jnp.exp(st - lse)          # true softmax probabilities
        if causal:
            pt = jnp.where(_under_diagonal(qi, kv_idx, block_q, block_k),
                           pt, 0.0)
        dpt = _dot_nt(v, do)
        if dropout_rate > 0.0:
            dpt = dpt * _keep_scale(seed_ref[0], bh_idx, qi, kv_idx, block_q,
                                    block_k, dropout_rate)
        dst = (pt * (dpt - delta)).astype(k.dtype)
        return dq_acc + _dot(k.T, dst)

    dq = _for_tiles(tile, 0, _kv_end(qi, block_q, block_k, seq_len, causal),
                    jnp.zeros((q.shape[-1], block_q), jnp.float32))
    dq_ref[0] = (dq * sm_scale).T.astype(dq_ref.dtype)


def _flash_bwd_dkdv_kernel(seed_ref, q_ref, k_ref, v_ref, *rest, sm_scale,
                           causal, dropout_rate, block_q, block_k, seq_len,
                           has_bias, group):
    bias_ref = rest[0] if has_bias else None
    do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_sum, dv_sum = rest[-7:]
    kv_idx = pl.program_id(1)
    member = pl.program_id(2)           # which query head of this k/v head's
    bh_idx = pl.program_id(0) * group + member    # the query head: q, do,
    # lse and delta are its rows, and the dropout stream is keyed by it
    k = _scaled(k_ref[0], sm_scale)     # (block_k, d), the scores' scale on it
    v = v_ref[0]
    if has_bias:
        bias = bias_ref[0, 0][:, None]  # this k-block's, turned once a cell

    def tile(qi, carry):
        dk_acc, dv_acc = carry
        start = pl.multiple_of(qi * block_q, block_q)
        q = q_ref[0, pl.dslice(start, block_q), :]
        do = do_ref[0, pl.dslice(start, block_q), :]
        lse = lse_ref[0, :, pl.dslice(start, block_q)]          # (1, block_q)
        delta = delta_ref[0, :, pl.dslice(start, block_q)]
        st = _dot_nt(k, q)                                # (block_k, block_q)
        if has_bias:
            st = st + bias
        pt = jnp.exp(st - lse)          # true softmax probabilities
        if causal:
            pt = jnp.where(_under_diagonal(qi, kv_idx, block_q, block_k),
                           pt, 0.0)
        dpt = _dot_nt(v, do)
        if dropout_rate > 0.0:
            keep = _keep_scale(seed_ref[0], bh_idx, qi, kv_idx, block_q,
                               block_k, dropout_rate)
            dv_acc = dv_acc + _dot((pt * keep).astype(do.dtype), do)
            dpt = dpt * keep
        else:
            dv_acc = dv_acc + _dot(pt.astype(do.dtype), do)
        dst = pt * (dpt - delta)
        dk_acc = dk_acc + _dot(dst.astype(q.dtype), q)
        return dk_acc, dv_acc

    # q-blocks from the first that reaches this k-block's diagonal upwards;
    # the sums go on from what the group's earlier members left, and only a
    # member that has one before or after it touches the scratch (a group of
    # one never does)
    lo = (kv_idx * block_k) // block_q if causal else 0
    dk, dv = _for_tiles(tile, lo, seq_len // block_q, jax.lax.cond(
        member > 0, lambda: (dk_sum[...], dv_sum[...]),
        lambda: (jnp.zeros(k.shape, jnp.float32),
                 jnp.zeros(v.shape, jnp.float32))))

    @pl.when(member < group - 1)
    def _():
        dk_sum[...] = dk
        dv_sum[...] = dv

    @pl.when(member == group - 1)
    def _():
        dk_ref[0] = (dk * sm_scale).astype(dk_ref.dtype)
        dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_dq(q, k, v, bias, seed, o, lse, do, sm_scale, causal, dropout_rate,
              block_q, block_k):
    """dq and delta = rowsum(do * o), float32 `(bh, 1, seq)` like lse."""
    bh, seq_len, d = q.shape
    d_v = v.shape[-1]
    sp = _specs(q, k, bias)
    return pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
            dropout_rate=dropout_rate, block_q=block_q, block_k=block_k,
            seq_len=seq_len, has_bias=bias is not None),
        grid=(bh, seq_len // block_q),
        in_specs=[pl.BlockSpec(memory_space=_smem()),
                  sp["q_block"](block_q, d), sp["kv_rows"](d),
                  sp["kv_rows"](d_v)]
        + sp["q_bias_rows"]
        + [sp["q_block"](block_q, d_v), sp["q_block"](block_q, d_v),  # do, o
           sp["q_stat_block"](block_q)],                              # lse
        out_specs=[sp["q_block"](block_q, d), sp["q_stat_block"](block_q)],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(lse.shape, jnp.float32)],
        interpret=_cfg.interpret(),
        name="flash_dq",
    )(seed, q, k, v, *sp["bias"], do, o, lse)


def _flash_dkdv(q, k, v, bias, seed, lse, delta, do, sm_scale, causal,
                dropout_rate, block_q, block_k):
    bh, seq_len, d = q.shape
    d_v = v.shape[-1]
    sp = _specs(q, k, bias)
    return pl.pallas_call(
        functools.partial(
            _flash_bwd_dkdv_kernel, sm_scale=sm_scale, causal=causal,
            dropout_rate=dropout_rate, block_q=block_q, block_k=block_k,
            seq_len=seq_len, has_bias=bias is not None, group=sp["group"]),
        grid=(k.shape[0], seq_len // block_k, sp["group"]),
        in_specs=[pl.BlockSpec(memory_space=_smem()),
                  sp["member_rows"](d), sp["kv_block"](block_k, d),
                  sp["kv_block"](block_k, d_v)]
        + sp["kv_bias_block"](block_k)
        + [sp["member_rows"](d_v), sp["member_stat_rows"],      # do, lse
           sp["member_stat_rows"]],                             # delta
        out_specs=[sp["kv_block"](block_k, d), sp["kv_block"](block_k, d_v)],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[_vmem()((block_k, d), jnp.float32),
                        _vmem()((block_k, d_v), jnp.float32)],
        interpret=_cfg.interpret(),
        name="flash_dkdv",
    )(seed, q, k, v, *sp["bias"], do, lse, delta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash_attention_bhsd(q, k, v, bias, seed, sm_scale, causal, dropout_rate,
                          block_q, block_k):
    out, _ = _flash_forward(q, k, v, bias, seed, sm_scale, causal,
                            dropout_rate, block_q, block_k)
    return out


def _fwd(q, k, v, bias, seed, sm_scale, causal, dropout_rate, block_q, block_k):
    out, lse = _flash_forward(q, k, v, bias, seed, sm_scale, causal,
                              dropout_rate, block_q, block_k)
    return out, (q, k, v, bias, seed, out, lse)


def _bwd(sm_scale, causal, dropout_rate, block_q, block_k, res, g):
    q, k, v, bias, seed, out, lse = res
    static = (sm_scale, causal, dropout_rate, block_q, block_k)
    dq, delta = _flash_dq(q, k, v, bias, seed, out, lse, g, *static)
    dk, dv = _flash_dkdv(q, k, v, bias, seed, lse, delta, g, *static)
    # Padding bias carries no trainable state; seed is integer (no cotangent).
    return (dq, dk, dv, None if bias is None else jnp.zeros_like(bias), None)


_flash_attention_bhsd.defvjp(_fwd, _bwd)


def _normalize_bias_seed(bias, seed, b, s):
    """Shared by the standard and packed wrappers: pad-bias broadcast with
    the non-differentiable contract, and int32 seed normalization."""
    if bias is None:
        bias = jnp.zeros((b, s), jnp.float32)
    else:
        bias = jax.lax.stop_gradient(
            jnp.broadcast_to(bias.astype(jnp.float32), (b, s)))
    if seed is None:
        seed = jnp.zeros((1,), jnp.int32)
    else:
        seed = jnp.asarray(seed, jnp.int32).reshape((1,))
    return bias, seed


def supported(seq_len: int, head_dim: int, v_head_dim: int = None) -> bool:
    """Shapes the kernel handles: sublane-aligned head sizes (64 covers the
    BERT/ERNIE family, 192/128 latent attention; Mosaic pads lanes),
    block-divisible seq."""
    v_head_dim = head_dim if v_head_dim is None else v_head_dim
    return head_dim % 64 == 0 and v_head_dim % 64 == 0 \
        and seq_len % 128 == 0 and seq_len >= 128


def _fit_block(block, s):
    """The largest block under `block` that divides `s` by halving; on the
    chip at least 128 (Mosaic's lane constraint on the (1, 1, block)
    lse/bias/delta blocks; supported() guarantees 128 divides s)."""
    block = min(block, s)
    while s % block:
        block //= 2
    if not _cfg.interpret() and block < 128:
        if s % 128:
            raise ValueError(
                f"flash_attention requires seq_len % 128 == 0 on TPU, got {s}")
        block = 128
    return block


def flash_attention(q, k, v, bias=None, sm_scale=None, causal=False,
                    dropout_rate=0.0, seed=None,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K):
    """Flash attention over (batch, heads, seq, head_dim) inputs; ``v`` may
    have a head size of its own (the output's), and ``k`` and ``v`` fewer
    heads than ``q``, a divisor of its count: query head j attends
    key/value head j // (heads / kv_heads).

    ``bias`` is an optional additive k-position bias of shape (batch, seq_k)
    — the padding-mask case.  ``bias`` is treated as NON-DIFFERENTIABLE:
    it passes through ``stop_gradient``, so a learned bias (ALiBi-style)
    passed here silently receives zero gradient.  Use the composable
    ``ops.attention`` path for trainable biases.  ``seed`` (int32 scalar
    array) drives in-kernel dropout when ``dropout_rate > 0``.
    """
    b, h, s, d = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    bq, bk = _fit_block(block_q, s), _fit_block(block_k, s)
    _cfg.record_flash_tiles(*tile_counts(s, bq, bk, causal))
    has_bias = bias is not None
    # bias is non-differentiable (padding masks carry no trainable state;
    # the docstring carries the learned-bias warning)
    bias, seed = _normalize_bias_seed(bias, seed, b, s)
    if h % k.shape[1] or v.shape[1] != k.shape[1]:
        raise ValueError(f"{h} query heads over {k.shape[1]} key and "
                         f"{v.shape[1]} value heads")
    merged = lambda x: x.reshape(-1, s, x.shape[-1])
    _cfg.record_call("flash_attention")
    with jax.named_scope("pallas.flash_attention"):
        out = _flash_attention_bhsd(merged(q), merged(k), merged(v),
                                    bias if has_bias else None, seed,
                                    sm_scale, causal, float(dropout_rate),
                                    bq, bk)
    return out.reshape(b, h, s, v.shape[-1])
