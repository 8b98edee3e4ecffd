"""Grouped matrix product on the TPU: rows sorted by group, one matrix a
group — the held experts of `nn.DroplessMoE`.

    out[start_g : start_g + sizes[g]] = lhs[start_g : ...] @ rhs[g]

The kernel is the Pallas grouped product that ships with JAX
(`jax.experimental.pallas.ops.tpu.megablox`: `gmm` forward and for the
input's gradient, `tgmm` for the matrices'), called here under the repo's
conventions: interpret mode off the TPU, a counted call, a `pallas.` scope.
It visits the row tiles that hold a group's rows and no others, so its time
follows the pairs routed to the held experts, not the rows of the buffer;
rows past the last group are left as they were found, forward and
transposed (the caller selects the held pairs' rows inside its sums and
never multiplies the rest by 0: `nn/layer/moe.py`).

Why not `lax.ragged_dot`: XLA:TPU lowers it to a grouped kernel of its own
whose instruction carries `op_name="ragged-dot-none"` and nothing of the
program's scopes, so a region reader finds 9% of the expert step in no
region, and it was no faster (3.2-3.9 ms the widest product over four
layers either way; chip, PR 28: PERF.md section 6).  Off the TPU
`lax.ragged_dot` is the path.
"""
from __future__ import annotations

import jax

from paddle_tpu.ops.pallas import config as _cfg

TILE_ROWS = 256
# elements of a (k tile) x (n tile) block: the matrices' gradient keeps one
# in float32 beside its double-buffered output, 2048 x 768 the most that
# has run (16 MiB of scoped VMEM: 2048 x 1024 asks 19)
TILE_ELEMENTS = 2048 * 768


def _tile(n: int, cap: int) -> int:
    """The largest multiple of 128 up to `cap` that divides n."""
    return max(t for t in range(128, min(n, cap) + 1, 128) if n % t == 0)


def _tiling(m: int, k: int, n: int):
    """Tiles of one product.  The kernel asks product by product (the
    forward, the input's gradient with k and n exchanged, the matrices'
    gradient), so each gets tiles that divide its own sides: one triple for
    all three left the input's gradient half a tile of padding on both
    sides.  The whole contraction in one tile where it is at most 2048 keeps
    a group's matrix in VMEM from row tile to row tile (chip, PR 28, a layer
    forward and backward at 14,336 held pairs: 4.94 ms against 5.94 with
    256 x 1024 x 768/1024 for all three); the n tile as wide as
    `TILE_ELEMENTS` leaves beside it, at most 1024."""
    tile_k = _tile(k, 2048)
    return TILE_ROWS, tile_k, _tile(n, min(1024, TILE_ELEMENTS // tile_k))


def supported(m: int, k: int, n: int) -> bool:
    return m % TILE_ROWS == 0 and k % 128 == 0 and n % 128 == 0


def grouped_matmul(lhs, rhs, group_sizes):
    """lhs [m, k] (rows sorted by group), rhs [groups, k, n], group_sizes
    [groups] int32 (their sum at most m) -> [m, n] in lhs's dtype;
    differentiable in lhs and rhs."""
    from jax.experimental.pallas.ops.tpu import megablox

    _cfg.record_call("grouped_matmul")
    with jax.named_scope("pallas.grouped_matmul"):
        return megablox.gmm(lhs, rhs, group_sizes, lhs.dtype, _tiling, None,
                            None, False, _cfg.interpret())
