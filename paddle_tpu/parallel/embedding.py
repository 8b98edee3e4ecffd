"""TPU-native vocab-sharded embedding tables.

Reference parity: the reference's large-scale sparse story is the fleet PS
stack — ``lookup_table`` with ``is_sparse=True`` producing SelectedRows
gradients that ``push_sparse`` RPCs ship to parameter servers
(distributed_lookup_table_op.cc, pscore pull/push_sparse, the
heterogeneous pipeline of "End-to-end Adaptive Distributed Training on
PaddlePaddle", arxiv 2112.02752).  TPU-native design rebuilds that path on
the mesh instead of an RPC fabric, following the classic sparse-lookup
decomposition of "TensorFlow: A system for large-scale machine learning"
(arxiv 1605.08695 §4.2): dedup ids before the exchange, gather remotely,
segment-sum gradients back.

The table lives vocab-sharded over the mesh's model-parallel axis: device
``i`` of ``k`` holds rows ``[i*V/k, (i+1)*V/k)``.  One lookup is:

1. **dedup** — ``jnp.unique`` with a static size bound + inverse indices,
   so duplicate ids (the CTR norm: popular items dominate) cross the wire
   once;
2. **id exchange** — one ``all_to_all`` routes each unique id to the
   shard that owns it (ids are sorted by ``unique``, so owners are
   contiguous runs packed into a fixed ``(k, capacity)`` buffer);
3. **local gather** — each shard reads its own rows;
4. **row exchange** — the reverse ``all_to_all`` returns gathered rows,
   which the inverse indices scatter back to token order.

The backward is the mirror image and never materializes a dense
vocab-sized gradient on any single device: cotangent rows are
**segment-summed over duplicate ids**, exchanged back to their owner
shard (optionally block-quantized — sparse embedding rows are the
original gradient-compression use case, so the wire payload rides
``parallel/compress.py``'s int8/fp8 blockwise scheme with one fp32 scale
per row), and scatter-added into the local ``(V/k, D)`` shard.

Wired *under* the static ``lookup_table``/``lookup_table_v2`` lowerings
via ``ShardingPlan(embedding_shard=...)`` (see ``lower_lookup``), so
fleet/static CTR models run unchanged; ``shardcheck`` SC010 front-runs
indivisible vocabs and axis conflicts before any trace.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from . import mesh as _mesh
from ..utils import monitor as _monitor

__all__ = [
    "LOOKUP_OPS", "EmbeddingContext", "ShardedEmbedding",
    "sharded_lookup", "sparse_lookup", "lower_lookup", "exchange_bytes",
    "unique_capacity", "embedding_scope", "current_embedding",
    "resolve_tables", "to_host_table", "observe_serving_lookup",
]

# Static op types whose W input is an embedding table (the lowerings that
# consult the ambient EmbeddingContext).
LOOKUP_OPS = ("lookup_table", "lookup_table_v2", "embedding")

# -- telemetry (registered at import so metricsdump lists the family) --------
_m_exchange_bytes = _monitor.histogram(
    "emb.exchange_bytes",
    "Per-device wire bytes one sharded-embedding lookup site moves per "
    "step (id all_to_all + forward row all_to_all + backward gradient-row "
    "all_to_all, quantization accounted) — observed at trace time from the "
    "static shapes (`exchange_bytes`).")
_m_unique_ratio = _monitor.gauge(
    "emb.unique_ratio",
    "unique ids / submitted ids of the most recent deduplicated lookup "
    "(serving submit-side dedup); lower is better — duplicates cross the "
    "wire once.")
_m_lookup_ms = _monitor.histogram(
    "emb.lookup_ms",
    "End-to-end latency of one embedding-tenant lookup through the "
    "serving frontend (submit-side dedup -> batched execute -> inverse "
    "map), ms.")


def observe_serving_lookup(unique_ratio: Optional[float] = None,
                           ms: Optional[float] = None) -> None:
    """Record serving-side lookup telemetry (the frontend's dedup path
    calls this; kept here so every emb.* metric registers in one module)."""
    if unique_ratio is not None:
        _m_unique_ratio.set(float(unique_ratio))
    if ms is not None:
        _m_lookup_ms.observe(float(ms))


# ---------------------------------------------------------------------------
# capacity / wire accounting
# ---------------------------------------------------------------------------

def unique_capacity(n_ids: int, k: int,
                    capacity_factor: Optional[float] = None) -> int:
    """Per-peer slot capacity of the ``(k, C)`` exchange buffer for a local
    batch of ``n_ids`` ids over ``k`` vocab shards.  ``None`` (default) is
    the exact mode: ``C = n_ids`` admits the worst case of every id owned
    by one shard, so no id is ever dropped.  A float trades wire bytes for
    a drop risk on skewed batches: ``C = ceil(n_ids/k * factor)`` (hashed
    CTR ids are near-uniform, so ~1.2 is typical in PS deployments)."""
    n_ids = max(1, int(n_ids))
    if capacity_factor is None:
        return n_ids
    return max(1, min(n_ids, int(math.ceil(n_ids / k * capacity_factor))))


def exchange_bytes(n_ids: int, dim: int, k: int,
                   capacity_factor: Optional[float] = None,
                   quantize: Optional[str] = None,
                   ids_bytes: int = 4, row_bytes: int = 4) -> int:
    """Per-device off-chip wire bytes of one lookup's three all_to_alls
    (only the ``(k-1)/k`` of each buffer that leaves the chip counts):
    id request out, fp32 rows back, gradient rows out — the last carrying
    1 byte/element + one fp32 scale per row when block-quantized."""
    from . import compress as _compress

    if k <= 1:
        return 0
    c = unique_capacity(n_ids, k, capacity_factor)
    off = k - 1
    fwd = off * c * ids_bytes + off * c * dim * row_bytes
    if quantize in _compress.COMPRESS_KINDS:
        row_wire = dim + 4  # 1B/elem payload + one fp32 scale per row
    else:
        row_wire = dim * row_bytes
    return int(fwd + off * c * row_wire)


# ---------------------------------------------------------------------------
# ambient context: ShardingPlan(embedding_shard=...) -> lookup lowerings
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class EmbeddingContext:
    """What a lookup lowering needs to route a table through the sharded
    path: the plan (axis resolution per table name), its mesh, the feed
    batch axes (ids arrive batch-sharded), and the exchange knobs.  Made
    ambient by the Executor for exactly the duration of a trace —
    the same pattern as ``compress.comm_scope``."""
    plan: Any
    mesh: Mesh
    batch_axes: Tuple[str, ...] = ()
    capacity_factor: Optional[float] = None
    quantize: str = ""

    def axis_for_lookup(self, wname: str) -> Optional[str]:
        """The vocab-shard axis for table ``wname`` at a lookup site (the
        plan's dict patterns, bound names, or blanket default)."""
        return self.plan.embedding_axis_for(wname, lookup=True)


_EMB_STACK: List[EmbeddingContext] = []


@contextlib.contextmanager
def embedding_scope(ctx: Optional[EmbeddingContext]):
    """Make ``ctx`` the ambient embedding-shard configuration while a
    program traces (no-op when None)."""
    if ctx is None:
        yield None
        return
    _EMB_STACK.append(ctx)
    try:
        yield ctx
    finally:
        _EMB_STACK.pop()


def current_embedding() -> Optional[EmbeddingContext]:
    return _EMB_STACK[-1] if _EMB_STACK else None


def resolve_tables(program, plan) -> Dict[str, str]:
    """Scan a Program for lookup ops and map each table's W var name to its
    vocab-shard axis under ``plan.embedding_shard`` — how a blanket
    (``embedding_shard="tp"``) plan learns which *state* leaves are tables
    so ``state_shardings`` can place them (dict-form patterns match state
    names directly and need no program)."""
    out: Dict[str, str] = {}
    if getattr(plan, "embedding_shard", None) is None:
        return out
    for block in program.blocks:
        for op in block.ops:
            if op.type not in LOOKUP_OPS:
                continue
            names = op.inputs.get("W", ())
            if not names:
                continue
            axis = plan.embedding_axis_for(names[0], lookup=True)
            if axis is not None:
                out[names[0]] = axis
    return out


# ---------------------------------------------------------------------------
# the single-device sparse path (is_sparse / dedup'd segment-sum gradient)
# ---------------------------------------------------------------------------

def _int_cotangent(ids):
    # custom_vjp wants a cotangent per primal; integer primals take float0
    return np.zeros(np.shape(ids), jax.dtypes.float0)


def sparse_lookup(weight, ids):
    """``weight[ids]`` whose backward is the SelectedRows analogue: unique
    the ids (static size bound), segment-sum cotangent rows over the
    duplicates, and scatter only the unique rows — the gradient *work*
    scales with batch ids, not vocab size (the reference's ``is_sparse``
    contract, lookup_table_op.cc SelectedRows branch).  ``ids`` is 1-D."""
    vocab = int(weight.shape[0])
    wdtype = jnp.result_type(weight)

    @jax.custom_vjp
    def _lookup(w, ids_):
        return jnp.take(w, ids_, axis=0)

    def _fwd(w, ids_):
        return jnp.take(w, ids_, axis=0), ids_

    def _bwd(res, g):
        ids_ = res
        n = ids_.shape[0]
        uniq, inv = jnp.unique(ids_, size=n, fill_value=vocab,
                               return_inverse=True)
        g_u = jax.ops.segment_sum(g, inv.reshape(-1), num_segments=n)
        # sentinel-padded slots index row `vocab` -> out of bounds -> drop
        dw = jnp.zeros(weight.shape, wdtype).at[uniq].add(
            g_u.astype(wdtype), mode="drop")
        return dw, _int_cotangent(ids_)

    _lookup.defvjp(_fwd, _bwd)
    return _lookup(weight, ids)


# ---------------------------------------------------------------------------
# the sharded path: dedup -> all_to_all ids -> gather -> all_to_all rows
# ---------------------------------------------------------------------------

def _quantize_rows(rows, kind: str):
    """(payload, per-row scales) via compress.quantize_blockwise with one
    block per row — the PR 7 wire format, block_size = embedding dim."""
    from . import compress as _compress

    dim = rows.shape[-1]
    payload, scales = _compress.quantize_blockwise(
        rows.reshape(-1), kind=kind, block_size=dim)
    return payload.reshape(rows.shape), scales.reshape(rows.shape[:-1])


def _dequantize_rows(payload, scales):
    return payload.astype(jnp.float32) * scales[..., None]


def _make_body(k: int, axis: str, rows_per: int, vocab: int, cap: int,
               quantize: str, wdtype):
    """Per-device program of one vocab-sharded lookup (runs inside
    shard_map with ``axis`` bound).  The table is replicated over the
    data-parallel axes; shard_map's transpose psums its cotangent over
    them (each replica contributes its local batch's sparse update), so
    the body must NOT psum — tests/test_sharded_embedding.py pins the
    dp>1 gradient parity that would catch a double count."""

    def _route(ids_local):
        n = ids_local.shape[0]
        uniq, inv = jnp.unique(ids_local, size=n, fill_value=vocab,
                               return_inverse=True)
        owner = uniq // rows_per                     # sorted; sentinel -> k
        starts = jnp.searchsorted(owner, jnp.arange(k))
        pos = jnp.arange(n) - starts[jnp.clip(owner, 0, k - 1)]
        kept = (uniq < vocab) & (pos >= 0) & (pos < cap) & (owner < k)
        send = jnp.full((k, cap), vocab, ids_local.dtype)
        send = send.at[owner, pos].set(uniq, mode="drop")
        return inv.reshape(-1), owner, pos, kept, send

    def _fwd_core(w_local, ids_local):
        inv, owner, pos, kept, send = _route(ids_local)
        me = lax.axis_index(axis)
        recv = lax.all_to_all(send, axis, split_axis=0, concat_axis=0)
        lo = me * rows_per
        lidx = jnp.clip(recv - lo, 0, rows_per - 1)
        mine = (recv >= lo) & (recv < lo + rows_per)
        rows = jnp.where(mine[..., None], w_local[lidx],
                         jnp.zeros((), w_local.dtype))
        back = lax.all_to_all(rows, axis, split_axis=0, concat_axis=0)
        u_rows = back[jnp.clip(owner, 0, k - 1), jnp.clip(pos, 0, cap - 1)]
        u_rows = jnp.where(kept[:, None], u_rows,
                           jnp.zeros((), u_rows.dtype))
        out = u_rows[inv]
        return out, (inv, owner, pos, kept, recv, mine)

    @jax.custom_vjp
    def body(w_local, ids_local):
        return _fwd_core(w_local, ids_local)[0]

    def body_fwd(w_local, ids_local):
        out, res = _fwd_core(w_local, ids_local)
        return out, (res, ids_local)

    def body_bwd(saved, g):
        (inv, owner, pos, kept, recv, mine), ids_local = saved
        n = inv.shape[0]
        # segment-sum over duplicate ids: each unique row's cotangent is the
        # sum of its token cotangents — the only reduction over the batch
        g_u = jax.ops.segment_sum(g, inv, num_segments=n)
        g_u = jnp.where(kept[:, None], g_u, jnp.zeros((), g.dtype))
        send_g = jnp.zeros((k, cap) + g.shape[1:], g.dtype)
        send_g = send_g.at[owner, pos].set(g_u, mode="drop")
        if quantize:
            payload, scales = _quantize_rows(send_g, quantize)
            recv_p = lax.all_to_all(payload, axis, split_axis=0,
                                    concat_axis=0)
            recv_s = lax.all_to_all(scales, axis, split_axis=0,
                                    concat_axis=0)
            recv_g = _dequantize_rows(recv_p, recv_s)
        else:
            recv_g = lax.all_to_all(send_g, axis, split_axis=0,
                                    concat_axis=0)
        me = lax.axis_index(axis)
        lidx = jnp.where(mine, recv - me * rows_per, rows_per)  # OOB -> drop
        dw = jnp.zeros((rows_per,) + g.shape[1:], wdtype)
        dw = dw.at[lidx.reshape(-1)].add(
            recv_g.reshape(-1, g.shape[-1]).astype(wdtype), mode="drop")
        return dw, _int_cotangent(ids_local)

    body.defvjp(body_fwd, body_bwd)
    return body


def sharded_lookup(weight, ids, *, mesh: Mesh, axis: str,
                   batch_axes: Sequence[str] = (),
                   capacity_factor: Optional[float] = None,
                   quantize: str = ""):
    """Lookup 1-D ``ids`` in a ``(V, D)`` table vocab-sharded over mesh
    ``axis``.  Falls back to the dedup'd single-device path when the axis
    is degree-1.  ``batch_axes`` shard the id batch (data parallelism);
    the table is replicated over them."""
    vocab, dim = int(weight.shape[0]), int(weight.shape[1])
    k = int(mesh.shape[axis]) if axis in mesh.axis_names else 1
    if k <= 1:
        return sparse_lookup(weight, ids)
    if vocab % k:
        raise ValueError(
            f"vocab {vocab} is not divisible by mesh axis {axis!r} size {k} "
            "(shardcheck SC010 front-runs this when check_sharding is on)")
    n_global = int(ids.shape[0])
    bspec = tuple(a for a in batch_axes if a in mesh.axis_names)
    dp = 1
    for a in bspec:
        dp *= int(mesh.shape[a])
    if dp <= 1 or n_global % dp:
        bspec, dp = (), 1
    n_local = n_global // dp
    cap = unique_capacity(n_local, k, capacity_factor)
    _m_exchange_bytes.observe(float(exchange_bytes(
        n_local, dim, k, capacity_factor, quantize or None)))
    body = _make_body(k, axis, vocab // k, vocab, cap, quantize,
                      jnp.result_type(weight))
    b = (bspec if len(bspec) > 1 else bspec[0]) if bspec else None
    out = jax.shard_map(
        body, mesh=mesh,
        in_specs=(PartitionSpec(axis, None), PartitionSpec(b)),
        out_specs=PartitionSpec(b, None), check_vma=False)(
        weight, ids)
    return out


# ---------------------------------------------------------------------------
# the lowering entry point (shared by lookup_table / lookup_table_v2)
# ---------------------------------------------------------------------------

def lower_lookup(w, ids, attrs: Dict[str, Any], wname: str):
    """One embedding lookup as the static lowerings execute it: routes to
    the vocab-sharded exchange when the ambient plan covers ``wname``, to
    the dedup'd sparse-gradient path when ``is_sparse`` asks for it, and
    to a plain gather otherwise; ``padding_idx`` rows are zeroed in the
    output (and therefore contribute zero gradient — the mask rides the
    chain rule)."""
    pad = attrs.get("padding_idx", -1)
    pad = None if pad is None or int(pad) < 0 else int(pad)
    flat = ids.reshape(-1).astype(jnp.int32)
    ctx = current_embedding()
    axis = ctx.axis_for_lookup(wname) if ctx is not None else None
    if axis is not None:
        out = sharded_lookup(
            w, flat, mesh=ctx.mesh, axis=axis, batch_axes=ctx.batch_axes,
            capacity_factor=ctx.capacity_factor, quantize=ctx.quantize)
    elif attrs.get("is_sparse", False):
        out = sparse_lookup(w, flat)
    else:
        out = jnp.take(w, flat, axis=0)
    if pad is not None:
        out = out * (flat != pad).astype(out.dtype)[:, None]
    return out.reshape(tuple(ids.shape) + (int(w.shape[-1]),))


# ---------------------------------------------------------------------------
# the user-facing subsystem + PS hybrid interop
# ---------------------------------------------------------------------------

class ShardedEmbedding:
    """A vocab-sharded embedding table as a first-class object (dygraph /
    jit use; static programs go through ``ShardingPlan(embedding_shard=)``
    instead).  The table is placed ``P(axis, None)`` on construction and
    every ``lookup`` runs the dedup + all_to_all exchange; gradients flow
    through ``jax.grad`` as sparse row exchanges."""

    def __init__(self, num_embeddings: int, embedding_dim: int, *,
                 axis: str = _mesh.TP_AXIS, mesh: Optional[Mesh] = None,
                 capacity_factor: Optional[float] = None,
                 quantize: str = "", padding_idx: Optional[int] = None,
                 weight=None, name: str = "sharded_embedding",
                 seed: int = 0):
        self.mesh = mesh or _mesh.current_mesh()
        self.axis = axis
        self.name = name
        self.capacity_factor = capacity_factor
        self.quantize = quantize
        self.padding_idx = padding_idx
        k = (int(self.mesh.shape[axis])
             if axis in self.mesh.axis_names else 1)
        if num_embeddings % max(k, 1):
            raise ValueError(
                f"num_embeddings {num_embeddings} not divisible by mesh "
                f"axis {axis!r} size {k}")
        if weight is None:
            key = jax.random.PRNGKey(seed)
            weight = (jax.random.normal(
                key, (num_embeddings, embedding_dim), jnp.float32)
                / np.sqrt(embedding_dim))
        else:
            weight = jnp.asarray(weight)
            if tuple(weight.shape) != (num_embeddings, embedding_dim):
                raise ValueError(
                    f"weight shape {tuple(weight.shape)} != "
                    f"({num_embeddings}, {embedding_dim})")
        self.weight = jax.device_put(
            weight, NamedSharding(self.mesh, PartitionSpec(axis, None)))

    @property
    def num_embeddings(self) -> int:
        return int(self.weight.shape[0])

    @property
    def embedding_dim(self) -> int:
        return int(self.weight.shape[1])

    def lookup(self, ids, weight=None):
        """Rows for ``ids`` (any shape) — ``ids.shape + (D,)``.  Pass an
        explicit ``weight`` to differentiate through it functionally."""
        w = self.weight if weight is None else weight
        ids = jnp.asarray(ids)
        flat = ids.reshape(-1).astype(jnp.int32)
        out = sharded_lookup(
            w, flat, mesh=self.mesh, axis=self.axis,
            capacity_factor=self.capacity_factor, quantize=self.quantize)
        if self.padding_idx is not None:
            out = out * (flat != self.padding_idx).astype(out.dtype)[:, None]
        return out.reshape(tuple(ids.shape) + (self.embedding_dim,))

    __call__ = lookup

    def spec(self) -> Tuple[str, None]:
        """The annotation tuple a ShardingPlan places this table with."""
        return (self.axis, None)

    def to_host_table(self, *, name: Optional[str] = None,
                      num_shards: int = 4, optimizer: str = "sgd"):
        """Export onto the host PS plane — see module-level
        :func:`to_host_table`."""
        return to_host_table(self.weight, name=name or self.name,
                             num_shards=num_shards, optimizer=optimizer)


def to_host_table(weight, *, name: Optional[str] = None,
                  num_shards: int = 4, optimizer: str = "sgd"):
    """The hybrid host-table path: materialize a (possibly device-sharded)
    table as a ``distributed.ps.SparseTable`` preloaded with its trained
    rows, and — when ``name`` is given — register it for the PS data-plane
    ops (``distributed_lookup_table``/``pull_sparse``/``push_sparse``), so
    a fleet program can keep serving/updating the same weights host-side
    after mesh training (the reference's heterogeneous PS story)."""
    from ..distributed.ps import SparseTable
    from ..static.ops_tail2 import register_ps_table

    host = np.asarray(weight, np.float32)
    vocab, dim = host.shape
    table = SparseTable(dim=int(dim), num_shards=int(num_shards),
                        initializer=lambda d: np.zeros(d, np.float32),
                        optimizer=optimizer)
    ids = np.arange(vocab, dtype=np.int64)
    table.apply_delta(ids, host)
    if name:
        register_ps_table(name, table)
    return table
