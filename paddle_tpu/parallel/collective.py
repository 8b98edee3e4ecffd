"""Collective communication API.

Reference parity: python/paddle/distributed/collective.py (broadcast :59,
all_reduce :116, reduce :191, all_gather :274, scatter :347, barrier :419)
and the `c_*` collective op family (paddle/fluid/operators/collective/
c_allreduce_op.h:38 etc.), whose NCCL communicators are keyed by ring_id
(platform/collective_helper.h:62).

TPU-native design: a "group" IS a mesh axis (ring_id ≈ axis name —
SURVEY.md §5.8).  Each function works in two execution contexts:

1. **Traced** inside `shard_map`/`pjit` (the hot path): lowers directly to
   the XLA collective (`lax.psum`, `lax.all_gather`, `lax.ppermute`, …) on
   the group's axis, riding ICI.
2. **Eager** on global arrays: wraps itself in a one-off `shard_map` over the
   current mesh, giving the same SPMD semantics for scripts/tests that call
   `dist.all_reduce(t)` imperatively like the reference's dygraph fast path
   (`core.ops.c_allreduce_sum_`).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec


def shard_map(f, mesh=None, in_specs=None, out_specs=None, check_vma=False):
    """`jax.shard_map` with VMA checking off by default (our collectives
    manage replication semantics explicitly)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)

from . import mesh as _mesh

__all__ = [
    "ReduceOp", "Group", "new_group", "get_group", "in_traced_context",
    "all_reduce", "all_gather", "all_to_all", "broadcast", "reduce",
    "reduce_scatter", "scatter", "barrier", "send", "recv", "ppermute",
]


class ReduceOp:
    """ref: distributed/collective.py ReduceOp enum."""
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


class Group:
    """A communicator group = a set of mesh axes (ref `ring_id` →
    `NCCLComm`, collective_helper.h:50).  Group 0 is "all axes" (the global
    ring); named groups reduce over a single axis."""

    def __init__(self, axes: Sequence[str], id: int = 0):
        self.axes = tuple(axes)
        self.id = id

    @property
    def axis(self):
        return self.axes if len(self.axes) > 1 else self.axes[0]

    def size(self, mesh=None) -> int:
        n = 1
        for a in self.axes:
            n *= _mesh.mesh_axis_size(a, mesh)
        return n

    @property
    def nranks(self) -> int:
        return self.size()

    @property
    def world_size(self) -> int:
        return self.size()

    def __repr__(self):
        return f"Group(id={self.id}, axes={self.axes})"


_groups: Dict[int, Group] = {}
_next_group_id = [1]


def _global_group() -> Group:
    m = _mesh.current_mesh()
    return Group(tuple(m.axis_names), id=0)


def new_group(axes=None, id: Optional[int] = None) -> Group:
    """Create a group over the given mesh axis/axes (default: all axes).

    ref: distributed/collective.py new_group / c_comm_init with ring_id.
    """
    if axes is None:
        g = _global_group()
    else:
        if isinstance(axes, str):
            axes = (axes,)
        gid = id if id is not None else _next_group_id[0]
        _next_group_id[0] = max(_next_group_id[0], gid) + 1
        g = Group(tuple(axes), id=gid)
    _groups[g.id] = g
    return g


def get_group(id: int = 0) -> Group:
    if id == 0:
        return _global_group()
    return _groups[id]


def _resolve(group) -> Group:
    if group is None:
        return _global_group()
    if isinstance(group, str):
        return Group((group,))
    if isinstance(group, (tuple, list)):
        return Group(tuple(group))
    return group


def in_traced_context() -> bool:
    """True when called under a jax trace (pjit/shard_map/grad), i.e. the
    axis names are live and lax collectives can be issued directly."""
    try:
        return not jax.core.trace_state_clean()
    except AttributeError:  # older/newer jax spelling
        return isinstance(jnp.zeros(()) + 0, jax.core.Tracer)


def bound_data_axis():
    """The data-parallel mesh axis usable from the CURRENT trace, or None.

    Inside shard_map (or any context that binds the axis name) this is the
    scoped data axis (env._DataAxisScope) falling back to the mesh's dp
    axis; under a plain jit / GSPMD trace or eager execution the name is
    unbound and collectives must degrade to identities."""
    from ..distributed import env as _env
    from . import mesh as _mesh

    if not in_traced_context():
        return None
    axis = _env.current_data_axis() or _mesh.DP_AXIS
    try:
        jax.lax.axis_index(axis)  # probe: is the name bound in this trace?
    except Exception as e:  # noqa: BLE001 — jax version-dependent error type
        if isinstance(e, NameError) or "unbound axis" in str(e):
            return None
        raise
    return axis


def _eager_axes(group: Group):
    """(mesh, group axes present in it, lax axis arg) — axes is None when the
    group is degenerate (absent axes / size 1) and the collective is a no-op."""
    m = _mesh.current_mesh()
    axes = tuple(a for a in group.axes if a in m.axis_names)
    if not axes or _resolve_size(m, axes) == 1:
        return m, None, None
    return m, axes, (axes if len(axes) > 1 else axes[0])


def _strip_axes(spec: PartitionSpec, axes) -> list:
    """Spec dims with the given axis names removed (dims that were sharded
    over a reduced/gathered axis become replicated); other axes keep their
    placement."""
    drop = set(axes)
    out = []
    for dim in tuple(spec):
        if dim is None:
            out.append(None)
        elif isinstance(dim, tuple):
            kept = tuple(a for a in dim if a not in drop)
            out.append(kept if kept else None)
        else:
            out.append(None if dim in drop else dim)
    return out


def _eager_collective(fn, x, axes, scatter_dim: Optional[int] = None):
    """Run `fn` (which issues lax collectives over `axes`) eagerly by
    shard_mapping it over the current mesh.

    Semantics are decided by the input's *actual placement*, never by shape
    heuristics: if `x` is already sharded over any of the group's axes, each
    rank's shard is its local tensor (the reference's per-rank view);
    otherwise `x` is replicated and every rank holds the full value.

    The output spec is derived from the input spec: the group's axes are
    consumed by the collective (replicated result along them) while sharding
    over *other* mesh axes is preserved — per-rank results differ along those
    axes and must stay sharded.  `scatter_dim` pins the group's axes onto that
    output dim (reduce_scatter)."""
    m = _mesh.current_mesh()
    in_spec = PartitionSpec()
    if isinstance(x, jax.Array) and hasattr(x, "sharding"):
        spec = getattr(x.sharding, "spec", None)
        if spec is not None:
            used = {a for dim in tuple(spec) if dim is not None
                    for a in (dim if isinstance(dim, tuple) else (dim,))}
            if used & set(axes):
                in_spec = spec
    out = _strip_axes(in_spec, axes)
    if scatter_dim is not None:
        while len(out) <= scatter_dim:
            out.append(None)
        out[scatter_dim] = axes if len(axes) > 1 else axes[0]
    while out and out[-1] is None:
        out.pop()
    f = shard_map(fn, mesh=m, in_specs=(in_spec,),
                  out_specs=PartitionSpec(*out), check_vma=False)
    return f(jnp.asarray(x))


def _resolve_size(m, axes) -> int:
    n = 1
    for a in axes:
        n *= m.shape[a]
    return n


# -- core collectives --------------------------------------------------------

def _resolve_compress(compress):
    """Normalize the compress= argument, consulting the ambient comm scope
    (compress.comm_scope — set by ShardingPlan/DistributedStrategy) when the
    caller passed None.  Returns a payload kind or None; "none" explicitly
    forces full precision inside a quantizing scope."""
    from . import compress as _compress
    if compress is None:
        opts = _compress.current_comm()
        return opts.payload() if opts is not None else None
    if compress in ("", "none", False):
        return None
    if compress not in _compress.COMPRESS_KINDS:
        raise ValueError(
            f"compress={compress!r}; expected one of "
            f"{_compress.COMPRESS_KINDS} or 'none'")
    return compress


def all_reduce(tensor, op: str = ReduceOp.SUM, group=None, sync_op=True,
               compress=None, block_size: int = 256, hierarchy="auto"):
    """ref: distributed/collective.py:116; c_allreduce_op.h:38.

    Traced: psum/pmax/pmin over the group axis.  Eager: global-view
    reduction across the leading-dim shards.

    compress="int8"/"fp8" rides the wire as an EQuARX-style block-quantized
    payload (parallel/compress.py; SUM and AVG only, single-axis groups);
    None inherits the ambient comm_scope, "none" forces full precision."""
    g = _resolve(group)
    opname = op.lower() if isinstance(op, str) else op
    kind = _resolve_compress(compress) \
        if opname in (ReduceOp.SUM, ReduceOp.AVG) else None
    if kind is not None and len(g.axes) > 1:
        kind = None  # multi-axis global ring: no single hierarchy, stay exact

    def _reduce_local(x, ax):
        if kind is not None:
            from . import compress as _compress
            return _compress.optimized_all_reduce(
                x, ax, compress=kind, block_size=block_size,
                hierarchy=hierarchy, mean=opname == ReduceOp.AVG)
        if opname == ReduceOp.SUM:
            return lax.psum(x, ax)
        if opname == ReduceOp.MAX:
            return lax.pmax(x, ax)
        if opname == ReduceOp.MIN:
            return lax.pmin(x, ax)
        if opname == ReduceOp.PROD:
            # sign-safe product: gather shards and multiply (no rooted
            # product primitive on ICI; log-sum-exp would NaN on x<=0)
            return jnp.prod(lax.all_gather(x, ax, axis=0, tiled=False), axis=0)
        if opname == ReduceOp.AVG:
            return lax.pmean(x, ax)
        raise ValueError(f"unknown reduce op {op!r}")

    if in_traced_context():
        return _reduce_local(tensor, g.axis)
    m, axes, ax = _eager_axes(g)
    if axes is None:
        return jnp.asarray(tensor)
    # Eager global view: each rank's tensor is the same-shaped replica; the
    # global-array equivalent of "every rank ends with the reduction" is just
    # the reduction itself, computed with one jitted psum over shards when the
    # array is sharded, else a no-op sum of one.
    return _instrumented_eager(
        lambda x: _reduce_local(x, ax), tensor, axes, ax, opname, kind,
        block_size, _resolve_size(m, axes))


def _instrumented_eager(fn, tensor, axes, ax, opname, kind, block_size, n):
    """Eager allreduce wrapped in a tracecat span + monitor histograms
    (comm.allreduce_bytes{axis,dtype}, comm.allreduce_ms{axis},
    comm.compress_ratio) so imperative sync shows up as comm, not compute.
    The device sync inside the timer only happens while metrics are on."""
    from . import compress as _compress
    from ..utils import monitor as _monitor
    from ..utils import trace as _trace

    nelem = int(jnp.size(tensor))
    wire = _compress.wire_bytes(nelem, kind, block_size, n)
    axis_label = "+".join(axes)
    with _trace.span("comm::allreduce", axis=axis_label, op=opname,
                     bytes=wire, compress=kind or "none"):
        timer = _monitor.histogram(
            "comm.allreduce_ms", "eager allreduce wall time",
            labelnames=("axis",), buckets=_monitor.TIME_MS_BUCKETS)
        with timer.time(axis=axis_label):
            out = _eager_collective(fn, tensor, axes)
            if _monitor.enabled():
                out = jax.block_until_ready(out)
    _monitor.histogram(
        "comm.allreduce_bytes", "wire bytes per allreduce",
        labelnames=("axis", "dtype"),
        buckets=(1 << 10, 1 << 14, 1 << 18, 1 << 22, 1 << 26, 1 << 30),
    ).observe(wire, axis=axis_label, dtype=kind or str(jnp.asarray(tensor).dtype))
    raw = _compress.wire_bytes(nelem, None, block_size, n)
    if raw:
        _monitor.gauge(
            "comm.compress_ratio", "wire bytes relative to fp32 allreduce",
        ).set(wire / raw)
    return out


def all_gather(tensor_or_list, tensor=None, group=None, axis: int = 0):
    """ref: distributed/collective.py:274 (list-out API) — also usable
    functionally: ``out = all_gather(x)`` returns the concatenation.

    Traced: lax.all_gather over the group axis (tiled into dim `axis`)."""
    out_list = None
    if isinstance(tensor_or_list, list):
        out_list, x = tensor_or_list, tensor
    else:
        x = tensor_or_list
    g = _resolve(group)

    if in_traced_context():
        out = lax.all_gather(x, g.axis, axis=axis, tiled=True)
    else:
        m, axes, ax = _eager_axes(g)
        if axes is None:
            out = jnp.asarray(x)
        else:
            # Eager/global view: every rank ends with the full concatenation
            # along the group's axes (sharding over other axes is preserved).
            out = _eager_collective(
                lambda v: lax.all_gather(v, ax, axis=axis, tiled=True),
                x, axes)
    if out_list is not None:
        n = g.size()
        out_list.extend(jnp.split(out, n, axis=axis))
        return out_list
    return out


def reduce_scatter(tensor, op: str = ReduceOp.SUM, group=None, axis: int = 0):
    """ref: operators/collective/c_reducescatter_op.cc.  Traced only→eager
    wrapper: psum_scatter over the group axis."""
    g = _resolve(group)
    if op.lower() != ReduceOp.SUM:
        raise NotImplementedError("reduce_scatter supports sum")
    if in_traced_context():
        return lax.psum_scatter(tensor, g.axis, scatter_dimension=axis,
                                tiled=True)
    m, axes, ax = _eager_axes(g)
    if axes is None:
        return jnp.asarray(tensor)
    return _eager_collective(
        lambda v: lax.psum_scatter(v, ax, scatter_dimension=axis, tiled=True),
        tensor, axes, scatter_dim=axis)


def all_to_all(in_tensor_list, out_tensor_list=None, group=None,
               split_axis: int = 0, concat_axis: int = 0):
    """ref: distributed/collective.py alltoall.  Functional form: pass a
    tensor, get the all-to-all'd tensor (split along split_axis, concat along
    concat_axis) — the Ulysses sequence-parallel primitive."""
    g = _resolve(group)
    if isinstance(in_tensor_list, (list, tuple)):
        x = jnp.concatenate([jnp.asarray(t)[None] for t in in_tensor_list], axis=0)
        split_axis, concat_axis = 0, 0
        listed = True
    else:
        x = in_tensor_list
        listed = False

    def _a2a(v, ax):
        return lax.all_to_all(v, ax, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=True)

    if in_traced_context():
        out = _a2a(x, g.axis)
    else:
        m, axes, ax = _eager_axes(g)
        if axes is None:
            out = jnp.asarray(x)
        else:
            spec_in = [None] * jnp.ndim(x)
            spec_in[concat_axis] = ax
            out = shard_map(lambda v: _a2a(v, ax), mesh=m,
                            in_specs=(PartitionSpec(*spec_in),),
                            out_specs=PartitionSpec(*_moved(spec_in, concat_axis, split_axis)),
                            check_vma=False)(jnp.asarray(x))
    if listed and out_tensor_list is not None:
        out_tensor_list.extend(list(out))
        return out_tensor_list
    return out


def _moved(spec, src, dst):
    spec = list(spec)
    spec[dst] = spec[src]
    if dst != src:
        spec[src] = None
    return spec


def broadcast(tensor, src: int = 0, group=None, sync_op=True):
    """ref: distributed/collective.py:59; c_broadcast_op.

    Traced: select rank-src's shard and psum-broadcast it.  Eager on a global
    array: returns src's shard replicated (leading dim = shards)."""
    g = _resolve(group)
    if in_traced_context():
        return _bcast_from(tensor, src, g.axis)
    m, axes, ax = _eager_axes(g)
    if axes is None:
        return jnp.asarray(tensor)
    return _eager_collective(lambda x: _bcast_from(x, src, ax), tensor, axes)


def reduce(tensor, dst: int = 0, op: str = ReduceOp.SUM, group=None):
    """ref: distributed/collective.py:191.  SPMD note: every rank computes the
    reduction (XLA has no rooted reduce on ICI); dst is accepted for API
    parity."""
    return all_reduce(tensor, op=op, group=group)


def scatter(tensor, tensor_list=None, src: int = 0, group=None):
    """ref: distributed/collective.py:347.  Traced: dynamic-slice this rank's
    chunk of src's tensor."""
    g = _resolve(group)
    if tensor_list is not None:
        stacked = jnp.stack([jnp.asarray(t) for t in tensor_list], axis=0)
    else:
        stacked = tensor

    def _scatter(x, ax):
        x = _bcast_from(x, src, ax)
        idx = lax.axis_index(ax)
        return lax.dynamic_index_in_dim(x, idx, axis=0, keepdims=False)

    if in_traced_context():
        return _scatter(stacked, g.axis)
    m, axes, ax = _eager_axes(g)
    if axes is None:
        return jnp.asarray(stacked)[0] if tensor_list is not None else jnp.asarray(stacked)
    # Eager global view: the scatter result is the stacked tensor with its
    # leading (rank) dim sharded over the group — each rank owns its chunk.
    return jax.device_put(
        jnp.asarray(stacked), NamedSharding(m, PartitionSpec(ax)))


def _bcast_from(x, src, ax):
    idx = lax.axis_index(ax)
    return lax.psum(jnp.where(idx == src, x, jnp.zeros_like(x)), ax)


def barrier(group=None):
    """ref: distributed/collective.py:419 (barrier op = allreduce of a scalar).
    On TPU a barrier is a psum of 1 + block_until_ready."""
    g = _resolve(group)
    m, axes, ax = _eager_axes(g)
    if axes is None:
        return
    out = _eager_collective(lambda x: lax.psum(x, ax), jnp.ones(()), axes)
    jax.block_until_ready(out)


def ppermute(tensor, perm, group=None):
    """Ring permute (the primitive under ring attention / pipeline bubbles;
    no reference equivalent — NCCL send/recv pairs play this role).  Traced
    contexts only: eager code has no per-rank view to permute."""
    if not in_traced_context():
        raise NotImplementedError(
            "ppermute is a per-rank SPMD primitive; call it inside "
            "shard_map/pjit (see parallel.pipeline / parallel.ring_attention)")
    g = _resolve(group)
    ax = g.axes if len(g.axes) > 1 else g.axes[0]
    return lax.ppermute(tensor, ax, perm)


def send(tensor, dst: int, group=None):
    """ref: distributed send/recv (PS-era RPC send_op).  Traced SPMD: a
    ppermute edge src→dst; usable only inside shard_map pairs with recv."""
    raise NotImplementedError(
        "point-to-point send/recv are expressed as lax.ppermute edges inside "
        "shard_map on TPU; use parallel.collective.ppermute")


recv = send
