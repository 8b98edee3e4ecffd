"""Communication compression & scheduling for data-parallel gradient sync.

Reference parity: the fleet gradient-sync stack — `c_allreduce_sum` ring
allreduce (operators/collective/c_allreduce_op.h), gradient bucket
coalescing (imperative/reducer.cc `Group`/`assign_group_by_size`, the
`comm_buffer_size` knob on dygraph DataParallel), and DGC's
sparse-allreduce ancestry (sparse_all_reduce_op_handle.cc).

TPU-native design (SURVEY.md §5.8): XLA's collectives cannot be interposed
per-hop, so EQuARX-style block-quantized allreduce (arxiv 2506.17615) is
rebuilt from mesh-axis primitives inside the traced step:

    local blockwise quantize (int8 / fp8-e4m3, per-block fp32 scale)
      -> all_to_all of the quantized payload (the reduce-scatter exchange)
      -> dequantize each peer chunk and accumulate in fp32
      -> re-quantize the reduced shard
      -> all_gather of the quantized shard -> dequantize

so only quantized bytes ride the interconnect while every accumulation
happens in fp32.  Hierarchical (TACCL-sketch, arxiv 2111.04867) scheduling
factors the dp axis into (intra-host, inter-host) via `axis_index_groups`:
full-precision reduce-scatter on the fast intra-host links, (optionally
quantized) allreduce of the 1/intra shard across hosts, intra-host
all-gather.  Bucketing coalesces gradient leaves into ~`comm_buffer_size`
MB flat fp32 buffers in deterministic reverse-topological order and chains
bucket *inputs* with `lax.optimization_barrier` so XLA issues each bucket's
collective as soon as its gradients exist (backward overlap) without
serializing the collectives themselves.
"""
from __future__ import annotations

import contextlib
import dataclasses
import hashlib
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

__all__ = [
    "COMPRESS_KINDS", "CommOptions", "comm_scope", "current_comm",
    "quantize_blockwise", "dequantize_blockwise", "all_reduce_compressed",
    "optimized_all_reduce", "hierarchical_groups", "resolve_hierarchy",
    "bucket_assignment", "bucket_signature", "bucketed_all_reduce",
    "sync_gradients", "wire_bytes",
]

# Quantized payload dtypes.  fp8 uses e4m3fn (finite max 448) when jaxlib
# ships it; int8 is always available.
COMPRESS_KINDS = ("int8", "fp8")
_QMAX = {"int8": 127.0, "fp8": 448.0}


def _payload_dtype(kind: str):
    if kind == "int8":
        return jnp.int8
    if kind == "fp8":
        if not hasattr(jnp, "float8_e4m3fn"):
            raise NotImplementedError(
                "fp8 gradient compression needs jnp.float8_e4m3fn, which "
                "this jaxlib does not provide; use compress='int8'")
        return jnp.float8_e4m3fn
    raise ValueError(
        f"unknown compression kind {kind!r}; expected one of {COMPRESS_KINDS}")


def _check_kind(kind: str) -> str:
    _payload_dtype(kind)  # raises on unknown/unsupported
    return kind


# -- comm options scope -------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CommOptions:
    """Gradient-communication options carried by DistributedStrategy /
    ShardingPlan into the traced step.

    quantize: "" (off), "none" (owned sync, full precision), "int8", "fp8".
    hierarchy: "auto" (factor by jax.local_device_count), "off"/None (flat),
        an int intra-group size, or an explicit (intra, inter) tuple.
    """
    quantize: str = ""
    block_size: int = 256
    buffer_mb: float = 25.0
    hierarchy: Any = "auto"

    def payload(self) -> Optional[str]:
        """The compression kind actually applied to wire payloads, or None."""
        return self.quantize if self.quantize in COMPRESS_KINDS else None

    def signature(self) -> str:
        return (f"q={self.quantize};bs={int(self.block_size)};"
                f"buf={float(self.buffer_mb):g};hier={self.hierarchy!r}")


_COMM_STACK: List[CommOptions] = []


@contextlib.contextmanager
def comm_scope(options: Optional[CommOptions]):
    """Make `options` the ambient comm configuration for collectives traced
    inside the scope (consumed by collective.all_reduce and the static
    c_allreduce_* lowerings when no explicit compress= is given)."""
    if options is None:
        yield None
        return
    _COMM_STACK.append(options)
    try:
        yield options
    finally:
        _COMM_STACK.pop()


def current_comm() -> Optional[CommOptions]:
    return _COMM_STACK[-1] if _COMM_STACK else None


# -- blockwise quantization ---------------------------------------------------

def quantize_blockwise(flat, kind: str = "int8", block_size: int = 256):
    """Quantize a flat fp32 vector (size divisible by block_size) into
    (payload, scales): payload is int8/fp8 with one fp32 scale per block of
    `block_size` elements (scale = blockwise max|x| / qmax, EQuARX-style).
    Zero blocks get scale 0 and a zero payload."""
    _check_kind(kind)
    flat = jnp.asarray(flat, jnp.float32)
    if flat.size % block_size:
        raise ValueError(
            f"quantize_blockwise needs size % block_size == 0, got "
            f"{flat.size} % {block_size}")
    blocks = flat.reshape(-1, block_size)
    amax = jnp.max(jnp.abs(blocks), axis=1, keepdims=True)
    scale = amax / _QMAX[kind]
    y = blocks / jnp.where(scale > 0, scale, 1.0)
    if kind == "int8":
        q = jnp.clip(jnp.round(y), -127.0, 127.0).astype(jnp.int8)
    else:
        q = y.astype(_payload_dtype(kind))
    return q.reshape(-1), scale.reshape(-1)


def dequantize_blockwise(payload, scales, block_size: int = 256):
    """Inverse of quantize_blockwise: flat fp32 vector."""
    blocks = payload.reshape(-1, block_size).astype(jnp.float32)
    return (blocks * scales.reshape(-1, 1)).reshape(-1)


# -- wire accounting ----------------------------------------------------------

def wire_bytes(nelem: int, compress: Optional[str] = None,
               block_size: int = 256, n: int = 2,
               dtype_bytes: int = 4) -> int:
    """Bytes moved over the interconnect by one ring allreduce of `nelem`
    elements across `n` members: 2*(n-1)/n * payload bytes, where the
    quantized payload carries 1 byte/element plus one fp32 scale per block.
    Counted from the sizes: cost_analysis on forced-host CPU does not
    model inter-device traffic."""
    if n <= 1:
        return 0
    if compress in COMPRESS_KINDS:
        per_elem = 1.0 + 4.0 / float(block_size)
    else:
        per_elem = float(dtype_bytes)
    return int(round(2.0 * (n - 1) / n * nelem * per_elem))


# -- hierarchy resolution -----------------------------------------------------

def hierarchical_groups(n: int, intra: int):
    """(intra_groups, inter_groups) partitioning axis ranks 0..n-1 assuming
    host-major device order (jax.devices() lists each host's devices
    consecutively): intra groups are runs of `intra` consecutive ranks,
    inter groups connect rank i of every host."""
    if n % intra:
        raise ValueError(f"axis size {n} not divisible by intra size {intra}")
    inter = n // intra
    intra_groups = [[h * intra + i for i in range(intra)]
                    for h in range(inter)]
    inter_groups = [[h * intra + i for h in range(inter)]
                    for i in range(intra)]
    return intra_groups, inter_groups


def resolve_hierarchy(hierarchy, n: int) -> Optional[Tuple[int, int]]:
    """Normalize a hierarchy spec to (intra, inter) or None (flat).

    "auto" factors by jax.local_device_count() (see mesh.dp_hierarchy) and
    degrades to flat when the axis lives on one host (or one device per
    host); an int is the intra-group size; a tuple is taken as-is."""
    if hierarchy in (None, "off", "flat", False, 0, 1):
        return None
    if hierarchy == "auto":
        from . import mesh as _mesh
        return _mesh.dp_hierarchy(n)
    if isinstance(hierarchy, (tuple, list)):
        intra, inter = int(hierarchy[0]), int(hierarchy[1])
        if intra * inter != n:
            raise ValueError(
                f"hierarchy {hierarchy!r} does not factor axis size {n}")
    else:
        intra = int(hierarchy)
        if n % intra:
            raise ValueError(
                f"hierarchy intra size {intra} does not divide axis size {n}")
        inter = n // intra
    if intra <= 1 or inter <= 1:
        return None
    return intra, inter


# -- quantized / hierarchical allreduce ---------------------------------------

def _group_size(axis, groups) -> int:
    if groups is not None:
        return len(groups[0])
    return lax.psum(1, axis)  # static python int


def all_reduce_compressed(x, axis, *, compress: str = "int8",
                          block_size: int = 256, groups=None,
                          mean_denom: Optional[int] = None):
    """Block-quantized sum-allreduce over a bound mesh axis (or a subset of
    it via axis_index_groups).  Payload rides the wire as int8/fp8 with
    per-block fp32 scales; accumulation is fp32.  `mean_denom` divides the
    reduced value before the second quantization (pmean semantics without
    spending quantization range on the division)."""
    _check_kind(compress)
    n = _group_size(axis, groups)
    if n <= 1:
        out = jnp.asarray(x, jnp.float32)
        if mean_denom:
            out = out / mean_denom
        return out.astype(x.dtype) if hasattr(x, "dtype") else out
    shape, dtype = jnp.shape(x), jnp.asarray(x).dtype
    flat = jnp.asarray(x, jnp.float32).reshape(-1)
    m = flat.size
    chunk = n * block_size
    pad = (-m) % chunk
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
    # 1. local blockwise quantize
    q, s = quantize_blockwise(flat, compress, block_size)
    # 2. reduce-scatter exchange: row j of the reshaped payload is the chunk
    #    owned by group member j; all_to_all hands each member everyone's
    #    copy of its own chunk.
    q = q.reshape(n, -1)
    s = s.reshape(n, -1)
    qx = lax.all_to_all(q, axis, split_axis=0, concat_axis=0, tiled=False,
                        axis_index_groups=groups)
    sx = lax.all_to_all(s, axis, split_axis=0, concat_axis=0, tiled=False,
                        axis_index_groups=groups)
    # 3. dequantize each peer's contribution and accumulate in fp32
    shard = jnp.sum(
        qx.reshape(n, -1, block_size).astype(jnp.float32)
        * sx.reshape(n, -1, 1), axis=0).reshape(-1)
    if mean_denom:
        shard = shard / mean_denom
    # 4. re-quantize the reduced shard and all-gather it
    q2, s2 = quantize_blockwise(shard, compress, block_size)
    qg = lax.all_gather(q2, axis, axis=0, tiled=True,
                        axis_index_groups=groups)
    sg = lax.all_gather(s2, axis, axis=0, tiled=True,
                        axis_index_groups=groups)
    out = dequantize_blockwise(qg, sg, block_size)
    if pad:
        out = out[:m]
    return out.reshape(shape).astype(dtype)


def optimized_all_reduce(x, axis, *, compress: Optional[str] = None,
                         block_size: int = 256, hierarchy: Any = "auto",
                         mean: bool = False):
    """Sum (or mean) allreduce over a bound mesh axis with optional
    block-quantized payload and optional hierarchical scheduling.

    Flat unquantized calls lower to plain lax.psum/pmean (bitwise-identical
    to the legacy path).  Hierarchical unquantized: intra reduce-scatter ->
    inter allreduce -> intra all-gather, all fp32.  With compress set, only
    the phase that crosses the slow (inter) links carries quantized bytes;
    hierarchical intra phases stay full precision."""
    if compress is not None:
        _check_kind(compress)
    n = lax.psum(1, axis)  # static
    hier = resolve_hierarchy(hierarchy, n)
    denom = n if mean else None
    _record_comm(axis, jnp.size(x), compress, block_size, n)
    if hier is None:
        if compress is None:
            return lax.pmean(x, axis) if mean else lax.psum(x, axis)
        return all_reduce_compressed(
            x, axis, compress=compress, block_size=block_size,
            mean_denom=denom)
    intra, _inter = hier
    intra_groups, inter_groups = hierarchical_groups(n, intra)
    shape, dtype = jnp.shape(x), jnp.asarray(x).dtype
    flat = jnp.asarray(x, jnp.float32).reshape(-1)
    m = flat.size
    pad = (-m) % intra
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
    # intra-host reduce-scatter on the fast links (full precision)
    shard = lax.psum_scatter(flat, axis, scatter_dimension=0, tiled=True,
                             axis_index_groups=intra_groups)
    # inter-host allreduce of the 1/intra shard (quantized when requested)
    if compress is None:
        shard = lax.psum(shard, axis, axis_index_groups=inter_groups)
        if denom:
            shard = shard / denom
    else:
        shard = all_reduce_compressed(
            shard, axis, compress=compress, block_size=block_size,
            groups=inter_groups, mean_denom=denom)
    # intra-host all-gather back to the full vector
    full = lax.all_gather(shard, axis, axis=0, tiled=True,
                          axis_index_groups=intra_groups)
    if pad:
        full = full[:m]
    return full.reshape(shape).astype(dtype)


def _record_comm(axis, nelem, compress, block_size, n):
    """Trace-time telemetry: wire bytes and compression ratio for one
    allreduce.  Recorded when the step is traced (not per execution — XLA
    runs the compiled collective, not this Python)."""
    try:
        from ..utils import monitor as _monitor
        wire = wire_bytes(nelem, compress, block_size, n)
        raw = wire_bytes(nelem, None, block_size, n)
        _monitor.histogram(
            "comm.allreduce_bytes", "wire bytes per allreduce",
            labelnames=("axis", "dtype"),
            buckets=(1 << 10, 1 << 14, 1 << 18, 1 << 22, 1 << 26, 1 << 30),
        ).observe(wire, axis=str(axis), dtype=compress or "fp32")
        if raw:
            _monitor.gauge(
                "comm.compress_ratio",
                "wire bytes relative to fp32 allreduce",
            ).set(wire / raw)
    except Exception:  # telemetry must never break tracing
        pass


# -- gradient bucketing -------------------------------------------------------

def bucket_assignment(sizes: Sequence[int], buffer_mb: float) -> List[List[int]]:
    """Greedy capacity fill: partition leaf indices (already in issue order)
    into contiguous buckets of at most ~buffer_mb MB of fp32 payload.  A
    leaf larger than the cap gets its own bucket.  Deterministic: depends
    only on the byte sizes and the cap."""
    cap = max(1, int(float(buffer_mb) * (1 << 20)))
    buckets: List[List[int]] = []
    cur: List[int] = []
    filled = 0
    for i, nbytes in enumerate(sizes):
        if cur and filled + nbytes > cap:
            buckets.append(cur)
            cur, filled = [], 0
        cur.append(i)
        filled += nbytes
    if cur:
        buckets.append(cur)
    return buckets


def _named_leaves(grads):
    """Flatten with stable path names.  Reversed flatten order is the issue
    order: backward produces the LAST layer's gradients first, and pytree
    registration order tracks forward/definition order."""
    leaves = jax.tree_util.tree_flatten_with_path(grads)[0]
    return [(jax.tree_util.keystr(kp), leaf) for kp, leaf in leaves]


def bucket_signature(grads, buffer_mb: float) -> str:
    """Stable hex digest of the bucket layout (leaf names, shapes, dtypes,
    cap).  Identical across processes/runs for the same gradient pytree —
    safe to feed the persistent compile-cache key."""
    named = _named_leaves(grads)
    rev = list(reversed(named))
    buckets = bucket_assignment(
        [int(jnp.size(leaf)) * 4 for _, leaf in rev], buffer_mb)
    h = hashlib.sha256()
    h.update(f"buffer_mb={float(buffer_mb):g}".encode())
    for b in buckets:
        h.update(b"|bucket")
        for i in b:
            name, leaf = rev[i]
            h.update(f";{name}:{jnp.shape(leaf)}:"
                     f"{jnp.asarray(leaf).dtype}".encode())
    return h.hexdigest()


def bucketed_all_reduce(grads, axis, *, buffer_mb: float = 25.0,
                        compress: Optional[str] = None,
                        block_size: int = 256, hierarchy: Any = "auto",
                        mean: bool = True):
    """Allreduce a gradient pytree in coalesced flat fp32 buckets.

    Leaves are concatenated in reverse flatten order (reverse-topological:
    the gradients the backward pass produces first go into the first
    bucket) and each bucket rides one `optimized_all_reduce`.  Bucket
    *inputs* are chained with lax.optimization_barrier so XLA schedules
    bucket k's collective before bucket k+1's gradients are complete —
    communication overlaps the remaining backward compute — without adding
    a data dependency between the collectives themselves."""
    named = _named_leaves(grads)
    treedef = jax.tree_util.tree_flatten(grads)[1]
    if not named:
        return grads
    rev = list(reversed(list(enumerate(named))))
    buckets = bucket_assignment(
        [int(jnp.size(leaf)) * 4 for _, (_, leaf) in rev], buffer_mb)
    out_flat: List[Any] = [None] * len(named)
    prev = None
    for bucket in buckets:
        parts = [jnp.asarray(rev[i][1][1], jnp.float32).reshape(-1)
                 for i in bucket]
        buf = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
        if prev is not None:
            # order the bucket inputs, not the results: collective k+1 may
            # not be issued before bucket k's buffer exists
            buf, prev = lax.optimization_barrier((buf, prev))
        else:
            prev = buf
        red = optimized_all_reduce(
            buf, axis, compress=compress, block_size=block_size,
            hierarchy=hierarchy, mean=mean)
        prev = buf
        off = 0
        for i in bucket:
            orig_idx, (_, leaf) = rev[i]
            size = int(jnp.size(leaf))
            piece = lax.dynamic_slice_in_dim(red, off, size, axis=0)
            out_flat[orig_idx] = piece.reshape(jnp.shape(leaf)).astype(
                jnp.asarray(leaf).dtype)
            off += size
    return jax.tree_util.tree_unflatten(treedef, out_flat)


def _leaf_varying(leaf, axis) -> bool:
    """Whether a value still varies over the axis (needs a true allreduce)
    vs arrives pre-summed (replicated-param backward under a
    ``check_vma=True`` shard_map).  Under ``check_vma=False`` nothing is
    tracked and nothing is pre-summed — even ``axis_index`` then reports an
    empty vma — so every leaf counts as varying."""
    tracked = axis in jax.typeof(lax.axis_index(axis)).vma
    return not tracked or axis in jax.typeof(leaf).vma


def sync_gradients(grads, axis, *, compress: Optional[str] = None,
                   block_size: int = 256, buffer_mb: float = 25.0,
                   hierarchy: Any = "auto"):
    """Average a gradient pytree over the bound dp axis: the shared bucketer
    behind fleet's comm_quantize and dygraph DataParallel(comm_buffer_size).

    Leaves that no longer vary over the axis (already summed by a
    VMA-tracking backward) are divided by the axis size locally; varying
    leaves ride the bucketed (optionally quantized, hierarchical) mean
    allreduce."""
    n = lax.psum(1, axis)
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    varying = [_leaf_varying(leaf, axis) for leaf in leaves]
    if all(varying):
        return bucketed_all_reduce(
            grads, axis, buffer_mb=buffer_mb, compress=compress,
            block_size=block_size, hierarchy=hierarchy, mean=True)
    # mixed tree: bucket the varying leaves, divide the rest in place
    idx = [i for i, v in enumerate(varying) if v]
    synced = bucketed_all_reduce(
        [leaves[i] for i in idx], axis, buffer_mb=buffer_mb,
        compress=compress, block_size=block_size, hierarchy=hierarchy,
        mean=True)
    out = [leaf if v else leaf / n for leaf, v in zip(leaves, varying)]
    for i, s in zip(idx, synced):
        out[i] = s
    return jax.tree_util.tree_unflatten(treedef, out)
