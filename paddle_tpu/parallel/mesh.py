"""Device-mesh management.

Replaces the reference's communicator topology layer: `NCCLCommContext`'s
ring_id→communicator map (paddle/fluid/platform/collective_helper.h:62),
`InitNCCLCtxs`/`InitHierarchicalCtxs` multi-ring setup
(framework/parallel_executor.cc:118/:209), and the launch-time endpoint
plumbing (python/paddle/distributed/fleet/launch.py:188).  On TPU the
topology is a named `jax.sharding.Mesh`: each parallelism kind is a named
axis; "rings" are mesh axes; hierarchical (node-local + cross-node) rings are
simply the ICI/DCN split JAX makes when `jax.distributed` is initialized and
devices span hosts.
"""
from __future__ import annotations

import contextlib
import math
import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

# Canonical hybrid-parallel axis names (order = outermost..innermost; tp is
# innermost so tensor-parallel collectives ride the fastest ICI links).
DP_AXIS = "dp"
PP_AXIS = "pp"
EP_AXIS = "ep"
SP_AXIS = "sp"
TP_AXIS = "tp"
_CANONICAL_ORDER = (DP_AXIS, PP_AXIS, EP_AXIS, SP_AXIS, TP_AXIS)

_global_mesh: Optional[Mesh] = None


class MeshConfig:
    """Declarative hybrid-parallel topology (the rebuild's analogue of the
    reference's `DistributedStrategy` topology fields — sharding/pipeline
    configs in framework/distributed_strategy.proto:25–92).

    Any axis left as 1 is omitted from the mesh. ``dp=-1`` means "fill with
    whatever devices remain" (like the reference's nranks inference from
    endpoints).
    """

    def __init__(self, dp: int = -1, pp: int = 1, tp: int = 1, sp: int = 1,
                 ep: int = 1, devices: Optional[Sequence] = None):
        self.dp, self.pp, self.tp, self.sp, self.ep = dp, pp, tp, sp, ep
        self.devices = devices

    def resolve(self) -> Dict[str, int]:
        devices = self.devices if self.devices is not None else jax.devices()
        n = len(devices)
        sizes = {DP_AXIS: self.dp, PP_AXIS: self.pp, EP_AXIS: self.ep,
                 SP_AXIS: self.sp, TP_AXIS: self.tp}
        fixed = math.prod(v for v in sizes.values() if v != -1)
        if n % fixed != 0:
            raise ValueError(
                f"device count {n} not divisible by requested parallel "
                f"degrees {sizes} (product {fixed})")
        for k, v in sizes.items():
            if v == -1:
                sizes[k] = n // fixed
                fixed = n
        if math.prod(sizes.values()) != n:
            raise ValueError(f"mesh sizes {sizes} do not cover {n} devices")
        return sizes


def build_mesh(config: Optional[MeshConfig] = None, **axes) -> Mesh:
    """Create a Mesh from a MeshConfig or axis sizes (``build_mesh(dp=2, tp=4)``)."""
    if config is None:
        config = MeshConfig(**axes) if axes else MeshConfig()
    sizes = config.resolve()
    devices = config.devices if config.devices is not None else jax.devices()
    names = tuple(a for a in _CANONICAL_ORDER if sizes[a] > 1)
    if not names:  # degenerate single-axis mesh so collectives still resolve
        names = (DP_AXIS,)
    shape = tuple(sizes[a] for a in names)
    arr = np.asarray(devices).reshape(shape)
    return Mesh(arr, names)


def set_mesh(mesh: Optional[Mesh]) -> None:
    global _global_mesh
    _global_mesh = mesh


def get_mesh() -> Optional[Mesh]:
    return _global_mesh


@contextlib.contextmanager
def mesh_scope(mesh: Optional[Mesh]):
    """Make ``mesh`` the current mesh while a step that was built for it is
    traced, whatever `set_mesh` said last (a trainer holding an explicit
    mesh traces at its first call, which may come after another
    `fleet.init`)."""
    global _global_mesh
    prev, _global_mesh = _global_mesh, mesh
    try:
        yield
    finally:
        _global_mesh = prev


def current_mesh() -> Mesh:
    """The active mesh, creating a default all-`dp` mesh on first use (the
    reference's lazy ring-0 `NCCLCommContext` bootstrap equivalent)."""
    global _global_mesh
    if _global_mesh is None:
        _global_mesh = build_mesh(MeshConfig())
    return _global_mesh


def mesh_axis_size(axis: str, mesh: Optional[Mesh] = None) -> int:
    mesh = mesh or current_mesh()
    return mesh.shape[axis] if axis in mesh.axis_names else 1


def dp_hierarchy(axis_size: int,
                 local: Optional[int] = None) -> Optional[Tuple[int, int]]:
    """Factor a data-parallel axis of `axis_size` members into
    (intra-host, inter-host) group sizes, or None when the axis does not
    span hosts (everything local, or one device per host, or the host size
    does not divide the axis).

    The intra size comes from jax.local_device_count(): devices on one host
    share the fast ICI links, so collectives should reduce-scatter there
    before touching DCN (the InitHierarchicalCtxs two-ring split,
    parallel_executor.cc:209, rebuilt on mesh axis_index_groups)."""
    if local is None:
        local = jax.local_device_count()
    local = int(local)
    if local <= 1 or local >= axis_size or axis_size % local:
        return None
    return local, axis_size // local


def mesh_fingerprint(mesh: Optional[Mesh] = None) -> str:
    """Stable content fingerprint of a mesh's *shape*: axis names/sizes plus
    the device platform and kind.  Two processes over equivalent topologies
    (same axis layout, same hardware generation) produce the same string —
    the mesh component of the persistent compile-cache key
    (static/compile_cache.py); deliberately excludes device ids, which vary
    per process."""
    mesh = mesh or current_mesh()
    d0 = mesh.devices.ravel()[0]
    axes = ",".join(f"{a}={mesh.shape[a]}" for a in mesh.axis_names)
    return (f"mesh({axes})x{mesh.devices.size}"
            f"@{d0.platform}:{getattr(d0, 'device_kind', '?')}")


def init_parallel_env(strategy=None, *, dp: Optional[int] = None, pp: int = 1,
                      tp: int = 1, sp: int = 1, ep: int = 1,
                      devices: Optional[Sequence] = None) -> Mesh:
    """Initialize the distributed environment (ref:
    python/paddle/distributed/parallel.py:32 ``init_parallel_env`` — which
    exchanges NCCL ids over TCP and builds per-process communicators).

    TPU-native: multi-host coordination is jax.distributed (PJRT handles the
    DCN bootstrap; no id exchange), and the "environment" is just the global
    mesh.  Single-host virtual meshes (xla_force_host_platform_device_count)
    work identically.  ``devices`` restricts the mesh to a subset of
    ``jax.devices()`` (default: all of them).
    """
    if int(os.environ.get("PADDLE_TRAINERS_NUM", "1")) > 1:
        # fleetrun-style multi-process launch: defer to jax.distributed using
        # the same env contract as the reference's launch_utils endpoints.
        # launch.py exports PADDLE_COORDINATOR; PADDLE_MASTER / MASTER_ADDR
        # are accepted for reference/torchrun-style launchers.
        coord = (os.environ.get("PADDLE_COORDINATOR")
                 or os.environ.get("PADDLE_MASTER")
                 or os.environ.get("MASTER_ADDR", "127.0.0.1") + ":"
                 + os.environ.get("MASTER_PORT", "8271"))
        try:
            jax.distributed.initialize(
                coordinator_address=coord,
                num_processes=int(os.environ["PADDLE_TRAINERS_NUM"]),
                process_id=int(os.environ.get("PADDLE_TRAINER_ID", "0")))
        except RuntimeError as e:
            # Only the re-entrant case is benign; a failed bootstrap must not
            # silently degrade to single-host (wrong topology, divergence).
            if "already initialized" not in str(e).lower():
                raise
    cfg = MeshConfig(dp=-1 if dp is None else dp, pp=pp, tp=tp, sp=sp, ep=ep,
                     devices=devices)
    mesh = build_mesh(cfg)
    set_mesh(mesh)
    return mesh


def replicated(x, mesh: Optional[Mesh] = None):
    """Place a value fully replicated on the mesh."""
    mesh = mesh or current_mesh()
    return jax.device_put(x, NamedSharding(mesh, PartitionSpec()))


def data_sharding(mesh: Optional[Mesh] = None, batch_axes: Sequence[str] = (DP_AXIS,),
                  seq_axis: Optional[str] = None) -> NamedSharding:
    """Sharding for an input batch: leading dim over dp (and ep if present),
    optional second (sequence) dim over sp."""
    mesh = mesh or current_mesh()
    batch = tuple(a for a in batch_axes if a in mesh.axis_names)
    spec = [batch if batch else None]
    if seq_axis is not None and seq_axis in mesh.axis_names:
        spec.append(seq_axis)
    return NamedSharding(mesh, PartitionSpec(*spec))


def batch_shards(batch: int) -> int:
    """How a Pallas kernel is dispatched from the current trace, for a
    batch of ``batch`` rows: the number of data-parallel shards the batch
    splits into (the dp size of the current mesh; 1 when there is no mesh,
    no dp, dp is already manual, or dp does not divide the batch) — or 0
    when the kernel cannot be dispatched at all.

    That last case is a trace that is manual over SOME mesh axes only (the
    pp pipeline's shard_map, with dp/tp left to GSPMD): the kernel would
    need a shard_map nested in the partial-manual one, which XLA's SPMD
    partitioner does not survive under the 1F1B schedule (a CHECK failure
    in spmd_partitioner_util on jaxlib 0.9.0).  Callers fall back to the
    XLA lowering and count it (`pallas.fallbacks`)."""
    mesh = _global_mesh
    if mesh is None:
        return 1
    manual = jax.sharding.get_abstract_mesh().manual_axes
    free = [a for a in mesh.axis_names if a not in manual]
    if manual and math.prod(mesh.shape[a] for a in free) > 1:
        return 0
    if DP_AXIS not in free or batch % mesh.shape[DP_AXIS]:
        return 1
    return mesh.shape[DP_AXIS]


def per_batch_shard(fn, n_shards: int, batched: Sequence,
                    replicated: Sequence = ()):
    """``fn(*batched, *replicated)`` for a Pallas kernel inside a step that
    GSPMD partitions over the current mesh: run once per data-parallel
    shard of the leading (batch) dim of every ``batched`` argument
    (``n_shards`` from `batch_shards`; 1 = the batch is not split).

    A Mosaic call has no SPMD partitioning rule — jax refuses to lower one
    bare in a multi-device program ("cannot be automatically partitioned")
    — so every mesh axis becomes manual here: dp carries the batch, the
    others see replicated operands, which is what GSPMD does for any op it
    cannot partition.  On a one-device mesh, or inside a shard_map that is
    already manual over every axis, ``fn`` is called directly."""
    mesh = _global_mesh
    if (mesh is None or mesh.size == 1
            or jax.sharding.get_abstract_mesh().manual_axes):
        return fn(*batched, *replicated)
    over_dp = PartitionSpec(DP_AXIS) if n_shards > 1 else PartitionSpec()
    specs = (over_dp,) * len(batched) + (PartitionSpec(),) * len(replicated)
    return jax.shard_map(
        fn, mesh=mesh, in_specs=specs, out_specs=over_dp,
        check_vma=False)(*batched, *replicated)
