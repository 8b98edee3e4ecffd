"""Pipeline parallelism over the `pp` mesh axis.

Reference parity: `PipelineOptimizer` (python/paddle/fluid/optimizer.py:3661)
splits a ProgramDesc into per-device "section" programs and runs them with
`PipelineTrainer`/`SectionWorker` threads connected by host queues
(framework/trainer.h:207, device_worker.h:415); micro-batch count comes from
PipelineConfig (framework/distributed_strategy.proto:92).

TPU-native design: no section programs, no queues — a *circular collective
pipeline*.  All pp ranks run the same jitted SPMD program under `shard_map`;
each rank holds its stage's parameters (the leading block dim is sharded over
`pp`), and activations rotate around the ring with `lax.ppermute` once per
tick of a `lax.scan`.  Micro-batch b enters stage 0 at tick b and exits stage
S-1 at tick b+S-1 — the same GPipe schedule the reference implements with
threads, expressed as data flow that XLA overlaps with compute on ICI.  The
whole schedule is differentiable (scan + ppermute transpose), so backward
pipelining comes from AD rather than a hand-written 1F1B interpreter.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec

from . import mesh as _mesh
from .collective import shard_map

__all__ = [
    "microbatch", "unmicrobatch", "pipeline_apply", "pipeline_train_1f1b",
    "stack_block_params", "blockwise_stage_fn", "PipelineStage",
]


def microbatch(x, num_micro: int):
    """[B, ...] -> [num_micro, B/num_micro, ...] (ref PipelineConfig
    micro_batch splitting of the feed batch)."""
    if x.shape[0] % num_micro != 0:
        raise ValueError(
            f"batch {x.shape[0]} not divisible by micro-batch count {num_micro}")
    return x.reshape((num_micro, x.shape[0] // num_micro) + x.shape[1:])


def unmicrobatch(x):
    """Inverse of microbatch."""
    return x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:])


def pipeline_apply(stage_fn: Callable, stage_params, xs, *, axis: str = _mesh.PP_AXIS):
    """Run the circular pipeline. MUST be called inside shard_map/pjit with
    `axis` bound (each rank sees only its stage's params).

    stage_fn: (stage_params, x) -> y with y.shape == x.shape (uniform stages —
      the transformer-block case; put embedding/head outside the pipeline).
    stage_params: this rank's parameters (leading stage dim already consumed
      by the shard_map in_spec).
    xs: [num_micro, mb, ...] micro-batched activations, identical on every pp
      rank (replicated over `axis`).
    Returns [num_micro, mb, ...] outputs, replicated over `axis`.
    """
    n = lax.psum(1, axis)
    me = lax.axis_index(axis)
    num_micro = xs.shape[0]
    total_ticks = num_micro + n - 1
    state0 = jnp.zeros_like(xs[0])
    outs0 = jnp.zeros_like(xs)
    # psum(1) constant-folds to the (static) axis size, so python arithmetic
    # on n is fine.
    ring = [(i, (i + 1) % n) for i in range(n)]

    def tick(carry, t):
        state, outs = carry
        # stage 0 ingests micro-batch t (clamped; garbage after the last one
        # never reaches the final stage within the scan horizon)
        inp = lax.dynamic_index_in_dim(xs, jnp.clip(t, 0, num_micro - 1), 0,
                                       keepdims=False)
        state = jnp.where(me == 0, inp, state)
        y = stage_fn(stage_params, state)
        # last stage retires micro-batch t-(n-1)
        w = t - (n - 1)
        wc = jnp.clip(w, 0, num_micro - 1)
        valid = (me == n - 1) & (w >= 0)
        cur = lax.dynamic_index_in_dim(outs, wc, 0, keepdims=False)
        outs = lax.dynamic_update_index_in_dim(
            outs, jnp.where(valid, y, cur), wc, 0)
        nxt = lax.ppermute(y, axis, ring)
        return (nxt, outs), None

    (_, outs), _ = lax.scan(tick, (state0, outs0), jnp.arange(total_ticks))
    # Broadcast the retired outputs from the last stage to every rank so the
    # loss/head can run replicated (psum of a one-hot-by-rank contribution).
    outs = lax.psum(jnp.where(me == n - 1, outs, jnp.zeros_like(outs)), axis)
    return outs


def pipeline_train_1f1b(stage_fn: Callable, loss_fn: Callable,
                        stage_params, head_params, xs, targets, *,
                        axis: str = _mesh.PP_AXIS):
    """One-forward-one-backward pipeline schedule with manual VJP.

    Reference parity: the SectionWorker's interleaved schedule
    (framework/device_worker.h:415; fluid/optimizer.py:3661 emits the
    per-section programs it runs).  Unlike `pipeline_apply` (GPipe shape:
    forward scan + AD-transposed backward scan, all micro-batch residuals
    live), 1F1B retires each micro-batch's backward as soon as its cotangent
    arrives, so at most ``2*n_stages - 1`` micro-batch *boundary inputs* are
    stashed per rank — and the stage forward is recomputed from the stashed
    input during backward (activation recompute), so no block-internal
    residuals survive a tick.  Peak activation memory is O(n_stages) instead
    of GPipe's O(num_micro + n_stages); FLOPs pay one extra stage forward
    per micro-batch (the usual remat trade).

    Schedule (paired fwd+bwd slots per tick; ranks ``me``, ticks ``t``):
      * forward of micro-batch b on rank me at   t = b + me
      * loss + output cotangent on the LAST rank at t = b + n - 1 (same tick
        as its forward — the 1F1B property)
      * backward of micro-batch b on rank me at  t = b + 2(n-1) - me
    Total horizon T = num_micro + 2(n-1).

    Must be called inside shard_map with `axis` manual.  Arguments:
      stage_fn:   (stage_params, x, micro_idx) -> y, uniform stages,
                  y.shape == x.shape.  ``micro_idx`` (traced int32) is the
                  micro-batch index — identical between a micro-batch's
                  forward and its backward replay, so per-micro randomness
                  (dropout keys folded on it) stays consistent across the
                  recompute.
      loss_fn:    (head_params, y_mb, target_mb, micro_idx) -> scalar mean
                  loss for one micro-batch.  Runs on the last rank only
                  (guarded by lax.cond, so other ranks skip the head
                  compute); differentiated w.r.t. (head_params, y_mb).
                  ``micro_idx`` serves per-micro RNG, like stage_fn's.
      stage_params: this rank's stage parameters (pp dim consumed)
      head_params:  replicated head/criterion parameters (pytree, may be {})
      xs:         [num_micro, mb, ...] micro-batched stage-0 inputs
                  (replicated over pp)
      targets:    pytree of [num_micro, ...] per-micro-batch labels
    Returns (loss_mean, stage_grads, head_grads, dxs) where dxs is
    [num_micro, mb, ...] — the cotangents w.r.t. xs (for the caller to
    continue backward into the embedding), replicated over pp.
    """
    n = lax.psum(1, axis)
    me = lax.axis_index(axis)
    num_micro = xs.shape[0]
    S = min(2 * n - 1, num_micro)  # max in-flight stash slots per rank
    T = num_micro + 2 * (n - 1)
    ring_fwd = [(i, (i + 1) % n) for i in range(n)]
    ring_bwd = [((i + 1) % n, i) for i in range(n)]

    zero_act = jnp.zeros_like(xs[0])
    stash0 = jnp.zeros((S,) + xs.shape[1:], xs.dtype)
    sgrads0 = jax.tree_util.tree_map(jnp.zeros_like, stage_params)
    hgrads0 = jax.tree_util.tree_map(jnp.zeros_like, head_params)
    inv_micro = 1.0 / num_micro

    def loss_cot(args):
        """loss and cotangents for one micro-batch on the last rank."""
        hp, y, tgt, b = args
        l, (dh, dy) = jax.value_and_grad(
            lambda h_, y_: loss_fn(h_, y_, tgt, b), argnums=(0, 1))(hp, y)
        return l.astype(jnp.float32), dh, dy

    def loss_skip(args):
        hp, y, tgt, b = args
        return (jnp.zeros((), jnp.float32),
                jax.tree_util.tree_map(jnp.zeros_like, hp),
                jnp.zeros_like(y))

    def tick(carry, t):
        fwd_state, bwd_cot, stash, dxs, sgrads, hgrads, loss_sum = carry

        # ---- forward slot: micro b_f = t - me -----------------------------
        b_f = t - me
        active_f = (b_f >= 0) & (b_f < num_micro)
        b_fc = jnp.clip(b_f, 0, num_micro - 1)
        inp = lax.dynamic_index_in_dim(xs, b_fc, 0, keepdims=False)
        x_in = jnp.where(me == 0, inp, fwd_state)
        y = stage_fn(stage_params, x_in, b_fc)
        slot_f = jnp.mod(b_fc, S)
        old = lax.dynamic_index_in_dim(stash, slot_f, 0, keepdims=False)
        stash = lax.dynamic_update_index_in_dim(
            stash, jnp.where(active_f, x_in, old), slot_f, 0)

        # ---- last rank: per-micro loss + output cotangent -----------------
        # lax.cond (scalar pred inside the manual shard_map) so non-last
        # ranks skip the head forward+backward entirely instead of masking
        # it out — the head can be a vocab-sized projection.
        tgt = jax.tree_util.tree_map(
            lambda a: lax.dynamic_index_in_dim(a, b_fc, 0, keepdims=False),
            targets)
        is_last = me == n - 1
        take_loss = active_f & is_last
        l_b, dh_b, dy_b = lax.cond(take_loss, loss_cot, loss_skip,
                                   (head_params, y, tgt, b_fc))
        loss_sum = loss_sum + l_b
        hgrads = jax.tree_util.tree_map(
            lambda acc, g: acc + g * inv_micro, hgrads, dh_b)

        # ---- backward slot: micro b_b = t - 2(n-1) + me -------------------
        b_b = t - 2 * (n - 1) + me
        active_b = (b_b >= 0) & (b_b < num_micro)
        b_bc = jnp.clip(b_b, 0, num_micro - 1)
        # last rank consumes its own dy from THIS tick (b_b == b_f there)
        cot_in = jnp.where(is_last, dy_b * inv_micro, bwd_cot)
        slot_b = jnp.mod(b_bc, S)
        x_saved = lax.dynamic_index_in_dim(stash, slot_b, 0, keepdims=False)
        _, vjp_fn = jax.vjp(
            lambda sp, x: stage_fn(sp, x, b_bc), stage_params, x_saved)
        dparams, dx = vjp_fn(cot_in)
        sgrads = jax.tree_util.tree_map(
            lambda acc, g: acc + jnp.where(active_b, g, jnp.zeros_like(g)),
            sgrads, dparams)
        # rank 0 retires dx into dxs (cotangent w.r.t. the pipeline input)
        take_dx = active_b & (me == 0)
        cur_dx = lax.dynamic_index_in_dim(dxs, b_bc, 0, keepdims=False)
        dxs = lax.dynamic_update_index_in_dim(
            dxs, jnp.where(take_dx, dx, cur_dx), b_bc, 0)

        # ---- rotate ------------------------------------------------------
        fwd_state = lax.ppermute(y, axis, ring_fwd)
        bwd_cot = lax.ppermute(dx, axis, ring_bwd)
        return (fwd_state, bwd_cot, stash, dxs, sgrads, hgrads, loss_sum), None

    carry0 = (zero_act, zero_act, stash0, jnp.zeros_like(xs), sgrads0,
              hgrads0, jnp.asarray(0.0, jnp.float32))
    (_, _, _, dxs, sgrads, hgrads, loss_sum), _ = lax.scan(
        tick, carry0, jnp.arange(T))

    # loss/head grads live on the last rank, dxs on rank 0: broadcast both
    loss = lax.psum(loss_sum, axis) * inv_micro
    hgrads = lax.psum(jax.tree_util.tree_map(
        lambda g: jnp.where(me == n - 1, g, jnp.zeros_like(g)), hgrads), axis)
    dxs = lax.psum(jnp.where(me == 0, dxs, jnp.zeros_like(dxs)), axis)
    return loss, sgrads, hgrads, dxs


def stack_block_params(block_params: Sequence[Dict[str, jax.Array]]
                       ) -> Dict[str, jax.Array]:
    """Stack per-block {name: array} dicts into {name: [L, ...] array} — the
    layout the pipeline shards over pp (and that lax.scan consumes within a
    stage). All blocks must be isomorphic."""
    keys = list(block_params[0])
    for bp in block_params[1:]:
        if list(bp) != keys:
            raise ValueError("pipeline blocks must have identical parameter "
                             f"structure; got {list(bp)} vs {keys}")
    return {k: jnp.stack([bp[k] for bp in block_params]) for k in keys}


def blockwise_stage_fn(block_fn: Callable) -> Callable:
    """Lift a single-block fn into a stage fn that scans over the stage's
    local blocks: stage_params leaves are [L_local, ...]."""

    def stage_fn(stage_params, x):
        def body(h, blk):
            return block_fn(blk, h), None

        out, _ = lax.scan(body, x, stage_params)
        return out

    return stage_fn


class PipelineStage:
    """High-level wrapper: partition a stack of isomorphic block Layers into
    pp stages and expose a pure pipelined apply for use inside pjit.

    Usage (inside your jitted train step, mesh active):
        pipe = PipelineStage(block_fn, stacked_params, num_micro=4)
        y = pipe(x)            # x: [B, ...] replicated over pp
    """

    def __init__(self, block_fn: Callable, stacked_params: Dict[str, jax.Array],
                 num_micro: int = 1, axis: str = _mesh.PP_AXIS,
                 mesh=None):
        self.block_fn = block_fn
        self.axis = axis
        self.num_micro = num_micro
        self.mesh = mesh or _mesh.current_mesh()
        n_stages = _mesh.mesh_axis_size(axis, self.mesh)
        L = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
        if L % n_stages != 0:
            raise ValueError(f"{L} blocks not divisible into {n_stages} stages")
        self.params = stacked_params

    def sharding_spec(self):
        """PartitionSpec placing the block dim over pp (leaves: [L, ...])."""
        return PartitionSpec(self.axis)

    def sharding_annotations(self):
        """Per-leaf annotation axes ({name: (pp, None, ...)}) in the format
        `parallel.sharding.infer_sharding` (and so `ShardingPlan`) consumes —
        pass these to `CompiledProgram.with_sharding(annotations=...)` to run
        pipeline-stage state under the Executor's sharded fast path."""
        return {k: (self.axis,) + (None,) * (v.ndim - 1)
                for k, v in self.params.items()}

    def shard_params(self):
        from . import sharding as _sharding

        self.params = _sharding.shard_params(
            self.params, mesh=self.mesh,
            annotations=self.sharding_annotations())
        return self.params

    def __call__(self, x, params=None):
        params = self.params if params is None else params
        n_stages = _mesh.mesh_axis_size(self.axis, self.mesh)
        if n_stages == 1:
            # degenerate: plain scan over all blocks
            stage = blockwise_stage_fn(self.block_fn)
            return stage(params, x)
        xs = microbatch(x, self.num_micro)
        stage = blockwise_stage_fn(self.block_fn)

        # Other mesh axes (dp/tp/sp) stay available inside: shard_map only
        # consumes pp here; data/weight sharding over other axes is preserved
        # by passing their specs through.
        def run(p, xs_):
            return pipeline_apply(stage, p, xs_, axis=self.axis)

        in_param_spec = jax.tree_util.tree_map(
            lambda _: PartitionSpec(self.axis), params)
        f = shard_map(
            run, mesh=self.mesh,
            in_specs=(in_param_spec, PartitionSpec()),
            out_specs=PartitionSpec(), check_vma=False)
        return unmicrobatch(f(params, xs))
