"""Fleet — the unified distributed-training facade.

Reference parity: python/paddle/distributed/fleet/base/fleet_base.py:41
(`Fleet.init` :103, `distributed_optimizer` :540, `minimize` :573), the
protobuf `DistributedStrategy` (framework/distributed_strategy.proto:94) and
the meta-optimizer chain (meta_optimizers/: amp, recompute, gradient_merge,
lars, lamb, localsgd, dgc, pipeline, graph_execution).

TPU-native design: `DistributedStrategy` is a typed dataclass (SURVEY.md §5.6
recommends replacing scattered proto/gflags with one config object); `init`
builds the hybrid mesh; `distributed_optimizer` composes the strategy into a
`DistributedOptimizer` whose functional `update` is pure/jit-safe, so the
whole "meta-optimizer program rewrite" collapses into ordinary function
composition inside one pjit'd train step:
  - amp            → bf16 compute dtype policy (+ optional dynamic loss scale
                     retained for fp16-style parity, amp_configs)
  - recompute      → jax.checkpoint policy applied by the train-step builder
  - gradient_merge → k-step gradient accumulation carried in opt state
  - localsgd       → k local steps then cross-dp param average
  - lars/lamb      → swap the inner optimizer rule
  - dgc            → top-k sparsify + momentum correction + error feedback
                     per replica, pmean the sparse tensor over dp, apply as
                     SGD (dgc_configs; ref dgc_op.cc +
                     sparse_all_reduce_op_handle.cc).  Note: on ICI, dense
                     allreduce is usually cheaper — DGC pays off over DCN.
  - sharding       → ZeRO-1: optimizer state sharded over dp
                     (HybridPretrainer constrains new opt state with
                     parallel.sharding.zero_spec)
  - pipeline/tensor/sequence degrees → mesh axes (hybrid_configs)
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from . import mesh as _mesh
from . import collective as _coll
from . import compress as _compress
from ..distributed import env as _env


@dataclasses.dataclass
class RecomputeConfig:  # proto :25 RecomputeConfig
    checkpoints: tuple = ()
    policy: str = "dots_saveable"  # jax.checkpoint policy name


@dataclasses.dataclass
class GradientMergeConfig:  # proto GradientMergeConfig
    k_steps: int = 1
    avg: bool = True


@dataclasses.dataclass
class LocalSGDConfig:  # proto :39 LocalSGDConfig
    k_steps: int = 1


@dataclasses.dataclass
class AMPConfig:  # contrib/mixed_precision decorator.py:218 knobs
    dtype: str = "bfloat16"
    init_loss_scaling: float = 1.0  # bf16 needs no scaling; >1 enables it
    incr_every_n_steps: int = 1000
    decr_every_n_nan_or_inf: int = 2
    incr_ratio: float = 2.0
    decr_ratio: float = 0.5
    use_dynamic_loss_scaling: bool = False


@dataclasses.dataclass
class PipelineConfig:  # proto :92 PipelineConfig
    micro_batch: int = 1
    schedule: str = "gpipe"  # or "1f1b"


@dataclasses.dataclass
class HybridConfig:
    dp_degree: int = -1
    mp_degree: int = 1   # tensor parallel ("mp" in fleet naming)
    pp_degree: int = 1
    sp_degree: int = 1   # sequence/context parallel
    ep_degree: int = 1


@dataclasses.dataclass
class ShardingConfig:  # ZeRO; fleet "sharding" strategy
    stage: int = 1


@dataclasses.dataclass
class DGCConfig:  # proto :47 DGCConfig
    rampup_begin_step: int = 0
    sparsity: float = 0.999
    momentum: float = 0.9


@dataclasses.dataclass
class ElasticConfig:
    """Elastic fault-tolerance knobs (ref: the fleet elastic manager +
    incubate checkpoint saver; paddle_tpu/elastic/).  ``save_every`` > 0
    with a ``ckpt_dir`` turns on periodic resharding-capable manifest
    checkpoints inside hapi Model.fit (via the elastic_* flags);
    ``dead_after_s``/``heartbeat_s`` parameterize membership when a worker
    builds an ``ElasticMember`` from this config."""
    ckpt_dir: str = ""
    save_every: int = 0
    keep_last: int = 2
    heartbeat_s: float = 0.5
    dead_after_s: float = 3.0


@dataclasses.dataclass
class CommConfig:
    """Gradient-sync communication knobs (parallel/compress.py): bucket
    coalescing size (the reducer.cc `comm_buffer_size` analogue), quantized
    payload block size, and hierarchical (intra-host/inter-host) scheduling
    ("auto" factors by jax.local_device_count; "off" forces flat; an int is
    the intra-group size)."""
    block_size: int = 256
    buffer_size_mb: float = 25.0
    hierarchical: Any = "auto"


@dataclasses.dataclass
class EmbeddingConfig:
    """Vocab-sharded embedding knobs (parallel/embedding.py): the mesh
    axis tables shard over, the exchange-buffer capacity factor (None =
    exact, no drops), and the backward-exchange wire quantization
    ("int8"/"fp8" per-row blockwise, or "")."""
    axis: str = _mesh.TP_AXIS
    capacity_factor: Any = None
    quantize: str = ""


class DistributedStrategy:
    """Typed strategy object (ref proto distributed_strategy.proto:94)."""

    def __init__(self):
        self.amp = False
        self.amp_configs = AMPConfig()
        self.recompute = False
        self.recompute_configs = RecomputeConfig()
        self.gradient_merge = False
        self.gradient_merge_configs = GradientMergeConfig()
        self.localsgd = False
        self.localsgd_configs = LocalSGDConfig()
        self.lars = False
        self.lamb = False
        self.dgc = False
        self.dgc_configs = DGCConfig()
        self.sharding = False
        self.sharding_configs = ShardingConfig()
        self.pipeline = False
        self.pipeline_configs = PipelineConfig()
        self.hybrid_configs = HybridConfig()
        self.elastic = False
        self.elastic_configs = ElasticConfig()
        self.sequence_parallel = False
        # Gradient-sync ownership: "" leaves sync to the train-step builder
        # (legacy psum/pmean); "none" makes update() own a bucketed
        # full-precision sync; "int8"/"fp8" additionally quantize the wire
        # payload (EQuARX-style, parallel/compress.py).
        self.comm_quantize = ""
        self.comm_configs = CommConfig()
        # the reference's sparse-embedding story (fleet PS lookup_table)
        # mapped to the mesh: vocab-shard every lookup-op table
        self.sharded_embedding = False
        self.embedding_configs = EmbeddingConfig()
        # cost-model-driven plan search (parallel/autoplan.py): the
        # static-graph path resolves the whole ShardingPlan — mesh
        # factoring, placement rules, zero stage, embedding coverage,
        # quantization — at first run instead of honoring hand knobs;
        # compose via auto_shard_plan(program, strategy)
        self.auto_shard = False
        self.find_unused_parameters = False  # parity no-op
        self.fuse_all_reduce_ops = True      # parity no-op (XLA fuses)
        self.nccl_comm_num = 1               # parity no-op (ICI)

    def __repr__(self):
        on = [k for k, v in self.__dict__.items() if v is True]
        return f"DistributedStrategy(enabled={on})"


def embedding_plan_kwargs(strategy: DistributedStrategy) -> Dict[str, Any]:
    """``ShardingPlan`` kwargs for a strategy's sharded-embedding knobs —
    the bridge from fleet's typed strategy to the static-graph plan::

        plan = ShardingPlan(mesh=mesh, **embedding_plan_kwargs(strategy))

    Empty dict when ``strategy.sharded_embedding`` is off, so it composes
    with other plan kwargs unconditionally."""
    if not getattr(strategy, "sharded_embedding", False):
        return {}
    cfg = strategy.embedding_configs
    return {"embedding_shard": cfg.axis,
            "embedding_capacity": cfg.capacity_factor,
            "embedding_quantize": cfg.quantize}


def auto_shard_plan(program, strategy: Optional[DistributedStrategy] = None,
                    mesh=None, feed=None, fetch_names=()):
    """Resolve a ``ShardingPlan`` for ``program`` through the autoplan
    cost-model search (parallel/autoplan.py) — the static-graph face of
    ``DistributedStrategy.auto_shard``::

        strategy.auto_shard = True
        plan = fleet.auto_shard_plan(main, strategy)
        compiled = static.CompiledProgram(main).with_sharding(plan=plan)

    Memoized by program-content x mesh fingerprints (resolve_auto), so
    every rank of a job derives the same plan and the chosen fingerprint
    rides the persistent compile-cache key.  With ``strategy.auto_shard``
    off this returns None — callers fall through to hand-written knobs."""
    if strategy is not None and not getattr(strategy, "auto_shard", False):
        return None
    from . import autoplan as _autoplan

    return _autoplan.resolve_auto(program, mesh=mesh, feed=feed,
                                  fetch_names=fetch_names)


class _RoleMaker:
    """Env-var role maker (ref: fleet/base/role_maker.py:220
    PaddleCloudRoleMaker — PADDLE_TRAINER_ID/PADDLE_TRAINERS_NUM contract;
    on TPU the process topology comes from jax.distributed)."""

    def worker_index(self) -> int:
        return _env.get_rank()

    def worker_num(self) -> int:
        return _env.get_world_size()

    def is_first_worker(self) -> bool:
        return self.worker_index() == 0

    def is_worker(self) -> bool:
        return True

    def is_server(self) -> bool:
        return False  # PS mode is host-offloaded/descoped on TPU (SURVEY §2.2)


class Fleet:
    """ref: fleet_base.py:41.  Singleton accessed as paddle_tpu.distributed.fleet."""

    def __init__(self):
        self._role_maker: Optional[_RoleMaker] = None
        self._strategy: Optional[DistributedStrategy] = None
        self._mesh = None

    # -- lifecycle -----------------------------------------------------------
    def init(self, role_maker=None, is_collective: bool = True,
             strategy: Optional[DistributedStrategy] = None,
             devices=None) -> "Fleet":
        """``devices`` restricts the mesh to a subset of ``jax.devices()``
        (e.g. one chip of a four-chip host); default all of them."""
        self._role_maker = role_maker or _RoleMaker()
        self._strategy = strategy or DistributedStrategy()
        hc = self._strategy.hybrid_configs
        if not isinstance(hc, HybridConfig):  # allow dict like fleet does
            hc = HybridConfig(**{k: v for k, v in dict(hc).items()
                                 if k in HybridConfig.__dataclass_fields__})
            self._strategy.hybrid_configs = hc
        self._mesh = _mesh.init_parallel_env(
            dp=None if hc.dp_degree == -1 else hc.dp_degree,
            pp=hc.pp_degree, tp=hc.mp_degree, sp=hc.sp_degree,
            ep=hc.ep_degree, devices=devices)
        ec = self._strategy.elastic_configs
        if self._strategy.elastic and ec.save_every > 0 and ec.ckpt_dir:
            # surface the cadence through the flags Model.fit reads, so
            # strategy-driven jobs get periodic elastic checkpoints without
            # touching their fit() call
            from ..core import flags as _flags

            _flags.set_flags({"elastic_save_every": int(ec.save_every),
                              "elastic_ckpt_dir": ec.ckpt_dir,
                              "elastic_keep_last": int(ec.keep_last)})
        return self

    @property
    def mesh(self):
        return self._mesh or _mesh.current_mesh()

    @property
    def strategy(self):
        return self._strategy

    # -- role queries (ref fleet_base worker_* API) ---------------------------
    def worker_index(self):
        return self._role().worker_index()

    def worker_num(self):
        return self._role().worker_num()

    def is_first_worker(self):
        return self._role().is_first_worker()

    def is_worker(self):
        return True

    def is_server(self):
        return False

    def barrier_worker(self):
        _coll.barrier()

    def _role(self):
        if self._role_maker is None:
            self.init()
        return self._role_maker

    # -- optimizer -----------------------------------------------------------
    def distributed_optimizer(self, optimizer, strategy=None):
        strategy = strategy or self._strategy or DistributedStrategy()
        self._strategy = strategy
        return DistributedOptimizer(optimizer, strategy)


class DistributedOptimizer:
    """Strategy-composed optimizer (the meta-optimizer chain as function
    composition).  Exposes the same functional init/update contract as
    optimizer.Optimizer, so train-step builders treat it identically."""

    def __init__(self, inner, strategy: DistributedStrategy):
        from ..optimizer.optimizers import SGD, Lamb, LarsMomentum
        self.strategy = strategy
        cq = getattr(strategy, "comm_quantize", "")
        if cq not in ("", "none") and cq not in _compress.COMPRESS_KINDS:
            raise ValueError(
                f"DistributedStrategy.comm_quantize={cq!r}; expected '' "
                f"(builder-owned sync), 'none', or one of "
                f"{_compress.COMPRESS_KINDS}")
        # Pass the raw _lr through so an LRScheduler keeps scheduling (get_lr()
        # would freeze it at its current scalar value).
        if strategy.lamb and not isinstance(inner, Lamb):
            inner = Lamb(learning_rate=inner._lr,
                         parameters=inner._parameters)
        elif strategy.lars and not isinstance(inner, LarsMomentum):
            inner = LarsMomentum(learning_rate=inner._lr,
                                 parameters=inner._parameters)
        if strategy.dgc:
            # DGC's momentum correction folds momentum into the compressed
            # velocity (ref DGCMomentumOptimizer, fluid/optimizer.py:1176):
            # the inner update must be plain SGD or momentum compounds.
            # Pre-rampup momentum comes from the wrapper's velocity (the
            # reference's momentum-SGD warmup), so nothing is lost here.
            self._dgc_momentum = getattr(
                inner, "momentum", strategy.dgc_configs.momentum)
            if not isinstance(inner, SGD):
                inner = SGD(learning_rate=inner._lr,
                            parameters=inner._parameters)
        self.inner = inner

    # passthrough niceties
    def get_lr(self, step=None):
        return self.inner.get_lr(step)

    @property
    def _parameters(self):
        return self.inner._parameters

    def init(self, params) -> Dict[str, Any]:
        state = {"inner": self.inner.init(params)}
        if self.strategy.dgc:
            zeros = lambda tree: jax.tree_util.tree_map(  # noqa: E731
                lambda p: jnp.zeros(jnp.shape(p), jnp.float32), tree)
            state["dgc"] = {"velocity": zeros(params),
                            "error": zeros(params)}
        gm = self.strategy.gradient_merge_configs
        if self.strategy.gradient_merge and gm.k_steps > 1:
            state["acc"] = jax.tree_util.tree_map(
                lambda p: jnp.zeros(jnp.shape(p), jnp.float32), params)
            state["acc_count"] = jnp.zeros((), jnp.int32)
        if (self.strategy.amp and
                self.strategy.amp_configs.use_dynamic_loss_scaling):
            state["loss_scale"] = jnp.asarray(
                self.strategy.amp_configs.init_loss_scaling, jnp.float32)
            state["good_steps"] = jnp.zeros((), jnp.int32)
        return state

    def update(self, grads, state, params, lr=None):
        """Pure. Composes: [unscale+skip-on-nonfinite] → [k-step merge] →
        inner update → [localsgd periodic average]."""
        new_state = dict(state)
        cfg = self.strategy

        if getattr(cfg, "comm_quantize", "") and not cfg.dgc:
            # Owned gradient sync (comm_quantize set): bucketed, optionally
            # quantized mean-allreduce over the bound dp axis, issued on the
            # still-scaled grads — blockwise quantization is loss-scale
            # invariant, and a non-finite grad on ANY replica propagates
            # through the mean so every replica takes the same skip-step
            # branch below.  Under GSPMD/eager the axis is unbound and sync
            # falls back to the builder (identity here).
            axis = _coll.bound_data_axis()
            if axis is not None:
                cc = cfg.comm_configs
                grads = _compress.sync_gradients(
                    grads, axis,
                    compress=None if cfg.comm_quantize == "none"
                    else cfg.comm_quantize,
                    block_size=cc.block_size, buffer_mb=cc.buffer_size_mb,
                    hierarchy=cc.hierarchical)

        finite = None
        if "loss_scale" in state:
            scale = state["loss_scale"]
            grads = jax.tree_util.tree_map(lambda g: g / scale, grads)
            finite = jnp.array(True)
            for g in jax.tree_util.tree_leaves(grads):
                finite &= jnp.all(jnp.isfinite(g))
            ac = cfg.amp_configs
            good = jnp.where(finite, state["good_steps"] + 1, 0)
            scale = jnp.where(
                finite & (good >= ac.incr_every_n_steps), scale * ac.incr_ratio,
                jnp.where(finite, scale, scale * ac.decr_ratio))
            new_state["loss_scale"] = scale
            new_state["good_steps"] = jnp.where(
                good >= ac.incr_every_n_steps, 0, good)

        if cfg.dgc and "dgc" in state:
            # ref dgc_op.cc + sparse_all_reduce_op_handle.cc: compress each
            # replica's LOCAL gradient (momentum correction + error
            # feedback + top-k), allreduce only the sparse tensor over the
            # dp axis, and apply it as the update (inner is SGD; momentum
            # already folded by the compression).
            from ..optimizer.extras import dgc_compress

            dc = cfg.dgc_configs
            step = state["inner"].get("step", jnp.zeros((), jnp.int32)) \
                if isinstance(state["inner"], dict) else jnp.zeros((), jnp.int32)
            rampup = int(dc.rampup_begin_step)

            mom = getattr(self, "_dgc_momentum", dc.momentum)

            def compressed(g32, v, e):
                return dgc_compress(g32, v, e, dc.sparsity, mom)

            if rampup <= 0:
                # compression is active from step 0 forever: compile only
                # the compressed branch (no dead v_warm top-k-side FLOPs)
                def one(g, v, e):
                    return compressed(g.astype(jnp.float32), v, e)
            else:
                # pre-rampup: plain momentum-SGD warmup using the same
                # velocity slot (ref DGCMomentumOptimizer warmup dynamics);
                # lax.cond executes exactly one branch per step instead of
                # computing both and selecting
                def one(g, v, e):
                    def warm(args):
                        g32, v_, e_ = args
                        v_warm = mom * v_ + g32
                        return v_warm, v_warm, e_
                    return jax.lax.cond(
                        step >= rampup, lambda args: compressed(*args), warm,
                        (g.astype(jnp.float32), v, e))

            flat_g, treedef = jax.tree_util.tree_flatten(grads)
            flat_v = treedef.flatten_up_to(state["dgc"]["velocity"])
            flat_e = treedef.flatten_up_to(state["dgc"]["error"])
            outs = [one(g, v, e) for g, v, e in zip(flat_g, flat_v, flat_e)]
            sparse = [o[0] for o in outs]
            axis = _coll.bound_data_axis()
            if axis is not None:
                cq = getattr(cfg, "comm_quantize", "")
                if cq in _compress.COMPRESS_KINDS:
                    cc = cfg.comm_configs
                    sparse = [_compress.optimized_all_reduce(
                        s, axis, compress=cq, block_size=cc.block_size,
                        hierarchy=cc.hierarchical, mean=True)
                        for s in sparse]
                else:
                    sparse = [jax.lax.pmean(s, axis) for s in sparse]
            grads = jax.tree_util.tree_unflatten(treedef, sparse)
            new_state["dgc"] = {
                "velocity": jax.tree_util.tree_unflatten(
                    treedef, [o[1] for o in outs]),
                "error": jax.tree_util.tree_unflatten(
                    treedef, [o[2] for o in outs])}

        if cfg.gradient_merge and "acc" in state:
            k = cfg.gradient_merge_configs.k_steps
            acc = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(jnp.float32), state["acc"], grads)
            count = state["acc_count"] + 1
            do_step = count >= k

            def merged(g_sum):
                if cfg.gradient_merge_configs.avg:
                    return jax.tree_util.tree_map(lambda a: a / k, g_sum)
                return g_sum

            new_p, inner_state = self.inner.update(
                merged(acc), state["inner"], params, lr=lr)
            # cond on pytrees: keep old (params, inner) unless k-th step
            new_params = jax.tree_util.tree_map(
                lambda np_, p: jnp.where(do_step, np_, jnp.asarray(p)),
                new_p, params)
            new_inner = jax.tree_util.tree_map(
                lambda n, o: jnp.where(do_step, n, o) if hasattr(n, "shape") or hasattr(o, "shape") else n,
                inner_state, state["inner"])
            new_state["acc"] = jax.tree_util.tree_map(
                lambda a: jnp.where(do_step, jnp.zeros_like(a), a), acc)
            new_state["acc_count"] = jnp.where(do_step, 0, count)
            new_state["inner"] = new_inner
            new_p = new_params
        else:
            new_p, new_state["inner"] = self.inner.update(
                grads, state["inner"], params, lr=lr)

        if finite is not None:
            # Skip-step semantics of update_loss_scaling (mixed_precision/
            # decorator.py:169): a non-finite step leaves parameters AND
            # optimizer state untouched (zeroing grads would still move
            # params via weight decay / momentum), keeping only the
            # loss-scale bookkeeping above.
            def _keep_old(new, old):
                if hasattr(new, "shape") or hasattr(old, "shape"):
                    return jnp.where(finite, new, jnp.asarray(old))
                return new

            new_p = jax.tree_util.tree_map(_keep_old, new_p, params)
            for key in new_state:
                if key not in ("loss_scale", "good_steps"):
                    new_state[key] = jax.tree_util.tree_map(
                        _keep_old, new_state[key], state[key])

        if cfg.localsgd and _coll.in_traced_context():
            k = cfg.localsgd_configs.k_steps
            step = new_state["inner"]["step"] if isinstance(
                new_state["inner"], dict) and "step" in new_state["inner"] else None
            axis = _env.current_data_axis() or _mesh.DP_AXIS
            if step is not None:
                do_avg = (step % k) == 0
                new_p = jax.tree_util.tree_map(
                    lambda p: jnp.where(do_avg, jax.lax.pmean(p, axis), p), new_p)
        return new_p, new_state

    # Stateful facade (dygraph-style step) mirrors Optimizer.step.
    def step(self, grads=None):
        params = self.inner._param_list()
        if grads is None:
            raise ValueError(
                "step() needs explicit grads: this framework has no global "
                "tape; compute grads via paddle_tpu.autograd.value_and_grad")
        if isinstance(grads, dict):
            grads = list(grads.values())
        values = [p.value for p in params]
        if getattr(self, "_state", None) is None:
            self._state = self.init(values)
        new_values, self._state = self.update(list(grads), self._state, values)
        for p, v in zip(params, new_values):
            p.value = v

    def clear_grad(self):
        pass

    def state_dict(self):
        return {"state": getattr(self, "_state", None)}


fleet = Fleet()
