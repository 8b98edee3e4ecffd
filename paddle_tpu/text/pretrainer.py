"""Hybrid-parallel pretraining trainer (ERNIE/BERT, and any model described
as a `PretrainModel`: embedding, groups of uniform blocks, head, loss).

This is the rebuild's answer to the reference's fleet hybrid stack — the
composition of the PipelineOptimizer program splitter (fluid/optimizer.py:3661),
the collective data-parallel rewrites (transpiler/collective.py:178) and the
(absent-in-reference, designed-fresh) tensor/sequence/expert parallelism —
as ONE pjit'd train step over a dp×pp×ep×sp×tp mesh:

  dp — batch dim sharding (GSPMD inserts the gradient psum)
  tp — Megatron param sharding via ShardingRules (GSPMD collectives)
  sp — activation sequence-dim sharding (GSPMD) — ring attention available
       separately in parallel.ring_attention for the manual path
  pp — encoder blocks run through the circular ppermute pipeline inside a
       partial-manual shard_map (axis_names={'pp'}): pp is manual, all other
       axes stay GSPMD-automatic inside the body
  ep — MoE expert dim sharding (nn.MoEFFN every `moe_every` blocks)

The trainer builds no model of its own: it takes a `PretrainModel` — the
model's pieces and which batch keys each reads — and stacks, shards, scans
and differentiates them.  `ernie_pretrain_model` is the first such
description (an `ErnieConfig` handed to the trainer is turned into it);
`text/deepseek_v3.py` gives a decoder-only one with two groups of blocks,
`text/lfm2_moe.py` and `text/granite_hybrid.py` ones whose groups follow a
layer pattern (a group a run of one kind of block: `runs_of_one_kind`,
`run_groups`).
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
from typing import Any, Callable, Dict, Hashable, List, Optional, Sequence, \
    Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec

from .. import nn
from ..autograd import functional_call, parameters_dict
from ..core import random as _random
from ..parallel import mesh as _mesh
from ..parallel.pipeline import (
    blockwise_stage_fn,
    microbatch,
    pipeline_apply,
    stack_block_params,
    unmicrobatch,
)
from ..parallel.sharding import TRANSFORMER_RULES, infer_sharding
from ..utils import monitor
from ..utils import xprof as _xprof
from .ernie import ErnieConfig, ErnieEmbeddings, ErniePretrainingCriterion


class _MoEBlock(nn.Layer):
    """Encoder block whose FFN is expert-parallel (attention + MoEFFN)."""

    def __init__(self, cfg: ErnieConfig, num_experts: int):
        super().__init__()
        self.self_attn = nn.MultiHeadAttention(
            cfg.hidden_size, cfg.num_attention_heads,
            dropout=cfg.attention_probs_dropout_prob)
        self.norm1 = nn.LayerNorm(cfg.hidden_size)
        self.norm2 = nn.LayerNorm(cfg.hidden_size)
        self.moe = nn.MoEFFN(cfg.hidden_size, cfg.intermediate_size,
                             num_experts=num_experts, top_k=2,
                             capacity_factor=2.0)
        self.dropout = nn.Dropout(cfg.hidden_dropout_prob)

    def forward(self, x):
        with jax.named_scope(_xprof.REGION_ATTN):
            out = self.self_attn(x)
        with jax.named_scope(_xprof.REGION_LN):
            x = self.norm1(x + self.dropout(out))
        with jax.named_scope(_xprof.REGION_FFN):
            out = self.moe(x)
        with jax.named_scope(_xprof.REGION_LN):
            x = self.norm2(x + self.dropout(out))
        return x


@dataclasses.dataclass
class PretrainModel:
    """A model as `HybridPretrainer` trains it.

    embeddings: Layer called with the batch's `embed_inputs` -> [B, S, H].
    groups: {name: Layer with `.layers`, a list of uniform blocks x -> x};
      each group's parameters are stacked [L, ...] under its name and
      scanned (`blockwise_stage_fn`), the groups in order.  No name may be
      "embed" or "head".
    head: Layer called with (hidden, *batch's `head_inputs`, None where the
      batch lacks one) -> whatever `criterion` takes first.
    criterion: (head's outputs, batch) -> scalar loss.
    token_keys / row_keys: the batch keys the step reads, [B, S] ones
      (sharded over dp and sp) and [B, ...] ones (over dp).
    tied: {head parameter: embedding parameter} kept as one leaf under
      "embed" and bound into the head at call time.
    """
    embeddings: Any
    groups: Dict[str, Any]
    head: Any
    criterion: Callable
    embed_inputs: Tuple[str, ...]
    head_inputs: Tuple[str, ...] = ()
    token_keys: Tuple[str, ...] = ("input_ids",)
    row_keys: Tuple[str, ...] = ()
    tied: Dict[str, str] = dataclasses.field(default_factory=dict)
    config: Any = None


def runs_of_one_kind(kinds: Sequence[Hashable]) -> List[Tuple[Any, int]]:
    """A layer pattern as the groups a trainer can scan: the layers' kinds
    in order -> [(kind, layers)], the maximal runs of one kind.  Nothing
    here knows a period, so a pattern that ends mid-period needs no special
    case."""
    return [(kind, len(list(run))) for kind, run in itertools.groupby(kinds)]


def run_groups(kinds: Sequence[Hashable], label: Callable[[Any], str],
               make_block: Callable[[Any], Any]) -> Dict[str, Any]:
    """`PretrainModel.groups` of a layer pattern: one group a run of
    `runs_of_one_kind`, named `run<index>_<label(kind)>` (its place, so the
    names sort in the model's order) and holding `make_block(kind)` once a
    layer."""
    groups = {}
    for index, (kind, n) in enumerate(runs_of_one_kind(kinds)):
        holder = nn.Layer()
        holder.layers = nn.LayerList([make_block(kind) for _ in range(n)])
        groups[f"run{index:02d}_{label(kind)}"] = holder
    return groups


def ernie_pretrain_model(cfg: ErnieConfig,
                         moe_experts: int = 0) -> PretrainModel:
    """ERNIE/BERT pretraining (MLM + NSP) as the trainer's pieces.  The
    encoder stack must be uniform, so embeddings/pooler/heads live outside
    it; with `moe_experts` every block's FFN is an `nn.MoEFFN`."""
    embeddings = ErnieEmbeddings(cfg)
    if moe_experts:
        block = _MoEBlock(cfg, moe_experts)
    else:
        block = nn.TransformerEncoderLayer(
            cfg.hidden_size, cfg.num_attention_heads,
            cfg.intermediate_size, dropout=cfg.hidden_dropout_prob,
            activation=cfg.hidden_act,
            attn_dropout=cfg.attention_probs_dropout_prob, act_dropout=0.0)
    # fresh per-block init via the cloning LayerList (clones re-draw from
    # each parameter's recorded initializer)
    stack = nn.TransformerEncoder(block, cfg.num_hidden_layers) \
        if not moe_experts else _CloneList(block, cfg.num_hidden_layers)
    criterion = ErniePretrainingCriterion(cfg.vocab_size)

    def loss(outputs, batch):
        logits, nsp = outputs
        return criterion(logits.astype(jnp.float32), nsp.astype(jnp.float32),
                         batch["mlm_labels"], batch["nsp_labels"])

    return PretrainModel(
        embeddings=embeddings, groups={"blocks": stack},
        head=_PretrainHead(cfg, embeddings.word_embeddings.weight),
        criterion=loss, embed_inputs=("input_ids", "token_type_ids"),
        head_inputs=("masked_positions",),
        token_keys=("input_ids", "token_type_ids"),
        # (b, n_mask) labels/indices and (b,) nsp labels: batch-sharded
        # only.  n_mask is not a sequence dim (sp rarely divides it), and
        # the masked-position indices address the full sequence, so none
        # get seq-axis sharding.
        row_keys=("mlm_labels", "nsp_labels", "masked_positions"),
        # the MLM decoder weight is TIED to the embedding table: one pytree
        # leaf (under "embed"), so its gradient accumulates from both uses
        # and donation never sees the same buffer twice.
        tied={"cls.predictions.decoder_weight": "word_embeddings.weight"},
        config=cfg)


class HybridPretrainer:
    """Assembles params + shardings + a pure train step for a pretraining
    model on the current hybrid mesh.

    `config` is either the model's description (a `PretrainModel`) or an
    `ErnieConfig` (default: ERNIE-1.0 base), which `ernie_pretrain_model`
    turns into one (`moe_experts` is that description's option).

    Parameters are {"embed": ..., <group>: stacked [L, ...] blocks, ...,
    "head": ...}.  Pipeline note: a group of blocks must be uniform, so
    embeddings and head live outside the pipeline (replicated over pp) and
    the blocks' leading dim is sharded over pp; pp > 1 takes a model with
    one group.
    """

    def __init__(self, config=None, *,
                 mesh=None, num_micro: int = 1, moe_experts: int = 0,
                 rules=TRANSFORMER_RULES, recompute: bool = False,
                 recompute_policy: Optional[str] = None, strategy=None):
        if isinstance(config, PretrainModel):
            if moe_experts:
                raise ValueError("moe_experts belongs to the ERNIE "
                                 "description, not to a PretrainModel")
            self.model = config
        else:
            self.model = ernie_pretrain_model(config or ErnieConfig(),
                                              moe_experts)
        if {"embed", "head"} & set(self.model.groups):
            raise ValueError("a group of blocks may not be named 'embed' "
                             "or 'head'")
        self.cfg = self.model.config
        self.mesh = mesh or _mesh.current_mesh()
        self.num_micro = num_micro
        self.rules = rules
        self.moe_experts = moe_experts
        # fleet wiring: DistributedStrategy.recompute(_configs) drives
        # per-block jax.checkpoint (ref RecomputeOptimizer optimizer.py:4513)
        if strategy is not None and getattr(strategy, "recompute", False):
            recompute = True
            recompute_policy = strategy.recompute_configs.policy
        self.recompute = recompute or getattr(self.cfg, "enable_recompute", False)
        self.recompute_policy = recompute_policy
        # fleet wiring: PipelineConfig.schedule selects the pp schedule
        # (ref device_worker.h:415 SectionWorker's 1F1B vs GPipe).
        self.pp_schedule = "gpipe"
        if strategy is not None and getattr(strategy, "pipeline", False):
            sched = strategy.pipeline_configs.schedule
            if sched not in ("gpipe", "1f1b"):
                raise ValueError(
                    f"unknown pipeline schedule {sched!r}: use 'gpipe' or "
                    "'1f1b'")
            self.pp_schedule = sched
            if num_micro == 1:
                num_micro = strategy.pipeline_configs.micro_batch
                self.num_micro = num_micro
        # fleet wiring: sequence_parallel asserts the mesh carries an sp
        # axis (activations are then sp-sharded by _data_constraint); a
        # silent True with no sp axis would be the no-op antipattern.
        if strategy is not None and getattr(strategy, "sequence_parallel",
                                            False):
            if _mesh.SP_AXIS not in self.mesh.axis_names or \
                    _mesh.mesh_axis_size(_mesh.SP_AXIS, self.mesh) <= 1:
                raise ValueError(
                    "DistributedStrategy.sequence_parallel=True but the "
                    "mesh has no sp axis (>1); build the mesh with "
                    "sp_degree > 1 (hybrid_configs)")
        # fleet wiring: sharding (ZeRO-1) shards fp32 optimizer state over
        # dp via with_sharding_constraint on the updated state
        # (parallel/sharding.py zero_spec; ref proto sharding_configs).
        self.zero_sharding = bool(strategy is not None
                                  and getattr(strategy, "sharding", False))
        if len(self.model.groups) > 1 and \
                _mesh.mesh_axis_size(_mesh.PP_AXIS, self.mesh) > 1:
            raise ValueError("pp > 1 pipelines one group of uniform blocks; "
                             f"this model has {list(self.model.groups)}")
        self.embeddings = self.model.embeddings
        self.head = self.model.head
        self.block_templates = {name: stack.layers[0]
                                for name, stack in self.model.groups.items()}

    # -- parameters ---------------------------------------------------------
    def init_params(self) -> Dict[str, Any]:
        tied = set(self.model.tied)
        out = {"embed": parameters_dict(self.embeddings)}
        for name, stack in self.model.groups.items():
            out[name] = stack_block_params(
                [parameters_dict(l) for l in stack.layers])
        out["head"] = {k: v for k, v in parameters_dict(self.head).items()
                       if k not in tied}
        return out

    def param_shardings(self, params) -> Dict[str, Any]:
        m = self.mesh
        out = {
            "embed": infer_sharding(params["embed"], m, self.rules),
            "head": infer_sharding(params["head"], m, self.rules),
        }
        for group, template in self.block_templates.items():
            blk = {}
            for name, v in params[group].items():
                ann = None
                p = _find_param(template, name)
                if p is not None and \
                        getattr(p, "sharding_axes", None) is not None:
                    ann = tuple(p.sharding_axes)
                if ann is None:
                    match = self.rules.match(name, v.ndim - 1)
                    ann = match if match is not None \
                        else (None,) * (v.ndim - 1)
                spec = (_mesh.PP_AXIS,) + tuple(ann)
                blk[name] = NamedSharding(m, _clean(spec, m, v.shape))
            out[group] = blk
        return out

    def place_params(self, params):
        sh = self.param_shardings(params)
        return jax.tree_util.tree_map(
            lambda v, s: jax.device_put(v, s), params, sh,
            is_leaf=lambda x: not isinstance(x, dict))

    # -- forward ------------------------------------------------------------
    def _block_fn(self, group: str):
        """Single-block apply of one group (+ optional recompute wrap)
        shared by the GPipe and 1F1B paths."""
        template = self.block_templates[group]

        def block_fn(blk, x):
            return functional_call(template, blk, (x,))

        if self.recompute:
            from ..autograd import checkpoint_policy

            block_fn = jax.checkpoint(
                block_fn, policy=checkpoint_policy(self.recompute_policy))
        return block_fn

    def _encode(self, params, h):
        """Run the groups of blocks in order, each a scan over its stacked
        parameters; pipelined over pp when the axis exists (one group)."""
        pp = _mesh.mesh_axis_size(_mesh.PP_AXIS, self.mesh)
        if pp == 1:
            for group in self.model.groups:
                h = blockwise_stage_fn(self._block_fn(group))(params[group], h)
            return h

        (group,) = self.model.groups
        blocks, block_fn = params[group], self._block_fn(group)
        xs = microbatch(h, self.num_micro)

        def run(blk, xs_):
            return pipeline_apply(blockwise_stage_fn(block_fn), blk, xs_,
                                  axis=_mesh.PP_AXIS)

        blk_specs = jax.tree_util.tree_map(
            lambda _: PartitionSpec(_mesh.PP_AXIS), blocks)
        f = jax.shard_map(
            run, mesh=self.mesh, in_specs=(blk_specs, PartitionSpec()),
            out_specs=PartitionSpec(),
            axis_names={_mesh.PP_AXIS}, check_vma=False)
        return unmicrobatch(f(blocks, xs))

    def _head_params(self, params):
        head_params = dict(params["head"])
        for head_name, embed_name in self.model.tied.items():
            head_params[head_name] = params["embed"][embed_name]
        return head_params

    def loss_fn(self, params, batch, key):
        model = self.model
        # kernel dispatch shards over THIS trainer's mesh (mesh_scope)
        with _mesh.mesh_scope(self.mesh), _random.rng_scope(key):
            with jax.named_scope(_xprof.REGION_EMBED):
                h = functional_call(
                    self.embeddings, params["embed"],
                    tuple(batch[k] for k in model.embed_inputs))
                h = self._data_constraint(h)
            with jax.named_scope(_xprof.REGION_ENCODER):
                h = self._encode(params, h)
            with jax.named_scope(_xprof.REGION_HEAD):
                outputs = functional_call(
                    self.head, self._head_params(params),
                    (h, *(batch.get(k) for k in model.head_inputs)))
        with jax.named_scope(_xprof.REGION_LOSS):
            loss = model.criterion(outputs, batch)
        # MoE load-balancing aux loss is not added here: the blocks run under
        # lax.scan (and the pp shard_map), so the per-block aux values are
        # trace-local.  Custom loops wanting it should call
        # MoEFFN.forward_with_aux and thread the aux through the scan carry.
        return loss

    def _data_constraint(self, h):
        m = self.mesh
        spec = [None, None, None]
        if _mesh.DP_AXIS in m.axis_names:
            spec[0] = _mesh.DP_AXIS
        if _mesh.SP_AXIS in m.axis_names:
            spec[1] = _mesh.SP_AXIS
        return lax.with_sharding_constraint(h, NamedSharding(m, PartitionSpec(*spec)))

    # -- train step ---------------------------------------------------------
    def make_train_step(self, optimizer, compute_dtype=jnp.float32):
        pp = _mesh.mesh_axis_size(_mesh.PP_AXIS, self.mesh)
        if self.pp_schedule == "1f1b" and pp > 1:
            return self._make_train_step_1f1b(optimizer, compute_dtype)

        def train_step(params, opt_state, batch, key):
            def _loss(p):
                return self.loss_fn(_cast_floating(p, compute_dtype), batch,
                                    key)

            loss, grads = jax.value_and_grad(_loss)(params)
            new_params, new_state = self._update(optimizer, grads, opt_state,
                                                 params)
            return new_params, new_state, loss

        return train_step

    @jax.named_scope(_xprof.REGION_OPTIMIZER)
    def _update(self, optimizer, grads, opt_state, params):
        """The update over every leaf (gradients cast to the parameters'
        dtype first), then the ZeRO constraint: both schedules' tail."""
        grads = jax.tree_util.tree_map(
            lambda g, q: g.astype(q.dtype), grads, params,
            is_leaf=lambda x: not isinstance(x, dict))
        new_params, new_state = optimizer.update(grads, opt_state, params)
        return new_params, self._zero_constrain(new_state)

    def _zero_constrain(self, opt_state):
        """ZeRO-1 (fleet sharding strategy): constrain fp32 optimizer-state
        leaves to be sharded over dp — GSPMD then stores each moment
        1/dp-sized per device instead of replicated."""
        if not self.zero_sharding or \
                _mesh.mesh_axis_size(_mesh.DP_AXIS, self.mesh) <= 1:
            return opt_state
        from ..parallel.sharding import zero_spec

        def constrain(s):
            if not hasattr(s, "shape") or not s.shape:
                return s
            spec = zero_spec(s.shape, self.mesh, _mesh.DP_AXIS)
            return lax.with_sharding_constraint(
                s, NamedSharding(self.mesh, spec))

        return jax.tree_util.tree_map(constrain, opt_state)

    def _make_train_step_1f1b(self, optimizer, compute_dtype):
        """1F1B pipeline schedule (ref SectionWorker device_worker.h:415):
        the loss runs per micro-batch on the last stage inside the pipeline
        and each micro-batch's backward retires as soon as its cotangent
        arrives — peak activation memory O(pp) instead of GPipe's
        O(num_micro).  Uses manual VJP (parallel.pipeline.pipeline_train_1f1b)
        with stage-input stashing + forward recompute.

        RNG contract: the stage forward and its VJP replay must draw the
        SAME dropout masks, so the per-micro-batch key is derived from the
        micro index and threaded explicitly (the ambient traced-counter
        stream would desynchronize between the fwd slot and the bwd-slot
        replay)."""
        from ..parallel.pipeline import pipeline_train_1f1b

        def train_step(params, opt_state, batch, key):
            p = _cast_floating(params, compute_dtype)

            model = self.model
            (group,) = model.groups
            block_fn = self._block_fn(group)

            @jax.named_scope(_xprof.REGION_ENCODER)
            def stage_fn(blk, x, micro_idx):
                with _random.rng_scope(
                        jax.random.fold_in(key, 2 * micro_idx + 2)):
                    def body(h, one_blk):
                        return block_fn(one_blk, h), None

                    out, _ = lax.scan(body, x, blk)
                return out

            def loss_fn(hp, y, tgt, micro_idx):
                # odd salts for the head (even+2 are the stages'): per-micro
                # head randomness advances like the GPipe stream would
                with _random.rng_scope(
                        jax.random.fold_in(key, 2 * micro_idx + 3)), \
                        jax.named_scope(_xprof.REGION_HEAD):
                    outputs = functional_call(
                        self.head, hp,
                        (y, *(tgt.get(k) for k in model.head_inputs)))
                with jax.named_scope(_xprof.REGION_LOSS):
                    return model.criterion(outputs, tgt)

            @jax.named_scope(_xprof.REGION_EMBED)
            def embed_fn(ep):
                with _random.rng_scope(jax.random.fold_in(key, 0)):
                    h = functional_call(
                        self.embeddings, ep,
                        tuple(batch[k] for k in model.embed_inputs))
                return self._data_constraint(h)

            head_params = self._head_params(p)

            h, vjp_embed = jax.vjp(embed_fn, p["embed"])
            xs = microbatch(h, self.num_micro)
            targets = {k: microbatch(batch[k], self.num_micro)
                       for k in model.token_keys + model.row_keys
                       if k in batch and k not in model.embed_inputs}

            blk_specs = jax.tree_util.tree_map(
                lambda _: PartitionSpec(_mesh.PP_AXIS), p[group])

            def run(blk, hp, xs_, ts_):
                return pipeline_train_1f1b(
                    stage_fn, loss_fn, blk, hp, xs_, ts_,
                    axis=_mesh.PP_AXIS)

            f = jax.shard_map(
                run, mesh=self.mesh,
                in_specs=(blk_specs, PartitionSpec(), PartitionSpec(),
                          PartitionSpec()),
                out_specs=(PartitionSpec(), blk_specs, PartitionSpec(),
                           PartitionSpec()),
                axis_names={_mesh.PP_AXIS}, check_vma=False)
            loss, sgrads, hgrads, dxs = f(p[group], head_params, xs,
                                          targets)
            (egrads,) = vjp_embed(unmicrobatch(dxs))

            hgrads, egrads = dict(hgrads), dict(egrads)
            for head_name, embed_name in model.tied.items():
                egrads[embed_name] = egrads[embed_name] + \
                    hgrads.pop(head_name)
            grads = {"embed": egrads, group: dict(sgrads),
                     "head": hgrads}
            new_params, new_state = self._update(optimizer, grads, opt_state,
                                                 params)
            return new_params, new_state, loss

        @functools.wraps(train_step)
        def scoped(*args):
            # kernel dispatch shards over THIS trainer's mesh (mesh_scope)
            with _mesh.mesh_scope(self.mesh):
                return train_step(*args)

        return scoped

    def data_shardings(self, mesh=None):
        """{batch key: sharding} for the keys the model's step reads."""
        m = mesh or self.mesh
        tok = _mesh.data_sharding(m, seq_axis=_mesh.SP_AXIS)
        dp_only = NamedSharding(m, PartitionSpec(
            _mesh.DP_AXIS if _mesh.DP_AXIS in m.axis_names else None))
        return {**{k: tok for k in self.model.token_keys},
                **{k: dp_only for k in self.model.row_keys}}


class _PretrainHead(nn.Layer):
    """Pooler + MLM/NSP heads (pipeline keeps them off the block stack)."""

    def __init__(self, cfg: ErnieConfig, embedding_weight):
        super().__init__()
        from .ernie import ErniePooler, ErniePretrainingHeads
        self.pooler = ErniePooler(cfg.hidden_size)
        self.cls = ErniePretrainingHeads(cfg, embedding_weight)

    def forward(self, hidden, masked_positions=None):
        pooled = self.pooler(hidden)
        return self.cls(hidden, pooled, masked_positions)


class _CloneList(nn.Layer):
    """num_layers fresh clones of a block (TransformerEncoder's cloning,
    reused for arbitrary block types)."""

    def __init__(self, block, num_layers):
        super().__init__()
        import copy
        from ..nn.layer.transformer import _reinit
        clones = []
        for _ in range(num_layers):
            c = copy.deepcopy(block)
            _reinit(c)
            clones.append(c)
        self.layers = nn.LayerList(clones)


@jax.named_scope(_xprof.REGION_OPTIMIZER)
def _cast_floating(params, compute_dtype):
    """The weights in the compute dtype (differentiated through, the
    gradients' cast back lands in the same region)."""
    if compute_dtype == jnp.float32:
        return params
    return jax.tree_util.tree_map(
        lambda x: x.astype(compute_dtype)
        if jnp.issubdtype(x.dtype, jnp.floating) else x, params)


# ---------------------------------------------------------------------------
# routing counters
# ---------------------------------------------------------------------------
_ROUTING_STATS = ("pairs_routed", "pairs_held", "held_load_max_over_mean",
                  "pairs_dropped", "buffer_rows")
_routing_gauges = {name: monitor.gauge(
    f"moe.{name}", "DroplessMoE routing of the last `routing_stats` call, "
    "per expert layer", labelnames=("layer",)) for name in _ROUTING_STATS}


def routing_stats(trainer: HybridPretrainer, params, batch,
                  compute_dtype=jnp.bfloat16):
    """One forward pass of `trainer`'s model over `batch` that walks its
    groups in order and reads the routing of every block that has an expert
    layer (`nn.DroplessMoE`; such a block's forward takes
    `routing_stats=True` and returns (y, the layer's `routing_stats`)):
    {name: [expert layers] array}, and the gauges `moe.pairs_routed`,
    `moe.pairs_held`, `moe.held_load_max_over_mean`, `moe.pairs_dropped`,
    `moe.buffer_rows` (label `layer`: the expert layer's place among the
    expert layers).  Not part of a train step: run it on a trained state
    when the counts are wanted."""
    model = trainer.model
    routed = {group for group, template in trainer.block_templates.items()
              if any(isinstance(l, nn.DroplessMoE)
                     for l in template.sublayers())}
    if not routed:
        return {}

    @jax.jit
    def stats(params, batch):
        p = _cast_floating(params, compute_dtype)
        h = functional_call(model.embeddings, p["embed"],
                            tuple(batch[k] for k in model.embed_inputs))
        out = []
        for group in model.groups:
            if group not in routed:
                h = blockwise_stage_fn(trainer._block_fn(group))(p[group], h)
                continue
            template = trainer.block_templates[group]
            h, read = lax.scan(
                lambda x, blk: functional_call(
                    template, blk, (x,), {"routing_stats": True}),
                h, p[group])
            out.append(read)
        return {name: jnp.concatenate([read[name] for read in out])
                for name in _ROUTING_STATS}

    out = jax.device_get(stats(params, batch))
    for name in _ROUTING_STATS:
        gauge = _routing_gauges[name]
        for labels, _ in gauge.samples():   # a deeper model's last call
            gauge.remove(**labels)
        for layer, value in enumerate(out[name]):
            gauge.set(float(value), layer=str(layer))
    return out


def _find_param(layer, name: str):
    for n, p in layer.named_parameters():
        if n == name:
            return p
    return None


def _clean(spec, mesh, shape):
    out = []
    for i, a in enumerate(spec):
        if a is None or a not in mesh.axis_names:
            out.append(None)
        elif shape[i] % mesh.shape[a] != 0:
            out.append(None)
        else:
            out.append(a)
    while out and out[-1] is None:
        out.pop()
    return PartitionSpec(*out)
