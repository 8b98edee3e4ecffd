"""Hybrid-parallel ERNIE/BERT pretraining trainer.

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
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

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


class HybridPretrainer:
    """Assembles params + shardings + a pure train step for ERNIE pretraining
    on the current hybrid mesh.

    Pipeline note: the encoder stack must be uniform, so embeddings/pooler/
    heads live outside the pipeline (replicated over pp) and the blocks'
    parameters are stacked [L, ...] with the leading dim sharded over pp.
    """

    def __init__(self, config: Optional[ErnieConfig] = None, *,
                 mesh=None, num_micro: int = 1, moe_experts: int = 0,
                 rules=TRANSFORMER_RULES, recompute: bool = False,
                 recompute_policy: Optional[str] = None, strategy=None):
        self.cfg = config or ErnieConfig()
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
        cfg = self.cfg

        self.embeddings = ErnieEmbeddings(cfg)
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
        self._stack = nn.TransformerEncoder(block, cfg.num_hidden_layers) \
            if not moe_experts else _CloneList(block, cfg.num_hidden_layers)
        self.block_template = self._stack.layers[0]
        self.head = _PretrainHead(cfg, self.embeddings.word_embeddings.weight)
        self.criterion = ErniePretrainingCriterion(cfg.vocab_size)

    # -- parameters ---------------------------------------------------------
    _TIED = "cls.predictions.decoder_weight"
    _EMB = "word_embeddings.weight"

    def init_params(self) -> Dict[str, Any]:
        blocks = [parameters_dict(l) for l in self._stack.layers]
        # the MLM decoder weight is TIED to the embedding table: keep one
        # pytree leaf (under "embed") and bind it into the head at call time,
        # so its gradient accumulates from both uses and donation never sees
        # the same buffer twice.
        head = {k: v for k, v in parameters_dict(self.head).items()
                if k != self._TIED}
        return {
            "embed": parameters_dict(self.embeddings),
            "blocks": stack_block_params(blocks),
            "head": head,
        }

    def param_shardings(self, params) -> Dict[str, Any]:
        m = self.mesh
        out = {
            "embed": infer_sharding(params["embed"], m, self.rules),
            "head": infer_sharding(params["head"], m, self.rules),
        }
        blk = {}
        for name, v in params["blocks"].items():
            ann = None
            p = _find_param(self.block_template, name)
            if p is not None and getattr(p, "sharding_axes", None) is not None:
                ann = tuple(p.sharding_axes)
            if ann is None:
                match = self.rules.match(name, v.ndim - 1)
                ann = match if match is not None else (None,) * (v.ndim - 1)
            spec = (_mesh.PP_AXIS,) + tuple(ann)
            blk[name] = NamedSharding(m, _clean(spec, m, v.shape))
        out["blocks"] = blk
        return out

    def place_params(self, params):
        sh = self.param_shardings(params)
        return jax.tree_util.tree_map(
            lambda v, s: jax.device_put(v, s), params, sh,
            is_leaf=lambda x: not isinstance(x, dict))

    # -- forward ------------------------------------------------------------
    def _block_fn(self):
        """Single-block apply (+ optional recompute wrap) shared by the
        GPipe and 1F1B paths."""
        template = self.block_template

        def block_fn(blk, x):
            return functional_call(template, blk, (x,))

        if self.recompute:
            from ..autograd import checkpoint_policy

            block_fn = jax.checkpoint(
                block_fn, policy=checkpoint_policy(self.recompute_policy))
        return block_fn

    def _encode(self, blocks, h):
        """Run the encoder stack: pipelined over pp when the axis exists."""
        pp = _mesh.mesh_axis_size(_mesh.PP_AXIS, self.mesh)
        block_fn = self._block_fn()

        if pp == 1:
            stage = blockwise_stage_fn(block_fn)
            return stage(blocks, h)

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

    def loss_fn(self, params, batch, key):
        cfg = self.cfg
        # kernel dispatch shards over THIS trainer's mesh (mesh_scope)
        with _mesh.mesh_scope(self.mesh), _random.rng_scope(key):
            with jax.named_scope(_xprof.REGION_EMBED):
                h = functional_call(
                    self.embeddings, params["embed"],
                    (batch["input_ids"], batch["token_type_ids"]))
                h = self._data_constraint(h)
            with jax.named_scope(_xprof.REGION_ENCODER):
                h = self._encode(params["blocks"], h)
            head_params = dict(params["head"])
            head_params[self._TIED] = params["embed"][self._EMB]
            with jax.named_scope(_xprof.REGION_HEAD):
                logits, nsp = functional_call(
                    self.head, head_params,
                    (h, batch.get("masked_positions")))
        with jax.named_scope(_xprof.REGION_LOSS):
            loss = self.criterion(logits.astype(jnp.float32),
                                  nsp.astype(jnp.float32),
                                  batch["mlm_labels"], batch["nsp_labels"])
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

            block_fn = self._block_fn()

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
                    logits, nsp = functional_call(
                        self.head, hp, (y, tgt.get("masked_positions")))
                with jax.named_scope(_xprof.REGION_LOSS):
                    return self.criterion(
                        logits.astype(jnp.float32), nsp.astype(jnp.float32),
                        tgt["mlm_labels"], tgt["nsp_labels"])

            @jax.named_scope(_xprof.REGION_EMBED)
            def embed_fn(ep):
                with _random.rng_scope(jax.random.fold_in(key, 0)):
                    h = functional_call(
                        self.embeddings, ep,
                        (batch["input_ids"], batch["token_type_ids"]))
                return self._data_constraint(h)

            head_params = dict(p["head"])
            head_params[self._TIED] = p["embed"][self._EMB]

            h, vjp_embed = jax.vjp(embed_fn, p["embed"])
            xs = microbatch(h, self.num_micro)
            targets = {k: microbatch(batch[k], self.num_micro)
                       for k in ("masked_positions", "mlm_labels",
                                 "nsp_labels") if k in batch}

            blk_specs = jax.tree_util.tree_map(
                lambda _: PartitionSpec(_mesh.PP_AXIS), p["blocks"])

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
            loss, sgrads, hgrads, dxs = f(p["blocks"], head_params, xs,
                                          targets)
            (egrads,) = vjp_embed(unmicrobatch(dxs))

            hgrads = dict(hgrads)
            tied_g = hgrads.pop(self._TIED)
            egrads = dict(egrads)
            egrads[self._EMB] = egrads[self._EMB] + tied_g
            grads = {"embed": egrads, "blocks": dict(sgrads),
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
        m = mesh or self.mesh
        tok = _mesh.data_sharding(m, seq_axis=_mesh.SP_AXIS)
        dp_only = NamedSharding(m, PartitionSpec(
            _mesh.DP_AXIS if _mesh.DP_AXIS in m.axis_names else None))
        return {"input_ids": tok, "token_type_ids": tok,
                # (b, n_mask) labels/indices and (b,) nsp labels: batch-
                # sharded only.  n_mask is not a sequence dim (sp rarely
                # divides it), and the masked-position indices address the
                # full sequence, so none get seq-axis sharding.
                "mlm_labels": dp_only, "nsp_labels": dp_only,
                "masked_positions": dp_only}


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
