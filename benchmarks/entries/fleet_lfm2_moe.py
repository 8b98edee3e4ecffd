"""Entry point: an LFM2-MoE-class causal LM through fleet +
HybridPretrainer, the calling sequence of `fleet_causal_lm.py` over another
family's description:

    Fleet().init(strategy, devices) -> HybridPretrainer(pretrain_model(cfg),
    mesh=fleet.mesh, strategy=strategy) -> fleet.distributed_optimizer(
    Adam(lr)) -> jax.jit(trainer.make_train_step(opt, compute_dtype),
    donate_argnums=(0, 1))

This file is the only one of the configuration's that imports the program.
The traffic generator's batches carry five keys; the model reads
`input_ids` and the others are placed and ignored.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class Training:
    trainer: Any
    step_fn: Callable               # the pure step, as the product builds it
    step: Callable                  # jitted, params and state donated
    data_shardings: Dict[str, Any]
    key: jax.Array
    first_gradient: Callable        # optimizer state after one step -> grads
    init_opt_state: Callable
    step_module: str                # the step's program, as the trace names it
    stats_batch: Dict[str, Any]     # what `counters` routes once, afterwards
    compute_dtype: Any
    params: Any = None
    opt_state: Any = None

    def param_shardings(self, shapes):
        return self.trainer.param_shardings(shapes)

    def counters(self) -> dict:
        """The kernels' dispatch counters and, where the run left a trained
        state, the expert layers' routing of one batch on it
        (`text.pretrainer.routing_stats`: one forward pass, after the
        window)."""
        from paddle_tpu.text.pretrainer import routing_stats
        from paddle_tpu.utils import monitor
        if self.params is not None:
            routing_stats(self.trainer, self.params, self.stats_batch,
                          self.compute_dtype)
        out = {}
        for name in ("pallas.kernel_calls", "pallas.fallbacks",
                     "moe.pairs_routed", "moe.pairs_held",
                     "moe.held_load_max_over_mean", "moe.pairs_dropped"):
            c = monitor.default_registry().get(name)
            if c is not None:
                out[name] = {
                    ",".join(f"{k}={v}" for k, v in sorted(labels.items())): n
                    for labels, n in c.samples()}
        return out


def build(config: dict, mix: dict, devices) -> Training:
    import paddle_tpu
    from paddle_tpu.optimizer import Adam
    from paddle_tpu.parallel.fleet import DistributedStrategy, Fleet
    from paddle_tpu.text.lfm2_moe import Lfm2MoeConfig, pretrain_model
    from paddle_tpu.text.pretrainer import HybridPretrainer

    m, o = config["model"], config["train"]["optimizer"]
    if m["conv_bias"] or not m["use_expert_bias"] or \
            not m["tie_word_embeddings"] or \
            m["head_dim"] * m["num_attention_heads"] != m["hidden_size"]:
        raise ValueError("the program builds conv_bias false, "
                         "use_expert_bias true, a tied head and "
                         "head_dim = hidden / heads")
    cfg = Lfm2MoeConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        num_hidden_layers=m["num_hidden_layers"],
        num_attention_heads=m["num_attention_heads"],
        num_key_value_heads=m["num_key_value_heads"],
        intermediate_size=m["intermediate_size"],
        moe_intermediate_size=m["moe_intermediate_size"],
        num_experts=m["router_experts"],
        num_experts_per_tok=m["num_experts_per_tok"],
        num_dense_layers=m["num_dense_layers"],
        layer_types=m["layer_types"], conv_L_cache=m["conv_L_cache"],
        rope_theta=m["rope_theta"], norm_eps=m["norm_eps"],
        norm_topk_prob=m["norm_topk_prob"],
        routed_scaling_factor=m["routed_scaling_factor"],
        initializer_range=m["initializer_range"],
        held_experts=tuple(m["held_experts"]))
    mesh = mix["mesh"]
    if set(mesh) != {"dp"}:
        raise ValueError(f"this entry builds dp meshes only, not {mesh}")
    strategy = DistributedStrategy()
    strategy.hybrid_configs.dp_degree = mesh["dp"]
    fleet = Fleet().init(strategy=strategy, devices=list(devices)[:mesh["dp"]])
    paddle_tpu.seed(0)
    trainer = HybridPretrainer(pretrain_model(cfg), mesh=fleet.mesh,
                               strategy=strategy)
    if o["name"] != "adam":
        raise ValueError(f"optimizer {o['name']!r}")
    opt = fleet.distributed_optimizer(Adam(
        learning_rate=o["lr"], beta1=o["beta1"], beta2=o["beta2"],
        epsilon=o["epsilon"]))
    compute_dtype = jnp.dtype(config["train"]["compute_dtype"])
    step_fn = trainer.make_train_step(opt, compute_dtype=compute_dtype)

    def first_gradient(opt_state):
        # Adam's first moment after one step is (1 - beta1) * gradient
        return [mv[0] / (1.0 - o["beta1"])
                for mv in opt_state["inner"]["per_param"]]

    # the generator's five keys: the model's own get the trainer's
    # shardings, the rest ride along by rows
    mine = trainer.data_shardings()
    by_row = jax.sharding.NamedSharding(
        fleet.mesh, jax.sharding.PartitionSpec("dp"))
    data_shardings = {k: mine.get(k, by_row) for k in (
        "input_ids", "token_type_ids", "masked_positions", "mlm_labels",
        "nsp_labels")}
    rows = mix["batch_per_chip"] * mix["chips"]
    stats_ids = np.random.default_rng(0).integers(
        1, m["vocab_size"], (rows, mix["seq"])).astype(np.int32)

    return Training(
        trainer=trainer, step_fn=step_fn,
        step=jax.jit(step_fn, donate_argnums=(0, 1)),
        data_shardings=data_shardings,
        # the model has no dropout: the key is the step's fourth argument
        key=jax.random.key(0, impl="rbg"),
        first_gradient=jax.jit(first_gradient),
        init_opt_state=jax.jit(opt.init),
        step_module="jit_train_step",
        stats_batch={"input_ids": stats_ids}, compute_dtype=compute_dtype)
