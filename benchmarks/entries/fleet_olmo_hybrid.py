"""Entry point: an Olmo-Hybrid-class causal LM through fleet +
HybridPretrainer, the calling sequence of `fleet_granite_hybrid.py` over
another family's description, with the strategy's per-block recomputation
on as the configuration's `train.recompute` says:

    strategy.recompute = True; strategy.recompute_configs.policy = <policy>
    Fleet().init(strategy, devices) -> HybridPretrainer(pretrain_model(cfg),
    mesh=fleet.mesh, strategy=strategy) -> fleet.distributed_optimizer(
    Adam(lr)) -> jax.jit(trainer.make_train_step(opt, compute_dtype),
    donate_argnums=(0, 1))

so the step the benchmark times is the one the trainer builds for any user
who sets the strategy.  This file is the only one of the configuration's
that imports the program.  The traffic generator's batches carry five keys;
the model reads `input_ids` and the others are placed and ignored.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

COUNTERS = ("pallas.kernel_calls", "pallas.fallbacks", "gdn.delta_calls")


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
    params: Any = None
    opt_state: Any = None

    def param_shardings(self, shapes):
        return self.trainer.param_shardings(shapes)

    def counters(self) -> dict:
        """The dispatch counters of the kernels and of the delta rule
        (counted where each is traced)."""
        from paddle_tpu.utils import monitor
        out = {}
        for name in COUNTERS:
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
    from paddle_tpu.text.olmo_hybrid import OlmoHybridConfig, pretrain_model
    from paddle_tpu.text.pretrainer import HybridPretrainer

    m, train = config["model"], config["train"]
    o = train["optimizer"]
    if m["head_dim"] * m["num_attention_heads"] != m["hidden_size"]:
        raise ValueError("the program builds head_dim = hidden / heads")
    cfg = OlmoHybridConfig(**{
        k: m[k] for k in (
            "vocab_size", "hidden_size", "intermediate_size",
            "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
            "layer_types", "linear_num_key_heads", "linear_num_value_heads",
            "linear_key_head_dim", "linear_value_head_dim",
            "linear_conv_kernel_dim", "linear_allow_neg_eigval",
            "linear_chunk_size", "attention_bias", "hidden_act", "rope_theta",
            "tie_word_embeddings", "rms_norm_eps", "initializer_range")})
    mesh = mix["mesh"]
    if set(mesh) != {"dp"}:
        raise ValueError(f"this entry builds dp meshes only, not {mesh}")
    strategy = DistributedStrategy()
    strategy.hybrid_configs.dp_degree = mesh["dp"]
    strategy.recompute = bool(train["recompute"]["enable"])
    strategy.recompute_configs.policy = train["recompute"]["policy"]
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

    return Training(
        trainer=trainer, step_fn=step_fn,
        step=jax.jit(step_fn, donate_argnums=(0, 1)),
        data_shardings=data_shardings,
        # the model has no dropout: the key is the step's fourth argument
        key=jax.random.key(0, impl="rbg"),
        first_gradient=jax.jit(first_gradient),
        init_opt_state=jax.jit(opt.init),
        step_module="jit_train_step")
