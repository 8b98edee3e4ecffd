"""Entry point: fleet + HybridPretrainer, the product's own training path.

    Fleet().init(strategy, devices) -> HybridPretrainer(cfg, mesh=fleet.mesh,
    strategy=strategy) -> fleet.distributed_optimizer(Adam(lr)) ->
    jax.jit(trainer.make_train_step(opt, compute_dtype), donate_argnums=(0, 1))

exactly as `chip_smoke.build_training` does it (copied from there: the
program may change, the benchmark's copy of the calling sequence may not).
This file is the only place of the benchmark that imports the program.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp


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
        from paddle_tpu.utils import monitor
        out = {}
        for name in ("pallas.kernel_calls", "pallas.fallbacks"):
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
    from paddle_tpu.text.ernie import ErnieConfig
    from paddle_tpu.text.pretrainer import HybridPretrainer

    m, o = config["model"], config["train"]["optimizer"]
    cfg = ErnieConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        num_hidden_layers=m["num_hidden_layers"],
        num_attention_heads=m["num_attention_heads"],
        intermediate_size=m["intermediate_size"], hidden_act=m["hidden_act"],
        hidden_dropout_prob=m["hidden_dropout_prob"],
        attention_probs_dropout_prob=m["attention_probs_dropout_prob"],
        max_position_embeddings=m["max_position_embeddings"],
        type_vocab_size=m["type_vocab_size"],
        initializer_range=m["initializer_range"])
    mesh = mix["mesh"]
    if set(mesh) != {"dp"}:
        raise ValueError(f"this entry builds dp meshes only, not {mesh}")
    strategy = DistributedStrategy()
    strategy.hybrid_configs.dp_degree = mesh["dp"]
    fleet = Fleet().init(strategy=strategy, devices=list(devices)[:mesh["dp"]])
    paddle_tpu.seed(0)
    trainer = HybridPretrainer(cfg, mesh=fleet.mesh, strategy=strategy)
    if o["name"] != "adam":
        raise ValueError(f"optimizer {o['name']!r}")
    opt = fleet.distributed_optimizer(Adam(
        learning_rate=o["lr"], beta1=o["beta1"], beta2=o["beta2"],
        epsilon=o["epsilon"]))
    step_fn = trainer.make_train_step(
        opt, compute_dtype=jnp.dtype(config["train"]["compute_dtype"]))

    def first_gradient(opt_state):
        # Adam's first moment after one step is (1 - beta1) * gradient
        return [mv[0] / (1.0 - o["beta1"])
                for mv in opt_state["inner"]["per_param"]]

    return Training(
        trainer=trainer, step_fn=step_fn,
        step=jax.jit(step_fn, donate_argnums=(0, 1)),
        data_shardings=trainer.data_shardings(),
        # rbg (hardware) PRNG for the framework's dropout key stream
        key=jax.random.key(0, impl="rbg"),
        first_gradient=jax.jit(first_gradient),
        init_opt_state=jax.jit(opt.init),
        step_module="jit_train_step")
