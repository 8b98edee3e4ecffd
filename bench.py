"""Flagship benchmark: ERNIE-base MLM+NSP pretraining throughput (tok/s/chip).

BASELINE.json config 3 ("PaddleNLP ERNIE-1.0 / BERT-base pretrain") on the
available chip.  A side path kept for the benchmark PR (ROADMAP S0) to
absorb: it jits `__graft_entry__.make_train_step`, not fleet +
HybridPretrainer (`chip_smoke.py` drives that).  No chip, no number: it
exits non-zero off-TPU.

Prints exactly ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "device": {...}, ...}
"""
from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from __graft_entry__ import make_train_step
from paddle_tpu.autograd import parameters_dict
from paddle_tpu.core.jax_cache import configure_compile_cache
from paddle_tpu.optimizer import Adam
from paddle_tpu.text.ernie import (
    ErnieConfig,
    ErnieForPretraining,
    ErniePretrainingCriterion,
)
from paddle_tpu.utils import xprof


def main():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"bench.py needs a TPU; JAX found platform {dev.platform!r}",
              file=sys.stderr)
        return 1
    configure_compile_cache()
    recompute = os.environ.get("BENCH_RECOMPUTE", "0") == "1"
    cfg = ErnieConfig(enable_recompute=recompute)  # L12 H768 A12 V18000
    batch, seq = int(os.environ.get("BENCH_BATCH", "64")), 512
    warmup, iters = 3, int(os.environ.get("BENCH_ITERS", "40"))

    model = ErnieForPretraining(cfg)
    model.train()
    criterion = ErniePretrainingCriterion(cfg.vocab_size)
    opt = Adam(learning_rate=1e-4)

    params = parameters_dict(model)
    opt_state = opt.init(params)
    step = jax.jit(make_train_step(model, criterion, opt, jnp.bfloat16),
                   donate_argnums=(0, 1))

    rng = np.random.default_rng(0)
    # ERNIE pretraining contract (ref PaddleNLP ernie pretraining reader):
    # feed mask_pos so only masked tokens hit the vocab projection.
    n_mask = max(1, int(seq * 0.15))
    mask_pos = np.stack([rng.choice(seq, n_mask, replace=False)
                         for _ in range(batch)]).astype(np.int32)
    batch_data = {
        "input_ids": jnp.asarray(
            rng.integers(1, cfg.vocab_size, (batch, seq)), jnp.int32),
        "token_type_ids": jnp.zeros((batch, seq), jnp.int32),
        "masked_positions": jnp.asarray(mask_pos),
        "mlm_labels": jnp.asarray(
            rng.integers(0, cfg.vocab_size, (batch, n_mask)), jnp.int32),
        "nsp_labels": jnp.asarray(rng.integers(0, 2, (batch,)), jnp.int32),
    }
    # rbg (hardware) PRNG for dropout: threefry mask generation alone costs
    # ~45ms/step at this shape (measured r03); the typed key carries its
    # impl into every fold_in/bernoulli downstream.
    key = jax.random.key(0, impl="rbg")

    for _ in range(warmup):
        params, opt_state, loss = step(params, opt_state, batch_data, key)
    jax.block_until_ready(loss)

    # steps chain through the donated params; one wait ends the window
    t0 = time.perf_counter()
    for _ in range(iters):
        params, opt_state, loss = step(params, opt_state, batch_data, key)
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0

    # the un-sharded step runs on ONE chip however many are visible
    toks_per_sec = batch * seq * iters / dt

    # Analytic model FLOPs per token (training = 3x forward matmul FLOPs):
    # per layer QKV+out projections 8H^2, FFN 4HI, attention scores+values
    # 4sH; MLM head only touches the masked fraction of tokens; pooler+NSP
    # amortize per sequence.  (6*n_params would overcount the embedding
    # gather and the unmasked tokens' vocab projection.)
    H, I, L, V = (cfg.hidden_size, cfg.intermediate_size,
                  cfg.num_hidden_layers, cfg.vocab_size)
    mask_frac = n_mask / seq
    fwd_per_tok = (L * (8 * H * H + 4 * H * I + 4 * seq * H)
                   + mask_frac * (2 * H * H + 2 * H * V)
                   + (2 * H * H + 4 * H) / seq)
    flops_per_tok = 3 * fwd_per_tok
    peaks = xprof.resolve_peaks()  # keyed by device_kind; unknown raises
    mfu = toks_per_sec * flops_per_tok / peaks.flops_per_sec

    print(json.dumps({
        "metric": "ernie_base_pretrain_throughput",
        "value": round(toks_per_sec, 2),
        "unit": "tokens/sec/chip",
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "batch": batch, "seq_len": seq, "iters": iters,
        "loss": round(float(loss), 4),
        "mfu_est": round(mfu, 4),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
