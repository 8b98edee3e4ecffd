"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Runs ERNIE-base pretraining (`ErnieConfig()` defaults: 12 layers, hidden 768,
12 heads of 64, FFN 3072, vocab 18,000; batch 64 x seq 512 per chip,
masked-position MLM + NSP, dropout on, bf16 compute) for a few optimizer
steps through the product's own training entry points:

    fleet.init(strategy) -> HybridPretrainer(cfg, mesh=fleet.mesh, strategy=...)
      -> fleet.distributed_optimizer(Adam(1e-4))
      -> jax.jit(trainer.make_train_step(opt, compute_dtype=bfloat16),
                 donate_argnums=(0, 1))

* Leg A: one chip (a mesh over `jax.devices()[:1]`).
* Leg B: four chips, `hybrid_configs` dp=4 — runs when >= 4 chips are
  visible, and the output says whether it ran.

It checks, on every leg: finite loss on every step, last loss below the
first, no compilation after the first step (JAX's own compile events), and
that the Mosaic kernels it expects are IN the compiled program — so a
silent trip through `scaled_dot_product_attention` or the `jnp` LayerNorm
fails the smoke instead of passing slowly.  Leg B also checks dp parity
against the one-chip loss and, from the partitioned HLO, that every chip
runs the kernels on its own quarter of the batch.

There is no size switch, no CPU mode and no environment variable that
changes what it runs: without a TPU it exits non-zero before building
anything.  One process; it starts no child.  Each leg's status and the
run's counters go out on the `[leg ...]` / `[summary]` lines; the last line
of stdout is one JSON object with exactly these keys:
{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}.
"""
from __future__ import annotations

import dataclasses
import importlib.metadata
import json
import re
import sys
import time
from typing import Any, Callable, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

BATCH_PER_CHIP = 64
SEQ = 512
# The compile step + 11.  Adam(1e-4) with no warm-up on a fresh post-LN
# encoder spikes before it descends (10.66 -> 14.4 -> 10.4 over the first
# five steps on a v5e, PR 21); a dozen steps put the last loss clearly
# under the first.
STEPS = 12

# Mosaic kernels the compiled step must hold, by the `name=` each
# `pallas_call` carries into its custom call's op_name metadata.  Inside the
# scanned encoder block: packed flash attention (forward + both backward
# kernels) and the fused residual+dropout+LayerNorm epilogue (forward +
# backward).  Outside it (embeddings, MLM head): the fused LayerNorm.
SCANNED_KERNELS = ("flash_packed_fwd", "flash_packed_dkdv", "flash_packed_dq",
                   "rdln_fwd", "rdln_bwd")
HEAD_KERNELS = ("ln_fwd", "ln_bwd")


class SmokeFailure(AssertionError):
    """A check of the smoke did not hold (raised, never `assert`ed: the
    checks must survive `python -O`)."""


def require(cond, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


# ---------------------------------------------------------------------------
# the path (shared with tests/test_chip_smoke.py, which runs it tiny on CPU)
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Training:
    trainer: Any
    step: Callable          # the jitted train step
    params: Any
    opt_state: Any
    batch: Dict[str, jax.Array]
    key: jax.Array


def make_batch(cfg, global_batch: int, seq: int, seed: int = 0):
    """The ERNIE pretraining feed contract: 15% masked positions per row,
    MLM labels aligned with them, NSP labels.  Host arrays from a seed."""
    rng = np.random.default_rng(seed)
    n_mask = max(1, int(seq * 0.15))
    return {
        "input_ids": rng.integers(1, cfg.vocab_size,
                                  (global_batch, seq)).astype(np.int32),
        "token_type_ids": np.zeros((global_batch, seq), np.int32),
        "masked_positions": np.stack([
            rng.choice(seq, n_mask, replace=False)
            for _ in range(global_batch)]).astype(np.int32),
        "mlm_labels": rng.integers(0, cfg.vocab_size,
                                   (global_batch, n_mask)).astype(np.int32),
        "nsp_labels": rng.integers(0, 2, (global_batch,)).astype(np.int32),
    }


def build_training(cfg, devices: Sequence, dp: int, batch: Dict[str, Any],
                   init_params=None, compute_dtype=jnp.bfloat16) -> Training:
    """fleet.init -> HybridPretrainer -> distributed_optimizer -> jitted
    step, with params/optimizer state/batch placed on the fleet mesh.
    ``init_params`` (a host pytree) pins the initial weights; default: the
    trainer's own init from seed 0."""
    import paddle_tpu
    from paddle_tpu.optimizer import Adam
    from paddle_tpu.parallel.fleet import DistributedStrategy, Fleet
    from paddle_tpu.text.pretrainer import HybridPretrainer

    strategy = DistributedStrategy()
    strategy.hybrid_configs.dp_degree = dp
    fleet = Fleet().init(strategy=strategy, devices=list(devices)[:dp])
    paddle_tpu.seed(0)
    trainer = HybridPretrainer(cfg, mesh=fleet.mesh, strategy=strategy)
    opt = fleet.distributed_optimizer(Adam(learning_rate=1e-4))
    params = trainer.place_params(
        trainer.init_params() if init_params is None else init_params)
    opt_state = opt.init(params)  # zeros_like inherits each param's sharding
    shardings = trainer.data_shardings()
    placed = {k: jax.device_put(v, shardings[k]) for k, v in batch.items()}
    step = jax.jit(trainer.make_train_step(opt, compute_dtype=compute_dtype),
                   donate_argnums=(0, 1))
    # rbg (hardware) PRNG for the framework's dropout key stream
    key = jax.random.key(0, impl="rbg")
    return Training(trainer, step, params, opt_state, placed, key)


def eval_loss(t: Training) -> float:
    """The trainer's bf16 loss on its placed batch at its current params —
    the parity probe (build with a dropout-free config)."""
    def loss(params, batch, key):
        params = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, params)
        return t.trainer.loss_fn(params, batch, key)

    return float(jax.jit(loss)(t.params, t.batch, t.key))


# ---------------------------------------------------------------------------
# JAX's own compile events
# ---------------------------------------------------------------------------
class CompileLog:
    """Every XLA compile request this process makes, from jax.monitoring:
    (function name, served from the persistent cache?)."""

    def __init__(self):
        self.events: List[tuple] = []
        self._hit = False
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event: str, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self._hit = True

    def _on_duration(self, event: str, _secs: float, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((kw.get("fun_name", "?"), self._hit))
            self._hit = False

    def __len__(self):
        return len(self.events)


# ---------------------------------------------------------------------------
# reading the compiled program
# ---------------------------------------------------------------------------
_SHAPE_RE = re.compile(r"\b[a-z]+\d*\[([\d,]*)\]")


def hlo_computations(hlo: str) -> Dict[str, str]:
    """{computation name: body text} of an optimized-HLO module dump."""
    comps, name, lines = {}, None, []
    for line in hlo.splitlines():
        m = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if m:
            name, lines = m.group(1), []
        elif line.startswith("}"):
            if name is not None:
                comps[name] = "\n".join(lines)
            name = None
        elif name is not None:
            lines.append(line)
    return comps


def mosaic_calls(text: str) -> List[Dict[str, Any]]:
    """The `tpu_custom_call`s in a piece of HLO text: the pallas_call's
    name (from op_name metadata ".../<name>/pallas_call") and the operand
    shapes XLA hands the kernel (operand_layout_constraints)."""
    calls = []
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        head = line.split("backend_config=")[0]  # drop the serialized body
        name = re.search(r'op_name="[^"]*?/(\w+)/pallas_call', head)
        ops = re.search(r"operand_layout_constraints=\{(.*?)\}, \w+=", head)
        calls.append({
            "kernel": name.group(1) if name else "?",
            "operands": [tuple(int(d) for d in m.group(1).split(",") if d)
                         for m in _SHAPE_RE.finditer(ops.group(1))],
        })
    return calls


def scanned_bodies(hlo: str) -> List[str]:
    """Text of every while-loop body computation (the lax.scan bodies),
    each with the computations it calls, transitively."""
    comps = hlo_computations(hlo)

    def closure(name, seen):
        if name in seen or name not in comps:
            return ""
        seen.add(name)
        callees = re.findall(
            r"\b(?:calls|to_apply|body|condition)=%?([\w.\-]+)", comps[name])
        return "\n".join([comps[name]] + [closure(c, seen) for c in callees])

    return [closure(b, set())
            for b in set(re.findall(r"\bbody=%?([\w.\-]+)", hlo))]


def check_kernels_in_program(hlo: str) -> Dict[str, Any]:
    """Assert the expected Mosaic kernels are in the compiled step: the
    scanned encoder body holds flash fwd/dkdv/dq and the fused-epilogue
    fwd/bwd; the rest of the program holds the fused LayerNorm fwd/bwd."""
    in_scan = [c["kernel"] for body in scanned_bodies(hlo)
               for c in mosaic_calls(body)]
    everywhere = [c["kernel"] for c in mosaic_calls(hlo)]
    missing = [k for k in SCANNED_KERNELS if k not in in_scan]
    require(not missing,
            f"Mosaic kernels missing from the scanned encoder body: "
            f"{missing}; found in scan bodies: {sorted(set(in_scan))}, in "
            f"the program: {sorted(set(everywhere))}")
    missing = [k for k in HEAD_KERNELS if k not in everywhere]
    require(not missing,
            f"fused LayerNorm kernels missing from the program: {missing}; "
            f"found: {sorted(set(everywhere))}")
    return {"in_scan": {k: in_scan.count(k) for k in SCANNED_KERNELS},
            "outside_scan": {k: everywhere.count(k) for k in HEAD_KERNELS}}


def check_per_chip_batch(hlo: str, per_chip_batch: int, global_batch: int,
                         seq: int) -> Dict[str, Any]:
    """Leg B, from the partitioned program: every Mosaic call's activation
    operands carry the PER-CHIP batch, none the global one, and no
    all-gather rebuilds a global-batch activation anywhere."""
    local_rows, global_rows = per_chip_batch * seq, global_batch * seq
    shapes = {}
    for c in mosaic_calls(hlo):
        lead = {d[0] for d in c["operands"] if len(d) >= 2}
        require(not lead & {global_batch, global_rows},
                f"{c['kernel']} runs on the GLOBAL batch on every chip: "
                f"{c['operands']}")
        if c["kernel"] in SCANNED_KERNELS:
            require(lead & {per_chip_batch, local_rows},
                    f"{c['kernel']} operands carry no per-chip batch dim "
                    f"({per_chip_batch} or {local_rows}): {c['operands']}")
        shapes[c["kernel"]] = [list(d) for d in c["operands"][:3]]
    gathers = [ln.split("backend_config=")[0].strip()[:240]
               for ln in hlo.splitlines() if re.search(
                   rf"= \S*\[(?:{global_batch}|{global_rows}),[\d,]*\]\S* "
                   r"all-gather(?:-start)?\(", ln)]
    require(not gathers,
            "all-gather of a global-batch activation in the partitioned "
            "step:\n" + "\n".join(gathers[:4]))
    return shapes


# ---------------------------------------------------------------------------
# a leg
# ---------------------------------------------------------------------------
def run_steps(t: Training, n_steps: int, log: CompileLog) -> Dict[str, Any]:
    """AOT-compile the jitted step once (the executable that runs is the
    one whose HLO is read), then ``n_steps`` steps on the fixed batch,
    each ended with block_until_ready."""
    t0 = time.perf_counter()
    compiled = t.step.lower(t.params, t.opt_state, t.batch, t.key).compile()
    compile_s = time.perf_counter() - t0
    n_compiles_before_loop = None
    losses, step_ms = [], []
    params, opt_state = t.params, t.opt_state
    for i in range(n_steps):
        t0 = time.perf_counter()
        params, opt_state, loss = compiled(params, opt_state, t.batch, t.key)
        jax.block_until_ready(loss)
        step_ms.append((time.perf_counter() - t0) * 1e3)
        losses.append(float(loss))
        if i == 0:
            n_compiles_before_loop = len(log)
    t.params, t.opt_state = params, opt_state
    require(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    require(losses[-1] < losses[0], f"loss did not fall: {losses}")
    late = log.events[n_compiles_before_loop:]
    require(not late, f"compilations after the first step: {late}")
    return {"losses": [round(x, 4) for x in losses],
            "compile_s": round(compile_s, 1),
            "step_ms": [round(x, 1) for x in step_ms],
            "hlo": compiled.as_text()}


def device_residency(t: Training, devices) -> Dict[str, Any]:
    """Every device holds shards of the batch and a copy (or shard) of the
    state — read from the arrays' own addressable shards."""
    want = {d.id for d in devices}
    ids = t.batch["input_ids"]
    rows = {s.data.shape[0] for s in ids.addressable_shards}
    require({s.device.id for s in ids.addressable_shards} == want
            and rows == {ids.shape[0] // len(want)},
            f"batch not sharded evenly over {sorted(want)}: rows {rows}")
    for leaf in jax.tree_util.tree_leaves((t.params, t.opt_state)):
        require({s.device.id for s in leaf.addressable_shards} == want,
                f"a state leaf is not on every device of {sorted(want)}")
    return {"devices": sorted(want), "batch_rows_per_device": rows.pop()}


def leg(name: str, cfg, devices, dp: int, log: CompileLog) -> Dict[str, Any]:
    global_batch = BATCH_PER_CHIP * dp
    batch = make_batch(cfg, global_batch, SEQ)
    t = build_training(cfg, devices, dp, batch)
    out = run_steps(t, STEPS, log)
    hlo = out.pop("hlo")
    out["kernels"] = check_kernels_in_program(hlo)
    if dp > 1:
        out["per_chip_operands"] = check_per_chip_batch(
            hlo, BATCH_PER_CHIP, global_batch, SEQ)
        out["residency"] = device_residency(t, list(devices)[:dp])
    print(f"[leg {name}] {json.dumps(out)}", flush=True)
    return out


def dp_parity(cfg, devices, dp: int) -> Dict[str, float]:
    """Same initial params, same global batch of BATCH_PER_CHIP: the dp-way
    loss against the one-chip (Leg A mesh) loss, within bf16 tolerance.
    ``cfg`` must be dropout-free."""
    batch = make_batch(cfg, BATCH_PER_CHIP, SEQ, seed=1)
    single = build_training(cfg, devices, 1, batch)
    raw = jax.tree_util.tree_map(np.asarray, single.params)
    one = eval_loss(single)
    many = eval_loss(build_training(cfg, devices, dp, batch, init_params=raw))
    require(abs(one - many) <= 1e-2 * abs(one),
            f"dp={dp} loss {many} vs one-chip loss {one}")
    return {"one_chip": round(one, 5), f"dp{dp}": round(many, 5)}


def result_line(devices) -> str:
    """The last line of stdout on a pass: one JSON object with exactly the
    keys "ok" and "device", the device as JAX reports it."""
    return json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}})


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU — JAX found platform {dev.platform!r} "
              f"({dev.device_kind}); nothing was built", file=sys.stderr)
        return 2
    from paddle_tpu.core import native
    from paddle_tpu.core.jax_cache import configure_compile_cache
    from paddle_tpu.ops.pallas import config as pcfg
    from paddle_tpu.text.ernie import ErnieConfig
    from paddle_tpu.utils import monitor, xprof

    cache_dir = configure_compile_cache()
    devices = jax.devices()
    peaks = xprof.resolve_peaks()  # an unknown device_kind raises
    print(json.dumps({
        "device": json.loads(result_line(devices))["device"],
        "peaks": peaks.to_json(),
        "versions": {p: importlib.metadata.version(p)
                     for p in ("jax", "jaxlib", "libtpu")},
        "compile_cache_dir": cache_dir,
        "native_runtime_built": native.available(),
    }), flush=True)
    require(peaks.source == "table", f"peaks not from the table: "
            f"{peaks.to_json()}")

    log = CompileLog()
    cfg = ErnieConfig()
    legs: Dict[str, Any] = {}
    legs["A"] = leg("A", cfg, devices, 1, log)

    if len(devices) >= 4:
        parity = dp_parity(ErnieConfig(hidden_dropout_prob=0.0,
                                       attention_probs_dropout_prob=0.0),
                           devices, 4)
        legs["B"] = leg("B", cfg, devices, 4, log)
        legs["B"]["parity"] = parity
    else:
        legs["B"] = f"not run: {len(devices)} chip(s) visible, needs 4"
        print(f"[leg B] {legs['B']}", flush=True)

    calls = monitor.default_registry().get("pallas.kernel_calls")
    train_step_compiles = [hit for fn, hit in log.events
                           if "train_step" in fn]
    summary = {
        "legs": legs,
        "pallas_kernel_calls": {
            k: calls.value(kernel=k)
            for k in ("flash_attention_packed", "fused_rdln",
                      "fused_layer_norm")},
        "pallas_fingerprint": pcfg.fingerprint(),
        "compiles": len(log),
        "train_step_cache_hits": sum(train_step_compiles),
        "train_step_compiles": len(train_step_compiles),
    }
    print(f"[summary] {json.dumps(summary)}", flush=True)
    print(result_line(devices), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
