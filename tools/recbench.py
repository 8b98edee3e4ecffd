"""Recommender benchmark: wide&deep CTR training + serving over
vocab-sharded embeddings (parallel/embedding.py).

The ISSUE-15 acceptance harness as a tool: builds a synthetic wide&deep
CTR model (a wide ``(V, 1)`` linear table + a deep ``(V, D)`` embedding ->
slot-mean -> MLP, squashed through sigmoid + log loss), trains it on an
8-device CPU mesh with ``ShardingPlan(embedding_shard="tp")`` — every
lookup routed through the dedup + all_to_all exchange — and serves the
trained deep table through the multi-tenant frontend's embedding tenant
(submit-side id dedup).  Prints exactly ONE JSON line:

  * ``results`` — benchdiff-compatible rows ({metric, value, unit}):
    training rows/sec through the sharded path, the per-step per-device
    exchange-byte accounting (`embedding.exchange_bytes` over both
    tables, fp32 and int8-backward variants), serving qps and the
    observed submit-side unique-id ratio.
  * ``parity`` — the correctness gates, all booleans (benchdiff ignores
    them; ``--selfcheck`` enforces them): **token rows bitwise** (the
    deep embedding's forward output fetched from the sharded run equals
    the single-device dense reference bit-for-bit), every training-step
    loss within rtol 1e-6 of the dense
    reference (whole-step fusion reassociates fp32 sums at the last ulp —
    the lookup itself is bitwise, pinned by tests/test_sharded_embedding
    .py), **serving rows bitwise** against ``weight[ids]``, and zero
    steady-state retraces (``executor.traces`` flat across the timed
    loop).

On forced-host CPU devices the wall numbers measure dispatch, not TPU
compute — the exchange-byte accounting and the parity gates are the
portable numbers.

Usage:
    python -m tools.recbench [--devices N] [--vocab V] [--dim D]
                             [--slots S] [--batch B] [--steps K]
                             [--out BENCH_REC.json]
    python -m tools.recbench --selfcheck     # small sizes + gates; rides tier-1
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _ensure_cpu_devices(n: int) -> None:
    """Must run BEFORE jax imports: force enough virtual XLA host devices
    for an N-way mesh (no-op when a harness already exported XLA_FLAGS)."""
    if "jax" in sys.modules:
        return
    env = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in env:
        os.environ["XLA_FLAGS"] = (
            env + f" --xla_force_host_platform_device_count={n}").strip()


def _build_ctr(vocab: int, dim: int, slots: int, lr: float):
    """The wide&deep program: returns (main, startup, loss, emb_out,
    deep_table_name)."""
    import paddle_tpu.static as static
    from paddle_tpu.static import layers as L

    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        ids = L.data("ids", [slots], dtype="int64")
        y = L.data("y", [1])
        deep = L.embedding(ids, size=[vocab, dim], name="deep_emb")
        wide = L.embedding(ids, size=[vocab, 1], name="wide_emb")
        concat = L.reshape(deep, (-1, slots * dim))
        hidden = L.fc(concat, max(16, dim), act="relu")
        deep_logit = L.fc(hidden, 1)
        wide_logit = L.fc(L.reshape(wide, (-1, slots)), 1)
        prob = L.sigmoid(L.elementwise_add(wide_logit, deep_logit))
        loss = L.mean(L.log_loss(prob, y))
        static.optimizer.SGD(learning_rate=lr).minimize(loss)
    return main, startup, loss, deep, "deep_emb.w"


def _zipf_ids(rng, vocab: int, shape, a: float = 1.3):
    """Skewed id draw (popular items dominate — the CTR dedup payoff)."""
    import numpy as np

    z = rng.zipf(a, size=shape)
    return ((z - 1) % vocab).astype(np.int64)


def run_bench(args) -> dict:
    import numpy as np
    import jax
    from jax.sharding import Mesh

    import paddle_tpu.static as static
    from paddle_tpu.parallel import embedding as pemb
    from paddle_tpu.utils import monitor

    V, D, S, B = args.vocab, args.dim, args.slots, args.batch
    steps, k = args.steps, args.devices
    rng = np.random.default_rng(0)
    ids = _zipf_ids(rng, V, (B, S))
    yv = (rng.random(size=(B, 1)) < 0.3).astype(np.float32)

    # -- single-device dense reference ------------------------------------
    main, startup, loss, emb_out, wname = _build_ctr(V, D, S, args.lr)
    exe = static.Executor()
    sc = static.Scope()
    losses_ref, rows_ref = [], None
    with static.scope_guard(sc):
        exe.run(startup)
        init = {p.name: np.array(sc.find_var(p.name))
                for p in main.all_parameters()}
        for i in range(steps):
            outs = exe.run(main, feed={"ids": ids, "y": yv},
                           fetch_list=[loss, emb_out])
            losses_ref.append(np.array(outs[0]))
            if i == 0:
                rows_ref = np.array(outs[1])

    # -- the sharded run: blanket embedding_shard over the tp axis --------
    if len(jax.devices()) < k:
        raise SystemExit(f"need {k} devices, have {len(jax.devices())}")
    mesh = Mesh(np.asarray(jax.devices()[:k]).reshape(1, k), ("dp", "tp"))
    main2, startup2, loss2, emb_out2, _ = _build_ctr(V, D, S, args.lr)
    comp = static.CompiledProgram(main2).with_sharding(
        mesh=mesh, embedding_shard="tp")
    exe2 = static.Executor()
    sc2 = static.Scope()
    traces = monitor.default_registry().get("executor.traces")
    losses_sh, rows_sh = [], None
    with static.scope_guard(sc2):
        exe2.run(startup2)
        for p1, p2 in zip(main.all_parameters(), main2.all_parameters()):
            sc2.set(p2.name, init[p1.name])
        # warmup (compiles) + token-row fetch for the parity gate
        outs = exe2.run(comp, feed={"ids": ids, "y": yv},
                        fetch_list=[loss2, emb_out2])
        losses_sh.append(np.array(outs[0]))
        rows_sh = np.array(outs[1])
        traces_warm = traces.value()
        t0 = time.perf_counter()
        for _ in range(steps - 1):
            losses_sh.append(np.array(exe2.run(
                comp, feed={"ids": ids, "y": yv},
                fetch_list=[loss2, emb_out2])[0]))
        dt = time.perf_counter() - t0
        retraces = traces.value() - traces_warm
        trained_w = np.asarray(sc2.find_var(wname), np.float32)
    rows_per_sec = B * max(1, steps - 1) / max(dt, 1e-9)

    # -- wire accounting: both covered tables, fp32 + int8 backward -------
    n_ids = B * S
    xbytes = (pemb.exchange_bytes(n_ids, D, k)
              + pemb.exchange_bytes(n_ids, 1, k))
    xbytes_q = (pemb.exchange_bytes(n_ids, D, k, quantize="int8")
                + pemb.exchange_bytes(n_ids, 1, k, quantize="int8"))

    # -- serving: embedding tenant + submit-side dedup --------------------
    from paddle_tpu.serving.frontend import Server

    req_ids = _zipf_ids(rng, V, (args.serve_rows,))
    n_req, qps, unique_ratio = 64, 0.0, 1.0
    with Server(bucket_edges=(args.serve_rows,), max_wait_ms=0.5) as srv:
        srv.add_embedding_tenant("ctr", trained_w)
        srv.submit("ctr", {"ids": req_ids}).result(timeout=60)  # warm
        t0 = time.perf_counter()
        futs = [srv.submit("ctr", {"ids": req_ids}) for _ in range(n_req)]
        outs = [f.result(timeout=60) for f in futs]
        qps = n_req / max(time.perf_counter() - t0, 1e-9)
        served = np.asarray(outs[-1][0], np.float32)
    g = monitor.default_registry().get("emb.unique_ratio")
    if g is not None:
        unique_ratio = float(g.value())
    serve_bitwise = bool(np.array_equal(served, trained_w[req_ids]))

    losses_ref_f = [float(x) for x in losses_ref]
    losses_sh_f = [float(x) for x in losses_sh]
    parity = {
        "token_rows_bitwise": bool(np.array_equal(rows_ref, rows_sh)),
        "losses_allclose_rtol1e6": bool(np.allclose(
            losses_ref_f, losses_sh_f, rtol=1e-6, atol=0.0)),
        "serve_rows_bitwise": serve_bitwise,
        "zero_steady_state_retraces": bool(retraces == 0),
    }
    results = [
        {"metric": "rec_train_throughput", "value": round(rows_per_sec, 1),
         "unit": "rows/sec", "devices": k, "batch": B, "slots": S},
        {"metric": "rec_exchange_bytes_per_step", "value": xbytes,
         "unit": "bytes/device", "tables": 2, "quantize": "none"},
        {"metric": "rec_exchange_bytes_per_step_int8", "value": xbytes_q,
         "unit": "bytes/device", "tables": 2, "quantize": "int8"},
        {"metric": "rec_serve_qps", "value": round(qps, 1),
         "unit": "req/sec", "rows": args.serve_rows},
        {"metric": "rec_serve_unique_ratio", "value": round(unique_ratio, 4),
         "unit": "ratio"},
    ]
    return {
        "_note": "recbench on XLA:CPU host devices — wall-clock rows/sec "
                 "and qps measure host dispatch, not TPU compute; the "
                 "exchange-byte accounting and the parity booleans are the "
                 "portable numbers.",
        "command": "python -m tools.recbench --out BENCH_REC.json",
        "bench": "recbench", "schema": 1, "environment": "cpu",
        "devices": k, "vocab": V, "dim": D, "slots": S, "batch": B,
        "steps": steps, "results": results, "parity": parity,
        "losses": {"ref": losses_ref_f, "sharded": losses_sh_f},
    }


def _selfcheck(result) -> int:
    """Acceptance gates (ISSUE 15): schema, every parity bool true,
    quantized wire strictly below fp32, positive throughput."""
    errors = []
    for field in ("results", "parity", "losses", "devices"):
        if field not in result:
            errors.append(f"missing field {field!r}")
    for name, ok in result.get("parity", {}).items():
        if not ok:
            errors.append(f"parity gate {name} failed")
    by_metric = {r["metric"]: r["value"] for r in result.get("results", ())}
    if not by_metric.get("rec_train_throughput", 0) > 0:
        errors.append("non-positive training throughput")
    if not by_metric.get("rec_serve_qps", 0) > 0:
        errors.append("non-positive serving qps")
    if not (0 < by_metric.get("rec_exchange_bytes_per_step_int8", 0)
            < by_metric.get("rec_exchange_bytes_per_step", 0)):
        errors.append("int8 exchange accounting not below fp32")
    if not by_metric.get("rec_serve_unique_ratio", 1.0) < 1.0:
        errors.append("zipf request batch deduplicated nothing")
    if errors:
        print("SELFCHECK FAIL:", "; ".join(errors), file=sys.stderr)
        return 1
    print("recbench selfcheck: OK", file=sys.stderr)
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="recbench", description=__doc__)
    p.add_argument("--devices", type=int, default=8)
    p.add_argument("--vocab", type=int, default=8192)
    p.add_argument("--dim", type=int, default=64)
    p.add_argument("--slots", type=int, default=8)
    p.add_argument("--batch", type=int, default=512)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--serve-rows", type=int, default=256)
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--out", default=None,
                   help="also write the JSON to this file")
    p.add_argument("--selfcheck", action="store_true",
                   help="small sizes + acceptance gates; exit 0/1")
    args = p.parse_args(argv)
    _ensure_cpu_devices(args.devices)
    from paddle_tpu.core.jax_cache import configure_compile_cache

    configure_compile_cache()
    if args.selfcheck:
        args.vocab, args.dim, args.slots = 64, 8, 4
        args.batch, args.steps, args.serve_rows = 32, 6, 64
    result = run_bench(args)
    text = json.dumps(result)
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1, sort_keys=False)
            f.write("\n")
    if args.selfcheck:
        return _selfcheck(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
