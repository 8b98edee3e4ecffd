"""autoplan — CLI for the cost-model-driven sharding-plan search.

Front-end for ``paddle_tpu.parallel.autoplan``: builds one of the
built-in demo models (no stable serialized Program format yet), searches
the plan space over an emulated N-device CPU mesh, and prints the ranked
candidate table — predicted comm bytes / peak HBM / roofline ms, the
ledger-corrected score, and (with ``--measure-top K``) a measured
step-time column from actually executing the leading candidates, so the
cost model's ranking can be eyeballed against reality.

Demo models (``--model``):

  * ``fc``       — the shardcheck demo tower (hand plan: pure dp)
  * ``toylm``    — ERNIE-toy: embedding + 2-layer MLP head (hand plan:
                   dp2 x tp4, Megatron column/row annotations, vocab-
                   sharded embedding)
  * ``resblock`` — a ResNet block: conv-bn-relu x2 + skip (hand plan:
                   pure dp; conv weights are 4-D so dp is the space)
  * ``rec``      — a wide&deep CTR model (hand plan: tp8 vocab-sharded
                   embeddings)

Usage::

    python -m tools.autoplan [--model fc] [--devices 8] [--top 12]
    python -m tools.autoplan --format json
    python -m tools.autoplan --measure-top 3 --steps 8
    python -m tools.autoplan --selfcheck     # CI probe; rides tier-1

``--selfcheck`` asserts, per demo: (1) the search's best predicted score
reproduces or beats the hand-written plan's score under the same cost
model; (2) every candidate was priced WITHOUT compiling anything
(``executor.traces`` flat across the search — SC/MC-invalid candidates
provably never trace); (3) executing the chosen plan next to the hand
plan from identical init yields matching loss curves and a measured
step time within tolerance-or-better; (4) steady state under the chosen
plan never retraces.  Exits non-zero on any violation.
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


# ---------------------------------------------------------------------------
# Demo models: (main, startup, loss, feed dict, hand-written plan builder)
# ---------------------------------------------------------------------------

def _build_fc(batch: int):
    import numpy as np
    import paddle_tpu.static as static
    from paddle_tpu.static import layers as L

    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        x = L.data("x", [64])
        y = L.data("y", [1])
        h = L.fc(x, 128, act="relu")
        h = L.fc(h, 128, act="relu")
        pred = L.fc(h, 1)
        loss = L.mean(L.square_error_cost(pred, y))
        static.optimizer.SGD(learning_rate=0.01).minimize(loss)
    rng = np.random.default_rng(0)
    feed = {"x": rng.normal(size=(batch, 64)).astype(np.float32),
            "y": rng.normal(size=(batch, 1)).astype(np.float32)}

    def hand_plan(devices):
        from jax.sharding import Mesh
        from paddle_tpu.parallel.sharding import ShardingPlan

        return ShardingPlan(mesh=Mesh(np.asarray(devices), ("dp",)))

    return main, startup, loss, feed, hand_plan


def _build_toylm(batch: int, vocab: int = 512, dim: int = 64, seq: int = 16):
    import numpy as np
    import paddle_tpu.static as static
    from paddle_tpu.static import layers as L

    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        ids = L.data("ids", [seq], dtype="int64")
        y = L.data("y", [1])
        emb = L.embedding(ids, size=[vocab, dim], name="tok_emb")
        h = L.reshape(emb, (-1, seq * dim))
        h = L.fc(h, 4 * dim, act="relu")     # "ffn in"  -> column-parallel
        h = L.fc(h, dim, act="relu")         # "ffn out" -> row-parallel
        pred = L.fc(h, 1)
        loss = L.mean(L.square_error_cost(pred, y))
        static.optimizer.SGD(learning_rate=0.01).minimize(loss)
    rng = np.random.default_rng(0)
    feed = {"ids": rng.integers(0, vocab, size=(batch, seq)).astype(np.int64),
            "y": rng.normal(size=(batch, 1)).astype(np.float32)}

    def hand_plan(devices):
        from jax.sharding import Mesh
        from paddle_tpu.parallel.sharding import ShardingPlan

        mesh = Mesh(np.asarray(devices).reshape(2, len(devices) // 2),
                    ("dp", "tp"))
        tp = int(mesh.shape["tp"])
        # the Megatron layout by hand: ffn-in column-parallel, ffn-out
        # row-parallel (picked by shape), vocab-sharded embedding
        ann = {}
        col = True
        for p in main.all_parameters():
            shape = tuple(p.shape)
            if len(shape) != 2 or p.name == "tok_emb.w":
                continue
            if col and shape[1] % tp == 0:
                ann[p.name] = (None, "tp")
                col = False
            elif not col and shape[0] % tp == 0:
                ann[p.name] = ("tp", None)
                col = True
        return ShardingPlan(mesh=mesh, annotations=ann,
                            embedding_shard="tp")

    return main, startup, loss, feed, hand_plan


def _build_resblock(batch: int, channels: int = 8, hw: int = 8):
    import numpy as np
    import paddle_tpu.static as static
    from paddle_tpu.static import layers as L

    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        x = L.data("x", [channels, hw, hw])
        y = L.data("y", [1])
        h = L.conv2d(x, channels, 3, padding=1, act="relu")
        h = L.conv2d(h, channels, 3, padding=1)
        h = L.relu(L.elementwise_add(h, x))          # the skip
        flat = L.reshape(h, (-1, channels * hw * hw))
        pred = L.fc(flat, 1)
        loss = L.mean(L.square_error_cost(pred, y))
        static.optimizer.SGD(learning_rate=0.01).minimize(loss)
    rng = np.random.default_rng(0)
    feed = {"x": rng.normal(size=(batch, channels, hw, hw)).astype(np.float32),
            "y": rng.normal(size=(batch, 1)).astype(np.float32)}

    def hand_plan(devices):
        from jax.sharding import Mesh
        from paddle_tpu.parallel.sharding import ShardingPlan

        return ShardingPlan(mesh=Mesh(np.asarray(devices), ("dp",)))

    return main, startup, loss, feed, hand_plan


def _build_ctr(vocab: int, dim: int, slots: int, lr: float):
    """The wide&deep program: a wide ``(V, 1)`` linear table + a deep
    ``(V, D)`` embedding -> MLP, sigmoid + log loss.  Returns (main,
    startup, loss)."""
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
    return main, startup, loss


def _zipf_ids(rng, vocab: int, shape, a: float = 1.3):
    """Skewed id draw (popular items dominate — the CTR dedup payoff)."""
    import numpy as np

    z = rng.zipf(a, size=shape)
    return ((z - 1) % vocab).astype(np.int64)


def _build_rec(batch: int, vocab: int = 256, dim: int = 8, slots: int = 4):
    import numpy as np

    main, startup, loss = _build_ctr(vocab, dim, slots, lr=0.05)
    rng = np.random.default_rng(0)
    feed = {"ids": _zipf_ids(rng, vocab, (batch, slots)),
            "y": (rng.random(size=(batch, 1)) < 0.3).astype(np.float32)}

    def hand_plan(devices):
        from jax.sharding import Mesh
        from paddle_tpu.parallel.sharding import ShardingPlan

        # every device on tp, blanket vocab sharding
        mesh = Mesh(np.asarray(devices).reshape(1, len(devices)),
                    ("dp", "tp"))
        return ShardingPlan(mesh=mesh, embedding_shard="tp")

    return main, startup, loss, feed, hand_plan


_DEMOS = {"fc": _build_fc, "toylm": _build_toylm,
          "resblock": _build_resblock, "rec": _build_rec}


# ---------------------------------------------------------------------------
# Execution: measure a plan for real
# ---------------------------------------------------------------------------

def _measure_plan(main, startup, loss, feed, plan, steps: int,
                  init=None):
    """(losses, ms_per_step, retraces, init) executing ``plan`` for
    ``steps`` steps — warmup (compile) excluded from the timing, retraces
    counted across the timed loop.  ``init`` seeds identical parameters
    across measured plans (captured on first call)."""
    import numpy as np
    import paddle_tpu.static as static
    from paddle_tpu.utils import monitor

    exe = static.Executor()
    scope = static.Scope()
    traces = monitor.default_registry().counter("executor.traces")
    with static.scope_guard(scope):
        exe.run(startup)
        if init is None:
            init = {p.name: np.array(scope.find_var(p.name))
                    for p in main.all_parameters()}
        else:
            for p in main.all_parameters():
                if p.name in init:
                    scope.set(p.name, init[p.name])
        compiled = static.CompiledProgram(main).with_sharding(plan=plan)
        losses = [float(np.asarray(
            exe.run(compiled, feed=feed, fetch_list=[loss])[0]).item())]
        warm = traces.value()
        t0 = time.perf_counter()
        for _ in range(max(1, steps - 1)):
            losses.append(float(np.asarray(
                exe.run(compiled, feed=feed, fetch_list=[loss])[0]).item()))
        dt = time.perf_counter() - t0
        retraces = traces.value() - warm
    return losses, dt * 1e3 / max(1, steps - 1), int(retraces), init


def _run_model(name: str, devices_n: int, batch: int):
    """(choice, hand_candidate, parts) — the search + the hand plan scored
    under the same corrections."""
    import jax
    from paddle_tpu.parallel import autoplan
    from paddle_tpu.static import memcheck as _memcheck

    build = _DEMOS[name]
    main, startup, loss, feed, hand_plan = build(batch)
    devices = list(jax.devices()[:devices_n])
    feed_shapes = _memcheck._feed_shape_dict(feed)
    choice = autoplan.search(main, devices=devices,
                             feed_shapes=feed_shapes,
                             fetch_names=(loss.name,))
    hand = autoplan.score_plan(main, hand_plan(devices),
                               feed_shapes=feed_shapes,
                               fetch_names=(loss.name,),
                               corrections=choice.corrections)
    hand.desc["placement"] = "hand"
    return choice, hand, (main, startup, loss, feed)


def _measure_top(choice, hand, parts, k: int, steps: int) -> None:
    """Execute the top-K candidates + the hand plan; fill measured
    columns in place."""
    main, startup, loss, feed = parts
    init = None
    for cand in [hand] + choice.ranked[:k]:
        losses, ms, retraces, init = _measure_plan(
            main, startup, loss, feed, cand.plan, steps, init)
        cand.measured = {"step_time_ms": ms, "final_loss": losses[-1],
                         "retraces": retraces}


# ---------------------------------------------------------------------------
# selfcheck: rides tier-1
# ---------------------------------------------------------------------------

def selfcheck(devices_n: int = 8, steps: int = 6) -> int:
    from paddle_tpu.utils import monitor

    traces = monitor.default_registry().counter("executor.traces")
    failures = []
    for name in ("fc", "toylm", "resblock", "rec"):
        t0 = traces.value()
        choice, hand, parts = _run_model(name, devices_n, batch=16)
        if traces.value() != t0:
            failures.append(f"{name}: the search itself compiled/traced "
                            "(pruning must be static)")
            continue
        if not choice.ranked:
            failures.append(f"{name}: no surviving candidates")
            continue
        best = choice.ranked[0]
        if hand.score is not None and best.score > hand.score * 1.001:
            failures.append(
                f"{name}: best predicted score {best.score:.4f}ms loses to "
                f"hand-written {hand.score:.4f}ms ({hand.plan.fingerprint()})")
            continue
        # execution parity: chosen vs hand from identical init
        main, startup, loss, feed = parts
        h_losses, h_ms, _h_re, init = _measure_plan(
            main, startup, loss, feed, hand.plan, steps)
        b_losses, b_ms, b_re, _ = _measure_plan(
            main, startup, loss, feed, best.plan, steps, init)
        import numpy as np

        if not np.allclose(h_losses, b_losses, rtol=5e-3, atol=1e-6):
            failures.append(f"{name}: loss curves diverge between chosen "
                            f"and hand plan: {b_losses} vs {h_losses}")
        if b_re != 0:
            failures.append(f"{name}: chosen plan retraced {b_re}x in "
                            "steady state")
        # CPU dispatch wall time is noisy — the gate is coarse
        # tolerance-or-better, not a benchmark
        if b_ms > h_ms * 3.0 + 5.0:
            failures.append(f"{name}: chosen plan measured {b_ms:.2f}ms/step"
                            f" vs hand {h_ms:.2f}ms/step (beyond tolerance)")
        print(f"  {name}: best={best.label!r} score={best.score:.4f}ms "
              f"hand={hand.score:.4f}ms measured {b_ms:.2f} vs "
              f"{h_ms:.2f} ms/step "
              f"({len(choice.ranked)} ok / {len(choice.pruned)} pruned)")
    if failures:
        for f in failures:
            print(f"autoplan selfcheck: {f}", file=sys.stderr)
        return 1
    print("autoplan selfcheck: OK")
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m tools.autoplan", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument("--model", choices=sorted(_DEMOS), default="fc")
    parser.add_argument("--devices", type=int, default=8,
                        help="emulated CPU mesh size (default 8)")
    parser.add_argument("--batch", type=int, default=16)
    parser.add_argument("--top", type=int, default=12,
                        help="table rows to print (default 12)")
    parser.add_argument("--measure-top", type=int, default=0, metavar="K",
                        help="execute the top K candidates (+ the hand "
                        "plan) and add measured columns")
    parser.add_argument("--steps", type=int, default=6,
                        help="steps per measured plan (with --measure-top)")
    parser.add_argument("--format", choices=("text", "json"), default="text")
    parser.add_argument("--selfcheck", action="store_true",
                        help="CI probe: reproduce-or-beat the hand-written "
                        "plans, static pruning, execution parity")
    args = parser.parse_args(argv)

    _ensure_cpu_devices(args.devices)

    if args.selfcheck:
        return selfcheck(args.devices)

    choice, hand, parts = _run_model(args.model, args.devices, args.batch)
    if args.measure_top > 0:
        _measure_top(choice, hand, parts, args.measure_top, args.steps)
    if args.format == "json":
        doc = choice.to_dict()
        doc["hand"] = hand.to_dict()
        print(json.dumps(doc, sort_keys=True))
    else:
        print(choice.render(top=args.top))
        hs = f"{hand.score:.3f}" if hand.score is not None else "-"
        hm = (f"  measured {hand.measured['step_time_ms']:.3f}ms/step"
              if "step_time_ms" in hand.measured else "")
        print(f"hand-written plan [{hand.label}]: score {hs}ms{hm}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
