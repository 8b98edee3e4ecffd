"""Inspect and dry-run elastic checkpoints (elastic/checkpoint.py).

The fault-tolerance analogue of proglint/metricsdump: one command that
answers "is this checkpoint intact, and what would restoring it onto a
different mesh actually move?" without touching the training job.

Usage::

    python -m tools.elastic inspect  CKPT_DIR [--step N] [--verify-shards]
    python -m tools.elastic reshard  CKPT_DIR --mesh dp=2 [--zero-stage N]
    python -m tools.elastic selfcheck [--json]

``inspect`` prints the digest-verified manifest for one step (default:
latest): step, source mesh, plan fingerprint, and a per-leaf table of
shape/dtype/spec/shards.  ``--verify-shards`` additionally re-hashes every
shard file against its recorded SHA-256.

``reshard`` is a dry run of an elastic resume at a new mesh shape: it
builds the target ShardingPlan, computes each leaf's target placement
(without loading any shard data), and reports which leaves would physically
reshard and how many bytes that moves — the cost report for an eviction
before you pay it.

``selfcheck`` forces 8 host devices, saves a ZeRO-3 dp=4 state, restores
it under a dp=2 plan, and verifies the gathered values are bitwise
identical with a nonzero reshard count — a tier-1-safe end-to-end probe of
the whole save → manifest → gather → re-place path.  Exits nonzero on any
mismatch.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys


def _force_host_devices(n: int = 8) -> None:
    """Before the first jax import: make XLA expose n host devices so dp
    meshes exist on a CPU-only machine."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()


def _parse_mesh_arg(spec: str):
    """'dp=2' or 'dp=2,tp=4' -> ordered {axis: size}."""
    axes = {}
    for part in spec.split(","):
        if "=" not in part:
            raise SystemExit(f"--mesh: expected axis=size, got {part!r}")
        name, _, size = part.partition("=")
        axes[name.strip()] = int(size)
    return axes


def _build_mesh(axes):
    import numpy as np

    import jax
    from jax.sharding import Mesh

    n = 1
    for s in axes.values():
        n *= s
    devs = jax.devices()
    if len(devs) < n:
        raise SystemExit(
            f"mesh {axes} needs {n} devices, have {len(devs)} "
            f"(set XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    return Mesh(np.asarray(devs[:n]).reshape(tuple(axes.values())),
                tuple(axes.keys()))


# ---------------------------------------------------------------------------
# inspect
# ---------------------------------------------------------------------------

def _leaf_bytes(leaf) -> int:
    import numpy as np

    n = 1
    for d in leaf["shape"]:
        n *= int(d)
    return n * np.dtype(leaf.get("dtype", "float32")).itemsize


def cmd_inspect(args) -> int:
    from paddle_tpu.elastic import checkpoint as eckpt

    try:
        body = eckpt.load_manifest(args.ckpt_dir, args.step)
    except eckpt.CheckpointError as e:
        print(f"elastic: {e}", file=sys.stderr)
        return 1
    step = body["step"]
    print(f"checkpoint {args.ckpt_dir} step {step}")
    print(f"  schema:           {body['schema']}")
    print(f"  mesh:             {body['mesh']['axes'] or '(single host)'} "
          f"[{body['mesh']['fingerprint']}]")
    print(f"  plan fingerprint: {body['plan_fingerprint'] or '(none)'}")
    print(f"  prng key:         {body['prng_key'] or '(none)'}")
    print(f"  steps on disk:    {eckpt.list_steps(args.ckpt_dir)} "
          f"(latest={eckpt.latest_step(args.ckpt_dir)})")
    total = 0
    print(f"  leaves ({len(body['leaves'])}):")
    for leaf in body["leaves"]:
        total += _leaf_bytes(leaf)
        spec = leaf["spec"] or "replicated"
        print(f"    {leaf['name']:<32} {str(tuple(leaf['shape'])):<16} "
              f"{leaf['dtype']:<10} spec={spec} shards={len(leaf['shards'])}")
    print(f"  total state: {total} bytes")
    if args.verify_shards:
        sdir = os.path.join(args.ckpt_dir, f"step_{int(step):08d}")
        bad = 0
        for leaf in body["leaves"]:
            for sh in leaf["shards"]:
                fpath = os.path.join(sdir, sh["file"])
                try:
                    with open(fpath, "rb") as f:
                        ok = hashlib.sha256(f.read()).hexdigest() == sh["sha256"]
                except OSError:
                    ok = False
                if not ok:
                    bad += 1
                    print(f"elastic: shard digest mismatch: {fpath}",
                          file=sys.stderr)
        if bad:
            return 1
        print("  shard digests: all OK")
    return 0


# ---------------------------------------------------------------------------
# reshard dry run
# ---------------------------------------------------------------------------

def cmd_reshard(args) -> int:
    _force_host_devices()
    import numpy as np

    from paddle_tpu.elastic import checkpoint as eckpt
    from paddle_tpu.parallel.sharding import ShardingPlan

    try:
        body = eckpt.load_manifest(args.ckpt_dir, args.step)
    except eckpt.CheckpointError as e:
        print(f"elastic: {e}", file=sys.stderr)
        return 1
    axes = _parse_mesh_arg(args.mesh)
    mesh = _build_mesh(axes)
    plan = ShardingPlan(mesh=mesh, zero_stage=args.zero_stage)
    # placement only needs shapes: zero-copy broadcast views stand in for
    # the real leaves, no shard file is read
    fake = {leaf["name"]: np.broadcast_to(
                np.zeros((), dtype=leaf.get("dtype", "float32")),
                tuple(leaf["shape"]))
            for leaf in body["leaves"]}
    shardings = plan.state_shardings(fake, mesh)
    saved_axes = body["mesh"]["axes"]
    target_axes = {str(a): int(mesh.shape[a]) for a in mesh.axis_names}
    moved_bytes = 0
    moved = []
    for leaf in body["leaves"]:
        tspec = eckpt._spec_to_json(shardings[leaf["name"]].spec)
        if (eckpt._placement_sig(saved_axes, leaf["spec"])
                != eckpt._placement_sig(target_axes, tspec)):
            moved.append((leaf["name"], leaf["spec"] or "replicated",
                          tspec or "replicated"))
            moved_bytes += _leaf_bytes(leaf)
    print(f"reshard dry run: step {body['step']} "
          f"{saved_axes or '(single host)'} -> {target_axes} "
          f"zero_stage={args.zero_stage}")
    print(f"  target plan: {plan.fingerprint()}")
    if not moved:
        print("  no leaf reshards (placements identical)")
    for name, old, new in moved:
        print(f"  reshard {name:<32} {old} -> {new}")
    print(f"  {len(moved)}/{len(body['leaves'])} leaves reshard, "
          f"{moved_bytes} bytes move")
    return 0


# ---------------------------------------------------------------------------
# selfcheck
# ---------------------------------------------------------------------------

def cmd_selfcheck(args) -> int:
    _force_host_devices()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import tempfile

    import numpy as np

    import jax
    from jax.sharding import Mesh

    from paddle_tpu.elastic import checkpoint as eckpt
    from paddle_tpu.parallel.mesh import DP_AXIS
    from paddle_tpu.parallel.sharding import ShardingPlan

    verdict = {"ok": False, "devices": jax.device_count()}
    try:
        rng = np.random.default_rng(0)
        state = {
            "w": rng.normal(size=(64, 16)).astype(np.float32),
            "b": rng.normal(size=(16,)).astype(np.float32),
            "step": np.float32(3.0),
        }

        def dp_plan(n):
            return ShardingPlan(
                mesh=Mesh(np.asarray(jax.devices()[:n]), (DP_AXIS,)),
                zero_stage=3)

        with tempfile.TemporaryDirectory() as d:
            eckpt.save_checkpoint(d, state, 7, plan=dp_plan(4))
            restored, meta = eckpt.restore_checkpoint(d, plan=dp_plan(2))
        mismatches = [k for k in state
                      if not np.array_equal(np.asarray(restored[k]), state[k])]
        verdict.update(
            step=meta["step"], resharded_leaves=meta["resharded_leaves"],
            saved_mesh=meta["mesh_axes"], mismatched_leaves=mismatches,
            ok=(not mismatches and meta["step"] == 7
                and meta["resharded_leaves"] > 0))
    except Exception as e:  # selfcheck reports, never tracebacks
        verdict["error"] = f"{type(e).__name__}: {e}"
    if args.json:
        print(json.dumps(verdict, sort_keys=True))
    else:
        print(f"elastic selfcheck: {'OK' if verdict['ok'] else 'FAIL'} "
              f"({verdict})")
    return 0 if verdict["ok"] else 1


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m tools.elastic", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    sub = parser.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("inspect", help="print the digest-verified manifest")
    p.add_argument("ckpt_dir")
    p.add_argument("--step", type=int, default=None)
    p.add_argument("--verify-shards", action="store_true",
                   help="re-hash every shard file against the manifest")
    p.set_defaults(fn=cmd_inspect)

    p = sub.add_parser("reshard",
                       help="dry-run a restore onto a different mesh")
    p.add_argument("ckpt_dir")
    p.add_argument("--step", type=int, default=None)
    p.add_argument("--mesh", required=True,
                   help="target mesh, e.g. dp=2 or dp=2,tp=2")
    p.add_argument("--zero-stage", type=int, default=0)
    p.set_defaults(fn=cmd_reshard)

    p = sub.add_parser("selfcheck",
                       help="end-to-end save/reshard-restore parity probe")
    p.add_argument("--json", action="store_true")
    p.set_defaults(fn=cmd_selfcheck)

    args = parser.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
