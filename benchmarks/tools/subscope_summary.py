"""Where one cell's step spends the device's time below the regions: run the
cell with `--trace 1`, keep the profiler's file, join it with the compiled
step's text and print, for every region the program divides further
(`paddle_tpu/utils/xprof.SUBSCOPES`: `attn`, `ffn`), child by child and pass
by pass (`fwd`, the recomputed forward `remat`, `bwd`), milliseconds and
events per step; each parent's remainder (what lies in no child) with its
five largest operations and how each was resolved; the regions' forward
beside their recomputed forward (what XLA dropped of the second is the
difference); and the time in fusions that mix a recomputed forward with
other work, which go whole to one side.

A child is resolved as the metrics resolve it (`readers/trace_subscope_ms`:
the instruction's own path, else its inner products', else most of its
instructions'), a recomputed forward as `readers/trace_remat_ms` does, so a
row here is the metric of that name.

    python3 benchmarks/tools/subscope_summary.py --workload <cell> [--seed n] [--seconds s] [--out file]

With `--out` the compiled step's text and the table's operations (name ->
nanoseconds, events) are kept beside the file (`.hlo.txt`, `.table.json`):
an operation's name changes with every compile, and with both a session
without a chip can resolve `fusion.1041` again.
"""
import argparse
import collections
import json
import pathlib
import sys
import time

T_START = time.perf_counter()
ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

PASSES = ("fwd", "remat", "bwd")
CORE = "core"       # a bucket of its own in the region table (`attn/core`)


def every_scope(subscopes: dict, region: str):
    """Every scope anywhere under `region`, `core` left out: the `subs` list
    the metrics of that region ask the sub-scope reader with."""
    every, todo = [], [region]
    while todo:
        parent = todo.pop(0)
        for c in subscopes.get(parent, ()):
            if c != CORE and c not in every:
                every.append(c)
            todo.append(f"{parent}/{c}")
    return every


def path_under(op_name: str, region: str, subscopes: dict):
    """The scopes of the table that an `op_name` path goes through below
    `region`, in the table's order (`.../attn/mixer/ssm/proj/in_proj/dot` ->
    "ssm/proj"); None where it goes through none, or not through `region`."""
    from benchmarks.harness import regions
    comps = regions._WRAPPERS.sub("", regions._JIT.sub("", op_name)).split("/")
    if region not in comps:
        return None
    node = region
    for c in comps[len(comps) - comps[::-1].index(region):]:
        if c != CORE and c in subscopes.get(node, ()):
            node = f"{node}/{c}"
    return node[len(region) + 1:] or None


def instruction_paths(comps: dict, region: str, subscopes: dict) -> dict:
    """{instruction name: its `path_under` the region, or None}, resolved
    as the sub-scope reader resolves (own path; a fusion whose own path
    names no scope, its inner products' most frequent, else its
    instructions'), to the whole path where the reader gives the innermost
    name: `ssm/proj` and `proj` are two rows and one metric."""
    from benchmarks.harness import regions
    out = {}
    for instrs in comps.values():
        for ins in instrs:
            path = path_under(ins.op_name, region, subscopes)
            if path is None and ins.calls:
                inner = regions._inside(comps, ins.calls)
                for pool in ([i for i in inner
                              if i.opcode in regions._PRODUCTS], inner):
                    found = collections.Counter(
                        p for p in (path_under(i.op_name, region, subscopes)
                                    for i in pool) if p)
                    if found:
                        path = found.most_common(1)[0][0]
                        break
            out[ins.name] = path
    return out


def served_paths(comps: dict, paths: dict) -> dict:
    """{an instruction the compiler made and gave no path: the path of the
    first instruction that uses it, through others of its kind}: such an
    operation (a layout `copy`, a prefetch's `copy-done`) gets its user's
    region in the region table and no sub-scope from the reader, so it
    stands in the region's remainder; this says whose work it does."""
    served = {}
    for instrs in comps.values():
        user = {}
        for ins in instrs:
            for ref in ins.operands:
                user.setdefault(ref, ins.name)
        for ins in reversed(instrs):        # a user comes after its operand
            if not (ins.op_name or ins.calls) and ins.name in user:
                path = served.get(user[ins.name]) or paths.get(user[ins.name])
                if path:
                    served[ins.name] = path
    return served


def row_of(region: str, path, subscopes: dict) -> str:
    """A row's name: the path; what lies in a divided scope (or the region)
    and in none of its children is that scope's `(in no child)`."""
    if path and f"{region}/{path}" not in subscopes:
        return path
    return f"{path or region} (in no child)"


def rows_of(t: dict, counts: dict, region: str, paths: dict, remat: dict,
            subscopes: dict):
    """{row: {pass: [ns, events, {name: ns}]}} of one region's bucket."""
    rows = collections.defaultdict(
        lambda: {p: [0.0, 0, {}] for p in PASSES})
    for (bucket, which), ops in t["ops"].items():
        if bucket != region:
            continue
        for name, ns in ops.items():
            cell = rows[row_of(region, paths.get(name), subscopes)][
                "remat" if remat.get(name, (False,))[0] else which]
            cell[0] += ns
            cell[1] += counts.get(name, 0)
            cell[2][name] = ns
    return rows


def bucket_ms(t: dict, bucket: str) -> float:
    return sum(v for (b, _), v in t["ns"].items() if b == bucket) \
        / t["steps"] / 1e6


def render(t, counts, text, subscopes, subscope_reader, remat_reader,
           instr_regions, out, top: int = 3) -> None:
    from benchmarks.harness import regions
    steps = t["steps"]
    ms = lambda ns: ns / steps / 1e6  # noqa: E731
    t0 = time.perf_counter()
    remat = remat_reader.instruction_remat(text)
    print(f"{t['chip']}: {steps} steps, busy {ms(t['busy_ns']):.3f} ms per "
          f"step, names found for {100 * t['coverage']:.3f}% of it; the text "
          f"read for the recomputed forward in "
          f"{time.perf_counter() - t0:.1f} s", file=out)
    comps = regions.parse(text)

    def how(name):
        return (f"region by {instr_regions.get(name, (0, 0, '?'))[2]}"
                f"{', mixes passes' if remat.get(name, (0, 0, 0))[2] else ''}")

    for region in (r for r in subscopes if "/" not in r):
        paths = instruction_paths(comps, region, subscopes)
        rows = rows_of(t, counts, region, paths, remat, subscopes)
        if not rows:
            continue
        # the reader itself, asked as the metrics ask it: what one `subs`
        # list costs a traced run, and that a row's last scope is its answer
        t0 = time.perf_counter()
        leaf = subscope_reader.instruction_subscopes(
            text, region, every_scope(subscopes, region))
        seconds = time.perf_counter() - t0
        differ = [n for n, low in leaf.items()
                  if low != ((paths[n] or "").split("/")[-1] or None)]
        remainder = row_of(region, None, subscopes)
        served = served_paths(comps, paths)
        total = sum(c[0] for r in rows.values() for c in r.values())
        print(f"\n{region}: {ms(total):.3f} ms per step in the bucket "
              f"`{region}`" + (f" (`{region}/{CORE}` is a bucket of its own: "
                               f"{bucket_ms(t, region + '/' + CORE):.3f})"
                               if region + "/" + CORE in
                               {b for b, _ in t["ns"]} else "")
              + f"; the text read for its scopes in {seconds:.1f} s (what the "
              f"metrics of one `subs` list cost a traced run); "
              f"{len(differ)} instructions that the reader resolves to "
              f"another scope than their row {differ[:5]}", file=out)
        print(f"{'child':<26}{'pass':<7}{'ms/step':>9}{'events/step':>13}  "
              f"largest operations (ms/step)", file=out)
        for label in sorted(rows, key=lambda r: -sum(
                c[0] for c in rows[r].values())):
            whole = sum(c[0] for c in rows[label].values())
            print(f"{label:<26}{'all':<7}{ms(whole):>9.3f}"
                  f"{sum(c[1] for c in rows[label].values()) / steps:>13.1f}",
                  file=out)
            no_child = label.endswith("(in no child)")
            for which in PASSES:
                ns, events, ops = rows[label][which]
                if not events and not ns:
                    continue
                largest = sorted(ops.items(), key=lambda kv: -kv[1])
                shown = "; ".join(
                    f"{n} {ms(v):.3f}" + (f" ({how(n)})" if no_child else "")
                    for n, v in largest[:5 if no_child else top])
                print(f"{'':<26}{which:<7}{ms(ns):>9.3f}"
                      f"{events / steps:>13.1f}  {shown}", file=out)
            if label == remainder:
                whose = collections.Counter()
                for cell in rows[label].values():
                    for name, ns in cell[2].items():
                        whose[served.get(name, "no child's")] += ns
                print(f"{'':<26}of it, operations the compiler made (no path "
                      f"of their own) for an operation of: " + "; ".join(
                          f"{k} {ms(v):.3f}" for k, v in whose.most_common()),
                      file=out)

    # the regions' forward beside their recomputed forward, and the mixed
    by_pass = collections.defaultdict(lambda: dict.fromkeys(PASSES, 0.0))
    mixed = collections.defaultdict(collections.Counter)
    mixed_ops = {}
    for (bucket, which), ops in t["ops"].items():
        for name, ns in ops.items():
            is_remat, via, mixes = remat.get(name, (False, "", False))
            by_pass[bucket]["remat" if is_remat else which] += ns
            if mixes:
                mixed[bucket][via == "inner majority"] += ns
                mixed_ops[name] = (ns, bucket, is_remat)
    print(f"\n{'region':<11}" + "".join(f"{p:>10}" for p in PASSES)
          + f"{'mixed':>10}{'of it':>10}   ms per step; mixed: in fusions "
          f"that hold a recomputed forward and other work and go whole to "
          f"one pass; of it: those that hold no product, decided by their "
          f"instructions' majority", file=out)
    for bucket in sorted(by_pass, key=lambda b: -sum(by_pass[b].values())):
        print(f"{bucket:<11}" + "".join(
            f"{ms(by_pass[bucket][p]):>10.3f}" for p in PASSES)
            + f"{ms(sum(mixed[bucket].values())):>10.3f}"
            f"{ms(mixed[bucket][True]):>10.3f}", file=out)
    sums = {p: sum(v[p] for v in by_pass.values()) for p in PASSES}
    print(f"{'sum':<11}" + "".join(f"{ms(sums[p]):>10.3f}" for p in PASSES)
          + f"{ms(sum(sum(m.values()) for m in mixed.values())):>10.3f}"
          f"{ms(sum(m[True] for m in mixed.values())):>10.3f}", file=out)
    print("the mixed fusions with the most time (ms/step, bucket, booked "
          "as):", file=out)
    for name, (ns, bucket, is_remat) in sorted(
            mixed_ops.items(), key=lambda kv: -kv[1][0])[:8]:
        print(f"  {ms(ns):9.3f}  {name:<44} {bucket:<10} "
              f"{'remat' if is_remat else 'not remat'} "
              f"({remat[name][1]})", file=out)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    from benchmarks.harness import regions, runner, xplane
    from paddle_tpu.utils import xprof
    scratch = ROOT / ".bench_trace"
    result = runner.run(ROOT / "BENCHMARK.json", args.workload, args.seed,
                        args.seconds, True, t_start=T_START,
                        scratch=str(scratch), keep_trace=True)
    man = runner.Manifest(ROOT / "BENCHMARK.json")
    cell = man.cell(args.workload)
    config = man.config(cell["config"])
    mix = man.json_of("traffic", cell["traffic"])
    text, step_module = regions.step_text(man, config["model"], mix,
                                          cell["chips"])
    out = open(args.out, "w") if args.out else sys.stdout
    if text is None:
        print("the program's step has no region scopes", file=out)
    else:
        instr_regions = regions.instruction_regions(text)
        ws = xplane.windows(
            xplane.load(str(scratch / f"{args.workload}.{args.seed}")),
            step_module)
        if not ws:
            raise SystemExit(f"the trace holds no run of {step_module!r}")
        slowest = max(ws, key=lambda w: xplane.total(w.busy))
        counts = collections.Counter(
            e.name for e in xplane.leaves(slowest.ops))
        t = regions.table(ws, instr_regions)
        render(t, counts, text, getattr(xprof, "SUBSCOPES", {}),
               man.module("readers", "trace_subscope_ms"),
               man.module("readers", "trace_remat_ms"), instr_regions, out)
        if args.out:    # what a session without a chip can read again
            pathlib.Path(args.out + ".hlo.txt").write_text(text)
            pathlib.Path(args.out + ".table.json").write_text(json.dumps({
                "steps": t["steps"], "busy_ns": t["busy_ns"],
                "coverage": t["coverage"], "counts": counts,
                "ops": {f"{b}|{w}": ops for (b, w), ops in t["ops"].items()}}))
    print(json.dumps(result), file=out)
    if args.out:
        out.close()
        print(json.dumps(result))


if __name__ == "__main__":
    main()
