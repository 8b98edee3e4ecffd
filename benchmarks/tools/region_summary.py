"""Where one cell's step spends the device's time, by region: run the cell
with `--trace 1`, keep the profiler's file, join it with the compiled step's
text (`harness/regions.py`) and print, per region and pass, milliseconds and
events per step and the three largest operations with how each was resolved
(its own `op_name`, an instruction inside it, its user); then the unscoped
operations by time, the event names the text lacks, and the region of each
of the ten operations with the most time.  Events per step is also the
per-step count of a kernel's calls (the program's `pallas.kernel_calls`
counts traces).

    python3 benchmarks/tools/region_summary.py --workload <cell> [--seed n] [--seconds s] [--out file]
"""
import argparse
import json
import pathlib
import sys
import time

T_START = time.perf_counter()
ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def render(t: dict, instr_regions: dict, out, top: int = 3) -> None:
    from benchmarks.harness import regions
    steps = t["steps"]
    ms = lambda ns: ns / steps / 1e6  # noqa: E731
    busy = ms(t["busy_ns"])
    print(f"{t['chip']}: {steps} steps, busy {busy:.3f} ms per step, names "
          f"found for {100 * t['coverage']:.3f}% of it", file=out)
    print(f"{'region':<11}{'pass':<5}{'ms/step':>9}{'share':>8}"
          f"{'events/step':>13}  largest operations (ms/step, x per step, "
          f"resolved by)", file=out)
    for bucket in regions.BUCKETS:
        for which in ("fwd", "bwd"):
            key = (bucket, which)
            if key not in t["ns"]:
                continue
            ops = sorted(t["ops"][key].items(), key=lambda kv: -kv[1])[:top]
            shown = "; ".join(
                f"{name} {ms(ns):.3f}"
                f" ({instr_regions.get(name, (None, '', 'not in the text'))[2]})"
                for name, ns in ops)
            print(f"{bucket:<11}{which:<5}{ms(t['ns'][key]):>9.3f}"
                  f"{100 * t['ns'][key] / t['busy_ns']:>7.2f}%"
                  f"{t['events'][key] / steps:>13.1f}  {shown}", file=out)
    total = sum(t["ns"].values())
    print(f"{'sum':<16}{ms(total):>9.3f}{100 * total / t['busy_ns']:>7.2f}%",
          file=out)
    unscoped = sorted(t["ops"].get((regions.UNSCOPED, "fwd"), {}).items(),
                      key=lambda kv: -kv[1])
    print(f"unscoped operations by time ({len(unscoped)} names):", file=out)
    for name, ns in unscoped[:15]:
        print(f"  {ms(ns):9.4f} ms  {name}"
              f"{'' if name in instr_regions else '  (not in the text)'}",
              file=out)
    by_name = {}
    for key, ops in t["ops"].items():
        for name, ns in ops.items():
            by_name[name] = (ns, key)
    print("the ten operations with the most time:", file=out)
    for name, (ns, key) in sorted(by_name.items(),
                                  key=lambda kv: -kv[1][0])[:10]:
        how = instr_regions.get(name, (None, "", "not in the text"))[2]
        print(f"  {ms(ns):9.3f} ms  {name:<48} {key[0]} {key[1]} ({how})",
              file=out)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    from benchmarks.harness import regions, runner, xplane
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
        render(regions.table(ws, instr_regions), instr_regions, out)
    print(json.dumps(result), file=out)
    if args.out:
        out.close()
        print(json.dumps(result))


if __name__ == "__main__":
    main()
