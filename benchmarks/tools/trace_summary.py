"""Look at one trace by hand: run a cell with `--trace 1`, keep the
profiler's file, and print what it holds: planes, lines, the event names
with the most time on each device line, and the string stats of a few
events.  Read this before changing `harness/xplane.py`.

    python3 benchmarks/tools/trace_summary.py --workload <cell> [--seed n] [--seconds s] [--out file]
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


def summarize(log_dir: str, out):
    from jax.profiler import ProfileData

    from benchmarks.harness import xplane
    data = ProfileData.from_file(xplane.find_xplane(log_dir))
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines", file=out)
        for line in lines:
            events = list(line.events)
            if not events:
                continue
            lo = min(e.start_ns for e in events)
            hi = max(e.start_ns + e.duration_ns for e in events)
            print(f"  LINE {line.name!r}: {len(events)} events, "
                  f"{lo / 1e6:.3f}..{hi / 1e6:.3f} ms", file=out)
            if not (plane.name.startswith("/device:")
                    or any(e.name.startswith("bench.") for e in events[:2000])):
                continue
            by_name = collections.Counter()
            count = collections.Counter()
            sample = {}
            for e in events:
                by_name[e.name] += e.duration_ns
                count[e.name] += 1
                sample.setdefault(e.name, e)
            for name, ns in by_name.most_common(40):
                stats = {k: (v if not isinstance(v, str) else v[:160])
                         for k, v in sample[name].stats}
                print(f"    {ns / 1e6:10.3f} ms x{count[name]:<6} {name[:90]}  "
                      f"{json.dumps(stats, default=str)[:400]}", file=out)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    from benchmarks.harness import runner
    scratch = ROOT / ".bench_trace"
    result = runner.run(ROOT / "BENCHMARK.json", args.workload, args.seed,
                        args.seconds, True, t_start=T_START,
                        scratch=str(scratch), keep_trace=True)
    out = open(args.out, "w") if args.out else sys.stdout
    summarize(str(scratch / f"{args.workload}.{args.seed}"), out)
    print(json.dumps(result), file=out)
    if args.out:
        out.close()
        print(json.dumps(result))


if __name__ == "__main__":
    main()
