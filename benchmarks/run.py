"""The benchmark's command:

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine that holds the chips the cell
asks for.  Prints one JSON object as the last line of standard output; exits
non-zero, with no result, where JAX finds no TPU (or too few chips) or the
program is not there.  See PERF.md for what each part measures.
"""
import time

T_START = time.perf_counter()   # set-up is counted from here

import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(ROOT))   # the program under test: `import paddle_tpu`


def main() -> int:
    try:
        import paddle_tpu  # noqa: F401  (fail here, not half-way, without it)
    except ImportError as e:
        print(f"benchmark: the program is not in this checkout: {e}",
              file=sys.stderr)
        return 4
    from benchmarks.harness import runner
    return runner.main(sys.argv[1:], T_START, ROOT / "BENCHMARK.json")


if __name__ == "__main__":
    sys.exit(main())
