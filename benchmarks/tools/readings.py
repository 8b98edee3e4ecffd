"""The readings that a cell's limits are set from, many seeds in one process
(set-up is long; the contract allows it):

* the program: the cell's own step, built once, driven from each seed
  through its first steps by the window's own loop, against the reference;
* the control: the reference put in the program's place in the precision
  below the configuration's (fp8 for bfloat16), against the reference;
* the faults a training cell can have, planted in the reference put in the
  program's place: half of the batch left out (the mean over the rest) and,
  across chips, the exchange left out (one chip's rows alone).

    python3 benchmarks/tools/readings.py --workload <cell> --seeds 1,2,3 \
        --control-seeds 1,2,3 --out chiprun_out/readings.<cell>.json

The benchmark's own runs never run this.  PERF.md holds what it read.
"""
import argparse
import gc
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--control", default="fp8")
    ap.add_argument("--out", required=True)
    ap.add_argument("--manifest", default=str(ROOT / "BENCHMARK.json"))
    ap.add_argument("--search", default="")
    ap.add_argument("--no-tpu", action="store_true",
                    help="tests only: run where JAX finds no TPU")
    args = ap.parse_args()

    import jax

    from benchmarks.harness import compare, runner, trafficgen, weights
    from benchmarks.harness.manifest import Manifest

    ints = lambda s: [int(x) for x in s.split(",") if x]  # noqa: E731
    seeds, control_seeds = ints(args.seeds), ints(args.control_seeds)
    man = Manifest(args.manifest, [args.search] if args.search else [])
    cell = man.cell(args.workload)
    config = man.config(cell["config"])
    mix = trafficgen.load(man.find("traffic", f"{cell['traffic']}.json"))
    devices = runner._devices(cell["chips"], not args.no_tpu)
    if not args.no_tpu:
        runner.configure_compile_cache(man.root)
    entry = man.module("entries", config["entry"])
    reference = man.module("references", config["reference"])
    spec = reference.param_spec(config["model"])
    steps = mix["check_steps"]
    rows = config.get("reference_rows_per_block", 8)
    make_whole = weights.maker(spec, runner.whole_sharding(devices))
    out = {"workload": args.workload, "device": devices[0].device_kind,
           "program": {}, "control": {}, "faults": {}, "seconds": {}}

    def ref_readings(seed, pool, **kw):
        return runner.reference_readings(
            reference, config, make_whole(weights.seed_key(seed)),
            pool[:steps], devices, rows_per_block=rows, **kw)

    def record(group, seed, program, ref, names):
        values, worst = compare.numbers(program, ref, names)
        out[group][str(seed)] = {
            **values, "worst_leaf": worst, "losses": program["losses"],
            "leaf_grad_gaps": compare.leaf_gaps(
                program["grad_norms"], ref["grad_norms"]).tolist(),
            "leaf_change_gaps": compare.leaf_gaps(
                program["change_norms"], ref["change_norms"],
                compare.alive_leaves(ref["grad_norms"])).tolist(),
            "leaf_diff_gaps": [
                compare.diff_rel([a], [b]) for a, b in zip(
                    jax.tree_util.tree_leaves(program["first_grad"]),
                    jax.tree_util.tree_leaves(ref["first_grad"]))]}
        out["leaves"] = names
        print(group, seed, json.dumps(values), flush=True)

    names = None
    training = make_params = None
    if seeds:
        training = entry.build(config, mix, devices)
        make_params = weights.maker(
            spec, training.param_shardings(weights.shapes(spec)))
    faults = {"half_batch": 0.5}
    if cell["chips"] > 1:
        faults["no_exchange"] = 1.0 / cell["chips"]
    for seed in seeds + [s for s in control_seeds if s not in seeds]:
        t0 = time.perf_counter()
        pool = trafficgen.make_pool(mix, config["model"], seed)
        key = weights.seed_key(seed)
        program = None
        if seed in seeds:
            training.params = make_params(key)
            training.opt_state = training.init_opt_state(training.params)
            names = names or compare.leaf_names(training.params)
            loop = runner.Loop(training, pool, mix["in_flight"],
                               runner.Spans())
            program = runner.program_readings(loop, training, make_params,
                                              key, steps)
            training.params = training.opt_state = None
            del loop
            gc.collect()
        t1 = time.perf_counter()
        ref = ref_readings(seed, pool, yardstick=True)
        out["seconds"][str(seed)] = {"program": t1 - t0,
                                     "reference": time.perf_counter() - t1}
        names = names or compare.leaf_names(ref["first_grad"])
        if program is not None:
            record("program", seed, program, ref, names)
        del program
        if seed in control_seeds:
            record("control", seed,
                   ref_readings(seed, pool, precision=args.control), ref,
                   names)
            for fault, share in faults.items():
                record("faults", f"{fault}.{seed}",
                       ref_readings(seed, pool, row_share=share), ref, names)
        del ref
        gc.collect()

    def over_seeds(group, pick, prefix=""):
        rows = [v for k, v in out[group].items() if k.startswith(prefix)]
        numbers = sorted({k for v in rows for k, x in v.items()
                          if isinstance(x, float)})
        return {k: pick(v[k] for v in rows if k in v) for k in numbers} \
            if rows else None

    out["summary"] = {"program_max": over_seeds("program", max),
                      "control_min": over_seeds("control", min)}
    for fault in faults:
        out["summary"][f"{fault}_min"] = over_seeds("faults", min, fault + ".")
    pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    pathlib.Path(args.out).write_text(json.dumps(out, indent=1))
    print(json.dumps(out["summary"], indent=1))


if __name__ == "__main__":
    main()
