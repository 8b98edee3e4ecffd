"""`kanana-2-30b-a3b.s4096`'s step compiled at its real size for a described
`v5e:2x2` topology (no chip: libtpu's compiler is installed here), as
`test_bench_aot_fit.py` does for the ERNIE cells: the step fits the chip's
memory, the flash kernels at q·k 192 / v 128 and the grouped-product kernels
are in the compiled text under their scopes, and no dispatch fell back.  A
compile is not a chip run.

A file of its own because the benchmark's files that exist are not this
PR's to edit; under the driver's workers (`ALLOW_MULTIPLE_LIBTPU_LOAD=1`)
both load libtpu, in one plain process they share it, and where a second
process cannot have it the fixture skips.
"""
import json
import re

import jax
import pytest
from jax.sharding import NamedSharding, PartitionSpec
# the topology, cache and dispatch-gate fixtures are that file's: described
# inside a fixture, never at import time
from test_bench_aot_fit import REPO, no_cache, on_tpu, topo  # noqa: F401

from benchmarks.harness import trafficgen, weights
from benchmarks.harness.manifest import Manifest

# XLA:TPU reports 15.75G usable on a v5e and counts in GiB: `bytes_limit`
# reads 16,909,336,064 on the chip (PR 28), of which it reserves 258 MiB
USABLE_HBM = 15.75 * 2 ** 30 - 258 * 2 ** 20
CELL = "kanana-2-30b-a3b.s4096"


def compile_cell(devices):
    man = Manifest(REPO / "BENCHMARK.json")
    cell = man.cell(CELL)
    config = man.config(cell["config"])
    mix = man.json_of("traffic", cell["traffic"])
    t = man.module("entries", config["entry"]).build(config, mix, devices)
    spec = man.module("references", config["reference"]).param_spec(
        config["model"])
    whole = NamedSharding(t.trainer.mesh, PartitionSpec())

    def placed(tree, shardings):
        return jax.tree_util.tree_map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            tree, shardings)

    shapes = weights.shapes(spec)
    params = placed(shapes, t.param_shardings(shapes))
    state = jax.eval_shape(t.init_opt_state, shapes)
    state = placed(state, jax.tree_util.tree_map(lambda _: whole, state))
    pool = trafficgen.make_pool(dict(mix, pool=1), config["model"], 0)
    batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                     sharding=t.data_shardings[k])
             for k, v in pool[0].items()}
    key = jax.ShapeDtypeStruct(t.key.shape, t.key.dtype, sharding=whole)
    compiled = t.step.lower(params, state, batch, key).compile()
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    return compiled, n_params


def test_the_cell_fits_and_holds_its_kernels(topo, no_cache, on_tpu):
    from paddle_tpu.utils import monitor

    def fallbacks():
        c = monitor.default_registry().get("pallas.fallbacks")
        return sorted((sorted(labels.items()), n) for labels, n in c.samples())

    before = fallbacks()
    compiled, counted = compile_cell(topo.devices[:1])
    # 1 dense layer + 4 expert layers of 16 experts + an eighth of the
    # vocabulary twice (ISSUE 28's table: 64.1 + 446.2 + 65.7 M)
    assert counted == 575_955_968
    mem = compiled.memory_analysis()
    temp, args = mem.temp_size_in_bytes / 1e9, mem.argument_size_in_bytes / 1e9
    print(json.dumps({"cell": CELL, "temp_gb": temp, "args_gb": args}))
    assert (temp + args) * 1e9 < USABLE_HBM
    # the figures PERF.md records (PR 28): a drift of more than 5% means
    # the step changed, and the cell's memory lines with it
    assert temp == pytest.approx(9.36, rel=0.05)
    assert args == pytest.approx(6.91, rel=0.01)
    text = compiled.as_text()
    calls = re.findall(r'op_name="([^"]*/pallas_call)"', text)
    kernels = {c.split("/")[-2] for c in calls}
    assert {"flash_fwd", "flash_dkdv", "flash_dq", "jit(gmm)",
            "jit(tgmm)"} <= kernels, sorted(kernels)
    # every kernel lies under its region's scope, the grouped products
    # under ffn/.../experts: nothing for XLA's own ragged-dot to take
    assert all("/attn/core/" in c for c in calls if "flash_" in c)
    assert all(re.search(r"/ffn/(\w+/)*experts/", c) for c in calls
               if "gmm)" in c)
    assert "ragged-dot" not in text
    assert fallbacks() == before      # no dispatch of this step fell back
