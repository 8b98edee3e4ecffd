"""The two one-chip cells' steps compiled at their real sizes for a described
`v5e:2x2` topology (no chip: libtpu's compiler is installed here).  Guards,
at no chip time, that each cell still fits the chip's memory and that the
Mosaic kernels are in the compiled step.  A compile is not a chip run: it
says nothing about results or times.

The topology is described inside a fixture, never at import time, and all
such compiles live in this one file (on-chip-measurement guide, section 2).
"""
import json
import os
import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from benchmarks.harness import trafficgen, weights  # noqa: E402
from benchmarks.harness.manifest import Manifest  # noqa: E402

USABLE_HBM = 15.75e9      # what XLA:TPU reports as usable on a v5e (PR 21)
KERNELS = ("flash_packed_fwd", "flash_packed_dkdv", "flash_packed_dq",
           "rdln_fwd", "rdln_bwd", "ln_fwd", "ln_bwd")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def no_cache():
    """Such a compile is written to the persistent cache but cannot be read
    back without a chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


@pytest.fixture
def on_tpu(monkeypatch):
    """Open the program's dispatch gate: it asks `jax.default_backend()`,
    which is the CPU here, so the test steers it (not a new option)."""
    from paddle_tpu.ops.pallas import config as pcfg
    from paddle_tpu.parallel import mesh as mesh_mod
    monkeypatch.setattr(pcfg, "backend_is_tpu", lambda: True)
    yield
    mesh_mod.set_mesh(None)


def compile_cell(cell_name, devices):
    man = Manifest(REPO / "BENCHMARK.json")
    cell = man.cell(cell_name)
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
    rows, seq = trafficgen.global_batch(mix), mix["seq"]
    n_mask = trafficgen.n_masked(mix)
    dims = {"input_ids": (rows, seq), "token_type_ids": (rows, seq),
            "masked_positions": (rows, n_mask), "mlm_labels": (rows, n_mask),
            "nsp_labels": (rows,)}
    batch = {k: jax.ShapeDtypeStruct(d, jnp.int32,
                                     sharding=t.data_shardings[k])
             for k, d in dims.items()}
    key = jax.ShapeDtypeStruct(t.key.shape, t.key.dtype, sharding=whole)
    compiled = t.step.lower(params, state, batch, key).compile()
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    return compiled, n_params


@pytest.mark.parametrize("cell, n_params, temp_gb, args_gb", [
    ("ernie-base.s512", 100_477_778, 12.76, 1.21),
    ("ernie-large.s512", 336_228_156, 9.41, 4.03),
])
def test_one_chip_cell_fits_and_holds_the_kernels(
        topo, no_cache, on_tpu, cell, n_params, temp_gb, args_gb):
    compiled, counted = compile_cell(cell, topo.devices[:1])
    assert counted == n_params
    mem = compiled.memory_analysis()
    temp, args = mem.temp_size_in_bytes / 1e9, mem.argument_size_in_bytes / 1e9
    print(json.dumps({"cell": cell, "temp_gb": temp, "args_gb": args}))
    assert temp + args < USABLE_HBM / 1e9
    # the figures ISSUE 25 / PR 21 recorded: a drift of more than 5% means
    # the step changed, and PERF.md's memory lines with it
    assert temp == pytest.approx(temp_gb, rel=0.05)
    assert args == pytest.approx(args_gb, rel=0.05)
    found = set(re.findall(r"/(\w+)/pallas_call", compiled.as_text()))
    assert set(KERNELS) <= found, sorted(found)
