"""`lfm2-24b-a2b.s8192`'s step compiled at its real size for a described
`v5e:2x2` topology (no chip: libtpu's compiler is installed here), as
`test_bench_aot_fit_deepseek_v3.py` does for `kanana`'s cell: the step fits
the chip's memory, the three flash kernels with 32 query heads over 8
key/value heads at s = 8192 and the grouped-product kernels are in the
compiled text under their scopes, the convolution mixers' work lies under
`attn/.../conv`, and no dispatch fell back.  A compile is not a chip run.
"""
import json
import re

import pytest
# the topology, cache and dispatch-gate fixtures and the cell's compile are
# that file's: described inside a fixture, never at import time
from test_bench_aot_fit import compile_cell, no_cache, on_tpu, topo  # noqa: F401

# XLA:TPU reports 15.75G usable on a v5e and counts in GiB: `bytes_limit`
# reads 16,909,336,064 on the chip (PR 28), of which it reserves 258 MiB
USABLE_HBM = 15.75 * 2 ** 30 - 258 * 2 ** 20
CELL = "lfm2-24b-a2b.s8192"
TEMP_GB = 5.72     # the compile's temporaries, as PERF.md section 4 records them


def test_the_cell_fits_and_holds_its_kernels(topo, no_cache, on_tpu):
    from paddle_tpu.utils import monitor

    def fallbacks():
        c = monitor.default_registry().get("pallas.fallbacks")
        return sorted((sorted(labels.items()), n) for labels, n in c.samples())

    before = fallbacks()
    compiled, counted = compile_cell(CELL, topo.devices[:1])
    # ISSUE 32's table: dense layer 89,139,200 + attention expert layer
    # 86,118,592 + 3 x 92,416,064 + embedding 16,777,216 + final norm 2,048
    assert counted == 469_285_248
    mem = compiled.memory_analysis()
    temp, args = mem.temp_size_in_bytes / 1e9, mem.argument_size_in_bytes / 1e9
    print(json.dumps({"cell": CELL, "temp_gb": temp, "args_gb": args}))
    assert (temp + args) * 1e9 < USABLE_HBM
    # the figures PERF.md records (PR 32): a drift of more than 5% means
    # the step changed, and the cell's memory lines with it
    assert temp == pytest.approx(TEMP_GB, rel=0.05)
    assert args == pytest.approx(5.63, rel=0.01)
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
    # the convolution mixers' products lie under attn/.../conv, and no
    # expert layer planted a `shared` scope
    paths = set(re.findall(r'op_name="([^"]*)"', text))
    assert any(re.search(r"/attn/(\w+/)*conv/.*dot_general", p)
               for p in paths)
    assert not any(re.search(r"/ffn/(\w+/)*shared/", p) for p in paths)
    assert fallbacks() == before      # no dispatch of this step fell back
