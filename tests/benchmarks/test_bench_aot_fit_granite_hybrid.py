"""`granite-4.0-h-micro.s4096`'s step compiled at its real size for a
described `v5e:2x2` topology (no chip: libtpu's compiler is installed here),
as `test_bench_aot_fit_lfm2_moe.py` does for `lfm2`'s cell: the step — every
block recomputed — fits the chip's memory, the three flash kernels with 32
query heads over 8 key/value heads at s = 4096 are in the compiled text
under `attn/core`, the Mamba-2 mixers' products lie under `attn/.../ssm` and
their scans' under `attn/.../ssm/ssd`, and no dispatch fell back.  A compile
is not a chip run.
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
CELL = "granite-4.0-h-micro.s4096"
TEMP_GB = 4.15     # the compile's temporaries, as PERF.md section 4 records them
ARGS_GB = 9.27     # 12 bytes a parameter: float32 weights and two Adam moments


def test_the_cell_fits_and_holds_its_kernels(topo, no_cache, on_tpu):
    from paddle_tpu.utils import monitor

    def samples(name):          # a counter exists once its module is imported
        c = monitor.default_registry().get(name)
        return sorted((sorted(labels.items()), n)
                      for labels, n in (c.samples() if c else ()))

    fallbacks, scans = samples("pallas.fallbacks"), samples("ssm.scan_calls")
    compiled, counted = compile_cell(CELL, topo.devices[:1])
    # ISSUE 34's table: 9 Mamba-2 layers of 76,182,976 + the attention layer
    # 60,821,504 + embedding 25,690,112 + final norm 2,048
    assert counted == 772_160_448
    mem = compiled.memory_analysis()
    temp, args = mem.temp_size_in_bytes / 1e9, mem.argument_size_in_bytes / 1e9
    print(json.dumps({"cell": CELL, "temp_gb": temp, "args_gb": args}))
    assert (temp + args) * 1e9 < USABLE_HBM
    # the figures PERF.md records (PR 34): a drift of more than 5% means
    # the step changed, and the cell's memory lines with it
    assert temp == pytest.approx(TEMP_GB, rel=0.05)
    assert args == pytest.approx(ARGS_GB, rel=0.01)
    text = compiled.as_text()
    calls = re.findall(r'op_name="([^"]*/pallas_call)"', text)
    assert {c.split("/")[-2] for c in calls} == {
        "flash_fwd", "flash_dkdv", "flash_dq"}
    assert all("/attn/core/" in c for c in calls)
    paths = set(re.findall(r'op_name="([^"]*)"', text))
    ssm = [p for p in paths if re.search(r"/attn/(\w+/)*ssm/", p)]
    # the mixer's own products under ssm and not under ssd; the scan's under
    # ssd; both forward and in the rematerialised backward
    for inside_scan in (False, True):
        products = [p for p in ssm if "dot_general" in p
                    and ("/ssd/" in p) is inside_scan]
        assert any("transpose(" in p for p in products), inside_scan
        assert any("transpose(" not in p for p in products), inside_scan
    assert not [p for p in paths if "/ssd/" in p and "/ssm/" not in p]
    assert any("checkpoint" in p or "rematted_computation" in p for p in ssm)
    assert samples("pallas.fallbacks") == fallbacks   # nothing fell back
    # the scan was traced at the published chunk, by the one implementation
    new = [s for s in samples("ssm.scan_calls") if s not in scans]
    assert [labels for labels, _ in new] == [[("chunk", "256"),
                                              ("impl", "xla")]]
