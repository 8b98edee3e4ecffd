"""`olmo-hybrid-7b.s4096`'s step compiled at its real size for a described
`v5e:2x2` topology (no chip: libtpu's compiler is installed here), as
`test_bench_aot_fit_granite_hybrid.py` does for `granite`'s cell: the step —
every block recomputed, one group a layer — fits the chip's memory, the
three flash kernels with 30 heads of 128 at s = 4096 are in the compiled
text under `attn/core`, the Gated DeltaNet mixers' products lie under
`attn/.../gdn` and their rule's under `attn/.../gdn/delta`, and no dispatch
fell back.  A compile is not a chip run.
"""
import json
import re

import pytest
# the topology, cache and dispatch-gate fixtures and the cell's compile are
# that file's: described inside a fixture, never at import time
from test_bench_aot_fit import compile_cell, no_cache, on_tpu, topo  # noqa: F401

# XLA:TPU reports 15.75G usable on a v5e and counts in GiB: `bytes_limit`
# reads 16,909,336,064 on the chip, of which it reserves 258 MiB
USABLE_HBM = 15.75 * 2 ** 30 - 258 * 2 ** 20
CELL = "olmo-hybrid-7b.s4096"
TEMP_GB = 5.09     # the compile's temporaries, as PERF.md section 4 records them
ARGS_GB = 11.15    # 12 bytes a parameter: float32 weights and two Adam moments


def test_the_cell_fits_and_holds_its_kernels(topo, no_cache, on_tpu):
    from paddle_tpu.utils import monitor

    def samples(name):          # a counter exists once its module is imported
        c = monitor.default_registry().get(name)
        return sorted((sorted(labels.items()), n)
                      for labels, n in (c.samples() if c else ()))

    fallbacks, rules = samples("pallas.fallbacks"), samples("gdn.delta_calls")
    compiled, counted = compile_cell(CELL, topo.devices[:1])
    # the published count: 3 Gated DeltaNet layers of 215,570,172 + the
    # attention layer 185,809,920 + embedding and head 96,337,920 + the
    # final norm 3,840
    assert counted == 928_862_196
    mem = compiled.memory_analysis()
    temp, args = mem.temp_size_in_bytes / 1e9, mem.argument_size_in_bytes / 1e9
    print(json.dumps({"cell": CELL, "temp_gb": temp, "args_gb": args}))
    # a margin of 0.3 GB under what the chip can hold
    assert (temp + args) * 1e9 < USABLE_HBM - 0.3e9
    # the figures PERF.md records: a drift of more than 5% means
    # the step changed, and the cell's memory lines with it
    assert temp == pytest.approx(TEMP_GB, rel=0.05)
    assert args == pytest.approx(ARGS_GB, rel=0.01)
    text = compiled.as_text()
    calls = re.findall(r'op_name="([^"]*/pallas_call)"', text)
    assert {c.split("/")[-2] for c in calls} == {
        "flash_fwd", "flash_dkdv", "flash_dq"}
    assert all("/attn/core/" in c for c in calls)
    paths = set(re.findall(r'op_name="([^"]*)"', text))
    gdn = [p for p in paths if re.search(r"/attn/(\w+/)*gdn/", p)]
    # the mixer's own products under gdn and not under delta; the rule's
    # under delta; both forward and in the backward
    for inside_rule in (False, True):
        products = [p for p in gdn if "dot_general" in p
                    and ("/delta/" in p) is inside_rule]
        assert any("transpose(" in p for p in products), inside_rule
        assert any("transpose(" not in p for p in products), inside_rule
    assert any(re.search(r"/gdn/(\w+/)*delta/", p) for p in gdn)
    assert not [p for p in paths if "/delta/" in p and "/gdn/" not in p]
    assert any("checkpoint" in p or "rematted_computation" in p for p in gdn)
    assert samples("pallas.fallbacks") == fallbacks   # nothing fell back
    # the rule was traced at the configuration's chunk, forward and backward
    new = [s for s in samples("gdn.delta_calls") if s not in rules]
    assert sorted(labels for labels, _ in new) == [
        [("chunk", "64"), ("pass", "bwd")], [("chunk", "64"), ("pass", "fwd")]]
