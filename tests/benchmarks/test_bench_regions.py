"""Device time by region (`harness/regions.py`): synthetic HLO text joined
with a synthetic trace built from `xplane`'s own tuples, the two readers,
and the tiny cell's step rebuilt and mapped on the CPU through a fixture
directory of its own (`fixtures_regions/`, found through `search`)."""
import io
import pathlib

import pytest
from bench_testlib import FIXTURES, Manifest

from benchmarks.harness import regions, runner, xplane

HERE = pathlib.Path(__file__).resolve().parent / "fixtures_regions"
STEP = "jit(train_step)"
FWD, BWD = f"{STEP}/jvp(encoder)/while/body", \
    f"{STEP}/transpose(jvp(encoder))/while/body"

# An entry with a forward and a backward scan.  In the forward body: a
# q-projection fusion (own op_name), the flash kernel, a fusion rooted in
# the scan's dynamic-update-slice that holds the FFN's dot (jax 0.9 text: no
# shapes on the operands), a fusion of scan bookkeeping alone, a prefetch
# that the compiler made for the FFN fusion (no metadata), and an
# all-reduce under `ffn`.  Outside: embeddings, head, loss, Adam, and one
# operation in no scope.
TEXT = f"""HloModule jit_train_step, is_scheduled=true

%fused_q (p0: bf16[8,8]) -> bf16[8,8] {{
  %p0 = bf16[8,8]{{1,0}} parameter(0)
  ROOT %dot.1 = bf16[8,8]{{1,0}} dot(%p0, %p0), lhs_contracting_dims={{1}}, rhs_contracting_dims={{0}}, metadata={{op_name="{FWD}/closed_call/0/attn/self_attn/q_proj/dot_general"}}
}}

%fused_dus (p0: bf16[2,8,8], p1: bf16[8,8], p2: s32[]) -> bf16[2,8,8] {{
  %p0 = bf16[2,8,8]{{2,1,0}} parameter(0)
  %p1 = bf16[8,8]{{1,0}} parameter(1)
  %p2 = s32[] parameter(2)
  %convolution.7 = bf16[8,8]{{1,0}} convolution(%p1, %p1), dim_labels=bf_io->bf, metadata={{op_name="{FWD}/closed_call/0/ffn/linear1/dot_general"}}
  %bitcast.3 = bf16[1,8,8]{{2,1,0}} bitcast(%convolution.7), metadata={{op_name="{FWD}/closed_call/0/ln/norm2/div"}}
  %mul.3 = bf16[1,8,8]{{2,1,0}} multiply(%bitcast.3, %bitcast.3), metadata={{op_name="{FWD}/closed_call/0/ln/norm2/mul"}}
  ROOT %dus.1 = bf16[2,8,8]{{2,1,0}} dynamic-update-slice(%p0, %mul.3, %p2, %p2, %p2), metadata={{op_name="{FWD}/dynamic_update_slice"}}
}}

%fused_majority (p0: bf16[8,8]) -> bf16[8,8] {{
  %p0 = bf16[8,8]{{1,0}} parameter(0)
  %add.5 = bf16[8,8]{{1,0}} add(%p0, %p0), metadata={{op_name="{BWD}/closed_call/0/ln/norm1/add"}}
  %mul.5 = bf16[8,8]{{1,0}} multiply(%add.5, %p0), metadata={{op_name="{BWD}/closed_call/0/ln/norm1/mul"}}
  ROOT %neg.5 = bf16[8,8]{{1,0}} negate(%mul.5), metadata={{op_name="{BWD}/closed_call/0/ffn/neg"}}
}}

%fused_slice (p0: bf16[2,8,8], p1: s32[]) -> bf16[8,8] {{
  %p0 = bf16[2,8,8]{{2,1,0}} parameter(0)
  %p1 = s32[] parameter(1)
  ROOT %ds.1 = bf16[8,8]{{1,0}} dynamic-slice(%p0, %p1, %p1, %p1), dynamic_slice_sizes={{1,8,8}}, metadata={{op_name="{BWD}/dynamic_slice"}}
}}

%body_fwd (t: (s32[], bf16[8,8], bf16[2,8,8])) -> (s32[], bf16[8,8], bf16[2,8,8]) {{
  %t = (s32[], bf16[8,8]{{1,0}}, bf16[2,8,8]{{2,1,0}}) parameter(0)
  %i = s32[] get-tuple-element(%t), index=0
  %h = bf16[8,8]{{1,0}} get-tuple-element(%t), index=1
  %stack = bf16[2,8,8]{{2,1,0}} get-tuple-element(%t), index=2
  %fusion.1 = bf16[8,8]{{1,0}} fusion(%h), kind=kOutput, calls=%fused_q, metadata={{op_name="{FWD}/closed_call/0/attn/self_attn/q_proj/dot_general"}}
  %flash_packed_fwd.3 = bf16[8,8]{{1,0}} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={{op_name="{FWD}/closed_call/0/attn/self_attn/attn/core/pallas.flash_attention_packed/flash_packed_fwd/pallas_call"}}
  %copy-start.2 = (bf16[8,8]{{1,0}}, bf16[8,8]{{1,0}}, u32[]) copy-start(%flash_packed_fwd.3)
  %copy-done.2 = bf16[8,8]{{1,0}} copy-done(%copy-start.2)
  %bitcast_dynamic-update-slice_fusion.41 = bf16[2,8,8]{{2,1,0}} fusion(%stack, %copy-done.2, %i), kind=kOutput, calls=%fused_dus, metadata={{op_name="{FWD}/dynamic_update_slice"}}
  %all-reduce.4 = bf16[8,8]{{1,0}} all-reduce(%copy-done.2), replica_groups={{}}, to_apply=%add_comp, metadata={{op_name="{FWD}/closed_call/0/ffn/linear2/dot_general"}}
  ROOT %tuple.1 = (s32[], bf16[8,8]{{1,0}}, bf16[2,8,8]{{2,1,0}}) tuple(%i, %all-reduce.4, %bitcast_dynamic-update-slice_fusion.41)
}}

%body_bwd (t: (s32[], bf16[8,8], bf16[2,8,8])) -> (s32[], bf16[8,8], bf16[2,8,8]) {{
  %t = (s32[], bf16[8,8]{{1,0}}, bf16[2,8,8]{{2,1,0}}) parameter(0)
  %i = s32[] get-tuple-element(%t), index=0
  %stack = bf16[2,8,8]{{2,1,0}} get-tuple-element(%t), index=2
  %dynamic-slice_fusion.2 = bf16[8,8]{{1,0}} fusion(%stack, %i), kind=kLoop, calls=%fused_slice, metadata={{op_name="{BWD}/dynamic_slice"}}
  %fusion.9 = bf16[8,8]{{1,0}} fusion(%dynamic-slice_fusion.2), kind=kLoop, calls=%fused_majority, metadata={{op_name="{BWD}/closed_call"}}
  ROOT %tuple.2 = (s32[], bf16[8,8]{{1,0}}, bf16[2,8,8]{{2,1,0}}) tuple(%i, %fusion.9, %stack)
}}

ENTRY %main.1 (ids: s32[8], w: f32[8,8]) -> f32[8,8] {{
  %ids = s32[8]{{0}} parameter(0), metadata={{op_name="batch['input_ids']"}}
  %w = f32[8,8]{{1,0}} parameter(1), metadata={{op_name="params['w']"}}
  %gather.1 = bf16[8,8]{{1,0}} gather(%w, %ids), metadata={{op_name="{STEP}/jvp(embed)/word_embeddings/gather"}}
  %while.4 = (s32[], bf16[8,8]{{1,0}}, bf16[2,8,8]{{2,1,0}}) while(%tuple.0), condition=%cond, body=%body_fwd, metadata={{op_name="{STEP}/jvp(encoder)/while"}}
  %fusion.20 = f32[8,8]{{1,0}} fusion(%gather.1), kind=kLoop, calls=%fused_q, metadata={{op_name="{STEP}/jvp(head)/cls/predictions/transform/dot_general"}}
  %reduce.1 = f32[] reduce(%fusion.20, %w), dimensions={{0,1}}, to_apply=%add_comp, metadata={{op_name="{STEP}/jvp(loss)/reduce_sum"}}
  %while.5 = (s32[], bf16[8,8]{{1,0}}, bf16[2,8,8]{{2,1,0}}) while(%tuple.0), condition=%cond, body=%body_bwd, metadata={{op_name="{STEP}/transpose(jvp(encoder))/while"}}
  %copy.9 = f32[8,8]{{1,0}} copy(%w), metadata={{op_name="{STEP}/convert_element_type"}}
  ROOT %subtract_fusion.1 = f32[8,8]{{1,0}} fusion(%w, %copy.9), kind=kLoop, calls=%fused_q, metadata={{op_name="{STEP}/optimizer/sub"}}
}}
"""

# (name, start us, end us) on the `XLA Ops` line of one step, 0..1000 us
OPS = [("gather.1", 0, 50),
       ("while.4", 50, 500),
       ("fusion.1", 50, 100), ("flash_packed_fwd.3", 100, 200),
       ("copy-done.2", 200, 210),
       ("bitcast_dynamic-update-slice_fusion.41", 210, 400),
       ("all-reduce.4", 400, 450),
       ("fusion.20", 500, 550), ("reduce.1", 550, 560),
       ("while.5", 560, 800),
       ("dynamic-slice_fusion.2", 560, 600), ("fusion.9", 600, 800),
       ("copy.9", 800, 820), ("subtract_fusion.1", 820, 1000)]


def window(ops=OPS, steps=1, name="/device:TPU:0"):
    events = [xplane.Event(n, lo * 1e3, hi * 1e3) for n, lo, hi in ops]
    hi = max(e.end for e in events)
    mods = [xplane.Event("jit_train_step(1)", i * hi / steps,
                         (i + 1) * hi / steps) for i in range(steps)]
    return xplane.device_window(xplane.DeviceTrace(name, events, mods),
                                "jit_train_step")


@pytest.fixture(scope="module")
def mapped():
    return regions.instruction_regions(TEXT)


def test_region_and_pass_of_forward_backward_and_recomputed_paths():
    r = regions.region_of
    assert r(f"{FWD}/closed_call/0/ffn/linear1/dot_general") == ("ffn", "fwd")
    assert r(f"{BWD}/closed_call/0/ffn/linear1/transpose") == ("ffn", "bwd")
    assert r(f"{BWD}/closed_call/checkpoint/rematted_computation/ln/add") \
        == ("ln", "bwd")
    assert r(f"{FWD}/closed_call/0/attn/self_attn/attn/core/pallas.x/call") \
        == ("attn/core", "fwd")
    assert r(f"{FWD}/closed_call/0/attn/self_attn/q_proj/core") == \
        ("attn", "fwd")                     # `core` counts right under `attn`
    assert r(f"{FWD}/dynamic_update_slice") == ("encoder", "fwd")
    assert r(f"{STEP}/optimizer/sub") == ("optimizer", "fwd")
    assert r("jit(head)/convert_element_type") == (None, "fwd")  # a jit's name
    assert r("params['w']") == (None, "fwd")


def test_a_fusion_rooted_in_the_scans_update_takes_its_inner_products_region(
        mapped):
    assert mapped["bitcast_dynamic-update-slice_fusion.41"] == \
        ("ffn", "fwd", "inner product")      # not `ln`, its most frequent
    assert mapped["fusion.9"] == ("ln", "bwd", "inner majority")
    assert mapped["dynamic-slice_fusion.2"] == ("encoder", "bwd", "own")
    assert mapped["fusion.1"] == ("attn", "fwd", "own")
    assert mapped["flash_packed_fwd.3"] == ("attn/core", "fwd", "own")
    # the compiler's prefetch has no metadata: its user's region
    assert mapped["copy-done.2"] == ("ffn", "fwd", "user")
    assert mapped["copy-start.2"] == ("ffn", "fwd", "user")
    assert mapped["copy.9"] == (None, "fwd", "own")


def test_every_leaf_lands_in_one_bucket_and_the_buckets_sum_to_busy_time(
        mapped):
    t = regions.table([window()], mapped)
    assert t["coverage"] == 1.0 and t["steps"] == 1
    assert sum(t["ns"].values()) == pytest.approx(t["busy_ns"]) == 950_000
    assert sum(t["events"].values()) == len(OPS) - 2    # the whiles are not
    assert set(b for b, _ in t["ns"]) <= set(regions.BUCKETS)
    ms = lambda *b: regions.ms_per_step(t, b)  # noqa: E731
    assert ms("attn") == pytest.approx(0.050)
    assert ms("attn/core") == pytest.approx(0.100)
    assert ms("ffn") == pytest.approx(0.010 + 0.190)
    assert ms("ln") == pytest.approx(0.200)
    assert ms("scan") == pytest.approx(0.040)
    assert ms("embed", "head", "loss") == pytest.approx(0.050 + 0.050 + 0.010)
    assert ms("optimizer") == pytest.approx(0.180)
    assert ms("unscoped") == pytest.approx(0.020)
    assert t["ns"][("ln", "bwd")] == 200_000 and ("ln", "fwd") not in t["ns"]


def test_a_collective_is_collective_whatever_its_scope(mapped):
    assert mapped["all-reduce.4"][0] == "ffn"
    t = regions.table([window()], mapped)
    assert regions.ms_per_step(t, ["collective"]) == pytest.approx(0.050)
    assert t["ops"][("collective", "fwd")] == {"all-reduce.4": 50_000}
    assert "all-reduce.4" not in t["ops"][("ffn", "fwd")]


def test_the_slowest_chip_is_read_and_steps_divide(mapped):
    twice = OPS + [(n, lo + 1000, hi + 1000) for n, lo, hi in OPS]
    slow = [(n, lo, hi + (100 if n == "subtract_fusion.1" else 0))
            for n, lo, hi in OPS]
    t = regions.table([window(twice, steps=2), window(slow, name="/device:TPU:1")],
                      mapped)
    assert t["chip"] == "/device:TPU:0" and t["steps"] == 2
    assert regions.ms_per_step(t, ["optimizer"]) == pytest.approx(0.180)
    t = regions.table([window(), window(slow, name="/device:TPU:1")], mapped)
    assert t["chip"] == "/device:TPU:1"
    assert regions.ms_per_step(t, ["optimizer"]) == pytest.approx(0.280)


def ctx_with(ws, man, peaks=None):
    return {"trace": ws, "manifest": man, "model": {"hidden_size": 8,
            "num_hidden_layers": 2}, "mix": {"batch_per_chip": 8, "seq": 8},
            "chips": 1, "peaks": peaks}


@pytest.fixture
def man():
    return Manifest(HERE / "BENCHMARK.json", [HERE, FIXTURES])


def read(man, metric, ctx):
    how = man.json_of("layer_metrics", metric)
    return man.module("readers", how["reader"]).read(ctx, how.get("params", {}))


def test_a_name_the_text_lacks_lowers_coverage_and_under_99_nothing_is_read(
        mapped, man, monkeypatch, capsys):
    monkeypatch.setattr(regions, "step_text", lambda *a, **k: (TEXT, "m"))
    renamed = [("fusion.77" if n == "fusion.9" else n, lo, hi)
               for n, lo, hi in OPS]
    t = regions.table([window(renamed)], mapped)
    assert t["coverage"] == pytest.approx(750 / 950)
    assert t["missing"] == {"fusion.77": 200_000}
    assert regions.ms_per_step(t, ["unscoped"]) == pytest.approx(0.220)
    ctx = ctx_with([window(renamed)], man)
    for metric in ("step.ffn_ms", "step.unscoped_share", "attn.core.roofline"):
        assert read(man, metric, ctx) is None       # never 0
    assert "under 99%" in capsys.readouterr().err
    # half a percent missing is read, and counts as unscoped
    small = OPS + [("copy.1234", 1000, 1005)]
    ctx = ctx_with([window(small)], man)
    assert read(man, "step.unscoped_share", ctx) == \
        pytest.approx(100 * 25 / 955)
    assert read(man, "step.attn_ms", ctx) == pytest.approx(0.150)
    assert read(man, "step.head_ms", ctx) == pytest.approx(0.110)


def test_readers_give_nothing_without_a_trace_or_without_scopes(
        man, monkeypatch):
    calls = []
    monkeypatch.setattr(regions, "step_text",
                        lambda *a, **k: calls.append(1) or (None, "m"))
    assert read(man, "step.ffn_ms", ctx_with(None, man)) is None
    assert not calls                      # no trace: nothing is rebuilt
    ctx = ctx_with([window()], man)
    assert read(man, "step.ffn_ms", ctx) is None    # a program without scopes
    assert read(man, "step.scan_ms", ctx) is None
    assert len(calls) == 1                # asked once for all the metrics
    assert regions.has_scopes(TEXT)
    assert not regions.has_scopes(TEXT.replace("(encoder)", "(enc)").replace(
        "/ffn/", "/f/").replace("/ln/", "/l/").replace("/attn/", "/a/")
        .replace("(embed)", "(e)").replace("(head)", "(h)")
        .replace("(loss)", "(lo)").replace("/optimizer/", "/o/"))


def test_the_roofline_reader_divides_the_cost_by_the_regions_time(
        mapped, man, monkeypatch):
    monkeypatch.setattr(regions, "step_text", lambda *a, **k: (TEXT, "m"))
    peaks = {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e12}
    ctx = ctx_with([window()], man, peaks)
    # ernie:flash_attention_train: 12 * b * s^2 * H * L operations
    least_ms = 12.0 * 8 * 8 * 8 * 8 * 2 / 1e9 * 1e3
    assert read(man, "attn.core.roofline", ctx) == \
        pytest.approx(100 * least_ms / 0.100)
    assert read(man, "attn.core.roofline", ctx_with([window()], man)) is None
    no_core = [o for o in OPS if o[0] != "flash_packed_fwd.3"]
    assert read(man, "attn.core.roofline",
                ctx_with([window(no_core)], man, peaks)) is None
    assert read(man, "step.attn_ms", ctx_with([window(no_core)], man)) == \
        pytest.approx(0.050)


def test_the_tiny_cells_step_is_rebuilt_and_mapped_on_the_cpu(man):
    cell = man.cell("tiny.s128")
    config = man.config(cell["config"])
    mix = man.json_of("traffic", cell["traffic"])
    err = io.StringIO()
    text, module = regions.step_text(man, config["model"], mix, 1, err)
    assert module == "jit_train_step" and "rebuilt" in err.getvalue()
    mapped = regions.instruction_regions(text)
    found = {r for r, _, _ in mapped.values()}
    assert set(regions.SCOPES) <= found
    passes = {(r, w) for r, w, _ in mapped.values()}
    for region in ("attn", "attn/core", "ffn", "ln", "encoder", "embed",
                   "head", "loss"):
        assert {(region, "fwd"), (region, "bwd")} <= passes, region
    # a trace that runs every top-level fusion of the text once is covered
    fusions = [n for n, (r, _, _) in mapped.items() if "fusion" in n][:50]
    ops = [(n, 10 * i, 10 * i + 10) for i, n in enumerate(fusions)]
    t = regions.table([window(ops)], mapped)
    assert t["coverage"] == 1.0
    assert regions.step_text(man, config["model"], mix, 1)[0] is text  # once


def test_a_traced_tiny_run_reports_no_region_metric_off_the_chip(tmp_path):
    err = io.StringIO()
    result = runner.run(HERE / "BENCHMARK.json", "tiny.s128", 7, 0.2, True,
                        search=[HERE, FIXTURES], require_tpu=False,
                        compile_cache=False, scratch=str(tmp_path), err=err)
    assert result["correct"] is True
    assert set(result["metrics"]) == {"loop.dispatch_ms"}
