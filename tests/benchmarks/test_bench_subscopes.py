"""The second level of the step's scopes as the benchmark reads it, on
synthetic HLO text joined with a synthetic trace (as `test_bench_regions.py`
does for the regions): the children of `attn` and of `ffn/experts` add up to
their parent's bucket through the metric files this PR added; the recomputed
forward (`readers/trace_remat_ms.py`) by own path, by inner product and by
majority; nothing without a table, without a recomputed operation or under
99% coverage; `tools/subscope_summary.py`'s table."""
import io

import pytest
from bench_testlib import REPO, Manifest

from benchmarks.harness import regions, xplane

STEP = "jit(train_step)"
FWD = f"{STEP}/jvp(encoder)/while/body/closed_call"
BWD = f"{STEP}/transpose(jvp(encoder))/while/body/closed_call/checkpoint"
REMAT = f"{BWD}/rematted_computation"


def op(name, path, opcode="multiply", operand="%x"):
    return (f'  %{name} = bf16[8,8]{{1,0}} {opcode}({operand}, {operand}), '
            f'metadata={{op_name="{path}"}}\n')


def fusion(name, calls, path, operand="%x"):
    return (f'  %{name} = bf16[8,8]{{1,0}} fusion({operand}), kind=kLoop, '
            f'calls=%{calls}, metadata={{op_name="{path}"}}\n')


def computation(name, body):
    return (f"%{name} (p0: bf16[8,8]) -> bf16[8,8] {{\n"
            f"  %p0.{name} = bf16[8,8]{{1,0}} parameter(0)\n{body}}}\n\n")


# One attention layer, one state-space mixer, one expert layer: forward,
# recomputed forward and backward.  Fusions: a backward fusion that holds a
# recomputed product (XLA fuses the recomputed product with the pass that
# reads it), one that holds mostly recomputed elementwise work, one that
# holds mostly backward work, one of scan bookkeeping around a `proj`.
TEXT = "HloModule jit_train_step, is_scheduled=true\n\n" + computation(
    "fused_remat_product",
    op("dot.31", f"{REMAT}/ffn/mlp/experts/products/dot_general", "dot",
       "%p0.fused_remat_product")
    + op("mul.31", f"{BWD}/ffn/mlp/experts/gated/mul")
    + "  ROOT " + op("mul.32", f"{BWD}/ffn/mlp/experts/gated/mul").lstrip()
) + computation(
    "fused_remat_majority",
    op("exp.41", f"{REMAT}/attn/mixer/ssm/pointwise/exp")
    + op("mul.41", f"{REMAT}/attn/mixer/ssm/pointwise/mul")
    + "  ROOT " + op("mul.42", f"{BWD}/attn/mixer/ssm/pointwise/mul").lstrip()
) + computation(
    "fused_bwd_majority",
    op("exp.51", f"{REMAT}/attn/mixer/ssm/pointwise/exp")
    + op("mul.51", f"{BWD}/attn/mixer/ssm/pointwise/mul")
    + "  ROOT " + op("mul.52", f"{BWD}/attn/mixer/ssm/pointwise/mul").lstrip()
) + computation(
    "fused_stack",
    op("dot.61", f"{FWD}/attn/mixer/ssm/proj/in_proj/dot_general", "dot",
       "%p0.fused_stack")
    + "  ROOT " + op("dus.61", f"{STEP}/jvp(encoder)/while/body/"
                     "dynamic_update_slice").lstrip()
) + "ENTRY %main.1 (x: bf16[8,8]) -> bf16[8,8] {\n" \
    "  %x = bf16[8,8]{1,0} parameter(0)\n" + "".join([
        # attention: proj, prep, core
        op("q.1", f"{FWD}/attn/self_attn/proj/q_proj/dot_general", "dot"),
        op("rope.1", f"{FWD}/attn/self_attn/prep/mul"),
        op("flash.1", f"{FWD}/attn/self_attn/attn/core/pallas_call",
           "custom-call"),
        op("merge.1", f"{FWD}/attn/self_attn/prep/transpose", "transpose"),
        op("q.2", f"{REMAT}/attn/self_attn/proj/q_proj/dot_general", "dot"),
        op("q.3", f"{BWD}/attn/self_attn/proj/q_proj/transpose", "dot"),
        op("rope.3", f"{BWD}/attn/self_attn/prep/mul"),
        # a state-space mixer: proj (inside a fusion rooted in the scan's
        # stacking), pointwise behind a layout copy that the compiler made
        # and gave no path, the scan, and a stray directly under `ssm`
        fusion("stack.1", "fused_stack",
               f"{STEP}/jvp(encoder)/while/body/dynamic_update_slice"),
        "  %copy.9 = bf16[8,8]{1,0} copy(%x)\n",    # the compiler's own
        op("silu.1", f"{FWD}/attn/mixer/ssm/pointwise/logistic",
           operand="%copy.9"),
        op("scan.1", f"{FWD}/attn/mixer/ssm/ssd/dot_general", "dot"),
        op("stray.1", f"{FWD}/attn/mixer/ssm/reshape"),
        fusion("norm.2", "fused_remat_majority",
               f"{BWD}/attn/mixer/ssm/pointwise/mul"),
        fusion("norm.3", "fused_bwd_majority",
               f"{BWD}/attn/mixer/ssm/pointwise/mul"),
        # the expert layer: the sort directly under `experts`, four passes
        op("route.1", f"{FWD}/ffn/mlp/router/dot_general", "dot"),
        op("sort.1", f"{FWD}/ffn/mlp/experts/checkpoint/sort", "sort"),
        op("rows.1", f"{FWD}/ffn/mlp/experts/checkpoint/dispatch/gather",
           "gather"),
        op("gmm.1", f"{FWD}/ffn/mlp/experts/checkpoint/products/"
           "pallas.grouped_matmul/jit(gmm)/pallas_call", "custom-call"),
        op("act.1", f"{FWD}/ffn/mlp/experts/checkpoint/gated/while/body/mul"),
        op("sum.1", f"{FWD}/ffn/mlp/experts/checkpoint/combine/add"),
        # recomputed inside a recomputed block: the mark twice
        op("gmm.2", f"{REMAT}/ffn/mlp/experts/checkpoint/"
           "rematted_computation/products/pallas.grouped_matmul/jit(gmm)/"
           "pallas_call", "custom-call"),
        fusion("act.3", "fused_remat_product",
               f"{BWD}/ffn/mlp/experts/gated/mul"),
        op("tgmm.3", f"{BWD}/ffn/mlp/experts/checkpoint/products/"
           "pallas.grouped_matmul/jit(tgmm)/pallas_call", "custom-call"),
        op("back.3", f"{BWD}/ffn/mlp/experts/checkpoint/combine/while/body/"
           "mul"),
        op("dx.3", f"{BWD}/ffn/mlp/experts/checkpoint/dispatch/add_any"),
        op("dense.1", f"{FWD}/ffn/mlp/gate_up/dot_general", "dot"),
    ]) + "  ROOT " + op("adam.1", f"{STEP}/optimizer/sub").lstrip() + "}\n"

# microseconds of each operation in the one traced step, in text order
US = {"q.1": 40, "rope.1": 10, "flash.1": 100, "merge.1": 5, "q.2": 40,
      "q.3": 80, "rope.3": 15,
      "stack.1": 60, "copy.9": 4, "silu.1": 20, "scan.1": 30, "stray.1": 2,
      "norm.2": 12, "norm.3": 18,
      "route.1": 7, "sort.1": 3, "rows.1": 9, "gmm.1": 50, "act.1": 8,
      "sum.1": 6, "gmm.2": 50, "act.3": 70, "tgmm.3": 90, "back.3": 11,
      "dx.3": 13, "dense.1": 25, "adam.1": 30}
ATTN = ["conv", "ssm", "ssd", "proj", "prep", "pointwise"]
FFN = ["router", "experts", "shared", "dispatch", "products", "gated",
       "combine"]
NEW = {"step.attn_proj_ms": ("attn", "proj"),
       "step.attn_prep_ms": ("attn", "prep"),
       "step.mixer_pointwise_ms": ("attn", "pointwise"),
       "step.experts_dispatch_ms": ("ffn", "dispatch"),
       "step.experts_products_ms": ("ffn", "products"),
       "step.experts_gated_ms": ("ffn", "gated"),
       "step.experts_combine_ms": ("ffn", "combine")}


def window(us=US, rename=None):
    events, at = [], 0.0
    for name, width in us.items():
        events.append(xplane.Event((rename or {}).get(name, name), at * 1e3,
                                   (at + width) * 1e3))
        at += width
    mods = [xplane.Event("jit_train_step(1)", 0.0, at * 1e3)]
    return xplane.device_window(
        xplane.DeviceTrace("/device:TPU:0", events, mods), "jit_train_step")


@pytest.fixture
def man():
    return Manifest(REPO / "BENCHMARK.json")


@pytest.fixture
def ctx(man, monkeypatch):
    monkeypatch.setattr(regions, "step_text", lambda *a, **k: (TEXT, "m"))
    return {"trace": [window()], "manifest": man, "model": {}, "mix": {},
            "chips": 1, "peaks": None}


def read(man, metric, ctx):
    how = man.json_of("layer_metrics", metric)
    return man.module("readers", how["reader"]).read(ctx,
                                                     how.get("params", {}))


def remat_reader(man):
    return man.module("readers", "trace_remat_ms")


# ---------------------------------------------------------------------------
# the children through the metric files
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("metric", sorted(NEW) + ["step.remat_ms"])
def test_the_metric_is_in_the_manifest_with_one_subs_list_a_region(
        man, metric):
    entry = next(m for m in man.data["per_layer"] if m["name"] == metric)
    assert (entry["unit"], entry["better"], entry["source"], entry["layer"],
            entry["moves"]) == ("ms", "lower", "device_trace", "step_builder",
                                "items_per_s_per_chip")
    how = man.json_of("layer_metrics", metric)
    if metric == "step.remat_ms":
        assert how["reader"] == "trace_remat_ms" and how["params"] == {}
        return
    region, sub = NEW[metric]
    assert how["reader"] == "trace_subscope_ms"     # data only
    assert how["params"] == {"region": region, "sub": sub,
                             "subs": ATTN if region == "attn" else FFN}


@pytest.mark.parametrize("metric, ms", [
    ("step.attn_proj_ms", 0.040 + 0.040 + 0.080 + 0.060),
    ("step.attn_prep_ms", 0.010 + 0.005 + 0.015),
    ("step.mixer_pointwise_ms", 0.020 + 0.012 + 0.018),
    ("step.ssd_ms", 0.030),
    ("step.experts_dispatch_ms", 0.009 + 0.013),
    ("step.experts_products_ms", 0.050 + 0.050 + 0.090),
    ("step.experts_gated_ms", 0.008 + 0.070),
    ("step.experts_combine_ms", 0.006 + 0.011)])
def test_a_child_reads_its_own_operations_in_every_pass(man, ctx, metric, ms):
    assert read(man, metric, ctx) == pytest.approx(ms)


def test_the_children_of_attn_add_up_to_its_bucket(man, ctx):
    children = sum(read(man, m, ctx) for m in (
        "step.attn_proj_ms", "step.attn_prep_ms", "step.mixer_pointwise_ms",
        "step.ssd_ms"))
    t = regions.of(ctx)
    # directly under `ssm`, in no child; and the compiler's copy, which the
    # region table gives its user's region and the reader no sub-scope
    stray, bare = 0.002, 0.004
    assert children + stray + bare == pytest.approx(
        regions.ms_per_step(t, ["attn"]))
    assert children + stray + bare + 0.100 == pytest.approx(
        read(man, "step.attn_ms", ctx))             # the core beside them
    assert read(man, "step.ssm_ms", ctx) == pytest.approx(
        0.060 + 0.020 + 0.012 + 0.018 + 0.030 + stray)
    # one parse of the text for all the files of a region
    assert [k for k in ctx["subscopes"] if k[0] == "attn"
            and "proj" in k[1]] == [("attn", tuple(ATTN))]


def test_the_children_of_experts_and_the_sort_add_up_to_experts(man, ctx):
    children = sum(read(man, m, ctx) for m in NEW if NEW[m][0] == "ffn")
    assert children + 0.003 == pytest.approx(
        read(man, "step.experts_ms", ctx))          # 0.003: the sort
    assert read(man, "step.router_ms", ctx) == pytest.approx(0.007)
    assert len([k for k in ctx["subscopes"] if k[0] == "ffn"]) == 2


def test_a_program_without_the_children_reads_as_nothing(man, monkeypatch):
    """The parent of the PR that planted them: the same text without the
    second level."""
    older = TEXT
    for child in ("proj", "prep", "pointwise", "dispatch", "products",
                  "gated", "combine"):
        older = older.replace(f"/{child}/", "/")
    monkeypatch.setattr(regions, "step_text", lambda *a, **k: (older, "m"))
    ctx = {"trace": [window()], "manifest": man, "model": {}, "mix": {},
           "chips": 1, "peaks": None}
    for metric in NEW:
        assert read(man, metric, ctx) is None       # never 0, never raises
    assert read(man, "step.experts_ms", ctx) == pytest.approx(0.310)  # as with
    assert read(man, "step.remat_ms", ctx) is not None   # JAX's own mark


# ---------------------------------------------------------------------------
# the recomputed forward
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name, verdict", [
    ("q.2", (True, "own", False)),
    ("gmm.2", (True, "own", False)),                # the mark twice
    ("act.3", (True, "inner product", True)),       # own path: backward
    ("norm.2", (True, "inner majority", True)),     # two of three
    ("norm.3", (False, "inner majority", True)),    # one of three
    ("q.3", (False, "own", False)),
    ("stack.1", (False, "own", False)),
    ("tgmm.3", (False, "own", False))])
def test_an_instruction_is_a_recomputed_forward_by(man, name, verdict):
    assert remat_reader(man).instruction_remat(TEXT)[name] == verdict


def test_recomputed_reads_a_path_by_its_components(man):
    f = remat_reader(man).recomputed
    assert f(f"{REMAT}/ffn/mul") and not f(f"{BWD}/ffn/mul")
    assert not f(f"{FWD}/ffn/rematted_computation_of_mine/mul")
    assert not f("")


def test_remat_ms_sums_the_recomputed_operations_once(man, ctx):
    # q.2, norm.2, gmm.2 (its path holds the mark twice), act.3
    assert read(man, "step.remat_ms", ctx) == pytest.approx(
        0.040 + 0.012 + 0.050 + 0.070)
    r = remat_reader(man).read
    assert r(ctx, {"regions": ["attn"]}) == pytest.approx(0.052)
    assert r(ctx, {"regions": ["ffn"]}) == pytest.approx(0.120)
    assert r(ctx, {"regions": ["attn/core", "optimizer"]}) is None
    assert "remat" in ctx                           # parsed once a run


@pytest.mark.parametrize("case", ["no trace", "no scopes",
                                  "nothing recomputed", "under 99%"])
def test_remat_ms_is_none_where_there_is_nothing_to_read(
        man, monkeypatch, case, capsys):
    text, trace = TEXT, [window()]
    if case == "no trace":
        trace = None
    elif case == "no scopes":
        text = None
    elif case == "nothing recomputed":
        text = TEXT.replace("/rematted_computation", "")
    else:
        trace = [window(rename={"tgmm.3": "fusion.404"})]
    monkeypatch.setattr(regions, "step_text", lambda *a, **k: (text, "m"))
    ctx = {"trace": trace, "manifest": man, "model": {}, "mix": {},
           "chips": 1, "peaks": None}
    assert read(man, "step.remat_ms", ctx) is None
    assert ("under 99%" in capsys.readouterr().err) == (case == "under 99%")


def test_the_new_readers_ask_for_the_text_and_rebuild_nothing(
        man, monkeypatch):
    calls = []
    monkeypatch.setattr(regions, "_step_text",
                        lambda *a: calls.append(1) or (TEXT, "m"))
    monkeypatch.setattr(regions, "_texts", {})
    ctx = {"trace": [window()], "manifest": man, "model": {"a": 1},
           "mix": {}, "chips": 1, "peaks": None}
    for metric in list(NEW) + ["step.remat_ms", "step.attn_ms"]:
        assert read(man, metric, ctx) is not None
    assert len(calls) == 1


# ---------------------------------------------------------------------------
# the tool's table
# ---------------------------------------------------------------------------
def test_subscope_summary_prints_child_by_pass_and_the_remainders(man):
    import importlib.util
    import collections
    spec = importlib.util.spec_from_file_location(
        "_subscope_summary", REPO / "benchmarks/tools/subscope_summary.py")
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    from paddle_tpu.utils import xprof
    assert sorted(tool.every_scope(xprof.SUBSCOPES, "attn")) == sorted(ATTN)
    assert sorted(tool.every_scope(xprof.SUBSCOPES, "ffn")) == sorted(FFN)
    under = lambda path, region: tool.path_under(  # noqa: E731
        path, region, xprof.SUBSCOPES)
    assert under(f"{FWD}/attn/mixer/ssm/proj/in_proj/dot_general",
                 "attn") == "ssm/proj"
    assert under(f"{FWD}/attn/self_attn/proj/q_proj/dot_general",
                 "attn") == "proj"
    assert under(f"{FWD}/0/ffn/mlp/experts/0/ffn/mlp/experts/checkpoint/"
                 "rematted_computation/products/gmm", "ffn") == \
        "experts/products"
    assert under(f"{FWD}/ffn/mlp/shared/products/mul", "ffn") == "shared"
    assert under(f"{FWD}/ffn/mlp/gate_up/dot_general", "ffn") is None
    assert under(f"{FWD}/attn/self_attn/proj/mul", "ffn") is None
    instr_regions = regions.instruction_regions(TEXT)
    w = window()
    t = regions.table([w], instr_regions)
    counts = collections.Counter(e.name for e in xplane.leaves(w.ops))
    out = io.StringIO()
    tool.render(t, counts, TEXT, xprof.SUBSCOPES,
                man.module("readers", "trace_subscope_ms"),
                remat_reader(man), instr_regions, out)
    lines = out.getvalue().splitlines()

    def row(label, which):
        at = next(i for i, line in enumerate(lines)
                  if line.startswith(label.ljust(26) + "all"))
        hit = next(line for line in lines[at + 1:at + 4]
                   if line.split()[0] == which)
        return float(hit.split()[1])

    assert row("proj", "fwd") == pytest.approx(0.040)
    assert row("proj", "remat") == pytest.approx(0.040)
    assert row("proj", "bwd") == pytest.approx(0.080)
    assert row("ssm/proj", "fwd") == pytest.approx(0.060)
    assert row("ssm/pointwise", "remat") == pytest.approx(0.012)
    assert row("ssm/ssd", "fwd") == pytest.approx(0.030)
    assert row("experts/products", "bwd") == pytest.approx(0.090)
    assert row("experts/gated", "remat") == pytest.approx(0.070)
    assert row("experts (in no child)", "fwd") == pytest.approx(0.003)
    assert row("ffn (in no child)", "fwd") == pytest.approx(0.025)
    assert row("router", "fwd") == pytest.approx(0.007)
    remainder = next(line for line in lines if "stray.1" in line)
    assert "region by own" in remainder             # how it was resolved
    # the compiler's copy stands in the region's remainder, as the metrics
    # have it, with the row it works for beside it
    assert row("attn (in no child)", "fwd") == pytest.approx(0.004)
    assert any("copy.9 0.004 (region by user)" in line for line in lines)
    assert any("for an operation of: ssm/pointwise 0.004" in line
               for line in lines)
    # every row's last scope is what the metrics' reader answers
    assert sum("0 instructions that the reader resolves to another" in line
               for line in lines) == 2
    # region x pass with the mixed fusions' time beside it
    ffn = next(line for line in lines if line.startswith("ffn".ljust(11))
               and "child" not in line)
    assert [float(v) for v in ffn.split()[1:]] == pytest.approx(
        [0.108, 0.120, 0.114, 0.070, 0.0])          # act.3 holds a product
    attn = next(line for line in lines if line.startswith("attn".ljust(11))
                and "child" not in line and "ms per step in" not in line)
    assert [float(v) for v in attn.split()[1:]][3:] == pytest.approx(
        [0.030, 0.030])                             # norm.2, norm.3: none
    assert any("act.3" in line and "remat (inner product)" in line
               for line in lines)
