"""The stable region scopes inside the compiled training step
(`utils/xprof.REGIONS`): a tiny ERNIE step built through Fleet +
HybridPretrainer carries every region in its compiled text, forward and
backward, whatever `xprof_scopes` says and on both pipeline schedules;
the small step of each of the four families carries the second level of
scopes (`utils/xprof.SUBSCOPES`) on a forward, a recomputed and a backward
path; `step_region` reads a path; `parse_hlo` prices a dot from jax 0.9's
shapeless-operand text."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.core import flags
from paddle_tpu.optimizer import Adam
from paddle_tpu.parallel import mesh as mesh_mod
from paddle_tpu.parallel.fleet import DistributedStrategy, Fleet
from paddle_tpu.text.ernie import ErnieConfig
from paddle_tpu.text.pretrainer import HybridPretrainer
from paddle_tpu.utils import xprof

CFG = dict(vocab_size=64, hidden_size=32, num_hidden_layers=2,
           num_attention_heads=2, intermediate_size=64,
           max_position_embeddings=32, hidden_dropout_prob=0.0,
           attention_probs_dropout_prob=0.0)


@pytest.fixture(autouse=True)
def _reset_mesh():
    yield
    mesh_mod.set_mesh(None)


@pytest.fixture(autouse=True, scope="module")
def _metadata_in_cache_key():
    """The persistent compile cache keys a program without its metadata: an
    entry compiled before the scopes existed would serve its own text."""
    name = "jax_compilation_cache_include_metadata_in_key"
    old = getattr(jax.config, name)
    jax.config.update(name, True)
    yield
    jax.config.update(name, old)


def compiled_paths(strategy, devices, rows=4, seq=16):
    """Every `op_name` of the compiled train step, built as fleet's users
    build it."""
    fleet = Fleet().init(strategy=strategy, devices=devices)
    trainer = HybridPretrainer(ErnieConfig(**CFG), mesh=fleet.mesh,
                               strategy=strategy)
    opt = fleet.distributed_optimizer(Adam(learning_rate=1e-4))
    step = jax.jit(trainer.make_train_step(opt, compute_dtype=jnp.bfloat16))
    params = trainer.place_params(trainer.init_params())
    rng = np.random.default_rng(0)
    batch = {
        "input_ids": rng.integers(1, 64, (rows, seq)).astype(np.int32),
        "token_type_ids": np.zeros((rows, seq), np.int32),
        "mlm_labels": rng.integers(0, 64, (rows, seq)).astype(np.int32),
        "nsp_labels": rng.integers(0, 2, (rows,)).astype(np.int32)}
    sh = trainer.data_shardings()
    batch = {k: jax.device_put(v, sh[k]) for k, v in batch.items()}
    text = step.lower(params, opt.init(params), batch,
                      jax.random.PRNGKey(0)).compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', text))


def one_chip():
    strategy = DistributedStrategy()
    strategy.hybrid_configs.dp_degree = 1
    return compiled_paths(strategy, jax.devices()[:1])


@pytest.fixture(scope="module")
def found():
    """{(region, pass)} of the one-chip step, and its paths."""
    paths = one_chip()
    mesh_mod.set_mesh(None)
    return {xprof.step_region(p) for p in paths}, paths


@pytest.mark.parametrize("region", [r for r in xprof.REGIONS
                                    if r != xprof.REGION_OPTIMIZER])
def test_region_has_a_forward_and_a_backward_path(found, region):
    assert {(region, "fwd"), (region, "bwd")} <= found[0]


def test_optimizer_runs_outside_forward_and_backward(found):
    regions, paths = found
    assert (xprof.REGION_OPTIMIZER, "fwd") in regions
    outside = [p for p in paths if "/optimizer/" in p and "jvp(" not in p]
    assert outside and not any("transpose(" in p for p in outside)
    # the weights' casts are differentiated through: the gradients' casts
    # back are the region's backward
    assert (xprof.REGION_OPTIMIZER, "bwd") in regions


def test_the_scan_keeps_its_own_bookkeeping_under_encoder_alone(found):
    _, paths = found
    stacking = [p for p in paths if p.endswith("/dynamic_update_slice")
                and "while/body" in p]
    assert stacking
    assert {xprof.step_region(p)[0] for p in stacking} == \
        {xprof.REGION_ENCODER}


def test_regions_do_not_hang_on_the_xprof_scopes_flag(found):
    saved = flags.get_flags(["xprof_scopes"])
    flags.set_flags({"xprof_scopes": False})
    try:
        regions = {xprof.step_region(p) for p in one_chip()}
    finally:
        flags.set_flags(saved)
    assert regions == found[0]
    # the per-attribute Layer scopes are what the flag switches
    assert any("/self_attn/" in p for p in found[1])


def test_the_1f1b_step_carries_the_regions():
    strategy = DistributedStrategy()
    strategy.hybrid_configs.dp_degree = 1
    strategy.hybrid_configs.pp_degree = 2
    strategy.pipeline = True
    strategy.pipeline_configs.schedule = "1f1b"
    strategy.pipeline_configs.micro_batch = 2
    regions = {xprof.step_region(p)[0]
               for p in compiled_paths(strategy, jax.devices()[:2])}
    assert set(xprof.REGIONS) <= regions


def test_step_region_reads_forward_backward_and_recomputed_paths():
    sr = xprof.step_region
    body = "while/body/closed_call"
    assert xprof.PASSES == ("fwd", "remat", "bwd")
    assert sr(f"jit(train_step)/jvp(encoder)/{body}/ffn/linear1/dot_general") \
        == ("ffn", "fwd")
    assert sr(f"jit(train_step)/transpose(jvp(encoder))/{body}/ffn/mul") == \
        ("ffn", "bwd")
    # a checkpoint's recomputed forward, as JAX marks it; its backward runs
    # under `checkpoint` alone
    assert sr(f"jit(train_step)/transpose(jvp(encoder))/{body}/checkpoint/"
              "rematted_computation/attn/self_attn/attn/core/exp") == \
        (xprof.ATTN_CORE, "remat")
    assert sr(f"jit(train_step)/transpose(jvp(encoder))/{body}/checkpoint/"
              "attn/self_attn/attn/core/mul") == (xprof.ATTN_CORE, "bwd")
    # a checkpoint inside a recomputed block: recomputed, once
    assert sr(f"jit(train_step)/transpose(jvp(encoder))/{body}/checkpoint/"
              "rematted_computation/ffn/checkpoint/rematted_computation/"
              "experts/gated/mul") == ("ffn", "remat")
    # the marker is a scope of the path, not a part of a name
    assert sr(f"jit(train_step)/transpose(jvp(encoder))/{body}/ffn/"
              "rematted_computation_of_mine/mul") == ("ffn", "bwd")
    assert sr(f"jit(train_step)/jvp(encoder)/{body}/attn/self_attn/q_proj/"
              "dot_general") == ("attn", "fwd")
    assert sr("jit(train_step)/jvp(encoder)/while/body/dynamic_update_slice") \
        == ("encoder", "fwd")
    assert sr("jit(train_step)/optimizer/sub") == ("optimizer", "fwd")
    assert sr("jit(loss)/mul") == (None, "fwd")     # a jitted function's name
    assert sr("jit(f)/core/mul") == (None, "fwd")   # `core` under no `attn`
    assert xprof._region_of("jit(s)/jvp(encoder)/while/body/ffn/tanh") == \
        ("ffn.fwd", "ffn", True)
    assert xprof._region_of("jit(s)/transpose(jvp(head))/cls/dot_general") \
        == ("head.bwd", "head", True)
    assert xprof._region_of(
        "jit(s)/transpose(jvp(encoder))/while/body/checkpoint/"
        "rematted_computation/ffn/dot_general") == ("ffn.remat", "ffn", True)
    # the Executor's op scopes still win, other Layer paths stay as they were
    assert xprof._region_of("jit(s)/mul.b0.i3/dot_general")[0] == "mul.b0.i3"
    assert xprof._region_of("jit(s)/Net/proj/dot_general")[0] == "Net/proj"


REMAT_HLO = """HloModule jit_f, is_scheduled=true

ENTRY %main.1 (a.1: f32[32,64], b.1: f32[64,16]) -> f32[32,16] {
  %a.1 = f32[32,64]{1,0} parameter(0), metadata={op_name="a"}
  %b.1 = f32[64,16]{1,0} parameter(1), metadata={op_name="b"}
  %dot.1 = f32[32,16]{1,0} dot(%a.1, %b.1), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(f)/jvp(encoder)/while/body/closed_call/checkpoint/ffn/dot_general"}
  %dot.2 = f32[32,16]{1,0} dot(%a.1, %b.1), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(f)/transpose(jvp(encoder))/while/body/closed_call/checkpoint/rematted_computation/ffn/dot_general"}
  ROOT %add.1 = f32[32,16]{1,0} add(%dot.1, %dot.2), metadata={op_name="jit(f)/transpose(jvp(encoder))/while/body/closed_call/checkpoint/ffn/add_any"}
}
"""


def test_attribute_hlo_reports_the_recomputed_forward_apart():
    regions = xprof.attribute_hlo(REMAT_HLO)
    assert {"ffn.fwd", "ffn.remat", "ffn.bwd"} <= set(regions)
    assert regions["ffn.remat"].flops == regions["ffn.fwd"].flops == \
        2 * 32 * 16 * 64
    assert regions["ffn.bwd"].flops == 32 * 16


# ---------------------------------------------------------------------------
# the second level of scopes, family by family
# ---------------------------------------------------------------------------
def _family_models():
    from paddle_tpu.text import deepseek_v3 as ds
    from paddle_tpu.text import granite_hybrid as gh
    from paddle_tpu.text import lfm2_moe as lm
    return {
        "ernie": lambda: ErnieConfig(**CFG),
        "deepseek_v3": lambda: ds.pretrain_model(ds.DeepseekV3Config(
            vocab_size=96, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=2, intermediate_size=64,
            moe_intermediate_size=16, n_routed_experts=16,
            n_shared_experts=2, num_experts_per_tok=3, kv_lora_rank=16,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            held_experts=(4, 4))),
        "lfm2_moe": lambda: lm.pretrain_model(lm.Lfm2MoeConfig(
            vocab_size=96, hidden_size=64, num_hidden_layers=3,
            num_attention_heads=4, num_key_value_heads=2,
            intermediate_size=96, moe_intermediate_size=32, num_experts=8,
            num_experts_per_tok=2, num_dense_layers=1,
            layer_types=["conv", "full_attention", "conv"],
            held_experts=(4, 4))),
        "granite_hybrid": lambda: gh.pretrain_model(gh.GraniteHybridConfig(
            vocab_size=96, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2,
            shared_intermediate_size=48, layer_types=["mamba", "attention"],
            mamba_n_heads=4, mamba_d_head=16, mamba_d_state=8,
            mamba_chunk_size=8)),
    }


# the parents of `xprof.SUBSCOPES` that a family's step has; the ERNIE step
# takes the packed attention path, which has no `prep`
FAMILY_PARENTS = {
    "ernie": ("attn",),
    "deepseek_v3": ("attn", "ffn/experts"),
    "lfm2_moe": ("attn", "attn/conv", "ffn/experts"),
    "granite_hybrid": ("attn", "attn/ssm"),
}
SECOND_LEVEL = (xprof.SCOPE_PROJ, xprof.SCOPE_PREP, xprof.SCOPE_POINTWISE,
                xprof.SCOPE_DISPATCH, xprof.SCOPE_PRODUCTS, xprof.SCOPE_GATED,
                xprof.SCOPE_COMBINE)
FAMILY_SCOPES = [
    (family, f"{parent}/{child}")
    for family, parents in FAMILY_PARENTS.items() for parent in parents
    for child in xprof.SUBSCOPES[parent] if child in SECOND_LEVEL
    and (family, child) != ("ernie", xprof.SCOPE_PREP)]


def family_paths(family, scopes_flag):
    """Every `op_name` of a family's small compiled step, every block under
    `jax.checkpoint` with nothing saved (as the recomputed cell runs it)."""
    saved = flags.get_flags(["xprof_scopes"])
    flags.set_flags({"xprof_scopes": scopes_flag})
    try:
        strategy = DistributedStrategy()
        strategy.hybrid_configs.dp_degree = 1
        strategy.recompute = True
        strategy.recompute_configs.policy = None
        fleet = Fleet().init(strategy=strategy, devices=jax.devices()[:1])
        trainer = HybridPretrainer(_family_models()[family](),
                                   mesh=fleet.mesh, strategy=strategy)
        opt = fleet.distributed_optimizer(Adam(learning_rate=1e-4))
        step = jax.jit(trainer.make_train_step(opt,
                                               compute_dtype=jnp.bfloat16))
        params = trainer.place_params(trainer.init_params())
        ids = np.random.default_rng(0).integers(1, 64, (2, 16)).astype(
            np.int32)
        batch = {"input_ids": ids}
        if family == "ernie":
            batch.update(token_type_ids=np.zeros((2, 16), np.int32),
                         mlm_labels=ids, nsp_labels=np.zeros((2,), np.int32))
        sh = trainer.data_shardings()
        batch = {k: jax.device_put(v, sh[k]) for k, v in batch.items()}
        text = step.lower(params, opt.init(params), batch,
                          jax.random.PRNGKey(0)).compile().as_text()
    finally:
        flags.set_flags(saved)
        mesh_mod.set_mesh(None)
    return set(re.findall(r'op_name="([^"]*)"', text))


@pytest.fixture(scope="module")
def family_steps():
    made = {}

    def paths(family, scopes_flag):
        if (family, scopes_flag) not in made:
            made[family, scopes_flag] = family_paths(family, scopes_flag)
        return made[family, scopes_flag]
    return paths


def passes_under(paths, scope):
    """The passes (`xprof.PASSES`) of the paths that go through `scope`, a
    Layer attribute's own scope (`self_attn`, `mlp`) allowed in between."""
    under = re.compile("/" + r"/(?:[\w.]+/)*?".join(scope.split("/")) + "/")
    return {xprof.step_region(p)[1] for p in paths if under.search(p)}


@pytest.mark.parametrize("scopes_flag", [True, False],
                         ids=["xprof_scopes", "no-xprof_scopes"])
@pytest.mark.parametrize("family, scope", FAMILY_SCOPES)
def test_family_step_carries_the_second_level_in_every_pass(
        family_steps, family, scope, scopes_flag):
    assert passes_under(family_steps(family, scopes_flag), scope) == \
        set(xprof.PASSES)


def test_every_second_level_scope_is_planted_in_some_family():
    planted = {scope.split("/")[-1] for _, scope in FAMILY_SCOPES}
    assert planted == set(SECOND_LEVEL)
    # and the table names nothing else beside the first level's scopes
    named = {c for kids in xprof.SUBSCOPES.values() for c in kids}
    assert named - set(SECOND_LEVEL) == {
        xprof.REGION_ATTN_CORE, xprof.SCOPE_CONV, xprof.SCOPE_SSM,
        xprof.SCOPE_SSD, xprof.SCOPE_ROUTER, xprof.SCOPE_EXPERTS,
        xprof.SCOPE_SHARED}
    assert not hasattr(xprof, "SCOPE_LATENT")


@pytest.mark.parametrize("family", ["deepseek_v3", "lfm2_moe"])
def test_the_sort_stays_directly_under_experts(family_steps, family):
    paths = family_steps(family, True)
    sorts = [p for p in paths if "/experts/" in p and p.endswith("/sort")]
    assert sorts
    assert not any(set(p.split("/")) & set(SECOND_LEVEL) for p in sorts)


HEAD = """HloModule jit_f, is_scheduled=true

ENTRY %main.1 (a.1: f32[32,64], b.1: f32[64,16]) -> f32[32,16] {
"""
# as jax 0.9.0 prints it: no shapes on the operands
NEW = HEAD + """  %a.1 = f32[32,64]{1,0} parameter(0), metadata={op_name="a"}
  %b.1 = f32[64,16]{1,0} parameter(1), metadata={op_name="b"}
  ROOT %dot_general.1 = f32[32,16]{1,0} dot(%a.1, %b.1), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(f)/jvp(encoder)/ffn/dot_general" stack_frame_id=3}
}
"""
OLD = HEAD + """  %a.1 = f32[32,64]{1,0} parameter(0), metadata={op_name="a"}
  %b.1 = f32[64,16]{1,0} parameter(1), metadata={op_name="b"}
  ROOT %dot_general.1 = f32[32,16]{1,0} dot(f32[32,64]{1,0} %a.1, f32[64,16]{1,0} %b.1), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(f)/jvp(encoder)/ffn/dot_general"}
}
"""


@pytest.mark.parametrize("text", [NEW, OLD], ids=["jax-0.9", "with-shapes"])
def test_parse_hlo_prices_a_dot_with_or_without_operand_shapes(text):
    comps, entries = xprof.parse_hlo(text)
    dot = comps[entries[0]][-1]
    assert dot.opcode == "dot"
    assert dot.operand_shapes == [("f32", (32, 64)), ("f32", (64, 16))]
    assert xprof._instr_flops(dot) == 2 * 32 * 16 * 64
    assert xprof._instr_bytes(dot) == 4 * (32 * 16 + 32 * 64 + 64 * 16)
    region = xprof.attribute_hlo(text)["ffn.fwd"]
    assert region.flops == 2 * 32 * 16 * 64 and region.attributed


def test_decoder_layer_scopes_both_attentions_the_ffn_and_the_norms():
    import paddle_tpu.nn as nn
    layer = nn.TransformerDecoderLayer(16, 2, 32, dropout=0.0,
                                       normalize_before=True)
    x = jnp.ones((2, 4, 16), jnp.float32)
    text = jax.jit(lambda t, m: layer(t, m)).lower(x, x).as_text(
        debug_info=True)
    paths = set(re.findall(r'loc\("([^"]*/[^"]*)"', text))
    by_region = {}
    for p in paths:
        by_region.setdefault(xprof.step_region(p)[0], []).append(p)
    assert {"attn", xprof.ATTN_CORE, "ffn", "ln"} <= set(by_region)
    assert any("/cross_attn/" in p for p in by_region["attn"])
    assert any("/self_attn/" in p for p in by_region[xprof.ATTN_CORE])
    assert any("/norm3/" in p for p in by_region["ln"])     # the pre-LN call
    assert not any("/linear1/" in p for p in by_region["ln"])


# ---------------------------------------------------------------------------
# the product's host spans on the device trace's clock
# ---------------------------------------------------------------------------
def _host_events(logdir):
    import glob

    from jax.profiler import ProfileData
    (path,) = glob.glob(f"{logdir}/plugins/profile/*/*.xplane.pb")
    data = ProfileData.from_file(path)
    return [(plane.name, e.name) for plane in data.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


def test_record_event_and_span_show_on_the_captures_host_plane(tmp_path):
    from paddle_tpu.utils import profiler, trace
    profiler.start_device_trace(str(tmp_path))
    try:
        with profiler.RecordEvent("data_load"):
            with trace.span("executor::run", program=7):
                jnp.ones((8, 8)).sum().block_until_ready()
    finally:
        profiler.stop_device_trace()
    names = {n for _, n in _host_events(tmp_path)}
    assert {"pdtpu.data_load", "pdtpu.executor::run"} <= names
    with profiler.RecordEvent("outside"):     # no capture: nothing to enter
        pass


def test_fit_wraps_each_batch_in_a_step_annotation(tmp_path):
    import paddle_tpu.nn as nn
    from paddle_tpu.hapi import Model
    from paddle_tpu.hapi.model import _traced_steps
    from paddle_tpu.optimizer import SGD
    from paddle_tpu.utils import profiler

    from paddle_tpu.io import TensorDataset
    rng = np.random.default_rng(0)
    data = TensorDataset([rng.normal(size=(8, 4)).astype(np.float32),
                          rng.normal(size=(8, 1)).astype(np.float32)])
    model = Model(nn.Linear(4, 1))
    model.prepare(SGD(learning_rate=0.01,
                      parameters=model.network.parameters()),
                  nn.MSELoss())
    profiler.start_device_trace(str(tmp_path))
    try:
        model.fit(data, batch_size=4, epochs=2, verbose=0)
    finally:
        profiler.stop_device_trace()
    steps = [n for _, n in _host_events(tmp_path) if n == "train"]
    assert len(steps) == 4                    # 2 batches x 2 epochs
    import itertools
    seen = list(_traced_steps(iter("ab"), itertools.count(5)))
    assert seen == ["a", "b"]
