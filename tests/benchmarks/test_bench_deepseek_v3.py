"""What `kanana-2-30b-a3b` brought to the benchmark, at a tiny size on the
CPU: the plain reference (`references/deepseek_v3_lm.py`) against the
program layer by layer and over a whole cell through `runner.run`, the
shares of an expert-parallel group adding up to the uncut reference, the
control and the fault moving the numbers, the operation counts by hand, the
sub-scope readers on a hand-made step, and the configuration's file holding
the published widths."""
import functools
import io
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from bench_testlib import REPO, Manifest

from benchmarks.harness import compare, regions, runner, trafficgen, weights

FIXTURES = pathlib.Path(__file__).resolve().parent / "fixtures_deepseek_v3"
CATALOG = pathlib.Path("/opt/skills/guides/model-configs/architectures.jsonl")


def tiny_manifest():
    return Manifest(FIXTURES / "BENCHMARK.json", [FIXTURES])


def tiny_model(**over):
    return dict(tiny_manifest().config("tiny-moe")["model"], **over)


def reference():
    return tiny_manifest().module("references", "deepseek_v3_lm")


def drawn(spec, seed=5):
    return weights.maker(spec)(weights.seed_key(seed))


def mm32(spec, a, b):
    return reference()._mm(spec, a, b, "float32")


# ---------------------------------------------------------------------------
# layer by layer: the program's Layers against the reference's functions
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def block_weights():
    """One expert block's weights in the reference's layout (the leading
    layer axis taken off), and the program's config for the same sizes."""
    from paddle_tpu.text.deepseek_v3 import DeepseekV3Config
    m = tiny_model()
    spec = reference().param_spec(m)["expert_blocks"]
    p = {k: v[0] for k, v in drawn(spec).items()}
    cfg = DeepseekV3Config(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        num_hidden_layers=m["num_hidden_layers"],
        num_attention_heads=m["num_attention_heads"],
        intermediate_size=m["intermediate_size"],
        moe_intermediate_size=m["moe_intermediate_size"],
        n_routed_experts=m["router_experts"],
        n_shared_experts=m["n_shared_experts"],
        num_experts_per_tok=m["num_experts_per_tok"],
        kv_lora_rank=m["kv_lora_rank"],
        qk_nope_head_dim=m["qk_nope_head_dim"],
        qk_rope_head_dim=m["qk_rope_head_dim"], v_head_dim=m["v_head_dim"],
        rope_theta=m["rope_theta"], rms_norm_eps=m["rms_norm_eps"],
        routed_scaling_factor=m["routed_scaling_factor"],
        held_experts=tuple(m["held_experts"]))
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 128, m["hidden_size"]))
    return m, cfg, p, x


def under(prefix, p):
    return {k[len(prefix):]: v for k, v in p.items() if k.startswith(prefix)}


def test_latent_attention_is_the_references(block_weights):
    from paddle_tpu.autograd import functional_call
    from paddle_tpu.text.deepseek_v3 import LatentAttention
    m, cfg, p, x = block_weights
    got = functional_call(LatentAttention(cfg), under("self_attn.", p), (x,))
    want = reference()._attention(x, p, m, mm32)
    # float32 on both sides; summation order (query blocks, fused products)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=1e-4)


def test_expert_layer_is_the_references(block_weights):
    from paddle_tpu.autograd import functional_call
    m, cfg, p, x = block_weights
    import paddle_tpu.nn as nn
    layer = nn.DroplessMoE(
        m["hidden_size"], m["moe_intermediate_size"], m["router_experts"],
        m["num_experts_per_tok"], held=tuple(m["held_experts"]),
        n_shared_experts=m["n_shared_experts"],
        routed_scaling_factor=m["routed_scaling_factor"])
    got = functional_call(layer, under("mlp.", p), (x,))
    want = reference().expert_layer(x, p, m, mm32)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=1e-4)


def test_block_and_its_gradient_are_the_references(block_weights):
    from paddle_tpu.autograd import functional_call
    from paddle_tpu.text.deepseek_v3 import DeepseekV3Block
    m, cfg, p, x = block_weights
    block = DeepseekV3Block(cfg, expert=True)

    def program(p, x):
        return jnp.sum(jnp.square(functional_call(block, p, (x,))))

    def plain(p, x):
        return jnp.sum(jnp.square(reference()._block(
            x, p, m, "float32", expert=True)))

    a, ga = jax.value_and_grad(program)(p, x)
    b, gb = jax.value_and_grad(plain)(p, x)
    assert float(a) == pytest.approx(float(b), rel=1e-5)
    assert not np.asarray(ga["mlp.router_bias"]).any()
    assert not np.asarray(gb["mlp.router_bias"]).any()
    for k in gb:
        # the worst leaf against its own largest entry: float32 round-off
        # through two norms, a softmax and a sort
        scale = float(jnp.max(jnp.abs(gb[k]))) or 1.0
        assert float(jnp.max(jnp.abs(ga[k] - gb[k]))) / scale < 1e-4, k


@pytest.mark.parametrize("shares", [8, 2])
def test_the_shares_add_up_to_the_uncut_reference(shares):
    """Eight chips holding experts 0-15 ... 112-127 of one layer: the parts
    that the program's layers give, the shared experts counted once, are
    the uncut reference's layer output."""
    import paddle_tpu.nn as nn
    from paddle_tpu.autograd import functional_call
    E, k, H, F = 128, 6, 64, 32
    held = E // shares
    m = tiny_model(hidden_size=H, moe_intermediate_size=F, router_experts=E,
                   n_routed_experts=E, held_experts=[0, E],
                   num_experts_per_tok=k)
    spec = reference().param_spec(m)["expert_blocks"]
    p = {k_[len("mlp."):]: v[0] for k_, v in drawn(spec, 9).items()
         if k_.startswith("mlp.")}
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 64, H))
    whole = reference().expert_layer(
        x, {"mlp." + k_: v for k_, v in p.items()}, m, mm32)
    shared = reference()._swiglu(x, p["shared_mlp.gate_up.weight"],
                                 p["shared_mlp.down.weight"], mm32)
    total = shared
    for first in range(0, E, held):
        layer = nn.DroplessMoE(H, F, E, k, held=(first, held),
                               n_shared_experts=m["n_shared_experts"],
                               routed_scaling_factor=2.448)
        part = functional_call(layer, {
            **p, "w_in": p["w_in"][first:first + held],
            "w_out": p["w_out"][first:first + held]}, (x,))
        total = total + part - shared
    np.testing.assert_allclose(total, whole, atol=3e-6, rtol=1e-4)
    # and the reference's own share is the program's
    mine = dict(m, held_experts=[16, 16])
    ref_part = reference().expert_layer(x, {
        **{"mlp." + k_: v for k_, v in p.items()},
        "mlp.w_in": p["w_in"][16:32], "mlp.w_out": p["w_out"][16:32]},
        mine, mm32)
    layer = nn.DroplessMoE(H, F, E, k, held=(16, 16), n_shared_experts=2,
                           routed_scaling_factor=2.448)
    got = functional_call(layer, {**p, "w_in": p["w_in"][16:32],
                                  "w_out": p["w_out"][16:32]}, (x,))
    np.testing.assert_allclose(got, ref_part, atol=3e-6, rtol=1e-4)


def test_routing_weights_follow_the_published_router():
    m = tiny_model()
    E, k = m["router_experts"], m["num_experts_per_tok"]
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 9, m["hidden_size"]))
    w_r = jax.random.normal(jax.random.PRNGKey(7), (m["hidden_size"], E))
    bias = jnp.zeros((E,)).at[3].set(10.0)      # expert 3 always selected
    w = np.asarray(reference().routing_weights(x, w_r, bias, m, mm32))[0]
    s = np.asarray(jax.nn.sigmoid(x[0] @ w_r))
    assert ((w > 0).sum(axis=1) == k).all() and (w[:, 3] > 0).all()
    np.testing.assert_allclose(w.sum(axis=1), m["routed_scaling_factor"],
                               rtol=1e-5)
    # the bias moves the selection, not the weight: weights are the scores
    picked = w > 0
    np.testing.assert_allclose(
        w[picked], (s * picked / (s * picked).sum(1, keepdims=True)
                    * m["routed_scaling_factor"])[picked], rtol=1e-5)


# ---------------------------------------------------------------------------
# the whole step: the tiny cell through the harness
# ---------------------------------------------------------------------------
def tiny_run(seed=7, trace=False, tmp=None):
    err = io.StringIO()
    result = runner.run(FIXTURES / "BENCHMARK.json", "tiny-moe.s128", seed,
                        0.3, trace, search=[FIXTURES], require_tpu=False,
                        compile_cache=False,
                        scratch=str(tmp) if tmp else None, err=err)
    return result, err.getvalue()


@pytest.fixture(scope="module")
def plain():
    return tiny_run()


def test_tiny_cell_is_correct_against_the_reference(plain):
    result, err = plain
    assert result["correct"] is True and result["failed"] == 0
    # float32 program against float32 reference over three Adam steps:
    # losses to 1e-4 relative, gradient and change to 1e-3 by the worst leaf
    assert len(result["compared"]) == 8
    for name, j in result["compared"].items():
        assert j["value"] <= j["limit"], name
    assert result["compared"]["loss_gap_1"]["value"] < 1e-5
    assert result["compared"]["grad_diff_gap"]["value"] < 1e-4
    assert set(result["metrics"]) == {"items_per_s_per_chip", "step_ms_p90",
                                      "setup_s"}
    lines = [ln for ln in err.splitlines() if ln.startswith("compared ")]
    assert len(lines) == 8


def test_tiny_cell_reports_the_routing_counters(plain):
    counters = plain[0]["notes"]["counters"]
    assert counters["moe.pairs_routed"] == {"layer=0": 4 * 128 * 3,
                                            "layer=1": 4 * 128 * 3}
    assert counters["moe.pairs_dropped"] == {"layer=0": 0, "layer=1": 0}
    held = counters["moe.pairs_held"]
    assert all(0 < held[k] < 4 * 128 * 3 for k in held)
    assert all(v >= 1.0 for v in
               counters["moe.held_load_max_over_mean"].values())


def test_tiny_cell_traced_off_the_chip_reports_no_device_metric(tmp_path):
    result, _ = tiny_run(seed=2 ** 31 + 77, trace=True, tmp=tmp_path)
    assert result["correct"] is True
    assert "loop.dispatch_ms" in result["metrics"]
    assert "device.idle_share" not in result["metrics"]
    assert "busy_s" not in result["device"]


@pytest.fixture(scope="module")
def readings():
    """The reference in float32, in the yardstick's bfloat16, as the fp8
    control, and with half of the rows left out, on one seed."""
    man = tiny_manifest()
    config, ref = man.config("tiny-moe"), reference()
    mix = trafficgen.load(man.find("traffic", "train.tiny-moe.json"))
    pool = trafficgen.make_pool(mix, config["model"], 11)[:2]
    params = drawn(ref.param_spec(config["model"]), 11)
    run = functools.partial(ref.run, config["model"],
                            config["train"]["optimizer"], params, pool,
                            devices=jax.devices()[:1], rows_per_block=2)
    return {"float32": run(), "bfloat16": run(precision="bfloat16"),
            "fp8": run(precision="fp8"), "half": run(row_share=0.5)}


def test_reference_precisions_stand_in_order(readings):
    exact = readings["float32"]["first_grad"]
    bf16 = compare.diff_rel(readings["bfloat16"]["first_grad"], exact)
    fp8 = compare.diff_rel(readings["fp8"]["first_grad"], exact)
    assert 0 < bf16 < fp8 and fp8 > 3 * bf16
    assert all(np.isfinite(readings[k]["losses"]).all() for k in readings)


def test_half_of_the_batch_left_out_moves_the_gradient(readings):
    exact, half = readings["float32"], readings["half"]
    assert compare.diff_rel(half["first_grad"], exact["first_grad"]) > 0.3
    assert abs(half["losses"][0] - exact["losses"][0]) > 1e-4


def test_reference_leaves_the_selection_bias_where_it_was(readings):
    r = readings["float32"]
    assert not np.asarray(
        r["first_grad"]["expert_blocks"]["mlp.router_bias"]).any()
    assert not np.asarray(
        r["param_change"]["expert_blocks"]["mlp.router_bias"]).any()
    assert np.asarray(
        r["param_change"]["expert_blocks"]["mlp.w_in"]).any()


def test_reference_imports_nothing_of_the_program():
    text = (REPO / "benchmarks/references/deepseek_v3_lm.py").read_text()
    assert "paddle_tpu" not in text.replace("`", "").split('"""', 2)[2]


# ---------------------------------------------------------------------------
# the counts, by hand
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def real():
    man = Manifest(REPO / "BENCHMARK.json")
    return (man.config("kanana-2-30b-a3b"),
            man.json_of("traffic", "train.b2.s4096"),
            man.module("opcounts", "deepseek_v3"))


def test_train_flops_per_token_by_hand(real):
    config, mix, oc = real
    proj = 2 * (2048 * 32 * 192 + 2048 * 576 + 512 * 32 * 256
                + 32 * 128 * 2048)
    core = 4097 * 32 * (192 + 128)
    dense = proj + core + 6 * 2048 * 6144
    expert = proj + core + 2 * 2048 * 128 + 6 * 2048 * 1536 \
        + 0.75 * 6 * 2048 * 768
    want = 3 * (dense + 4 * expert + 2 * 2048 * 16032)
    assert oc.train_flops_per_item(config["model"], mix) == \
        pytest.approx(want, rel=1e-12)
    assert want == pytest.approx(2.16e9, rel=0.005)      # ISSUE 28's figure
    assert oc.expected_pairs_per_token(config["model"]) == 0.75


def test_kernel_costs_by_hand(real):
    config, mix, oc = real
    core = oc.mla_core_train(config["model"], mix)
    pairs = 4096 * 4097 / 2
    assert core["ops"] == pytest.approx(
        2 * 2 * 32 * pairs * (3 * 192 + 3 * 128) * 5, rel=1e-12)
    assert core["bytes"] == 2 * 2 * 32 * 4096 * (4 * 192 + 4 * 128) * 5
    experts = oc.held_experts_train(config["model"], mix)
    held_pairs = 8192 * 6 * 16 / 128
    assert held_pairs == 6144
    assert experts["ops"] == 6 * held_pairs * 3 * 2048 * 768 * 4
    assert experts["bytes"] == 2 * (3 * 16 * 3 * 2048 * 768 + 3 * held_pairs
                                    * (2 * 2048 + 3 * 768)) * 4


# ---------------------------------------------------------------------------
# the sub-scope readers, on a hand-made step
# ---------------------------------------------------------------------------
STEP = """HloModule jit_train_step, is_scheduled=true

%fused_computation.1 (p.1: f32[8,8]) -> f32[8,8] {
  %p.1 = f32[8,8]{1,0} parameter(0)
  ROOT %dot.9 = f32[8,8]{1,0} dot(%p.1, %p.1), metadata={op_name="jit(train_step)/jvp(encoder)/while/body/closed_call/0/ffn/mlp/shared/shared_mlp/down/dot_general"}
}

ENTRY %main.1 (a.1: f32[8,8]) -> f32[8,8] {
  %a.1 = f32[8,8]{1,0} parameter(0), metadata={op_name="a"}
  %sort.1 = f32[8,8]{1,0} sort(%a.1), metadata={op_name="jit(train_step)/jvp(encoder)/while/body/closed_call/0/ffn/mlp/router/jit(argsort)/sort"}
  %ragged.1 = f32[8,8]{1,0} custom-call(%sort.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/transpose(jvp(encoder))/while/body/closed_call/0/ffn/mlp/experts/checkpoint/rematted_computation/ragged_dot_general"}
  %fusion.1 = f32[8,8]{1,0} fusion(%ragged.1), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jvp(encoder)/while/body/dynamic_update_slice"}
  %mul.1 = f32[8,8]{1,0} multiply(%fusion.1, %fusion.1), metadata={op_name="jit(train_step)/jvp(encoder)/while/body/closed_call/0/ffn/mul"}
  ROOT %exp.1 = f32[8,8]{1,0} exponential(%mul.1), metadata={op_name="jit(train_step)/jvp(encoder)/while/body/closed_call/0/attn/self_attn/latent/exp"}
}
"""
SUBS = ["router", "experts", "shared"]


def subscope_reader():
    return Manifest(REPO / "BENCHMARK.json").module("readers",
                                                    "trace_subscope_ms")


def test_subscope_of_an_op_name():
    f = subscope_reader().subscope_of
    body = "jit(train_step)/transpose(jvp(encoder))/while/body/closed_call"
    assert f(f"{body}/0/ffn/mlp/experts/ragged_dot_general", "ffn",
             SUBS) == "experts"
    assert f(f"{body}/0/ffn/mlp/router/0/ffn/mlp/router/checkpoint/div",
             "ffn", SUBS) == "router"
    assert f(f"{body}/0/ffn/mul", "ffn", SUBS) is None
    assert f(f"{body}/0/attn/router/mul", "ffn", SUBS) is None
    assert f("jit(train_step)/optimizer/experts/mul", "ffn", SUBS) is None


def test_instructions_resolve_to_their_subscope():
    where = subscope_reader().instruction_subscopes(STEP, "ffn", SUBS)
    assert where["sort.1"] == "router" and where["ragged.1"] == "experts"
    assert where["fusion.1"] == "shared"      # by its inner product
    assert where["mul.1"] is None and where["exp.1"] is None
    assert subscope_reader().instruction_subscopes(
        STEP, "attn", ["latent"])["exp.1"] == "latent"


def fake_ctx(monkeypatch, coverage=True):
    """A ctx whose region table is made by hand: 2 steps, the operations of
    STEP with their own nanoseconds."""
    ns = {"sort.1": 4e6, "ragged.1": 10e6, "fusion.1": 6e6, "mul.1": 2e6}
    table = {"ops": {("ffn", "fwd"): {k: v for k, v in ns.items()
                                      if k != "ragged.1"},
                     ("ffn", "bwd"): {"ragged.1": ns["ragged.1"]},
                     ("attn", "fwd"): {"exp.1": 1e6}},
             "steps": 2}
    ctx = {"regions": table if coverage else None, "trace": [object()],
           "manifest": Manifest(REPO / "BENCHMARK.json"), "model": {},
           "mix": {}, "chips": 1,
           "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}
    monkeypatch.setattr(regions, "step_text", lambda *a, **k: (STEP, "m"))
    return ctx


@pytest.mark.parametrize("sub, ms", [("router", 2.0), ("experts", 5.0),
                                     ("shared", 3.0)])
def test_subscope_ms_per_step(monkeypatch, sub, ms):
    ctx = fake_ctx(monkeypatch)
    got = subscope_reader().read(ctx, {"region": "ffn", "sub": sub,
                                       "subs": SUBS})
    assert got == pytest.approx(ms)


def test_subscope_readers_give_nothing_without_a_table(monkeypatch):
    ctx = fake_ctx(monkeypatch, coverage=False)
    params = {"region": "ffn", "sub": "experts", "subs": SUBS,
              "cost": "deepseek_v3:held_experts_train",
              "flops_peak": "bf16_flops_per_s"}
    assert subscope_reader().read(ctx, params) is None
    roofline = ctx["manifest"].module("readers", "trace_subscope_roofline")
    assert roofline.read(ctx, params) is None
    # and nothing, never 0, where nothing ran under the sub-scope
    ctx = fake_ctx(monkeypatch)
    assert subscope_reader().read(
        ctx, {"region": "attn", "sub": "nothing", "subs": ["nothing"]}) is None


def test_subscope_roofline_is_the_cost_over_the_time(monkeypatch, real,
                                                     capsys):
    config, mix, oc = real
    ctx = dict(fake_ctx(monkeypatch), model=config["model"], mix=mix)
    roofline = ctx["manifest"].module("readers", "trace_subscope_roofline")
    got = roofline.read(ctx, {
        "region": "ffn", "sub": "experts", "subs": SUBS,
        "cost": "deepseek_v3:held_experts_train",
        "flops_peak": "bf16_flops_per_s"})
    cost = oc.held_experts_train(config["model"], mix)
    least_ms = max(cost["ops"] / 1e12, cost["bytes"] / 1e11) * 1e3
    assert got == pytest.approx(100 * least_ms / 5.0)
    assert "ffn/experts" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# the configuration's file
# ---------------------------------------------------------------------------
PUBLISHED_WIDTHS = {
    "hidden_size": 2048, "num_attention_heads": 32, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "v_head_dim": 128, "kv_lora_rank": 512,
    "moe_intermediate_size": 768, "n_shared_experts": 2,
    "intermediate_size": 6144, "num_experts_per_tok": 6,
    "routed_scaling_factor": 2.448, "first_k_dense_replace": 1,
    "rope_theta": 1000000, "rms_norm_eps": 1e-06}


def test_the_file_holds_every_published_width(real):
    config = real[0]
    for key, value in PUBLISHED_WIDTHS.items():
        assert config[key] == value and config["model"][key] == value, key
    assert config["model"]["router_experts"] == 128
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 48,
                                   "n_routed_experts": 128,
                                   "vocab_size": 128256}
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (5, 16, 16032)
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert config["model"]["held_experts"] == [0, 16]
    assert "8 chips" in config["deployment"]
    assert config["departures"] and config["assumed"]
    # what the harness reads is what the file states at its top level
    for key in config["model"]:
        if key in config and key != "model":
            assert config[key] == config["model"][key], key


def test_the_file_holds_the_catalogs_row():
    if not CATALOG.exists():
        pytest.skip("the catalog is not on this machine")
    row = next(json.loads(line) for line in CATALOG.read_text().splitlines()
               if "kanana-2-30b-a3b-instruct-2601" in line)
    config = Manifest(REPO / "BENCHMARK.json").config("kanana-2-30b-a3b")
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
