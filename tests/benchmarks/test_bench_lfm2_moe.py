"""What `lfm2-24b-a2b` brought to the benchmark, at a tiny size on the CPU:
the plain reference (`references/lfm2_moe_lm.py`) against the program mixer
by mixer, layer by layer and over a whole cell through `runner.run`, the
shares of an expert-parallel group adding up to the uncut reference, the
control and the fault moving the numbers, the operation counts by hand, and
the configuration's file holding the published widths."""
import functools
import io
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from bench_testlib import REPO, Manifest

from benchmarks.harness import compare, runner, trafficgen, weights

FIXTURES = pathlib.Path(__file__).resolve().parent / "fixtures_lfm2_moe"
CATALOG = pathlib.Path("/opt/skills/guides/model-configs/architectures.jsonl")
CONV_EXPERT, ATTN_EXPERT, CONV_DENSE = (
    "run02_conv_expert", "run01_attention_expert", "run00_conv_dense")


def tiny_manifest():
    return Manifest(FIXTURES / "BENCHMARK.json", [FIXTURES])


def tiny_model(**over):
    return dict(tiny_manifest().config("tiny-hybrid")["model"], **over)


def reference():
    return tiny_manifest().module("references", "lfm2_moe_lm")


def drawn(spec, seed=5):
    return weights.maker(spec)(weights.seed_key(seed))


def mm32(spec, a, b):
    return reference()._mm(spec, a, b, "float32")


def program_config(m):
    from paddle_tpu.text.lfm2_moe import Lfm2MoeConfig
    return Lfm2MoeConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        num_hidden_layers=m["num_hidden_layers"],
        num_attention_heads=m["num_attention_heads"],
        num_key_value_heads=m["num_key_value_heads"],
        intermediate_size=m["intermediate_size"],
        moe_intermediate_size=m["moe_intermediate_size"],
        num_experts=m["router_experts"],
        num_experts_per_tok=m["num_experts_per_tok"],
        num_dense_layers=m["num_dense_layers"], layer_types=m["layer_types"],
        conv_L_cache=m["conv_L_cache"], rope_theta=m["rope_theta"],
        norm_eps=m["norm_eps"], held_experts=tuple(m["held_experts"]))


def under(prefix, p):
    return {k[len(prefix):]: v for k, v in p.items() if k.startswith(prefix)}


# ---------------------------------------------------------------------------
# mixer by mixer, layer by layer: the program's Layers against the
# reference's functions
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny():
    """The tiny model's sizes, the program's config for them, one layer's
    weights of every run in the reference's layout (the leading layer axis
    taken off), and an input."""
    m = tiny_model()
    spec = reference().param_spec(m)
    assert [name for name, *_ in reference().runs(m)] == [
        CONV_DENSE, ATTN_EXPERT, CONV_EXPERT]
    p = {g: {k: v[0] for k, v in drawn(spec[g]).items()}
         for g in (CONV_DENSE, ATTN_EXPERT, CONV_EXPERT)}
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 128, m["hidden_size"]))
    return m, program_config(m), p, x


def test_short_convolution_mixer_is_the_references(tiny):
    from paddle_tpu.autograd import functional_call
    from paddle_tpu.text.lfm2_moe import ShortConv
    m, cfg, p, x = tiny
    got = functional_call(ShortConv(cfg), under("operator.", p[CONV_EXPERT]),
                          (x,))
    want = reference().short_conv(x, p[CONV_EXPERT], m, mm32)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=1e-4)


def test_short_convolution_is_causal_and_three_taps_deep(tiny):
    """Position t reads z of t-2, t-1, t and nothing else: moving the input
    at position 40 moves the outputs 40, 41, 42 alone; and it is the
    published depthwise Conv1d(kernel 3, padding 2) cut to s outputs."""
    from paddle_tpu.nn import functional as F
    m, _, p, x = tiny
    ref, q = reference(), p[CONV_EXPERT]
    base = ref.short_conv(x, q, m, mm32)
    moved = ref.short_conv(x.at[:, 40].add(1.0), q, m, mm32)
    changed = np.flatnonzero(np.abs(np.asarray(moved - base)).max((0, 2)))
    assert changed.tolist() == [40, 41, 42]
    gate_b, gate_c, u = jnp.split(x @ q["operator.in_proj.weight"], 3, -1)
    z = (gate_b * u).transpose(0, 2, 1)                # channels first
    c = F.conv1d(z, q["operator.taps"].T[:, None, :], padding=2,
                 groups=m["hidden_size"])[..., :x.shape[1]]
    want = (gate_c * c.transpose(0, 2, 1)) @ q["operator.out_proj.weight"]
    np.testing.assert_allclose(base, want, atol=2e-6, rtol=1e-4)


def test_grouped_query_attention_is_the_references(tiny):
    from paddle_tpu.autograd import functional_call
    from paddle_tpu.text.lfm2_moe import GroupedQueryAttention
    m, cfg, p, x = tiny
    got = functional_call(GroupedQueryAttention(cfg),
                          under("operator.", p[ATTN_EXPERT]), (x,))
    want = reference().attention(x, p[ATTN_EXPERT], m, mm32)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=1e-4)


def test_reference_attention_by_hand(tiny):
    """The reference's grouped einsum against attention written head by
    head: query head j reads key/value head j // 2, q and k normalised over
    their 16 channels, halves rotated."""
    m, _, p, x = tiny
    ref, q_ = reference(), p[ATTN_EXPERT]
    h, kv, d, s = 4, 2, 16, x.shape[1]
    qkv = x @ q_["operator.qkv_proj.weight"]
    q = qkv[..., :h * d].reshape(2, s, h, d)
    k = qkv[..., h * d:(h + kv) * d].reshape(2, s, kv, d)
    v = qkv[..., (h + kv) * d:].reshape(2, s, kv, d)

    def norm_rot(t, w):
        t = t / jnp.sqrt(jnp.mean(t * t, -1, keepdims=True) + 1e-5) * w
        inv = 1e6 ** (-jnp.arange(0, d, 2) / d)
        ang = jnp.arange(s)[:, None] * inv[None, :]
        a, b = t[..., :d // 2], t[..., d // 2:]
        cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    q = norm_rot(q, q_["operator.q_norm.weight"])
    k = norm_rot(k, q_["operator.k_norm.weight"])
    outs = []
    for j in range(h):
        sc = jnp.einsum("bqd,bkd->bqk", q[:, :, j], k[:, :, j // 2]) / 4.0
        sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
        outs.append(jax.nn.softmax(sc, -1) @ v[:, :, j // 2])
    want = jnp.concatenate(outs, -1) @ q_["operator.out_proj.weight"]
    got = ref.attention(x, q_, m, mm32)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=1e-4)


def test_expert_layer_is_the_references(tiny):
    import paddle_tpu.nn as nn
    from paddle_tpu.autograd import functional_call
    m, cfg, p, x = tiny
    layer = nn.DroplessMoE(
        m["hidden_size"], m["moe_intermediate_size"], m["router_experts"],
        m["num_experts_per_tok"], held=tuple(m["held_experts"]),
        norm_eps=m["router_norm_eps"])
    assert layer.shared_mlp is None
    got = functional_call(layer, under("feed_forward.", p[CONV_EXPERT]), (x,))
    want = reference().expert_layer(x, p[CONV_EXPERT], m, mm32)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=1e-4)


@pytest.mark.parametrize("group, mixer, expert", [
    (CONV_DENSE, "conv", False), (ATTN_EXPERT, "full_attention", True),
    (CONV_EXPERT, "conv", True)])
def test_block_and_its_gradient_are_the_references(tiny, group, mixer, expert):
    from paddle_tpu.autograd import functional_call
    from paddle_tpu.text.lfm2_moe import Lfm2MoeBlock
    m, cfg, p, x = tiny
    block, p = Lfm2MoeBlock(cfg, mixer, expert), p[group]

    def program(p, x):
        return jnp.sum(jnp.square(functional_call(block, p, (x,))))

    def plain(p, x):
        return jnp.sum(jnp.square(reference()._block(
            x, p, m, "float32", mixer=mixer, expert=expert)))

    a, ga = jax.value_and_grad(program)(p, x)
    b, gb = jax.value_and_grad(plain)(p, x)
    assert float(a) == pytest.approx(float(b), rel=1e-5)
    assert set(ga) == set(gb)
    if expert:
        assert not np.asarray(ga["feed_forward.router_bias"]).any()
        assert not np.asarray(gb["feed_forward.router_bias"]).any()
    for k in gb:
        # the worst leaf against its own largest entry: float32 round-off
        # through two norms, a softmax and a sort
        scale = float(jnp.max(jnp.abs(gb[k]))) or 1.0
        assert float(jnp.max(jnp.abs(ga[k] - gb[k]))) / scale < 1e-4, k


def test_the_two_shares_add_up_to_the_uncut_reference():
    """Two chips holding experts 0-3 and 4-7 of one layer: the parts that
    the program's layers give add up to the uncut reference's layer output.
    Nothing is counted twice: there is no shared expert."""
    import paddle_tpu.nn as nn
    from paddle_tpu.autograd import functional_call
    E, k, H, F = 8, 2, 64, 32
    m = tiny_model(router_experts=E, num_experts=E, held_experts=[0, E])
    spec = reference().param_spec(m)[CONV_EXPERT]
    p = {k_[len("feed_forward."):]: v[0] for k_, v in drawn(spec, 9).items()
         if k_.startswith("feed_forward.")}
    assert set(p) == {"router_weight", "router_bias", "w_in", "w_out"}
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 64, H))
    whole = reference().expert_layer(
        x, {"feed_forward." + k_: v for k_, v in p.items()}, m, mm32)
    total = jnp.zeros_like(x)
    for first in (0, 4):
        layer = nn.DroplessMoE(H, F, E, k, held=(first, 4), norm_eps=1e-6)
        part = functional_call(layer, {
            **p, "w_in": p["w_in"][first:first + 4],
            "w_out": p["w_out"][first:first + 4]}, (x,))
        assert float(jnp.abs(part).max()) > 0
        total = total + part
        # and the reference's own share is the program's
        ref_part = reference().expert_layer(x, {
            **{"feed_forward." + k_: v for k_, v in p.items()},
            "feed_forward.w_in": p["w_in"][first:first + 4],
            "feed_forward.w_out": p["w_out"][first:first + 4]},
            dict(m, held_experts=[first, 4]), mm32)
        np.testing.assert_allclose(part, ref_part, atol=3e-6, rtol=1e-4)
    np.testing.assert_allclose(total, whole, atol=3e-6, rtol=1e-4)


def test_routing_weights_follow_the_published_router():
    """The bias selects and does not weigh; the weights are the selected
    scores over (their sum + 1e-6), times routed_scaling_factor 1."""
    m = tiny_model()
    E, k = m["router_experts"], m["num_experts_per_tok"]
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 9, m["hidden_size"]))
    w_r = jax.random.normal(jax.random.PRNGKey(7), (m["hidden_size"], E))
    bias = jnp.zeros((E,)).at[3].set(10.0)      # expert 3 always selected
    w = np.asarray(reference().routing_weights(x, w_r, bias, m, mm32))[0]
    s = np.asarray(jax.nn.sigmoid(x[0] @ w_r))
    assert ((w > 0).sum(axis=1) == k).all() and (w[:, 3] > 0).all()
    picked = w > 0
    np.testing.assert_allclose(
        w[picked], (s * picked / ((s * picked).sum(1, keepdims=True)
                                  + 1e-6))[picked], rtol=1e-6)
    # the epsilon is the family's, and shows: the weights sum to less than 1
    # by sum/(sum + 1e-6), which 1e-20 would not
    sums = (s * picked).sum(1)
    np.testing.assert_allclose(w.sum(1), sums / (sums + 1e-6), rtol=1e-6)
    big = dict(m, router_norm_eps=0.5)
    w_big = np.asarray(reference().routing_weights(x, w_r, bias, big,
                                                   mm32))[0]
    np.testing.assert_allclose(w_big.sum(1), sums / (sums + 0.5), rtol=1e-6)


# ---------------------------------------------------------------------------
# the whole step: the tiny cell through the harness
# ---------------------------------------------------------------------------
def tiny_run(seed=7, trace=False, tmp=None):
    err = io.StringIO()
    result = runner.run(FIXTURES / "BENCHMARK.json", "tiny-hybrid.s128",
                        seed, 0.3, trace, search=[FIXTURES],
                        require_tpu=False, compile_cache=False,
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
    """Three expert layers in two groups (one attention layer, two
    convolution layers), numbered in the model's order."""
    counters = plain[0]["notes"]["counters"]
    every = {f"layer={i}": 4 * 128 * 2 for i in range(3)}
    assert counters["moe.pairs_routed"] == every
    assert counters["moe.pairs_dropped"] == dict.fromkeys(every, 0)
    held = counters["moe.pairs_held"]
    assert held.keys() == every.keys()
    assert all(0 < held[k] < 4 * 128 * 2 for k in held)
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
    control, and with half of the step's tokens left out — of four rows
    (two whole rows) and of one row (its leading half) — on one seed."""
    man = tiny_manifest()
    config, ref = man.config("tiny-hybrid"), reference()
    mix = trafficgen.load(man.find("traffic", "train.tiny-hybrid.json"))
    pool = trafficgen.make_pool(mix, config["model"], 11)[:2]
    one_row = [{"input_ids": b["input_ids"][:1]} for b in pool]
    params = drawn(ref.param_spec(config["model"]), 11)
    run = functools.partial(ref.run, config["model"],
                            config["train"]["optimizer"], params,
                            devices=jax.devices()[:1], rows_per_block=2)
    return {"float32": run(pool), "bfloat16": run(pool, precision="bfloat16"),
            "fp8": run(pool, precision="fp8"),
            "half": run(pool, row_share=0.5), "one_row": run(one_row),
            "half_of_one_row": run(one_row, row_share=0.5),
            "one_row_cut": run([{"input_ids": b["input_ids"][:, :64]}
                                for b in one_row])}


def test_reference_precisions_stand_in_order(readings):
    exact = readings["float32"]["first_grad"]
    bf16 = compare.diff_rel(readings["bfloat16"]["first_grad"], exact)
    fp8 = compare.diff_rel(readings["fp8"]["first_grad"], exact)
    assert 0 < bf16 < fp8 and fp8 > 3 * bf16
    assert all(np.isfinite(readings[k]["losses"]).all() for k in readings)


def test_half_of_the_tokens_left_out_moves_the_gradient(readings):
    exact, half = readings["float32"], readings["half"]
    assert compare.diff_rel(half["first_grad"], exact["first_grad"]) > 0.3
    assert abs(half["losses"][0] - exact["losses"][0]) > 1e-4


def test_row_share_of_one_row_keeps_its_leading_positions(readings):
    """`rows x share` under one row: the leading share of the row's
    positions, the mean over them — what the model gives the row cut to
    those positions (it is causal).  Whole rows where there are enough."""
    ref = reference()
    assert ref.kept(4, 128, 0.5) == (2, 128)
    assert ref.kept(1, 128, 0.5) == (1, 64)
    assert ref.kept(1, 8192, 0.5) == (1, 4096)
    assert ref.kept(4, 128, 0.25) == (1, 128)
    assert ref.kept(4, 128, 1.0) == (4, 128)
    whole, half, cut = (readings[k] for k in (
        "one_row", "half_of_one_row", "one_row_cut"))
    assert half["losses"] == pytest.approx(cut["losses"], rel=1e-6)
    assert compare.diff_rel(half["first_grad"], cut["first_grad"]) < 1e-5
    assert compare.diff_rel(half["first_grad"], whole["first_grad"]) > 0.3


def test_reference_leaves_the_selection_bias_where_it_was(readings):
    r = readings["float32"]
    for group in (ATTN_EXPERT, CONV_EXPERT):
        assert not np.asarray(
            r["first_grad"][group]["feed_forward.router_bias"]).any()
        assert not np.asarray(
            r["param_change"][group]["feed_forward.router_bias"]).any()
        assert np.asarray(r["param_change"][group]["feed_forward.w_in"]).any()
    assert np.asarray(r["param_change"][CONV_EXPERT]["operator.taps"]).any()


def test_reference_imports_nothing_of_the_program():
    text = (REPO / "benchmarks/references/lfm2_moe_lm.py").read_text()
    assert "paddle_tpu" not in text.replace("`", "").split('"""', 2)[2]
    assert "benchmarks" not in text.split('"""', 2)[2]


def test_the_tied_head_is_one_leaf():
    spec = reference().param_spec(tiny_model())
    assert set(spec["head"]) == {"final_norm.weight"}
    with pytest.raises(ValueError, match="ties"):
        reference().param_spec(tiny_model(tie_word_embeddings=False))


# ---------------------------------------------------------------------------
# the counts, by hand
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def real():
    man = Manifest(REPO / "BENCHMARK.json")
    return (man.config("lfm2-24b-a2b"),
            man.json_of("traffic", "train.b1.s8192"),
            man.module("opcounts", "lfm2_moe"))


def test_train_flops_per_token_by_hand(real):
    config, mix, oc = real
    conv = 2 * (2048 * 6144 + 2048 * 2048)
    attn = 2 * (2048 * 3072 + 2048 * 2048) + 8193 * 32 * 128
    expert = 2 * 2048 * 64 + 0.5 * 6 * 2048 * 1536
    dense_layer = conv + 6 * 2048 * 11776
    want = 3 * (dense_layer + (attn + expert) + 3 * (conv + expert)
                + 2 * 2048 * 8192)
    assert oc.train_flops_per_item(config["model"], mix) == \
        pytest.approx(want, rel=1e-12)
    assert want == pytest.approx(1.22e9, rel=0.005)      # ISSUE 32's figure
    assert dense_layer == pytest.approx(178e6, rel=0.005)
    assert attn + expert == pytest.approx(64e6, rel=0.01)
    assert oc.expected_pairs_per_token(config["model"]) == 0.5


def test_kernel_costs_by_hand(real):
    config, mix, oc = real
    core = oc.gqa_core_train(config["model"], mix)
    pairs = 8192 * 8193 / 2
    assert core["ops"] == pytest.approx(2 * 32 * pairs * 6 * 64, rel=1e-12)
    assert core["bytes"] == 2 * 8192 * 64 * (4 * 32 + 4 * 8)
    conv = oc.conv_mixer_train(config["model"], mix)
    matrices = 2048 * 6144 + 2048 * 2048
    assert conv["ops"] == 6 * 8192 * matrices * 4
    assert conv["bytes"] == 2 * (3 * matrices + 2 * 8192
                                 * (2048 + 6144 + 2048 + 2048)) * 4


def test_the_conv_scope_is_a_subscope_of_attn():
    reader = Manifest(REPO / "BENCHMARK.json").module("readers",
                                                      "trace_subscope_ms")
    how = Manifest(REPO / "BENCHMARK.json").json_of("layer_metrics",
                                                    "step.conv_ms")["params"]
    body = "jit(train_step)/transpose(jvp(encoder))/while/body/closed_call"
    assert reader.subscope_of(
        f"{body}/0/attn/operator/conv/checkpoint/dot_general",
        how["region"], how["subs"]) == "conv"
    assert reader.subscope_of(f"{body}/0/attn/operator/attn/core/x",
                              how["region"], how["subs"]) is None
    assert reader.subscope_of(f"{body}/0/ffn/conv/mul", how["region"],
                              how["subs"]) is None


# ---------------------------------------------------------------------------
# the configuration's file
# ---------------------------------------------------------------------------
PUBLISHED_WIDTHS = {
    "hidden_size": 2048, "num_attention_heads": 32, "num_key_value_heads": 8,
    "intermediate_size": 11776, "moe_intermediate_size": 1536,
    "num_experts_per_tok": 4, "conv_L_cache": 3, "conv_bias": False,
    "norm_eps": 1e-05, "norm_topk_prob": True, "routed_scaling_factor": 1,
    "use_expert_bias": True}


def test_the_file_holds_every_published_width(real):
    config = real[0]
    for key, value in PUBLISHED_WIDTHS.items():
        assert config[key] == value and config["model"][key] == value, key
    assert config["model"]["router_experts"] == 64
    assert config["model"]["head_dim"] == 64
    assert config["model"]["rope_theta"] == \
        config["rope_parameters"]["rope_theta"] == 1000000
    assert config["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                 "num_experts", "vocab_size", "layer_types"]
    published = config["published"]
    assert {k: published[k] for k in published if k != "layer_types"} == {
        "num_hidden_layers": 40, "num_dense_layers": 2, "num_experts": 64,
        "vocab_size": 65536}
    # the cut: published layers 1-5, one dense layer and one whole period
    assert config["layer_types"] == published["layer_types"][1:6] == [
        "conv", "full_attention", "conv", "conv", "conv"]
    assert (config["num_hidden_layers"], config["num_dense_layers"],
            config["num_experts"], config["vocab_size"]) == (5, 1, 8, 8192)
    assert config["vocab_size"] * 8 == published["vocab_size"]
    assert config["model"]["held_experts"] == [0, 8]
    assert "8 chips" in config["deployment"]
    assert config["departures"] and config["assumed"]
    assert config["assumed"].keys() >= {"tie_word_embeddings", "head_dim",
                                        "router_norm_eps", "in_proj_split"}
    # what the harness reads is what the file states at its top level
    for key in config["model"]:
        if key in config and key != "model":
            assert config[key] == config["model"][key], key


def test_the_file_holds_the_catalogs_row():
    if not CATALOG.exists():
        pytest.skip("the catalog is not on this machine")
    row = next(json.loads(line) for line in CATALOG.read_text().splitlines()
               if '"LFM2-24B-A2B"' in line)
    config = Manifest(REPO / "BENCHMARK.json").config("lfm2-24b-a2b")
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    # every layer kind in its published ratio: one attention in four
    kept = config["layer_types"][config["num_dense_layers"]:]
    assert kept.count("full_attention") * 4 == len(kept)


def test_the_parameters_are_the_issues_count(real):
    config = real[0]
    spec = Manifest(REPO / "BENCHMARK.json").module(
        "references", "lfm2_moe_lm").param_spec(config["model"])
    sizes = {g: sum(int(np.prod(shape)) for shape, _ in leaves.values())
             for g, leaves in spec.items()}
    assert sizes == {"embed": 16_777_216, "head": 2_048,
                     "run00_conv_dense": 89_139_200,
                     "run01_attention_expert": 86_118_592,
                     "run02_conv_expert": 3 * 92_416_064}
    assert sum(sizes.values()) == 469_285_248
