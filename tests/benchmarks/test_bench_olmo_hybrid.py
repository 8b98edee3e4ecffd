"""What `olmo-hybrid-7b` brought to the benchmark, at a tiny size on the CPU:
the plain reference (`references/olmo_hybrid_lm.py`, whose delta rule is
the recurrence itself) against the program over a whole cell through
`runner.run` with every block recomputed, the control and the planted
faults moving the numbers they should, the operation counts by hand at the
published sizes, the two sub-scope metrics' parameters, the configuration's
file holding the catalog's row, the cut and the parameter count."""
import functools
import io
import json
import math
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from bench_testlib import REPO, Manifest
# the two faults a one-chip training cell can have, planted in an entry's step
from test_bench_faults import half_batch_left_out, state_unchanged

from benchmarks.harness import compare, runner, trafficgen, weights

FIXTURES = pathlib.Path(__file__).resolve().parent / "fixtures_olmo_hybrid"
# the catalog of published architectures, its Olmo-Hybrid-7B row
CATALOG = FIXTURES / "catalog.jsonl"
CELL = "olmo-hybrid-7b.s4096"
LINEAR, FULL = "linear_attention", "full_attention"


def tiny_manifest():
    return Manifest(FIXTURES / "BENCHMARK.json", [FIXTURES])


def reference():
    return tiny_manifest().module("references", "olmo_hybrid_lm")


def drawn(spec, seed=5):
    return weights.maker(spec)(weights.seed_key(seed))


# ---------------------------------------------------------------------------
# the reference's own pieces, by hand
# ---------------------------------------------------------------------------
def test_reference_recurrence_by_hand():
    """The blocked, checkpointed rule of the reference against a Python loop
    over the positions in float64, and its state reaching across blocks."""
    ref = reference()
    k_ = [jax.random.fold_in(jax.random.PRNGKey(2), i) for i in range(5)]
    b, s, h, dk, dv = 1, 160, 2, 3, 4       # 2.5 blocks of SCAN_BLOCK
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(k_[0], (b, s, h, dk)))
    k = unit(jax.random.normal(k_[1], (b, s, h, dk)))
    v = jax.random.normal(k_[2], (b, s, h, dv))
    g = -0.02 * jax.nn.softplus(jax.random.normal(k_[3], (b, s, h)))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(k_[4], (b, s, h)))
    got = np.asarray(ref.recurrence(q, k, v, g, beta))
    S = np.zeros((b, h, dk, dv))
    qs, ks, vs, gs, bs = (np.asarray(t, np.float64)
                          for t in (q, k, v, g, beta))
    for t in range(s):
        kt = ks[:, t]
        move = np.eye(dk) - bs[:, t, :, None, None] * kt[..., :, None] \
            * kt[..., None, :]
        S = np.exp(gs[:, t])[..., None, None] * (move @ S) \
            + bs[:, t, :, None, None] * kt[..., :, None] * vs[:, t, :, None, :]
        o = np.einsum("bhkv,bhk->bhv", S, qs[:, t]) / math.sqrt(dk)
        np.testing.assert_allclose(got[:, t], o, atol=2e-5, rtol=2e-5)
    # past the first block's end, position 70 still reads position 60
    moved = np.asarray(ref.recurrence(q, k, v.at[:, 60].add(1.0), g, beta))
    assert np.abs(moved[:, 70] - got[:, 70]).max() > 1e-3
    assert ref.SCAN_BLOCK == 64


def test_reference_convolution_is_causal_four_taps_deep_without_bias():
    ref = reference()
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 32, 24))
    taps = jax.random.normal(jax.random.PRNGKey(5), (4, 24))
    base = ref.causal_conv(x, taps)
    moved = ref.causal_conv(x.at[:, 10].add(1.0), taps)
    changed = np.flatnonzero(np.abs(np.asarray(moved - base)).max((0, 2)))
    assert changed.tolist() == [10, 11, 12, 13]
    np.testing.assert_array_equal(ref.causal_conv(jnp.zeros_like(x), taps),
                                  0.0)


# ---------------------------------------------------------------------------
# the whole step: the tiny cell through the harness
# ---------------------------------------------------------------------------
def tiny_run(seed=7, trace=False, tmp=None):
    err = io.StringIO()
    result = runner.run(FIXTURES / "BENCHMARK.json", "tiny-olmo.s128",
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
    # float32 program (chunks of 32, every block recomputed) against the
    # float32 recurrence over three Adam steps
    assert len(result["compared"]) == 8
    for name, j in result["compared"].items():
        assert j["value"] <= j["limit"], name
    assert result["compared"]["loss_gap_1"]["value"] < 1e-5
    assert result["compared"]["grad_diff_gap"]["value"] < 1e-4
    assert set(result["metrics"]) == {"items_per_s_per_chip", "step_ms_p90",
                                      "setup_s"}
    assert len([ln for ln in err.splitlines()
                if ln.startswith("compared ")]) == 8


def test_tiny_cell_reports_the_rules_dispatch(plain):
    """`gdn.delta_calls{pass, chunk}` lands in the run's notes: three
    delta-rule layers, each traced forward and backward at the cell's
    chunk; the attention layer took no kernel off the chip."""
    counters = plain[0]["notes"]["counters"]
    assert counters["gdn.delta_calls"]["chunk=32,pass=fwd"] >= 3
    assert counters["gdn.delta_calls"]["chunk=32,pass=bwd"] >= 3
    assert set(counters) == {"pallas.kernel_calls", "pallas.fallbacks",
                             "gdn.delta_calls"}


def test_the_entry_sets_the_strategys_recompute():
    man = tiny_manifest()
    config = man.config("tiny-olmo")
    mix = trafficgen.load(man.find("traffic", "train.tiny-olmo.json"))
    entry = man.module("entries", "fleet_olmo_hybrid")
    t = entry.build(config, mix, jax.devices()[:1])
    assert t.trainer.recompute is True and t.trainer.recompute_policy is None
    assert list(t.trainer.model.groups) == [
        "run00_linear_attention", "run01_linear_attention",
        "run02_linear_attention", "run03_full_attention"]
    off = dict(config, train=dict(config["train"],
                                  recompute={"enable": False, "policy": None}))
    assert entry.build(off, mix, jax.devices()[:1]).trainer.recompute is False
    with pytest.raises(ValueError, match="dp meshes"):
        entry.build(config, dict(mix, mesh={"tp": 2}), jax.devices()[:1])


def test_tiny_cell_traced_off_the_chip_reports_no_device_metric(tmp_path):
    result, _ = tiny_run(seed=2 ** 31 + 77, trace=True, tmp=tmp_path)
    assert result["correct"] is True
    assert "loop.dispatch_ms" in result["metrics"]
    assert "device.idle_share" not in result["metrics"]
    assert "busy_s" not in result["device"]


@pytest.mark.parametrize("fault, over", [
    (state_unchanged, {"grad_norm_gap", "param_change_gap"}),
    (half_batch_left_out, {"grad_norm_gap", "grad_diff_gap"}),
])
def test_a_planted_fault_comes_out_not_correct(monkeypatch, fault, over):
    entry = tiny_manifest().module("entries", "fleet_olmo_hybrid")
    build = entry.build

    def build_broken(*a, **kw):
        t = build(*a, **kw)
        fault(t)
        return t

    monkeypatch.setattr(entry, "build", build_broken)
    result, _ = tiny_run(seed=5)
    assert result["correct"] is False
    failed = {n for n, j in result["compared"].items()
              if not j["value"] <= j["limit"]}
    assert over <= failed, result["compared"]
    if fault is state_unchanged:   # reads 1 by the measure, exactly
        assert result["compared"]["grad_norm_gap"]["value"] == 1.0
        assert result["compared"]["param_change_gap"]["value"] == 1.0


@pytest.fixture(scope="module")
def readings():
    """The reference in float32, in the yardstick's bfloat16, as the fp8
    control, and with half of the step's tokens left out — of four rows
    (two whole rows) and of one row (its leading half) — on one seed."""
    man = tiny_manifest()
    config, ref = man.config("tiny-olmo"), reference()
    mix = trafficgen.load(man.find("traffic", "train.tiny-olmo.json"))
    pool = trafficgen.make_pool(mix, config["model"], 11)[:2]
    one_row = [{"input_ids": b["input_ids"][:1]} for b in pool]
    params = drawn(ref.param_spec(config["model"]), 11)
    run = functools.partial(ref.run, config["model"],
                            config["train"]["optimizer"], params,
                            devices=jax.devices()[:1], rows_per_block=2)
    return {"params": params,
            "float32": run(pool), "bfloat16": run(pool, precision="bfloat16"),
            "fp8": run(pool, precision="fp8"),
            "half": run(pool, row_share=0.5), "one_row": run(one_row),
            "half_of_one_row": run(one_row, row_share=0.5),
            "one_row_cut": run([{"input_ids": b["input_ids"][:, :64]}
                                for b in one_row])}


def test_reference_precisions_stand_in_order(readings):
    """The fp8 control put in the program's place fails the number that
    tells precisions apart: over three times the bfloat16 yardstick."""
    exact = readings["float32"]["first_grad"]
    bf16 = compare.diff_rel(readings["bfloat16"]["first_grad"], exact)
    fp8 = compare.diff_rel(readings["fp8"]["first_grad"], exact)
    assert 0 < bf16 < fp8 and fp8 > 3 * bf16
    assert all(np.isfinite(readings[k]["losses"]).all()
               for k in readings if k != "params")


def test_half_of_the_tokens_left_out_moves_the_gradient(readings):
    exact, half = readings["float32"], readings["half"]
    assert compare.diff_rel(half["first_grad"], exact["first_grad"]) > 0.3
    assert abs(half["losses"][0] - exact["losses"][0]) > 1e-5


def test_row_share_of_one_row_keeps_its_leading_positions(readings):
    """`rows x share` under one row: the leading share of the row's
    positions, the mean over them — what the model gives the row cut to
    those positions (every mixer is causal, the delta rule too)."""
    ref = reference()
    assert ref.kept(1, 4096, 0.5) == (1, 2048)
    whole, half, cut = (readings[k] for k in (
        "one_row", "half_of_one_row", "one_row_cut"))
    assert half["losses"] == pytest.approx(cut["losses"], rel=1e-6)
    assert compare.diff_rel(half["first_grad"], cut["first_grad"]) < 1e-5
    assert compare.diff_rel(half["first_grad"], whole["first_grad"]) > 0.3


def test_reference_does_not_consume_its_parameters_and_steps_every_leaf(
        readings):
    man = tiny_manifest()
    config, ref = man.config("tiny-olmo"), reference()
    again = drawn(ref.param_spec(config["model"]), 11)
    for a, b in zip(jax.tree_util.tree_leaves(readings["params"]),
                    jax.tree_util.tree_leaves(again)):
        np.testing.assert_array_equal(a, b)
    r = readings["float32"]
    for tree in (r["first_grad"], r["param_change"]):
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
            assert np.asarray(leaf).any(), path


def test_reference_imports_nothing_of_the_program():
    text = (REPO / "benchmarks/references/olmo_hybrid_lm.py").read_text()
    assert "paddle_tpu" not in text.replace("`", "").split('"""', 2)[2]
    assert "benchmarks" not in text.split('"""', 2)[2]


@pytest.mark.parametrize("over", [
    {"tie_word_embeddings": True}, {"attention_bias": True},
    {"rope_theta": 10000.0}, {"linear_num_value_heads": 8},
    {"linear_allow_neg_eigval": False}])
def test_the_reference_refuses_other_forms(over):
    model = dict(tiny_manifest().config("tiny-olmo")["model"], **over)
    with pytest.raises(ValueError, match="this reference"):
        reference().param_spec(model)


# ---------------------------------------------------------------------------
# the counts, by hand
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def real():
    man = Manifest(REPO / "BENCHMARK.json")
    return (man.config("olmo-hybrid-7b"),
            man.json_of("traffic", "train.b1.s4096"),
            man.module("opcounts", "olmo_hybrid"))


def test_train_flops_per_token_by_hand(real):
    config, mix, oc = real
    # a head at chunk 64: QK^T 65/2 pairs, KK^T 63/2, T(bK), T(bV), the
    # scores times the written values, and Q S, W S, K~^T Delta
    head = 65 * 96 + 63 * 96 + 65 * 96 + 65 * 192 + 65 * 192 + 6 * 96 * 192
    assert oc.delta_flops_per_token(config["model"]) == 30 * head \
        == 4_622_400
    swiglu = 6 * 3840 * 11008
    linear = 2 * (3840 * 17340 + 5760 * 3840) + 30 * head + swiglu
    full = 2 * (3840 * 11520 + 3840 * 3840) + 4097 * 30 * 256 + swiglu
    want = 3 * (3 * linear + full + 2 * 3840 * 12544)
    assert oc.train_flops_per_item(config["model"], mix) == \
        pytest.approx(want, rel=1e-12)
    assert want == 5_419_068_480                    # 5.42 G a token


def test_kernel_costs_by_hand(real):
    config, mix, oc = real
    delta = oc.delta_train(config["model"], mix)
    assert delta["ops"] == 3 * 4096 * 4_622_400 * 3
    assert delta["bytes"] == 2 * 4096 * (2 * (2 * 2880 + 2 * 5760)
                                         + 4 * 2 * 30) * 3
    mixer = oc.gdn_mixer_train(config["model"], mix)
    matrices = 3840 * 17340 + 5760 * 3840
    assert mixer["ops"] == 6 * 4096 * matrices * 3 + delta["ops"]
    assert mixer["bytes"] == 2 * (3 * matrices + 2 * 4096 * (
        3840 + 17340 + 11520 + 5760 + 3840)) * 3
    # the mixer is compute-bound by the chip's peaks, the rule alone by its
    # bytes
    assert mixer["ops"] / 197e12 > mixer["bytes"] / 819e9
    assert delta["ops"] / 197e12 < delta["bytes"] / 819e9


def test_gdn_and_delta_are_subscopes_of_attn():
    """`step.gdn_ms` reads everything under attn/gdn, the rule included;
    `step.delta_ms` the rule alone; neither the attention layer's work."""
    man = Manifest(REPO / "BENCHMARK.json")
    reader = man.module("readers", "trace_subscope_ms")
    gdn = man.json_of("layer_metrics", "step.gdn_ms")["params"]
    delta = man.json_of("layer_metrics", "step.delta_ms")["params"]
    assert (gdn["region"], gdn["sub"], gdn["subs"]) == ("attn", "gdn", ["gdn"])
    assert (delta["region"], delta["sub"], delta["subs"]) == (
        "attn", "delta", ["gdn", "delta"])
    body = "jit(train_step)/transpose(jvp(encoder))/checkpoint"
    in_proj = f"{body}/attn/mixer/gdn/proj/in_proj/dot_general"
    rule = f"{body}/attn/mixer/gdn/delta/delta/transpose(jvp())/while/body/" \
        "closed_call/dot_general"
    core = f"{body}/attn/mixer/attn/core/pallas_call"
    assert reader.subscope_of(in_proj, "attn", gdn["subs"]) == "gdn"
    assert reader.subscope_of(rule, "attn", gdn["subs"]) == "gdn"
    assert reader.subscope_of(in_proj, "attn", delta["subs"]) == "gdn"
    assert reader.subscope_of(rule, "attn", delta["subs"]) == "delta"
    for how in (gdn, delta):
        assert reader.subscope_of(core, "attn", how["subs"]) is None
        assert reader.subscope_of(f"{body}/ffn/delta/mul", "attn",
                                  how["subs"]) is None
    for name, cost in (("gdn.mixer.roofline", "gdn_mixer_train"),
                       ("gdn.delta.roofline", "delta_train")):
        how = man.json_of("layer_metrics", name)
        assert how["reader"] == "trace_subscope_roofline"
        assert how["params"]["cost"] == f"olmo_hybrid:{cost}"
        assert callable(man.function("opcounts", how["params"]["cost"]))


def test_the_manifest_gives_the_cell_its_metrics():
    man = Manifest(REPO / "BENCHMARK.json")
    cell = man.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "olmo-hybrid-7b", "train.b1.s4096", 1)
    names = {m["name"] for m in man.metrics_of(CELL, "per_layer")}
    assert names == {
        "loop.dispatch_ms", "step.hbm_peak_gib", "device.idle_share",
        "step.attn_ms", "step.ffn_ms", "step.ln_ms", "step.scan_ms",
        "step.head_ms", "step.optimizer_ms", "step.unscoped_share",
        "step.mfu.olmo_hybrid", "step.gdn_ms", "step.delta_ms",
        "gdn.mixer.roofline", "gdn.delta.roofline"}
    for name in names:
        how = man.json_of("layer_metrics", name)
        assert man.find("readers", how["reader"] + ".py").exists()
    limits = man.json_of("limits", CELL)["numbers"]
    assert limits["nonfinite_losses"]["limit"] == 0
    assert limits["compiles_in_window"]["limit"] == 0
    assert limits["grad_diff_ratio"]["limit"] is not None


# ---------------------------------------------------------------------------
# the configuration's file
# ---------------------------------------------------------------------------
PUBLISHED_WIDTHS = {
    "hidden_size": 3840, "intermediate_size": 11008,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
    "attention_bias": False, "hidden_act": "silu"}


def test_the_file_holds_every_published_width(real):
    config = real[0]
    for key, value in PUBLISHED_WIDTHS.items():
        assert config[key] == value and config["model"][key] == value, key
    assert config["model"]["head_dim"] == 128
    assert config["model"]["rope_theta"] is None
    assert config["rope_parameters"] == {"rope_theta": None}
    assert config["reduced"] == ["num_hidden_layers", "layer_types",
                                 "vocab_size"]
    published = config["published"]
    assert {k: published[k] for k in published if k != "layer_types"} == {
        "num_hidden_layers": 32, "vocab_size": 100352}
    # the cut: published layers 0-3, one whole period, 3 : 1 as published
    assert config["layer_types"] == published["layer_types"][:4] == \
        [LINEAR] * 3 + [FULL]
    assert published["layer_types"] == ([LINEAR] * 3 + [FULL]) * 8
    assert (config["num_hidden_layers"], config["vocab_size"]) == (4, 12544)
    assert config["vocab_size"] * 8 == published["vocab_size"]
    assert "8 chips" in config["deployment"] and \
        "eight pipeline stages" in config["deployment"]
    assert config["train"]["recompute"] == {"enable": True, "policy": None}
    assert config["train"]["compute_dtype"] == "bfloat16"
    assert config["reference_yardstick"] == "bfloat16"
    assert config["model"]["linear_chunk_size"] == 64
    assert config["departures"].keys() >= {"delta_rule_leaves", "recompute",
                                           "layout"}
    assert config["assumed"].keys() >= {
        "initializer_range", "head_dim", "rope_theta", "norm_placement",
        "qk_norm", "gated_deltanet", "linear_chunk_size"}
    for key in config["model"]:
        if key in config and key != "model":
            assert config[key] == config["model"][key], key


def test_the_file_holds_the_catalogs_row():
    row = next(json.loads(line) for line in CATALOG.read_text().splitlines()
               if '"Olmo-Hybrid-7B"' in line)
    man = Manifest(REPO / "BENCHMARK.json")
    config = man.config("olmo-hybrid-7b")
    entry = next(c for c in man.data["configs"]
                 if c["name"] == "olmo-hybrid-7b")
    assert config["source"] == entry["source"] == row["source_url"]
    assert entry["reduced"] == config["reduced"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value
        else:
            assert config[key] == value, key


def test_the_parameters_are_the_published_count(real):
    config = real[0]
    spec = Manifest(REPO / "BENCHMARK.json").module(
        "references", "olmo_hybrid_lm").param_spec(config["model"])
    sizes = {g: sum(int(np.prod(shape)) for shape, _ in leaves.values())
             for g, leaves in spec.items()}
    linear, full = 215_570_172, 185_809_920            # the published count
    assert sizes == {"embed": 48_168_960, "head": 48_172_800,
                     "run00_linear_attention": linear,
                     "run01_linear_attention": linear,
                     "run02_linear_attention": linear,
                     "run03_full_attention": full}
    assert sum(sizes.values()) == 928_862_196
    one = {k: int(np.prod(shape[1:])) for k, (shape, _) in
           spec["run00_linear_attention"].items() if k.startswith("mixer.")}
    # q, k 11,059,200 each; v, g 22,118,400 each; a, b 115,200 each
    assert one == {"mixer.in_proj.weight": 2 * 11_059_200 + 2 * 22_118_400
                   + 2 * 115_200, "mixer.taps": 46_080,
                   "mixer.dt_bias": 30, "mixer.a_log": 30,
                   "mixer.out_norm.weight": 192,
                   "mixer.out_proj.weight": 22_118_400}
    kinds = {k: kind for k, (_, kind) in
             spec["run00_linear_attention"].items()}
    assert (kinds["mixer.a_log"], kinds["mixer.dt_bias"],
            kinds["mixer.out_norm.weight"]) == ("bias", "bias", "scale")
