"""What `granite-4.0-h-micro` brought to the benchmark, at a tiny size on the
CPU: the plain reference (`references/granite_hybrid_lm.py`, whose scan is
the recurrence itself) against the program mixer by mixer, layer by layer
and over a whole cell through `runner.run` with every block recomputed, the
control and the planted faults moving the numbers they should, the
operation counts by hand at the published sizes, the two sub-scope metrics'
parameters, and the configuration's file holding the catalog's row."""
import functools
import io
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from bench_testlib import REPO, Manifest
# the two faults a one-chip training cell can have, planted in an entry's step
from test_bench_faults import half_batch_left_out, state_unchanged

from benchmarks.harness import compare, runner, trafficgen, weights

FIXTURES = pathlib.Path(__file__).resolve().parent / "fixtures_granite_hybrid"
CATALOG = pathlib.Path("/opt/skills/guides/model-configs/architectures.jsonl")
CELL = "granite-4.0-h-micro.s4096"
MAMBA_RUN, ATTN_RUN = "run00_mamba", "run01_attention"


def tiny_manifest():
    return Manifest(FIXTURES / "BENCHMARK.json", [FIXTURES])


def tiny_model(**over):
    return dict(tiny_manifest().config("tiny-granite")["model"], **over)


def reference():
    return tiny_manifest().module("references", "granite_hybrid_lm")


def drawn(spec, seed=5):
    return weights.maker(spec)(weights.seed_key(seed))


def mm32(spec, a, b):
    return reference()._mm(spec, a, b, "float32")


def program_config(m):
    from paddle_tpu.text.granite_hybrid import GraniteHybridConfig
    return GraniteHybridConfig(**{k: v for k, v in m.items()
                                  if k not in ("head_dim",
                                               "type_vocab_size")})


def under(prefix, p):
    return {k[len(prefix):]: v for k, v in p.items() if k.startswith(prefix)}


# ---------------------------------------------------------------------------
# mixer by mixer, layer by layer: the program's Layers against the
# reference's functions
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny():
    """The tiny model's sizes, the program's config for them, one layer's
    weights of a Mamba-2 run and of the attention run in the reference's
    layout (the leading layer axis taken off; the state-space leaves and the
    taps redrawn O(1), so that each shows), and an input."""
    m = tiny_model()
    spec = reference().param_spec(m)
    assert [name for name, *_ in reference().runs(m)] == [
        MAMBA_RUN, ATTN_RUN, "run02_mamba"]
    p = {g: {k: v[0] for k, v in drawn(spec[g]).items()}
         for g in (MAMBA_RUN, ATTN_RUN)}
    key = jax.random.PRNGKey(8)
    for i, (name, scale) in enumerate([
            ("mixer.dt_bias", 0.5), ("mixer.a_log", 0.5),
            ("mixer.taps", 0.5), ("mixer.taps_bias", 0.3)]):
        p[MAMBA_RUN][name] = scale * jax.random.normal(
            jax.random.fold_in(key, i), p[MAMBA_RUN][name].shape)
    p[MAMBA_RUN]["mixer.in_proj.weight"] = 10 * \
        p[MAMBA_RUN]["mixer.in_proj.weight"]
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 128, m["hidden_size"]))
    return m, program_config(m), p, x


def test_mamba_mixer_is_the_references(tiny):
    """The chunked scan inside the program's mixer (4 chunks of 32) against
    the reference's recurrence, position by position."""
    from paddle_tpu.autograd import functional_call
    from paddle_tpu.text.granite_hybrid import Mamba2Mixer
    m, cfg, p, x = tiny
    got = functional_call(Mamba2Mixer(cfg), under("mixer.", p[MAMBA_RUN]),
                          (x,))
    want = reference().mamba(x, p[MAMBA_RUN], m, mm32)
    np.testing.assert_allclose(got, want, atol=5e-6, rtol=1e-4)
    assert float(jnp.abs(want).max()) > 0.1


def test_reference_recurrence_by_hand():
    """The blocked, checkpointed scan of the reference against a Python loop
    over the positions, and its state reaching across the blocks."""
    ref = reference()
    k = [jax.random.fold_in(jax.random.PRNGKey(2), i) for i in range(6)]
    b, s, h, p, n = 1, 160, 2, 3, 4           # 2.5 blocks of SCAN_BLOCK: 5 x 32
    x, B, C = (jax.random.normal(k[0], (b, s, h, p)),
               jax.random.normal(k[1], (b, s, n)),
               jax.random.normal(k[2], (b, s, n)))
    dt = 0.05 * jax.nn.softplus(jax.random.normal(k[3], (b, s, h)))
    A, D = -jnp.exp(jax.random.normal(k[4], (h,))), \
        jax.random.normal(k[5], (h,))
    got = np.asarray(ref.recurrence(x, dt, A, B, C, D))
    S = np.zeros((b, h, p, n))
    xs, dts, Bs, Cs = (np.asarray(t, np.float64) for t in (x, dt, B, C))
    for t in range(s):
        S = np.exp(dts[:, t] * np.asarray(A))[..., None, None] * S + \
            (dts[:, t, :, None] * xs[:, t])[..., None] * \
            Bs[:, t, None, None, :]
        y = (S * Cs[:, t, None, None, :]).sum(-1) + \
            np.asarray(D)[:, None] * xs[:, t]
        np.testing.assert_allclose(got[:, t], y, atol=2e-5, rtol=2e-5)
    # slow decay: position 159 still reads position 0
    moved = np.asarray(ref.recurrence(x.at[:, 0].add(1.0), dt, A, B, C, D))
    assert np.abs(moved[:, 159] - got[:, 159]).max() > 1e-6
    assert ref.SCAN_BLOCK == 64


def test_reference_convolution_is_causal_four_taps_deep_and_biased():
    from paddle_tpu.nn import functional as F
    ref = reference()
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 32, 24))
    taps = jax.random.normal(jax.random.PRNGKey(5), (4, 24))
    bias = jax.random.normal(jax.random.PRNGKey(6), (24,))
    base = ref.causal_conv(x, taps, bias)
    moved = ref.causal_conv(x.at[:, 10].add(1.0), taps, bias)
    changed = np.flatnonzero(np.abs(np.asarray(moved - base)).max((0, 2)))
    assert changed.tolist() == [10, 11, 12, 13]
    want = F.conv1d(x.transpose(0, 2, 1), taps.T[:, None, :], bias,
                    padding=3, groups=24)[..., :32].transpose(0, 2, 1)
    np.testing.assert_allclose(base, want, atol=1e-5)
    np.testing.assert_allclose(
        ref.causal_conv(jnp.zeros_like(x), taps, bias)[0, 0], bias)


def test_attention_is_the_references(tiny):
    from paddle_tpu.autograd import functional_call
    from paddle_tpu.text.granite_hybrid import NopeAttention
    m, cfg, p, x = tiny
    q = {**p[ATTN_RUN], "mixer.qkv_proj.weight":
         30 * p[ATTN_RUN]["mixer.qkv_proj.weight"]}
    got = functional_call(NopeAttention(cfg), under("mixer.", q), (x,))
    want = reference().attention(x, q, m, mm32)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=1e-4)


def test_reference_attention_by_hand(tiny):
    """The reference's grouped einsum against attention written head by
    head: query head j reads key/value head j // 2, no position anywhere,
    the scores times 1/64 and not 1/sqrt(16)."""
    m, _, p, x = tiny
    q_ = {**p[ATTN_RUN], "mixer.qkv_proj.weight":
          30 * p[ATTN_RUN]["mixer.qkv_proj.weight"]}
    h, kv, d, s = 4, 2, 16, x.shape[1]
    qkv = x @ q_["mixer.qkv_proj.weight"]
    q = qkv[..., :h * d].reshape(2, s, h, d)
    k = qkv[..., h * d:(h + kv) * d].reshape(2, s, kv, d)
    v = qkv[..., (h + kv) * d:].reshape(2, s, kv, d)
    outs = []
    for j in range(h):
        sc = jnp.einsum("bqd,bkd->bqk", q[:, :, j], k[:, :, j // 2]) / 64.0
        sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
        outs.append(jax.nn.softmax(sc, -1) @ v[:, :, j // 2])
    want = jnp.concatenate(outs, -1) @ q_["mixer.out_proj.weight"]
    got = reference().attention(x, q_, m, mm32)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=1e-4)
    other = reference().attention(x, q_, dict(m, attention_multiplier=0.25),
                                  mm32)
    assert float(jnp.abs(other - got).max()) > 1e-4


@pytest.mark.parametrize("group, mixer", [(MAMBA_RUN, "mamba"),
                                          (ATTN_RUN, "attention")])
def test_block_and_its_gradient_are_the_references(tiny, group, mixer):
    from paddle_tpu.autograd import functional_call
    from paddle_tpu.text.granite_hybrid import GraniteHybridBlock
    m, cfg, p, x = tiny
    block, p = GraniteHybridBlock(cfg, mixer), p[group]

    def program(p, x):
        return jnp.sum(jnp.square(functional_call(block, p, (x,))))

    def plain(p, x):
        return jnp.sum(jnp.square(reference()._block(
            x, p, m, "float32", mixer=mixer)))

    a, ga = jax.value_and_grad(program)(p, x)
    b, gb = jax.value_and_grad(plain)(p, x)
    assert float(a) == pytest.approx(float(b), rel=1e-5)
    assert set(ga) == set(gb)
    for k in gb:
        # the worst leaf against its own largest entry: float32 round-off
        # through two norms and a scan of 128 positions in another order
        scale = float(jnp.max(jnp.abs(gb[k]))) or 1.0
        assert scale > 0 and \
            float(jnp.max(jnp.abs(ga[k] - gb[k]))) / scale < 1e-4, k
    # the residual multiplier is in the block: y - x is 0.22 of the branches
    y = reference()._block(x, p, dict(m, residual_multiplier=0.0), "float32",
                           mixer=mixer)
    np.testing.assert_array_equal(y, x)


# ---------------------------------------------------------------------------
# the whole step: the tiny cell through the harness
# ---------------------------------------------------------------------------
def tiny_run(seed=7, trace=False, tmp=None):
    err = io.StringIO()
    result = runner.run(FIXTURES / "BENCHMARK.json", "tiny-granite.s128",
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


def test_tiny_cell_reports_the_scans_dispatch(plain):
    """`ssm.scan_calls{impl, chunk}` lands in the run's notes: two runs of
    Mamba-2 blocks, each traced once at the cell's chunk; the attention
    layer took no kernel off the chip and nothing fell back."""
    counters = plain[0]["notes"]["counters"]
    assert counters["ssm.scan_calls"].keys() >= {"chunk=32,impl=xla"}
    assert counters["ssm.scan_calls"]["chunk=32,impl=xla"] >= 2
    assert set(counters) == {"pallas.kernel_calls", "pallas.fallbacks",
                             "ssm.scan_calls"}


def test_the_entry_sets_the_strategys_recompute():
    man = tiny_manifest()
    config = man.config("tiny-granite")
    mix = trafficgen.load(man.find("traffic", "train.tiny-granite.json"))
    entry = man.module("entries", "fleet_granite_hybrid")
    t = entry.build(config, mix, jax.devices()[:1])
    assert t.trainer.recompute is True and t.trainer.recompute_policy is None
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
    entry = tiny_manifest().module("entries", "fleet_granite_hybrid")
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
    config, ref = man.config("tiny-granite"), reference()
    mix = trafficgen.load(man.find("traffic", "train.tiny-granite.json"))
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
    those positions (every mixer is causal, the state-space scan too)."""
    ref = reference()
    assert ref.kept(4, 128, 0.5) == (2, 128)
    assert ref.kept(1, 128, 0.5) == (1, 64)
    assert ref.kept(1, 4096, 0.5) == (1, 2048)
    whole, half, cut = (readings[k] for k in (
        "one_row", "half_of_one_row", "one_row_cut"))
    assert half["losses"] == pytest.approx(cut["losses"], rel=1e-6)
    assert compare.diff_rel(half["first_grad"], cut["first_grad"]) < 1e-5
    assert compare.diff_rel(half["first_grad"], whole["first_grad"]) > 0.3


def test_reference_does_not_consume_its_parameters_and_steps_every_leaf(
        readings):
    """`params` comes back as it went in (the first update writes a copy),
    every leaf takes a gradient and moves, and two blocks of rows add up to
    what one block of all the rows gives."""
    man = tiny_manifest()
    config, ref = man.config("tiny-granite"), reference()
    again = drawn(ref.param_spec(config["model"]), 11)
    for a, b in zip(jax.tree_util.tree_leaves(readings["params"]),
                    jax.tree_util.tree_leaves(again)):
        np.testing.assert_array_equal(a, b)
    r = readings["float32"]
    for tree in (r["first_grad"], r["param_change"]):
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
            assert np.asarray(leaf).any(), path
    mix = trafficgen.load(man.find("traffic", "train.tiny-granite.json"))
    pool = trafficgen.make_pool(mix, config["model"], 11)[:1]
    one_block = ref.run(config["model"], config["train"]["optimizer"],
                        readings["params"], pool, devices=jax.devices()[:1],
                        rows_per_block=4)
    assert one_block["losses"][0] == pytest.approx(r["losses"][0], rel=1e-6)
    assert compare.diff_rel(one_block["first_grad"], r["first_grad"]) < 1e-5


def test_reference_imports_nothing_of_the_program():
    text = (REPO / "benchmarks/references/granite_hybrid_lm.py").read_text()
    assert "paddle_tpu" not in text.replace("`", "").split('"""', 2)[2]
    assert "benchmarks" not in text.split('"""', 2)[2]


def test_the_tied_head_is_one_leaf_and_the_reference_refuses_other_forms():
    spec = reference().param_spec(tiny_model())
    assert set(spec["head"]) == {"final_norm.weight"}
    for over in ({"tie_word_embeddings": False}, {"mamba_n_groups": 2},
                 {"num_local_experts": 4}, {"position_embedding_type": "rope"}):
        with pytest.raises(ValueError, match="this reference"):
            reference().param_spec(tiny_model(**over))


# ---------------------------------------------------------------------------
# the counts, by hand
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def real():
    man = Manifest(REPO / "BENCHMARK.json")
    return (man.config("granite-4.0-h-micro"),
            man.json_of("traffic", "train.b1.s4096"),
            man.module("opcounts", "granite_hybrid"))


def test_train_flops_per_token_by_hand(real):
    config, mix, oc = real
    scan = 128.5 * 2 * 128 + 128.5 * 2 * 4096 + 2 * (2 * 128 * 4096)
    assert oc.scan_flops_per_token(config["model"]) == scan == 3_182_720
    swiglu = 6 * 2048 * 8192
    mamba = 2 * (2048 * 8512 + 4096 * 2048) + scan + swiglu
    attn = 2 * (2048 * 3072 + 2048 * 2048) + 4097 * 32 * 128 + swiglu
    want = 3 * (9 * mamba + attn + 2 * 2048 * 12544)
    assert oc.train_flops_per_item(config["model"], mix) == \
        pytest.approx(want, rel=1e-12)
    assert want == 4_767_575_424                         # ISSUE 34: 4.77 G
    assert 2 * (2048 * 8512 + 4096 * 2048) == pytest.approx(51.6e6, rel=2e-3)


def test_kernel_costs_by_hand(real):
    config, mix, oc = real
    ssd = oc.ssd_train(config["model"], mix)
    assert ssd["ops"] == 3 * 4096 * 3_182_720 * 9
    assert ssd["bytes"] == 2 * 2 * 4096 * (4096 + 128 + 128 + 64 + 4096) * 9
    mixer = oc.ssm_mixer_train(config["model"], mix)
    matrices = 2048 * 8512 + 4096 * 2048
    assert mixer["ops"] == 6 * 4096 * matrices * 9 + ssd["ops"]
    assert mixer["bytes"] == 2 * (3 * matrices + 2 * 4096 * (
        2048 + 8512 + 4352 + 4096 + 2048)) * 9
    # compute-bound both, by the chip's peaks
    for cost in (ssd, mixer):
        assert cost["ops"] / 197e12 > cost["bytes"] / 819e9


def test_ssm_and_ssd_are_subscopes_of_attn():
    """`step.ssm_ms` reads everything under attn/ssm, the scan included;
    `step.ssd_ms` the scan alone; neither the attention layer's work."""
    man = Manifest(REPO / "BENCHMARK.json")
    reader = man.module("readers", "trace_subscope_ms")
    ssm = man.json_of("layer_metrics", "step.ssm_ms")["params"]
    ssd = man.json_of("layer_metrics", "step.ssd_ms")["params"]
    assert (ssm["region"], ssm["sub"], ssm["subs"]) == ("attn", "ssm", ["ssm"])
    assert (ssd["region"], ssd["sub"]) == ("attn", "ssd")
    body = "jit(train_step)/transpose(jvp(encoder))/while/body/checkpoint"
    in_proj = f"{body}/0/attn/mixer/ssm/in_proj/dot_general"
    scan = f"{body}/0/attn/mixer/ssm/ssd/while/body/mul"
    core = f"{body}/0/attn/mixer/attn/core/pallas_call"
    assert reader.subscope_of(in_proj, "attn", ssm["subs"]) == "ssm"
    assert reader.subscope_of(scan, "attn", ssm["subs"]) == "ssm"
    assert reader.subscope_of(in_proj, "attn", ssd["subs"]) == "ssm"
    assert reader.subscope_of(scan, "attn", ssd["subs"]) == "ssd"
    for how in (ssm, ssd):
        assert reader.subscope_of(core, "attn", how["subs"]) is None
        assert reader.subscope_of(f"{body}/0/ffn/ssd/mul", "attn",
                                  how["subs"]) is None
    for name, cost in (("ssm.mixer.roofline", "ssm_mixer_train"),
                       ("ssm.ssd.roofline", "ssd_train")):
        how = man.json_of("layer_metrics", name)
        assert how["reader"] == "trace_subscope_roofline"
        assert how["params"]["cost"] == f"granite_hybrid:{cost}"
        assert callable(man.function("opcounts", how["params"]["cost"]))


def test_the_manifest_gives_the_cell_its_metrics():
    man = Manifest(REPO / "BENCHMARK.json")
    cell = man.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "granite-4.0-h-micro", "train.b1.s4096", 1)
    names = {m["name"] for m in man.metrics_of(CELL, "per_layer")}
    assert names == {
        "loop.dispatch_ms", "step.hbm_peak_gib", "device.idle_share",
        "step.attn_ms", "step.ffn_ms", "step.ln_ms", "step.scan_ms",
        "step.head_ms", "step.optimizer_ms", "step.unscoped_share",
        "step.mfu.granite_hybrid", "step.ssm_ms", "step.ssd_ms",
        "ssm.mixer.roofline", "ssm.ssd.roofline"}
    for name in names:
        how = man.json_of("layer_metrics", name)
        assert man.find("readers", how["reader"] + ".py").exists()
    mix = man.json_of("traffic", cell["traffic"])
    assert (mix["batch_per_chip"], mix["seq"], mix["chips"], mix["pool"],
            mix["in_flight"], mix["check_steps"], mix["warm_steps"],
            mix["trace_steps"], mix["masked_share"], mix["mesh"]) == (
        1, 4096, 1, 8, 2, 3, 3, 8, 0.0, {"dp": 1})
    limits = man.json_of("limits", CELL)["numbers"]
    assert limits["nonfinite_losses"]["limit"] == 0
    assert limits["compiles_in_window"]["limit"] == 0
    assert limits["grad_diff_ratio"]["limit"] is not None


# ---------------------------------------------------------------------------
# the configuration's file
# ---------------------------------------------------------------------------
PUBLISHED_WIDTHS = {
    "hidden_size": 2048, "num_attention_heads": 32, "num_key_value_heads": 8,
    "shared_intermediate_size": 8192, "mamba_d_state": 128,
    "mamba_d_head": 64, "mamba_n_heads": 64, "mamba_n_groups": 1,
    "mamba_d_conv": 4, "mamba_expand": 2, "mamba_chunk_size": 256,
    "mamba_conv_bias": True, "mamba_proj_bias": False,
    "attention_multiplier": 0.015625, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "logits_scaling": 8, "rms_norm_eps": 1e-05,
    "tie_word_embeddings": True, "position_embedding_type": "nope",
    "num_local_experts": 0}


def test_the_file_holds_every_published_width(real):
    config = real[0]
    for key, value in PUBLISHED_WIDTHS.items():
        assert config[key] == value and config["model"][key] == value, key
    assert config["model"]["head_dim"] == 64
    assert config["reduced"] == ["num_hidden_layers", "layer_types",
                                 "vocab_size"]
    published = config["published"]
    assert {k: published[k] for k in published if k != "layer_types"} == {
        "num_hidden_layers": 40, "vocab_size": 100352}
    # the cut: published layers 0-9, one whole period, 9 : 1 as published
    assert config["layer_types"] == published["layer_types"][:10] == \
        ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert published["layer_types"].count("attention") * 10 == 40
    assert (config["num_hidden_layers"], config["vocab_size"]) == (10, 12544)
    assert config["vocab_size"] * 8 == published["vocab_size"]
    assert "8 chips" in config["deployment"] and \
        "four pipeline stages" in config["deployment"]
    assert config["train"]["recompute"] == {"enable": True, "policy": None}
    assert config["train"]["compute_dtype"] == "bfloat16"
    assert config["reference_yardstick"] == "bfloat16"
    assert config["departures"].keys() >= {"state_space_leaves", "recompute",
                                           "layout"}
    assert config["assumed"].keys() >= {
        "initializer_range", "head_dim", "time_step_limit", "in_proj_split",
        "rope_theta"}
    # what the harness reads is what the file states at its top level
    for key in config["model"]:
        if key in config and key != "model":
            assert config[key] == config["model"][key], key


def test_the_file_holds_the_catalogs_row():
    if not CATALOG.exists():
        pytest.skip("the catalog is not on this machine")
    row = next(json.loads(line) for line in CATALOG.read_text().splitlines()
               if '"granite-4.0-h-micro"' in line)
    man = Manifest(REPO / "BENCHMARK.json")
    config = man.config("granite-4.0-h-micro")
    entry = next(c for c in man.data["configs"]
                 if c["name"] == "granite-4.0-h-micro")
    assert config["source"] == entry["source"] == row["source_url"]
    assert entry["reduced"] == config["reduced"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value
        else:
            assert config[key] == value, key


def test_the_parameters_are_the_issues_count(real):
    config = real[0]
    spec = Manifest(REPO / "BENCHMARK.json").module(
        "references", "granite_hybrid_lm").param_spec(config["model"])
    sizes = {g: sum(int(np.prod(shape)) for shape, _ in leaves.values())
             for g, leaves in spec.items()}
    mamba, attn = 76_182_976, 60_821_504              # ISSUE 34's table
    assert sizes == {"embed": 25_690_112, "head": 2_048,
                     "run00_mamba": 5 * mamba, "run01_attention": attn,
                     "run02_mamba": 4 * mamba}
    assert sum(sizes.values()) == 772_160_448
    one = {k: int(np.prod(shape[1:])) for k, (shape, _) in
           spec["run00_mamba"].items() if k.startswith("mixer.")}
    assert one == {"mixer.in_proj.weight": 17_432_576, "mixer.taps": 17_408,
                   "mixer.taps_bias": 4_352, "mixer.dt_bias": 64,
                   "mixer.a_log": 64, "mixer.d_skip": 64,
                   "mixer.gate_norm.weight": 4_096,
                   "mixer.out_proj.weight": 8_388_608}
    kinds = {k: kind for k, (_, kind) in spec["run00_mamba"].items()}
    assert (kinds["mixer.a_log"], kinds["mixer.dt_bias"],
            kinds["mixer.d_skip"]) == ("bias", "bias", "scale")
