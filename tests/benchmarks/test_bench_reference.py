"""The plain reference against the trainer at a tiny size, with the Pallas
kernels on the path (interpret mode), and the control: the reference in the
precision below the cell's has to fail the cell's limits."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from bench_testlib import tiny_manifest

from benchmarks.harness import compare, runner, trafficgen, weights


@pytest.fixture(scope="module")
def cell():
    man = tiny_manifest()
    config = man.config("tiny")
    mix = trafficgen.load(man.find("traffic", "train.tiny.json"))
    reference = man.module("references", "ernie_pretrain")
    spec = reference.param_spec(config["model"])
    seed = 31
    pool = trafficgen.make_pool(mix, config["model"], seed)
    params = weights.maker(spec)(weights.seed_key(seed))
    limits = man.json_of("limits", "tiny.s128")["numbers"]
    return dict(man=man, config=config, mix=mix, reference=reference,
                spec=spec, pool=pool, params=params, limits=limits, seed=seed)


@pytest.fixture(scope="module")
def ref(cell):
    return runner.reference_readings(
        cell["reference"], cell["config"], cell["params"], cell["pool"][:3],
        jax.devices()[:1], rows_per_block=2)


def test_trainers_param_tree_is_the_references_layout(cell):
    t = cell["man"].module("entries", "fleet_pretrainer").build(
        cell["config"], cell["mix"], jax.devices()[:1])
    theirs = jax.tree_util.tree_map(lambda x: x.shape, t.trainer.init_params())
    ours = jax.tree_util.tree_map(lambda s: s.shape,
                                  weights.shapes(cell["spec"]))
    assert theirs == ours


def test_reference_agrees_with_the_trainer_with_the_kernels_on_the_path(
        cell, ref, monkeypatch):
    """Gate open: flash attention (packed), fused residual+LN and fused LN
    run in interpret mode through the same dispatch sites as on the chip."""
    from paddle_tpu.core import flags
    from paddle_tpu.ops.pallas import config as pcfg
    monkeypatch.setattr(pcfg, "kernel_enabled",
                        lambda name: bool(flags.get_flag(name)))
    calls = lambda: {k: pcfg._m_calls.value(kernel=k) for k in (  # noqa: E731
        "flash_attention_packed", "fused_rdln", "fused_layer_norm")}
    before = calls()
    t = cell["man"].module("entries", "fleet_pretrainer").build(
        cell["config"], cell["mix"], jax.devices()[:1])
    make = weights.maker(cell["spec"],
                         t.param_shardings(weights.shapes(cell["spec"])))
    key = weights.seed_key(cell["seed"])
    t.params = make(key)
    t.opt_state = t.init_opt_state(t.params)
    loop = runner.Loop(t, cell["pool"], 2, runner.Spans())
    program = runner.program_readings(loop, t, make, key, 3)
    assert all(now > was for now, was in zip(calls().values(),
                                             before.values()))
    values, _ = compare.numbers(program, ref)
    assert max(values.values()) < 1e-4, values


def test_first_block_of_rows_alone_is_not_the_batch(cell, ref):
    """Blocks of rows add up: one block's loss is not the batch's."""
    part = cell["reference"].run(
        cell["config"]["model"], cell["config"]["train"]["optimizer"],
        cell["params"], [{k: v[:2] for k, v in cell["pool"][0].items()}],
        rows_per_block=2)
    assert abs(part["losses"][0] - ref["losses"][0]) > 1e-4


@pytest.mark.parametrize("precision", ["bfloat16", "fp8"])
def test_control_in_a_lower_precision_fails_the_cells_limits(
        cell, ref, precision):
    """The tiny cell states float32, so bfloat16 is its control; fp8 is the
    real cells' (they state bfloat16).  Either must come out not correct."""
    control = runner.reference_readings(
        cell["reference"], cell["config"], cell["params"], cell["pool"][:3],
        jax.devices()[:1], rows_per_block=2, precision=precision)
    values, _ = compare.numbers(control, ref)
    judged, _ = compare.judge(values, cell["limits"])
    assert not compare.correct(judged), judged
    assert values["grad_diff_gap"] > 100 * 1e-6   # the program reads ~2e-7


def test_norm_gap_is_by_the_worst_leaf_against_leaf_or_median():
    ref = np.array([1.0, 2.0, 3.0, 1e-9])
    gap, i = compare.worst_gap(np.array([1.0, 2.0, 3.3, 0.5]), ref)
    # the all-but-zero leaf is held against the median leaf (1.5), not itself
    assert i == 3 and gap == pytest.approx(0.5 / 1.5)
    gap, i = compare.worst_gap(np.array([1.0, 2.0, 3.3, 0.5]), ref,
                               keep=np.array([1, 1, 1, 0], bool))
    assert i == 2 and gap == pytest.approx(0.1)
    assert compare.worst_gap(np.array([np.nan, 1.0]), np.array([1.0, 1.0]))[0] \
        == np.inf


def test_dead_gradient_leaves_are_left_out_of_the_change_only():
    program = {"losses": [1.0], "grad_norms": [1.0, 1.0, 1e-9],
               "change_norms": [1.0, 1.0, 5.0]}
    reference = {"losses": [1.0], "grad_norms": [1.0, 1.0, 1e-12],
                 "change_norms": [1.0, 1.0, 0.0]}
    values, _ = compare.numbers(program, reference)
    assert values["param_change_gap"] == 0.0 and values["grad_norm_gap"] < 1e-8
    with pytest.raises(KeyError, match="no limit"):
        compare.judge(values, {"loss_gap_1": {"limit": 1}})
    limits = {k: {"limit": 1.0} for k in values}
    limits["loss_gap_1"] = {"limit": None, "why": "read, not compared"}
    compared, only_read = compare.judge(values, limits)
    assert list(only_read) == ["loss_gap_1"] and "loss_gap_1" not in compared


def test_gradient_distance_is_also_read_in_units_of_the_yardstick():
    """`grad_diff_ratio`: the program's distance from the reference's first
    gradient over the distance the reference itself reads in the precision
    the configuration states; absent where no yardstick was taken."""
    grad = {"w": jnp.ones((4, 4)), "b": jnp.ones((4,))}
    off = jax.tree_util.tree_map(lambda x: 1.03 * x, grad)
    program = {"losses": [1.0], "grad_norms": [1.0, 1.0],
               "change_norms": [1.0, 1.0], "first_grad": off}
    reference = {"losses": [1.0], "grad_norms": [1.0, 1.0],
                 "change_norms": [1.0, 1.0], "first_grad": grad}
    values, _ = compare.numbers(program, reference)
    assert values["grad_diff_gap"] == pytest.approx(0.03, rel=1e-4)
    assert "grad_diff_ratio" not in values
    values, _ = compare.numbers(
        program, dict(reference, grad_diff_yardstick=0.01))
    assert values["grad_diff_ratio"] == pytest.approx(3.0, rel=1e-4)
