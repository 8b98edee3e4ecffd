"""`run.py` end to end: a tiny cell on the CPU through the harness (the look
for a chip skipped), the result line's keys, and the command itself refusing
to run without a TPU or without the program."""
import json
import os
import shutil
import subprocess
import sys

import jax
import pytest
from bench_testlib import (FIXTURES, REPO, needs_devices, tiny_manifest,
                           tiny_run)

from benchmarks.harness import runner, trafficgen, weights


@pytest.fixture(scope="module")
def plain():
    return tiny_run(trace=False)


def test_result_has_the_contracts_keys_and_compared_comes_last(plain):
    result, err = plain
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics",
                                "device"]
    assert list(result)[-1] == "compared"
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 3
    assert set(result["metrics"]) == {"items_per_s_per_chip", "step_ms_p90",
                                      "setup_s"}
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    json.dumps(result)   # one JSON object, as printed


def test_every_number_compared_is_printed_beside_its_limit(plain):
    result, err = plain
    lines = [ln for ln in err.splitlines() if ln.startswith("compared ")]
    assert len(lines) == len(result["compared"]) == 8
    assert err.rstrip().splitlines()[-1].startswith("compared ")
    for name, j in result["compared"].items():
        assert set(j) == {"value", "limit"} and j["value"] <= j["limit"]
    assert result["compared"]["compiles_in_window"] == {"value": 0.0,
                                                        "limit": 0.0}


def test_traced_run_reports_no_device_metric_off_the_chip(tmp_path):
    result, _ = tiny_run(trace=True, tmp=tmp_path)
    assert result["correct"] is True
    # spans and counts are there; nothing that only a device trace gives
    assert "loop.dispatch_ms" in result["metrics"]
    for name in ("step.mfu", "kernel.flash.roofline", "device.idle_share",
                 "coll.exposed_ms", "step.hbm_peak_gib"):
        assert name not in result["metrics"]
    assert "busy_s" not in result["device"]
    assert not list(tmp_path.iterdir())      # the trace is read, then removed


@needs_devices
def test_four_virtual_chips_through_the_same_harness():
    result, _ = tiny_run(cell="tiny.s128.dp4", seed=2 ** 31 + 12345)
    assert result["correct"] is True and result["device"]["count"] == 4


def test_without_a_tpu_the_harness_refuses_and_prints_no_result():
    with pytest.raises(runner.BenchError, match="no TPU"):
        runner.run(FIXTURES / "BENCHMARK.json", "tiny.s128", 1, 0.1, False,
                   search=[FIXTURES], compile_cache=False)


def test_the_command_exits_non_zero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "ernie-base.s512",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_the_command_exits_non_zero_where_only_the_benchmark_is(tmp_path):
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    for d in manifest["paths"]:
        shutil.copytree(REPO / d, tmp_path / d, ignore=shutil.ignore_patterns(
            "__pycache__", ".jax_cache"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, *manifest["command"][1:], "--workload",
         "ernie-base.s512", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=dict(env, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_traffic_is_from_the_seed_and_every_row_differs():
    man = tiny_manifest()
    mix = trafficgen.load(man.find("traffic", "train.tiny.json"))
    model = man.config("tiny")["model"]
    a = trafficgen.make_pool(mix, model, 2 ** 31 + 7)
    b = trafficgen.make_pool(mix, model, 2 ** 31 + 7)
    c = trafficgen.make_pool(mix, model, 2 ** 31 + 8)
    assert len(a) == mix["pool"]
    for x, y in zip(a, b):
        assert all((x[k] == y[k]).all() for k in x)
    assert not (a[0]["input_ids"] == c[0]["input_ids"]).all()
    assert {k: v.shape for k, v in a[0].items()} == \
        {k: v.shape for k, v in c[0].items()}
    rows = [r.tobytes() for batch in a for r in batch["input_ids"]]
    assert len(set(rows)) == len(rows)
    pos = a[0]["masked_positions"]
    assert pos.shape[1] == trafficgen.n_masked(mix) == 19
    assert all(len(set(r)) == len(r) for r in pos.tolist())


def test_weights_are_from_the_seed_whatever_its_size():
    man = tiny_manifest()
    spec = man.module("references", "ernie_pretrain").param_spec(
        man.config("tiny")["model"])
    make = weights.maker(spec)
    a, b = make(weights.seed_key(2 ** 32 + 5)), make(weights.seed_key(2 ** 32 + 5))
    c = make(weights.seed_key(5))
    la, lb, lc = (jax.tree_util.tree_leaves(t) for t in (a, b, c))
    assert all((x == y).all() for x, y in zip(la, lb))
    assert not any((x == y).all() for x, y in zip(la, lc))
    scale = a["blocks"]["norm1.weight"]
    assert abs(float(scale.mean()) - 1.0) < 0.01
