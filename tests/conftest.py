"""Test configuration: force an 8-device virtual CPU platform so distributed
tests exercise real mesh shardings without TPU hardware (SURVEY.md §4 note:
the reference simulates multi-node with multi-process on localhost; we
simulate a pod with a virtual device mesh).  Tests are CPU tests whatever
the machine holds: the tier-1 command (ROADMAP) already exports
JAX_PLATFORMS=cpu, and setting it here covers a bare `pytest tests/` and
any subprocess a test spawns.
"""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags = (flags + " --xla_force_host_platform_device_count=8").strip()
if "xla_backend_optimization_level" not in flags:
    # Tests compile model-sized graphs on ONE CPU core; backend opt level 0
    # cuts XLA CPU compile ~30% and the tiny test arrays don't need fast
    # codegen (measured r03: vision-zoo file 61s -> 43s cold).
    flags = (flags + " --xla_backend_optimization_level=0").strip()
os.environ["XLA_FLAGS"] = flags
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

# Persistent XLA compile cache (machine-local): model-sized test graphs cost
# 10-70s each to compile; re-runs hit the disk cache instead.  Placed like
# core/jax_cache.py places the on-chip one — JAX_COMPILATION_CACHE_DIR wins
# — but the fallback stays outside the tree: thousands of CPU entries
# would bloat what the chip tool copies.
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_compilation_cache_dir",
                      "/tmp/paddle_tpu_jax_cache")
# Cache EVERY executable (threshold 0): the suite is dominated by hundreds
# of sub-2s per-op eager compiles (each conv shape in the vision zoo is its
# own executable) that the default 1s threshold would refuse to persist.
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

import numpy as np
import pytest


def pytest_configure(config):
    # tier-1 runs `-m 'not slow'` (ROADMAP verify command); registering the
    # marker here keeps `--strict-markers` viable and kills the warning
    config.addinivalue_line(
        "markers",
        "slow: stress/soak variants excluded from the tier-1 gate "
        "(run explicitly with `-m slow`)")


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu

    paddle_tpu.seed(2024)
    np.random.seed(2024)
    yield


@pytest.fixture(autouse=True, scope="session")
def _session_verifier_sweep():
    """End-of-session gate: every program the suite ran through the
    Executor (i.e. that passed check_program_cached) must still verify
    with zero errors at teardown — catches tests that mutate a program
    into an invalid state after its memoized check, and any
    nondeterminism in the verifier itself."""
    yield
    from paddle_tpu.static import analysis

    failures = []
    for prog, version, _feeds, _fetches in analysis.session_passed_programs():
        # feed/fetch-agnostic recheck: data vars are assumed feedable, so
        # only structural/shape/dtype regressions can fire
        diags, _eng = analysis.infer_program(prog)
        errs = [d for d in diags if d.severity == "error"]
        if errs:
            failures.append(
                f"program (checked at version {version}, now "
                f"{prog._version}): "
                + "; ".join(f"{d.code} {d.message}" for d in errs[:3]))
    assert not failures, (
        "programs that passed the verifier during the session now fail:\n"
        + "\n".join(failures))
