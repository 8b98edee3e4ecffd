"""Shared by the benchmark's tests: where things are, and a tiny run."""
import io
import pathlib
import sys

import jax
import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
FIXTURES = pathlib.Path(__file__).resolve().parent / "fixtures"
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmarks.harness import runner  # noqa: E402
from benchmarks.harness.manifest import Manifest  # noqa: E402


needs_devices = pytest.mark.skipif(jax.device_count() < 4,
                                   reason="needs the virtual CPU mesh")


def tiny_manifest() -> Manifest:
    return Manifest(FIXTURES / "BENCHMARK.json", [FIXTURES])


def tiny_run(cell="tiny.s128", seed=7, trace=False, seconds=0.3, tmp=None):
    """The rest of a run without the harness's look for a chip."""
    err = io.StringIO()
    result = runner.run(FIXTURES / "BENCHMARK.json", cell, seed, seconds,
                        trace, search=[FIXTURES], require_tpu=False,
                        compile_cache=False,
                        scratch=str(tmp) if tmp else None, err=err)
    return result, err.getvalue()
