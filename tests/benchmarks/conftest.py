"""The benchmark's tests build fleets: leave no mesh behind for the next."""
import pytest
from bench_testlib import REPO, Manifest


@pytest.fixture(autouse=True)
def _reset_mesh():
    yield
    from paddle_tpu.parallel import mesh as mesh_mod
    mesh_mod.set_mesh(None)


@pytest.fixture(scope="module")
def man():
    """The repo's own manifest (the tiny one is `bench_testlib.tiny_manifest`)."""
    return Manifest(REPO / "BENCHMARK.json")
