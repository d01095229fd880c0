"""The scratch checkout of the benchmark's tests."""
import pytest

from bench_helpers import ScratchRoot


@pytest.fixture
def scratch_root(tmp_path):
    """A checkout root into which a test drops new configuration, traffic
    and metric files (`bench_helpers.ScratchRoot`)."""
    return ScratchRoot(tmp_path / "checkout")
