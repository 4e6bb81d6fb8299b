"""Fixtures of the chip benchmark's CPU rehearsals."""
import pytest

from chipbench_tiny import make_root


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return make_root(str(tmp_path_factory.mktemp("chipbench")))
