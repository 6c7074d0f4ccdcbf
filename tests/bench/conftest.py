"""Fixtures for the benchmark's own tests."""

import pytest

from benchroot import make_root


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return make_root(str(tmp_path_factory.mktemp("benchroot")))
