"""Fixtures of the chip benchmark's CPU tests (helpers in
``bench_fixtures.py``)."""
import pytest

from bench_fixtures import reduce_tree


@pytest.fixture(scope="session")
def tree(tmp_path_factory):
    return reduce_tree(str(tmp_path_factory.mktemp("bench_tree")))
