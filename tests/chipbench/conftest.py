"""The ``root`` fixture: a temporary benchmark root with two tiny cells."""

import pytest

from chipbench_cells import make_root


@pytest.fixture
def root(tmp_path):
    return make_root(tmp_path)
