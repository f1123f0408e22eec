"""The two roots a shape test of the benchmark runs against: the tree as
committed, and a copy grown by a whole cell (``grown_root.py``), built
once a session."""

import pytest

import grown_root              # beside this file (pytest prepends its directory)
from chipbench import spec


@pytest.fixture(scope="session")
def grown(tmp_path_factory):
    return grown_root.build(str(tmp_path_factory.mktemp("grown") / "root"))


@pytest.fixture(params=["as committed", "grown by a cell"])
def root(request):
    """Where ``BENCHMARK.json`` and ``chipbench/`` lie: what
    ``spec.load_cell``, ``layer_metric_file``, ``load_reader`` and
    ``load_kernel_cost`` take as their ``root``."""
    if request.param == "as committed":
        return spec.ROOT
    return request.getfixturevalue("grown").root
