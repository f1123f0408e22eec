"""A temporary root in which the fixture family is a cell.

The fixture (``tests/chipbench/fixtures/``: a ``deepseek_v3`` configuration
with its ``weights.py`` and ``reference.py``, and a traffic mix) is
entered in no ``BENCHMARK.json`` of the repo.
Tests, and the builder's run on the chip, make a root of their own: a
copy of the benchmark as it is, then the fixture's files and entries
ADDED to it, exactly as a later PR would add a configuration of another
family: no copied file is edited.

    python3 tests/chipbench/fixture_root.py <new directory>

prints the cell's name; run it from there as

    cd <new directory> && PYTHONPATH=<repo> python3 -m chipbench.run \\
        --workload <cell> --seed <n> --seconds <s> --trace <0|2>
"""

from __future__ import annotations

import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
FIXTURES = os.path.join(HERE, "fixtures")


def copy_benchmark(root: str, src: str = REPO) -> None:
    """``BENCHMARK.json`` and ``chipbench/`` as the repo has them (or as
    another root ``src`` has them: a copy that a cell was added to)."""
    os.makedirs(root, exist_ok=True)
    shutil.copy(os.path.join(src, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(src, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))


def mtimes(root: str) -> dict:
    """Every file under ``root`` but ``BENCHMARK.json`` (the one file an
    addition lengthens) with its modification time: equal before and
    after means no copied file was edited."""
    return {os.path.join(dp, f): os.path.getmtime(os.path.join(dp, f))
            for dp, _, fs in os.walk(root) for f in fs
            if f != "BENCHMARK.json"}


def add_fixture(root: str) -> str:
    """The fixture's files under ``chipbench/`` of ``root`` and its entries
    in ``root``'s ``BENCHMARK.json``. Returns the cell's name."""
    with open(os.path.join(FIXTURES, "entries.json")) as f:
        entries = json.load(f)
    bench_dir = os.path.join(root, "chipbench")
    name = entries["config"]["name"]
    shutil.copytree(os.path.join(FIXTURES, name),
                    os.path.join(bench_dir, "configs", name),
                    ignore=shutil.ignore_patterns("__pycache__"))
    for file in os.listdir(os.path.join(FIXTURES, "traffic")):
        dst = os.path.join(bench_dir, "traffic", file)
        if os.path.exists(dst):
            raise FileExistsError(f"{dst}: the fixture edits no file")
        shutil.copy(os.path.join(FIXTURES, "traffic", file), dst)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    cell = entries["workload"]["name"]
    bench["configs"].append(entries["config"])
    bench["workloads"].append(entries["workload"])
    # no per-layer entry of its own: the three metrics it reports are
    # read by files that are there, so their lists are lengthened
    joins = entries["end_to_end_workloads"] + entries["per_layer_workloads"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in joins:
            m["workloads"].append(cell)
    with open(path, "w") as f:
        json.dump(bench, f, indent=1)
    return cell


def build(root: str) -> str:
    copy_benchmark(root)
    return add_fixture(root)


if __name__ == "__main__":
    print(build(sys.argv[1]))
