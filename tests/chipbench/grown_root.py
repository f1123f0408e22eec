"""A temporary root in which the benchmark has grown by a whole cell, the
way a ``model_config`` PR grows it: new files and new entries, and no
edit to a file that is there.

What is added to a copy of ``BENCHMARK.json`` and ``chipbench/``:

- a configuration directory (``mistral-7b-v03``'s files under a new name)
  with its entry under ``configs``;
- a traffic mix file and the cell's entry under ``workloads``;
- the cell's name appended to the ``workloads`` list of every metric
  whose file fits the cell as it stands, WHICHEVER cells that list
  names: ``out_tok_s``'s and every per-layer metric's that names every
  cell there was (``decode_step_ms.docqa``, ``kv_index_ms.docqa``,
  ``hbm_peak_gb``, ...: a name's suffix is the mix the metric first
  arrived with and says nothing about who reports it), and the two of
  ``JOINS``, whose lists name only some cells and whose files read a
  dense grouped-query decoder's step as they stand;
- four trailing ``per_layer`` entries that list the new cell alone, one of
  each ``source``, each with its metric file; one new reader serves them:
  the example of what is truly new (a reading no file that is there
  gives). No twin: a copy of a file under a name of the new mix's would
  read what the entry that is there reads
  (``test_no_two_entered_metric_files_read_the_same``);
- a kernel cost function.

The shape tests of ``tests/chipbench`` run against the tree as committed
AND against this root (``conftest.py``'s ``root``), so a test that says
how many cells there are, which entry is last, or which names every
cell prints fails in the run of the PR that writes it.
"""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
from typing import Dict, List

import fixture_root            # beside this file (pytest prepends its directory)

CONFIG, MIX, CELL = "grown-model", "trickle", "grown-model-trickle"
READER, KERNEL_COST = "grown_constant", "grown_matmul"
# metrics whose lists name only some cells and whose files fit this one:
# the batch's occupancy (the sparse cells lack it), and the paged
# kernel's roofline under ``kernel_costs/decode_attention.py`` (every
# layer of a grouped-query decoder attends)
JOINS = ("decode_batch_occupancy.docqa", "decode_attn_roofline.docqa")
# one metric of each source, all the new cell's alone
METRICS = {"grown_device_ms.trickle": "device_trace",
           "grown_span_ms.trickle": "program_span",
           "grown_count.trickle": "program_counter",
           "grown_clock_ms.trickle": "host_clock"}


@dataclasses.dataclass
class Grown:
    root: str
    cell: str
    cells_before: List[str]
    files_added: List[str]          # relative to ``root``


def _write_new(root: str, rel: str, text: str, added: List[str]) -> None:
    path = os.path.join(root, rel)
    if os.path.exists(path):
        raise FileExistsError(f"{path}: a cell edits no file that is there")
    with open(path, "w") as f:
        f.write(text)
    added.append(rel)


def grow(root: str) -> Grown:
    """Add the cell to the copy of the benchmark at ``root``."""
    bench_path = os.path.join(root, "BENCHMARK.json")
    with open(bench_path) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    added: List[str] = []
    configs = os.path.join(root, "chipbench", "configs")
    shutil.copytree(os.path.join(configs, "mistral-7b-v03"),
                    os.path.join(configs, CONFIG),
                    ignore=shutil.ignore_patterns("__pycache__"))
    added += sorted(os.path.join("chipbench", "configs", CONFIG, f)
                    for f in os.listdir(os.path.join(configs, CONFIG)))
    with open(os.path.join(configs, CONFIG, "meta.json")) as f:
        meta = json.load(f)
    with open(os.path.join(root, "chipbench", "traffic", "chat.json")) as f:
        mix = json.load(f)
    mix["rate_rps"] = 1.0
    _write_new(root, f"chipbench/traffic/{MIX}.json", json.dumps(mix), added)
    _write_new(root, f"chipbench/readers/{READER}.py",
               "def read(ctx, info):\n    return info.get(\"value\")\n",
               added)
    _write_new(root, f"chipbench/kernel_costs/{KERNEL_COST}.py",
               "def cost(m, n, k):\n"
               "    return 2.0 * m * n * k, 2.0 * (m*k + k*n + m*n)\n", added)
    entries: List[Dict] = []
    for name, source in METRICS.items():
        entry = {"name": name, "unit": "count" if "count" in name else "ms",
                 "better": "lower", "source": source, "layer": "device",
                 "moves": "ttft_p50_ms", "workloads": [CELL]}
        entries.append(entry)
        info = {k: v for k, v in entry.items() if k != "workloads"}
        _write_new(root, f"chipbench/layer_metrics/{name}.json",
                   json.dumps(dict(info, reader=READER, value=1.5)), added)
    bench["configs"].append({
        "name": CONFIG, "source": meta["source"],
        "file": f"chipbench/configs/{CONFIG}/config.json",
        "reduced": meta["reduced"], "why": "a test's: the guard's fourth"})
    bench["workloads"].append({
        "name": CELL, "config": CONFIG, "traffic": MIX, "chips": 1,
        "why": "a test's: what a model_config PR adds, entered in no "
               "BENCHMARK.json of the repo"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if set(m.get("workloads", ())) == set(cells) \
                or m["name"] in JOINS:                    # out_tok_s too
            m["workloads"].append(CELL)
    bench["per_layer"].extend(entries)
    with open(bench_path, "w") as f:
        json.dump(bench, f, indent=1)
    return Grown(root=root, cell=CELL, cells_before=cells, files_added=added)


def build(root: str) -> Grown:
    fixture_root.copy_benchmark(root)
    return grow(root)
