"""Finds everything a cell needs by name, from ``BENCHMARK.json`` down.

The harness holds no table of cells, configurations, mixes or metrics: a
cell names a configuration and a traffic mix, a metric names its reader,
and each of those is a file of its own under ``chipbench/``. A later PR
adds files and entries and edits nothing here.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(path: str) -> Any:
    with open(path, "r", encoding="utf-8") as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config_file: str            # the HF-style config.json as it is run
    config: Dict[str, Any]
    meta: Dict[str, Any]        # source, reduced, assumed, deployment
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    run_seconds: int

    @property
    def config_dir(self) -> str:
        return os.path.dirname(self.config_file)


def _reports(metric: Dict[str, Any], cell: str) -> bool:
    cells = metric.get("workloads")
    return cells is None or cell in cells


def load_cell(workload: str, root: str = ROOT) -> Cell:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                         f"(have: {', '.join(sorted(cells))})")
    w = cells[workload]
    cfg = next(c for c in bench["configs"] if c["name"] == w["config"])
    config_file = os.path.join(root, cfg["file"])
    meta_file = os.path.join(os.path.dirname(config_file), "meta.json")
    traffic_file = os.path.join(root, "chipbench", "traffic",
                                w["traffic"] + ".json")
    return Cell(
        name=workload, chips=int(w["chips"]), config_name=w["config"],
        traffic_name=w["traffic"], config_file=config_file,
        config=load_json(config_file), meta=load_json(meta_file),
        traffic=load_json(traffic_file),
        end_to_end=[m for m in bench["end_to_end"]
                    if _reports(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, workload)],
        run_seconds=int(bench["run_seconds"]))


def layer_metric_file(name: str, root: str = ROOT) -> Dict[str, Any]:
    """``chipbench/layer_metrics/<metric>.json``: layer, unit, source,
    moves and the reader module's name."""
    return load_json(os.path.join(root, "chipbench", "layer_metrics",
                                  name + ".json"))


def _load_by_path(module_name: str, path: str) -> Any:
    spec = importlib.util.spec_from_file_location(module_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _load_named(kind: str, name: str, root: str) -> Any:
    """``chipbench/<kind>/<name>.py`` under ``root``, by path as a
    configuration's files are: what a later PR adds is found in the root
    it was added to, with no package index to touch."""
    return _load_by_path(f"chipbench_{kind}_{name}", os.path.join(
        root, "chipbench", kind, name + ".py"))


def load_reader(name: str, root: str = ROOT):
    """The reader module of a per-layer metric: ``read(ctx, info) -> float
    | None`` in ``chipbench/readers/<reader>.py``."""
    return _load_named("readers", name, root)


def load_kernel_cost(name: str, root: str = ROOT):
    """``cost(...) -> (flops, bytes)`` in
    ``chipbench/kernel_costs/<name>.py``; a reader passes its
    ``ctx["root"]``."""
    return _load_named("kernel_costs", name, root)


def _load_beside_config(cell_or_dir, what: str) -> Any:
    """``<what>.py`` beside a configuration's ``config.json``, imported by
    path, so that a configuration added later brings its own without
    touching a package index."""
    d = cell_or_dir if isinstance(cell_or_dir, str) else cell_or_dir.config_dir
    return _load_by_path(
        f"chipbench_{what}_" + os.path.basename(d).replace("-", "_")
        .replace(".", "_"), os.path.join(d, what + ".py"))


def load_reference(cell_or_dir) -> Any:
    """The configuration's plain reference: ``reference.py`` beside its
    ``config.json`` (a body of its own, or a binding to its family's
    under ``chipbench/reference/``): ``embed``, ``layer(x, lp, cfg, mm,
    kind, carry) -> (x, carry)``, ``logits``, ``forward``, ``mm_f32``."""
    return _load_beside_config(cell_or_dir, "reference")


def load_weights(cell_or_dir) -> Any:
    """The configuration's weights from the seed: ``weights.py`` beside
    its ``config.json`` (a body of its own, or a binding to its family's
    under ``chipbench/weight_families/``): ``layer_kinds``,
    ``layer_params``, ``head_params``, ``program_tree``
    (``chipbench/weights.py`` says what each gives)."""
    return _load_beside_config(cell_or_dir, "weights")


def peaks_for(device_kind: str, root: str = ROOT) -> Dict[str, float]:
    """Published peaks of the chip, keyed by ``device_kind``. A device
    that is not in the table is an error, never a default."""
    table = load_json(os.path.join(root, "chipbench", "peaks.json"))
    row: Optional[Dict[str, Any]] = table["chips"].get(device_kind)
    if row is None:
        raise KeyError(
            f"device kind {device_kind!r} is not in chipbench/peaks.json "
            f"(have: {', '.join(sorted(table['chips']))})")
    return row
