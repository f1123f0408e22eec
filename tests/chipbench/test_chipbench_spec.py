"""BENCHMARK.json against the contract's limits, and the requirement that
a cell, a configuration, a mix, a per-layer metric and a kernel cost
function can each be added as new files, with no edit to one that is
there."""

import json
import os
import re
import shutil
import sys

import pytest

from chipbench import spec

ROOT = spec.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj).*size|_dim$|"
                   r"_rank$|head_dim|expan|experts_per_tok")


@pytest.fixture(scope="module")
def bench():
    return spec.load_json(os.path.join(ROOT, "BENCHMARK.json"))


def test_top_level_keys_and_sizes(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer",
                          "trace_in_run"}
    assert bench["trace_in_run"] is True    # the driver passes 0 and 2
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536
    assert bench["paths"] == ["chipbench", "tests/chipbench"]
    assert 1 <= bench["run_seconds"] <= 51
    assert (2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
    for word in bench["command"]:
        assert not word.startswith("/") and ".." not in word


def test_names_units_and_lines(bench):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in bench[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group in ("end_to_end", "per_layer"), e["name"]))
            for key in ("why", "layer", "source"):
                if key in e and group in ("configs", "workloads") \
                        or key == "layer" and key in e:
                    assert 1 <= len(e[key]) <= 200 and "\n" not in e[key] \
                        and "\t" not in e[key], (e["name"], key)
    assert len(set(names)) == len(names)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}


def test_cells_configs_and_what_each_reports(bench):
    cells = {w["name"]: w for w in bench["workloads"]}
    configs = {c["name"]: c for c in bench["configs"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert len({(w["config"], w["traffic"]) for w in cells.values()}) \
        == len(cells)
    assert {w["config"] for w in cells.values()} == set(configs)
    assert sum(w["chips"] == 4 for w in cells.values()) \
        <= max(1, len(cells) // 4)
    for c in configs.values():
        assert c["file"].startswith("chipbench/")
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        assert os.path.exists(os.path.join(
            ROOT, os.path.dirname(c["file"]), "reference.py"))
        assert len(c["reduced"]) <= 16
        assert not any(WIDTH.search(k) for k in c["reduced"])
        meta = spec.load_json(os.path.join(
            ROOT, os.path.dirname(c["file"]), "meta.json"))
        assert meta["source"] == c["source"]
        assert meta["reduced"] == c["reduced"]
        # what run.py takes from the configuration and holds no table of
        config = spec.load_json(os.path.join(ROOT, c["file"]))
        assert meta["rehearsal_widths"] \
            and set(meta["rehearsal_widths"]) <= set(config)
        assert meta["step_programs_from_cache"] in (True, False)
        assert os.path.exists(os.path.join(
            ROOT, os.path.dirname(c["file"]), "weights.py"))
    for name in cells:
        cell = spec.load_cell(name)
        mine = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in mine and len(mine) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in mine, (name, m["name"])
            info = spec.layer_metric_file(m["name"])
            for key in ("layer", "unit", "source", "moves", "better"):
                assert info[key] == m[key], (m["name"], key)
            assert hasattr(spec.load_reader(info["reader"]), "read")
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", cells):
            scope = e2e[m["moves"]].get("workloads")
            assert scope is None or w in scope


def test_published_widths_are_untouched():
    for name, vocab, window, theta in (
            ("mistral-7b-v03", 32768, None, 1e6),
            ("mistral-7b-v01", 32000, 4096, 1e4)):
        cfg = spec.load_json(os.path.join(ROOT, "chipbench", "configs",
                                          name, "config.json"))
        assert (cfg["hidden_size"], cfg["intermediate_size"],
                cfg["num_attention_heads"], cfg["num_key_value_heads"]) \
            == (4096, 14336, 32, 8)
        assert (cfg["vocab_size"], cfg["sliding_window"],
                cfg["rope_theta"]) == (vocab, window, theta)
        assert cfg["num_hidden_layers"] == 16      # the one reduced key


def test_a_cell_arrives_as_new_files_only(tmp_path):
    """Copy the benchmark, add one configuration, one mix, one per-layer
    metric with its reader, one kernel cost function and one cell, all
    as new files plus new entries, and see the harness find each."""
    root = tmp_path / "copy"
    shutil.copytree(os.path.join(ROOT, "chipbench"), root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: os.path.getmtime(os.path.join(dp, p))
              for dp, _, fs in os.walk(root) for p in fs}
    bench = spec.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    src = root / "chipbench" / "configs" / "mistral-7b-v03"
    new = root / "chipbench" / "configs" / "new-model"
    shutil.copytree(src, new)
    mix = spec.load_json(root / "chipbench" / "traffic" / "chat.json")
    mix["rate_rps"] = 1.0
    (root / "chipbench" / "traffic" / "trickle.json").write_text(
        json.dumps(mix))
    (root / "chipbench" / "layer_metrics" / "late_ms.json").write_text(
        json.dumps({"name": "late_ms", "layer": "device", "unit": "ms",
                    "better": "lower", "source": "host_clock",
                    "moves": "ttft_p50_ms", "reader": "late_ms"}))
    (root / "chipbench" / "readers" / "late_ms.py").write_text(
        "def read(ctx, info):\n    return 1.5\n")
    (root / "chipbench" / "kernel_costs" / "matmul.py").write_text(
        "def cost(m, n, k):\n    return 2.0 * m * n * k, 2.0 * (m*k + k*n + m*n)\n")
    bench["configs"].append({
        "name": "new-model", "source": "https://example.org/new",
        "file": "chipbench/configs/new-model/config.json",
        "reduced": ["num_hidden_layers"], "why": "a test's"})
    bench["workloads"].append({
        "name": "new-model.trickle", "config": "new-model",
        "traffic": "trickle", "chips": 1, "why": "a test's"})
    bench["per_layer"].append({
        "name": "late_ms", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "device", "moves": "ttft_p50_ms",
        "workloads": ["new-model.trickle"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell("new-model.trickle", str(root))
    assert cell.traffic["rate_rps"] == 1.0
    assert cell.config_dir == str(new)
    assert "late_ms" in [m["name"] for m in cell.per_layer]
    info = spec.layer_metric_file("late_ms", str(root))
    sys.path.insert(0, str(root))
    saved = {k: sys.modules.pop(k) for k in list(sys.modules)
             if k == "chipbench" or k.startswith("chipbench.")}
    try:
        import chipbench.spec as copy_spec
        assert copy_spec.ROOT == str(root)
        assert copy_spec.load_reader(info["reader"]).read({}, info) == 1.5
        assert copy_spec.load_kernel_cost("matmul").cost(2, 3, 4)[0] == 48.0
        assert hasattr(copy_spec.load_reference(cell), "forward")
    finally:
        for k in [k for k in sys.modules if k == "chipbench"
                  or k.startswith("chipbench.")]:
            del sys.modules[k]
        sys.modules.update(saved)
        sys.path.remove(str(root))
    # the old cell is untouched and still loads from the copy
    old = spec.load_cell("mistral7b-v01-docqa", str(root))
    assert "late_ms" not in [m["name"] for m in old.per_layer]
    after = {p: os.path.getmtime(os.path.join(dp, p))
             for dp, _, fs in os.walk(root) for p in fs}
    assert all(after[p] == t for p, t in before.items())   # no file edited


def test_an_unknown_workload_is_refused():
    with pytest.raises(SystemExit, match="no workload"):
        spec.load_cell("no-such-cell")
