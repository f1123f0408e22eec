"""BENCHMARK.json against the contract's limits, and the requirement that
a cell, a configuration, a mix, a per-layer metric and a kernel cost
function can each be added as new files, with no edit to one that is
there."""

import json
import os
import re
import sys

import pytest

import fixture_root            # beside this file (pytest prepends its directory)
import grown_root
from chipbench import spec

ROOT = spec.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj).*size|_dim$|"
                   r"_rank$|head_dim|expan|experts_per_tok")


@pytest.fixture
def bench(root):
    """``BENCHMARK.json`` as committed and grown by a cell, in turn
    (``conftest.py``): no test here may count the cells, look at the last
    entry or name what every cell reports."""
    return spec.load_json(os.path.join(root, "BENCHMARK.json"))


def test_top_level_keys_and_sizes(bench, root):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer",
                          "trace_in_run"}
    assert bench["trace_in_run"] is True    # the driver passes 0 and 2
    assert os.path.getsize(os.path.join(root, "BENCHMARK.json")) < 65536
    # the contract's other two counts: at most 24 cells, 128 per-layer
    # metrics (PR 49 met the second with 128 entries, 73 of them twins)
    assert 1 <= len(bench["workloads"]) <= 24
    assert 1 <= len(bench["per_layer"]) <= 128
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


def test_cells_configs_and_what_each_reports(bench, root):
    cells = {w["name"]: w for w in bench["workloads"]}
    configs = {c["name"]: c for c in bench["configs"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert len({(w["config"], w["traffic"]) for w in cells.values()}) \
        == len(cells)
    assert {w["config"] for w in cells.values()} == set(configs)
    assert sum(w["chips"] == 4 for w in cells.values()) \
        <= max(1, len(cells) // 4)
    assert len({c["file"] for c in configs.values()}) == len(configs)
    for c in configs.values():
        assert c["file"].startswith("chipbench/")
        assert os.path.exists(os.path.join(root, c["file"]))
        assert os.path.exists(os.path.join(
            root, os.path.dirname(c["file"]), "reference.py"))
        assert len(c["reduced"]) <= 16
        assert not any(WIDTH.search(k) for k in c["reduced"])
        meta = spec.load_json(os.path.join(
            root, os.path.dirname(c["file"]), "meta.json"))
        assert meta["source"] == c["source"]
        assert meta["reduced"] == c["reduced"]
        # what run.py takes from the configuration and holds no table of
        config = spec.load_json(os.path.join(root, c["file"]))
        assert meta["rehearsal_widths"] \
            and set(meta["rehearsal_widths"]) <= set(config)
        assert meta["step_programs_from_cache"] in (True, False)
        assert os.path.exists(os.path.join(
            root, os.path.dirname(c["file"]), "weights.py"))
    for name in cells:
        cell = spec.load_cell(name, root)
        mine = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in mine and len(mine) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in mine, (name, m["name"])
            info = spec.layer_metric_file(m["name"], root)
            for key in ("layer", "unit", "source", "moves", "better"):
                assert info[key] == m[key], (m["name"], key)
            assert hasattr(spec.load_reader(info["reader"], root), "read")
            if "kernel_cost" in info:
                assert hasattr(spec.load_kernel_cost(info["kernel_cost"],
                                                     root), "cost")
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", cells):
            assert w in cells, (m["name"], w)
            scope = e2e[m["moves"]].get("workloads")
            assert scope is None or w in scope


# ---- one entry a metric, not one a metric a cell (PR 52) -----------------

# What each cell reported on the tree BEFORE the fold (PR 51's, 128
# entries), by stem: the name before its last dot. Written from that
# tree's BENCHMARK.json; the folded file has to reproduce it exactly, a
# cell under one entry a stem.
PARENT_EVERY_CELL = """compiles_in_window decode_ahead_ms decode_step_ms
    decode_tail_ms decode_upload_ms device_idle_share emit_ms
    engine_thread_own_share hbm_peak_gb itl_tail_ms kv_index_ms
    kv_pages_peak_share launch_gap_ms obs_flush_ms prefill_tok_s
    prefix_hit_token_share sched_pack_ms ttft_lock_wait_ms
    ttft_master_in_ms ttft_parse_ms ttft_post_emit_ms
    ttft_prefill_device_ms ttft_prefill_host_ms ttft_queue_ms
    ttft_stream_out_ms ttft_unattributed_ms""".split()
PARENT_BESIDES = {
    "mistral7b-v01-docqa": "decode_attn_roofline decode_batch_occupancy",
    "joyai-flash-docqa32": """mla_attend_roofline mla_share_of_decode_step
        moe_dropped_assignments moe_experts_touched_share moe_gmm_roofline
        moe_gmm_share_of_decode_step moe_load_max_over_mean""",
    "lfm2-24b-docqa64": """attn_share_of_decode_step decode_attn_roofline
        moe_dropped_assignments moe_experts_touched_share moe_gmm_roofline
        moe_gmm_share_of_decode_step moe_load_max_over_mean
        state_restored_share""",
    "ouro-2.6b-preamble8": """attn_share_of_decode_step
        decode_attn_roofline decode_batch_occupancy decode_step_roofline
        exit_cdf_before_last_pass layer_passes_per_step""",
    "falcon-h1-34b-syschat32": """attn_share_of_decode_step
        decode_attn_roofline decode_batch_occupancy decode_step_roofline
        ssm_share_of_decode_step ssm_update_roofline state_restored_share
        state_slots_live_peak state_snapshot_evictions""",
    "solar-open2-250b-statedoc64": """attn_share_of_decode_step
        decode_attn_roofline decode_batch_occupancy decode_step_roofline
        kda_share_of_decode_step kda_update_roofline
        moe_dropped_assignments moe_experts_touched_share moe_gmm_roofline
        moe_gmm_share_of_decode_step moe_held_assignment_share
        moe_load_max_over_mean state_restored_share state_slots_live_peak
        state_snapshot_evictions"""}
FREE_TEXT = ("name", "why", "what", "note", "about")


def stem(name):
    return name.rsplit(".", 1)[0]


def reading_keys(info):
    """A metric file without its name and its notes: what a reader is
    handed that can change what it reads."""
    return {k: v for k, v in info.items()
            if k not in FREE_TEXT and not k.endswith("_note")}


@pytest.mark.parametrize("cell", sorted(PARENT_BESIDES))
def test_a_cell_reports_the_stems_it_reported_before_the_fold(cell, root):
    names = [m["name"] for m in spec.load_cell(cell, root).per_layer]
    assert sorted(map(stem, names)) == sorted(
        PARENT_EVERY_CELL + PARENT_BESIDES[cell].split())


def test_no_two_entered_metric_files_read_the_same(bench, root):
    """A metric whose file fits a cell as it stands takes the cell into
    its ``workloads``; an entry and a file of its own are for a reading
    that differs (a reader, a pattern, a cost function). So no two
    entered files are equal once ``name`` and the notes are set aside,
    and every entry says which cells report it."""
    seen = {}
    for m in bench["per_layer"]:
        assert isinstance(m.get("workloads"), list) and m["workloads"], \
            m["name"]
        key = json.dumps(reading_keys(
            spec.layer_metric_file(m["name"], root)), sort_keys=True)
        assert key not in seen, (m["name"], "is a twin of", seen[key])
        seen[key] = m["name"]


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
    """Copy the benchmark, add one configuration, one mix, per-layer
    metrics with their reader, one kernel cost function and one cell, all
    as new files plus new entries (``grown_root.grow``: what the shape
    tests' second root is made by), and see the harness find each."""
    root = str(tmp_path / "copy")
    fixture_root.copy_benchmark(root)
    before = fixture_root.mtimes(root)
    old_bench = spec.load_json(os.path.join(root, "BENCHMARK.json"))
    grown = grown_root.grow(root)
    after = fixture_root.mtimes(root)
    assert all(after[p] == t for p, t in before.items())   # no file edited
    assert sorted(os.path.relpath(p, root) for p in set(after) - set(before)) \
        == sorted(grown.files_added)
    # entries are added, each group's old ones first and as they were;
    # the one thing lengthened is a list of cells, by the new cell's
    # name: of every metric that names every cell there was, and of the
    # two whose file fits this cell though their list names only some
    bench = spec.load_json(os.path.join(root, "BENCHMARK.json"))
    for group in ("configs", "workloads"):
        assert bench[group][:-1] == old_bench[group]
    joined = []
    for new, old in zip(bench["end_to_end"] + bench["per_layer"],
                        old_bench["end_to_end"] + old_bench["per_layer"]):
        assert {**new, "workloads": None} == {**old, "workloads": None}
        if set(old.get("workloads", ())) == set(grown.cells_before) \
                or old["name"] in grown_root.JOINS:
            assert new["workloads"] == old["workloads"] + [grown.cell]
            joined.append(old["name"])
        else:
            assert new.get("workloads") == old.get("workloads")
    assert set(grown_root.JOINS) < set(joined)
    assert {"decode_step_ms.docqa", "kv_index_ms.docqa",
            "device_idle_share.docqa", "hbm_peak_gb"} <= set(joined)
    assert grown.cell in next(m for m in bench["end_to_end"]
                              if m["name"] == "out_tok_s")["workloads"]
    trailing = bench["per_layer"][len(old_bench["per_layer"]):]
    assert [m["name"] for m in trailing] == list(grown_root.METRICS)
    assert {m["source"] for m in trailing} == {
        "device_trace", "program_span", "program_counter", "host_clock"}
    assert all(m["workloads"] == [grown.cell] for m in trailing)
    # four entries are all the cell costs: it reports the stems a dense
    # decoder's cell reports, each under the entry that was there
    assert len(bench["per_layer"]) == len(old_bench["per_layer"]) + 4
    assert {stem(m["name"]) for m in spec.load_cell(
        grown.cell, root).per_layer} == set(
            PARENT_EVERY_CELL + PARENT_BESIDES[grown.cells_before[0]].split()
        ) | {stem(n) for n in grown_root.METRICS}
    cell = spec.load_cell(grown.cell, root)
    assert cell.traffic["rate_rps"] == 1.0
    assert cell.config_dir == os.path.join(root, "chipbench", "configs",
                                           grown_root.CONFIG)
    assert set(grown_root.METRICS) <= {m["name"] for m in cell.per_layer}
    info = spec.layer_metric_file("grown_clock_ms.trickle", root)
    assert spec.load_reader(info["reader"], root).read({}, info) == 1.5
    assert spec.load_kernel_cost(grown_root.KERNEL_COST, root).cost(
        2, 3, 4)[0] == 48.0
    # and the copy's own harness, run from the copy, finds them by name
    sys.path.insert(0, root)
    saved = {k: sys.modules.pop(k) for k in list(sys.modules)
             if k == "chipbench" or k.startswith("chipbench.")}
    try:
        import chipbench.spec as copy_spec
        assert copy_spec.ROOT == root
        assert copy_spec.load_reader(info["reader"]).read({}, info) == 1.5
        assert copy_spec.load_kernel_cost(
            grown_root.KERNEL_COST).cost(2, 3, 4)[0] == 48.0
        assert hasattr(copy_spec.load_reference(cell), "forward")
    finally:
        for k in [k for k in sys.modules if k == "chipbench"
                  or k.startswith("chipbench.")]:
            del sys.modules[k]
        sys.modules.update(saved)
        sys.path.remove(root)
    # a cell that was there is untouched and still loads from the copy
    old = spec.load_cell(grown.cells_before[0], root)
    assert not set(grown_root.METRICS) & {m["name"] for m in old.per_layer}


def test_an_unknown_workload_is_refused(root):
    with pytest.raises(SystemExit, match="no workload"):
        spec.load_cell("no-such-cell", root)
