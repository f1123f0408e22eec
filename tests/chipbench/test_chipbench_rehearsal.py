"""The whole command, walked on the CPU at tiny widths: the result line's
shape, no device metric under a CPU run, no result without a chip, and a
timed path broken underneath comes out as not correct."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import spec

ROOT = spec.ROOT
ENV = {**os.environ, "JAX_PLATFORMS": "cpu",
       "PYTHONPATH": ROOT + os.pathsep + os.environ.get("PYTHONPATH", "")}


def run(args, code=None, cwd=ROOT, timeout=600):
    cmd = [sys.executable, "-c", code, *args] if code else \
        [sys.executable, "-m", "chipbench.run", *args]
    return subprocess.run(cmd, cwd=cwd, env=ENV, timeout=timeout,
                          capture_output=True, text=True)


def rounds_enough():
    """``--override`` for every rehearsal here. A closed-loop client
    takes its requests from a list as long as the mix's
    ``max_rounds_per_s`` allows, and the load generator reports a client
    that runs out of them as a failed request. The rehearsal's value (12
    a second) was set on a slower CPU than tests run on today: at 4-8
    tokens a reply a client here finishes more rounds than that, both
    clients ran dry before the run's end, and the run came out
    ``correct: false`` with ``requests_failed 2``. A list for 100 rounds
    a second is more than any CPU finishes; one it does not finish costs
    nothing."""
    mix = spec.load_json(os.path.join(ROOT, "chipbench", "traffic",
                                      "docqa.json"))
    return json.dumps({"rehearsal": dict(mix["rehearsal"],
                                         max_rounds_per_s=100.0)})


def window_schedule(cell):
    """What the measured window's load generator was handed, as the run
    left it on disk."""
    with open(os.path.join(ROOT, ".chipbench_run", cell,
                           "load.schedule.json"), "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


SCHEDULES = {}      # trace mode -> the window's schedule, same seed


# The 26 per-layer metrics that list every cell: a name's suffix is the
# mix a metric first arrived with, its entry's ``workloads`` says who
# reports it (PR 52 folded the cells' twins into these lists).
EVERY_CELL_REPORTS = {"prefill_tok_s", "hbm_peak_gb"} | {
    f"{n}.docqa" for n in (
        "prefix_hit_token_share", "kv_pages_peak_share",
        "compiles_in_window", "engine_thread_own_share", "decode_step_ms",
        "device_idle_share", "launch_gap_ms", "sched_pack_ms",
        "itl_tail_ms", "emit_ms", "obs_flush_ms", "kv_index_ms",
        "decode_upload_ms", "decode_ahead_ms", "decode_tail_ms")} | {
    f"ttft_{s}_ms.docqa" for s in (
        "master_in", "parse", "lock_wait", "queue", "prefill_host",
        "prefill_device", "post_emit", "stream_out", "unattributed")}


def rehearsal_counters(cell, root=ROOT):
    """The per-layer metrics a ``--rehearse-cpu`` run of ``cell`` prints
    under a trace: those that LIST THE CELL and whose file says
    ``program_counter`` (``run.py`` leaves every other source out of a
    CPU run), less the device's memory peak, which a CPU does not have.
    Never every ``program_counter`` name in ``BENCHMARK.json``: a metric
    that a later cell brings for itself is no part of this cell's line."""
    return {m["name"] for m in spec.load_cell(cell, root).per_layer
            if spec.layer_metric_file(m["name"], root)["source"]
            == "program_counter"} - {"hbm_peak_gb"}


def test_a_rehearsal_is_held_to_the_counters_that_list_its_cell(root):
    """The arithmetic above on both roots: every cell has counters to
    print; each lists the cell; and a cell that was there prints, in a
    benchmark grown by a cell, what it prints in the tree as committed
    (the grown copy's new cell brings a ``program_counter`` metric of its
    own, which enters no other cell's line)."""
    bench = spec.load_json(os.path.join(root, "BENCHMARK.json"))
    entries = {m["name"]: m for m in bench["per_layer"]}
    committed = {w["name"] for w in spec.load_json(
        os.path.join(ROOT, "BENCHMARK.json"))["workloads"]}
    for cell in [w["name"] for w in bench["workloads"]]:
        counts = rehearsal_counters(cell, root)
        assert counts and "hbm_peak_gb" not in counts
        for name in counts:
            assert entries[name]["source"] == "program_counter"
            assert cell in entries[name].get("workloads", [cell])
        if cell in committed:
            assert counts == rehearsal_counters(cell, ROOT)
        else:
            assert any(entries[n]["workloads"] == [cell] for n in counts)


def last_json(proc):
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert lines, proc.stderr[-2000:]
    return json.loads(lines[-1])


@pytest.mark.parametrize("cell,trace", [("mistral7b-v01-docqa", 0),
                                        ("mistral7b-v01-docqa", 1),
                                        ("mistral7b-v01-docqa", 2)])
def test_rehearsal_walks_the_whole_command(cell, trace):
    p = run(["--workload", cell, "--seed", str(2**31 + 21), "--seconds",
             "5", "--trace", str(trace), "--rehearse-cpu", "--limit",
             "0.05", "--override", rounds_enough()])
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    SCHEDULES[trace] = window_schedule(cell)
    out = last_json(p)
    assert set(out) >= {"correct", "attempted", "failed", "metrics",
                        "device"}
    assert out["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert "generator lateness: median" in p.stdout
    assert "CHECK served_token_gap_max" in p.stdout
    assert "CHECK served_token_gap_max" in p.stderr.splitlines()[-3]
    # each number compared beside its limit, as the line's last key too
    assert list(out)[-1] == "compared"
    assert list(out["compared"]) == [
        "served_token_gap_max", "served_tokens_compared", "requests_failed"]
    assert out["compared"]["served_token_gap_max"]["limit"] == 0.05
    # a CPU run reports the counters that list ITS cell and never a device
    # time; a CPU time is never written under an end-to-end metric's
    # name; --trace 1 prints no end-to-end metric, the other two setup_s
    counts = rehearsal_counters(cell)
    assert set(out["metrics"]) == (counts if trace else set()) | (
        set() if trace == 1 else {"setup_s"})
    assert "breakdown" not in out and "busy_s" not in out["device"]
    if trace:
        assert out["metrics"]["compiles_in_window.docqa"]["unit"] == "count"
        assert "itl_tail_ms.docqa" not in out["metrics"]   # a time
        assert out["metrics"]["prefix_hit_token_share.docqa"]["value"] > 50
        assert 0 < out["metrics"]["decode_batch_occupancy.docqa"][
            "value"] <= 100
        # the trace went through the worker's control and holds the
        # program's spans (a CPU trace is its host plane alone)
        assert "xllm.loop.step " in [
            ln for ln in p.stdout.splitlines()
            if "program spans in the trace: " in ln][-1]
    if trace == 2:
        assert "traced stretch: " in p.stdout
        assert ", 0 failed" in [ln for ln in p.stdout.splitlines()
                                if "traced stretch: " in ln][-1]


def test_trace_2_measures_on_the_schedule_of_trace_0():
    """Same seed, same ``--seconds``: the measured window of a
    ``--trace 2`` run is offered byte for byte what ``--trace 0`` offers
    (the parametrised rehearsals above left both on disk in turn)."""
    if not {0, 2} <= set(SCHEDULES):
        pytest.skip("needs the --trace 0 and --trace 2 rehearsals above")
    assert SCHEDULES[2] == SCHEDULES[0]


BREAK = """
import sys
from xllm_service_tpu.runtime import engine as E
real = E.Engine._append_token
count = [0]
def altered(self, seq, tok, logprob, top=None):
    # every third served token is replaced where it is produced
    count[0] += 1
    if count[0] % 3 == 0:
        tok = 3 + (tok + 97) % (self.cfg.vocab_size - 3)
    return real(self, seq, tok, logprob, top)
E.Engine._append_token = altered
from chipbench import run
raise SystemExit(run.main(sys.argv[1:]))
"""


def test_a_broken_timed_path_comes_out_not_correct():
    p = run(["--workload", "mistral7b-v01-docqa", "--seed", "77", "--seconds",
             "5", "--trace", "0", "--rehearse-cpu", "--limit", "0.05",
             "--override", rounds_enough()], code=BREAK)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    out = last_json(p)
    assert out["correct"] is False and out["failed"] == 0
    assert "FAIL" in [ln for ln in p.stderr.splitlines()
                      if ln.startswith("CHECK served_token_gap_max")][-1]


def test_no_chip_no_result():
    p = run(["--workload", "mistral7b-v01-docqa", "--seed", "1", "--seconds",
             "3", "--trace", "0"], timeout=120)
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for d in spec.load_json(os.path.join(ROOT, "BENCHMARK.json"))["paths"]:
        shutil.copytree(os.path.join(ROOT, d), tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         "mistral7b-v01-docqa", "--seed", "1", "--seconds", "3", "--trace", "0",
         "--rehearse-cpu"], cwd=tmp_path, capture_output=True, text=True,
        env={k: v for k, v in ENV.items() if k != "PYTHONPATH"},
        timeout=120)
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
