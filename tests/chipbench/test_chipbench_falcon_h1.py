"""The configuration ``falcon-h1-34b`` and its cell
``falcon-h1-34b-syschat32``: the config is the published one with the
depth alone reduced, the plain reference (the recurrence token by token,
the filter over the whole sequence, no cache) agrees with the program's
prefill and decode through the pools at the rehearsal widths, the
reference changes when a mechanism is taken out of it, a served token
that was altered fails the check, the cost files' arithmetic stands on
hand-worked shapes, the mix is the issue's, the new reader reads what it
says, and the cell walks ``run.py --rehearse-cpu`` in a copied root."""

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import fixture_root            # beside this file (pytest prepends its directory)
from chipbench import check, spec, weights
from test_chipbench_rehearsal import (EVERY_CELL_REPORTS,
                                      rehearsal_counters)

CONFIG = os.path.join(spec.ROOT, "chipbench", "configs", "falcon-h1-34b")
CELL = "falcon-h1-34b-syschat32"
ENV = {**os.environ, "JAX_PLATFORMS": "cpu",
       "PYTHONPATH": spec.ROOT + os.pathsep
       + os.environ.get("PYTHONPATH", "")}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def published():
    return spec.load_json(os.path.join(CONFIG, "config.json"))


def meta():
    return spec.load_json(os.path.join(CONFIG, "meta.json"))


def tiny_config(**over):
    return {**published(), **meta()["rehearsal_widths"], **over}


def stored(cfg, seed):
    wts = spec.load_weights(CONFIG)
    key = weights.root_key(seed)
    return {**wts.head_params(cfg, key),
            "layers": [wts.layer_params(cfg, key, i, kind)
                       for i, kind in enumerate(wts.layer_kinds(cfg))]}


def test_the_configuration_is_the_published_one_with_the_depth_reduced():
    cfg, m = published(), meta()
    assert m["reduced"] == ["num_hidden_layers"] and m["source"].endswith(
        "tiiuae/Falcon-H1-34B-Instruct/blob/main/config.json")
    assert m["published"]["num_hidden_layers"] == 72
    assert cfg["num_hidden_layers"] == 6 >= 4           # the floor
    assert (cfg["model_type"], cfg["hidden_size"], cfg["intermediate_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["vocab_size"], cfg["mamba_d_ssm"],
            cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"],
            cfg["mamba_n_groups"], cfg["mamba_d_conv"],
            cfg["mamba_chunk_size"], cfg["rope_theta"]) == (
        "falcon_h1", 5120, 21504, 20, 4, 128, 261120, 4096, 32, 128, 256, 2,
        4, 128, 100000000000)
    # no width among the rehearsal's changes leaks into the file as run,
    # and a rehearsal changes sizes of this family alone
    assert set(m["rehearsal_widths"]) <= set(cfg)
    if os.path.exists(CATALOG):
        row = next(json.loads(ln) for ln in open(CATALOG)
                   if '"Falcon-H1-34B-Instruct"' in ln)
        assert row["source_url"] == m["source"]
        differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
        assert differs == {"num_hidden_layers"}
    bench = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    entry = next(c for c in bench["configs"] if c["name"] == "falcon-h1-34b")
    assert entry["reduced"] == m["reduced"] \
        and entry["source"] == m["source"]
    assert m["step_programs_from_cache"] is False
    assert "float32" in m["assumed"]["state dtype"]


def test_the_weights_and_the_pools_are_what_the_files_say():
    """The deployment's arithmetic (meta.json), from shapes alone."""
    import jax
    from xllm_service_tpu.config import ModelConfig
    from xllm_service_tpu.models import transformer
    cfg = published()
    wts = spec.load_weights(CONFIG)
    tree = jax.eval_shape(lambda: wts.program_tree(cfg, 1))
    layer = sum(int(np.prod(x.shape[1:])) for x in
                jax.tree_util.tree_leaves(tree["stacks"]["mix+dense"]))
    assert abs(layer - 430.1e6) < 0.2e6
    total = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                for x in jax.tree_util.tree_leaves(tree))
    assert abs(total - 10.51e9) < 0.02e9
    mc = ModelConfig.from_hf_config(cfg, "falcon-h1-34b")
    eng = spec.load_cell(CELL).traffic["engine"]
    kv = jax.eval_shape(lambda: transformer.init_kv_cache(
        mc, eng["num_pages"], eng["page_size"],
        state_slots=1 + 3 * eng["max_batch_size"]))
    k, v, tails, state = (int(np.prod(x.shape)) * x.dtype.itemsize
                          for x in kv)
    assert (k + v) // eng["num_pages"] == 128 * 12288      # 1.57 MB a page
    assert state // 97 == 6 * 4_194_304                    # 25.2 MB a slot
    assert abs(state - 2.44e9) < 0.01e9 and tails < 0.07e9


@pytest.mark.parametrize("seed, over", [
    (5, {}), (2**31 + 9, {"torch_dtype": "float32"}),
    (7, {"torch_dtype": "float32", "mamba_n_groups": 1,
         "num_key_value_heads": 5})])
def test_reference_agrees_with_the_programs_prefill_and_decode(seed, over):
    """Prefill in two windows, then decode, through the pools (state row
    1: slots 1 and 2) against the reference's full forward: float32 to
    2e-5 of the largest logit; bfloat16 (the served type) to a quarter
    of it at these tiny widths, where a product has 64 terms."""
    import jax.numpy as jnp
    from xllm_service_tpu.config import ModelConfig
    from xllm_service_tpu.models import transformer as T
    from xllm_service_tpu.runtime.engine import Engine
    cfg = tiny_config(**over)
    dtype = cfg.get("torch_dtype") or "bfloat16"
    wts, ref = spec.load_weights(CONFIG), spec.load_reference(CONFIG)
    params = wts.program_tree(cfg, seed)
    mc = dataclasses.replace(ModelConfig.from_hf_config(cfg, "tiny"),
                             dtype=dtype)
    toks = np.random.default_rng(seed % 1000).integers(
        3, cfg["vocab_size"], size=300)
    n, more, ps = 290, 6, 128
    want = np.asarray(ref.forward(stored(cfg, seed), toks[:n + more], cfg))
    tol = (2e-5 if dtype == "float32" else 0.25) * np.abs(want).max()
    kv = T.init_kv_cache(mc, 8, ps, jnp.dtype(dtype), state_slots=5)
    pt = jnp.asarray([[1, 2, 3, 0]], jnp.int32)
    slot = Engine._live_slot

    def window(kv, lo, hi, bucket, cols):
        tk = np.zeros((1, bucket), np.int32)
        tk[0, :hi - lo] = toks[lo:hi]
        _, everything, kv = T.forward_prefill(
            params, mc, jnp.asarray(tk), jnp.asarray([lo], jnp.int32),
            jnp.asarray([hi - lo], jnp.int32), kv, pt,
            return_all_logits=True,
            state_cols=jnp.asarray([cols], jnp.int32))[:3]
        return np.asarray(everything)[0, :hi - lo], kv

    # a first window of one page, then the rest from its state, with a
    # snapshot at the prompt's last full page boundary (256)
    got, kv = window(kv, 0, ps, 128, (0, slot(1, ps - 1), 0, 0))
    assert np.abs(got - want[:ps]).max() <= tol
    got, kv = window(kv, ps, n, 256,
                     (slot(1, ps - 1), slot(1, n - 1), 3, 256 - ps))
    assert np.abs(got - want[ps:n]).max() <= tol
    for pos in range(n, n + more):
        lg, kv = T.forward_decode(
            params, mc, jnp.asarray([toks[pos]]), jnp.asarray([pos]),
            jnp.asarray([True]), kv, pt, state_rows=jnp.asarray([1]))[:2]
        assert np.abs(np.asarray(lg)[0] - want[pos]).max() <= tol, pos
    # and from a COPY of the snapshot (slot 3), the tokens behind the
    # boundary once more: what a prefix hit's first window does
    got, _ = window(kv, 256, n, 64, (3, slot(1, n - 1), 0, 0))
    assert np.abs(got - want[256:n]).max() <= tol


@pytest.mark.parametrize("what", [
    "embedding_multiplier", "lm_head_multiplier", "key_multiplier",
    "attention_out_multiplier", "ssm_in_multiplier", "ssm_out_multiplier",
    "ssm_multipliers", "mlp_multipliers", "the filter's bias", "D",
    "dt_bias", "the gated norm's weight", "the mixer", "attention"])
def test_the_reference_sees_what_the_program_must_not_lose(what):
    """The reference changes when a mechanism is taken out of it: each is
    therefore something the check on the chip would catch in the
    program. (``attention_in_multiplier`` is 1 as published and moves
    nothing.)"""
    import jax.numpy as jnp
    cfg = tiny_config(torch_dtype="float32")
    ref = spec.load_reference(CONFIG)
    leaves = stored(cfg, 5)
    toks = np.random.default_rng(5).integers(3, cfg["vocab_size"], size=40)
    want = np.asarray(ref.forward(leaves, toks, cfg))
    broken = dict(leaves, layers=[dict(lp) for lp in leaves["layers"]])
    run_cfg = dict(cfg)
    names = {"the filter's bias": "mamba.conv1d.bias", "D": "mamba.D",
             "dt_bias": "mamba.dt_bias",
             "the gated norm's weight": "mamba.norm",
             "the mixer": "mamba.out_proj", "attention": "self_attn.o_proj"}
    if what in names:
        for lp in broken["layers"]:
            w = lp[names[what]]
            lp[names[what]] = (jnp.ones_like(w) if "norm" in what
                               else jnp.zeros_like(w))
    elif what in ("ssm_multipliers", "mlp_multipliers"):
        run_cfg[what] = [1.0] * len(cfg[what])
    else:
        run_cfg[what] = 1.0
    got = np.asarray(ref.forward(broken, toks, run_cfg))
    assert np.abs(got - want).max() > 1e-2 * np.abs(want).max()


def test_the_reference_refuses_what_it_has_no_body_for():
    cfg = tiny_config()
    ref = spec.load_reference(CONFIG)
    leaves = stored(cfg, 5)
    for key, value in (("mamba_norm_before_gate", True),
                       ("mamba_rms_norm", False), ("mamba_use_mlp", False),
                       ("rope_scaling", {"factor": 2.0}),
                       ("mlp_bias", True)):
        with pytest.raises(ValueError):
            ref.forward(leaves, [5, 6, 7], dict(cfg, **{key: value}))


def test_an_altered_served_token_fails_the_check():
    """``check.compare`` over this configuration's reference and weights
    (the first layer applies the embedding's multiplier and hands on a
    marker, which every later layer of the sequence is handed): the
    reference's own greedy continuation reads a gap of 0 at every served
    token, and one token swapped for another reads a gap."""
    cfg = tiny_config()
    wts, ref = spec.load_weights(CONFIG), spec.load_reference(CONFIG)
    seed = 2**31 + 5
    leaves = stored(cfg, seed)
    prompt = [int(t) for t in np.random.default_rng(3).integers(
        3, cfg["vocab_size"], size=20)]
    served = []
    for _ in range(6):
        lg = np.asarray(ref.forward(leaves, prompt + served, cfg))
        served.append(int(lg[-1].argmax()))
    sample = [{"id": "r0", "prompt": prompt, "token_ids": served}]
    good = check.compare(ref, wts, cfg, seed, sample, control="int8")
    assert good["gap_max"] < 1e-4 and good["served_tokens"] == 6
    assert good["control"]["positions"] == 6
    altered = list(served)
    altered[3] = (altered[3] + 1) % cfg["vocab_size"]
    bad = check.compare(ref, wts, cfg, seed,
                        [dict(sample[0], token_ids=altered)])
    assert bad["gap_max"] > 0.05 and bad["not_best"] >= 1


def test_the_cost_files_arithmetic_on_hand_worked_shapes():
    cfg = published()
    ssm = spec.load_kernel_cost("ssm_decode_update")
    step = spec.load_kernel_cost("decode_step_hybrid_state")
    att = spec.load_kernel_cost("decode_attention")
    # one layer's state of one sequence: 32 x 128 x 256 = 1,048,576
    # elements, 4 MiB in float32; a token reads and writes it in 6 layers
    assert ssm.state_elements(cfg) == 1_048_576
    ops, bytes_ = ssm.cost(1000, cfg)
    assert ops == 6 * 6.0 * 1_048_576
    small = 3 * 4096 + 2 * 512
    assert bytes_ == 6 * (2 * 1_048_576 + small) * 4
    assert ssm.cost(1, cfg) == (ops, bytes_)          # no context in it
    assert abs(bytes_ / 6 - 8.39e6) < 0.06e6          # 8.39 MB a row a layer
    # a layer's matrices: attention 31.5 M, the mixer 68.4 M (the input
    # projection's 9,248 columns and the output's 4096 rows), SwiGLU 330.3 M
    attention = 5120 * (2560 + 512 + 512) + 2560 * 5120
    mixer = 5120 * (4096 + 5120 + 32) + 4096 * 5120
    assert step.layer_weights(cfg) == attention + mixer + 3 * 5120 * 21504
    assert abs(step.layer_weights(cfg) - 430.0e6) < 0.2e6
    # a step's weight read: 6 layers and the head once, bfloat16
    _, walk = step.step_cost(cfg)
    assert walk == (6 * step.layer_weights(cfg) + 5120 * 261120) * 2
    assert abs(walk - 7.83e9) < 0.02e9
    # a row at 1,000 positions: its products by every matrix, attention's
    # and the state update's operations; its states, its keys and values
    # and its embedding row
    r_ops, r_bytes = step.row_cost(1000, cfg)
    a_ops, a_bytes = att.cost(1000, cfg)
    assert r_ops == 2.0 * (6 * step.layer_weights(cfg) + 5120 * 261120) \
        + a_ops + ops
    assert r_bytes == a_bytes + bytes_ + 5120 * 2
    # 32 rows at about 1,000: the mixer's states are a fifth of the bytes
    tot_ops, tot_bytes = step.cost(1, [1000] * 32, cfg)
    assert tot_bytes == walk + 32 * r_bytes and tot_ops == 32 * r_ops
    assert 0.15 < 32 * bytes_ / tot_bytes < 0.20
    # a tiny configuration, by hand: 2 heads of 4 x 8, 1 group, 3 layers
    tiny = dict(cfg, mamba_n_heads=2, mamba_d_head=4, mamba_d_state=8,
                mamba_n_groups=1, mamba_d_ssm=8, num_hidden_layers=3)
    assert ssm.cost(0, tiny) == (3 * 6.0 * 64,
                                 3 * (2 * 64 + 3 * 8 + 2 * 8) * 4)


def test_the_mix_is_the_issues():
    mix = spec.load_cell(CELL).traffic
    assert (mix["loop"], mix["clients"], mix["stagger_s"], mix["ramp_s"],
            mix["tail_s"], mix["max_rounds_per_s"]) == (
        "closed", 32, 0.15, 9, 2, 1.0)
    sp = mix["shared_prefix"]
    assert sorted(sp["lengths"]) == [412, 412, 668, 668, 924, 924]
    assert all((n - 28) % 128 == 0 for n in sp["lengths"])
    assert sp["choose"] == "round_robin" and sp["prefill_in_setup"]
    assert mix["prompt_tokens"] == {"dist": "uniform", "min": 104,
                                    "max": 192}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 96,
                                    "max": 160}
    assert mix["sampling"] == {"temperature": 0.0, "ignore_eos": True}
    assert mix["engine"] == {"page_size": 128, "num_pages": 256,
                             "max_model_len": 2048, "max_batch_size": 32}
    assert mix["check"]["served_tokens"] == 512
    # the longest request fits the model length, and in 10 pages
    longest = 924 + 192 + 160
    assert longest == 1276 <= mix["engine"]["max_model_len"]
    assert -(-longest // 128) == 10
    # 51 s under the ceiling hold fewer than two cycles of 32 rounds: a
    # permutation a round
    assert 51 * mix["max_rounds_per_s"] < 2 * mix["clients"]
    from chipbench import traffic
    shapes = traffic.warmup_shapes(mix, 128)
    assert sorted(shapes["prefill"]) == sorted(
        [(1, 512, 4), (1, 1024, 8)]
        + [(B, 256, mp) for B in (1, 2, 4, 8) for mp in (8, 16)])
    assert shapes["decode_widths"] == [8, 16]
    # every follow-up computes 28 + 104..192 tokens: the 256 bucket
    assert 28 + 104 > 128 and 28 + 192 <= 256
    cell = next(w for w in spec.load_json(os.path.join(
        spec.ROOT, "BENCHMARK.json"))["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "falcon-h1-34b", "syschat32", 1)


def test_the_new_reader_on_hand_made_steps():
    lo = 50.0

    def st(**kw):
        return dict(dict(live=0, snapshots=0, restored=0, snapshotted=0,
                         evicted=0), **kw)
    steps = [
        {"t_wall": 100.5, "kind": "mixed", "state_restored": (1, 1),
         "state": st(live=30, snapshots=32, restored=2, snapshotted=2,
                     evicted=2)},
        {"t_wall": 100.6, "kind": "decode", "state_restored": (),
         "state": st(live=32, snapshots=32)},
        {"t_wall": 100.7, "kind": "mixed", "state_restored": (1, 0),
         "state": st(live=31, snapshots=31, restored=1, snapshotted=1,
                     evicted=3)},
        {"t_wall": 300.0, "kind": "decode", "state_restored": (0,),
         "state": st(live=99, evicted=99)}]
    ctx = {"steps": steps, "config": published(), "open_t": lo,
           "close_t": lo + 2.0, "wall_minus_mono": 50.0,
           "cell": spec.load_cell(CELL), "root": spec.ROOT}

    def read(metric, ctx=ctx):
        info = spec.layer_metric_file(metric)
        return spec.load_reader(info["reader"]).read(ctx, info)

    assert read("state_snapshot_evictions.syschat32") == 5
    assert read("state_slots_live_peak.syschat32") == 32
    assert read("state_restored_share.syschat32") == 75.0
    # a program without the records (the parent, or a model whose state
    # is its pages alone) gives the readers nothing
    old = dict(ctx, steps=[{"t_wall": 100.5, "kind": "decode",
                            "state_restored": None, "state": None},
                           {"t_wall": 100.6, "kind": "decode"}])
    assert read("state_snapshot_evictions.syschat32", old) is None
    assert read("state_slots_live_peak.syschat32", old) is None
    assert read("state_restored_share.syschat32", old) is None
    # and no device metric without a trace
    for name in ("ssm_update_roofline.syschat32",
                 "ssm_share_of_decode_step.syschat32",
                 "decode_step_roofline.syschat32",
                 "decode_attn_roofline.docqa"):
        assert read(name) is None
    with pytest.raises(ValueError):
        spec.load_reader("state_step_stat").read(ctx, {"stat": "nope"})


def test_the_kernel_rooflines_on_a_hand_made_trace():
    """Two executions of a decode program of 14 ms each with six state
    updates of 0.4 ms in each, 64 tokens inside the traced seconds: the
    state update's share of its roofline is its bytes over the bandwidth
    over the kernel's time, its share of the step its time over the
    program's, and the whole step's share the step cost's."""
    dev = "/device:TPU:0"
    hlo = "%while.6 = (s32[], bf16[32,1,5120], bf16[6,256,128,4,128])"
    events = []
    for i in range(2):
        t0 = 1_000_000 + i * 20_000_000
        events += [
            {"plane": dev, "line": "XLA Modules", "name": "jit__unknown(1)",
             "start": t0, "dur": 14_000_000},
            {"plane": dev, "line": "XLA Ops", "name": hlo, "start": t0,
             "dur": 13_000_000}]
        events += [
            {"plane": dev, "line": "XLA Ops",
             "name": f"%ssm_decode_update.{11 + j} = custom-call()",
             "start": t0 + 1_000_000 * (j + 1), "dur": 400_000}
            for j in range(6)]
    cfg = published()
    records = [{"n_prompt": 600 + r, "frames": [[10.0 + 0.01 * k, 1]
                                                for k in range(3)]}
               for r in range(32)]
    for r in records[:10]:
        r["frames"] = r["frames"][:2]               # 32 + 32 + 22 - ...
    n_tokens = sum(len(r["frames"]) for r in records)
    ctx = {"trace": {"events": events, "wall0": 59.5, "wall1": 61.0},
           "records": records, "config": cfg, "wall_minus_mono": 50.0,
           "device_kind": "TPU v5 lite", "root": spec.ROOT}
    peaks = spec.peaks_for("TPU v5 lite")

    def read(metric):
        info = spec.layer_metric_file(metric)
        return spec.load_reader(info["reader"]).read(ctx, info)

    _, b = spec.load_kernel_cost("ssm_decode_update").cost(0, cfg)
    kernel_s = 12 * 0.4e-3
    assert read("ssm_update_roofline.syschat32") == pytest.approx(
        100.0 * n_tokens * b / peaks["hbm_bytes_s"] / kernel_s)
    assert read("ssm_share_of_decode_step.syschat32") == pytest.approx(
        100.0 * kernel_s / 0.028)
    contexts = [r["n_prompt"] + i for r in records
                for i in range(1, len(r["frames"]))]
    ops, bytes_ = spec.load_kernel_cost("decode_step_hybrid_state").cost(
        2, contexts, cfg)
    assert bytes_ / peaks["hbm_bytes_s"] > ops / peaks["bf16_flops"]
    assert read("decode_step_roofline.syschat32") == pytest.approx(
        100.0 * (bytes_ / peaks["hbm_bytes_s"]) / 0.028)


def test_every_metric_of_the_cell_has_its_file_and_its_reader(root):
    cell = spec.load_cell(CELL, root)
    names = {m["name"] for m in cell.per_layer}
    own = {f"{n}.syschat32" for n in (
        "ssm_update_roofline", "ssm_share_of_decode_step",
        "decode_step_roofline")}
    # entries that other cells list too (PR 52 folded the twins into lists)
    shared = EVERY_CELL_REPORTS | {
        "decode_batch_occupancy.docqa",
        "attn_share_of_decode_step.docqa64", "decode_attn_roofline.docqa",
        "state_restored_share.syschat32",
        "state_snapshot_evictions.syschat32",
        "state_slots_live_peak.syschat32"}
    assert own | shared == names
    assert {m["name"] for m in cell.end_to_end} == {
        "ttft_p50_ms", "out_tok_s", "setup_s"}
    for m in cell.per_layer:
        info = spec.layer_metric_file(m["name"], root)
        assert info["name"] == m["name"] and info["layer"] == m["layer"]
        assert (info["unit"], info["source"], info["moves"]) \
            == (m["unit"], m["source"], m["moves"])
        assert callable(spec.load_reader(info["reader"], root).read)
        if "kernel_cost" in info:
            assert callable(spec.load_kernel_cost(info["kernel_cost"],
                                                  root).cost)
    assert all(m["workloads"] == [CELL] for m in cell.per_layer
               if m["name"] in own)
    assert rehearsal_counters(CELL, root) == {
        "prefix_hit_token_share.docqa", "kv_pages_peak_share.docqa",
        "compiles_in_window.docqa", "decode_batch_occupancy.docqa",
        "state_restored_share.syschat32",
        "state_snapshot_evictions.syschat32",
        "state_slots_live_peak.syschat32"}


def test_the_span_the_index_metric_reads_is_in_the_catalog():
    from xllm_service_tpu.obs import steptrace
    import re
    info = spec.layer_metric_file("kv_index_ms.docqa")
    hit = [n for n in steptrace.SPAN_NAMES
           if re.search(info["span_pattern"], n)]
    assert sorted(hit) == ["xllm.kv.match_prefix", "xllm.kv.register_pages",
                           "xllm.kv.state_slots"]
    assert "state" in steptrace.STEP_FIELDS


def test_the_cell_walks_the_whole_command_in_a_copied_root(tmp_path):
    """``--rehearse-cpu --trace 2`` at the configuration's tiny widths (2
    layers): set-up (the six system prompts' pages and their snapshots),
    a window of turns that each restore a snapshot, the reference check
    over 12 served tokens, and the ``program_counter`` metrics that list
    the cell in the line."""
    root = str(tmp_path / "copy")
    fixture_root.copy_benchmark(root)
    mix = spec.load_cell(CELL, root).traffic
    over = json.dumps({"rehearsal": dict(mix["rehearsal"],
                                         max_rounds_per_s=100.0)})
    p = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", CELL,
         "--seed", str(2**31 + 77), "--seconds", "5", "--trace", "2",
         "--rehearse-cpu", "--limit", "0.05", "--override", over],
        cwd=root, env=ENV, timeout=900, capture_output=True, text=True)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads([ln for ln in p.stdout.splitlines()
                      if ln.strip()][-1])
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 0
    cmp_ = out["compared"]
    assert cmp_["served_tokens_compared"] == {"value": 12, "limit": 12}
    assert cmp_["served_token_gap_max"]["value"] < 0.05
    m = out["metrics"]
    assert set(m) == {"setup_s"} | rehearsal_counters(CELL, root)
    # every turn began from a copy of its system prompt's snapshot, and
    # 384 of 412, 640 of 668, 896 of 924 tokens of a prompt of system
    # prompt + 104-192 came from the cache
    assert m["state_restored_share.syschat32"]["value"] == 100.0
    assert 70 < m["prefix_hit_token_share.docqa"]["value"] < 90
    assert 1 <= m["state_slots_live_peak.syschat32"]["value"] <= 2
    assert m["state_snapshot_evictions.syschat32"]["value"] >= 0
