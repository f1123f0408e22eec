"""The configuration ``brumby-14b`` and its cell ``brumby-14b-longdoc16``:
the config is the catalog's with the depth alone reduced, the plain
reference (the ATTENTION form over the whole sequence, no state) agrees
with the program's prefill and decode through the pool at the rehearsal
widths, the reference changes when a mechanism is taken out of it, a
served token that was altered fails the check, the cost files'
arithmetic stands on hand-worked shapes, the mix is the issue's, the
rooflines read a hand-made trace, and the cell walks ``run.py
--rehearse-cpu`` in a copied root."""

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

CONFIG = os.path.join(spec.ROOT, "chipbench", "configs", "brumby-14b")
CELL = "brumby-14b-longdoc16"
ENV = {**os.environ, "JAX_PLATFORMS": "cpu",
       "PYTHONPATH": spec.ROOT + os.pathsep
       + os.environ.get("PYTHONPATH", "")}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
OWN = {f"{n}.longdoc16" for n in (
    "retention_update_roofline", "retention_share_of_decode_step",
    "decode_step_roofline")}


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


def test_the_configuration_is_the_catalogs_with_the_depth_reduced():
    cfg, m = published(), meta()
    assert m["reduced"] == ["num_hidden_layers"] and m["source"].endswith(
        "manifestai/Brumby-14B-Base/blob/main/config.json")
    assert m["published"]["num_hidden_layers"] == 40
    assert cfg["num_hidden_layers"] == 4 >= 4           # the floor
    assert (cfg["model_type"], cfg["hidden_size"], cfg["intermediate_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["vocab_size"], cfg["rope_theta"],
            cfg["rms_norm_eps"], cfg["tie_word_embeddings"]) == (
        "brumby", 5120, 17408, 40, 8, 128, 151936, 1000000, 1e-06, False)
    assert set(m["rehearsal_widths"]) <= set(cfg)
    if os.path.exists(CATALOG):
        row = next(json.loads(ln) for ln in open(CATALOG)
                   if '"Brumby-14B-Base"' in ln)
        assert row["source_url"] == m["source"]
        differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
        assert differs == {"num_hidden_layers"}
    bench = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    entry = next(c for c in bench["configs"] if c["name"] == "brumby-14b")
    assert entry["reduced"] == m["reduced"] \
        and entry["source"] == m["source"]
    assert m["step_programs_from_cache"] is False
    # every size the config does not carry is stated, and the departure
    # of the cut
    for key in ("degree", "gate", "normalisation", "state dtype",
                "state size", "weights"):
        assert key in m["assumed"], key
    assert "float32" in m["assumed"]["state dtype"]
    assert "STATED DEPARTURE OF THE CUT" in m["deployment"]
    assert "37%" in m["deployment"] and "Ten pipeline stages" \
        in m["deployment"]


def test_from_hf_config_on_the_catalogs_config_dict():
    from xllm_service_tpu.config import ModelConfig
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the guides")
    row = next(json.loads(ln) for ln in open(CATALOG)
               if '"Brumby-14B-Base"' in ln)
    mc = ModelConfig.from_hf_config(row["config"], "brumby-14b")
    assert mc.layer_kinds == ("ret+dense",) * 40 == ("ret+dense",) * row[
        "layers"]
    assert (mc.hidden_size, mc.num_heads, mc.num_kv_heads, mc.head_dim,
            mc.vocab_size, mc.intermediate_size) == (
        row["hidden_size"], row["num_attention_heads"],
        row["num_key_value_heads"], row["head_dim"], row["vocab_size"],
        row["dense_width"])
    assert mc.num_attn_layers == mc.num_conv_layers == 0
    assert mc.num_state_layers == 40 and mc.sliding_window is None


def test_the_weights_and_the_pools_are_what_the_files_say():
    """The deployment's arithmetic (meta.json), from shapes alone."""
    import jax
    from xllm_service_tpu.config import ModelConfig
    from xllm_service_tpu.models import transformer
    cfg = published()
    wts = spec.load_weights(CONFIG)
    tree = jax.eval_shape(lambda: wts.program_tree(cfg, 1))
    layer = sum(int(np.prod(x.shape[1:])) for x in
                jax.tree_util.tree_leaves(tree["stacks"]["ret+dense"]))
    assert abs(layer - 330.35e6) < 0.05e6
    total = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                for x in jax.tree_util.tree_leaves(tree))
    assert abs(total - 5.75e9) < 0.01e9
    mc = ModelConfig.from_hf_config(cfg, "brumby-14b")
    eng = spec.load_cell(CELL).traffic["engine"]
    kv = jax.eval_shape(lambda: transformer.init_kv_cache(
        mc, eng["num_pages"], eng["page_size"],
        state_slots=1 + 3 * eng["max_batch_size"]))
    k, v, tails, state = (int(np.prod(x.shape)) * x.dtype.itemsize
                          for x in kv)
    assert k == v == tails == 0             # pages are bookkeeping
    assert kv[0].shape[1:3] == (1024, 128)
    assert kv[3].shape == (4, 49, 8, 8392, 128)
    assert state // 49 == 4 * 34_373_632                # 137.5 MB a slot
    assert abs(state - 6.74e9) < 0.01e9
    # the bare count the cost file reads is 0.9% under what the layout holds
    cost = spec.load_kernel_cost("power_retention_decode_update")
    assert 1.008 < 34_373_632 / (4 * cost.state_elements(cfg)) < 1.010


@pytest.mark.parametrize("seed, over", [
    (5, {}), (2**31 + 9, {"torch_dtype": "float32"}),
    (7, {"torch_dtype": "float32", "num_key_value_heads": 5,
         "head_dim": 8})])
def test_reference_agrees_with_the_programs_prefill_and_decode(seed, over):
    """Prefill in two windows, then decode, through the pool (state row
    1: slots 1 and 2) against the reference's full forward: float32 to
    2e-5 of the largest logit; bfloat16 (the served type) to a quarter
    of it at these tiny widths, where a product has 64 terms."""
    import jax.numpy as jnp
    import chipbench.reference.power_retention_decoder as body
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
    assert body.BLOCK == 512 > n + more      # one block of queries here
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
    assert [int(x.nbytes) for x in kv[:3]] == [0, 0, 0]


@pytest.mark.parametrize("what", [
    "the decay", "the decay's sign", "the rotation", "the q norm",
    "the k norm", "the grouping of heads", "the retention",
    "the feed-forward"])
def test_the_reference_sees_what_the_program_must_not_lose(what):
    """The reference changes when a mechanism is taken out of it: each is
    therefore something the check on the chip would catch in the
    program."""
    import jax.numpy as jnp
    import chipbench.reference.power_retention_decoder as body
    cfg = tiny_config(torch_dtype="float32")
    leaves = stored(cfg, 5)
    toks = np.random.default_rng(5).integers(3, cfg["vocab_size"], size=40)
    want = np.asarray(body.forward(leaves, toks, cfg))
    broken = dict(leaves, layers=[dict(lp) for lp in leaves["layers"]])
    run_cfg = dict(cfg)
    zero = {"the decay": "self_attn.g_proj",
            "the retention": "self_attn.o_proj",
            "the feed-forward": "mlp.down_proj"}
    ones = {"the q norm": "self_attn.q_norm", "the k norm":
            "self_attn.k_norm"}
    if what in zero:        # a gate of zeros is a decay of 1/2 a token
        for lp in broken["layers"]:
            lp[zero[what]] = jnp.zeros_like(lp[zero[what]])
    elif what in ones:
        for lp in broken["layers"]:
            lp[ones[what]] = jnp.ones_like(lp[ones[what]])
    elif what == "the decay's sign":
        for lp in broken["layers"]:
            lp["self_attn.g_proj"] = -lp["self_attn.g_proj"]
    elif what == "the rotation":
        run_cfg["rope_theta"] = 1e30            # every angle 0 but one
    else:                                       # the grouping of heads
        for lp in broken["layers"]:             # head a reads group a % 2
            w = lp["self_attn.q_proj"]
            lp["self_attn.q_proj"] = w.reshape(w.shape[0], 2, 5, -1) \
                .swapaxes(1, 2).reshape(w.shape)
    got = np.asarray(body.forward(broken, toks, run_cfg))
    assert np.abs(got - want).max() > 1e-2 * np.abs(want).max()


def test_the_reference_refuses_what_it_has_no_body_for():
    cfg = tiny_config()
    ref = spec.load_reference(CONFIG)
    leaves = stored(cfg, 5)
    for key, value in (("rope_scaling", {"factor": 2.0}),
                       ("attention_bias", True), ("hidden_act", "gelu")):
        with pytest.raises(ValueError):
            ref.forward(leaves, [5, 6, 7, 8], dict(cfg, **{key: value}))
    with pytest.raises(ValueError):
        ref.layer(None, {}, cfg, ref.mm_f32, "attn+dense", None)


def test_the_reference_imports_nothing_from_the_program():
    for path in (os.path.join(CONFIG, "reference.py"), os.path.join(
            spec.ROOT, "chipbench", "reference",
            "power_retention_decoder.py")):
        text = open(path).read()
        assert "xllm_service_tpu" not in text.replace(
            "``runtime/", "").split('"""', 2)[2]
    body = open(os.path.join(spec.ROOT, "chipbench", "reference",
                             "power_retention_decoder.py")).read()
    code = body.split('"""', 2)[2]
    # the attention form: no state, no expanded features, no chunk scan
    assert "scan(" not in code and "phi" not in code
    assert "HIGHEST" in code and "float32" in code


def test_an_altered_served_token_fails_the_check():
    """``check.compare`` over this configuration's reference and weights:
    the reference's own greedy continuation reads a gap of 0 at every
    served token, one token swapped for another reads a gap, and the
    int8 control is read at the same positions."""
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
    upd = spec.load_kernel_cost("power_retention_decode_update")
    step = spec.load_kernel_cost("decode_step_power_retention")
    # a key-value head's state: the symmetric half of 128 x 128
    assert upd.state_rows(cfg) == 128 * 129 // 2 == 8256
    # a layer's state of one sequence: 8 x (8,256 x 128 + 8,256) values,
    # 34.08 MB in float32; a token reads and writes it in 4 layers
    assert upd.state_elements(cfg) == 8 * 8256 * 129 == 8_520_192
    ops, bytes_ = upd.cost(20000, cfg)
    small = (2 * 8 + 2 * 40) * 128 + 8
    assert bytes_ == 4 * (2 * 8_520_192 + small) * 4
    assert abs(bytes_ / 4 - 68.2e6) < 0.1e6            # 68 MB a row a layer
    head = (2 * 8256 * 6 + 3 * 8256 * 128 + 2 * 8256
            + 5 * (2 * 8256 * 128 + 2 * 8256))
    assert ops == 4 * 8 * head
    assert abs(ops / 4 - 111e6) < 1e6                  # ~111 MFLOP
    assert upd.cost(1, cfg) == (ops, bytes_)           # no context in it
    # bound by bandwidth by a factor of about 150
    peaks = spec.peaks_for("TPU v5 lite")
    assert 100 < (bytes_ / peaks["hbm_bytes_s"]) \
        / (ops / peaks["bf16_flops"]) < 200
    # a layer's matrices: q, k, v, o, the decay's 5120 x 8 and the SwiGLU
    retention = 5120 * (5120 + 1024 + 1024) + 5120 * 5120 + 5120 * 8
    assert step.layer_weights(cfg) == retention + 3 * 5120 * 17408
    assert abs(step.layer_weights(cfg) - 330.35e6) < 0.01e6
    _, walk = step.step_cost(cfg)
    assert walk == (4 * step.layer_weights(cfg) + 5120 * 151936) * 2
    assert abs(walk - 4.20e9) < 0.01e9
    r_ops, r_bytes = step.row_cost(20000, cfg)
    assert r_ops == 2.0 * (4 * step.layer_weights(cfg) + 5120 * 151936) \
        + ops
    assert r_bytes == bytes_ + 5120 * 2
    assert step.row_cost(7, cfg) == (r_ops, r_bytes)   # NO keys and values
    # 16 rows: the states are half of the step's bytes, 8.56 GB in all
    tot_ops, tot_bytes = step.cost(1, [15000] * 16, cfg)
    assert tot_bytes == walk + 16 * r_bytes and tot_ops == 16 * r_ops
    assert 0.50 < 16 * bytes_ / tot_bytes < 0.52
    assert abs(tot_bytes - 8.56e9) < 0.01e9
    # a tiny configuration, by hand: 1 kv head of 4 over 2 query heads,
    # 3 layers: 10 rows of 4 and 10 of the normaliser
    tiny = dict(cfg, num_attention_heads=2, num_key_value_heads=1,
                head_dim=4, num_hidden_layers=3)
    assert upd.state_rows(tiny) == 10 and upd.state_elements(tiny) == 50
    assert upd.cost(0, tiny) == (
        3 * (2 * 10 * 3 + 3 * 40 + 20 + 2 * (80 + 20)),
        3 * (2 * 50 + (2 + 4) * 4 + 1) * 4)


def test_the_mix_is_the_issues():
    mix = spec.load_cell(CELL).traffic
    assert (mix["loop"], mix["clients"], mix["stagger_s"], mix["ramp_s"],
            mix["tail_s"], mix["max_rounds_per_s"]) == (
        "closed", 16, 0.3, 9, 2, 0.6)
    sp = mix["shared_prefix"]
    assert sp["lengths"] == [12316, 16412, 20508] * 2
    assert [(n - 28) // 128 for n in sp["lengths"][:3]] == [96, 128, 160]
    assert sum(sp["lengths"]) == 98472
    assert sum(-(-n // 128) for n in sp["lengths"]) == 774
    assert sp["choose"] == "round_robin" and sp["prefill_in_setup"]
    assert mix["prompt_tokens"] == {"dist": "uniform", "min": 104,
                                    "max": 192}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 160,
                                    "max": 288}
    assert mix["sampling"] == {"temperature": 0.0, "ignore_eos": True}
    assert mix["engine"] == {"page_size": 128, "num_pages": 1024,
                             "max_model_len": 21504, "max_batch_size": 16}
    assert mix["check"]["served_tokens"] == 512
    longest = 20508 + 192 + 288
    assert longest == 20988 <= mix["engine"]["max_model_len"]
    # 51 s under the ceiling hold fewer than two cycles of 16 rounds (a
    # permutation a round), and the schedule holds 39 rounds a client
    assert 51 * mix["max_rounds_per_s"] < 2 * mix["clients"]
    import math
    assert math.ceil(62 * mix["max_rounds_per_s"]) + 1 == 39
    from chipbench import traffic
    shapes = traffic.warmup_shapes(mix, 128)
    assert sorted(shapes["prefill"]) == sorted(
        [(1, 2048, 168), (1, 64, 168)]
        + [(B, 256, 168) for B in (1, 2, 4, 8, 16)])
    assert shapes["decode_widths"] == [168] == [21504 // 128]
    assert 28 + 104 > 128 and 28 + 192 <= 256
    cell = next(w for w in spec.load_json(os.path.join(
        spec.ROOT, "BENCHMARK.json"))["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "brumby-14b", "longdoc16", 1)


def test_the_kernel_rooflines_on_a_hand_made_trace():
    """Two executions of a decode program of 13 ms each with four state
    updates of 1.8 ms in each, tokens of 16 rows inside the traced
    seconds: the state update's share of its roofline is its bytes over
    the bandwidth over the kernel's time, its share of the step its time
    over the program's, and the whole step's share the step cost's."""
    dev = "/device:TPU:0"
    hlo = "%while.6 = (s32[], bf16[16,1,5120], bf16[0,1024,128,8,128])"
    events = []
    for i in range(2):
        t0 = 1_000_000 + i * 20_000_000
        events += [
            {"plane": dev, "line": "XLA Modules", "name": "jit__unknown(1)",
             "start": t0, "dur": 13_000_000},
            {"plane": dev, "line": "XLA Ops", "name": hlo, "start": t0,
             "dur": 12_000_000}]
        events += [
            {"plane": dev, "line": "XLA Ops",
             "name": f"%retention_decode_update.{11 + j} = custom-call()",
             "start": t0 + 3_000_000 * j + 500_000, "dur": 1_800_000}
            for j in range(4)]
    cfg = published()
    records = [{"n_prompt": 12316 + 150 + r,
                "frames": [[10.0 + 0.01 * k, 1] for k in range(2)]}
               for r in range(16)]
    n_tokens = sum(len(r["frames"]) for r in records)
    ctx = {"trace": {"events": events, "wall0": 59.5, "wall1": 61.0},
           "records": records, "config": cfg, "wall_minus_mono": 50.0,
           "device_kind": "TPU v5 lite", "root": spec.ROOT}
    peaks = spec.peaks_for("TPU v5 lite")

    def read(metric):
        info = spec.layer_metric_file(metric)
        return spec.load_reader(info["reader"]).read(ctx, info)

    _, b = spec.load_kernel_cost("power_retention_decode_update").cost(0, cfg)
    kernel_s = 8 * 1.8e-3
    got = read("retention_update_roofline.longdoc16")
    assert got == pytest.approx(
        100.0 * n_tokens * b / peaks["hbm_bytes_s"] / kernel_s)
    assert 0 < got < 100
    assert read("retention_share_of_decode_step.longdoc16") \
        == pytest.approx(100.0 * kernel_s / 0.026)
    contexts = [r["n_prompt"] + i for r in records
                for i in range(1, len(r["frames"]))]
    ops, bytes_ = spec.load_kernel_cost("decode_step_power_retention").cost(
        2, contexts, cfg)
    assert bytes_ / peaks["hbm_bytes_s"] > ops / peaks["bf16_flops"]
    assert read("decode_step_roofline.longdoc16") == pytest.approx(
        100.0 * (bytes_ / peaks["hbm_bytes_s"]) / 0.026)
    # a program without the kernel (the parent) gives the readers nothing
    bare = dict(ctx, trace=dict(ctx["trace"], events=[
        e for e in events if "retention" not in e["name"]]))
    info = spec.layer_metric_file("retention_update_roofline.longdoc16")
    assert spec.load_reader(info["reader"]).read(bare, info) is None
    for name in OWN:
        info = spec.layer_metric_file(name)
        assert spec.load_reader(info["reader"]).read(
            dict(ctx, trace=None), info) is None


def test_every_metric_of_the_cell_has_its_file_and_its_reader(root):
    cell = spec.load_cell(CELL, root)
    names = {m["name"] for m in cell.per_layer}
    shared = EVERY_CELL_REPORTS | {
        "decode_batch_occupancy.docqa", "state_restored_share.syschat32",
        "state_snapshot_evictions.syschat32",
        "state_slots_live_peak.syschat32"}
    assert OWN | shared == names
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
    assert all(m["workloads"] == [CELL] and m["moves"] == "out_tok_s"
               for m in cell.per_layer if m["name"] in OWN)
    # NOT the attention, expert, ring or loop metrics
    assert not {n for n in names if n.startswith((
        "decode_attn", "attn_share", "moe_", "mla_", "layer_passes",
        "exit_cdf", "ssm_", "kda_"))}
    assert rehearsal_counters(CELL, root) == {
        "prefix_hit_token_share.docqa", "kv_pages_peak_share.docqa",
        "compiles_in_window.docqa", "decode_batch_occupancy.docqa",
        "state_restored_share.syschat32",
        "state_snapshot_evictions.syschat32",
        "state_slots_live_peak.syschat32"}


def test_the_cell_walks_the_whole_command_in_a_copied_root(tmp_path):
    """``--rehearse-cpu --trace 2`` at the configuration's tiny widths (2
    layers): set-up (the two documents' snapshots; their pages hold no
    byte), a window of follow-ups that each restore a snapshot, the
    reference check over 12 served tokens, and the ``program_counter``
    metrics that list the cell in the line."""
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
    # every follow-up began from a copy of its document's snapshot, and
    # 384 of 412, 640 of 668 tokens of a prompt of document + 104-192
    # came from the cache (of the index: no page holds a byte)
    assert m["state_restored_share.syschat32"]["value"] == 100.0
    assert 65 < m["prefix_hit_token_share.docqa"]["value"] < 90
    assert 1 <= m["state_slots_live_peak.syschat32"]["value"] <= 2
    assert m["state_snapshot_evictions.syschat32"]["value"] >= 0
