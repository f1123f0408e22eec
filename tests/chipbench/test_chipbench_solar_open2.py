"""The configuration ``solar-open2-250b`` and its cell
``solar-open2-250b-statedoc64``: the config is the published one with
the depth, the experts held and the vocabulary reduced (a share of a
stated deployment), the plain reference (the delta rule token by token,
the filters over the whole sequence, the router over all 320 with the
held experts alone computed) agrees with the program's prefill and
decode through the pools at the rehearsal widths, the reference changes
when a mechanism is taken out of it, a served token that was altered
fails the check, the cost files' arithmetic stands on hand-worked
shapes, the mix is the issue's, the new readers read what they say, and
the cell walks ``run.py --rehearse-cpu`` in a copied root."""

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

CONFIG = os.path.join(spec.ROOT, "chipbench", "configs", "solar-open2-250b")
CELL = "solar-open2-250b-statedoc64"
ENV = {**os.environ, "JAX_PLATFORMS": "cpu",
       "PYTHONPATH": spec.ROOT + os.pathsep
       + os.environ.get("PYTHONPATH", "")}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]


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


def test_the_configuration_is_the_published_one_cut_to_a_chips_share():
    cfg, m = published(), meta()
    assert m["reduced"] == REDUCED and m["source"].endswith(
        "upstage/Solar-Open2-250B/blob/main/config.json")
    assert m["published"] == {"num_hidden_layers": 48,
                              "n_routed_experts": 320, "vocab_size": 196608}
    assert cfg["num_hidden_layers"] == 4                # the floor, a period
    assert (cfg["model_type"], cfg["hidden_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["n_shared_experts"],
            cfg["linear_attn_config"]) == (
        "solar_open2", 4096, 64, 8, 128, 1280, 8, 1,
        {"short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
         "num_kv_heads": None})
    # the share: 40 held of 8 x 40 = the published 320, an eighth of the
    # vocabulary, both stated as a deployment's
    assert cfg["n_routed_experts"] * cfg["expert_share_chips"] == 320
    assert cfg["vocab_size"] * 8 == 196608 and cfg["expert_share_rank"] == 0
    assert "Eight chips share each layer" in m["deployment"]
    wts = spec.load_weights(CONFIG)
    assert wts.layer_kinds(cfg) == ["attn+moe", "kda+moe", "kda+moe",
                                    "kda+moe"]
    assert set(m["rehearsal_widths"]) <= set(cfg)
    if os.path.exists(CATALOG):
        row = next(json.loads(ln) for ln in open(CATALOG)
                   if '"Solar-Open2-250B"' in ln)
        assert row["source_url"] == m["source"]
        differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
        assert differs == set(REDUCED)
        # and what the file adds are the program's two keys for the share
        assert set(cfg) - set(row["config"]) == {"expert_share_chips",
                                                 "expert_share_rank"}
    bench = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    entry = next(c for c in bench["configs"]
                 if c["name"] == "solar-open2-250b")
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
    key = weights.root_key(1)
    # a layer of each kind under the program's names (program_tree
    # stacks them, one compiled call a kind)
    one = {kind: jax.eval_shape(lambda kind=kind: wts.program_layer(
        wts.layer_params(cfg, key, 0, kind), kind))
        for kind in ("attn+moe", "kda+moe")}
    assert set(one["kda+moe"]) >= {"kda_qkv", "kda_conv_w", "kda_out"} \
        and one["kda+moe"]["kda_qkv"].shape == (4096, 3 * 8192)

    def layer(kind, experts):
        return sum(int(np.prod(x.shape)) for k, x in one[kind].items()
                   if (k in ("gate_proj", "up_proj", "down_proj"))
                   == experts)
    assert layer("kda+moe", True) == layer("attn+moe", True) == 629_145_600
    assert abs(layer("kda+moe", False) - 154.8e6) < 0.1e6
    assert abs(layer("attn+moe", False) - 126.1e6) < 0.1e6
    head = jax.eval_shape(lambda: wts.head_params(cfg, key))
    total = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                for x in jax.tree_util.tree_leaves(
                    [head, one["attn+moe"]] + 3 * [one["kda+moe"]]))
    assert abs(total - 6.62e9) < 0.01e9
    mc = ModelConfig.from_hf_config(cfg, "solar-open2-250b")
    eng = spec.load_cell(CELL).traffic["engine"]
    kv = jax.eval_shape(lambda: transformer.init_kv_cache(
        mc, eng["num_pages"], eng["page_size"],
        state_slots=1 + 3 * eng["max_batch_size"]))
    k, v, tails, state = (int(np.prod(x.shape)) * x.dtype.itemsize
                          for x in kv)
    assert (k + v) // eng["num_pages"] == 524_288       # one layer attends
    assert tails // eng["num_pages"] == 589_824         # 3 rings of 4 x 24,576
    assert state // 193 == 3 * 4_194_304                # 12.6 MB a slot
    assert abs(state - 2.43e9) < 0.01e9
    assert abs(k + v + tails - 2.10e9) < 0.01e9
    assert abs(total + k + v + tails + state - 11.15e9) < 0.05e9


@pytest.mark.parametrize("seed, over", [
    (5, {}),
    (2**31 + 9, {"torch_dtype": "float32", "expert_share_rank": 3,
                 "num_key_value_heads": 2})])
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

    # a first window of one page (4 chunks of 32), then the rest from
    # its state, with a snapshot at the prompt's last full page boundary
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
    "the decay", "dt_bias", "beta", "the filters", "the output gate",
    "the head norm's weight", "the delta-rule layer", "attention",
    "attention's gate", "the selection bias", "the shared expert",
    "the held experts", "the share's rank"])
def test_the_reference_sees_what_the_program_must_not_lose(what):
    """The reference changes when a mechanism is taken out of it: each is
    therefore something the check on the chip would catch in the
    program."""
    import jax.numpy as jnp
    cfg = tiny_config(torch_dtype="float32")
    ref = spec.load_reference(CONFIG)
    leaves = stored(cfg, 5)
    toks = np.random.default_rng(5).integers(3, cfg["vocab_size"], size=40)
    want = np.asarray(ref.forward(leaves, toks, cfg))
    broken = dict(leaves, layers=[dict(lp) for lp in leaves["layers"]])
    run_cfg = dict(cfg)
    zero = {"dt_bias": ["self_attn.dt_bias"],
            "beta": ["self_attn.b_proj"],
            "the output gate": ["self_attn.g_b_proj"],
            "the delta-rule layer": ["self_attn.f_b_proj",
                                     "self_attn.k_proj"],
            "attention": ["self_attn.v_proj"],
            "attention's gate": ["self_attn.g_proj"],
            "the selection bias": ["mlp.gate.e_score_correction_bias"],
            "the shared expert": ["mlp.shared_experts.down_proj"],
            "the held experts": ["mlp.experts.down_proj"]}
    for i, lp in enumerate(broken["layers"]):
        kda = "self_attn.A_log" in lp
        if what == "the decay" and kda:
            lp["self_attn.A_log"] = lp["self_attn.A_log"] - 20.0  # alpha 1
        elif what == "the filters" and kda:
            for n in "qkv":
                w = lp[f"self_attn.{n}_conv1d"]
                lp[f"self_attn.{n}_conv1d"] = jnp.zeros_like(w).at[-1].set(1)
        elif what == "the head norm's weight" and kda:
            lp["self_attn.o_norm"] = jnp.ones_like(lp["self_attn.o_norm"])
        elif what == "the selection bias":
            # made large first, so that it does choose: then taken out
            lp["mlp.gate.e_score_correction_bias"] = \
                lp["mlp.gate.e_score_correction_bias"] * 50.0
        elif what in zero:
            for name in zero[what]:
                if name in lp and (kda or "attention" in what
                                   or name.startswith("mlp.")):
                    if what == "attention" and kda:
                        continue
                    if what == "the delta-rule layer" and not kda:
                        continue
                    lp[name] = jnp.zeros_like(lp[name])
    if what == "the selection bias":
        want = np.asarray(ref.forward(broken, toks, cfg))
        for lp in broken["layers"]:
            lp["mlp.gate.e_score_correction_bias"] = jnp.zeros_like(
                lp["mlp.gate.e_score_correction_bias"])
    if what == "the share's rank":
        run_cfg["expert_share_rank"] = 1
    got = np.asarray(ref.forward(broken, toks, run_cfg))
    assert np.abs(got - want).max() > 1e-2 * np.abs(want).max()


def test_the_reference_refuses_what_it_has_no_body_for():
    cfg = tiny_config()
    ref = spec.load_reference(CONFIG)
    leaves = stored(cfg, 5)
    for key, value in (("kda_use_full_proj", True),
                       ("kda_allow_neg_eigval", False), ("use_rope", True),
                       ("use_gqa_gate", False),
                       ("first_k_dense_replace", 1)):
        with pytest.raises(ValueError):
            ref.forward(leaves, [5, 6, 7], dict(cfg, **{key: value}))


def test_an_altered_served_token_fails_the_check():
    """``check.compare`` over this configuration's reference and weights:
    the reference's own greedy continuation reads a gap of 0 at every
    served token, and one token swapped for another reads a gap."""
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
    kda = spec.load_kernel_cost("kda_decode_update")
    step = spec.load_kernel_cost("decode_step_delta_rule_moe")
    att = spec.load_kernel_cost("decode_attention_by_gqa_layers")
    moe = spec.load_kernel_cost("moe_experts")
    # one layer's state of one sequence: 64 x 128 x 128 = 1,048,576
    # elements, 4 MiB in float32; a token reads and writes it in 3 layers
    assert kda.state_elements(cfg) == 1_048_576 and kda.delta_layers(cfg) == 3
    ops, bytes_ = kda.cost(1000, cfg)
    assert ops == 3 * 8.0 * 1_048_576
    assert bytes_ == 3 * (2 * 1_048_576 + 6 * 8192) * 4
    assert kda.cost(1, cfg) == (ops, bytes_)          # no context in it
    assert abs(bytes_ / 3 - 8.59e6) < 0.01e6
    # one layer of four attends: the accepted cost at one layer
    assert att.attention_layers(cfg) == 1
    assert att.cost(9000, cfg) == spec.load_kernel_cost(
        "decode_attention").cost(9000, dict(cfg, num_hidden_layers=1))
    assert att.attention_layers(dict(cfg, num_hidden_layers=48)) == 12
    # the layers' own matrices: 126.1 M where one attends, 154.8 M in a
    # delta-rule layer (the issue's reckoning)
    every = 4096 * 320 + 3 * 4096 * 1280
    attention = 3 * 4096 * 8192 + 2 * 4096 * 1024
    delta = 4 * 4096 * 8192 + 2 * (4096 * 128 + 128 * 8192) + 4096 * 64
    assert step.own_weights(cfg) == attention + every + 3 * (delta + every)
    assert abs(attention + every - 126.1e6) < 0.1e6
    assert abs(delta + every - 154.7e6) < 0.1e6
    _, walk = step.step_cost(cfg)
    assert walk == (step.own_weights(cfg) + 4096 * 24576) * 2
    r_ops, r_bytes = step.row_cost(9000, cfg)
    a_ops, a_bytes = att.cost(9000, cfg)
    assert r_ops == 2.0 * (step.own_weights(cfg) + 4096 * 24576) \
        + a_ops + ops
    assert r_bytes == a_bytes + bytes_ + 4096 * 2
    # 64 rows at about 9,200, 32 of 40 experts touched a layer: the
    # experts and the states about 60% of the bytes, attention a quarter
    m_ops, m_bytes = moe.cost(64 * 4, 32 * 4, cfg)
    tot_ops, tot_bytes = step.cost(1, [9200] * 64, cfg, 64 * 4, 32 * 4)
    r_ops, r_bytes = step.row_cost(9200, cfg)
    assert tot_bytes == m_bytes + walk + 64 * r_bytes
    assert tot_ops == m_ops + 64 * r_ops
    assert 0.55 < (m_bytes + 64 * bytes_) / tot_bytes < 0.65
    assert 0.22 < 64 * att.cost(9200, cfg)[1] / tot_bytes < 0.28
    assert abs(tot_bytes / 819e9 - 11.6e-3) < 0.2e-3
    # a tiny configuration, by hand: 2 heads of 4 x 4, layers 1-2 of 3
    tiny = dict(cfg, linear_attn_config={"num_heads": 2, "head_dim": 4},
                num_hidden_layers=3)
    assert kda.cost(0, tiny) == (2 * 8.0 * 32, 2 * (2 * 32 + 6 * 8) * 4)


def test_the_mix_is_the_issues():
    mix = spec.load_cell(CELL).traffic
    assert (mix["loop"], mix["clients"], mix["stagger_s"], mix["ramp_s"],
            mix["tail_s"], mix["max_rounds_per_s"]) == (
        "closed", 64, 0.1, 9, 2, 1.0)
    sp = mix["shared_prefix"]
    assert sorted(sp["lengths"]) == [8092] * 7 + [9116] * 7 + [10140] * 7
    assert all((n - 28) % 128 == 0 for n in sp["lengths"])
    assert sum(sp["lengths"]) == 191_436
    assert sum(-(-n // 128) for n in sp["lengths"]) == 1512
    assert sp["choose"] == "round_robin" and sp["prefill_in_setup"]
    assert mix["prompt_tokens"] == {"dist": "uniform", "min": 104,
                                    "max": 192}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 96,
                                    "max": 160}
    assert mix["sampling"] == {"temperature": 0.0, "ignore_eos": True}
    assert mix["engine"] == {"page_size": 128, "num_pages": 1888,
                             "max_model_len": 12288, "max_batch_size": 64}
    assert mix["check"]["served_tokens"] == 512
    assert 10140 + 192 + 160 <= mix["engine"]["max_model_len"]
    # 51 s under the ceiling hold fewer than two cycles of 64 rounds: a
    # permutation a round
    assert 51 * mix["max_rounds_per_s"] < 2 * mix["clients"]
    from chipbench import traffic
    shapes = traffic.warmup_shapes(mix, 128)
    assert sorted(shapes["prefill"]) == sorted(
        [(1, 2048, 16), (1, 2048, 32), (1, 2048, 64), (1, 2048, 96),
         (1, 1024, 96)] + [(B, 256, 96) for B in (1, 2, 4, 8, 16)])
    assert shapes["decode_widths"] == [96]
    # every follow-up computes 28 + 104..192 tokens: the 256 bucket
    assert 28 + 104 > 128 and 28 + 192 <= 256
    cell = next(w for w in spec.load_json(os.path.join(
        spec.ROOT, "BENCHMARK.json"))["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "solar-open2-250b", "statedoc64", 1)
    assert len(cell["why"]) <= 200


def test_the_new_readers_on_hand_made_steps():
    lo = 50.0

    def moe(a, e, touched=100, **kw):
        return dict(dict(assignments=a, elsewhere=e, dropped=0,
                         experts_touched=touched, load_max_over_mean=2.0),
                    **kw)
    steps = [
        {"t_wall": 100.5, "kind": "mixed", "moe": moe(300, 2100)},
        {"t_wall": 100.6, "kind": "decode", "moe": moe(250, 1798, 120)},
        {"t_wall": 100.7, "kind": "decode", "moe": moe(262, 1786, 136)},
        {"t_wall": 100.8, "kind": "decode", "moe": None},
        {"t_wall": 300.0, "kind": "decode", "moe": moe(9999, 1)}]
    ctx = {"steps": steps, "config": published(), "open_t": lo,
           "close_t": lo + 2.0, "wall_minus_mono": 50.0,
           "cell": spec.load_cell(CELL), "root": spec.ROOT}

    def read(metric, ctx=ctx):
        info = spec.layer_metric_file(metric)
        return spec.load_reader(info["reader"]).read(ctx, info)

    assert read("moe_held_assignment_share.statedoc64") == pytest.approx(
        (300 + 250 + 262) / (2400 + 2048 + 2048))
    # of the 40 HELD x 4 sparse layers, over the two decode-only steps
    assert read("moe_experts_touched_share.statedoc64") == pytest.approx(
        100.0 * (120 + 136) / (40 * 4 * 2))
    assert read("moe_dropped_assignments.docqa32") == 0
    # the parent's records (no elsewhere), and a program without any
    old = dict(ctx, steps=[{"t_wall": 100.5, "kind": "decode", "moe": {
        "assignments": 5, "dropped": 0, "experts_touched": 3,
        "load_max_over_mean": 1.0}}, {"t_wall": 100.6, "kind": "decode"}])
    assert read("moe_held_assignment_share.statedoc64", old) is None
    assert read("moe_held_assignment_share.statedoc64",
                dict(ctx, steps=[])) is None
    # and no device metric without a trace
    for name in ("kda_update_roofline.statedoc64",
                 "kda_share_of_decode_step.statedoc64",
                 "decode_step_roofline.statedoc64",
                 "decode_attn_roofline.statedoc64",
                 "moe_gmm_roofline.docqa32",
                 "attn_share_of_decode_step.docqa64"):
        assert read(name) is None


def test_the_kernel_rooflines_on_a_hand_made_trace():
    """Two executions of a decode program of 14 ms each with three state
    updates of 0.8 ms in each, tokens of 64 rows inside the traced
    seconds: the update's share of its roofline is its bytes over the
    bandwidth over the kernel's time, its share of the step its time
    over the program's, and the whole step's share the step cost's with
    the experts the DECODE-ONLY records say were touched."""
    dev = "/device:TPU:0"
    hlo = "%while.6 = (s32[], bf16[64,1,4096], bf16[1,1888,128,8,128])"
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
             "name": f"%kda_decode_update.{11 + j} = custom-call()",
             "start": t0 + 1_000_000 * (j + 1), "dur": 800_000}
            for j in range(3)]
    cfg = published()
    records = [{"n_prompt": 8200 + 16 * r,
                "frames": [[10.0 + 0.01 * k, 1] for k in range(3)]}
               for r in range(64)]
    for r in records[:10]:
        r["frames"] = r["frames"][:2]
    n_tokens = sum(len(r["frames"]) for r in records)
    steps = [{"t_wall": 60.0, "kind": "decode", "moe": {
                  "assignments": 250, "experts_touched": 124}},
             {"t_wall": 60.1, "kind": "mixed", "moe": {
                  "assignments": 700, "experts_touched": 160}},
             {"t_wall": 70.0, "kind": "decode", "moe": {
                  "assignments": 9, "experts_touched": 9}}]
    ctx = {"trace": {"events": events, "wall0": 59.5, "wall1": 61.0},
           "records": records, "steps": steps, "config": cfg,
           "wall_minus_mono": 50.0, "device_kind": "TPU v5 lite",
           "root": spec.ROOT}
    peaks = spec.peaks_for("TPU v5 lite")

    def read(metric, ctx=ctx):
        info = spec.layer_metric_file(metric)
        return spec.load_reader(info["reader"]).read(ctx, info)

    _, b = spec.load_kernel_cost("kda_decode_update").cost(0, cfg)
    kernel_s = 6 * 0.8e-3
    assert read("kda_update_roofline.statedoc64") == pytest.approx(
        100.0 * n_tokens * b / peaks["hbm_bytes_s"] / kernel_s)
    assert read("kda_share_of_decode_step.statedoc64") == pytest.approx(
        100.0 * kernel_s / 0.028)
    contexts = [r["n_prompt"] + i for r in records
                for i in range(1, len(r["frames"]))]
    # one decode-only record inside the traced seconds stands for both
    # executions
    ops, bytes_ = spec.load_kernel_cost("decode_step_delta_rule_moe").cost(
        2, contexts, cfg, 2 * 250, 2 * 124)
    assert bytes_ / peaks["hbm_bytes_s"] > ops / peaks["bf16_flops"]
    share = read("decode_step_roofline.statedoc64")
    assert share == pytest.approx(
        100.0 * (bytes_ / peaks["hbm_bytes_s"]) / 0.028)
    assert 0 < share < 100
    # records without moe (the parent has them for this family never:
    # it cannot run it) give the whole step's share nothing
    assert read("decode_step_roofline.statedoc64",
                dict(ctx, steps=[{"t_wall": 60.0, "kind": "decode"}])) \
        is None


def test_every_metric_of_the_cell_has_its_file_and_its_reader(root):
    cell = spec.load_cell(CELL, root)
    names = {m["name"] for m in cell.per_layer}
    own = {f"{n}.statedoc64" for n in (
        "kda_update_roofline", "kda_share_of_decode_step",
        "moe_experts_touched_share", "moe_held_assignment_share",
        "decode_attn_roofline", "decode_step_roofline")}
    # entries that other cells list too (PR 52 folded the twins into lists)
    shared = EVERY_CELL_REPORTS | {
        "decode_batch_occupancy.docqa",
        "attn_share_of_decode_step.docqa64",
        "state_restored_share.syschat32",
        "state_snapshot_evictions.syschat32",
        "state_slots_live_peak.syschat32"} | {
        f"{n}.docqa32" for n in (
            "moe_gmm_roofline", "moe_gmm_share_of_decode_step",
            "moe_load_max_over_mean", "moe_dropped_assignments")}
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
        "moe_held_assignment_share.statedoc64",
        "state_restored_share.syschat32",
        "state_snapshot_evictions.syschat32",
        "state_slots_live_peak.syschat32"}


def test_the_cell_walks_the_whole_command_in_a_copied_root(tmp_path):
    """``--rehearse-cpu --trace 2`` at the configuration's tiny widths:
    set-up (the two documents' pages and their snapshots), a window of
    follow-ups that each restore a snapshot, the reference check over 12
    served tokens, and the ``program_counter`` metrics that list the
    cell in the line."""
    root = str(tmp_path / "copy")
    fixture_root.copy_benchmark(root)
    mix = spec.load_cell(CELL, root).traffic
    over = json.dumps({"rehearsal": dict(mix["rehearsal"],
                                         max_rounds_per_s=100.0)})
    p = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", CELL,
         "--seed", str(2**31 + 77), "--seconds", "5", "--trace", "2",
         "--rehearse-cpu", "--limit", "8", "--override", over],
        cwd=root, env=ENV, timeout=900, capture_output=True, text=True)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads([ln for ln in p.stdout.splitlines()
                      if ln.strip()][-1])
    assert out["failed"] == 0 and out["attempted"] > 0
    cmp_ = out["compared"]
    assert cmp_["served_tokens_compared"] == {"value": 12, "limit": 12}
    assert cmp_["requests_failed"] == {"value": 0, "limit": 0}
    # the widest gap under the rehearsal's limit; the 90th percentile is
    # compared too, against the limit the chip's readings set (meta.json),
    # which 12 tokens at tiny widths in bfloat16 are not held to here
    assert cmp_["served_token_gap_max"]["value"] < 8.0
    assert cmp_["served_token_gap_p90"]["limit"] == 0.07
    m = out["metrics"]
    assert set(m) == {"setup_s"} | rehearsal_counters(CELL, root)
    assert m["state_restored_share.syschat32"]["value"] == 100.0
    assert 70 < m["prefix_hit_token_share.docqa"]["value"] < 90
    assert 1 <= m["state_slots_live_peak.syschat32"]["value"] <= 2
    # 4 of 32 experts held at the rehearsal's widths: about an eighth
    assert 0.05 < m["moe_held_assignment_share.statedoc64"]["value"] < 0.25
