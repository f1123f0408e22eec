"""A second family goes through every seam, as a fixture and not as a
cell: ``tests/chipbench/fixtures/deepseek-v3-mini`` (latent attention, a
dense layer then sparse ones, a sigmoid gate with the selection bias,
one shared expert) brings its own ``weights.py`` and ``reference.py``,
arrives in a copied root as new files and entries, agrees with the
program's prefill and decode, and walks ``run.py --rehearse-cpu`` to a
result line."""

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import fixture_root            # beside this file (pytest prepends its directory)
from chipbench import spec, weights
from test_chipbench_rehearsal import BREAK

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "fixtures", "deepseek-v3-mini")
ENV = {**os.environ, "JAX_PLATFORMS": "cpu",
       "PYTHONPATH": spec.ROOT + os.pathsep
       + os.environ.get("PYTHONPATH", "")}


def tiny_config():
    cfg = spec.load_json(os.path.join(FIXTURE, "config.json"))
    cfg.update(spec.load_json(os.path.join(FIXTURE, "meta.json"))[
        "rehearsal_widths"])
    return cfg


def test_the_fixture_is_in_no_benchmark_of_the_repo():
    bench = open(os.path.join(spec.ROOT, "BENCHMARK.json")).read()
    entries = spec.load_json(os.path.join(HERE, "fixtures", "entries.json"))
    for word in (entries["config"]["name"], entries["workload"]["name"],
                 entries["workload"]["traffic"], "deepseek", ".latent"):
        assert word not in bench
    assert not os.path.exists(os.path.join(
        spec.ROOT, "chipbench", "configs", entries["config"]["name"]))


def test_another_family_arrives_as_new_files_only(tmp_path, root):
    """The sibling of ``test_a_cell_arrives_as_new_files_only``, with a
    family the harness has never held: copy the benchmark (as committed,
    and as grown by a cell already: ``conftest.py``'s ``root``), ADD the
    fixture's files and entries, and see the harness find its weights,
    its reference, its kinds, its tiny widths, its mix and its metrics,
    with no copied file's mtime changed."""
    src, root = root, str(tmp_path / "copy")
    fixture_root.copy_benchmark(root, src)
    before = fixture_root.mtimes(root)
    old_bench = spec.load_json(os.path.join(root, "BENCHMARK.json"))
    name = fixture_root.add_fixture(root)
    after = fixture_root.mtimes(root)
    assert all(after[p] == t for p, t in before.items())
    added = sorted(os.path.relpath(p, root) for p in set(after) - set(before))
    assert added == sorted(
        ["chipbench/configs/deepseek-v3-mini/" + f for f in
         ("config.json", "meta.json", "reference.py", "weights.py")]
        + ["chipbench/traffic/latent-smoke.json"])
    # entries are added; none that was there is changed but for the one
    # list a later PR may lengthen (its cell's name under a metric). The
    # fixture brings NO per-layer entry and no metric file: the three
    # metrics it reports are read by files that are there
    bench = spec.load_json(os.path.join(root, "BENCHMARK.json"))
    for group in ("configs", "workloads"):
        assert bench[group][:len(old_bench[group])] == old_bench[group]
    assert len(bench["per_layer"]) == len(old_bench["per_layer"])
    mine = ["prefix_hit_token_share.docqa", "compiles_in_window.docqa",
            "device_idle_share.docqa"]
    for new, old in zip(bench["end_to_end"] + bench["per_layer"],
                        old_bench["end_to_end"] + old_bench["per_layer"]):
        assert {**new, "workloads": None} == {**old, "workloads": None}
        if old["name"] in mine + ["out_tok_s"]:
            assert new["workloads"] == old["workloads"] + [name]
        else:
            assert new.get("workloads") == old.get("workloads")
    cell = spec.load_cell(name, root)
    assert cell.config["model_type"] == "deepseek_v3"
    assert cell.config_dir == os.path.join(root, "chipbench", "configs",
                                           "deepseek-v3-mini")
    assert {m["name"] for m in cell.end_to_end} \
        == {"ttft_p50_ms", "out_tok_s", "setup_s"}
    assert [m["name"] for m in cell.per_layer] == mine
    for m in cell.per_layer:
        info = spec.layer_metric_file(m["name"], root)
        assert hasattr(spec.load_reader(info["reader"]), "read")
    wts, ref = spec.load_weights(cell), spec.load_reference(cell)
    assert wts.layer_kinds(cell.config) == ["dense", "sparse", "sparse"]
    assert all(hasattr(ref, f) for f in
               ("embed", "layer", "logits", "forward", "mm_f32"))
    assert set(cell.meta["rehearsal_widths"]) <= set(cell.config)
    # a latent pool's step programs may not come from the persistent
    # cache (chip run, PR 29); a Mistral pool's may
    assert cell.meta["step_programs_from_cache"] is False
    # the cell the repo has is untouched and still loads from the copy
    old = spec.load_cell("mistral7b-v01-docqa", root)
    assert [m["name"] for m in old.per_layer] == [m["name"] for m in
            spec.load_cell("mistral7b-v01-docqa", src).per_layer]
    assert spec.load_weights(old).layer_kinds(old.config) == ["layer"] * 16
    assert old.meta["step_programs_from_cache"] is True


def test_the_fixtures_files_import_nothing_of_the_program():
    for file in ("reference.py", "weights.py"):
        assert "xllm_service_tpu" not in open(
            os.path.join(FIXTURE, file)).read()


@pytest.mark.parametrize("seed", [7, 2**31 + 5])
def test_fixture_reference_agrees_with_the_programs_prefill_and_decode(seed):
    """Logits, seeded weights through the fixture's ``program_tree``:
    ``transformer.forward_prefill`` over 40 tokens, then 16 decode steps
    through the latent cache, against the plain reference's one forward
    pass over all 56 (float32 both sides: what is left is the order of
    summation). The program routes through its capacity buckets
    (``moe_capacity_factor`` 2.0): with 4 of 8 experts a token a bucket
    holds a whole group, so nothing is dropped, and the count says so."""
    import jax
    import jax.numpy as jnp
    from xllm_service_tpu.config import ModelConfig
    from xllm_service_tpu.models import transformer
    from xllm_service_tpu.parallel import expert
    cfg = tiny_config()
    wts, ref = spec.load_weights(FIXTURE), spec.load_reference(FIXTURE)
    mc = dataclasses.replace(ModelConfig.from_hf_config(cfg),
                             dtype="float32")
    assert mc.mla and mc.moe_scoring == "sigmoid" and mc.q_lora_rank
    assert mc.moe_capacity_factor == 2.0 and mc.moe_group_size == 512
    tree = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                  wts.program_tree(cfg, seed))
    key, kinds = weights.root_key(seed), wts.layer_kinds(cfg)
    stored = {**wts.head_params(cfg, key),
              "layers": [wts.layer_params(cfg, key, i, k)
                         for i, k in enumerate(kinds)]}
    T, P, ps = 56, 40, 16
    toks = np.random.default_rng(seed).integers(3, cfg["vocab_size"], size=T)
    want = np.asarray(ref.forward(stored, toks, cfg))
    scale = np.abs(want).max()
    n_pages = (T + ps - 1) // ps + 1
    kv = transformer.init_kv_cache(mc, n_pages + 1, ps, jnp.float32)
    assert kv[0].shape[-2:] == (1, cfg["kv_lora_rank"]
                                + cfg["qk_rope_head_dim"])
    table = jnp.arange(1, n_pages + 1, dtype=jnp.int32)[None, :]
    out = transformer.forward_prefill(
        tree, mc, jnp.asarray(toks[:P], jnp.int32)[None, :],
        jnp.zeros((1,), jnp.int32), jnp.asarray([P], jnp.int32), kv, table,
        return_all_logits=True, return_stats=True)
    assert int(out[-1]["moe_dropped"]) == 0
    assert np.abs(np.asarray(out[1][0]) - want[:P]).max() < 2e-4 * scale
    kv = out[2]
    for p in range(P, T):
        lg, kv = transformer.forward_decode(
            tree, mc, jnp.asarray(toks[p:p + 1], jnp.int32),
            jnp.asarray([p], jnp.int32), jnp.asarray([True]), kv, table)
        assert np.abs(np.asarray(lg[0]) - want[p]).max() < 2e-4 * scale, p
    # The program's latent path reports ``moe_dropped`` 0 whatever
    # happens (it discards ``moe_mlp``'s count), so count where the
    # count is made: the first sparse layer's own inputs and gate map
    # through the program's bucketed dispatch.
    eps = float(cfg["rms_norm_eps"])
    x, _ = ref.layer(ref.embed(jnp.asarray(toks), stored["embed"]),
                     stored["layers"][0], cfg, ref.mm_f32, "dense", None)
    lp = stored["layers"][1]
    x = x + ref.attention(ref.rms_norm(x, lp["input_layernorm"], eps), lp,
                          cfg, ref.mm_f32)
    h = ref.rms_norm(x, lp["post_attention_layernorm"], eps)
    gates = ref.gate_map(h, lp, cfg, ref.mm_f32)
    k = cfg["num_experts_per_tok"]
    assert (np.asarray(gates) > 0).sum(axis=-1).tolist() == [k] * T
    f32 = lambda a: a.astype(jnp.float32)        # noqa: E731
    routed, dropped = expert.moe_mlp(
        h[None], f32(lp["gate"]), f32(lp["experts.gate_proj"]),
        f32(lp["experts.up_proj"]), f32(lp["experts.down_proj"]), k,
        mc.moe_capacity_factor, group_size=mc.moe_group_size,
        norm_topk=False, gates=gates[None])
    assert int(dropped) == 0
    for group in (1, 4, 56, 128, 512):       # every group the mix forms
        assert expert.capacity(group, cfg["n_routed_experts"], k,
                               mc.moe_capacity_factor) == group


def test_the_selection_bias_and_the_shared_expert_count():
    """The reference is not blind to what makes the family: without the
    gate's selection bias, or without the shared expert, logits move."""
    cfg = tiny_config()
    wts, ref = spec.load_weights(FIXTURE), spec.load_reference(FIXTURE)
    key = weights.root_key(3)
    stored = {**wts.head_params(cfg, key),
              "layers": [wts.layer_params(cfg, key, i, k)
                         for i, k in enumerate(wts.layer_kinds(cfg))]}
    toks = np.random.default_rng(1).integers(3, cfg["vocab_size"], size=24)
    want = np.asarray(ref.forward(stored, toks, cfg))

    def without(change):
        layers = [change(dict(lp)) if "gate" in lp else lp
                  for lp in stored["layers"]]
        return np.asarray(ref.forward({**stored, "layers": layers}, toks,
                                      cfg))

    def no_bias(lp):
        lp["e_score_correction_bias"] = 0 * lp["e_score_correction_bias"]
        return lp

    def no_shared(lp):
        lp["shared_experts.down_proj"] = 0 * lp["shared_experts.down_proj"]
        return lp

    assert np.abs(without(no_bias) - want).max() > 1e-2
    assert np.abs(without(no_shared) - want).max() > 1e-2


# ---- the whole command on a temporary root -------------------------------

@pytest.fixture(scope="module")
def rooted(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("fixture") / "root")
    return root, fixture_root.build(root)


def rehearse(rooted, seed, code=None):
    root, cell = rooted
    args = ["--workload", cell, "--seed", str(seed), "--seconds", "5",
            "--trace", "0", "--rehearse-cpu", "--limit", "0.05"]
    cmd = [sys.executable, "-c", code, *args] if code else \
        [sys.executable, "-m", "chipbench.run", *args]
    p = subprocess.run(cmd, cwd=root, env=ENV, timeout=600,
                       capture_output=True, text=True)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    return p, json.loads(lines[-1])


def test_the_fixtures_cell_walks_the_whole_command(rooted):
    # The rehearsal is an open loop of one request every half second, so
    # which tokens are compared is the seed's and no clock's; and this
    # seed's 60 window tokens are ALL the float32 reference's best (at
    # tiny widths in bfloat16 one served token in twenty is not, by up to
    # 0.7: under the closed loop of 2 clients that the rehearsal was, with
    # seed 2**31 + 33, the widest gap of the 12 a run happened to compare
    # read 0.10-0.78 in 4 runs of 30 on an idle host; PERF.md, PR 52).
    p, out = rehearse(rooted, 2**31 + 35)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert out["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    assert set(out["metrics"]) == {"setup_s"}
    # the numbers compared, each beside its limit: the last lines of
    # standard error, and the last key of the result line
    assert [ln.split()[1] for ln in p.stderr.splitlines()[-4:]] == [
        "served_token_gap_max", "served_token_gap_p90",
        "served_tokens_compared", "requests_failed"]
    assert "CHECK served_tokens_compared 12 limit ==12 ok" in p.stderr
    assert list(out)[-1] == "compared"
    assert list(out["compared"]) == [
        "served_token_gap_max", "served_token_gap_p90",
        "served_tokens_compared", "requests_failed"]
    assert out["compared"]["served_token_gap_p90"]["limit"] == spec.load_json(
        os.path.join(FIXTURE, "meta.json"))["check"][
            "served_token_gap_quantile_limits"]["0.9"]
    # it ran the copy's harness on the fixture's own tiny widths
    root, cell = rooted
    served = spec.load_json(os.path.join(root, ".chipbench_run", cell,
                                         "model", "config.json"))
    assert served["model_type"] == "deepseek_v3"
    assert served["kv_lora_rank"] == 32 and served["hidden_size"] == 64


def test_the_fixtures_broken_timed_path_comes_out_not_correct(rooted):
    p, out = rehearse(rooted, 91, code=BREAK)
    assert out["correct"] is False and out["failed"] == 0
    for name in ("served_token_gap_max", "served_token_gap_p90"):
        assert "FAIL" in [ln for ln in p.stderr.splitlines()
                          if ln.startswith("CHECK " + name)][-1]
    assert out["compared"]["served_token_gap_p90"]["value"] \
        > out["compared"]["served_token_gap_p90"]["limit"]
