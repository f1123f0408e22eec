"""The plain references against the program's own forward pass at a tiny
size (window included), the weights' regeneration by layer, and the
lower-precision control at a size a test run can hold."""

import dataclasses
import os

import numpy as np
import pytest

from chipbench import check, spec, weights
from chipbench.weight_families import gqa_decoder as gqa_weights

CONFIG_DIRS = ["mistral-7b-v03", "mistral-7b-v01"]
TINY = {"hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_hidden_layers": 3, "vocab_size": 300}


def bf16_mm(x, w):
    """bfloat16 inputs, float32 accumulation: what the served type does."""
    import jax.numpy as jnp
    return jnp.matmul(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def tiny_config(name, **over):
    cfg = spec.load_json(os.path.join(spec.ROOT, "chipbench", "configs",
                                      name, "config.json"))
    cfg.update(TINY)
    cfg.update(over)
    return cfg


def config_dir(name):
    return os.path.join(spec.ROOT, "chipbench", "configs", name)


def reference(name):
    return spec.load_reference(config_dir(name))


def generator(name):
    return spec.load_weights(config_dir(name))


def by_layer(tree, n):
    return {**{k: tree[k] for k in ("embed", "final_norm", "lm_head")},
            "layers": [{k: v[i] for k, v in tree["layers"].items()}
                       for i in range(n)]}


def program_logits(cfg, tree, tokens):
    """All-position logits of the program's prefill over fresh pages."""
    import jax.numpy as jnp
    from xllm_service_tpu.config import ModelConfig
    from xllm_service_tpu.models import transformer
    mc = ModelConfig.from_hf_config(cfg)
    mc = dataclasses.replace(mc, dtype="float32")
    f32 = lambda a: a.astype(jnp.float32)        # noqa: E731
    import jax
    params = jax.tree_util.tree_map(f32, tree)
    ps, T = 16, len(tokens)
    n_pages = (T + ps - 1) // ps + 1
    kv = transformer.init_kv_cache(mc, n_pages + 1, ps, jnp.float32)
    table = jnp.arange(1, n_pages + 1, dtype=jnp.int32)[None, :]
    out = transformer.forward_prefill(
        params, mc, jnp.asarray(tokens, jnp.int32)[None, :],
        jnp.zeros((1,), jnp.int32), jnp.asarray([T], jnp.int32), kv,
        table, return_all_logits=True)
    return np.asarray(out[1][0]), mc


@pytest.mark.parametrize("name,window", [("mistral-7b-v03", None),
                                         ("mistral-7b-v01", 8)])
def test_reference_agrees_with_the_programs_forward(name, window):
    over = {} if window is None else {"sliding_window": window,
                                      "max_position_embeddings": 4096}
    cfg = tiny_config(name, **over)
    tree = generator(name).program_tree(cfg, 11)
    tokens = np.random.default_rng(0).integers(3, 300, size=40)
    got, mc = program_logits(cfg, tree, tokens)
    assert mc.sliding_window == window
    want = np.asarray(reference(name).forward(by_layer(tree, 3), tokens,
                                              cfg))
    # float32 both sides: what is left is the order of summation
    assert np.abs(got - want).max() < 2e-4 * np.abs(want).max()
    if window:
        full = np.asarray(reference(name).forward(
            by_layer(tree, 3), tokens, dict(cfg, sliding_window=None)))
        assert np.abs(full[:window] - want[:window]).max() < 1e-5
        assert np.abs(full[-1] - want[-1]).max() > 1e-3   # the window bites


def test_each_configuration_binds_the_one_shared_reference():
    """One body (``chipbench/reference/gqa_decoder.py``) and one
    generator (``chipbench/weight_families/gqa_decoder.py``), bound
    beside each ``config.json``; none of it imports the program."""
    import chipbench.reference.gqa_decoder as body
    for n in CONFIG_DIRS:
        ref, wts = reference(n), generator(n)
        assert all(getattr(ref, f) is getattr(body, f) for f in
                   ("embed", "layer", "logits", "forward", "mm_f32"))
        assert all(getattr(wts, f) is getattr(gqa_weights, f) for f in
                   ("layer_kinds", "layer_params", "head_params",
                    "program_tree"))
        for file in ("reference.py", "weights.py"):
            src = open(os.path.join(config_dir(n), file)).read()
            assert "xllm_service_tpu" not in src
    for mod in (body, gqa_weights, weights):
        assert "xllm_service_tpu" not in open(mod.__file__).read()


@pytest.mark.parametrize("seed", [5, 2**31 + 9])
def test_weights_regenerate_layer_by_layer(seed):
    import jax.numpy as jnp
    cfg = tiny_config("mistral-7b-v03")
    wts = generator("mistral-7b-v03")
    assert wts.layer_kinds(cfg) == ["layer"] * 3
    tree = wts.program_tree(cfg, seed)
    key = weights.root_key(seed)
    for i in range(3):
        for k, v in wts.layer_params(cfg, key, i, "layer").items():
            assert v.dtype == jnp.bfloat16
            assert (np.asarray(tree["layers"][k][i], np.float32)
                    == np.asarray(v, np.float32)).all(), (i, k)
    head = wts.head_params(cfg, key)
    for k in head:
        assert (np.asarray(tree[k], np.float32)
                == np.asarray(head[k], np.float32)).all()
    other = wts.program_tree(cfg, seed + 1)
    assert not (np.asarray(other["embed"], np.float32)
                == np.asarray(tree["embed"], np.float32)).all()
    assert abs(float(jnp.mean(tree["final_norm"].astype(jnp.float32)))
               - 1.0) < 0.1
    assert float(jnp.std(tree["final_norm"].astype(jnp.float32))) > 0.02


@pytest.mark.parametrize("name", CONFIG_DIRS)
def test_control_precision_comes_out_worse_than_the_served_one(name):
    """At test size: greedy tokens of a bfloat16 forward (the served
    type) against the float32 reference, and the int8 control's first
    choices at the very positions where those tokens were chosen (six
    requests of 64 served tokens), on three seeds: the control's
    smallest widest-gap is over three times the served type's largest."""
    import jax
    cfg = tiny_config(name, num_hidden_layers=4)
    ref, wts = reference(name), generator(name)
    P, N, R = 60, 64, 6
    served, control = [], []
    for seed in (1, 2, 3):
        rng = np.random.default_rng(seed)
        tree = by_layer(wts.program_tree(cfg, seed), 4)
        # Greedy decode in the served type. A sequence is padded to its
        # final length (causal: what follows a position cannot change
        # it), so that one compiled forward serves every step.
        step = jax.jit(lambda t: ref.forward(tree, t, cfg, mm=bf16_mm))
        sample = []
        for r in range(R):
            toks = np.zeros(P + N, np.int32)
            toks[:P] = rng.integers(3, 300, size=P)
            for i in range(P, P + N):
                toks[i] = int(np.asarray(step(toks))[i - 1].argmax())
            sample.append({"id": f"r{r}", "prompt": toks[:P].tolist(),
                           "token_ids": toks[P:].tolist()})
        sample[-1]["compare"] = N - 8         # the last one is cut
        out = check.compare(ref, wts, cfg, seed, sample, control="int8")
        served.append(out["gap_max"])
        control.append(out["control"]["gap_max"])
        assert out["served_tokens"] == R * N - 8
        assert out["control"]["positions"] == R * N - 8
        assert [len(r["control_gaps"]) for r in out["per_request"]] \
            == [N] * (R - 1) + [N - 8]
    assert min(control) > 3 * max(served), (served, control)
    assert min(control) > 0.01


def test_pick_sample_is_fixed_size_seeded_and_keeps_the_longest():
    """The same number of served tokens whatever the seed and however
    many requests the window finished; the longest request first; none
    cut by the window's close and none that failed."""
    recs = [{"id": f"r{i:02d}", "ok": True, "due": 10.0 + i,
             "done": 10.5 + i, "n_prompt": 100 + (7 * i) % 50,
             "token_ids": [1] * (4 + i % 9)} for i in range(20)]
    recs.append({"id": "cut", "ok": True, "due": 29.0, "done": 31.0,
                 "n_prompt": 999, "token_ids": [1] * 10})
    recs.append({"id": "bad", "ok": False, "due": 12.0, "done": 12.5,
                 "n_prompt": 999, "token_ids": []})
    a = check.pick_sample(recs, 10.0, 30.0, 45, seed=4)
    b = check.pick_sample(recs, 10.0, 30.0, 45, seed=4)
    c = check.pick_sample(recs, 10.0, 30.0, 45, seed=5)
    assert [r["id"] for r in a] == [r["id"] for r in b]
    assert [r["id"] for r in a] != [r["id"] for r in c]
    for s in (a, c):
        assert sum(r["compare"] for r in s) == 45
        assert all(r["compare"] == len(r["token_ids"]) for r in s[:-1])
        assert 0 < s[-1]["compare"] <= len(s[-1]["token_ids"])
        ids = {r["id"] for r in s}
        assert "cut" not in ids and "bad" not in ids
    longest = max(recs[:20], key=lambda r: (
        r["n_prompt"] + len(r["token_ids"]), r["id"]))
    assert a[0]["id"] == c[0]["id"] == longest["id"]
    # a window that finished too little gives what there is: the
    # verdict then refuses the count
    few = check.pick_sample(recs[:3], 10.0, 30.0, 45, seed=4)
    assert sum(r["compare"] for r in few) == 4 + 5 + 6
    assert check.pick_sample([], 10.0, 30.0, 45, seed=4) == []
