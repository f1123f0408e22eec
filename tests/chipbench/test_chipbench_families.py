"""The seams through which a configuration brings its own weights and its
own reference walk: the Mistral weights and the check's gaps are the
parent's (pinned on commit a40da0e, before the generator moved into a
family module), and the walk hands every layer its kind and what the
layer before it handed on."""

import hashlib
import os

import numpy as np
import pytest

from chipbench import check, spec, weights
from test_chipbench_reference import TINY, config_dir

PINS = spec.load_json(os.path.join(os.path.dirname(__file__), "pins",
                                   "mistral_parent_pr28.json"))
SEEDS = [5, 2**31 + 9]
CONFIGS = ["mistral-7b-v01", "mistral-7b-v03"]


def config(name, tiny, **over):
    cfg = spec.load_json(os.path.join(config_dir(name), "config.json"))
    if tiny:
        cfg.update(TINY)
    cfg.update(over)
    return cfg


def digest(tree):
    """Names, types, shapes and bits of every leaf, in the order of the
    leaves' paths (as the pins were taken)."""
    import jax
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_leaves_with_path(tree)
    for path, x in sorted(leaves,
                          key=lambda pl: jax.tree_util.keystr(pl[0])):
        a = np.asarray(x)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode() + str(a.shape).encode())
        h.update(a.view(np.uint16 if a.dtype.itemsize == 2 else np.uint8)
                 .tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", CONFIGS)
def test_mistral_weights_are_the_parents_at_tiny_widths(name, seed):
    wts, cfg = spec.load_weights(config_dir(name)), config(name, True)
    pins, key = PINS["weights"][name], weights.root_key(seed)
    assert digest(wts.program_tree(cfg, seed)) \
        == pins[f"tiny_tree_seed{seed}"]
    assert digest(wts.layer_params(cfg, key, 1, "layer")) \
        == pins[f"tiny_layer1_seed{seed}"]
    assert digest(wts.head_params(cfg, key)) == pins[f"tiny_head_seed{seed}"]


@pytest.mark.parametrize("name,seed", [("mistral-7b-v01", SEEDS[1]),
                                       ("mistral-7b-v03", SEEDS[0])])
def test_mistral_weights_are_the_parents_at_published_widths(name, seed):
    """One layer and the head as the chip makes them (4096 wide, 14336
    feed-forward, the whole vocabulary): a seed each side of 2**31."""
    import jax
    wts, cfg = spec.load_weights(config_dir(name)), config(name, False)
    pins, key = PINS["weights"][name], weights.root_key(seed)
    layer = jax.jit(lambda k, i: wts.layer_params(cfg, k, i, "layer"))
    assert digest(layer(key, 2)) == pins[f"published_layer2_seed{seed}"]
    assert digest(wts.head_params(cfg, key)) \
        == pins[f"published_head_seed{seed}"]


def fixed_sample(vocab):
    rng = np.random.default_rng(20290)
    sample = [{"id": f"r{r}",
               "prompt": rng.integers(3, vocab, size=p).tolist(),
               "token_ids": rng.integers(3, vocab, size=n).tolist()}
              for r, (p, n) in enumerate([(70, 16), (41, 16), (55, 16)])]
    sample[-1]["compare"] = 9
    return sample


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", CONFIGS)
def test_the_check_reads_the_parents_gaps(name, seed):
    """One fixed sample (random served tokens, so that every gap is a
    number of its own) at tiny widths, v0.1 with a window of 8: every
    per-token gap, with and without the int8 control, is what the
    parent's ``check.py`` read."""
    pins = PINS["gaps"][name]
    cfg = config(name, True, **pins["config_over"])
    want = pins[f"seed{seed}"]
    ref, wts = spec.load_reference(config_dir(name)), \
        spec.load_weights(config_dir(name))
    sample = fixed_sample(cfg["vocab_size"])
    plain = check.compare(ref, wts, cfg, seed, sample)
    ctl = check.compare(ref, wts, cfg, seed, sample, control="int8")
    assert "control" not in plain
    for out in (plain, ctl):
        assert out["served_tokens"] == want["served_tokens"] == 16 + 16 + 9
        assert out["not_best"] == want["not_best"]
        assert out["gap_max"] == pytest.approx(want["gap_max"], abs=1e-6)
        for got, pinned in zip(out["per_request"], want["gaps"]):
            np.testing.assert_allclose(got["gaps"], pinned, atol=1e-6,
                                       rtol=0)
    assert ctl["control"]["gap_max"] == pytest.approx(
        want["control_gap_max"], abs=1e-6)
    assert ctl["control"]["not_best"] == want["control_not_best"]
    for got, pinned in zip(ctl["per_request"], want["control_gaps"]):
        np.testing.assert_allclose(got["control_gaps"], pinned, atol=1e-6,
                                   rtol=0)


# ---- a toy family of two kinds whose carry must arrive -------------------

class ToyWeights:
    """``first`` then ``second`` layers in turn; one matrix a layer."""
    D, V = 16, 64

    @staticmethod
    def layer_kinds(cfg):
        return ["first", "second", "second", "first", "second"]

    @classmethod
    def layer_params(cls, cfg, key, i, kind):
        import jax
        import jax.numpy as jnp
        k = jax.random.fold_in(key, 50 + i)
        shape = (cls.D, cls.D) if kind == "first" else (cls.D, 2 * cls.D)
        return {"w": weights.scaled_normal(k, shape, cls.D, jnp.bfloat16)}

    @classmethod
    def head_params(cls, cfg, key):
        import jax
        import jax.numpy as jnp
        k = jax.random.split(jax.random.fold_in(key, 7), 3)
        dt = jnp.bfloat16
        return {"embed": weights.scaled_normal(k[0], (cls.V, cls.D), 1, dt),
                "final_norm": weights.norm_weight(k[1], (cls.D,), dt),
                "lm_head": weights.scaled_normal(k[2], (cls.D, cls.V),
                                                 cls.D, dt)}


class ToyReference:
    """A ``first`` layer hands on which of its positions it found
    strongest so far (a running, causal argmax, and how many ``first``
    layers have spoken); a ``second`` layer mixes that position's row
    into every later one and REFUSES to run without it. ``walked``
    records what each layer was handed."""

    def __init__(self):
        self.walked = []

    @staticmethod
    def mm_f32(x, w):
        import jax
        import jax.numpy as jnp
        return jnp.matmul(x.astype(jnp.float32), w.astype(jnp.float32),
                          precision=jax.lax.Precision.HIGHEST)

    @staticmethod
    def embed(tokens, embed_w):
        import jax.numpy as jnp
        return embed_w[tokens].astype(jnp.float32)

    def layer(self, x, lp, cfg, mm, kind, carry):
        import jax
        import jax.numpy as jnp
        self.walked.append((kind, None if carry is None
                            else sorted(carry)))
        if kind == "first":
            y = x + jnp.tanh(mm(x, lp["w"]))
            score = jnp.sum(y * y, axis=-1)
            best = jax.lax.cummax(score, axis=0)
            pos = jax.lax.cummax(jnp.where(
                score >= best, jnp.arange(x.shape[0]), 0), axis=0)
            said = 1 if carry is None else carry["said"] + 1
            return y, {"pos": pos, "said": said}
        if carry is None:
            raise ValueError("a second layer needs what a first layer "
                             "handed on")
        a, b = jnp.split(mm(x, lp["w"]), 2, axis=-1)
        return x + jnp.tanh(a) + 0.5 * jnp.tanh(b)[carry["pos"]] \
            * carry["said"], carry

    @staticmethod
    def logits(x, final_norm, lm_head, cfg, mm=None):
        import jax.numpy as jnp
        mm = mm or ToyReference.mm_f32
        x = x.astype(jnp.float32)
        x = x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6)
        return mm(x * final_norm.astype(jnp.float32), lm_head)


def toy_sample(ref, wts, seed, mm):
    """Greedy tokens of a plain loop over the toy (layers one by one,
    carry threaded by hand): what a program serving it would serve."""
    key = weights.root_key(seed)
    head = wts.head_params({}, key)
    rng = np.random.default_rng(seed)
    sample = []
    for r, (p, n) in enumerate([(30, 12), (21, 12)]):
        toks = rng.integers(3, wts.V, size=p).tolist()
        for _ in range(n):
            x, carry = ref.embed(np.asarray(toks), head["embed"]), None
            for i, kind in enumerate(wts.layer_kinds({})):
                x, carry = ref.layer(x, wts.layer_params({}, key, i, kind),
                                     {}, mm, kind, carry)
            lg = ref.logits(x, head["final_norm"], head["lm_head"], {}, mm)
            toks.append(int(np.asarray(lg)[-1].argmax()))
        sample.append({"id": f"r{r}", "prompt": toks[:p],
                       "token_ids": toks[p:]})
    return sample


def test_the_walk_hands_each_layer_its_kind_and_the_carry():
    """Reference and control alike: five layers of two kinds, the carry
    starts at ``None`` for every sequence, arrives in every later layer,
    and a walk that dropped it would raise."""
    ref, wts = ToyReference(), ToyWeights
    sample = toy_sample(ref, wts, 3, ref.mm_f32)
    ref.walked.clear()
    out = check.compare(ref, wts, {}, 3, sample, control="int8")
    # served by the same arithmetic: every served token is the best
    assert out["served_tokens"] == 24 and out["gap_max"] < 1e-4
    assert out["control"]["positions"] == 24
    assert all(g >= 0 for r in out["per_request"]
               for g in r["control_gaps"])
    # a layer is traced once per (kind, mm, carry's structure), so each
    # of these was walked once by the reference and once by the control:
    # the first layer of a walk saw no carry, every other one the first
    # layer's, and no ``second`` layer ever went without
    assert ref.walked.count(("first", None)) == 2
    assert ref.walked.count(("second", ["pos", "said"])) == 2
    assert ref.walked.count(("first", ["pos", "said"])) == 2
    assert ("second", None) not in ref.walked
    # other weights give other tokens: the sample is not trivially best
    other = check.compare(ref, wts, {}, 4, sample)
    assert other["gap_max"] > 0.1


def test_a_walk_without_the_carry_would_raise():
    ref, wts = ToyReference(), ToyWeights
    key = weights.root_key(1)
    x = ref.embed(np.arange(8), wts.head_params({}, key)["embed"])
    with pytest.raises(ValueError, match="handed on"):
        ref.layer(x, wts.layer_params({}, key, 1, "second"), {},
                  ref.mm_f32, "second", None)


def result_of(gaps):
    return {"gap_max": max(gaps), "not_best": sum(g > 0 for g in gaps),
            "served_tokens": len(gaps),
            "per_request": [{"gaps": gaps[:40]}, {"gaps": gaps[40:]}]}


def test_a_quantile_of_the_gaps_is_compared_where_a_configuration_asks(
        capsys):
    """A model in which rounding now and then changes a discrete choice
    (an expert) serves a few tokens far from the reference's best, sound
    run and control alike (chip readings of PR 29: 5-7 of 96 with gaps up
    to 1.9, the int8 control 20-31 of 96 up to 2.2). Its ``meta.json``
    gives a quantile's limit beside the widest gap's: a few wide gaps
    pass it, many moderate ones do not; without the key only the widest
    gap is compared, as for the Mistral cells."""
    sound = [0.0] * 89 + [1.9, 1.09, 0.28, 0.06, 0.03, 0.03, 0.01]
    lower = [0.0] * 76 + [1.49, 0.91, 0.5, 0.49, 0.31, 0.3, 0.2, 0.18,
                          0.16, 0.16, 0.1, 0.09, 0.08, 0.07, 0.05, 0.05,
                          0.03, 0.03, 0.02, 0.01]
    assert len(sound) == len(lower) == 96
    assert check.gap_quantile(sound, 0.9) == 0.0       # the 87th smallest
    assert check.gap_quantile(lower, 0.9) == 0.16
    assert check.gap_quantile([], 0.9) == 0.0
    assert check.gap_quantile([0.5], 0.9) == 0.5
    limits = {"0.9": 0.05}
    ok, compared = check.verdict(result_of(sound), 4.0, 0, 96, limits)
    assert ok and list(compared) == [
        "served_token_gap_max", "served_token_gap_p90",
        "served_tokens_compared", "requests_failed"]
    assert compared["served_token_gap_p90"] == {"value": 0.0, "limit": 0.05}
    bad, compared = check.verdict(result_of(lower), 4.0, 0, 96, limits)
    assert not bad and compared["served_token_gap_p90"]["value"] == 0.16
    err = capsys.readouterr().err.splitlines()
    assert err[-3] == "CHECK served_token_gap_p90 0.160000 limit 0.050000 FAIL"
    # the widest gap alone, where no quantile is asked for
    ok, compared = check.verdict(result_of(lower), 4.0, 0, 96)
    assert ok and list(compared) == [
        "served_token_gap_max", "served_tokens_compared", "requests_failed"]
    assert not check.verdict(result_of(sound), 1.0, 0, 96)[0]
    assert not check.verdict(result_of(sound), 4.0, 1, 96, limits)[0]
    assert not check.verdict(result_of(sound), 4.0, 0, 97, limits)[0]


def test_the_harness_names_no_family():
    """``run.py``, ``check.py``, ``cluster.py``, ``spec.py`` and the
    shared ``weights.py`` read no tensor name of any family and no width
    key but ``vocab_size``; ``run.py`` and ``check.py`` import no family
    module by name."""
    import re
    names = re.compile(
        r"\b(q_proj|k_proj|v_proj|o_proj|gate_proj|up_proj|down_proj|"
        r"kv_a|kv_b|q_a|q_b|router_bias|layers_moe|hidden_size|intermediate_size|"
        r"num_attention_heads|num_key_value_heads|num_hidden_layers|"
        r"head_dim|kv_lora_rank|q_lora_rank|REHEARSAL_WIDTHS)\b")
    for file in ("run.py", "check.py", "cluster.py", "spec.py",
                 "weights.py"):
        src = open(os.path.join(spec.ROOT, "chipbench", file)).read()
        hits = [ln for ln in src.splitlines() if names.search(ln)]
        assert not hits, (file, hits)
    for file in ("run.py", "check.py"):
        src = open(os.path.join(spec.ROOT, "chipbench", file)).read()
        assert "gqa_decoder" not in src and "weight_families" not in src
        assert "chipbench.reference" not in src
