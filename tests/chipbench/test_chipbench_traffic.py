"""Equal work in every run: a mix fixes counts and token totals for a
given window; a seed permutes and jitters and changes neither."""

import json
import os

import pytest

from chipbench import spec, traffic

ROOT = spec.ROOT
MIXES = ["chat", "docqa"]
SEEDS = list(range(1, 19)) + [2**31 + 11, 2**31 + 2**20]


def mix(name):
    return spec.load_json(os.path.join(ROOT, "chipbench", "traffic",
                                       name + ".json"))


@pytest.mark.parametrize("name", MIXES)
@pytest.mark.parametrize("phase", ["window", None])
def test_totals_equal_over_20_seeds(name, phase):
    m = mix(name)
    seen = {json.dumps(traffic.totals(
        traffic.build(m, s, 30.0, 32000), phase), sort_keys=True)
        for s in SEEDS}
    assert len(SEEDS) == 20 and len(seen) == 1, seen


@pytest.mark.parametrize("name", MIXES)
def test_seed_changes_tokens_and_order_only(name):
    m = mix(name)
    a, b = (traffic.build(m, s, 30.0, 32000) for s in (3, 4))
    assert [r["tokens"] for r in a["requests"]] != \
        [r["tokens"] for r in b["requests"]]
    assert sorted(len(r["tokens"]) for r in a["requests"]) == \
        sorted(len(r["tokens"]) for r in b["requests"])
    assert traffic.build(m, 3, 30.0, 32000) == a    # same seed, same run


def test_open_loop_counts_and_due_times():
    m = mix("chat")
    for seed in SEEDS:
        s = traffic.build(m, seed, 30.0, 32000)
        due = [r["due"] for r in s["requests"] if r["phase"] == "window"]
        assert len(due) == round(m["rate_rps"] * 30.0)
        assert all(s["open_t"] <= t < s["close_t"] for t in due)
        assert due == sorted(due)
        gaps = [b - a for a, b in zip(due, due[1:])]
        mean = 30.0 / len(due)
        assert min(gaps) > 0.02 * mean and max(gaps) < 2.5 * mean


def test_chat_prompts_in_range_and_page_edges_find_their_program():
    """Prompts that end on a page edge (256 and 384 tokens in the 51 s
    window) own one page more than their text fills; the table width
    they take is among the warmed shapes like every other prompt's."""
    m = mix("chat")
    s = traffic.build(m, 7, 51.0, 32000)
    page = m["engine"]["page_size"]
    warmed = set(traffic.warmup_shapes(m, page)["prefill"])
    buckets = (64, 128, 256, 512, 1024, 2048)
    lengths = [len(r["tokens"]) for r in s["requests"]]
    assert any(n % page == 0 for n in lengths)
    for r in s["requests"]:
        n = len(r["tokens"])
        assert 64 <= n <= 2047
        T = next(b for b in buckets if b >= n)
        owned = -(-(n + 1) // page)
        MP = 1 << (max(owned, T // page) - 1).bit_length()
        assert (1, T, MP) in warmed, (n, T, MP)
        assert 16 <= r["max_tokens"] <= 256
        assert min(r["tokens"]) >= traffic.FIRST_TOKEN_ID
        assert max(r["tokens"]) < 32000


def test_closed_loop_round_holds_every_stratum_once():
    m = mix("docqa")
    K = m["clients"]
    q = sorted(traffic.strata(m["prompt_tokens"], K))
    a = sorted(traffic.strata(m["output_tokens"], K))
    for seed in (1, 2, 2**31 + 5):
        s = traffic.build(m, seed, 30.0, 32000)
        rounds = {}
        for r in s["requests"]:
            rounds.setdefault(r["order"], []).append(r)
        for k, reqs in rounds.items():
            assert sorted(len(r["tokens"]) for r in reqs) == q
            assert sorted(r["max_tokens"] for r in reqs) == a
            assert sorted(r["client"] for r in reqs) == list(range(K))


def walks_shifted(m, seconds=51.0, seed=2**31 + 11):
    """Pairs of clients of which one walks the other's sequence of answer
    lengths, up to three rounds behind it."""
    walks = {}
    for r in traffic.build(m, seed, seconds, 32000)["requests"]:
        walks.setdefault(r["client"], []).append(r["max_tokens"])
    n = min(len(w) for w in walks.values())
    assert n >= 8
    return [(c, d, shift) for c in walks for d in walks if c != d
            for shift in range(4) if walks[c][shift:n] == walks[d][:n - shift]]


def test_how_a_rounds_lengths_are_dealt_follows_from_clients_and_window():
    """No mix states how a round's strata are dealt: where the window
    holds two whole cycles of K rounds or more by the mix's own ceiling
    (the Mistral cell's 8 clients in 51 s), one relabelling a run, and
    client c takes at round k + 1 what client c + 3 took at round k;
    where it holds fewer (32 and 64 clients; 8 clients in a window of a
    few seconds) each round is dealt by a permutation of its own and no
    client walks another's sequence (PERF.md section 6, PR 42)."""
    for name in ("docqa", "docqa32", "docqa64"):
        assert "deal" not in mix(name)
    for name in ("docqa32", "docqa64"):
        assert walks_shifted(mix(name)) == []
        assert walks_shifted(dict(mix(name), clients=8))
    K = mix("docqa")["clients"]
    assert {(c, d) for c, d, s in walks_shifted(mix("docqa")) if s == 1} \
        >= {(c, (c + 3) % K) for c in range(K)}
    ceiling = mix("docqa")["max_rounds_per_s"]
    assert walks_shifted(mix("docqa"), 2 * K / ceiling)
    assert walks_shifted(mix("docqa"), 2 * K / ceiling - 1.0) == []


def test_docqa_followups_compute_one_bucket():
    """Every follow-up computes (document tail past its last full page)
    + question tokens: more than 128 and at most 256, the one prefill
    bucket the mix warms up."""
    m = mix("docqa")
    page = m["engine"]["page_size"]
    s = traffic.build(m, 5, 30.0, 32000)
    assert [len(d) for d in s["docs"]] == m["shared_prefix"]["lengths"]
    for r in s["requests"]:
        doc = len(s["docs"][r["doc"]])
        fresh = doc % page + len(r["tokens"])
        assert 128 < fresh <= 256
        total = doc + len(r["tokens"]) + r["max_tokens"]
        assert total <= m["engine"]["max_model_len"]
    assert len(s["setup_requests"]) == len(s["docs"])
    pool = m["engine"]["num_pages"] * page
    assert 0.6 < sum(len(d) for d in s["docs"]) / pool < 0.85


def test_warmup_lattice_from_data():
    shapes = traffic.warmup_shapes(mix("chat"), 128)
    warm = shapes["prefill"]
    assert (1, 2048, 16) in warm and (1, 64, 1) in warm
    assert (4, 256, 2) in warm and (4, 1024, 16) in warm
    assert (1, 1024, 4) not in warm              # 4 pages < 1024 tokens
    assert len(set(warm)) == len(warm) == 58
    assert shapes["decode_widths"] == [1, 2, 4, 8, 16, 32]
    d = traffic.warmup_shapes(mix("docqa"), 128)
    assert set(T for _, T, _ in d["prefill"]) == {256, 1024, 2048}
    made = traffic.warmup_shapes({"warmup": {
        "prefill": [{"shapes": [[1, 64, 1]]}, {"B": [1, 2], "T": [64, 256],
                                               "MP": [1, 2]}],
        "decode_widths": [4]}}, 128)
    assert made["prefill"] == [(1, 64, 1), (1, 64, 2), (1, 256, 2),
                               (2, 64, 1), (2, 64, 2), (2, 256, 2)]


def test_quantiles():
    ln = {"dist": "lognormal", "median": 384, "sigma": 0.85, "min": 64,
          "max": 2047}
    assert traffic.quantile(ln, 0.5) == 384
    assert traffic.quantile(ln, 1e-6) == 64
    assert traffic.quantile(ln, 1 - 1e-6) == 2047
    assert traffic.quantile({"dist": "uniform", "min": 10, "max": 20},
                            0.5) == 15
    assert traffic.quantile({"dist": "fixed", "value": 128, "min": 1,
                             "max": 999}, 0.3) == 128
    with pytest.raises(ValueError):
        traffic.quantile({"dist": "zipf", "min": 1, "max": 2}, 0.5)
