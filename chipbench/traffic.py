"""One general traffic generator, driven by a mix's data file.

A mix (``chipbench/traffic/<mix>.json``) fixes, for a given window
length, HOW MUCH work a run offers: the number of requests and the totals
of prompt, shared-prefix and output tokens. A seed decides only the
order, the arrival jitter and the token values. Lengths are the
mid-quantiles of the mix's distributions (quantile-stratified), so their
multiset is the same for every seed; arrival gaps are drawn and then
rescaled to the window, so the count inside the window is the same too.

No JAX here: the load generator's process imports this module.
"""

from __future__ import annotations

import math
import statistics
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

FIRST_TOKEN_ID = 3      # 0..2 are pad / bos / eos in the served tokenizer


def quantile(spec: Dict[str, Any], q: float) -> int:
    """The ``q``-quantile of a length distribution, clipped to its
    range. ``lognormal`` (median, sigma) or ``uniform`` (min, max)."""
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        z = statistics.NormalDist().inv_cdf(min(max(q, 1e-9), 1 - 1e-9))
        v = float(spec["median"]) * math.exp(float(spec["sigma"]) * z)
    elif spec["dist"] == "uniform":
        v = lo + q * (hi - lo)
    elif spec["dist"] == "fixed":
        v = float(spec["value"])
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return int(round(min(max(v, lo), hi)))


def strata(spec: Dict[str, Any], n: int) -> List[int]:
    """``n`` lengths at the mid-quantiles (i + 1/2) / n: the same
    multiset whatever the seed."""
    return [quantile(spec, (i + 0.5) / n) for i in range(n)]


def _tokens(rng: np.random.Generator, n: int, vocab: int) -> List[int]:
    return rng.integers(FIRST_TOKEN_ID, vocab, size=n).tolist()


def _arrivals(rng: np.random.Generator, n: int, start: float, span: float,
              jitter: float) -> List[float]:
    """``n`` due times in [start, start + span): gaps of mean span / n,
    each jittered by up to +-``jitter`` of itself, then rescaled so that
    they fill the span exactly. The count in the span cannot vary."""
    if n <= 0:
        return []
    gaps = 1.0 + jitter * (2.0 * rng.random(n) - 1.0)
    gaps *= span / gaps.sum()
    first = gaps[0] * rng.random()     # phase of the first arrival
    t = start + first + np.concatenate([[0.0], np.cumsum(gaps[1:])])
    return [float(x) for x in np.minimum(t, start + span - 1e-6)]


def _phases(mix: Dict[str, Any], seconds: float
            ) -> List[Tuple[str, float, float]]:
    ramp, tail = float(mix["ramp_s"]), float(mix.get("tail_s", 0.0))
    return [("ramp", 0.0, ramp), ("window", ramp, seconds),
            ("tail", ramp + seconds, tail)]


def _docs(mix: Dict[str, Any], rng: np.random.Generator, vocab: int
          ) -> List[List[int]]:
    sp = mix.get("shared_prefix")
    if not sp:
        return []
    # Document j has the same length for every seed (only its tokens
    # differ), so the shared-prefix total of a run cannot vary.
    return [_tokens(rng, int(n), vocab) for n in sp["lengths"]]


def build(mix: Dict[str, Any], seed: int, seconds: float, vocab: int
          ) -> Dict[str, Any]:
    """The whole schedule of one run: shared documents, the requests that
    fill the cache in set-up, and the requests of ramp, window and tail
    with their due times (open loop) or their client and order (closed
    loop). Times are seconds after the generator's start."""
    rng = np.random.default_rng([int(seed), 0xC41B])
    docs = _docs(mix, rng, vocab)
    ramp = float(mix["ramp_s"])
    out: Dict[str, Any] = {
        "loop": mix["loop"], "seconds": float(seconds),
        "open_t": ramp, "close_t": ramp + float(seconds),
        "end_t": ramp + float(seconds) + float(mix.get("tail_s", 0.0)),
        "docs": docs, "requests": [],
        "setup_requests": [{"id": f"doc{j}", "doc": j, "tokens": [],
                            "max_tokens": 1} for j in range(len(docs))]
        if docs and mix["shared_prefix"].get("prefill_in_setup") else [],
    }
    if mix["loop"] == "open":
        out["requests"] = _open_loop(mix, rng, seconds, vocab, len(docs))
    elif mix["loop"] == "closed":
        out["clients"] = int(mix["clients"])
        out["stagger_s"] = float(mix["stagger_s"])
        out["requests"] = _closed_loop(mix, rng, seconds, vocab, len(docs))
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    return out


def _open_loop(mix, rng, seconds, vocab, n_docs) -> List[Dict[str, Any]]:
    rate = float(mix["rate_rps"])
    jitter = float(mix.get("arrival_jitter", 0.0))
    reqs: List[Dict[str, Any]] = []
    for phase, start, span in _phases(mix, seconds):
        n = int(round(rate * span))
        due = _arrivals(rng, n, start, span, jitter)
        p_len = [strata(mix["prompt_tokens"], n)[i]
                 for i in rng.permutation(n)]
        o_len = [strata(mix["output_tokens"], n)[i]
                 for i in rng.permutation(n)]
        for i in range(n):
            reqs.append({
                "id": f"{phase[0]}{i}", "phase": phase, "due": due[i],
                "doc": (len(reqs) % n_docs) if n_docs else None,
                "tokens": _tokens(rng, p_len[i], vocab),
                "max_tokens": o_len[i]})
    return reqs


def _closed_loop(mix, rng, seconds, vocab, n_docs) -> List[Dict[str, Any]]:
    """Each client's list of requests, sent one after another. Round k of
    all clients together holds every stratum exactly once, so the work of
    any stretch of the run is the same for every seed.

    How a round's strata go to the clients follows from the number of
    clients K and the window, with no key in the mix. Where the window
    holds two whole cycles of K rounds or more (by the mix's own ceiling,
    ``seconds * max_rounds_per_s``), client c takes stratum (c + k) mod K
    of the questions and (c + 3k) mod K of the answers after ONE seeded
    relabelling: every client walks the same cycle of lengths, after K
    rounds all have done the same work, and every seed reads alike (8
    clients in 51 s: the cycle closes every 18 s). Where it holds fewer
    (32 or 64 clients: half a cycle or less), which clients finish an
    iteration apart, and for how many rounds, would be the relabelling's,
    so each round is dealt by a permutation of its own (PR 42; PERF.md
    section 6 has what each dealing read in each cell)."""
    K = int(mix["clients"])
    total = float(mix["ramp_s"]) + seconds + float(mix.get("tail_s", 0.0))
    rounds = int(math.ceil(total * float(mix["max_rounds_per_s"]))) + 1
    q_len, a_len = strata(mix["prompt_tokens"], K), \
        strata(mix["output_tokens"], K)
    cyclic = seconds * float(mix["max_rounds_per_s"]) >= 2 * K
    # drawn under either dealing: the sets of PR 42 were measured so
    q_perm, a_perm = rng.permutation(K), rng.permutation(K)
    reqs: List[Dict[str, Any]] = []
    for k in range(rounds):
        if cyclic:
            q_deal = [q_perm[(c + k) % K] for c in range(K)]
            a_deal = [a_perm[(c + 3 * k) % K] for c in range(K)]
        else:
            q_deal, a_deal = rng.permutation(K), rng.permutation(K)
        for c in range(K):
            reqs.append({
                "id": f"c{c}k{k}", "client": c, "order": k, "due": None,
                "doc": ((c + k) % n_docs) if n_docs else None,
                "tokens": _tokens(rng, q_len[q_deal[c]], vocab),
                "max_tokens": int(a_len[a_deal[c]])})
    return reqs


def warm_requests(mix: Dict[str, Any], seed: int, vocab: int, n_docs: int
                  ) -> List[Dict[str, Any]]:
    """A few requests of the mix's own kind (median lengths) that end
    set-up, so that the served path has run end to end before the clock
    starts. Their tokens are not the window's."""
    rng = np.random.default_rng([int(seed), 0x3A7])
    n = int(mix.get("setup_warm_requests", 2))
    return [{"id": f"warm{i}", "doc": (i % n_docs) if n_docs else None,
             "tokens": _tokens(rng, quantile(mix["prompt_tokens"], 0.5),
                               vocab),
             "max_tokens": min(8, quantile(mix["output_tokens"], 0.5))}
            for i in range(n)]


def prompt_of(schedule: Dict[str, Any], req: Dict[str, Any]) -> List[int]:
    """The token ids a request sends: its shared document, then its own."""
    doc = schedule["docs"][req["doc"]] if req.get("doc") is not None else []
    return list(doc) + list(req["tokens"])


def totals(schedule: Dict[str, Any], phase: Optional[str] = "window"
           ) -> Dict[str, int]:
    """What the schedule offers: requests and token totals (of one phase
    of an open loop; of all rounds of a closed loop)."""
    reqs = [r for r in schedule["requests"]
            if phase is None or r.get("phase", phase) == phase]
    shared = sum(len(schedule["docs"][r["doc"]]) for r in reqs
                 if r.get("doc") is not None)
    return {"requests": len(reqs),
            "prompt_tokens": shared + sum(len(r["tokens"]) for r in reqs),
            "shared_prefix_tokens": shared,
            "output_tokens": sum(r["max_tokens"] for r in reqs)}


def warmup_shapes(mix: Dict[str, Any], page_size: int) -> Dict[str, Any]:
    """The step-program shapes this mix's traffic can reach, from the
    mix's own ``warmup`` data: prefill (batch, bucket, table width)
    triples and decode table widths. A lattice entry lists the values of
    each axis; a width too narrow to hold its bucket is left out.

    Every shape listed is warmed up in every run (each costs a run about
    1.3 s of tracing and loading); one left out compiles inside the
    window of the seed that reaches it."""
    w = mix["warmup"]
    prefill: List[Tuple[int, int, int]] = []
    for ent in w.get("prefill", []):
        shapes = [tuple(int(v) for v in s) for s in ent.get("shapes", [])]
        shapes += [(int(B), int(T), int(MP)) for B in ent.get("B", [])
                   for T in ent.get("T", []) for MP in ent.get("MP", [])
                   if MP * page_size >= T]
        prefill += [s for s in shapes if s not in prefill]
    return {"prefill": prefill,
            "decode_widths": [int(x) for x in w.get("decode_widths", [])]}
