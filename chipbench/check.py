"""What decides ``correct``: the tokens the timed window itself served,
held against the configuration's plain reference.

After the window has closed and the program's state is freed, a sample of
the requests it finished (drawn from the seed, the longest always in it,
cut to the same number of served tokens in every run)
is run ONCE through the reference: prompt and served tokens together,
teacher-forced, layer by layer, the weights regenerated from the seed.
Which layers there are, what their weights are called and what a layer
hands to the next are the configuration's own (its ``weights.py`` and
``reference.py``, ``chipbench/spec.py``): nothing here knows a family.
For every served token the reference's logits at the position before it
say how far that token's logit lies below the reference's best. All the
traffic decodes greedily, so an exact program serves the reference's own
best token and the gap is 0; rounding in the served type picks a
near-tie now and then and the gap is small; a wrong page, a wrong mask,
a dropped norm or a lower precision picks tokens the reference ranks
well down. The number compared is the WIDEST such gap, in logit units;
a configuration may ask for a quantile of the gaps beside it
(``verdict``).

The control (``--control int8``; never in a benchmark run) is the
reference itself in the nearest precision below bfloat16: every linear
layer's inputs rounded to int8 (weights per output channel, activations
per row). At the very positions of the same prompts at which the served
tokens were chosen, and at no others, it reads the gap of the token that
precision puts first: both numbers are the widest of the same draws.
"""

from __future__ import annotations

import sys
from typing import Any, Callable, Dict, List, Optional

import numpy as np


def pick_sample(records: List[Dict[str, Any]], open_t: float,
                close_t: float, n_tokens: int, seed: int
                ) -> List[Dict[str, Any]]:
    """Requests that were due and finished inside the window (never one
    cut by its close), until they hold ``n_tokens`` served tokens: the
    one with the most tokens first, then others in an order drawn from
    the seed. Each entry's ``compare`` says how many of its served
    tokens are compared (all, but for the last entry's), so that every
    run compares exactly ``n_tokens``; fewer only where the window
    finished fewer, which the verdict refuses."""
    done = [r for r in records if r.get("ok") and r.get("done") is not None
            and r["due"] is not None and open_t <= r["due"]
            and r["done"] <= close_t and r["token_ids"]]
    if not done:
        return []
    done.sort(key=lambda r: r["id"])
    longest = max(done, key=lambda r: (r["n_prompt"] + len(r["token_ids"]),
                                       r["id"]))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed), 0x5A3])
    sample, left = [], int(n_tokens)
    for r in [longest] + [rest[i] for i in rng.permutation(len(rest))]:
        if left <= 0:
            break
        sample.append(dict(r, compare=min(left, len(r["token_ids"]))))
        left -= sample[-1]["compare"]
    return sample


def int8_mm(x, w):
    """The control's linear layer: both inputs rounded to int8 (symmetric;
    the weight per output channel, the activation per row), multiplied
    exactly, scaled back."""
    import jax
    import jax.numpy as jnp
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    sx = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-30) / 127
    sw = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True), 1e-30) / 127
    xq = jnp.clip(jnp.round(x / sx), -127, 127)
    wq = jnp.clip(jnp.round(w / sw), -127, 127)
    return jnp.matmul(xq, wq, precision=jax.lax.Precision.HIGHEST) * sx * sw


CONTROLS: Dict[str, Callable] = {"int8": int8_mm}


PAD_TO = 512      # sequence lengths and
ROWS = 256        # served rows are padded to these, so that shapes repeat


def _final_hidden(ref, wts, cfg, key, embed_w, seqs: List[np.ndarray],
                  mms: List[Callable]) -> List[List[Any]]:
    """Final hidden states [T, D] of every sequence under every ``mm``,
    one layer's weights alive at a time. The configuration's own
    ``layer_kinds`` says what each layer is; one weight maker and one
    layer are compiled per kind (and per ``mm``), and whatever a layer
    hands on (``carry``; ``None`` before the first layer) goes to the next
    layer of the same sequence under the same ``mm``. Each sequence is
    padded at its end to a multiple of ``PAD_TO`` (causal attention:
    padding changes nothing before it), so that a handful of compiled
    layers serve every sequence of every run."""
    import jax
    import jax.numpy as jnp
    embed = jax.jit(ref.embed)
    padded = [jnp.asarray(np.pad(s, (0, -len(s) % PAD_TO))) for s in seqs]
    # state[m][s] = (x, carry) of sequence s under mms[m]
    state = [[(embed(t, embed_w), None) for t in padded] for _ in mms]
    kinds = list(wts.layer_kinds(cfg))
    make_layer = {kind: jax.jit(
        lambda k, i, kind=kind: wts.layer_params(cfg, k, i, kind))
        for kind in set(kinds)}
    run_layer = {kind: [jax.jit(
        lambda x, lp, carry, mm=mm, kind=kind:
        ref.layer(x, lp, cfg, mm, kind, carry)) for mm in mms]
        for kind in set(kinds)}
    for i, kind in enumerate(kinds):
        lp = make_layer[kind](key, i)
        state = [[f(x, lp, carry) for x, carry in row]
                 for f, row in zip(run_layer[kind], state)]
        del lp
    return [[x[:len(s)] for (x, _), s in zip(row, seqs)] for row in state]


def compare(ref, wts, cfg: Dict[str, Any], seed: int,
            sample: List[Dict[str, Any]], control: Optional[str] = None
            ) -> Dict[str, Any]:
    """Run the configuration's reference ``ref`` over the sample, on the
    weights its generator ``wts`` makes from the seed, and read the gaps.

    Each sample entry holds ``prompt`` (token ids), ``token_ids`` (the
    served tokens) and optionally ``compare`` (how many of them to
    compare; all without it). Returns the widest served-token gap, how
    many served tokens were not the reference's best, and per-request
    detail; with ``control``, also the widest gap of the control's first
    choices at the same positions."""
    import jax
    import jax.numpy as jnp
    from chipbench import weights
    out: Dict[str, Any] = {"served_tokens": 0, "not_best": 0,
                           "gap_max": 0.0, "per_request": []}
    if not sample:
        return out
    served = [list(s["token_ids"])[:s.get("compare", len(s["token_ids"]))]
              for s in sample]
    seqs = [np.asarray(list(s["prompt"]) + t, np.int32)
            for s, t in zip(sample, served)]
    ctl_mm = CONTROLS[control] if control else None
    mms = [ref.mm_f32] + ([ctl_mm] if control else [])
    key = weights.root_key(seed)
    head = wts.head_params(cfg, key)
    hidden = _final_hidden(ref, wts, cfg, key, head["embed"],
                           [q[:-1] for q in seqs], mms)
    # The output head goes in as an argument: closed over, JAX would embed
    # it in the module as a literal (1.06 GB of float32 for a vocabulary
    # of 129,280 by 2048: 19.5 s of every run of that cell).
    norm, lm = head["final_norm"], head["lm_head"]

    def gap_below_best(lg, chosen):
        return lg.max(axis=-1) - jnp.take_along_axis(
            lg, chosen[:, None], axis=-1)[:, 0]

    @jax.jit
    def served_gaps(x, chosen, norm, lm):
        return gap_below_best(ref.logits(x, norm, lm, cfg), chosen)

    @jax.jit
    def first_choice_gaps(x, xc, norm, lm):
        first = ref.logits(xc, norm, lm, cfg, ctl_mm).argmax(axis=-1)
        return gap_below_best(ref.logits(x, norm, lm, cfg), first)

    def padded(a, n):
        return jnp.pad(a, [(0, n - a.shape[0])] + [(0, 0)] * (a.ndim - 1))

    ctl = {"precision": control, "gap_max": 0.0, "positions": 0,
           "not_best": 0}
    for j, (s, toks) in enumerate(zip(sample, served)):
        p, n = len(s["prompt"]), len(toks)
        rows = -(-n // ROWS) * ROWS
        # Served token i was chosen at position p - 1 + i.
        at = padded(hidden[0][j][p - 1:], rows)
        gaps = np.asarray(served_gaps(
            at, padded(jnp.asarray(toks, jnp.int32), rows), norm, lm))[:n]
        out["served_tokens"] += n
        out["not_best"] += int((gaps > 0).sum())
        out["gap_max"] = max(out["gap_max"], float(gaps.max()))
        detail = {"id": s["id"], "prompt_tokens": p, "served": n,
                  "gap_max": float(gaps.max()), "at": int(gaps.argmax()),
                  "not_best": int((gaps > 0).sum()),
                  "gaps": [float(g) for g in gaps]}
        if control:
            g = np.asarray(first_choice_gaps(
                at, padded(hidden[1][j][p - 1:], rows), norm, lm))[:n]
            ctl["gap_max"] = max(ctl["gap_max"], float(g.max()))
            ctl["positions"] += n
            ctl["not_best"] += int((g > 0).sum())
            detail["control_gaps"] = [float(x) for x in g]
        out["per_request"].append(detail)
    if control:
        out["control"] = ctl
    return out


def quantile_name(q) -> str:
    return f"served_token_gap_p{round(100 * float(q))}"


def gap_quantile(gaps: List[float], q) -> float:
    """Nearest-rank quantile of the per-token gaps (``stats.percentile``;
    0 where there are none)."""
    from chipbench import stats
    return float(stats.percentile(gaps, 100.0 * float(q))) if gaps else 0.0


def verdict(result: Dict[str, Any], limit: float, n_failed: int,
            n_wanted: int,
            quantile_limits: Optional[Dict[str, float]] = None):
    """Print each number compared beside its limit (standard error too)
    and say whether the run is correct. Returns ``(correct, compared)``,
    ``compared`` being those numbers for the result line:
    ``{name: {"value": v, "limit": l}}``.

    The widest gap is always compared. A configuration whose rounding in
    the served type now and then changes a CHOICE inside the model (which
    experts a token takes) sees a few wide gaps at sound runs and at the
    control alike; it gives ``quantile_limits`` (``meta.json``
    ``check.served_token_gap_quantile_limits``, ``{"0.9": limit}``) and
    is held to that quantile of the served tokens' gaps as well, which
    the few leave alone and a lower precision moves."""
    gaps = [g for r in result["per_request"] for g in r["gaps"]]
    # (name, value, limit, within it, how the pair is printed, a note)
    rows = [("served_token_gap_max", result["gap_max"], limit,
             result["gap_max"] <= limit, "{:.6f} limit {:.6f}",
             f" ({result['not_best']} of {result['served_tokens']} served "
             f"tokens were not the reference's best)")]
    for q, q_limit in sorted((quantile_limits or {}).items()):
        value = gap_quantile(gaps, q)
        rows.append((quantile_name(q), value, float(q_limit),
                     value <= float(q_limit), "{:.6f} limit {:.6f}", ""))
    rows += [("served_tokens_compared", result["served_tokens"], n_wanted,
              result["served_tokens"] == n_wanted, "{} limit =={}",
              f" (of {len(result['per_request'])} requests)"),
             ("requests_failed", n_failed, 0, n_failed == 0,
              "{} limit {}", "")]
    for name, value, lim, ok, form, note in rows:
        ln = (f"CHECK {name} {form.format(value, lim)} "
              f"{'ok' if ok else 'FAIL'}{note}")
        print(ln, flush=True)
        print(ln, file=sys.stderr, flush=True)
    return (all(r[3] for r in rows),
            {r[0]: {"value": r[1], "limit": r[2]} for r in rows})
