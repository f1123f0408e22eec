"""Metric arithmetic: percentiles, spreads, and the reduction from the
load generator's per-request records to the end-to-end metrics.

Copied in spirit from ``benchmarks/loadgen.py`` (``_percentile``), with
the rule that a tail is reported only where the sample supports it."""

from __future__ import annotations

import statistics
from typing import Any, Dict, List, Optional, Sequence


def percentile(vals: Sequence[float], p: float) -> float:
    """Nearest-rank percentile on the sorted sample (loadgen's rule)."""
    if not vals:
        raise ValueError("percentile of an empty sample")
    s = sorted(vals)
    idx = min(int(round(p / 100.0 * (len(s) - 1))), len(s) - 1)
    return s[idx]


def supported(n: int, p: float, beyond: int = 10) -> bool:
    """A percentile is a statement about the samples beyond it: report
    it only where at least ``beyond`` samples lie past it (p90 wants 100
    samples, p95 wants 200). The median needs ``beyond`` on each side."""
    tail = min(p, 100.0 - p) / 100.0
    return n * tail >= beyond


def tail_percentile(vals: Sequence[float], p: float) -> Optional[float]:
    return percentile(vals, p) if supported(len(vals), p) else None


def iqr_share(vals: Sequence[float]) -> float:
    """The contract's spread: Q3 - Q1 of ``statistics.quantiles(n=4)``
    as a share of the median."""
    q = statistics.quantiles(vals, n=4)
    return (q[2] - q[0]) / statistics.median(vals)


def token_gaps(rec: Dict[str, Any]) -> List[List[float]]:
    """[arrival time, gap in s] of every streamed token after a request's
    first. A frame that carries k tokens after a wait of dt gives k gaps
    of dt / k at its arrival: what a reader of the stream sees, averaged
    over the frame."""
    out: List[List[float]] = []
    frames = rec["frames"]          # [[t, n_tokens], ...]
    for (t0, _), (t1, k) in zip(frames, frames[1:]):
        if k > 0:
            out.extend([[t1, (t1 - t0) / k]] * k)
    return out


def end_to_end(records: List[Dict[str, Any]], open_t: float, close_t: float
               ) -> Dict[str, Optional[float]]:
    """The end-to-end metrics of one window, from the generator's records
    (all times on the generator's monotonic clock, seconds).

    TTFT is from when a request was DUE, over the requests due inside
    the window; the token gap is over every token that arrived inside
    the window; tokens/s is every token that arrived inside the window
    over its length."""
    seconds = close_t - open_t
    ttft = [1000.0 * (r["frames"][0][0] - r["due"]) for r in records
            if r["ok"] and r["frames"] and open_t <= r["due"] < close_t]
    gaps = [1000.0 * g for r in records if r["frames"]
            for t, g in token_gaps(r) if open_t <= t < close_t]
    toks = sum(k for r in records for t, k in r["frames"]
               if open_t <= t < close_t)
    return {
        "ttft_p50_ms": tail_percentile(ttft, 50.0),
        "ttft_p90_ms": tail_percentile(ttft, 90.0),
        "itl_p95_ms": tail_percentile(gaps, 95.0),
        "out_tok_s": toks / seconds if seconds > 0 else None,
        "_n_ttft": len(ttft), "_n_gaps": len(gaps), "_tokens": toks,
    }


def lateness(records: List[Dict[str, Any]]) -> Dict[str, float]:
    """How late the generator sent each request after it was due."""
    late = [1000.0 * (r["sent"] - r["due"]) for r in records
            if r.get("sent") is not None]
    if not late:
        return {"median_ms": 0.0, "worst_ms": 0.0}
    return {"median_ms": statistics.median(late), "worst_ms": max(late)}
