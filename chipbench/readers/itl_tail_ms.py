"""The tail of the gap between streamed tokens over the window, as a
per-layer reading where the cell does not judge it: the percentile named
in the metric's file, from the load generator's records."""

from chipbench import stats


def read(ctx, info):
    gaps = [1000.0 * g for r in ctx["records"] if r["frames"]
            for t, g in stats.token_gaps(r)
            if ctx["open_t"] <= t < ctx["close_t"]]
    return stats.tail_percentile(gaps, float(info["percentile"]))
