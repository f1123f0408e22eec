"""A reading of what the sparse layers counted on the device, from the
step records' ``moe`` (the engine loop's record of each step) over the
steps that ended inside the window (under ``--trace 2`` the recorder's
ring holds the window's last 512 steps). ``stat``, the first two over the
decode-only steps:

- ``experts_touched_share``: experts that received a row, over the
  experts there are (``n_routed_experts`` x sparse layers x steps), in
  per cent: the share of the expert weights a decode step reads;
- ``load_max_over_mean``: median over the steps of the busiest expert's
  rows over the mean rows of the experts touched;
- ``dropped``: assignments the gate made and no expert computed, summed
  over every step (prefill steps too): 0 under the dropless layer.

A program whose step records carry no ``moe`` gives nothing.
"""

import statistics


def read(ctx, info):
    lo = ctx["open_t"] + ctx["wall_minus_mono"]
    hi = ctx["close_t"] + ctx["wall_minus_mono"]
    steps = [s for s in ctx["steps"]
             if lo <= s.get("t_wall", 0.0) < hi and s.get("moe")]
    if info["stat"] == "dropped":
        return sum(s["moe"]["dropped"] for s in steps) if steps else None
    recs = [s["moe"] for s in steps if s.get("kind") == "decode"]
    if not recs:
        return None
    if info["stat"] == "load_max_over_mean":
        return statistics.median(r["load_max_over_mean"] for r in recs)
    if info["stat"] == "experts_touched_share":
        cfg = ctx["config"]
        sparse = int(cfg["num_hidden_layers"]) \
            - int(cfg["first_k_dense_replace"])
        return 100.0 * sum(r["experts_touched"] for r in recs) / (
            int(cfg["n_routed_experts"]) * sparse * len(recs))
    raise ValueError(f"unknown stat {info['stat']!r}")
