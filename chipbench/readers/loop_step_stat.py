"""A reading of what a looped model's step programs counted on the
device, from the step records' ``passes`` and ``exit_cdf`` (the engine
loop's record of each step) over the steps that ended inside the window
(under ``--trace 2`` the recorder's ring holds the window's last 512
steps). ``stat``:

- ``passes_per_step``: over the decode-only steps (each reads exactly one
  decode program's outputs), the passes of the whole layer stack the
  program's own loop counted, over the steps: the model's
  ``total_ut_steps`` unless a change leaves work out;
- ``exit_cdf_before_last``: over the window's decode rows, the mean
  cumulative exit probability after the last pass but one (each step's
  mean weighted by its decode rows).

A program whose step records carry neither (the parent's, or a model
without a layer loop) gives nothing.
"""


def read(ctx, info):
    lo = ctx["open_t"] + ctx["wall_minus_mono"]
    hi = ctx["close_t"] + ctx["wall_minus_mono"]
    steps = [s for s in ctx["steps"] if lo <= s.get("t_wall", 0.0) < hi]
    if info["stat"] == "passes_per_step":
        n = [s["passes"] for s in steps
             if s.get("kind") == "decode" and s.get("passes") is not None]
        return sum(n) / len(n) if n else None
    if info["stat"] == "exit_cdf_before_last":
        rows = [(s["exit_cdf"][-1], s.get("decode_tokens", 0))
                for s in steps if s.get("exit_cdf")]
        total = sum(w for _, w in rows)
        return sum(c * w for c, w in rows) / total if total else None
    raise ValueError(f"unknown stat {info['stat']!r}")
