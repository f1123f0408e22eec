"""A reading of the pool of matrix states by slot, from the step records'
``state`` (the engine loop's record of each step: ``live`` and
``snapshots`` slots held after it, and what it ``restored``,
``snapshotted`` and ``evicted``) over the steps that ended inside the
window (under ``--trace 2`` the recorder's ring holds the window's last
512 steps). ``stat``:

- ``evicted``, ``snapshotted``, ``restored``: that event summed over the
  steps;
- ``live_peak``: the most rows that held a live state after a step.

A program whose step records carry no ``state`` (the parent's, or a model
whose state is its pages alone) gives nothing.
"""


def read(ctx, info):
    lo = ctx["open_t"] + ctx["wall_minus_mono"]
    hi = ctx["close_t"] + ctx["wall_minus_mono"]
    recs = [s["state"] for s in ctx["steps"]
            if lo <= s.get("t_wall", 0.0) < hi and s.get("state")]
    if not recs:
        return None
    stat = info["stat"]
    if stat == "live_peak":
        return max(r["live"] for r in recs)
    if stat in ("evicted", "snapshotted", "restored"):
        return sum(r[stat] for r in recs)
    raise ValueError(f"unknown stat {stat!r}")
