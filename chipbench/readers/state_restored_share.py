"""Share of admitted rows that began from a cached page's convolution
tails, in per cent, from the step records' ``state_restored`` (one 0 or 1
per row admitted in the step; ``None`` for a model whose cached state is
its pages alone) over the steps that ended inside the window (under
``--trace 2`` the recorder's ring holds the window's last 512 steps).

A program whose step records carry no ``state_restored`` (the parent's,
or a model without such state) gives nothing.
"""


def read(ctx, info):
    lo = ctx["open_t"] + ctx["wall_minus_mono"]
    hi = ctx["close_t"] + ctx["wall_minus_mono"]
    rows = [r for s in ctx["steps"] if lo <= s.get("t_wall", 0.0) < hi
            for r in (s.get("state_restored") or ())]
    return 100.0 * sum(rows) / len(rows) if rows else None
