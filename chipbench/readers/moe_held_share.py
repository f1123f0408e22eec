"""The share of the gate's assignments that THIS chip computed, for a
configuration that holds a share of a wider router: ``assignments`` over
``assignments`` + ``elsewhere``, from the step records' ``moe`` (the
engine loop's record of each step) over the steps that ended inside the
window (under ``--trace 2`` the recorder's ring holds the window's last
512 steps). With 40 of 320 experts held under an even router: 0.125.

A program whose step records carry no ``moe``, or whose ``moe`` carries
no ``elsewhere`` (the parent's), gives nothing.
"""


def read(ctx, info):
    del info
    lo = ctx["open_t"] + ctx["wall_minus_mono"]
    hi = ctx["close_t"] + ctx["wall_minus_mono"]
    recs = [s["moe"] for s in ctx["steps"]
            if lo <= s.get("t_wall", 0.0) < hi and s.get("moe")
            and "elsewhere" in s["moe"]]
    made = sum(r["assignments"] + r["elsewhere"] for r in recs)
    if not made:
        return None
    return sum(r["assignments"] for r in recs) / made
