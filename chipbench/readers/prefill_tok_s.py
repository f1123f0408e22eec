"""Prompt tokens computed per second of device time in prefill programs:
the step recorder's ``prefill_tokens`` of the steps inside the traced
window, over the summed device time of the prefill step program's
executions in the trace."""

from chipbench import trace


def read(ctx, info):
    tr = ctx.get("trace")
    if not tr:
        return None
    evs = trace.modules_containing(tr["events"],
                                   info["program_op_pattern"])
    dev_s = sum(e["dur"] for e in evs) / 1e9
    toks = sum(s.get("prefill_tokens", 0) for s in ctx["steps"]
               if tr["wall0"] <= s.get("t_wall", 0.0) < tr["wall1"])
    return toks / dev_s if dev_s > 0 and toks > 0 else None
