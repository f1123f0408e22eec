"""Median time the device sits idle between the end of one step program
and the start of the next, from the device plane: the gap between
consecutive executions on ``XLA Modules`` of the programs that hold a
layer loop (the metric file's ``program_op_patterns``), less whatever
small operation (a key split, a copy) ran on ``XLA Ops`` in between."""

import bisect
import statistics

from chipbench import trace


def gaps_ns(events, patterns):
    planes = trace.device_planes(events)
    if not planes:
        return []
    mods = {(m["start"], m["dur"]) for p in patterns
            for m in trace.modules_containing(events, p)}
    mods = sorted(mods)
    ops = sorted((e["start"], e["start"] + e["dur"])
                 for e in trace.on(events, planes[0], trace.OPS_LINE))
    starts = [s for s, _ in ops]
    out = []
    for (a, da), (b, _) in zip(mods, mods[1:]):
        lo, hi = a + da, b
        between = ops[bisect.bisect_left(starts, lo):
                      bisect.bisect_left(starts, hi)]
        busy = trace.union_ns((s, min(t, hi)) for s, t in between)
        out.append(max(0, hi - lo - busy))
    return out


def read(ctx, info):
    tr = ctx.get("trace")
    if not tr:
        return None
    gaps = gaps_ns(tr["events"], info["program_op_patterns"])
    return statistics.median(gaps) / 1e6 if gaps else None
