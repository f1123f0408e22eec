"""Share of a step program's device time that the operations matching
``op_pattern`` take, in per cent: their summed time inside the
executions of the programs that ``program_op_pattern`` tells, over those
executions' summed time, from the device plane. A loop or a call is left
out, because the operations of its body are events of their own.
"""

from chipbench import trace


def read(ctx, info):
    tr = ctx.get("trace")
    if not tr:
        return None
    mods = trace.modules_containing(tr["events"],
                                   info["program_op_pattern"])
    total = sum(m["dur"] for m in mods)
    ops = [e for e in trace.op_events(tr["events"], info["op_pattern"],
                                      within=mods)
           if not trace.CONTAINERS.match(e["name"])]
    if total <= 0 or not ops:
        return None
    return 100.0 * sum(e["dur"] for e in ops) / total
