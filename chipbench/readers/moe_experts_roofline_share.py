"""The routed experts' grouped matmuls' share of their roofline over the
traced seconds.

Numerator: the least time the chip could take for what the sparse layers
routed in the steps that ended inside the traced seconds, max(ops / peak
ops, bytes / peak bytes/s), with ops and bytes from
``chipbench/kernel_costs/<kernel_cost>.py`` and the counts the program
itself made on the device (the step records' ``moe``: assignments
computed, experts touched). Denominator: the device time of the
operations matching ``op_pattern``, the expert layer's three grouped
matmuls ALONE, over the same seconds, from the device plane (a loop or a
call is left out: its body's operations are events of their own). The
layer's sort, row gather, un-sort and weighted sum are XLA fusions that
a trace names ``fusion.<n>`` and ties to no layer (an operation's event
carries its text and its time, not the name the program gave it), so
they are outside this share. A program whose step records carry no
``moe`` gives nothing.
"""

from chipbench import spec, trace


def routed(ctx, lo, hi):
    """(assignments, experts touched) of the steps that ended in [lo, hi)
    on the wall clock; None where no step says."""
    recs = [s["moe"] for s in ctx["steps"]
            if lo <= s.get("t_wall", 0.0) < hi and s.get("moe")]
    if not recs:
        return None
    return (sum(r["assignments"] for r in recs),
            sum(r["experts_touched"] for r in recs))


def read(ctx, info):
    tr = ctx.get("trace")
    if not tr:
        return None
    counts = routed(ctx, tr["wall0"], tr["wall1"])
    kernel_s = sum(e["dur"] for e in trace.op_events(
        tr["events"], info["op_pattern"])
        if not trace.CONTAINERS.match(e["name"])) / 1e9
    if counts is None or kernel_s <= 0:
        return None
    flops, bytes_ = spec.load_kernel_cost(info["kernel_cost"],
                                          ctx["root"]).cost(
        counts[0], counts[1], ctx["config"])
    peaks = spec.peaks_for(ctx["device_kind"], ctx["root"])
    least_s = max(flops / peaks["bf16_flops"], bytes_ / peaks["hbm_bytes_s"])
    return 100.0 * least_s / kernel_s
