"""Decode attention's share of its roofline.

Numerator: the least time the chip could take for the decode attention
of every token generated inside the traced window, max(ops / peak ops,
bytes / peak bytes/s), with ops and bytes computed from shapes by
``chipbench/kernel_costs/<kernel_cost>.py`` (for a windowed model the
bytes of min(context, window) tokens: what the algorithm needs).
Denominator: the device time of the decode-attention operations inside
the decode step program's executions, from the device plane.
"""

from chipbench import spec, trace


def read(ctx, info):
    tr = ctx.get("trace")
    if not tr:
        return None
    mods = trace.modules_containing(tr["events"],
                                   info["program_op_pattern"])
    ops = trace.op_events(tr["events"], info["op_pattern"], within=mods)
    kernel_s = sum(e["dur"] for e in ops) / 1e9
    if kernel_s <= 0:
        return None
    cost = spec.load_kernel_cost(info["kernel_cost"], ctx["root"])
    peaks = spec.peaks_for(ctx["device_kind"], ctx["root"])
    off = ctx["wall_minus_mono"]
    flops = bytes_ = 0.0
    for r in ctx["records"]:
        n_seen = 0
        for t, k in r["frames"]:
            if tr["wall0"] <= t + off < tr["wall1"]:
                for i in range(k):
                    # token n_seen + i attends over prompt + earlier ones
                    f, b = cost.cost(r.get("n_prompt", 0) + n_seen + i,
                                     ctx["config"])
                    flops += f
                    bytes_ += b
            n_seen += k
    least_s = max(flops / peaks["bf16_flops"], bytes_ / peaks["hbm_bytes_s"])
    return 100.0 * least_s / kernel_s if least_s > 0 else None
