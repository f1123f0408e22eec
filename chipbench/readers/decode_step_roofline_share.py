"""The whole decode step's share of its roofline.

Numerator: the least time the chip could take for the decode steps that
ran inside the traced window, max(ops / peak ops, bytes / peak bytes/s),
with ops and bytes from shapes by ``chipbench/kernel_costs/<kernel_cost>
.py`` ``cost(steps, contexts, cfg)``: ``steps`` is the number of
executions of the decode step program in the trace (each walks the
weights, whatever its rows), ``contexts`` one entry for each token that
arrived inside the traced seconds and was sampled by a decode step (a
request's first token is the prefill's and is left out): the positions it
attended over. Denominator: the device time of those executions, from
the device plane's ``XLA Modules`` line.
"""

from chipbench import spec, trace


def read(ctx, info):
    tr = ctx.get("trace")
    if not tr:
        return None
    mods = trace.modules_containing(tr["events"],
                                   info["program_op_pattern"])
    step_s = sum(m["dur"] for m in mods) / 1e9
    if step_s <= 0:
        return None
    off = ctx["wall_minus_mono"]
    contexts = []
    for r in ctx["records"]:
        n_seen = 0
        for t, k in r["frames"]:
            if tr["wall0"] <= t + off < tr["wall1"]:
                contexts += [r.get("n_prompt", 0) + n_seen + i
                             for i in range(k) if n_seen + i > 0]
            n_seen += k
    cost = spec.load_kernel_cost(info["kernel_cost"], ctx["root"])
    peaks = spec.peaks_for(ctx["device_kind"], ctx["root"])
    flops, bytes_ = cost.cost(len(mods), contexts, ctx["config"])
    least_s = max(flops / peaks["bf16_flops"], bytes_ / peaks["hbm_bytes_s"])
    return 100.0 * least_s / step_s if contexts else None
