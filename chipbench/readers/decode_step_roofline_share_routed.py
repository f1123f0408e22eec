"""``decode_step_roofline_share`` for a model whose decode step reads
the experts that RECEIVED a row and not all it holds: the whole decode
step's share of its roofline, with the routed experts' part of the
numerator from what the program counted on the device.

As the accepted reader: ``steps`` is the number of executions of the
decode step program in the trace, ``contexts`` one entry for each token
that arrived inside the traced seconds and was sampled by a decode step,
the denominator the device time of those executions. Besides, the step
records' ``moe`` of the DECODE-ONLY steps that ended inside the traced
seconds (a mixed iteration's record holds its prefill window's routing
too) give the mean assignments and experts touched a decode step, which
are counted ``steps`` times:
``chipbench/kernel_costs/<kernel_cost>.py`` ``cost(steps, contexts, cfg,
assignments, experts_touched)``. A program whose step records carry no
``moe`` gives nothing.
"""

from chipbench import spec, trace


def read(ctx, info):
    tr = ctx.get("trace")
    if not tr:
        return None
    mods = trace.modules_containing(tr["events"],
                                   info["program_op_pattern"])
    step_s = sum(m["dur"] for m in mods) / 1e9
    recs = [s["moe"] for s in ctx["steps"]
            if tr["wall0"] <= s.get("t_wall", 0.0) < tr["wall1"]
            and s.get("moe") and s.get("kind") == "decode"]
    if step_s <= 0 or not recs:
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
    if not contexts:
        return None
    a_step = len(mods) / len(recs)
    cost = spec.load_kernel_cost(info["kernel_cost"], ctx["root"])
    peaks = spec.peaks_for(ctx["device_kind"], ctx["root"])
    flops, bytes_ = cost.cost(
        len(mods), contexts, ctx["config"],
        a_step * sum(r["assignments"] for r in recs),
        a_step * sum(r["experts_touched"] for r in recs))
    least_s = max(flops / peaks["bf16_flops"], bytes_ / peaks["hbm_bytes_s"])
    return 100.0 * least_s / step_s
