"""Median device time of one execution of the decode step program, from
the device plane's ``XLA Modules`` line."""

from chipbench import trace


def read(ctx, info):
    if not ctx.get("trace"):
        return None
    return trace.median_ms(trace.modules_containing(
        ctx["trace"]["events"], info["program_op_pattern"]))
