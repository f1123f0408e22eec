"""1 - (union of the intervals in which an operation ran on the device)
/ (the traced window), from the device plane's ``XLA Ops`` line."""

from chipbench import trace


def read(ctx, info):
    if not ctx.get("trace"):
        return None
    return 100.0 * trace.idle_share(ctx["trace"]["events"])
