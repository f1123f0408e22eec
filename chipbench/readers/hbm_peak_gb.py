"""``memory_stats()["peak_bytes_in_use"]`` after the window, before the
reference runs, in GB."""


def read(ctx, info):
    if ctx.get("rehearsal") or not ctx.get("memory_peak_bytes"):
        return None
    return ctx["memory_peak_bytes"] / 1e9
