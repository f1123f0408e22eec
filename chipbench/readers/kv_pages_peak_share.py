"""Peak share of the KV pool's pages in use over the window: the
``kv_usage`` the worker reports per engine step (the heartbeat's
``kv_cache_usage``), from its step recorder."""


def read(ctx, info):
    lo = ctx["open_t"] + ctx["wall_minus_mono"]
    hi = ctx["close_t"] + ctx["wall_minus_mono"]
    use = [s["kv_usage"] for s in ctx["steps"]
           if lo <= s.get("t_wall", 0.0) < hi and "kv_usage" in s]
    return 100.0 * max(use) if use else None
