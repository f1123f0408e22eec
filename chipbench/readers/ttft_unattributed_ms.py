"""What neither plane stamped of the time to the first token, in
milliseconds: the generator's mean (first frame - due) over the window's
own requests (due inside it, a first frame present: the sample of
``ttft_p50_ms``), less the master's mean share (stage ``master_in``) and
the worker's mean ``total`` (``first_token_stage_ms``). Left over: the
generator's connect and send, the forward's transit and the worker's
read of it, the relay out and the client's read. Nothing where the
program has no ``total``."""

from chipbench.readers.first_token_stage_ms import stage_mean_ms


def read(ctx, info):
    total = stage_mean_ms(ctx, "total")
    ttft = [1000.0 * (r["frames"][0][0] - r["due"]) for r in ctx["records"]
            if r.get("ok") and r.get("frames") and r.get("due") is not None
            and ctx["open_t"] <= r["due"] < ctx["close_t"]]
    if total is None or not ttft:
        return None
    return sum(ttft) / len(ttft) - (stage_mean_ms(ctx, "master_in") or 0.0) \
        - total
