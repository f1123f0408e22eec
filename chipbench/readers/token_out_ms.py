"""Mean time of one stage of a token's way from ``emit`` to the wire, in
milliseconds: ``xllm_worker_token_out_seconds_total`` for the metric
file's ``stage`` (``wake``: from the emit that handed the token out until
the handler's thread has it off the request's queue; ``write``: from
there until its frame is written) over
``xllm_worker_token_out_tokens_total``, close minus open, so over every
token a handler wrote between the window's two scrapes (a handler folds
its sums every 64 tokens and at its request's end). A program without
the counters gives nothing."""

from chipbench import cluster

SECONDS = "xllm_worker_token_out_seconds_total"
TOKENS = "xllm_worker_token_out_tokens_total"


def read(ctx, info):
    opened, closed = ctx["counters_open"], ctx["counters_close"]
    tokens = closed.get(TOKENS, 0.0) - opened.get(TOKENS, 0.0)
    if tokens <= 0:
        return None
    secs = cluster.labelled(closed, SECONDS, stage=info["stage"]) \
        - cluster.labelled(opened, SECONDS, stage=info["stage"])
    return 1e3 * secs / tokens
