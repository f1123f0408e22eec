"""Mean time of one stage of a request's way to its first token, in
milliseconds: ``xllm_worker_first_token_stage_ms`` sum over count for the
metric file's ``stage``, close minus open, so over every request whose
first frame the worker wrote between the window's two scrapes. The stages
are the program's own table (``obs/spans.py`` ``FIRST_TOKEN_STAMPS``,
with ``master_in`` and ``total``). A program without the histogram gives
nothing."""

from chipbench import cluster

FAMILY = "xllm_worker_first_token_stage_ms"


def stage_mean_ms(ctx, stage):
    def delta(suffix):
        return cluster.labelled(ctx["counters_close"], FAMILY + suffix,
                                stage=stage) \
            - cluster.labelled(ctx["counters_open"], FAMILY + suffix,
                               stage=stage)

    n = delta("_count")
    return delta("_sum") / n if n > 0 else None


def read(ctx, info):
    return stage_mean_ms(ctx, info["stage"])
