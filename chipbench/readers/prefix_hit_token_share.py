"""Share of the prompt tokens admitted in the window that the worker's
prefix cache covered: ``xllm_worker_prefix_cache_hit_tokens_total`` over
itself plus the prompt tokens the worker computed
(``xllm_worker_step_tokens_total{phase="prefill"}``), both close minus
open, both counted by the worker over the same interval."""

from chipbench import cluster


def read(ctx, info):
    def delta(get):
        return get(ctx["counters_close"]) - get(ctx["counters_open"])

    hit = delta(lambda c: c.get(
        "xllm_worker_prefix_cache_hit_tokens_total", 0.0))
    computed = delta(lambda c: cluster.labelled(
        c, "xllm_worker_step_tokens_total", phase="prefill"))
    return 100.0 * hit / (hit + computed) if hit + computed > 0 else None
