"""How full the decode batch ran: tokens sampled in decode
(``xllm_worker_step_tokens_total{phase="decode"}``) over the steps that
decoded (``xllm_worker_steps_total`` of phase ``decode`` or ``mixed``)
times the engine's ``max_batch_size`` (the mix's), close minus open, in
per cent."""

from chipbench import cluster


def read(ctx, info):
    def delta(family, **labels):
        return cluster.labelled(ctx["counters_close"], family, **labels) \
            - cluster.labelled(ctx["counters_open"], family, **labels)

    tokens = delta("xllm_worker_step_tokens_total", phase="decode")
    steps = delta("xllm_worker_steps_total", phase="decode") \
        + delta("xllm_worker_steps_total", phase="mixed")
    rows = int(ctx["cell"].traffic["engine"]["max_batch_size"])
    return 100.0 * tokens / (steps * rows) if steps > 0 else None
