"""Mean time a request waits in the worker's queue, from its arrival at
the engine to its first slot in a step: ``xllm_worker_queue_wait_ms``
sum over count, close minus open. A program without the histogram gives
nothing."""


def read(ctx, info):
    def delta(name):
        return ctx["counters_close"].get(name, 0.0) \
            - ctx["counters_open"].get(name, 0.0)

    n = delta("xllm_worker_queue_wait_ms_count")
    return delta("xllm_worker_queue_wait_ms_sum") / n if n > 0 else None
