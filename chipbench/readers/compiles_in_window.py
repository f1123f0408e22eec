"""Programs compiled inside the window: ``xllm_worker_jit_compiles_total``
plus ``xllm_worker_recompiles_total``, close minus open. 0 is the
contract; anything else was a stall some request paid for."""


def read(ctx, info):
    tot = 0.0
    for fam in ("xllm_worker_jit_compiles_total",
                "xllm_worker_recompiles_total"):
        tot += ctx["counters_close"].get(fam, 0.0) \
            - ctx["counters_open"].get(fam, 0.0)
    return tot
