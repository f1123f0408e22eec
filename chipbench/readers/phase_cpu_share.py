"""The engine thread's own share of its host phases, in percent:
``xllm_worker_phase_cpu_seconds_total`` over
``xllm_worker_phase_seconds_total``, each summed over the phases the
metric file lists (``phases``: exact names, or ``*.<suffix>`` for every
program's) and taken close minus open. The rest of those phases' wall
time went to other threads' hold of the interpreter. A program without
the counter gives nothing."""

import re

_SERIES = re.compile(r'^(\w+)\{.*phase="([^"]+)"')


def _listed(phase, phases):
    return any(phase == p or (p.startswith("*.") and phase.endswith(p[1:]))
               for p in phases)


def read(ctx, info):
    def delta(family):
        tot = 0.0
        for key, v in ctx["counters_close"].items():
            m = _SERIES.match(key)
            if m and m.group(1) == family and _listed(m.group(2),
                                                      info["phases"]):
                tot += v - ctx["counters_open"].get(key, 0.0)
        return tot

    wall = delta("xllm_worker_phase_seconds_total")
    cpu = delta("xllm_worker_phase_cpu_seconds_total")
    return 100.0 * cpu / wall if wall > 0 and cpu > 0 else None
