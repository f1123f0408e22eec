"""What the threads of some roots took of one core over the window, in
percent: the metric file's ``family`` (``xllm_thread_cpu_seconds_total``:
seconds on a core; ``xllm_thread_runq_wait_seconds_total``: seconds
runnable and waiting for one), close minus open, summed over the roots
it lists (``roots``) or over every root but those (``all_but``), over
``close_t - open_t``. Every thread of the worker's process has a root
(``obs/profiler.py``): the engine's loop, ``httpd.handler``, ``main``,
the other supervised ones, and ``unregistered`` for the runtime's native
threads.

Nothing where the program has no such series; nothing for ``all_but``
where no ``unregistered`` root is exported (an older program counts its
supervised threads alone, and their sum is not "the others"); nothing
for the run-queue wait where the host keeps no ``schedstat``
(``xllm_thread_clock{source="schedstat"}`` is not 1: the program then
reads ticks and knows no wait)."""

import re

_ROOT = re.compile(r'^(\w+)\{root="([^"]+)"\}$')
RUNQ = "xllm_thread_runq_wait_seconds_total"


def read(ctx, info):
    opened, closed = ctx["counters_open"], ctx["counters_close"]
    family = info["family"]
    if family == RUNQ and \
            closed.get('xllm_thread_clock{source="schedstat"}') != 1.0:
        return None
    series = {m.group(2): key for key in closed
              for m in [_ROOT.match(key)] if m and m.group(1) == family}
    if "all_but" in info:
        if "unregistered" not in series:
            return None
        roots = set(series) - set(info["all_but"])
    else:
        roots = set(series) & set(info["roots"])
    window = ctx["close_t"] - ctx["open_t"]
    if not roots or window <= 0:
        return None
    secs = sum(closed[series[r]] - opened.get(series[r], 0.0)
               for r in roots)
    return 100.0 * secs / window
