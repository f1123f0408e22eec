"""The program's own spans in a device trace, and the device's idle time
laid over them.

While a device trace runs, the worker writes its ``xllm.*`` spans
(``xllm_service_tpu/obs/steptrace.py`` ``SPAN_NAMES``) into the host
plane of the same ``.xplane.pb`` as the device's operations, on one
clock. ``idle_by_span`` gives every idle nanosecond of the device to the
innermost span that covers it on the engine-loop thread: the usual gap
between two launches is ``post``, ``emit``, ``obs_flush``, ``sched``,
``pack`` and ``dispatch`` one after another, none of them most of it, so
naming a gap by one span that covers most of it (``trace.top_idle_gaps``)
would name nothing. Events are ``trace.load_events``'s.
"""

from __future__ import annotations

import re
import statistics
from typing import Any, Dict, List, Optional, Sequence, Tuple

from chipbench import trace

Event = Dict[str, Any]

STEP_SPAN = "xllm.loop.step"
# What the engine-loop thread emits: the loop's own spans, the step's
# phases under them, and the prefix index's two (called from a step).
# ``xllm.admit*`` spans are on the request handlers' threads.
ENGINE_THREAD = re.compile(r"^xllm\.(loop|step|kv)\.")
NO_SPAN = "no span"


def program_spans(events: Sequence[Event], pattern: str = r"^xllm\."
                  ) -> List[Event]:
    """The program's spans whose name matches ``pattern``, by start."""
    rx = re.compile(pattern)
    out = [e for e in events if not trace.DEVICE_PLANE.match(e["plane"])
           and e["name"].startswith("xllm.") and rx.search(e["name"])]
    out.sort(key=lambda e: (e["start"], -e["dur"]))
    return out


def innermost_segments(spans: Sequence[Event]) -> List[Tuple[int, int, str]]:
    """Disjoint ``(start, end, name)`` segments, in order, each named by
    the innermost of the (nested) spans that cover it. A child that
    outlasts its parent is cut at the parent's end."""
    out: List[Tuple[int, int, str]] = []
    stack: List[Tuple[int, str]] = []         # (end, name), outermost first
    cur = 0

    def close_top() -> None:
        nonlocal cur
        end, name = stack.pop()
        if end > cur:
            out.append((cur, end, name))
            cur = end

    for e in sorted(spans, key=lambda e: (e["start"], -e["dur"])):
        s, t = e["start"], e["start"] + e["dur"]
        while stack and stack[-1][0] <= s:
            close_top()
        if stack:
            if s > cur:
                out.append((cur, s, stack[-1][1]))
            t = min(t, stack[-1][0])
        cur = max(cur, s) if stack else s
        stack.append((t, e["name"]))
    while stack:
        close_top()
    return out


def idle_by_span(events: Sequence[Event]) -> List[List[Any]]:
    """The device's idle time inside the traced window, by the innermost
    engine-loop span that covers it, and ``no span`` for the rest:
    ``[[name, seconds]]``, largest first; the seconds sum to the idle
    time (window minus busy of the first device plane)."""
    planes = trace.device_planes(events)
    if not planes:
        return []
    t0, t1 = trace.window_of(events)
    dev = [(e["start"], e["start"] + e["dur"])
           for e in trace.on(events, planes[0], trace.OPS_LINE)]
    gaps = trace.gaps_ns(dev, t0, t1)
    segs = innermost_segments(program_spans(events, ENGINE_THREAD.pattern))
    tot: Dict[str, int] = {}
    i = 0
    for s, t in gaps:
        covered = 0
        while i < len(segs) and segs[i][1] <= s:
            i += 1
        j = i
        while j < len(segs) and segs[j][0] < t:
            a, b, name = segs[j]
            cov = min(t, b) - max(s, a)
            if cov > 0:
                tot[name] = tot.get(name, 0) + cov
                covered += cov
            j += 1
        if t - s > covered:
            tot[NO_SPAN] = tot.get(NO_SPAN, 0) + (t - s) - covered
    return [[k, v / 1e9] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])]


def per_step_ms(events: Sequence[Event], pattern: str, reduce: str
                ) -> Optional[float]:
    """Milliseconds in the spans matching ``pattern``, reduced as the
    metric's file says: ``median_per_span`` (each span's own length),
    ``median_per_step`` (per ``xllm.loop.step``, the sum of the matching
    spans that start inside it) or ``mean_per_step`` (all of them over
    the number of steps: for spans most steps do not have). None where
    the trace holds no such span to reduce over."""
    match = program_spans(events, pattern)
    if reduce == "median_per_span":
        return statistics.median(e["dur"] for e in match) / 1e6 \
            if match else None
    steps = program_spans(events, "^" + re.escape(STEP_SPAN) + "$")
    if not steps:
        return None
    if reduce == "mean_per_step":
        return sum(e["dur"] for e in match) / len(steps) / 1e6
    if reduce != "median_per_step":
        raise ValueError(f"unknown reduction {reduce!r}")
    sums = [0] * len(steps)
    i = 0
    for e in match:                         # both lists are by start
        while i < len(steps) and \
                steps[i]["start"] + steps[i]["dur"] <= e["start"]:
            i += 1
        if i < len(steps) and steps[i]["start"] <= e["start"]:
            sums[i] += e["dur"]
    return statistics.median(sums) / 1e6
