"""From the profiler's trace to numbers: the one reduction every PR uses.

``load_events`` turns an ``.xplane.pb`` (read with nothing but JAX) into
plain event tuples; everything below works on those, so that the tests
can feed it a small recorded trace (``chipbench/testdata``) without a
chip. Times are nanoseconds on the trace's own clock.

Device planes are named ``/device:TPU:<n>``. On a device plane the line
``XLA Ops`` holds one event per executed operation (its busy time) and
``XLA Modules`` one per executed program. Host planes hold the threads'
TraceMe spans, among them the benchmark's own ``TraceAnnotation``s.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re
import statistics
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

Event = Dict[str, Any]   # plane, line, name, start, dur[, long]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
NAME_CHARS = 200    # an op's name is its whole HLO text: keep its head


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load_events(xplane_path: str, keep_host: bool = True) -> List[Event]:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    events: List[Event] = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and not keep_host:
            continue
        for line in plane.lines:
            for ev in line.events:
                e = {"plane": plane.name, "line": line.name,
                     "name": ev.name[:NAME_CHARS], "start": int(ev.start_ns),
                     "dur": int(ev.duration_ns)}
                if device and line.name == OPS_LINE:
                    for k, v in ev.stats:
                        if k in ("long_name", "tf_op", "hlo_op",
                                 "name_scope"):
                            e.setdefault("long", str(v))
                events.append(e)
    return events


def read_events(path: str) -> List[Event]:
    with gzip.open(path, "rt", encoding="utf-8") as f:
        return json.load(f)


def device_planes(events: Iterable[Event]) -> List[str]:
    return sorted({e["plane"] for e in events
                   if DEVICE_PLANE.match(e["plane"])})


def on(events: Iterable[Event], plane: str, line: str) -> List[Event]:
    return [e for e in events if e["plane"] == plane and e["line"] == line]


def union_ns(spans: Iterable[Tuple[int, int]]) -> int:
    """Total length of the union of [start, end) intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(spans):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_ns(spans: Iterable[Tuple[int, int]], t0: int, t1: int
            ) -> List[Tuple[int, int]]:
    """The idle intervals inside [t0, t1) that the spans leave."""
    out, cur = [], t0
    for s, e in sorted(spans):
        s, e = max(s, t0), min(e, t1)
        if e <= s:
            continue
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < t1:
        out.append((cur, t1))
    return out


def window_of(events: Sequence[Event]) -> Tuple[int, int]:
    """The traced window as the device saw it: first op start to last op
    end over all device planes."""
    ops = [e for e in events if DEVICE_PLANE.match(e["plane"])
           and e["line"] == OPS_LINE]
    if not ops:
        raise ValueError("the trace holds no operation on any device")
    return (min(e["start"] for e in ops),
            max(e["start"] + e["dur"] for e in ops))


def busy(events: Sequence[Event]) -> Dict[str, float]:
    """Seconds in which an operation ran, averaged over the device planes
    present, and the window's length: ``busy_s`` and ``window_s``."""
    t0, t1 = window_of(events)
    planes = device_planes(events)
    per = []
    for p in planes:
        spans = [(e["start"], e["start"] + e["dur"])
                 for e in on(events, p, OPS_LINE)]
        if spans:
            per.append(union_ns(spans))
    return {"busy_s": sum(per) / len(per) / 1e9,
            "window_s": (t1 - t0) / 1e9, "devices": len(per)}


def idle_share(events: Sequence[Event]) -> float:
    b = busy(events)
    return 1.0 - b["busy_s"] / b["window_s"]


def op_events(events: Sequence[Event], pattern: str,
              within: Optional[Sequence[Event]] = None) -> List[Event]:
    """Operations whose name (or long name) matches ``pattern``, on the
    first device plane; ``within`` keeps those inside these programs'
    executions."""
    planes = device_planes(events)
    if not planes:
        return []
    rx = re.compile(pattern)
    ops = [e for e in on(events, planes[0], OPS_LINE)
           if rx.search(e["name"]) or rx.search(e.get("long", ""))]
    if within is not None:
        spans = sorted((m["start"], m["start"] + m["dur"]) for m in within)
        ops = [e for e in ops if any(s <= e["start"] < t for s, t in spans)]
    return ops


def short(name: str) -> str:
    """An operation's own name: the HLO text up to its `` = ``."""
    return name.split(" = ", 1)[0].lstrip("%")[:80]


# The benchmark's own threads (its sleeps and polls) are not what the
# program's host was doing.
OWN_SPANS = re.compile(r"chipbench|cluster\.py|run\.py|\$time sleep")
CONTAINERS = re.compile(r"^%?(while|conditional|call)[.\s]")


def modules_containing(events: Sequence[Event], op_pattern: str
                       ) -> List[Event]:
    """Executions of the programs that ran an operation matching
    ``op_pattern``: the way to tell the step programs apart where the
    trace names every program ``jit__unknown``."""
    planes = device_planes(events)
    if not planes:
        return []
    rx = re.compile(op_pattern)
    starts = sorted(e["start"] for e in on(events, planes[0], OPS_LINE)
                    if rx.search(e["name"]))
    out = []
    for m in on(events, planes[0], MODULES_LINE):
        i = bisect.bisect_left(starts, m["start"])
        if i < len(starts) and starts[i] < m["start"] + m["dur"]:
            out.append(m)
    return out


def median_ms(evs: Sequence[Event]) -> Optional[float]:
    return statistics.median(e["dur"] for e in evs) / 1e6 if evs else None


def top_ops(events: Sequence[Event], n: int = 10) -> List[List[Any]]:
    """The device operations that took most time: [[name, seconds]]."""
    planes = device_planes(events)
    if not planes:
        return []
    tot: Dict[str, int] = {}
    for e in on(events, planes[0], OPS_LINE):
        if CONTAINERS.match(e["name"]):
            continue        # a loop's time is its body's operations'
        k = short(e["name"])
        tot[k] = tot.get(k, 0) + e["dur"]
    return [[k, v / 1e9] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def top_idle_gaps(events: Sequence[Event], n: int = 10) -> List[List[Any]]:
    """The longest device idle gaps, each named by what the host was
    doing in it: the innermost host span (a runtime TraceMe, or with the
    Python tracer a function) that covers at least 0.6 of the gap. Gaps
    of one name are summed: [[name, seconds]]."""
    planes = device_planes(events)
    if not planes:
        return []
    t0, t1 = window_of(events)
    dev = [(e["start"], e["start"] + e["dur"])
           for e in on(events, planes[0], OPS_LINE)]
    gaps = sorted(gaps_ns(dev, t0, t1), key=lambda g: g[0] - g[1])[:200]
    if not gaps:
        return []
    # Only a span at least 0.6 of the shortest gap long can name one:
    # that drops the Python tracer's hundreds of thousands of short calls.
    need = 0.6 * min(t - s for s, t in gaps)
    host = [e for e in events if not DEVICE_PLANE.match(e["plane"])
            and e["dur"] >= need and not OWN_SPANS.search(e["name"])]
    host.sort(key=lambda e: e["start"])
    tot: Dict[str, int] = {}
    for s, t in gaps:
        best, best_dur = "host: nothing traced (between launches)", None
        for e in host:
            if e["start"] >= t:
                break
            cov = min(t, e["start"] + e["dur"]) - max(s, e["start"])
            # the innermost span that covers most of the gap names it
            if cov >= 0.6 * (t - s) and (best_dur is None
                                         or e["dur"] < best_dur):
                best, best_dur = e["name"], e["dur"]
        tot[best] = tot.get(best, 0) + (t - s)
    return [[k[:80], v / 1e9] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def describe(events: Sequence[Event], n: int = 40) -> Dict[str, Any]:
    """What a trace holds, for looking at one by hand: per plane and line
    the event count and the names that took most time."""
    out: Dict[str, Any] = {}
    groups: Dict[Tuple[str, str], Dict[str, List[int]]] = {}
    for e in events:
        g = groups.setdefault((e["plane"], e["line"]), {})
        rec = g.setdefault(e["name"], [0, 0])
        rec[0] += 1
        rec[1] += e["dur"]
    for (plane, line), names in sorted(groups.items()):
        top = sorted(names.items(), key=lambda kv: -kv[1][1])[:n]
        out[f"{plane} | {line}"] = {
            "events": sum(v[0] for v in names.values()),
            "top": [[k[:300], v[0], round(v[1] / 1e6, 3)] for k, v in top]}
    return out
