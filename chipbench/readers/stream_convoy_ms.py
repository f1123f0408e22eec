"""How long after an ``emit`` began the last of the handlers it woke had
written its frame, in milliseconds: for each span matching the metric
file's ``emit_pattern`` (``xllm.loop.emit``, the engine's thread), the
latest END among the spans matching ``token_pattern``
(``xllm.stream.token``, a handler's thread: an output off its queue ->
its frames written) that START between that emit's start and the next
emit's, less the emit's start; the median over the traced seconds'
emits that woke a handler. All of it is on the device trace's clock. A
program without the handlers' span gives nothing."""

import statistics

from chipbench import spans


def read(ctx, info):
    tr = ctx.get("trace")
    if not tr:
        return None
    emits = spans.program_spans(tr["events"], info["emit_pattern"])
    tokens = spans.program_spans(tr["events"], info["token_pattern"])
    if not emits or not tokens:
        return None
    convoys = []
    i = 0
    for k, emit in enumerate(emits):        # both lists are by start
        until = emits[k + 1]["start"] if k + 1 < len(emits) else None
        last = None
        while i < len(tokens) and tokens[i]["start"] < emit["start"]:
            i += 1                          # woken by an emit before the trace
        while i < len(tokens) and (until is None
                                   or tokens[i]["start"] < until):
            end = tokens[i]["start"] + tokens[i]["dur"]
            last = end if last is None else max(last, end)
            i += 1
        if last is not None:
            convoys.append(last - emit["start"])
    return statistics.median(convoys) / 1e6 if convoys else None
