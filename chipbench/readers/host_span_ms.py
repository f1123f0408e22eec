"""Host time inside the program's own spans (``xllm.*``, written into the
device trace's host plane while the worker's device trace runs), in
milliseconds: the spans matching the metric file's ``span_pattern``,
reduced as its ``reduce`` says (``chipbench/spans.py`` ``per_step_ms``).
A program without such spans gives nothing."""

from chipbench import spans


def read(ctx, info):
    tr = ctx.get("trace")
    if not tr:
        return None
    return spans.per_step_ms(tr["events"], info["span_pattern"],
                             info["reduce"])
