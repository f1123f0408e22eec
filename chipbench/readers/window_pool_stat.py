"""A reading of the window layers' pool (the second pair of pools of a
model with sliding-window layers beside full ones) from the worker's
``/metrics`` at the window's two scrapes. ``stat``:

- ``pages_peak_share``: the most pages of the pool that rows and tails
  held at once, up to the closing scrape, over the pool's pages, in per
  cent (``xllm_worker_kv_window_pages{kind="peak"|"size"}``; set-up's
  documents are in it: a peak is a peak);
- ``tail_hit_share``: admissions inside the window that resumed at a
  cached prefix's tail over those plus the ones whose deepest matched
  boundary had no live tail, in per cent
  (``xllm_worker_kv_window_tail_events_total{event="hit"|"miss"}``, close
  minus open).

A program without the families (the parent's, or a model without a
window pool) gives nothing.
"""

from chipbench import cluster


def read(ctx, info):
    opened, closed = ctx["counters_open"], ctx["counters_close"]
    stat = info["stat"]
    if stat == "pages_peak_share":
        fam = "xllm_worker_kv_window_pages"
        size = cluster.labelled(closed, fam, kind="size")
        peak = cluster.labelled(closed, fam, kind="peak")
        return 100.0 * peak / size if size > 0 else None
    if stat == "tail_hit_share":
        fam = "xllm_worker_kv_window_tail_events_total"
        hit, miss = (cluster.labelled(closed, fam, event=e)
                     - cluster.labelled(opened, fam, event=e)
                     for e in ("hit", "miss"))
        return 100.0 * hit / (hit + miss) if hit + miss > 0 else None
    raise ValueError(f"unknown stat {stat!r}")
