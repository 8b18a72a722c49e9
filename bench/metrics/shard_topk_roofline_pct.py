"""The fused kernel's share of its roofline on a doc-sharded cell: the least
time of each chip's own share of the work (``n_docs / chips`` live rows,
``bench/work/<kernel>.py``, at the real queries per launch) over the
kernel's device time per chip, summed over the window's launches, in
percent.  ``topk_roofline_pct`` counts all ``n_docs`` on every chip, which
reads ``chips`` times high where each chip streams a part."""
from bench.lib import registry, tracing


def read(ctx):
    if not ctx.events or not ctx.peaks:
        return None
    w = ctx.cell.config["work"]
    work = registry.work_module(ctx.cell, w["kernel"])
    ops = tracing.ops_matching(ctx.events, work.TRACE_NAME)
    launches = ctx.counters["batches"]
    if not ops or not launches:
        return None
    planes = max(1, len(tracing.device_planes(ctx.events)))
    kernel_s = sum(o["dur_ns"] for o in ops) / planes / 1e9
    rows = ctx.counters["queries"] / launches
    share = int(ctx.cell.config["corpus"]["n_docs"]) // ctx.cell.chips
    least, _ = work.least_seconds(w["mode"], rows, share, int(w["width"]),
                                  int(w["itemsize"]), ctx.peaks)
    return 100.0 * launches * least / kernel_s if kernel_s > 0 else None
