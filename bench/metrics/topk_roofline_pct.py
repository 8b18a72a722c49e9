"""The fused kernel's share of its roofline: the least time of the logical
work of every launch in the window (``bench/work/<kernel>.py``, from the
real queries per launch and the live rows) over the kernel's summed device
time in the trace (per chip), in percent."""
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
    least, _ = work.least_seconds(w["mode"], rows, int(ctx.cell.config["corpus"]["n_docs"]),
                                  int(w["width"]), int(w["itemsize"]), ctx.peaks)
    return 100.0 * launches * least / kernel_s if kernel_s > 0 else None
