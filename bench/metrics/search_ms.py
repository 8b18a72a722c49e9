"""Mean device time of one run of the search executable: the ``XLA
Modules`` events of the trace that contain the fused kernel's op, in ms."""
from bench.lib import registry, tracing


def read(ctx):
    if not ctx.events:
        return None
    work = registry.work_module(ctx.cell, ctx.cell.config["work"]["kernel"])
    mods = tracing.modules_containing(ctx.events, work.TRACE_NAME)
    if not mods:
        return None
    return sum(m["dur_ns"] for m in mods) / len(mods) / 1e6
