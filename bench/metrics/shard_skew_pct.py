"""How much longer the slowest chip's fused kernel runs than the chips'
mean over the window: max over chips of the kernel's summed device time,
over their mean, minus 1, in percent.  Every chip waits for the slowest at
the merge's collective, so this is what uneven shards cost a launch.
Nothing to read on one chip."""
from bench.lib import registry, tracing


def read(ctx):
    if not ctx.events:
        return None
    work = registry.work_module(ctx.cell, ctx.cell.config["work"]["kernel"])
    per_chip = {p: 0.0 for p in tracing.device_planes(ctx.events)}
    for o in tracing.ops_matching(ctx.events, work.TRACE_NAME):
        per_chip[o["plane"]] += o["dur_ns"]
    if len(per_chip) < 2 or not all(per_chip.values()):
        return None
    mean = sum(per_chip.values()) / len(per_chip)
    return 100.0 * (max(per_chip.values()) / mean - 1.0)
