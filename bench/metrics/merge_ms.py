"""Device time of the cross-chip collectives per launch and per chip, in
ms: every op in the window whose opcode is a collective (``all-gather``,
``all-reduce`` and the other cross-device opcodes, sync or async halves),
summed over the chips' planes, over the launches and the chips.  Nothing
to read where the trace holds no collective (one chip)."""
import re

from bench.lib import tracing

COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|collective-permute|all-to-all|reduce-scatter)(-start|-done)?$"
)


def read(ctx):
    if not ctx.events:
        return None
    lo, hi = tracing.window(ctx.events)
    planes = tracing.device_planes(ctx.events)
    ops = [
        e for e in ctx.events
        if e["plane"] in planes and e["line"] == tracing.OPS_LINE and lo <= e["start_ns"] < hi
        and COLLECTIVE.match(tracing.op_label(e["name"]).split(" ")[-1])
    ]
    launches = ctx.counters["batches"]
    if not ops or not launches:
        return None
    return sum(o["dur_ns"] for o in ops) / len(planes) / launches / 1e6
