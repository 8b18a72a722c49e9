"""Device idle share of the traced window: 1 - the union of device op
intervals over the window, in percent.  Split by the end-to-end metric it
moves (``idle_pct.bulk``, ``idle_pct.poisson``); every split reads this."""
from bench.lib import tracing


def read(ctx):
    return tracing.idle_pct(ctx.events) if ctx.events else None
