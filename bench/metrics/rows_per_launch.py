"""Queries per launch of the async micro-batcher over the window:
``AnnService.queries_served`` / ``AnnService.async_launches`` (counter
deltas).  Nothing to read where the window made no async launch."""


def read(ctx):
    launches = ctx.counters["async_launches"]
    return ctx.counters["queries"] / launches if launches else None
