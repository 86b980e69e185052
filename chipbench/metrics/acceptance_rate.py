"""Speculation: draft tokens accepted over draft tokens proposed in the
window, from the registry's counters (deltas)."""


def read(ctx):
    drafted = ctx.delta("drafted")
    return ctx.delta("accepted") / drafted if drafted > 0 else None
