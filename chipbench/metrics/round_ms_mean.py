"""Step loop: mean wall time of one engine round in the window, from the
registry's ``round_wall_seconds`` sum and count (deltas)."""


def read(ctx):
    n = ctx.delta("round_wall_count")
    return 1e3 * ctx.delta("round_wall_sum") / n if n > 0 else None
