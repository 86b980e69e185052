"""Decode programs: mean device time of one execution of the draft step in
the window (the ``jit_step`` program that runs draft_len + 1 times a
round; see ``trace.classify_steps``)."""
from chipbench.trace import classify_steps


def read(ctx):
    if ctx.trace is None:
        return None
    runs = ctx.trace.in_window(ctx.trace.modules)
    names = classify_steps(runs, "jit_step")
    if names is None:
        return None
    mine = [m.dur_ns for m in runs if m.name == names["draft"]]
    return 1e-6 * sum(mine) / len(mine)
