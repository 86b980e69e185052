"""Step loop, as the clients see it: the 95th percentile of the gaps
between consecutive tokens of a stream, over every gap that ends in the
window, in seconds.  Above capacity this tail swings with how many
admissions (each a prefill that stalls every stream) fall in the window,
so it is read here and bounds nothing."""
import numpy as np


def read(ctx):
    gaps = [b - a for r in ctx.records for a, b in zip(r.times, r.times[1:])
            if ctx.t_open <= b < ctx.t_close]
    return float(np.percentile(gaps, 95)) if gaps else None
