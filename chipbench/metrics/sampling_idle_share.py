"""Host sampling: device-idle time in the window that lies inside the
engine's logits pulls, host sampling and accept spans (``engine.draft_pull``,
``engine.draft_sample``, ``engine.verify_pull``, ``engine.accept``: profiler
spans of ``serving/engine.py``), over the window, in percent.  A span is
matched by its name before any ``#`` metadata suffix.  ``None`` where the
trace holds none of these spans."""
from chipbench.trace import idle_gaps

SPANS = frozenset({"engine.draft_pull", "engine.draft_sample",
                   "engine.verify_pull", "engine.accept"})


def _merged(spans):
    out = []
    for s, e in sorted(spans):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def overlap_ns(a, b):
    """Total overlap of two lists of disjoint intervals, each sorted."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def read(ctx):
    tr = ctx.trace
    if tr is None or tr.window_ns <= 0:
        return None
    mine = _merged((h.start_ns, h.end_ns) for h in tr.host
                   if h.name.split("#", 1)[0] in SPANS)
    if not mine:
        return None
    gaps = sorted(idle_gaps(tr.in_window(tr.ops), tr.t0_ns, tr.t1_ns))
    return 100.0 * overlap_ns(gaps, mine) / tr.window_ns
