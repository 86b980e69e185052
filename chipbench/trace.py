"""Reduce a profiler trace of the measured window to device times.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it.  What a v5e trace holds (read by hand
on the chip):

- the chip's plane is ``/device:TPU:0`` (``/device:CUSTOM:...`` planes hold
  no operations);
- its line "XLA Modules" has one event per program execution, named
  ``<jit name>(<fingerprint>)``: ``jit_step(...)`` for both the target's
  verify and the draft step (two fingerprints), ``jit__prefill(...)`` for
  each prompt length of each model, ``jit__scatter_prefill(...)``;
- its line "XLA Ops" has one event per executed HLO operation, named by
  the operation's HLO text (``%fusion.242 = bf16[...] fusion(...)``); a
  ``while`` loop's event spans the operations of its body, so operations
  nest; the paged-attention kernel is the custom call
  ``%paged_decode_attention_pallas.<n>``;
- host planes (``/host:CPU``) hold the harness's ``TraceAnnotation``
  spans and the runtime's dispatch and transfer events, on the same clock.

Busy time is the union of the operation intervals; a gap is an interval of
the window with no operation running.  The reduction is plain interval
arithmetic over ``Event`` lists, so it is checked on synthetic traces.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

__all__ = ["Event", "DeviceTrace", "read", "union_ns", "idle_gaps", "label_gaps",
           "program_totals", "self_times", "top_ops", "classify_steps", "WINDOW_SPAN"]

WINDOW_SPAN = "chipbench.window"  # host span the harness opens over the window


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float
    module: str = ""  # the CPU backend's ``hlo_module`` stat, where given
    line: str = ""

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    @property
    def base(self) -> str:
        """A program's jit name without its fingerprint: ``jit_step``."""
        return self.name.split("(", 1)[0]


@dataclasses.dataclass
class DeviceTrace:
    ops: List[Event]  # operations on the first device
    modules: List[Event]  # program executions on the first device
    host: List[Event]  # host spans (TraceAnnotation, dispatch)
    t0_ns: float  # window on the trace clock
    t1_ns: float
    n_devices: int = 1

    @property
    def window_ns(self) -> float:
        return self.t1_ns - self.t0_ns

    def in_window(self, events: Iterable[Event]) -> List[Event]:
        return [e for e in events if e.end_ns > self.t0_ns and e.start_ns < self.t1_ns]

    def busy_ns(self) -> float:
        return union_ns(self.in_window(self.ops), self.t0_ns, self.t1_ns)


def union_ns(events: Sequence[Event], t0: float = None, t1: float = None) -> float:
    """Length of the union of the events' intervals, clipped to [t0, t1]."""
    spans = sorted((e.start_ns, e.end_ns) for e in events)
    total, cur_s, cur_e = 0.0, None, None
    for s, e in spans:
        if t0 is not None:
            s = max(s, t0)
        if t1 is not None:
            e = min(e, t1)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def idle_gaps(events: Sequence[Event], t0: float, t1: float) -> List[Tuple[float, float]]:
    """Intervals of [t0, t1] in which no event runs, longest first."""
    gaps, cur = [], t0
    for s, e in sorted((e.start_ns, e.end_ns) for e in events):
        if s > cur:
            gaps.append((cur, min(s, t1)))
        cur = max(cur, e)
        if cur >= t1:
            break
    if cur < t1:
        gaps.append((cur, t1))
    gaps = [(s, e) for s, e in gaps if e > s]
    return sorted(gaps, key=lambda g: g[0] - g[1])


def label_gaps(gaps, host: Sequence[Event], n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` longest gaps as ``(label, seconds)``: the label is the host
    span that overlaps the gap most ("idle" where none does)."""
    out = []
    for s, e in gaps[:n]:
        best, best_ov = "idle", 0.0
        for h in host:
            if h.name == WINDOW_SPAN:
                continue
            ov = min(e, h.end_ns) - max(s, h.start_ns)
            if ov > best_ov:
                best, best_ov = h.name, ov
        out.append((best, (e - s) * 1e-9))
    return out


def program_totals(events: Iterable[Event], key=lambda e: e.name) -> Dict:
    """``{key: (count, total_ns)}`` over the events."""
    out: Dict = {}
    for e in events:
        c, t = out.get(key(e), (0, 0.0))
        out[key(e)] = (c + 1, t + e.dur_ns)
    return out


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except Exception:  # an event without stats
        return {}


def op_name(hlo_text: str) -> str:
    """``%fusion.242 = bf16[...] fusion(...)`` -> ``fusion.242``."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


def _event(ev, line: str, name: str = None) -> Event:
    st = _stats(ev) if line != "XLA Ops" else {}
    return Event(name=name or ev.name, start_ns=float(ev.start_ns),
                 dur_ns=float(ev.duration_ns), module=str(st.get("hlo_module", "")),
                 line=line)


def _is_chip(plane_name: str) -> bool:
    return re.fullmatch(r"/device:[A-Za-z]+:\d+", plane_name) is not None \
        and not plane_name.startswith("/device:CPU")


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {log_dir}")
    return paths[-1]


def read(path: str) -> DeviceTrace:
    """Device operations, program executions and host spans of a trace."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices = sorted((p for p in pd.planes if _is_chip(p.name)), key=lambda p: p.name)
    ops: List[Event] = []
    modules: List[Event] = []
    if devices:
        for ln in devices[0].lines:
            if ln.name == "XLA Ops":
                ops = [_event(e, ln.name, op_name(e.name)) for e in ln.events]
            elif ln.name == "XLA Modules":
                modules = [_event(e, ln.name) for e in ln.events]
    host: List[Event] = []
    for p in pd.planes:
        if p.name.startswith("/host:"):
            for ln in p.lines:
                evs = [_event(e, ln.name) for e in ln.events]
                if not devices:  # the CPU backend runs its operations on host threads
                    ops.extend(e for e in evs if e.module)
                host.extend(e for e in evs if not e.module)
    win = [h for h in host if h.name == WINDOW_SPAN]
    if win:
        t0, t1 = win[0].start_ns, win[0].end_ns
    else:
        every = ops + modules
        t0 = min((e.start_ns for e in every), default=0.0)
        t1 = max((e.end_ns for e in every), default=0.0)
    return DeviceTrace(ops=ops, modules=modules, host=host, t0_ns=t0, t1_ns=t1,
                       n_devices=max(len(devices), 1))


def survey(path: str, n: int = 25) -> dict:
    """Planes, lines and the most frequent event names: for reading a new
    trace by hand before writing code against it."""
    from collections import Counter

    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = {}
    for p in pd.planes:
        lines = {}
        for ln in p.lines:
            evs = list(ln.events)
            names = Counter(e.name for e in evs)
            time_of = Counter()
            example = {}
            for e in evs:
                time_of[e.name] += e.duration_ns
                example.setdefault(e.name, e)
            lines[ln.name] = {
                "events": len(evs), "top": names.most_common(n),
                "top_by_time": [
                    (name, ns * 1e-9, {k: str(v)[:100] for k, v in _stats(example[name]).items()})
                    for name, ns in time_of.most_common(n)],
            }
        out[p.name] = lines
    return out


def self_times(ops: Sequence[Event]) -> List[Tuple[Event, float]]:
    """Each operation with its self time: its duration less that of the
    operations nested in it (a ``while`` holds its body's operations)."""
    order = sorted(ops, key=lambda e: (e.start_ns, -e.dur_ns))
    own = {id(e): e.dur_ns for e in order}
    stack: List[Event] = []
    for e in order:
        while stack and stack[-1].end_ns <= e.start_ns:
            stack.pop()
        if stack and e.end_ns <= stack[-1].end_ns:
            own[id(stack[-1])] -= e.dur_ns
        stack.append(e)
    return [(e, own[id(e)]) for e in order]


def program_labels(modules: Sequence[Event]) -> Dict[str, str]:
    """A readable label for each program execution's name: the jit name,
    with the two ``jit_step`` programs called ``verify`` and ``draft``."""
    labels = {m.name: m.base for m in modules}
    roles = classify_steps(modules, "jit_step")
    if roles:
        for role, name in roles.items():
            labels[name] = f"jit_step[{role}]"
    return labels


def top_ops(ops: Sequence[Event], modules: Sequence[Event],
            n: int = 10) -> List[Tuple[str, float]]:
    """Device operations that took the most self time, ``(program/op,
    seconds)``; an operation is named with the program it ran in."""
    runs = sorted(modules, key=lambda m: m.start_ns)
    starts = [m.start_ns for m in runs]
    labels = program_labels(modules)
    tot: Dict[str, float] = {}
    for e, own in self_times(ops):
        i = bisect.bisect_right(starts, e.start_ns) - 1
        prog = labels[runs[i].name] if i >= 0 and e.start_ns < runs[i].end_ns else "?"
        key = f"{prog}/{e.name}"
        tot[key] = tot.get(key, 0.0) + own
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [(name, t * 1e-9) for name, t in ranked]


def classify_steps(modules: Sequence[Event], base: str) -> Optional[Dict[str, str]]:
    """Programs that share the jit name ``base`` (the engine's target verify
    and draft step are both ``jit_step``), told apart by how often they ran:
    a chain round runs the draft step ``draft_len + 1`` times per verify.
    Returns ``{"verify": name, "draft": name}`` (names with fingerprints)."""
    counts = program_totals(m for m in modules if m.base == base)
    if len(counts) < 2:
        return None
    ranked = sorted(counts.items(), key=lambda kv: kv[1][0])
    return {"verify": ranked[0][0], "draft": ranked[-1][0]}
