"""Span tracer: engine and server spans on the profiler's clock, plus
per-request timelines exportable as Chrome-trace JSON (loadable in
Perfetto / ``chrome://tracing``) and as structured JSONL.

Two sinks, one API.  ``span(track, name, **args)`` is the single way a
hot-path site records a span:

* it always enters a ``jax.profiler.TraceAnnotation`` named
  ``<track>.<name>`` (``engine.draft_pull``, ``server.sse_write``) with
  ``args`` as the event's metadata, so a ``jax.profiler`` trace shows the
  host work between device programs on the same clock as the programs
  themselves.  With no profiler session active this is one TraceMe
  construction (about 2 us of host time) and the metadata is never encoded;
* on an enabled ``Tracer`` it also records the span in memory for the
  Chrome/JSONL export below.

``rec(t0, t1)`` stamps an in-memory span after the fact; it is kept only
for the round and phase spans the in-memory timeline draws (``step#k``,
``draft_phase``, ``fused_slot``, the per-row lifecycle), which the
profiler does not need: the engine's leaf spans already cover a round.

In-memory span hierarchy (docs/OBSERVABILITY.md draws it):

    engine track:   step#k [par_mode] > draft_phase / verify_phase (off)
                                      > fused_slot (wdos)
                    leaf spans: admit, prefill, table_upload, draft_*,
                    verify_*, accept, emit, ... (also on the profiler)
    row<i> track:   admit > prefill > {draft | verify}* > commit > finish
    http track:     request / disconnect / completion instants (server)

Every span/instant carries the request id in ``args`` where one applies,
and the same events stream to a JSONL file (one JSON object per line)
when the tracer is built with ``jsonl_path=...``.

The tracer never calls ``block_until_ready`` and never touches device
values, so it cannot add host syncs to the decode loop or perturb
bit-identity (tokens are identical with tracing and the profiler on;
tests/test_observability.py, tests/test_profiler_spans.py).

The engine holds ``NULL_TRACER`` unless one is passed
(``Engine(..., trace=Tracer())``): its ``span`` is the bare
``TraceAnnotation`` and every other method a constant-time no-op.

Export: ``to_chrome_trace()`` returns the Chrome Trace Event JSON dict
(``{"traceEvents": [...]}``, complete/``"X"`` events with microsecond
timestamps plus ``thread_name`` metadata per track); ``export(path)``
writes it.  Load it in https://ui.perfetto.dev or ``chrome://tracing``.
"""
from __future__ import annotations

import json
import threading
import time
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation

__all__ = ["Tracer", "NullTracer", "NULL_TRACER", "validate_chrome_trace"]


class Tracer:
    """Collects spans/instants on named tracks; thread-safe.

    Timestamps are seconds relative to tracer construction (one shared
    ``time.perf_counter`` origin), converted to integer microseconds at
    export.  ``rec()`` takes explicit boundaries so callers can reuse a
    wall-clock reading they already took for telemetry — zero extra clock
    reads on instrumented paths that already time themselves."""

    enabled = True

    def __init__(self, jsonl_path: Optional[str] = None):
        self._t0 = time.perf_counter()
        self._lock = threading.Lock()
        self._events: List[dict] = []
        self._jsonl = open(jsonl_path, "w") if jsonl_path else None

    # -- clock ----------------------------------------------------------------

    def now(self) -> float:
        """Seconds since tracer start (the span timebase)."""
        return time.perf_counter() - self._t0

    # -- recording ------------------------------------------------------------

    def _emit(self, ev: dict) -> None:
        with self._lock:
            self._events.append(ev)
            if self._jsonl is not None:
                self._jsonl.write(json.dumps(ev) + "\n")

    def rec(self, track: str, name: str, t0: float, t1: float,
            cat: str = "", **args) -> None:
        """One complete span [t0, t1] (tracer-relative seconds) on `track`."""
        self._emit({
            "ph": "X", "track": track, "name": name, "cat": cat,
            "ts": t0, "dur": max(t1 - t0, 0.0), "args": args,
        })

    def instant(self, track: str, name: str, cat: str = "", **args) -> None:
        self._emit({
            "ph": "i", "track": track, "name": name, "cat": cat,
            "ts": self.now(), "args": args,
        })

    def span(self, track: str, name: str, cat: str = "", **args):
        """Context manager: a profiler span ``<track>.<name>`` carrying
        ``args``, also recorded here as an in-memory span on ``track``."""
        return _Span(self, track, name, cat, args)

    # -- export ---------------------------------------------------------------

    def events(self) -> List[dict]:
        with self._lock:
            return list(self._events)

    def to_chrome_trace(self) -> dict:
        """Chrome Trace Event format: one pid, one tid per track (in
        first-seen order), ``thread_name`` metadata so Perfetto labels the
        tracks, microsecond integer timestamps."""
        tids: Dict[str, int] = {}
        out: List[dict] = []
        for ev in self.events():
            track = ev["track"]
            tid = tids.get(track)
            if tid is None:
                tid = tids[track] = len(tids)
                out.append({
                    "ph": "M", "name": "thread_name", "pid": 0, "tid": tid,
                    "args": {"name": track},
                })
            ce = {
                "ph": ev["ph"], "name": ev["name"], "cat": ev["cat"] or "serving",
                "pid": 0, "tid": tid, "ts": round(ev["ts"] * 1e6),
                "args": ev["args"],
            }
            if ev["ph"] == "X":
                ce["dur"] = max(round(ev["dur"] * 1e6), 1)
            else:
                ce["s"] = "t"  # thread-scoped instant
            out.append(ce)
        return {"traceEvents": out, "displayTimeUnit": "ms"}

    def export(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f)

    def close(self) -> None:
        with self._lock:
            if self._jsonl is not None:
                self._jsonl.close()
                self._jsonl = None


class _Span:
    """``Tracer.span``: the profiler annotation plus the in-memory record."""

    __slots__ = ("_tracer", "_track", "_name", "_cat", "_args", "_ann", "_t0")

    def __init__(self, tracer: Tracer, track: str, name: str, cat: str,
                 args: dict):
        self._tracer, self._track, self._name = tracer, track, name
        self._cat, self._args = cat, args
        self._ann = TraceAnnotation(f"{track}.{name}", **args)

    def __enter__(self):
        self._t0 = self._tracer.now()
        self._ann.__enter__()

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        self._tracer.rec(self._track, self._name, self._t0, self._tracer.now(),
                         self._cat, **self._args)
        return False


class NullTracer(Tracer):
    """The disabled fast path: every method is a constant-time no-op.
    Shared as ``NULL_TRACER`` — the engine's default when no tracer is
    passed, so instrumentation sites need no ``if`` guards."""

    enabled = False

    def __init__(self):  # no clock read, no lock, no buffers
        pass

    def now(self) -> float:
        return 0.0

    def rec(self, *a, **kw) -> None:
        pass

    def instant(self, *a, **kw) -> None:
        pass

    def span(self, track: str, name: str, cat: str = "", **args):
        return TraceAnnotation(f"{track}.{name}", **args)

    def events(self) -> List[dict]:
        return []

    def to_chrome_trace(self) -> dict:
        return {"traceEvents": [], "displayTimeUnit": "ms"}

    def export(self, path: str) -> None:
        raise RuntimeError("cannot export a NullTracer (tracing is off)")

    def close(self) -> None:
        pass


NULL_TRACER = NullTracer()


def validate_chrome_trace(obj: dict) -> List[str]:
    """Schema check for an exported trace: returns a list of problems
    (empty = valid).  Used by the trace-export tests and the CI smoke so a
    regression can never silently produce a file Perfetto rejects.

    Beyond the per-event field checks, every non-metadata event's tid must
    be introduced by a ``thread_name`` metadata event (Perfetto renders
    unnamed tids as anonymous tracks — always a tracer bug here, since
    ``Tracer`` emits the M record at first use of a track).
    """
    problems: List[str] = []
    if not isinstance(obj, dict) or "traceEvents" not in obj:
        return ["top level must be a dict with 'traceEvents'"]
    events = obj["traceEvents"]
    if not isinstance(events, list):
        return ["'traceEvents' must be a list"]
    track_by_tid: Dict[int, str] = {}
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            problems.append(f"event {i}: not a dict")
            continue
        ph = ev.get("ph")
        if ph not in ("X", "i", "M", "B", "E"):
            problems.append(f"event {i}: bad ph {ph!r}")
            continue
        for key in ("name", "pid", "tid"):
            if key not in ev:
                problems.append(f"event {i}: missing {key!r}")
        if ph == "M":
            if ev.get("name") == "thread_name":
                track = (ev.get("args") or {}).get("name")
                if isinstance(track, str) and "tid" in ev:
                    track_by_tid[ev["tid"]] = track
            continue
        tid = ev.get("tid")
        if tid is not None and tid not in track_by_tid:
            problems.append(
                f"event {i}: tid {tid} has no thread_name metadata"
            )
        ts = ev.get("ts")
        if not isinstance(ts, (int, float)) or ts < 0:
            problems.append(f"event {i}: bad ts {ts!r}")
            continue
        if ph == "X" and not isinstance(ev.get("dur"), (int, float)):
            problems.append(f"event {i}: complete event missing dur")
    return problems
