"""Span tracing for the SNN stream engine: one span API, two sinks.

``TraceRecorder.phase(name, track=...)`` times a block of host work.  It
enters a ``jax.profiler.TraceAnnotation`` of the same name, so under a
``jax.profiler`` capture the block lands on the profiler's host plane, on
the device trace's clock, beside the device's operations (about half a
microsecond when no capture is active); on exit it appends the block as a
``Span`` to the recorder's ring, if the ring is enabled, and gives the
caller its ``t0``/``t1`` so histograms keep their exact sums from the same
timestamps.  The engine's phases (``engine.poll``, ``engine.submit``,
``engine.admit`` with ``.upload`` and ``.dispatch``, ``engine.tick.prep``,
``engine.tick.dispatch``, ``engine.retire`` with ``.fetch``,
``engine.finalize``, ``engine.sample``) nest as the calls nest.

The ring is a bounded ``collections.deque`` of *completed* spans; the
oldest fall off the back, and capacity 0 turns it off.  ``span`` and
``instant`` record into the ring alone: the request lifecycle's
``submit`` (instant, queue track), ``queue`` (submit -> admission, which
crosses polls), ``occupy`` (admission end -> completion, one per request
on its slot's track), ``complete``, ``shed``, ``park`` and ``quarantine``.

Ring timestamps are ``time.perf_counter()`` seconds; export shifts them to
a common zero.  ``chrome_trace()`` emits Chrome trace-event JSON (the
``traceEvents`` array format) loadable in Perfetto (ui.perfetto.dev) or
``chrome://tracing``: each distinct track becomes a named thread of one
``engine`` process, spans are ``ph: "X"`` complete events, instants are
``ph: "i"`` with thread scope.
"""

from __future__ import annotations

import collections
import dataclasses
import json
import time
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation

__all__ = ["Phase", "Span", "TraceRecorder"]


@dataclasses.dataclass(frozen=True)
class Span:
    """One completed span (or instant, when ``t1 is None``)."""

    name: str
    t0: float  # perf_counter seconds
    t1: Optional[float]  # None -> instant event
    track: str = "engine"
    cat: str = "engine"
    args: Optional[Dict] = None

    @property
    def duration_s(self) -> float:
        return 0.0 if self.t1 is None else self.t1 - self.t0


class Phase:
    """A timed block of host work; see ``TraceRecorder.phase``.  After
    the block, ``t0``/``t1`` are its ``perf_counter`` bounds."""

    __slots__ = ("_rec", "_ann", "name", "track", "args", "t0", "t1")

    def __init__(self, rec: "TraceRecorder", name: str, track: str,
                 args: Optional[Dict]):
        self._rec = rec
        self.name = name
        self.track = track
        self.args = args

    def __enter__(self) -> "Phase":
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        if self._rec.enabled:
            self._rec._spans.append(
                Span(self.name, self.t0, self.t1, self.track, args=self.args)
            )

    @property
    def duration_s(self) -> float:
        return self.t1 - self.t0


class TraceRecorder:
    """Bounded ring of completed spans + Chrome trace-event export.
    ``capacity=0`` keeps no ring: ``phase`` still annotates the
    profiler's timeline and times its block."""

    def __init__(self, capacity: int = 8192, enabled: bool = True):
        if capacity < 0:
            raise ValueError("trace capacity must be >= 0")
        self.capacity = int(capacity)
        self.enabled = bool(enabled) and self.capacity > 0
        self._spans: "collections.deque[Span]" = collections.deque(
            maxlen=self.capacity
        )

    @staticmethod
    def now() -> float:
        return time.perf_counter()

    def phase(
        self, name: str, *, track: str, args: Optional[Dict] = None
    ) -> Phase:
        """``with recorder.phase(name, track=...) as p:`` times the block
        as a profiler annotation and, if the ring is on, a span."""
        return Phase(self, name, track, args)

    def span(
        self,
        name: str,
        t0: float,
        t1: float,
        *,
        track: str = "engine",
        cat: str = "engine",
        args: Optional[Dict] = None,
    ) -> None:
        """Record a completed span.  ``t1 < t0`` is rejected loudly —
        monotonic timestamps are an invariant the tests pin."""
        if not self.enabled:
            return
        if t1 < t0:
            raise ValueError(f"span {name!r}: t1 {t1} < t0 {t0}")
        self._spans.append(Span(name, t0, t1, track, cat, args))

    def instant(
        self,
        name: str,
        t: Optional[float] = None,
        *,
        track: str = "engine",
        cat: str = "engine",
        args: Optional[Dict] = None,
    ) -> None:
        if not self.enabled:
            return
        t = self.now() if t is None else t
        self._spans.append(Span(name, t, None, track, cat, args))

    def spans(self) -> List[Span]:
        """Snapshot of the ring, oldest first."""
        return list(self._spans)

    def clear(self) -> None:
        self._spans.clear()

    def __len__(self) -> int:
        return len(self._spans)

    # ------------------------------------------------------------ export
    def chrome_trace(self) -> Dict:
        """Chrome trace-event JSON object (``{"traceEvents": [...]}``),
        Perfetto-loadable.  Tracks map to threads of one process, in
        first-seen order; timestamps are microseconds from the earliest
        recorded span."""
        spans = self.spans()
        base = min((s.t0 for s in spans), default=0.0)
        tids: Dict[str, int] = {}
        events: List[Dict] = []
        for s in spans:
            tid = tids.setdefault(s.track, len(tids) + 1)
            ev = {
                "name": s.name,
                "cat": s.cat,
                "pid": 1,
                "tid": tid,
                "ts": (s.t0 - base) * 1e6,
            }
            if s.args:
                ev["args"] = dict(s.args)
            if s.t1 is None:
                ev["ph"] = "i"
                ev["s"] = "t"  # thread-scoped instant
            else:
                ev["ph"] = "X"
                ev["dur"] = (s.t1 - s.t0) * 1e6
            events.append(ev)
        meta = [
            {
                "name": "process_name",
                "ph": "M",
                "pid": 1,
                "args": {"name": "snn_stream_engine"},
            }
        ] + [
            {
                "name": "thread_name",
                "ph": "M",
                "pid": 1,
                "tid": tid,
                "args": {"name": track},
            }
            for track, tid in tids.items()
        ]
        return {"traceEvents": meta + events, "displayTimeUnit": "ms"}

    def write(self, path) -> None:
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f, indent=1)
            f.write("\n")
