"""Program spans: one bounded, always-on ring of closed spans.

A span is one named interval at a layer boundary of the program —
``with span("tick.ingest"): ...`` — stamped on ``time.perf_counter()``,
the clock the chip benchmark maps onto the profiler's device trace.
Each closed span holds:

- its name, start and end (seconds, ``perf_counter``);
- its own id and its parent's id (-1 for a root). The open span lives
  in a context variable, so the serving worker thread and each asyncio
  task nest their spans independently;
- ``seq``, the tick or window number that ties one tick's spans
  together (a front-end window and the tick that serves it share it,
  and so do the acks). A span given no ``seq`` takes its parent's;
- up to ``N_ATTRS`` numeric attributes.

Spans are written into preallocated numpy columns (``SpanRing``), so a
recorded span keeps no Python object alive and overwriting the oldest
slot is O(1): recording costs the same at the millionth span as at the
first, and nothing a collector has to walk grows while serving. Every
span entered with ``span`` also enters a ``jax.profiler.TraceAnnotation``
of the same name, so a profiler capture shows it on the host plane
(a span ``record``-ed after the fact, across threads, cannot be).

Readers: ``spans_between(t0, t1)`` returns the spans that lie inside a
host-clock interval; ``Tracer`` writes the spans closed since its last
flush as JSONL, at ``flush``/``close`` only — never while serving.
"""
from __future__ import annotations

import contextvars
import dataclasses
import itertools
import json
import threading
import time
from pathlib import Path

import numpy as np
from jax.profiler import TraceAnnotation

__all__ = [
    "N_ATTRS", "RING", "Span", "SpanRing", "Tracer", "record", "span",
    "spans_between",
]

N_ATTRS = 3          # numeric attributes a span may carry
_CAPACITY = 1 << 16  # ring slots: minutes of ticks at tens of spans each

# (id, seq) of the innermost open span of this thread / asyncio task
_CURRENT: contextvars.ContextVar[tuple[int, int]] = contextvars.ContextVar(
    "repro_obs_span", default=(-1, -1)
)


@dataclasses.dataclass(frozen=True)
class Span:
    """One closed span, as a reader sees it."""

    name: str
    start: float
    end: float
    seq: int
    id: int
    parent: int
    attrs: dict

    @property
    def seconds(self) -> float:
        return self.end - self.start


class SpanRing:
    """Bounded ring of closed spans in preallocated columns. The serving
    worker thread and the event loop record concurrently: a lock hands
    out slots, and ids come from an ``itertools.count``."""

    def __init__(self, capacity: int = _CAPACITY) -> None:
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self._name = np.full(capacity, -1, np.int32)
        self._start = np.zeros(capacity, np.float64)
        self._end = np.zeros(capacity, np.float64)
        self._seq = np.full(capacity, -1, np.int64)
        self._id = np.full(capacity, -1, np.int64)
        self._parent = np.full(capacity, -1, np.int64)
        self._akey = np.full((capacity, N_ATTRS), -1, np.int32)
        self._aval = np.zeros((capacity, N_ATTRS), np.float64)
        # names and attribute keys are interned: a fixed catalog, so
        # these grow to a few dozen entries and stop
        self._names: list[str] = []
        self._name_ids: dict[str, int] = {}
        self._ids = itertools.count()
        self._lock = threading.Lock()
        self._closed = 0  # slots claimed so far

    @property
    def recorded(self) -> int:
        """Spans closed since the ring was made (kept or overwritten)."""
        return self._closed

    def intern(self, name: str) -> int:
        i = self._name_ids.get(name)
        if i is None:
            with self._lock:
                i = self._name_ids.get(name)
                if i is None:
                    i = self._name_ids[name] = len(self._names)
                    self._names.append(name)
        return i

    def new_id(self) -> int:
        return next(self._ids)

    def write(self, name: int, start: float, end: float, seq: int,
              sid: int, parent: int, attrs: dict | None) -> None:
        if attrs and len(attrs) > N_ATTRS:
            raise ValueError(
                f"a span carries at most {N_ATTRS} attributes, got {sorted(attrs)}"
            )
        with self._lock:
            n = self._closed
            self._closed = n + 1
        i = n % self.capacity
        self._name[i] = name
        self._start[i] = start
        self._end[i] = end
        self._seq[i] = seq
        self._id[i] = sid
        self._parent[i] = parent
        if attrs:
            j = 0
            for k, v in attrs.items():
                self._akey[i, j] = self.intern(k)
                self._aval[i, j] = v
                j += 1
            self._akey[i, j:] = -1
        else:
            self._akey[i] = -1

    def rows(self, first: int = 0) -> list[Span]:
        """The kept spans among the ``first``-th closed onwards, oldest
        first."""
        last = self._closed
        first = max(first, last - self.capacity)
        out = []
        for n in range(first, last):
            i = n % self.capacity
            attrs = {
                self._names[int(k)]: float(v)
                for k, v in zip(self._akey[i], self._aval[i]) if k >= 0
            }
            out.append(Span(
                name=self._names[int(self._name[i])],
                start=float(self._start[i]), end=float(self._end[i]),
                seq=int(self._seq[i]), id=int(self._id[i]),
                parent=int(self._parent[i]), attrs=attrs,
            ))
        return out

    def between(self, t0: float, t1: float) -> list[Span]:
        """The kept spans that start at or after ``t0`` and end at or
        before ``t1`` (host clock), oldest first."""
        last = self._closed
        n = min(last, self.capacity)
        idx = (np.arange(last - n, last) % self.capacity)
        keep = (self._start[idx] >= t0) & (self._end[idx] <= t1)
        if not keep.any():
            return []
        first = last - n + int(np.argmax(keep))
        return [s for s in self.rows(first) if s.start >= t0 and s.end <= t1]


RING = SpanRing()


class _OpenSpan:
    """The context manager ``span`` returns. ``set`` adds attributes
    known only inside the span; ``seconds`` is the duration once it
    closed (the same two clock reads the ring keeps)."""

    __slots__ = ("_ring", "_name", "_seq", "_attrs", "_observe", "_id",
                 "_parent", "_token", "_ann", "start", "end")

    def __init__(self, ring, name, seq, attrs, observe):
        self._ring = ring
        self._name = name
        self._seq = seq
        self._attrs = attrs
        self._observe = observe
        self.start = self.end = None

    def set(self, **attrs) -> None:
        if self._attrs:
            self._attrs.update(attrs)
        else:
            self._attrs = attrs

    @property
    def seconds(self) -> float:
        return self.end - self.start

    def __enter__(self) -> "_OpenSpan":
        parent, pseq = _CURRENT.get()
        self._parent = parent
        if self._seq is None:
            self._seq = pseq
        self._id = self._ring.new_id()
        self._token = _CURRENT.set((self._id, self._seq))
        self._ann = TraceAnnotation(self._name)
        self._ann.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.end = end = time.perf_counter()
        self._ann.__exit__(*exc)
        _CURRENT.reset(self._token)
        ring = self._ring
        ring.write(ring.intern(self._name), self.start, end, self._seq,
                   self._id, self._parent, self._attrs)
        if self._observe is not None:
            self._observe(end - self.start)
        return False


def span(name: str, *, seq: int | None = None, observe=None,
         ring: SpanRing | None = None, **attrs) -> _OpenSpan:
    """One span around a ``with`` block. ``observe(seconds)`` receives
    its duration when it closes (the sink's phase histogram)."""
    return _OpenSpan(RING if ring is None else ring, name,
                     None if seq is None else int(seq), attrs or None, observe)


def record(name: str, start: float, end: float, *, seq: int = -1,
           parent: int = -1, ring: SpanRing | None = None, **attrs) -> None:
    """A span whose start and end were read elsewhere — on another
    thread, say — recorded after it closed."""
    ring = RING if ring is None else ring
    ring.write(ring.intern(name), float(start), float(end), int(seq),
               ring.new_id(), int(parent), attrs or None)


def spans_between(t0: float, t1: float, *, ring: SpanRing | None = None) -> list[Span]:
    """The spans inside the host-clock interval [t0, t1], oldest first."""
    return (RING if ring is None else ring).between(t0, t1)


class Tracer:
    """JSONL export of the ring: ``flush`` appends one line for every
    span closed since the previous flush (or since the tracer was made)
    and ``close`` flushes. Nothing is written while spans record.

    A line reads ``{"name": ..., "start": ..., "end": ..., "dur_s": ...,
    "seq": ..., "id": ..., "parent": ..., <attrs>}``; ``path=None``
    writes nothing."""

    def __init__(self, path: str | Path | None = None, *,
                 ring: SpanRing | None = None) -> None:
        self.ring = RING if ring is None else ring
        self.path = Path(path) if path is not None else None
        self._mark = self.ring.recorded
        self.events_written = 0
        if self.path is not None:
            self.path.parent.mkdir(parents=True, exist_ok=True)
            # truncate: one trace file per run, not an append-across-runs log
            self.path.write_text("")

    def flush(self) -> None:
        first, self._mark = self._mark, self.ring.recorded
        if self.path is None or first == self._mark:
            return
        lines = [
            json.dumps({"name": s.name, "start": s.start, "end": s.end,
                        "dur_s": s.seconds, "seq": s.seq, "id": s.id,
                        "parent": s.parent, **s.attrs})
            for s in self.ring.rows(first)
        ]
        with open(self.path, "a") as fh:
            fh.write("\n".join(lines) + "\n")
        self.events_written += len(lines)

    def close(self) -> None:
        self.flush()
