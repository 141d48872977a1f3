"""Spans and counts at the sampler's layer boundaries, on the profiler's clock.

``span(name, **counts)`` is a context manager around one piece of a layer's
work.  It records only while a JAX profiler session is active (between
``jax.profiler.start_trace`` and ``stop_trace``); then it keeps, in a
bounded in-memory buffer, the span's name, start and end, thread, parent
span, request id and the integer counts attached at the boundary, and it
also writes the span into the profiler's trace as a host event of the same
name.  Start and end are in the trace's own time base (wall-clock
nanoseconds, which the trace file stores relative to its
``profile_start_time``), so each device idle gap can be put down to the
program span the host was in.

Outside a profiler session a span costs one enabled check; a span opened
with ``timed=True`` also reads the monotonic clock at both ends, for a site
that publishes the duration (``.seconds``) in its own statistics.  Durations
are always monotonic; only the recorded start and end are mapped to the
trace's wall-clock base.

Request ids: a span opened with ``root=True`` starts a fresh request id;
every span opened inside it inherits the id and takes it as parent.  Worker
threads do not inherit the caller's context, so work handed to another
thread goes through :func:`carry`.

``spans()`` returns the records, ``dropped()`` how many did not fit the
buffer, and ``clear()`` empties both.
"""

from __future__ import annotations

import contextvars
import functools
import itertools
import threading
import time
from typing import NamedTuple, Optional

from jax._src.lib import _profiler

#: records kept per process; later spans are counted in ``dropped()``
MAX_SPANS = 1 << 18

_TraceMe = _profiler.TraceMe
#: (span id, request id) of the innermost open span of this context
_current: contextvars.ContextVar = contextvars.ContextVar(
    "repro_telemetry_span", default=None)
_ids = itertools.count(1)
_requests = itertools.count(1)


class SpanRecord(NamedTuple):
    name: str
    start_ns: int          # trace time base: wall-clock nanoseconds
    end_ns: int
    thread: str
    span_id: int
    parent: Optional[int]  # span id of the enclosing span, if any
    request: Optional[int]
    counts: dict

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


class _Buffer:
    def __init__(self, cap: int):
        self.cap = cap
        self.records: list = []
        self.dropped = 0
        self.lock = threading.Lock()

    def add(self, rec: SpanRecord) -> None:
        with self.lock:
            if len(self.records) < self.cap:
                self.records.append(rec)
            else:
                self.dropped += 1


_buffer = _Buffer(MAX_SPANS)


#: ``enabled()``: True while a JAX profiler session is active (tens of
#: nanoseconds); the one place the recorder asks
enabled = _TraceMe.is_enabled


def _to_trace_ns(mono_ns: int) -> int:
    # wall clock minus monotonic moves only when the wall clock is stepped
    return mono_ns + (time.time_ns() - time.perf_counter_ns())


class _Off:
    """A span outside a profiler session: records nothing."""

    __slots__ = ()
    seconds = 0.0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None

    def count(self, **counts) -> None:
        pass


class _Timed(_Off):
    """A ``timed`` span outside a profiler session: measures, records
    nothing."""

    __slots__ = ("_t0", "seconds")

    def __enter__(self):
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self.seconds = (time.perf_counter_ns() - self._t0) / 1e9


class _Span:
    """A span inside a profiler session: measured, recorded and written into
    the trace."""

    __slots__ = ("name", "counts", "root", "seconds", "_t0", "_id", "_parent",
                 "_request", "_token", "_tm")

    def __init__(self, name: str, root: bool, counts: dict):
        self.name, self.root, self.counts = name, root, counts
        self.seconds = 0.0

    def __enter__(self):
        cur = _current.get()
        self._parent = cur[0] if cur else None
        self._request = (next(_requests) if self.root or cur is None
                         else cur[1])
        self._id = next(_ids)
        self._token = _current.set((self._id, self._request))
        self._tm = _TraceMe(self.name, **self.counts)
        self._tm.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self._tm.__exit__(None, None, None)
        _current.reset(self._token)
        self.seconds = (t1 - self._t0) / 1e9
        start = _to_trace_ns(self._t0)
        _buffer.add(SpanRecord(
            self.name, start, start + (t1 - self._t0),
            threading.current_thread().name, self._id, self._parent,
            self._request, self.counts))

    def count(self, **counts) -> None:
        """Attach counts known only inside the span."""
        self.counts.update(counts)
        self._tm.set_metadata(**counts)


_OFF = _Off()


def span(name: str, *, root: bool = False, timed: bool = False, **counts):
    """Context manager for one span; see the module docstring.  The object
    it yields has ``seconds`` (after exit; measured when recording or
    ``timed``) and ``count(**counts)``."""
    if enabled():
        return _Span(name, root, counts)
    return _Timed() if timed else _OFF


def carry(fn):
    """``fn`` bound to the caller's span context, for work submitted to
    another thread (threads start with an empty context)."""
    if not enabled():
        return fn
    return functools.partial(contextvars.copy_context().run, fn)


def spans() -> list:
    """The recorded spans, oldest first."""
    with _buffer.lock:
        return list(_buffer.records)


def dropped() -> int:
    """Spans that did not fit the buffer since the last ``clear()``."""
    return _buffer.dropped


def clear() -> None:
    with _buffer.lock:
        _buffer.records.clear()
        _buffer.dropped = 0
