"""The span log: ``span(name, rid=None, **args)`` writes ONE record a span,
when it ends.

A record (a ``COMPLETE`` event in the ``observability.events`` ring) holds
the span's name, its start and duration in nanoseconds on the profiler's
clock (``time.time_ns()``, see ``events.py``), the thread, the name of the
span that was open on that thread when it began (``cause``), a request id
where the span belongs to one request, its arguments, and whether a
profiler trace was running when it began (``traced``).  Beside the ring,
which forgets, the ``span.seconds`` family keeps a count and a sum of
seconds per span name, which do not (``/metrics`` shows them).  A span also
opens a ``jax.profiler.TraceAnnotation`` named ``paddle_tpu.<name>``, so a
device trace carries the same spans; off the profiler that costs a third of
a microsecond, and the whole span two clock reads, one record and one
append.  There is no switch.

``complete(name, start_ns, ...)`` writes the record of a span whose begin
and end are on different threads (``gateway.deliver``: the worker's put to
the handler's write): whoever ends it writes it, with the start it was
handed.

**Program builds** are kept apart from the ring, in a table that is never
evicted (one record a compiled program): ``with build(program, key):``
round a cache miss records its total seconds and the seconds jax itself
reports for that miss through ``jax.monitoring`` — jaxpr tracing, lowering
to MLIR, backend compile, persistent-cache retrieval — with the remainder
(executable load, first run, whatever else the miss did) as ``rest_s``, and
whether the persistent cache was hit.  The listeners are registered once a
process; nothing is lowered or compiled a second time to time it.
"""

from __future__ import annotations

import threading
import time

from jax.profiler import TraceAnnotation as _Annotation

from . import events as _events
from . import metrics as _metrics

_tls = threading.local()

#: the log's clock (CLOCK_REALTIME nanoseconds: the profiler's)
now_ns = time.time_ns

#: count and total seconds per span name (``name=`` label), never forgotten
SPAN_SECONDS = _metrics.summary(
    "span.seconds", "count and total wall seconds of every span, by name")


def _stack():
    s = getattr(_tls, "stack", None)
    if s is None:
        s = _tls.stack = []
    return s


def current_span():
    """Name of the innermost open span on this thread (None outside)."""
    s = _stack()
    return s[-1].name if s else None


def span_depth():
    return len(_stack())


def _write(name, start_ns, dur_ns, cat, cause, rid, args, traced):
    try:
        ev = _events.Event(name, _events.COMPLETE, None, None, cat, None,
                           None, rid, cause, start_ns, dur_ns, traced)
        ev.args = args               # the span's own dict: no copy
        _events.default_log().append(ev)
    finally:
        # the totals must not depend on the ring
        SPAN_SECONDS.observe(dur_ns / 1e9, name=name)


def complete(name, start_ns, rid=None, cat="host", **args):
    """The record of a span that began at ``start_ns`` (``now_ns()``, on
    any thread) and ends now, on this one."""
    _write(name, start_ns, now_ns() - start_ns, cat, None, rid, args,
           _Annotation.is_enabled())


def records(name=None):
    """The span records the ring still holds, oldest first."""
    return [e for e in _events.events(name=name)
            if e.phase == _events.COMPLETE and e.start_ns is not None]


class span:
    """Context manager.  ``args`` may be added to until the span ends
    (``sp.args["tokens"] = n``); ``elapsed`` (seconds) is set when it
    has.  A raising body is recorded with ``error=<exception name>``."""

    __slots__ = ("name", "rid", "cat", "args", "elapsed", "cause", "_t0",
                 "_ann", "_traced")

    def __init__(self, name, rid=None, cat="host", **args):
        self.name = name
        self.rid = rid
        self.cat = cat
        self.args = args
        self.elapsed = None

    def __enter__(self):
        stack = _stack()
        self.cause = stack[-1].name if stack else None
        stack.append(self)
        self._traced = _Annotation.is_enabled()
        self._ann = _Annotation("paddle_tpu." + self.name)
        self._ann.__enter__()
        self._t0 = now_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        dur = now_ns() - self._t0
        self._ann.__exit__(exc_type, exc, tb)
        stack = _stack()
        if stack and stack[-1] is self:
            stack.pop()
        self.elapsed = dur / 1e9
        if exc_type is not None:
            self.args["error"] = exc_type.__name__
        _write(self.name, self._t0, dur, self.cat, self.cause, self.rid,
               self.args, self._traced)
        return False


# ------------------------------------------------------------ program builds
_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE = "/jax/core/compile/backend_compile_duration"
_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"

_builds = []
_listening = False
_listen_lock = threading.Lock()


def builds():
    """The build table: one dict a program build, in build order."""
    return list(_builds)


def _on_time_span(event, start, end, **kw):
    b = getattr(_tls, "build", None)
    if b is not None and event in b._spans:
        b._spans[event].append((start, end))


def _on_duration(event, seconds, **kw):
    b = getattr(_tls, "build", None)
    if b is not None and event == _RETRIEVAL:
        b._retrieval += seconds


def _on_event(event, **kw):
    b = getattr(_tls, "build", None)
    if b is not None and event == _CACHE_HIT:
        b._hits += 1


def _listen():
    """Register the three ``jax.monitoring`` listeners, once a process.
    jax calls them on the thread that traces and compiles, so a build
    collects only its own thread's events."""
    global _listening
    with _listen_lock:
        if not _listening:
            import jax.monitoring as mon

            mon.register_event_time_span_listener(_on_time_span)
            mon.register_event_duration_secs_listener(_on_duration)
            mon.register_event_listener(_on_event)
            _listening = True


def _covered(intervals):
    """Seconds covered by the union of ``(start, end)`` intervals: a jit
    traced inside another's trace reports its own span inside the outer
    one, and is counted once."""
    total, edge = 0.0, None
    for s, e in sorted(intervals):
        if edge is None or s > edge:
            total, edge = total + (e - s), e
        elif e > edge:
            total, edge = total + (e - edge), e
    return total


class build:
    """``with build(program, key):`` round one cache miss of a compiled
    program: one record in the build table (see the module docstring).
    ``key`` is the program's bucket key as a dict."""

    __slots__ = ("program", "key", "_t0", "_start_ns", "_spans",
                 "_retrieval", "_hits", "_outer")

    def __init__(self, program, key=None):
        self.program = program
        self.key = dict(key or {})

    def __enter__(self):
        _listen()
        self._spans = {_TRACE: [], _LOWER: [], _COMPILE: []}
        self._retrieval, self._hits = 0.0, 0
        self._outer = getattr(_tls, "build", None)
        _tls.build = self
        self._start_ns = now_ns()
        self._t0 = time.time()       # jax stamps its spans with this clock
        return self

    def __exit__(self, exc_type, exc, tb):
        total = time.time() - self._t0
        _tls.build = self._outer
        # each instant goes to the innermost phase (something compiled
        # while an outer function is traced is compile, not trace), so the
        # phases are disjoint and add up to no more than the total
        sp = self._spans
        backend = _covered(sp[_COMPILE])
        lowered = _covered(sp[_COMPILE] + sp[_LOWER])
        parts = _covered(sp[_COMPILE] + sp[_LOWER] + sp[_TRACE])
        trace_s, lower_s = parts - lowered, lowered - backend
        # jax's backend-compile span holds the cache look-up
        compile_s = max(0.0, backend - self._retrieval)
        _builds.append({
            "program": self.program, "key": self.key,
            "thread": threading.get_ident(), "start_ns": self._start_ns,
            "total_s": total, "trace_s": trace_s, "lower_s": lower_s,
            "compile_s": compile_s, "cache_retrieval_s": self._retrieval,
            "rest_s": max(0.0, total - parts),
            "cache_hit": self._hits > 0,
            "error": exc_type.__name__ if exc_type else None})
        return False
