"""What the three span-log readers share (`span_stat`, `host_gap`,
`build_stat`): the program's own span log, read in the process that ran the
engine, as plain tuples; and interval arithmetic on them.

The program keeps one process-wide log (`paddle_tpu/observability/span.py`):
one record a finished span — name, start and duration in nanoseconds (epoch,
the clock the profiler stamps host events with), thread, the span open on
that thread when it began, a request id, arguments, and whether a profiler
trace was running when it began — and, apart from it, one record a program
build.  A program without such a log (the parent of the PR that brought it)
gives `None`, and every reader then returns `None`.

The traced window.  `ctx.reduced["window_ns"]` is in the trace file's own
time, which counts from the profiler session's start; the file's
`profile_start_time` that would map it to the epoch is not kept by
`trace.reduce()`, and the file is gone when the readers run.  So the window
is found in the log itself: from the start of the first span ending in
`.enqueue` that began under the profiler (the first program the host queued
in the traced part, which is where the device trace's own window begins) to
the end of the last span that began under it.  It agrees with the device
trace's window to a few milliseconds (one span's length at either end).
"""

import collections
import importlib

Span = collections.namedtuple(
    "Span", "name start end tid cause rid args traced", defaults=(True,))


def _program_log():
    try:
        return importlib.import_module("paddle_tpu.observability.span")
    except ImportError:
        return None


def spans():
    """Every span the log still holds, oldest first, or None."""
    get = getattr(_program_log(), "records", None)
    if get is None:
        return None
    return [Span(e.name, e.start_ns, e.start_ns + e.dur_ns, e.tid, e.cause,
                 e.id, e.args, e.traced) for e in get()]


def builds():
    """The build table (a list of dicts), or None."""
    get = getattr(_program_log(), "builds", None)
    return None if get is None else get()


def window(ctx, records):
    """(lo, hi) of the traced window in the log's nanoseconds, or None:
    no traced run, or no program was queued under the profiler."""
    if not ctx.reduced:
        return None
    traced = [s for s in records if s.traced]
    first = [s.start for s in traced if s.name.endswith(".enqueue")]
    if not first:
        return None
    return min(first), max(s.end for s in traced)


def clipped(records, lo, hi):
    """The spans that overlap [lo, hi), cut to it."""
    out = []
    for s in records:
        a, b = max(s.start, lo), min(s.end, hi)
        if b > a:
            out.append(s._replace(start=a, end=b))
    return out


def union(intervals):
    """Merged, sorted [start, end) intervals."""
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1][1] = b
        else:
            merged.append([a, b])
    return merged


def covered(intervals):
    return sum(b - a for a, b in union(intervals))


def innermost(records, lo, hi):
    """[lo, hi) cut into pieces (a, b, name): the innermost span of ONE
    thread's `records` that covers each piece, None where none does."""
    pieces, stack, cursor = [], [], lo

    def advance(to):
        nonlocal cursor
        while cursor < to:
            while stack and stack[-1][0] <= cursor:
                stack.pop()
            nxt = min(to, stack[-1][0]) if stack else to
            pieces.append((cursor, nxt, stack[-1][1] if stack else None))
            cursor = nxt

    for s in sorted(clipped(records, lo, hi),
                    key=lambda s: (s.start, -s.end)):
        advance(s.start)
        stack.append((s.end, s.name))
    advance(hi)
    return pieces

