"""A statistic of the program's own spans of one name (or the sum of
several) inside the traced window (see `spanlog.py`, which also says how the
window is found in the log).

args: `name` (a span name or a list of them); `stat`: "mean" or "pNN" in
milliseconds, or "share" (their summed time over the window, in per cent);
`per`: for "mean", the span name whose count in the window divides the
summed time (default: the first of `name`), so that
`engine.decode.harvest` + `engine.step.publish` + `worker.flush` can be read
"a decode step"; `self_time`: true takes out of each span the part its child
spans (same thread, inside it) cover, a list of names only the children of
those names.  Spans the window cuts are cut.  None where the log holds no
such span in the window.
"""

from chipbench.readers import spanlog
from chipbench.readers.mean_ms import percentile


def self_seconds(target, thread_spans, only=None):
    """`target`'s nanoseconds without the part covered by the other spans
    of its thread that lie inside it (all of them, or those named)."""
    kids = [(s.start, s.end) for s in thread_spans
            if s is not target and s.start >= target.start
            and s.end <= target.end and (only is None or s.name in only)]
    return (target.end - target.start) - spanlog.covered(kids)


def reduce(records, lo, hi, name, stat="mean", per=None, self_time=False):
    names = [name] if isinstance(name, str) else list(name)
    inside = spanlog.clipped(records, lo, hi)
    mine = [s for s in inside if s.name in names]
    if not mine:
        return None
    if self_time:
        only = None if self_time is True else set(self_time)
        by_thread = {}
        for s in inside:
            by_thread.setdefault(s.tid, []).append(s)
        durations = [self_seconds(s, by_thread[s.tid], only) for s in mine]
    else:
        durations = [s.end - s.start for s in mine]
    if stat == "share":
        return 100.0 * sum(durations) / (hi - lo)
    if stat == "mean":
        count = sum(1 for s in inside if s.name == (per or names[0]))
        return sum(durations) / count / 1e6 if count else None
    return percentile(durations, float(stat[1:])) / 1e6


def read(ctx, name, stat="mean", per=None, self_time=False):
    records = spanlog.spans()
    w = spanlog.window(ctx, records) if records else None
    if w is None:
        return None
    return reduce(records, w[0], w[1], name, stat, per, self_time)
