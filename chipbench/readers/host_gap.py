"""The program's own account of when the device had nothing queued: on the
engine's worker thread, the part of the traced window that lies outside
every stretch from the start of an `engine.*.enqueue` span (the call of a
compiled program) to the end of the `engine.*.wait` span that follows it
(the host sync on its result), as a share of the window in per cent (see
`spanlog.py`).  Logged beside it, as a table: those seconds by the innermost
span that covered them (`engine.decode.harvest`, `worker.flush`,
`engine.prefill.build`, ...; `no span` for the rest).  The rows add up to
the share times the window.

It is what `breakdown.idle_gaps` cannot say while `trace.py` reads only
`chipbench.` annotations; against `device.idle_share` it also says whether
the device idles while work is queued (the runtime's) or only while none is
(the host's).  With several worker threads the share is their mean.  None
where the log holds no `engine.*.enqueue` on a worker thread in the window.
"""

from chipbench.readers import spanlog

ENQUEUE, WAIT = ".enqueue", ".wait"


def stretches(thread_spans):
    """[start of an enqueue, end of the wait of the same kind after it]."""
    out, open_at = [], {}
    for s in sorted(thread_spans, key=lambda s: s.start):
        if not s.name.startswith("engine."):
            continue
        if s.name.endswith(ENQUEUE):
            open_at.setdefault(s.name[:-len(ENQUEUE)], s.start)
        elif s.name.endswith(WAIT):
            start = open_at.pop(s.name[:-len(WAIT)], None)
            if start is not None:
                out.append((start, s.end))
    return out


def reduce(records, lo, hi):
    """(share in per cent, {span name: gap seconds}) or None."""
    by_thread = {}
    for s in records:
        by_thread.setdefault(s.tid, []).append(s)
    workers = [spans for spans in by_thread.values()
               if any(s.name.startswith("worker.") for s in spans)]
    shares, table = [], {}
    for spans in workers:
        queued = spanlog.union(
            (max(a, lo), min(b, hi)) for a, b in stretches(spans)
            if b > lo and a < hi)
        if not queued:
            continue
        gaps, edge = [], lo
        for a, b in queued:
            if a > edge:
                gaps.append((edge, a))
            edge = b
        if hi > edge:
            gaps.append((edge, hi))
        shares.append(100.0 * sum(b - a for a, b in gaps) / (hi - lo))
        # by the innermost span over each gap; enqueue and wait spans lie
        # inside the stretches and never cover one
        pieces, i = spanlog.innermost(spans, lo, hi), 0
        for a, b in gaps:
            while i < len(pieces) and pieces[i][1] <= a:
                i += 1
            j = i
            while j < len(pieces) and pieces[j][0] < b:
                pa, pb, name = pieces[j]
                cut = min(pb, b) - max(pa, a)
                if cut > 0:
                    key = name or "no span"
                    table[key] = table.get(key, 0.0) + cut / 1e9
                j += 1
    if not shares:
        return None
    n = len(shares)
    return sum(shares) / n, {k: v / n for k, v in table.items()}


def read(ctx):
    records = spanlog.spans()
    w = spanlog.window(ctx, records) if records else None
    if w is None:
        return None
    out = reduce(records, w[0], w[1])
    if out is None:
        return None
    share, table = out
    rows = sorted(table.items(), key=lambda kv: -kv[1])
    ctx.log(f"host gap {share:.2f} % of the {(w[1] - w[0]) / 1e9:.3f} s "
            "traced window; seconds by the span over them: "
            + ", ".join(f"{k} {v:.4f}" for k, v in rows))
    return share
