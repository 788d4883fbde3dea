"""Mean (or a percentile) of a list of seconds in the window's records, in
milliseconds.  args: `field` (key of the records), `stat` ("mean", "median"
or "pNN")."""

import statistics


def percentile(values, q):
    """Nearest-rank percentile, q in 0..100."""
    ordered = sorted(values)
    rank = max(1, -(-len(ordered) * q // 100))
    return ordered[int(rank) - 1]


def read(ctx, field, stat="mean"):
    values = ctx.records.get(field) if ctx.records else None
    if not values:
        return None
    if stat == "mean":
        v = statistics.fmean(values)
    elif stat == "median":
        v = statistics.median(values)
    else:
        v = percentile(values, float(stat[1:]))
    return v * 1e3
