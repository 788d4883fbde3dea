"""One of the statistics the driver computed over the window's records
(`records["stats"]`), by name: a tail that is too unsteady at this window's
length to carry a bound stands here, beside the steadier end-to-end metric
it moves.  args: `name`."""


def read(ctx, name):
    stats = (ctx.records or {}).get("stats") or {}
    row = stats.get(name)
    return None if row is None else row["value"]
