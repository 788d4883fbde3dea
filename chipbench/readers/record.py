"""One number the driver kept in the window's records, by its path of keys:
a reading of the program's own state at one of the window's ends, such as
the queue at the close.  A number that is there is returned as it is, 0
too (a queue of 0 is a reading: the cell has left overload); a records
object without the path (a driver that keeps no such field) gives None.
args: `path`, a list of keys."""


def read(ctx, path):
    at = ctx.records
    for key in path:
        if not isinstance(at, dict) or key not in at:
            return None
        at = at[key]
    is_number = isinstance(at, (int, float)) and not isinstance(at, bool)
    return at if is_number else None
