"""The program's build table (see `spanlog.py`) reduced to the mean or the
sum of one phase, in seconds, over the programs built in set-up (before the
traced window began).  A record is one cache miss of a compiled program:
`total_s` and, as jax itself reports them for that miss, `trace_s` (jaxpr
tracing), `lower_s` (lowering to MLIR), `compile_s` (backend compile),
`cache_retrieval_s` (the read from the persistent cache), with the
remainder as `rest_s` (executable load, first run, the program card's
probe).  Logs the whole table, a line a program.

args: `phase` (one of those keys), `stat` ("mean" or "sum"), `program`
(optional: only builds of that program name).  None where there is no
table or no build.
"""

from chipbench.readers import spanlog

PHASES = ("total_s", "trace_s", "lower_s", "compile_s",
          "cache_retrieval_s", "rest_s")


def reduce(table, before_ns=None, phase="total_s", stat="mean",
           program=None):
    rows = [b for b in table
            if (before_ns is None or b["start_ns"] < before_ns)
            and (program is None or b["program"] == program)]
    if not rows:
        return None
    total = sum(b[phase] for b in rows)
    return total / len(rows) if stat == "mean" else total


def read(ctx, phase="total_s", stat="mean", program=None):
    table = spanlog.builds()
    if not table:
        return None
    w = spanlog.window(ctx, spanlog.spans() or [])
    ctx.log("program builds (program key: " + " ".join(PHASES)
            + " cache_hit):")
    for b in table:
        ctx.log(f"  {b['program']} {b['key']}: "
                + " ".join(f"{b[p]:.3f}" for p in PHASES)
                + f" {b['cache_hit']}")
    for p in PHASES:
        ctx.log(f"  sum of {p}: {sum(b[p] for b in table):.2f} s over "
                f"{len(table)} builds")
    return reduce(table, w[0] if w else None, phase, stat, program)
