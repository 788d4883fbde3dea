"""Share of the traced window's busy device time taken by the ops whose
produced type matches `produced` (a regular expression on the type an op
produces, as `trace.short_name` gives it: `bf16[128,1,4,3584]`), in per
cent.  Ops that hold other ops (`while`, `conditional`, `call`) are not
counted: their bodies' ops are counted on their own.  Logs the matched
ops by produced type, so that a pattern can be checked against a run."""

import re

from chipbench import trace

HOLDERS = ("while", "conditional", "call")


def read(ctx, produced):
    r = ctx.reduced
    if not r or r.get("busy_s", 0) <= 0:
        return None
    rx = re.compile(produced)
    seconds, kinds = 0.0, {}
    for name, sec in r["op_seconds"].items():
        short = trace.short_name(name, limit=10_000)
        head, _, out = short.partition(" -> ")
        if head.rsplit(" ", 1)[-1].split(":")[0] in HOLDERS:
            continue
        if rx.search(out):
            seconds += sec
            kinds[out] = kinds.get(out, 0.0) + sec
    if not kinds:
        return None
    top = sorted(kinds.items(), key=lambda kv: -kv[1])[:8]
    ctx.log(f"device share {produced!r}: {seconds:.4f} s of {r['busy_s']:.4f}"
            f" busy s; by produced type: "
            + ", ".join(f"{k} {v:.4f}" for k, v in top))
    return 100.0 * seconds / r["busy_s"]
