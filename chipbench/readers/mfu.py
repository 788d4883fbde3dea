"""The whole step's share of the chip's peak, in per cent: the operations the
model needs for the window's work (chipbench/work.py; recompute not counted)
over the window's seconds and the bf16 peak.  args: `kind` "train" (steps x
forward+backward) or "serve" (every prompt and generated token processed)."""

from chipbench import work


def read(ctx, kind):
    r = ctx.records
    if not r or r.get("seconds", 0) <= 0:
        return None
    if kind == "train":
        tr = ctx.traffic
        flops = r["steps"] * work.train_step_flops(ctx.cfg, tr["batch"],
                                                   tr["seq"])
    elif kind == "serve":
        flops = sum(work.request_flops(ctx.cfg, q["prompt_len"],
                                       q.get("cached", 0), q["generated"])
                    for q in r["requests"] if q["generated"] > 0)
    else:
        raise ValueError(kind)
    if flops <= 0:
        return None
    return 100.0 * flops / (r["seconds"] * ctx.peaks["bf16_flops"])
