"""A kernel's share of its roofline, in per cent: the least time the chip
needs for the window's work of that kind (the larger of operations over the
bf16 peak and bytes over the HBM peak, from step or request records through
chipbench/work.py) over the kernel's device time in the trace.  args:
`pattern` (regular expression on the device op's name) and `work`
("flash_train" or "paged_attn").  Logs which bound it is."""

from chipbench import trace, work


def read(ctx, pattern, work_kind):
    if not ctx.reduced or not ctx.records:
        return None
    seconds = trace.seconds_matching(ctx.reduced, pattern)
    if not seconds:
        return None
    r = ctx.records
    if work_kind == "flash_train":
        f, b = work.flash_train_work(ctx.cfg, ctx.traffic["batch"],
                                     ctx.traffic["seq"])
        flops, nbytes = f * r["steps"], b * r["steps"]
    elif work_kind == "paged_attn":
        # only the work of the traced part of the window: tokens that
        # came before the trace ended (it may end before the window does)
        traced = ctx.reduced["window_s"]
        flops = nbytes = 0
        for q in r["requests"]:
            done = sum(1 for t in q["token_s"] if t <= traced)
            if done > 0:
                f, b = work.request_attn_work(
                    ctx.cfg, q["prompt_len"], q.get("cached", 0), done)
                flops, nbytes = flops + f, nbytes + b
    else:
        raise ValueError(work_kind)
    if flops <= 0:
        return None
    least, bound = work.least_seconds(flops, nbytes, ctx.peaks)
    ctx.log(f"roofline {pattern!r}: least {least:.4f} s ({bound}-bound), "
            f"kernel {seconds:.4f} s on the device")
    return 100.0 * least / seconds
