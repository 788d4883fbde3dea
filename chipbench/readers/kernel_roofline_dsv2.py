"""A kernel's share of its roofline for the DeepSeek-V2 family, in per cent
(readers/kernel_roofline.py's rule): the least time the chip needs for the
traced part's work of that kind over the device time of the ops `pattern`
selects.  `work_kind` "mla_attn": the attention work of every token that
came between the window's "go" and the trace's end, from the request
records (`work.window_part`); "moe_experts": the
routed experts' operations and bytes from the program's routing counters
read when the trace ended.  Logs which bound it is, and the Mosaic kernels
it saw by the type they produce (a Pallas kernel carries no name in the
trace), so that a pattern can be checked against a run's log."""

from chipbench import trace, work_deepseek_v2 as work


def mosaic_kernels(reduced):
    """{produced type: seconds} of the trace's Mosaic custom calls."""
    out = {}
    for name, sec in reduced["op_seconds"].items():
        if 'custom_call_target="tpu_custom_call"' in name:
            kind = trace.short_name(name).split("-> ")[-1]
            out[kind] = out.get(kind, 0.0) + sec
    return out


def read(ctx, pattern, work_kind):
    if not ctx.reduced or not ctx.records:
        return None
    seconds = trace.seconds_matching(ctx.reduced, pattern)
    if not seconds:
        return None
    r = ctx.records
    if work_kind == "mla_attn":
        traced = ctx.reduced["window_s"]
        flops = nbytes = 0
        for q in r["requests"]:
            prompt, cached, done, _ = work.window_part(q, traced)
            if done > 0:
                f, b = work.request_attn_work(ctx.cfg, prompt, cached, done)
                flops, nbytes = flops + f, nbytes + b
    elif work_kind == "moe_experts":
        moe = r.get("moe_traced")
        if not moe:
            return None
        flops, nbytes = work.experts_work(
            ctx.cfg, moe["rows"], moe["prefill"] + moe["decode"])
    else:
        raise ValueError(work_kind)
    if flops <= 0:
        return None
    least, bound = work.least_seconds(flops, nbytes, ctx.peaks)
    kernels = sorted(mosaic_kernels(ctx.reduced).items(),
                     key=lambda kv: -kv[1])[:8]
    ctx.log(f"roofline {pattern!r}: least {least:.4f} s ({bound}-bound), "
            f"selected ops {seconds:.4f} s on the device; Mosaic kernels "
            f"by produced type: "
            + ", ".join(f"{k} {v:.4f}" for k, v in kernels))
    return 100.0 * least / seconds
