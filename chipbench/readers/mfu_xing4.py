"""The whole step's share of the chip's bf16 peak for the Xing4.0 family,
in per cent (readers/mfu_dsv2.py's rule with work_xing4.py's counts: active
parameters a token with query compression and mHC's phi, mHC's mixing, the
head a generated token, attention in its expanded form) over the window's
seconds and the peak.  A request that was already streaming when the
window began counts for the tokens it decoded inside the window."""

from chipbench import work_xing4 as work


def read(ctx):
    r = ctx.records
    if not r or r.get("seconds", 0) <= 0:
        return None
    parts = [work.window_part(q, r["seconds"]) for q in r["requests"]]
    flops = sum(work.request_flops(ctx.cfg, prompt, cached, done)
                for prompt, cached, done, _ in parts if done > 0)
    if flops <= 0:
        return None
    return 100.0 * flops / (r["seconds"] * ctx.peaks["bf16_flops"])
