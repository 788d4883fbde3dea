"""Share of the HBM peak that the window's decode steps must move, in per
cent, for the Xing4.0 family (readers/hbm_share_dsv2.py's rule with
work_xing4.py's counts): every decode step streams the weights outside the
routed experts (phi and the compressed query's matrices among them) and the
head once, and of the experts those its rows touched
(`moe.experts_touched{kind=decode}`); every decoded token reads its
lane's live latents and reads and writes its residual streams at each
sublayer."""

from chipbench import work_xing4 as work


def read(ctx):
    r = ctx.records
    if not r or not r.get("engine") or r.get("seconds", 0) <= 0:
        return None
    moe, steps = r.get("moe"), r["engine"]["decode_steps"]
    if not moe or steps <= 0:
        return None
    cfg, close = ctx.cfg, r["seconds"]
    nbytes = steps * work.step_weight_bytes(cfg)
    nbytes += 2 * moe["decode"] * work.expert_params(cfg)
    for q in r["requests"]:
        nbytes += work.window_latent_bytes(cfg, q, close)
        nbytes += (work.window_decode_tokens(q, close)
                   * work.hc_stream_bytes_token(cfg))
    return 100.0 * nbytes / (close * ctx.peaks["hbm_bytes_per_s"])
