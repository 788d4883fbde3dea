"""Share of the HBM peak that the window's decode steps must move, in per
cent, for the DeepSeek-V2 family: every decode step streams the weights
outside the experts and the head once; of the experts it streams those its
rows touched, counted by the program (`moe.experts_touched{kind=decode}`,
distinct experts summed over expert layers and steps); and it reads its
lanes' live latents (from the request records)."""

from chipbench import work_deepseek_v2 as work


def read(ctx):
    r = ctx.records
    if not r or not r.get("engine") or r.get("seconds", 0) <= 0:
        return None
    moe, steps = r.get("moe"), r["engine"]["decode_steps"]
    if not moe or steps <= 0:
        return None
    nbytes = steps * work.step_weight_bytes(ctx.cfg)
    nbytes += 2 * moe["decode"] * work.expert_params(ctx.cfg)
    nbytes += sum(work.window_latent_bytes(ctx.cfg, q, r["seconds"])
                  for q in r["requests"])
    return 100.0 * nbytes / (r["seconds"] * ctx.peaks["hbm_bytes_per_s"])
