"""Share of the HBM peak that the decode steps of the window must move, in
per cent: every decode step streams the weights once (layers + LM head) and
reads the live keys and values of its lanes; steps from the engine's own
counter (`decode_steps`), keys and values from the request records."""

from chipbench import work


def read(ctx):
    r = ctx.records
    if not r or not r.get("engine") or r.get("seconds", 0) <= 0:
        return None
    steps = r["engine"]["decode_steps"]
    if steps <= 0:
        return None
    nbytes = steps * work.weight_bytes(ctx.cfg)
    for q in r["requests"]:
        if q["generated"] > 0:
            nbytes += work.decode_kv_bytes(ctx.cfg, q["prompt_len"],
                                           q["generated"])
    return 100.0 * nbytes / (r["seconds"] * ctx.peaks["hbm_bytes_per_s"])
