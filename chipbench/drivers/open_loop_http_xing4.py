"""Driver `open_loop_http_xing4`: `open_loop_http_deepseek_v2` (its lead-in,
its window with the routing counters at the window's ends and the trace's
end, its drain) in front of a model of the Xing4.0 family.  What names the
family's modules is here: the engine built round `program_xing4`'s model
from `weights_xing4`'s seeded weights, the check against
`reference/xing4.py`, and `calibrate` for chipbench/control.py, which also
reports each seed's window as the cell's end-to-end metrics do (a window
above the knee is a reading of capacity for the rate rule).

The program is built first thing in `setup`: a checkout without the family
fails there at once, on the import of its model.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from chipbench import serving
from chipbench.drivers.open_loop_http_deepseek_v2 import (  # noqa: F401
    _moe_delta, drain, start_client, window)
from chipbench.serving import counts, end_to_end  # noqa: F401


def build_engine(ctx, warm=True):
    """Weights from the seed a group a call, the program's model round
    them layer by layer, the engine at the configuration's sizes, the
    mix's programs warmed, the gateway started."""
    import jax.numpy as jnp

    from chipbench import program_xing4 as program
    from chipbench import weights_xing4 as W

    cfg, mix = ctx.cfg, ctx.traffic
    st = serving.State()
    t = time.perf_counter()
    dtype = jnp.dtype(cfg["dtype"])
    st.model = program.build_model(
        cfg, lambda g: W.make_group(cfg, ctx.seed, g, dtype))
    st.model.eval()
    t_model = time.perf_counter() - t

    from paddle_tpu.inference import create_llm_engine
    from paddle_tpu.serving.gateway import Gateway, GatewayConfig

    sizes = {k: v for k, v in cfg["engine"].items()
             if v is not None and k != "block_size"}
    st.engine = create_llm_engine(st.model, **sizes)
    ctx.log(f"weights and model {t_model:.1f} s, engine "
            f"{time.perf_counter() - t - t_model:.1f} s")
    plan = serving.program_plan(cfg, mix)
    st.programs = {"plan": plan["count"]}
    if warm:
        st.programs["warmed"] = serving.warm_up(ctx, st, plan)
    long = 3600.0                    # the harness ends a run, not the gateway
    st.gateway = Gateway([st.engine], GatewayConfig(
        request_timeout_s=long, watchdog_timeout_s=long))
    st.gateway.__enter__()
    return st


def setup(ctx):
    st = build_engine(ctx)
    start_client(ctx, st, ctx.traffic, ctx.seconds)
    return st


# ------------------------------------------------------------------- check

def reference_gaps(ctx, sample, prompts, mode="f32"):
    """Reference logits at every served position of the sample; returns
    (logits [n, vocab] as numpy, served tokens, request index a row)."""
    import jax.numpy as jnp

    from chipbench import weights_xing4 as W
    from chipbench.reference import xing4 as ref

    cfg = ctx.cfg
    pad = int(ctx.traffic.get("check_pad", 128))
    longest = max(len(p) + len(r["token_ids"])
                  for p, r in zip(prompts, sample))
    length = -(-longest // pad) * pad
    ids = np.zeros((len(sample), length), np.int32)
    rows, cols, tokens, owner = [], [], [], []
    for i, (p, r) in enumerate(zip(prompts, sample)):
        seq = list(p) + list(r["token_ids"])
        ids[i, :len(seq)] = seq
        for j, tok in enumerate(r["token_ids"]):
            rows.append(i)
            cols.append(len(p) - 1 + j)   # the logits that choose token j
            tokens.append(tok)
            owner.append(r["index"])
    dtype = jnp.dtype(cfg["dtype"])
    logits = ref.logits_at(
        cfg, lambda g: W.make_group(cfg, ctx.seed, g, dtype), ids,
        np.asarray(rows), np.asarray(cols), mode)
    return np.asarray(logits, np.float32), tokens, owner


def gaps(ref_logits, tokens):
    """(widest, mean) gap by which a served token's reference logit lies
    below the reference's best.  The widest catches one wrong token; it
    cannot tell this family's bfloat16 program from the fp8 control,
    because a near-tie of the sigmoid router flips an expert whose weight
    is renormalised (a quarter to a half of a layer's FFN) at a few tokens
    of either.  The mean over every served token can: the fp8 control
    moves most of them."""
    best = ref_logits.max(-1)
    d = best - ref_logits[np.arange(len(tokens)), np.asarray(tokens)]
    return float(d.max()), float(d.mean())


def _sample(ctx, st):
    """The finished greedy requests to compare and their prompts."""
    by_index = {q["index"]: q["body"]["prompt"] for q in st.sched["requests"]}
    sample = serving.check_sample(ctx, ctx.records["requests"])
    return sample, [by_index[r["index"]] for r in sample]


def check(ctx, st):
    """The widest and the mean gap by which a served greedy token's
    reference logit lies below the reference's best (`gaps`), and every
    request answered in full."""
    recs = ctx.records["requests"]
    sample, prompts = _sample(ctx, st)
    st.free()
    out = {name: {"value": None, "limit": ctx.limits[name], "tokens": 0}
           for name in ("logit_gap", "logit_gap_mean")}
    if sample:
        t = time.perf_counter()
        logits, tokens, _ = reference_gaps(ctx, sample, prompts)
        ctx.log(f"reference over {len(sample)} requests, {len(tokens)} "
                f"served tokens: {time.perf_counter() - t:.1f} s")
        for name, value in zip(out, gaps(logits, tokens)):
            out[name].update(value=value, tokens=len(tokens))
    unanswered = sum(1 for r in recs if not r["ok"])
    out["unanswered"] = {"value": float(unanswered), "limit": 0.0}
    return out


# ----------------------------------------------------------------- control

def swap_weights(ctx, st):
    """This seed's weights into the model and the engine, a group at a
    time, the old ones freed first (two copies do not fit)."""
    import jax.numpy as jnp

    from chipbench import program_xing4 as program
    from chipbench import weights_xing4 as W

    cfg = ctx.cfg
    names = list(st.engine._state_names)
    st.engine._state_arrays = None
    for p in dict(st.model.named_parameters()).values():
        p._data = jnp.zeros((), p._data.dtype)
    gc.collect()
    dtype = jnp.dtype(cfg["dtype"])
    for g in W.groups(cfg):
        leaves = W.make_group(cfg, ctx.seed, g, dtype)
        module = (st.model.model.layers[int(g.split(".")[1])]
                  if g.startswith("layer.") else st.model)
        params = dict(module.named_parameters())
        for path, value in program.program_leaves(cfg, g, leaves).items():
            params[path]._data = value
    sd = st.model.state_dict()
    st.engine._state_arrays = [sd[n]._data for n in names]


def calibrate(make_ctx, seeds, control_seeds, emit):
    """chipbench/serving_control.py's readings for this family: on every
    seed a window at the cell's load, its tokens/s and queue, and the
    program's widest logit gap; on the first seeds the fp8 control, the
    reference in bfloat16 and a served token altered."""
    st = None
    for i, seed in enumerate(seeds):
        ctx = make_ctx(seed)
        if st is None:
            st = build_engine(ctx, warm=True)
        else:
            swap_weights(ctx, st)
        start_client(ctx, st, ctx.traffic, ctx.seconds)
        ctx.records = window(ctx, st, ctx.seconds)
        drain(ctx, st)
        stats = end_to_end(ctx, st)
        sample, prompts = _sample(ctx, st)
        attempted, failed = counts(ctx)
        logits, tokens, _ = reference_gaps(ctx, sample, prompts)
        gap, mean = gaps(logits, tokens)
        emit({"seed": seed, "kind": "program", "logit_gap": gap,
              "logit_gap_mean": mean, "tokens": len(tokens), "requests": len(sample),
              "attempted": attempted, "failed": failed,
              "gen_tokens_per_s": stats["gen_tokens_per_s"]["value"],
              "seconds": ctx.records["seconds"],
              "decode_steps": ctx.records["engine"]["decode_steps"],
              "queue": ctx.records["queue"],
              "compiled_in_window":
                  ctx.records["programs_compiled_in_window"],
              "ref_best_abs_mean": float(np.abs(logits.max(-1)).mean())})
        if i < control_seeds:
            low, _, _ = reference_gaps(ctx, sample, prompts, "fp8")
            first = low.argmax(-1)
            gap8, mean8 = gaps(logits, first)
            emit({"seed": seed, "kind": "control_fp8", "logit_gap": gap8,
                  "logit_gap_mean": mean8, "tokens": len(tokens),
                  "tokens_changed": int((first != logits.argmax(-1)).sum())})
            # a witness of what bfloat16 rounding alone reads: the
            # reference's own greedy choices with every product's inputs
            # rounded to bfloat16, against its float32 logits
            half, _, _ = reference_gaps(ctx, sample, prompts, "bf16")
            first = half.argmax(-1)
            gap16, mean16 = gaps(logits, first)
            emit({"seed": seed, "kind": "reference_bf16",
                  "logit_gap": gap16, "logit_gap_mean": mean16,
                  "tokens": len(tokens),
                  "tokens_changed": int((first != logits.argmax(-1)).sum())})
            margin = logits.max(-1) - np.partition(logits, -2, -1)[:, -2]
            for name, pos in (("surest", int(margin.argmax())),
                              ("least_sure", int(margin.argmin()))):
                altered = list(tokens)
                altered[pos] = (altered[pos] + 1) % logits.shape[-1]
                gapf, meanf = gaps(logits, altered)
                emit({"seed": seed, "kind": f"fault_token_altered_{name}",
                      "logit_gap": gapf, "logit_gap_mean": meanf})
    if st is not None:
        st.free()
