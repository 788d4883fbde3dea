"""Driver `open_loop_http_deepseek_v2`: the open loop of `open_loop_http`
(chipbench/serving.py: the client process, the drain, the end-to-end
metrics, the counts, the sample that is checked, the plan of programs and
their warm-up, all as they are) in front of a model of the DeepSeek-V2
family.  What the family needs of its own is here: the engine built round
`program_deepseek_v2`'s model from `weights_deepseek_v2`'s seeded weights,
the check against `reference/deepseek_v2.py`, the routing counters of the
window (and of its traced part) for the readers, and `calibrate` for
chipbench/control.py.

And a LEAD-IN, which the dense driver lacks.  A request of this mix lives
for a good part of a window (about 280 tokens at 55-100 ms), so a window
that starts on an empty engine is a ramp and measures how early a seed's
first arrivals fall.  Here the schedule is one trace over `lead_in_s` +
the window's seconds; the client is told to go during set-up, the lead-in
is counted as set-up, and the timed window is the trace's last `seconds`,
begun on an engine in the state the offered rate leaves it in (above the
knee: every lane full and a queue).  A request's tokens count where they
were streamed: `generated` is those inside the window, `before` those
streamed during the lead-in.
"""

from __future__ import annotations

import gc
import threading
import time

import numpy as np

from chipbench import serving
from chipbench.serving import counts, end_to_end  # noqa: F401

#: the names the model gives its layers' counts (`layer_stat_names`)
ROWS, TOUCHED = "moe.rows", "moe.experts_touched"


def build_engine(ctx, warm=True):
    """Weights from the seed a group a call, the program's model round
    them layer by layer, the engine at the configuration's sizes, the
    mix's programs warmed, the gateway started."""
    import jax.numpy as jnp

    from paddle_tpu.inference import create_llm_engine
    from paddle_tpu.serving.gateway import Gateway, GatewayConfig

    from chipbench import program_deepseek_v2 as program
    from chipbench import weights_deepseek_v2 as W

    cfg, mix = ctx.cfg, ctx.traffic
    st = serving.State()
    t = time.perf_counter()
    dtype = jnp.dtype(cfg["dtype"])
    st.model = program.build_model(
        cfg, lambda g: W.make_group(cfg, ctx.seed, g, dtype))
    st.model.eval()
    t_model = time.perf_counter() - t
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


def start_client(ctx, st, mix, seconds):
    """serving.start_client over the lead-in and the window as one trace;
    the client is told to go, and this returns when the lead-in is over."""
    lead = float(mix.get("lead_in_s", 0.0))
    serving.start_client(ctx, st, mix, lead + seconds)
    st.client.stdin.write("go\n")
    st.client.stdin.flush()
    t = time.monotonic()
    time.sleep(lead)
    ctx.log(f"lead-in of {time.monotonic() - t:.1f} s at "
            f"{mix['arrivals']['rate_rps']} requests/s over")


def setup(ctx):
    st = build_engine(ctx)
    start_client(ctx, st, ctx.traffic, ctx.seconds)
    return st


def window(ctx, st, seconds):
    """serving.window without its "go" (the client has been going since
    the lead-in began): the engine's counters at the window's two ends,
    and once more when the trace ends (a traced run's kernel metrics count
    the traced part's routing, not the whole window's)."""
    st.stats_at_trace_end = None
    limit = ctx.traffic.get("trace_seconds") if ctx.trace else None
    timer = None
    if limit and limit < seconds:
        def snapshot():
            st.stats_at_trace_end = st.engine.stats()

        timer = threading.Timer(limit, snapshot)
        timer.start()
    st.stats_at_go = st.engine.stats()
    st.t_go = time.monotonic()
    try:
        with ctx.span("serve.window_wait"):
            time.sleep(max(0.0, seconds - (time.monotonic() - st.t_go)))
        st.stats_at_close = st.engine.stats()
        return {"seconds": time.monotonic() - st.t_go, "requests": []}
    finally:
        if timer is not None:
            timer.cancel()
            timer.join()


def _moe_delta(a, b):
    """Routing between two readings of `Engine.stats()`: distinct experts
    touched by kind of dispatch, rows routed, decode steps."""
    if not a.get("layer_stats") or not b.get("layer_stats"):
        return None              # a program without the routing counters
    d = {name: {k: b["layer_stats"][name][k] - a["layer_stats"][name][k]
                for k in ("prefill", "decode")} for name in (ROWS, TOUCHED)}
    return {"prefill": d[TOUCHED]["prefill"], "decode": d[TOUCHED]["decode"],
            "rows": d[ROWS]["prefill"] + d[ROWS]["decode"],
            "decode_steps": b["decode_steps"] - a["decode_steps"]}


def drain(ctx, st):
    """serving.drain (times come back counted from the window's "go", so
    the lead-in's are negative; it keeps the queue at the window's ends),
    then each request's tokens put where they were streamed, and the
    routing between the window's ends."""
    serving.drain(ctx, st)
    r = ctx.records
    close = r["seconds"]
    for rec in r["requests"]:
        rec["before"] = sum(1 for t in rec["token_s"] if t <= 0.0)
        rec["generated"] = sum(1 for t in rec["token_s"] if 0.0 < t <= close)
    r["moe"] = _moe_delta(st.stats_at_go, st.stats_at_close)
    traced = getattr(st, "stats_at_trace_end", None)
    r["moe_traced"] = (_moe_delta(st.stats_at_go, traced)
                       if traced is not None else r["moe"])
    ctx.log(f"routing in the window {r['moe']}, in its traced part "
            f"{r['moe_traced']}")


# ------------------------------------------------------------------- check

def reference_gaps(ctx, sample, prompts, mode="f32"):
    """Reference logits at every served position of the sample; returns
    (logits [n, vocab] as numpy, served tokens, request index a row)."""
    import jax.numpy as jnp

    from chipbench import weights_deepseek_v2 as W
    from chipbench.reference import deepseek_v2 as ref

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


def check(ctx, st):
    """serving.check's comparison against this family's reference: the
    widest gap by which a served greedy token's reference logit lies below
    the reference's best, and every request answered in full."""
    from chipbench import compare

    recs = ctx.records["requests"]
    by_index = {q["index"]: q["body"]["prompt"] for q in st.sched["requests"]}
    sample = serving.check_sample(ctx, recs)
    st.free()
    out = {"logit_gap": {"value": None, "limit": ctx.limits["logit_gap"],
                         "tokens": 0}}
    if sample:
        t = time.perf_counter()
        prompts = [by_index[r["index"]] for r in sample]
        logits, tokens, _ = reference_gaps(ctx, sample, prompts)
        gap, _ = compare.widest_logit_gap(logits, tokens)
        ctx.log(f"reference over {len(sample)} requests, {len(tokens)} "
                f"served tokens: {time.perf_counter() - t:.1f} s")
        out["logit_gap"].update(value=gap, tokens=len(tokens))
    unanswered = sum(1 for r in recs if not r["ok"])
    out["unanswered"] = {"value": float(unanswered), "limit": 0.0}
    return out


# ----------------------------------------------------------------- control

def swap_weights(ctx, st):
    """This seed's weights into the model and the engine, a group at a
    time, the old ones freed first (two copies do not fit)."""
    import jax.numpy as jnp

    from chipbench import program_deepseek_v2 as program
    from chipbench import weights_deepseek_v2 as W

    cfg = ctx.cfg
    names = list(st.engine._state_names)
    st.engine._state_arrays = None
    for p in dict(st.model.named_parameters()).values():
        p._data = jnp.zeros((), p._data.dtype)
    gc.collect()
    dtype = jnp.dtype(cfg["dtype"])
    for g in W.groups(cfg):
        leaves = W.make_group(cfg, ctx.seed, g, dtype)
        shaped = program.program_leaves(cfg, g, leaves)
        module = (st.model.model.layers[int(g.split(".")[1])]
                  if g.startswith("layer.") else st.model)
        params = dict(module.named_parameters())
        for path, value in shaped.items():
            params[path]._data = value
    sd = st.model.state_dict()
    st.engine._state_arrays = [sd[n]._data for n in names]


def calibrate(make_ctx, seeds, control_seeds, emit):
    """chipbench/serving_control.py's readings for this family: on every
    seed a window at the cell's load and the program's widest logit gap;
    on the first seeds the fp8 control and a served token altered."""
    from chipbench import compare

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
        recs = ctx.records["requests"]
        by_index = {q["index"]: q["body"]["prompt"]
                    for q in st.sched["requests"]}
        sample = serving.check_sample(ctx, recs)
        prompts = [by_index[r["index"]] for r in sample]
        attempted, failed = counts(ctx)
        logits, tokens, _ = reference_gaps(ctx, sample, prompts)
        gap, _ = compare.widest_logit_gap(logits, tokens)
        emit({"seed": seed, "kind": "program", "logit_gap": gap,
              "tokens": len(tokens), "requests": len(sample),
              "attempted": attempted, "failed": failed,
              "compiled_in_window":
                  ctx.records["programs_compiled_in_window"],
              "ref_best_abs_mean": float(np.abs(logits.max(-1)).mean())})
        if i < control_seeds:
            low, _, _ = reference_gaps(ctx, sample, prompts, "fp8")
            first = low.argmax(-1)
            gap8, _ = compare.widest_logit_gap(logits, first)
            emit({"seed": seed, "kind": "control_fp8", "logit_gap": gap8,
                  "tokens": len(tokens),
                  "tokens_changed": int((first != logits.argmax(-1)).sum())})
            margin = logits.max(-1) - np.partition(logits, -2, -1)[:, -2]
            for name, pos in (("surest", int(margin.argmax())),
                              ("least_sure", int(margin.argmin()))):
                altered = list(tokens)
                altered[pos] = (altered[pos] + 1) % logits.shape[-1]
                gapf, _ = compare.widest_logit_gap(logits, altered)
                emit({"seed": seed, "kind": f"fault_token_altered_{name}",
                      "logit_gap": gapf})
    if st is not None:
        st.free()
