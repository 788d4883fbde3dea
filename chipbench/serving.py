"""What the two HTTP drivers share: `create_llm_engine` behind a `Gateway`
on port 0, warm-up of the closed set of programs the mix can reach, the
window driven by the client process (chipbench/loadclient.py), the
end-to-end metrics from the client's records, and the check of served
greedy tokens against the plain reference.
"""

from __future__ import annotations

import gc
import json
import os
import subprocess
import sys
import time

import numpy as np

from chipbench.readers.mean_ms import percentile

HERE = os.path.dirname(os.path.abspath(__file__))


class State:
    def __init__(self):
        self.model = self.engine = self.gateway = None
        self.client = None
        self.sched = self.sched_path = self.out_path = None
        self.t_go = None
        self.stats_at_go = self.stats_at_close = None
        self.programs = None

    def free(self):
        if self.gateway is not None:
            self.gateway.__exit__(None, None, None)
        if self.engine is not None:
            self.engine.close()
        self.model = self.engine = self.gateway = None
        gc.collect()


# ------------------------------------------------------------------ set-up

def _pow2_at_least(n):
    b = 1
    while b < n:
        b *= 2
    return b


def program_plan(cfg, mix):
    """The closed set of programs the mix can reach, from the engine's own
    bucket rules: prefill (length bucket x lane bucket 1..num_slots) and
    decode (horizon 1..max_horizon x block-table width for the lengths a
    lane can have).  Returned as warm-up jobs, one `generate()` each."""
    eng = cfg["engine"]
    slots, block = eng["num_slots"], eng.get("block_size", 16)
    max_h = eng.get("max_horizon", 8)
    lo, hi = mix["prompts"]["min"], mix["prompts"]["max"]
    out = mix["outputs"]
    out_max = out["cap"] if out["kind"] == "pareto" else out["max"]
    floor = eng.get("min_prefill_bucket", 8)
    buckets, b = [], max(floor, _pow2_at_least(lo))
    while True:
        buckets.append(min(b, eng["max_seq_len"]))
        if b >= hi:
            break
        b *= 2
    # lane buckets above `warm_lanes_max` are left cold where the mix says
    # so: each program costs seconds of set-up even from a warm cache, and
    # a prefill of that many same-bucket requests needs as many queued at
    # one boundary.  The run reports every program compiled in the window.
    lanes, n = [], 1
    while n <= min(slots, mix.get("warm_lanes_max", slots)):
        lanes.append(n)
        n *= 2
    prefill = [{"kind": "prefill", "bucket": bk, "lanes": ln,
                "prompt_len": min(hi, bk), "new_tokens": 1}
               for bk in buckets for ln in lanes]
    # a lane's length runs from the shortest prompt to the longest prompt
    # plus the longest output: every power-of-two table width in between
    w_lo = _pow2_at_least(-(-(lo + 1) // block))
    w_hi = _pow2_at_least(-(-(hi + out_max) // block))
    decode, w = [], w_lo
    horizons = max_h.bit_length()          # 1, 2, 4, .. max_h
    while w <= w_hi:
        # one lane that starts inside this width and stays inside it while
        # the horizon grows 1, 2, 4, .. and shrinks again at the budget
        # (the width covers position + horizon, engine.py::_attn_blocks)
        start = min(hi, max(lo, (w // 2) * block + 1))
        decode.append({"kind": "decode", "width": w, "lanes": 1,
                       "prompt_len": start,
                       "new_tokens": 6 * max_h + 2 * horizons})
        w *= 2
    return {"prefill": prefill, "decode": decode,
            "count": len(prefill) + len(decode) * horizons}


def engine_programs(engine):
    s = engine.stats()
    return {"count": s["decode_compiles"] + s["prefill_compiles"],
            "prefill": [list(b) if isinstance(b, (tuple, list)) else b
                        for b in s["prefill"]["buckets"]],
            "decode": [list(b) if isinstance(b, (tuple, list)) else b
                       for b in s["decode_buckets"]]}


def warm_up(ctx, st, plan):
    """Drive each program of the plan once through `Engine.generate()`."""
    from paddle_tpu.serving import SamplingParams

    rng = np.random.default_rng([int(ctx.seed), 17])
    vocab = ctx.cfg["vocab_size"]
    took = []
    for job in plan["prefill"] + plan["decode"]:
        prompts = [rng.integers(0, vocab, job["prompt_len"]).tolist()
                   for _ in range(job["lanes"])]
        samplings = [SamplingParams(max_new_tokens=job["new_tokens"])
                     for _ in prompts]
        t = time.perf_counter()
        st.engine.generate(prompts, samplings)
        took.append(round(time.perf_counter() - t, 1))
    ctx.log(f"warm-up seconds a job (prefill bucket x lanes, then decode "
            f"widths): {took}")
    got = engine_programs(st.engine)
    ctx.log(f"warm-up: plan {plan['count']} programs, engine compiled "
            f"{got['count']}: prefill {got['prefill']} decode "
            f"{got['decode']}")
    return got


def build_engine(ctx, warm=True):
    """Weights from the seed in one jitted call, the program's model round
    them, the engine with the sizes the configuration states, the programs
    warmed, the gateway started."""
    import jax.numpy as jnp

    from paddle_tpu.inference import create_llm_engine
    from paddle_tpu.serving.gateway import Gateway, GatewayConfig

    from chipbench import program, weights as W

    cfg, mix = ctx.cfg, ctx.traffic
    st = State()
    t = time.perf_counter()
    w = W.make_all(cfg, ctx.seed, jnp.dtype(cfg["dtype"]))
    st.model = program.build_model(cfg, w)
    del w
    st.model.eval()
    t_model = time.perf_counter() - t
    sizes = {k: v for k, v in cfg["engine"].items()
             if v is not None and k != "block_size"}
    st.engine = create_llm_engine(st.model, **sizes)
    ctx.log(f"weights and model {t_model:.1f} s, engine "
            f"{time.perf_counter() - t - t_model:.1f} s")
    plan = program_plan(cfg, mix)
    st.programs = {"plan": plan["count"]}
    if warm:
        st.programs["warmed"] = warm_up(ctx, st, plan)
    long = 3600.0                    # the harness ends a run, not the gateway
    st.gateway = Gateway([st.engine], GatewayConfig(
        request_timeout_s=long, watchdog_timeout_s=long))
    st.gateway.__enter__()
    return st


def start_client(ctx, st, mix, seconds):
    """The schedule from the seed, written out, and the client process
    started and waiting for "go"."""
    from paddle_tpu.observability import events

    from chipbench import loadgen

    # the program's span ring holds 65,536 records by default; full lanes
    # streaming through the window and until the last answer write more
    # than that, and the traced part (the window's first seconds), which
    # the span readers need, would be gone before it is read
    events.set_capacity(1 << 20)
    st.sched = loadgen.schedule(mix, ctx.seed, seconds,
                                ctx.cfg["vocab_size"])
    os.makedirs(ctx.work_dir, exist_ok=True)
    st.sched_path = os.path.join(ctx.work_dir,
                                 f"schedule.{ctx.cell.name}.json")
    st.out_path = os.path.join(ctx.work_dir, f"records.{ctx.cell.name}.json")
    with open(st.sched_path, "w") as f:
        json.dump(st.sched, f)
    st.client = subprocess.Popen(
        [sys.executable, os.path.join(HERE, "loadclient.py"), st.sched_path,
         st.out_path, "127.0.0.1", str(st.gateway.port)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    if st.client.stdout.readline().strip() != "ready":
        raise RuntimeError("the load client did not start")
    ctx.log(f"gateway on port {st.gateway.port}, "
            f"{len(st.sched['requests'])} requests scheduled "
            f"({st.sched['mode']} loop)")


def build(ctx, warm=True):
    st = build_engine(ctx, warm)
    start_client(ctx, st, ctx.traffic, ctx.seconds)
    return st


def setup(ctx):
    return build(ctx)


# ------------------------------------------------------------------ window

def window(ctx, st, seconds):
    """From "go" to the close.  The engine works in the gateway's threads
    and the client in its own process; this thread only waits."""
    st.stats_at_go = st.engine.stats()
    st.t_go = time.monotonic()
    st.client.stdin.write("go\n")
    st.client.stdin.flush()
    with ctx.span("serve.window_wait"):
        time.sleep(max(0.0, seconds - (time.monotonic() - st.t_go)))
    st.stats_at_close = st.engine.stats()
    return {"seconds": time.monotonic() - st.t_go, "requests": []}


def drain(ctx, st):
    """After the close: wait for every answer (the client gives each
    `drain_s`), then read its records and the engine's counters."""
    limit = st.sched["drain_s"] + 30.0
    try:
        st.client.wait(timeout=limit)
    except subprocess.TimeoutExpired:
        st.client.kill()
        st.client.wait()
        raise RuntimeError("the load client did not end") from None
    with open(st.out_path) as f:
        doc = json.load(f)
    os.remove(st.out_path)
    os.remove(st.sched_path)
    # the client's "go" clock against this process's (one system clock)
    skew = doc["t0_monotonic"] - st.t_go
    r = ctx.records
    close = r["seconds"]
    recs = doc["records"]
    for rec in recs:
        for key in ("sent_s", "end_s", "due_s"):
            if rec[key] is not None:
                rec[key] += skew
        rec["token_s"] = [t + skew for t in rec["token_s"]]
        rec["ok"] = bool(rec["done"] and rec["error"] is None
                         and rec["status"] == 200
                         and len(rec["token_ids"]) == rec["max_tokens"])
        in_window = sum(1 for t in rec["token_s"] if t <= close)
        rec["generated"] = in_window        # work done inside the window
        rec["cached"] = 0
    r["requests"] = recs
    r["late_s"] = [rec["sent_s"] - rec["due_s"] for rec in recs
                   if rec["sent_s"] is not None and rec["due_s"] is not None]
    a, b = st.stats_at_go, st.stats_at_close
    r["engine"] = {k: b[k] - a[k] for k in (
        "decode_steps", "decode_calls", "prefill_calls", "prefill_requests",
        "tokens_generated", "decode_host_syncs", "prefix_hit_tokens",
        "preemptions")}
    after = engine_programs(st.engine)
    r["programs"] = {**st.programs, "after_window": after}
    warmed = st.programs.get("warmed", {}).get("count")
    r["programs_compiled_in_window"] = (after["count"] - warmed
                                        if warmed is not None else None)
    ctx.log(f"programs: plan {st.programs['plan']}, after warm-up {warmed}, "
            f"after the window {after['count']}")
    r["queue_s"] = queue_waits(st.engine)
    # requests waiting for a lane and lanes live at the window's two ends:
    # above its knee a cell closes on a queue, under it on none
    r["queue"] = {end: {k: stats[k] for k in ("queue_depth", "active_slots")}
                  for end, stats in (("at_go", a), ("at_close", b))}
    n_ok = sum(1 for rec in recs if rec["ok"])
    ends = [rec["end_s"] for rec in recs if rec["end_s"] is not None]
    ctx.log(f"{len(recs)} requests sent, {n_ok} complete; engine in window "
            f"{r['engine']}; queue and live lanes {r['queue']}; last answer "
            f"{max(ends, default=close) - close:.1f} s after the close")


def queue_waits(engine):
    """Submit -> first admission of every flight record the engine kept."""
    doc = engine.recorder.to_json()
    waits = []
    for fl in doc["recent"] + doc["live"]:
        for ev in fl["events"]:
            if ev.get("kind") == "prefill":
                waits.append(float(ev.get("t", 0.0)))
                break
    return waits


def end_to_end(ctx, st):
    r = ctx.records
    recs = r["requests"]
    worst = r["seconds"] + st.sched["drain_s"]
    ttft, gaps = [], []
    for rec in recs:
        if rec["token_s"] and rec["due_s"] is not None:
            ttft.append(rec["token_s"][0] - rec["due_s"])
        else:
            ttft.append(worst)              # failed or shed: the worst
        ts = rec["token_s"]
        gaps.extend(b - a for a, b in zip(ts, ts[1:]))
    close = r["seconds"]
    done_tokens = sum(rec["prompt_len"] + len(rec["token_ids"])
                      for rec in recs if rec["ok"] and rec["end_s"] <= close)
    # every token the engine got through inside the window: the prompt of a
    # request whose first token came before the close, and each token
    # streamed before it
    processed = sum((rec["prompt_len"] if rec["generated"] else 0)
                    + rec["generated"] for rec in recs)
    out = {}
    if ttft:
        for name, v in (("ttft_p95_ms", percentile(ttft, 95)),
                        ("ttft_p50_ms", percentile(ttft, 50)),
                        ("ttft_mean_ms", sum(ttft) / len(ttft))):
            out[name] = {"value": v * 1e3, "unit": "ms"}
    if gaps:
        for name, v in (("gap_p99_ms", percentile(gaps, 99)),
                        ("gap_p50_ms", percentile(gaps, 50)),
                        ("gap_mean_ms", sum(gaps) / len(gaps))):
            out[name] = {"value": v * 1e3, "unit": "ms"}
    out["serve_tokens_per_s"] = {"value": processed / close,
                                 "unit": "tokens/s"}
    out["completed_tokens_per_s"] = {"value": done_tokens / close,
                                     "unit": "tokens/s"}
    out["gen_tokens_per_s"] = {
        "value": sum(rec["generated"] for rec in recs) / close,
        "unit": "tokens/s"}
    ctx.log("every statistic the driver computes (BENCHMARK.json says "
            "which are metrics): " + json.dumps(
                {k: v["value"] for k, v in out.items()}))
    r["ttft_s"], r["n_gaps"], r["stats"] = ttft, len(gaps), out
    return out


def counts(ctx):
    recs = ctx.records["requests"]
    return len(recs), sum(1 for rec in recs if not rec["ok"])


# ------------------------------------------------------------------- check

def check_sample(ctx, recs):
    """Finished greedy requests to compare: the longest, and others drawn
    from the seed, `check_requests` in all."""
    greedy = [r for r in recs if r["greedy"] and r["ok"]]
    if not greedy:
        return []
    k = int(ctx.traffic.get("check_requests", 4))
    longest = max(greedy, key=lambda r: r["prompt_len"] + len(r["token_ids"]))
    rest = [r for r in greedy if r is not longest]
    rng = np.random.default_rng([int(ctx.seed), 19])
    picked = [rest[i] for i in rng.permutation(len(rest))[:k - 1]]
    return [longest] + picked


def reference_gaps(ctx, sample, prompts, mode="f32"):
    """Reference logits at every served position of the sample; returns
    (logits [n, vocab] as numpy, served tokens, request index a row)."""
    import jax.numpy as jnp

    from chipbench import weights as W
    from chipbench.reference import dense_gqa as ref

    cfg = ctx.cfg
    pad = int(ctx.traffic.get("check_pad", 128))
    longest = max(len(p) + len(r["token_ids"]) for p, r in zip(prompts, sample))
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
    """The widest gap by which a served greedy token's reference logit lies
    below the reference's best, over the sample; and every request due in
    the window answered in full."""
    from chipbench import compare

    recs = ctx.records["requests"]
    by_index = {q["index"]: q["body"]["prompt"] for q in st.sched["requests"]}
    sample = check_sample(ctx, recs)
    st.free()
    out = {}
    if sample:
        t = time.perf_counter()
        prompts = [by_index[r["index"]] for r in sample]
        logits, tokens, _ = reference_gaps(ctx, sample, prompts)
        gap, where = compare.widest_logit_gap(logits, tokens)
        ctx.log(f"reference over {len(sample)} requests, {len(tokens)} "
                f"served tokens: {time.perf_counter() - t:.1f} s")
        out["logit_gap"] = {"value": gap, "limit": ctx.limits["logit_gap"],
                            "tokens": len(tokens)}
    else:
        out["logit_gap"] = {"value": None, "limit": ctx.limits["logit_gap"],
                            "tokens": 0}
    unanswered = sum(1 for r in recs if not r["ok"])
    out["unanswered"] = {"value": float(unanswered), "limit": 0.0}
    return out
