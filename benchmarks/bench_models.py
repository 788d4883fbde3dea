#!/usr/bin/env python
"""Benchmark the legacy model-family configs on one TPU chip.

Each benchmark compiles the full train step (fwd+bwd+optimizer) as one XLA
program via paddle.jit.TrainStep and reports best-of-3 windows. The
flagship GPT/LLaMA config is benchmarked by the repo-root bench.py. Run:
python benchmarks/bench_models.py [resnet50|resnet50_f32|bert|unet|all]
("all" runs the bf16 resnet50 variant; resnet50_f32 reproduces the f32 row)
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _measure(step_fn, sync_out, units_per_step, steps=8, windows=3):
    step_fn()  # compile
    sync_out(step_fn())  # drain warmup before the first timed window
    best = None
    for _ in range(windows):
        t0 = time.time()
        for _ in range(steps):
            out = step_fn()
        sync_out(out)
        dt = time.time() - t0
        best = dt if best is None else min(best, dt)
    return units_per_step * steps / best


def _measure_scan(step, batches, units_per_dispatch, scan_k):
    """Measure a K-steps-per-dispatch run (TrainStep.many): same per-step
    math as __call__, K× fewer host round-trips. Syncing on the summed
    loss vector drains the whole pack."""
    return _measure(lambda: step.many(batches),
                    lambda o: float(o.numpy().sum()), units_per_dispatch,
                    steps=max(2, 8 // scan_k))


def _nominal_peak_flops():
    """Published bf16 peak of the chip this process runs on (FLOP/s),
    from the one table keyed by device kind; an accelerator without a
    row raises, and the CPU has none (None)."""
    import jax

    from paddle_tpu.observability.peaks import chip_peaks

    row = chip_peaks(jax.devices()[0].device_kind)
    return row.bf16_flops if row else None


def _ceiling_tflops():
    """Measured practical matmul ceiling, right now: a chain of 8192^3
    bf16 matmuls in one program, so utilization is also stated against
    what a bare matmul reaches on this chip today.  Needs the chip, so
    only the per-bench child processes call it, never the "all" parent."""
    import jax
    import jax.numpy as jnp

    if jax.default_backend() != "tpu":
        return None
    n, chain = 8192, 16

    @jax.jit
    def f(a, b):
        def body(c, _):
            return jnp.tanh(c @ b), ()
        out, _ = jax.lax.scan(body, a, None, length=chain)
        return out

    a = jnp.ones((n, n), jnp.bfloat16)
    b = jnp.ones((n, n), jnp.bfloat16)
    f(a, b).block_until_ready()
    best = None
    for _ in range(3):
        t0 = time.time()
        f(a, b).block_until_ready()
        dt = time.time() - t0
        best = dt if best is None else min(best, dt)
    return 2 * n ** 3 * chain / best / 1e12


def _flash_flops(b, heads, sq, skv, d, causal=False, remat=False):
    """Hand-counted FLOPs of one Pallas flash-attention call, fwd + bwd
    (VERDICT r3 weak #3: XLA's cost_analysis cannot see inside
    pallas_call, so flash-heavy models undercount utilization). fwd =
    QK^T + PV = 4·b·h·sq·skv·d (halved when causal block-skip applies);
    bwd ≈ 2.5× fwd (score recompute + dq + dk/dv kernels); remat runs
    the fwd once more inside the backward."""
    f = 4.0 * b * heads * sq * skv * d * (0.5 if causal else 1.0)
    return f * (3.5 + (1.0 if remat else 0.0))


def _utilization(result, step, batch, units_per_sec, units_per_step,
                 pallas_flops=0.0):
    """Attach the analytic utilization block: FLOPs/step from XLA's cost
    analysis of the exact compiled program PLUS the hand-counted Pallas
    kernel FLOPs (cost_analysis is blind inside pallas_call), achieved
    TFLOP/s, and % of both the chip's published bf16 peak and the
    live-measured matmul ceiling (SURVEY §6: MFU is the north-star for
    every family)."""
    try:
        flops_xla = float(step.cost_analysis(*batch)["flops"])
    except Exception as e:  # cost analysis unsupported on this backend
        result["utilization_error"] = f"{type(e).__name__}: {e}"[:120]
        return result
    flops_per_step = flops_xla + pallas_flops
    tflops = units_per_sec / units_per_step * flops_per_step / 1e12
    result["flops_per_step"] = flops_per_step
    if pallas_flops:
        result["pallas_flops_per_step_est"] = round(pallas_flops)
    result["achieved_tflops"] = round(tflops, 1)
    peak = _nominal_peak_flops()
    if peak:
        result["pct_nominal_peak"] = round(100 * tflops * 1e12 / peak, 1)
    ceiling = _ceiling_tflops()
    if ceiling:
        result["ceiling_tflops_now"] = round(ceiling, 1)
        result["pct_practical_ceiling"] = round(100 * tflops / ceiling, 1)
    return result


def bench_resnet50(dtype="bfloat16", B=64, scan_k=0):
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.vision.models import resnet50

    paddle.seed(0)
    model = resnet50(num_classes=1000)
    if dtype == "bfloat16":
        model.to(dtype="bfloat16")
    opt = paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                    parameters=model.parameters())

    def loss_fn(net, x, y):
        logits = net(x)
        if dtype == "bfloat16":
            logits = paddle.cast(logits, "float32")
        return nn.functional.cross_entropy(logits, y)

    step = paddle.jit.TrainStep(model, loss_fn, opt)
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(B, 3, 224, 224).astype(np.float32))
    if dtype == "bfloat16":
        x = paddle.cast(x, "bfloat16")
    y = paddle.to_tensor(rng.randint(0, 1000, (B,)).astype(np.int64))
    if scan_k:
        # isolates per-dispatch host latency from device throughput
        ips = _measure_scan(step, [(x, y)] * scan_k, B * scan_k, scan_k)
    else:
        ips = _measure(lambda: step(x, y), lambda o: float(o), B)
    tag = "bf16" if dtype == "bfloat16" else "f32"
    scan_tag = f", scan{scan_k}" if scan_k else ""
    res = {"metric":
           f"images/sec ResNet-50 {tag} train (b{B}, 224px{scan_tag})",
           "value": round(ips, 1), "unit": "images/s"}
    return _utilization(res, step, (x, y), ips, B)


def bench_bert(B=32, scan_k=0, S=128):
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.models import BertConfig, BertForMaskedLM

    paddle.seed(0)
    cfg = BertConfig(vocab_size=30522, hidden_size=768,
                     num_hidden_layers=12, num_attention_heads=12,
                     intermediate_size=3072, max_position_embeddings=512)
    model = BertForMaskedLM(cfg)
    model.to(dtype="bfloat16")
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 multi_precision=True)
    S = int(S)

    def loss_fn(net, ids, labels):
        out = net(ids, labels=labels)
        return out[0] if isinstance(out, tuple) else out

    step = paddle.jit.TrainStep(model, loss_fn, opt)
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, 30522, (B, S)).astype(np.int32))
    if scan_k:
        sps = _measure_scan(step, [(ids, ids)] * scan_k, B * scan_k,
                            scan_k)
    else:
        sps = _measure(lambda: step(ids, ids), lambda o: float(o), B)
    scan_tag = f", scan{scan_k}" if scan_k else ""
    res = {"metric":
           f"sequences/sec BERT-base MLM bf16 train (b{B}xs{S}{scan_tag})",
           "value": round(sps, 1), "unit": "sequences/s"}
    pallas = 12 * _flash_flops(B, 12, S, S, 64)   # 12 bidirectional layers
    return _utilization(res, step, (ids, ids), sps, B, pallas_flops=pallas)


def bench_unet(B=4, scan_k=0):
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.models import UNetConfig, UNet2DConditionModel

    paddle.seed(0)
    cfg = UNetConfig()  # SD-style defaults from models/unet.py
    model = UNet2DConditionModel(cfg)
    model.to(dtype="bfloat16")
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 multi_precision=True)
    def loss_fn(net, x, t, ctx, target):
        pred = net(x, t, ctx)
        return nn.functional.mse_loss(pred, target)

    step = paddle.jit.TrainStep(model, loss_fn, opt)
    rng = np.random.RandomState(0)
    lat = paddle.cast(paddle.to_tensor(
        rng.randn(B, cfg.in_channels, 32, 32).astype(np.float32)), "bfloat16")
    t = paddle.to_tensor(rng.randint(0, 1000, (B,)).astype(np.int32))
    ctx = paddle.cast(paddle.to_tensor(
        rng.randn(B, 77, cfg.cross_attention_dim).astype(np.float32)),
        "bfloat16")
    if scan_k:
        its = _measure_scan(step, [(lat, t, ctx, lat)] * scan_k, scan_k,
                            scan_k)
    else:
        its = _measure(lambda: step(lat, t, ctx, lat), lambda o: float(o), 1)
    scan_tag = f", scan{scan_k}" if scan_k else ""
    res = {"metric":
           f"iters/sec SD-UNet bf16 train (b{B}, 32x32 latents{scan_tag})",
           "value": round(its, 2), "unit": "iters/s"}
    return _utilization(res, step, (lat, t, ctx, lat), its, 1,
                        pallas_flops=_unet_attn_flops(cfg, B))


def _unet_attn_flops(cfg, B):
    """Per-step attention FLOPs of the SD-UNet's transformer blocks (self
    + cross per block), from the same topology the model builds: attn on
    down levels 0..n-2, the mid block, and up levels 1..n-1; spatial res
    halves after each non-final down level and doubles after each
    non-final up level (32x32 latents)."""
    heads = cfg.attention_head_dim
    chs = cfg.block_out_channels

    def pair(dim, res):
        s = res * res
        d = dim // heads
        if s < 128:
            # short rows take the XLA sdpa fallback (attention.py
            # _use_pallas: q seq >= 128) — cost_analysis already counts
            # those FLOPs; adding them here would double-count
            return 0.0
        return (_flash_flops(B, heads, s, s, d)          # self
                + _flash_flops(B, heads, s, 77, d))      # cross (ctx=77)

    total, res = 0.0, 32
    for i, c in enumerate(chs):
        if i < len(chs) - 1:
            total += cfg.layers_per_block * pair(c, res)
            res //= 2
    total += pair(chs[-1], res)                          # mid
    for i, c in enumerate(reversed(chs)):
        if i > 0:
            total += (cfg.layers_per_block + 1) * pair(c, res)
        if i < len(chs) - 1:
            res *= 2
    return total


def bench_llama():
    """LLaMA-family proxy for the BASELINE.json 13B stage-3+recompute config:
    the largest GQA preset that fits one 16 GB v5e chip (~0.9B params) with
    the exact feature set the 13B run would use — Pallas flash attention with
    native GQA, full-layer recompute (the single-chip analog of stage-3's
    free-the-activations strategy), fused chunked vocab CE, bf16 params with
    f32 optimizer moments."""
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                      intermediate_size=5632, num_hidden_layers=16,
                      num_attention_heads=16, num_key_value_heads=4,
                      max_position_embeddings=2048, use_recompute=True,
                      fused_lm_loss=True)
    model = LlamaForCausalLM(cfg)
    model.to(dtype="bfloat16")
    n_params = sum(p.size for p in model.parameters())
    # no f32 master copy: moments are f32 already, and the proxy must leave
    # HBM room for activations (the 13B target offloads state instead)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    B, S = 8, 2048

    def loss_fn(net, ids, labels):
        loss, _ = net(ids, labels=labels)
        return loss

    step = paddle.jit.TrainStep(model, loss_fn, opt)
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, 32000, (B, S)).astype(np.int32))
    tps = _measure(lambda: step(ids, ids), lambda o: float(o), B * S)
    res = {"metric": (f"tokens/sec/chip LLaMA-{n_params/1e6:.0f}M GQA "
                      f"bf16+recompute train (b{B}xs{S})"),
           "value": round(tps, 1), "unit": "tokens/s"}
    peak = _nominal_peak_flops()
    if peak:
        res["mfu_6N"] = round(tps * 6 * n_params / peak, 4)
    pallas = 16 * _flash_flops(B, 16, S, S, 128, causal=True, remat=True)
    return _utilization(res, step, (ids, ids), tps, B * S,
                        pallas_flops=pallas)


def bench_gpt_longseq(seq=8192, batch=2):
    """Long-context single-chip row: the flagship GPT at s4096/s8192 with
    full recompute — Pallas flash keeps attention memory linear in seq
    (dense softmax OOMs at s4096); tok/s decline vs s1024 tracks
    attention's quadratic FLOPs share plus the remat re-forward."""
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=32000, hidden_size=1536,
                    intermediate_size=4096, num_hidden_layers=12,
                    num_attention_heads=12, max_position_embeddings=seq,
                    fused_lm_loss=True, use_recompute=True)
    model = GPTForCausalLM(cfg)
    model.to(dtype="bfloat16")
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 multi_precision=True)

    def loss_fn(net, ids, labels):
        loss, _ = net(ids, labels=labels)
        return loss

    step = paddle.jit.TrainStep(model, loss_fn, opt)
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(
        rng.randint(0, 32000, (batch, seq)).astype(np.int32))
    tps = _measure(lambda: step(ids, ids), lambda o: float(o), batch * seq,
                   steps=6)
    res = {"metric": (f"tokens/sec/chip GPT-438M bf16+recompute long-seq "
                      f"train (b{batch}xs{seq})"),
           "value": round(tps, 1), "unit": "tokens/s"}
    pallas = 12 * _flash_flops(batch, 12, seq, seq, 128, causal=True,
                               remat=True)
    return _utilization(res, step, (ids, ids), tps, batch * seq,
                        pallas_flops=pallas)


def bench_decode(B=8, L=16, dim=2048, n_head=16, prefill=512, steps=256,
                 max_seq=1024):
    """Generation throughput through the fused serving stack (ref: the
    fused_multi_transformer CUDA generation path): bf16 prefill writes
    the KV caches, then ONE compiled program scans `steps` single-token
    decodes (inline cache write + attend at the traced time_step).
    Decode is HBM-bound physics — every step re-reads all weights plus
    the live cache — so the report includes the analytic HBM roofline
    (v5e ~819 GB/s) and the fraction achieved."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    import paddle_tpu.incubate.nn.functional as IF

    paddle.seed(0)
    rng = np.random.RandomState(0)
    hd = dim // n_head
    ffn = 4 * dim

    def mk(*sh):
        return paddle.cast(paddle.to_tensor(
            (rng.randn(*sh) * 0.02).astype(np.float32)), "bfloat16")

    P = dict(
        ln_scales=[mk(dim) + 1.0 for _ in range(L)],
        ln_biases=[mk(dim) for _ in range(L)],
        qkv_weights=[mk(3, n_head, hd, dim) for _ in range(L)],
        qkv_biases=[mk(3 * n_head * hd) for _ in range(L)],
        linear_weights=[mk(dim, dim) for _ in range(L)],
        linear_biases=[mk(dim) for _ in range(L)],
        ffn_ln_scales=[mk(dim) + 1.0 for _ in range(L)],
        ffn_ln_biases=[mk(dim) for _ in range(L)],
        ffn1_weights=[mk(dim, ffn) for _ in range(L)],
        ffn1_biases=[mk(ffn) for _ in range(L)],
        ffn2_weights=[mk(ffn, dim) for _ in range(L)],
        ffn2_biases=[mk(dim) for _ in range(L)],
    )
    x = paddle.cast(paddle.to_tensor(
        rng.randn(B, prefill, dim).astype(np.float32) * 0.3), "bfloat16")
    caches = [paddle.cast(paddle.to_tensor(
        np.zeros((2, B, n_head, max_seq, hd), np.float32)), "bfloat16")
        for _ in range(L)]

    # prefill as ONE compiled program (eager would pay a host dispatch
    # per op)
    def prefill_fn(x_arr, cache_arrs):
        with paddle.no_grad():
            o, nc = IF.fused_multi_transformer(
                paddle.Tensor(x_arr),
                cache_kvs=[paddle.Tensor(a) for a in cache_arrs], **P)
        return o._data, [c._data for c in nc]

    out_a, cache_arrays = jax.jit(prefill_fn, donate_argnums=(1,))(
        x._data, [c._data for c in caches])
    x0 = out_a[:, -1:, :]

    def decode_pack(cache_arrs, x_arr):
        def body(carry, i):
            arrs, xa = carry
            with paddle.no_grad():
                o, ncaches = IF.fused_multi_transformer(
                    paddle.Tensor(xa),
                    cache_kvs=[paddle.Tensor(a) for a in arrs],
                    time_step=paddle.Tensor(prefill + i), **P)
            return ([c._data for c in ncaches], o._data), ()

        (arrs, xa), _ = jax.lax.scan(
            body, (list(cache_arrs), x_arr),
            jnp.arange(steps, dtype=jnp.int32))
        return arrs, xa

    jitted = jax.jit(decode_pack, donate_argnums=(0,))
    arrs, xa = jitted(cache_arrays, x0)       # compile + warm
    jax.block_until_ready(xa)
    best = None
    for _ in range(3):
        t0 = time.time()
        arrs, xa = jitted(arrs, x0)
        jax.block_until_ready(xa)
        dt = time.time() - t0
        best = dt if best is None else min(best, dt)
    toks = B * steps / best
    # analytic HBM roofline: per decode step, all weights stream once and
    # the valid cache prefix is read (k+v) once
    weight_bytes = sum(
        int(np.prod(t.shape)) * 2 for lst in P.values() for t in lst)
    avg_t = prefill + steps / 2
    cache_bytes = 2 * L * B * n_head * avg_t * hd * 2
    hbm_bw = 819e9                             # v5e nominal
    roof_step = (weight_bytes + cache_bytes) / hbm_bw
    roof_toks = B / roof_step
    return {"metric": (f"decode tokens/s fused_multi_transformer bf16 "
                       f"(L{L} dim{dim} b{B}, prefill{prefill}+"
                       f"{steps} steps)"),
            "value": round(toks, 1), "unit": "tokens/s",
            "ms_per_step": round(1e3 * best / steps, 3),
            "hbm_roofline_tokens_s": round(roof_toks, 1),
            "pct_hbm_roofline": round(100 * toks / roof_toks, 1),
            "weight_gb_per_step": round(weight_bytes / 1e9, 2),
            "cache_gb_per_step_avg": round(cache_bytes / 1e9, 2)}


def bench_ernie_hybrid():
    """ERNIE-style HybridParallel composition (BASELINE.json north-star
    family): tp2 x pp2 x dp2 on an 8-device mesh. On a single-chip box this
    runs on the virtual CPU mesh — correctness evidence (losses decrease
    under the full composition), perf N/A off-chip; on a real v5e/v5p pod
    slice the same code path gives the perf number."""
    import subprocess

    code = r"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import __graft_entry__ as g
g.dryrun_multichip(8)
print("HYBRID_OK")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    t0 = time.time()
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=900, env=env,
                       cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    ok = "HYBRID_OK" in r.stdout
    return {"metric": "ernie-hybrid tp*pp*dp composition (8-dev virtual mesh)",
            "value": 1 if ok else 0, "unit": "ok",
            "wall_s": round(time.time() - t0, 1),
            "detail": [l for l in r.stdout.splitlines() if "dryrun" in l][:6]
                      if ok else r.stderr[-300:]}


MULTICHIP_SCHEMA_VERSION = 1


def _git_sha():
    import subprocess

    try:
        r = subprocess.run(["git", "rev-parse", "--short=12", "HEAD"],
                           capture_output=True, text=True, timeout=10,
                           cwd=os.path.dirname(os.path.abspath(__file__)))
        sha = r.stdout.strip()
        return sha if r.returncode == 0 and sha else "unknown"
    except (OSError, ValueError):
        return "unknown"


def bench_multichip_comms(out=None):
    """Collective-comms census + step timing of the explicit multichip
    configs (benchmarks/multichip_comms.py) on 8 virtual CPU devices.

    Rows carry the jaxpr walker's per-config collective counts by op
    (deterministic — gated EXACT by check-bench), the modeled ring
    wire bytes per step, and the comms-roofline share of the measured
    step.  Written with the DECODE_BENCH provenance discipline:
    ``out=None`` merge-writes the committed MULTICHIP_BENCH.json
    (run_id increments over the file's lifetime); ``out=FILE`` writes a
    fresh document with run_id 0 for ``check-bench --bench-file``."""
    import subprocess

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    child = os.path.join(root, "benchmarks", "multichip_comms.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    t0 = time.time()
    r = subprocess.run([sys.executable, child], capture_output=True,
                       text=True, timeout=1800, env=env, cwd=root)
    rows, errors = [], []
    for line in r.stdout.splitlines():
        if not line.startswith("{"):
            continue
        row = json.loads(line)
        (errors if "error" in row else rows).append(row)
    ok = "MULTICHIP_COMMS_OK" in r.stdout and not errors
    sha = _git_sha()

    if out is not None:
        for row in rows:
            row["schema_version"] = MULTICHIP_SCHEMA_VERSION
            row["git_sha"] = sha
            row["run_id"] = 0
        with open(out, "w") as f:
            json.dump({"backend": "cpu8", "results": rows}, f, indent=1)
    elif rows:
        path = os.path.join(root, "MULTICHIP_BENCH.json")
        kept, run_id = [], 1
        if os.path.exists(path):
            try:
                with open(path) as f:
                    prev = json.load(f)
                prev_rows = prev.get("results", [])
                new_metrics = {row["metric"] for row in rows}
                latest = {}
                for row in prev_rows:
                    if row.get("metric", "") not in new_metrics:
                        latest[row.get("metric", "")] = row
                kept = list(latest.values())
                run_id = 1 + max((int(row.get("run_id", 0))
                                  for row in prev_rows), default=0)
            except (ValueError, OSError):
                kept, run_id = [], 1
        for row in rows:
            row["schema_version"] = MULTICHIP_SCHEMA_VERSION
            row["git_sha"] = sha
            row["run_id"] = run_id
        with open(path, "w") as f:
            json.dump({"backend": "cpu8", "results": kept + rows},
                      f, indent=1)
    for row in rows:
        print(json.dumps(row))
    return {"metric": "multichip comms suite (8-dev virtual mesh)",
            "value": len(rows), "unit": "configs",
            "ok": ok, "wall_s": round(time.time() - t0, 1),
            **({"errors": [e.get("error", "")[:120] for e in errors]}
               if errors else {})}


def main():
    argv = sys.argv[1:]
    out = None
    if "--out" in argv:
        i = argv.index("--out")
        out = argv[i + 1]
        argv = argv[:i] + argv[i + 2:]
    which = argv[0] if argv else "all"
    benches = {"resnet50": bench_resnet50,
               "resnet50_f32": lambda: bench_resnet50(dtype="float32"),
               "bert": bench_bert,
               "unet": bench_unet,
               "unet_b16": lambda: bench_unet(B=16),
               "bert_b128": lambda: bench_bert(B=128),
               "resnet50_b256": lambda: bench_resnet50(B=256),
               "resnet50_scan8": lambda: bench_resnet50(scan_k=8),
               "bert_scan8": lambda: bench_bert(scan_k=8),
               "unet_scan8": lambda: bench_unet(scan_k=8),
               "decode": bench_decode,
               "gpt_s4096": lambda: bench_gpt_longseq(seq=4096, batch=4),
               "gpt_s8192": bench_gpt_longseq,
               "llama": bench_llama,
               "ernie_hybrid": bench_ernie_hybrid,
               "multichip_comms": lambda: bench_multichip_comms(out=out)}
    if which != "all" and which not in benches:
        print(f"unknown benchmark {which!r}; choose from "
              f"{sorted(benches)} or 'all'", file=sys.stderr)
        raise SystemExit(2)
    # "all" runs one variant per model family (bf16 resnet50); the f32
    # reproduction and throughput-optimal unet_b16 runs stay opt-in
    names = ([n for n in benches
              if n not in ("resnet50_f32", "unet_b16", "bert_b128",
                           "resnet50_b256", "resnet50_scan8", "bert_scan8",
                           "unet_scan8", "decode",
                           "gpt_s4096", "gpt_s8192", "multichip_comms")]
             if which == "all" else [which])
    if which == "all":
        # one fresh process per bench: HBM from a previous model (cached
        # executables, live donated buffers) must not shrink the next
        # model's budget — the llama proxy needs nearly the whole chip.
        # A chip belongs to one process at a time, so this parent must
        # never touch jax: it spawns, and only the children compile.
        import subprocess

        me = os.path.abspath(__file__)
        for n in names:
            try:
                r = subprocess.run([sys.executable, me, n],
                                   capture_output=True, text=True,
                                   timeout=1800)
            except subprocess.TimeoutExpired:
                print(json.dumps({"metric": n, "error": "timeout after 1800s"}))
                continue
            out = [l for l in r.stdout.splitlines() if l.startswith("{")]
            print(out[-1] if out else json.dumps(
                {"metric": n, "error": r.stderr[-300:]}))
        return
    from paddle_tpu.utils import compile_cache

    compile_cache.enable()
    for n in names:
        try:
            print(json.dumps(benches[n]()))
        except Exception as e:  # report, keep going
            print(json.dumps({"metric": n, "error": f"{type(e).__name__}: {e}"[:300]}))


if __name__ == "__main__":
    main()
