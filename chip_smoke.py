#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that both hot paths start on the chip.

Drives the trainer (`paddle.jit.TrainStep`) and the serving engine
(`create_llm_engine` -> `Engine` behind `Gateway`) once each, through the
entry points a user calls, at the full width of a preset the repo ships
(depth cut, weights random from a seed), checks what comes out by the
repo's own means, and checks every Pallas kernel of the two paths against
its XLA twin — compiled, never interpreted.

    python chip_smoke.py            one TPU chip, one process
    python chip_smoke.py --chips 4  only the two four-chip paths
                                    (tensor-parallel engine, dp2 x mp2
                                    train step) and what each is compared
                                    with

One JSON line per phase, then a last line that is exactly
`{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}`.
Without a TPU it exits non-zero and the last line says `"ok": false`:
there is no CPU branch and no toy-size branch.  Any phase that fails
makes the exit code non-zero.  The script is one process; the only
children it starts are two DataLoader workers that never touch jax.

The phase functions take their sizes as arguments so that
tests/test_chip_smoke.py can rehearse them at tiny widths on the CPU.
"""

import argparse
import contextlib
import dataclasses
import gc
import http.client
import json
import os
import sys
import threading
import time
import traceback

SEED = 0

# --- trainer: LLAMA2_7B widths, depth cut to what one 16 GB chip holds with
# bf16 parameters and AdamW(multi_precision=True) state (2 layers = 0.67 B
# parameters, ~9.3 GB of parameters + master weights + moments)
TRAIN_DEPTH = 2
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 4, 1024, 4      # 4096 tokens a step
TRAIN_LR = 5e-4
#: first-step loss of the kernel path against the plain XLA paths (XLA
#: attention, unfused CE), bf16, loss ~ ln(vocab) = 10.4
TRAIN_LOSS_TOL = 0.05

# --- server: LLAMA3_8B widths, depth cut to fit beside the pool below
SERVE_DEPTH = 4
SERVE_SLOTS, SERVE_MAX_SEQ = 8, 2048
#: every slot can grow to a full row (8 * 2048 / 16 = 1024 blocks) and as
#: much again is left to the prefix cache
SERVE_POOL_BLOCKS = 2048
#: one below 128, one of 256-512 (and a second in its bucket, so that one
#: prefill dispatch carries two lanes), one of 1024 or more.  Each compiled
#: serving program costs XLA seconds whatever the depth (7-12 in the
#: benchmark's serve cells), so every extra bucket is felt.
SERVE_PROMPT_LENS = (40, 300, 420, 1100)
SERVE_NEW_TOKENS = 32
#: paged cache + Pallas kernel against the dense model(ids) forward with
#: XLA attention: bf16 weights and activations, logits of magnitude ~4,
#: where one bf16 step is 0.03.  Also what counts as a near-tie when a
#: stream leaves generate()'s.
SERVE_LOGIT_TOL = 0.25

# --- kernels at the server's widths: [QH, KH, D, block]
PAGED_WIDTHS = (32, 8, 128, 16)
PAGED_WINDOWS = (1, 5, 256)        # decode, a K=4 verify window, a bucket
KERNEL_TOL = 2e-2                  # bf16 outputs of O(1) magnitude

# --- four chips
TP_PROMPT_LENS = (40, 300, 1100)
MESH_LOSS_TOL = 0.05


def _emit(row):
    print(json.dumps(row), flush=True)


class _Tokens:
    """A seeded stream of token rows; item i is (ids, next-token labels).
    Plain numpy, so the DataLoader's spawned workers build it without
    touching jax."""

    def __init__(self, n, seq, vocab, seed):
        self.n, self.seq, self.vocab, self.seed = n, seq, vocab, seed

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        import numpy as np

        row = np.random.RandomState(self.seed + i).randint(
            0, self.vocab, self.seq + 1).astype(np.int32)
        return row[:-1], row[1:]


class _CompileMeter:
    """Counts XLA backend compiles (cache look-ups included) and
    persistent-cache hits through jax.monitoring."""

    def __init__(self):
        import jax.monitoring as mon

        self.compiles, self.seconds, self.hits = 0, 0.0, 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, seconds, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += seconds

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def mark(self):
        return (self.compiles, self.seconds, self.hits)

    def since(self, mark):
        return {"xla_compiles": self.compiles - mark[0],
                "compile_seconds": round(self.seconds - mark[1], 2),
                "persistent_cache_hits": self.hits - mark[2]}


def _bytes_in_use(device):
    stats = device.memory_stats() or {}
    return stats.get("bytes_in_use")


@contextlib.contextmanager
def _xla_attention():
    """Steer the dense attention router to its XLA path from outside:
    the reference forward must use no Pallas kernel."""
    from paddle_tpu.nn.functional import attention

    real = attention._use_pallas
    attention._use_pallas = lambda *a, **k: False
    try:
        yield
    finally:
        attention._use_pallas = real


def _seeded_model(cls, cfg, dtype):
    import paddle_tpu as paddle

    paddle.seed(SEED)
    model = cls(cfg)
    if dtype != "float32":
        model.to(dtype=dtype)
    return model


# ------------------------------------------------------------------ trainer

def _loss_fn(net, ids, labels):
    loss, _ = net(ids, labels=labels)
    return loss


def _train(model, ids, labels, steps, lr):
    """`steps` TrainStep calls on one batch; returns (losses, step)."""
    import paddle_tpu as paddle

    opt = paddle.optimizer.AdamW(learning_rate=lr,
                                 parameters=model.parameters(),
                                 multi_precision=True)
    step = paddle.jit.TrainStep(model, _loss_fn, opt)
    return [float(step(ids, labels)) for _ in range(steps)], step


def trainer_phase(cfg, batch, seq, steps, lr, tol, dtype="bfloat16",
                  workers=2):
    import jax
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.io import DataLoader
    from paddle_tpu.models import GPTForCausalLM

    model = _seeded_model(GPTForCausalLM, cfg, dtype)
    n_params = sum(p.size for p in model.parameters())

    # the batch comes through spawned DataLoader workers: each re-imports
    # the package, and none may take the chip this process holds
    loader = DataLoader(_Tokens(batch, seq, cfg.vocab_size, SEED),
                        batch_size=batch, num_workers=workers,
                        use_process_workers=True)
    batches = iter(loader)
    ids, labels = next(batches)
    batches.close()                  # stops the workers

    # the same weights once through the plain XLA paths, before any update
    model.config.fused_lm_loss = False
    with _xla_attention(), paddle.no_grad():
        ref_loss = float(_loss_fn(model, ids, labels))
    model.config.fused_lm_loss = True

    losses, step = _train(model, ids, labels, steps, lr)

    # did the AUTO-layout path engage, and did the compiler choose anything
    # but the default layout for the state it was given?
    relaid = None
    if step.auto_layout and step._layout_owner is not None:
        _, formats, own = step._compiled_cache[step._layout_owner]
        relaid = sum(
            1 for i in own
            if tuple(formats[i].layout.major_to_minor)
            != tuple(range(len(formats[i].layout.major_to_minor))))
    checks = {
        "losses_finite": bool(np.all(np.isfinite(losses))),
        "loss_falls": losses[-1] < losses[0],
        "first_loss_vs_xla_paths": abs(losses[0] - ref_loss) <= tol,
    }
    return {
        "ok": all(checks.values()), "checks": checks,
        "model": "GPTForCausalLM @ LLAMA2_7B widths", "depth": len(
            model.model.layers),
        "hidden": cfg.hidden_size, "heads": cfg.num_attention_heads,
        "ffn": cfg.intermediate_size, "vocab": cfg.vocab_size,
        "params": int(n_params), "dtype": dtype,
        "tokens_per_step": batch * seq, "losses": losses,
        "xla_paths_first_loss": ref_loss,
        "first_loss_abs_diff": abs(losses[0] - ref_loss), "tolerance": tol,
        "attention_route": ("pallas flash"
                            if jax.default_backend() == "tpu" else "xla"),
        "norm_route": "xla (nn.RMSNorm -> F.rms_norm is the jnp path)",
        "loss_route": "fused chunked LM-head CE (lax.scan, XLA)",
        "auto_layout_engaged": bool(step.auto_layout
                                    and step._layout_owner is not None),
        "auto_layout_state_leaves_not_default": relaid,
        "dataloader": f"{workers} spawned process workers",
    }


# ------------------------------------------------------------------- server

def _requests(vocab, prompt_lens, new_tokens):
    """Seeded prompts of the given lengths; greedy and seeded-sampled
    requests alternate."""
    import numpy as np

    from paddle_tpu.serving import SamplingParams

    rng = np.random.RandomState(SEED)
    prompts = [rng.randint(0, vocab, n).tolist() for n in prompt_lens]
    samplings = [SamplingParams(max_new_tokens=new_tokens) if i % 2 == 0
                 else SamplingParams(max_new_tokens=new_tokens,
                                     temperature=0.8, top_k=50,
                                     seed=100 + i)
                 for i in range(len(prompts))]
    return prompts, samplings


def _stream_completion(port, body, timeout):
    """POST /v1/completions with stream=true; returns (token ids, finish
    reason, saw [DONE])."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", "/v1/completions", json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            raise RuntimeError(f"HTTP {resp.status}: {resp.read()[:300]}")
        toks, reason, done = [], None, False
        for raw in resp:
            line = raw.decode().strip()
            if not line.startswith("data: "):
                continue
            payload = line[6:]
            if payload == "[DONE]":
                done = True
                break
            choice = json.loads(payload)["choices"][0]
            toks += choice.get("token_ids", [])
            reason = choice.get("finish_reason") or reason
        return toks, reason, done
    finally:
        conn.close()


def _concurrent_pass(port, bodies, timeout):
    out = [None] * len(bodies)
    errors = []

    def one(i):
        try:
            out[i] = _stream_completion(port, bodies[i], timeout)
        except Exception as e:           # surfaced below, never dropped
            errors.append(f"request {i}: {type(e).__name__}: {e}")

    threads = [threading.Thread(target=one, args=(i,))
               for i in range(len(bodies))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout)
    if errors or any(o is None for o in out):
        raise RuntimeError("; ".join(errors) or "a stream never ended")
    return out


def _paged_logits(model, prompt, block_size):
    """Last-position logits of `prompt` through the paged cache and the
    router's own choice of attention (the Pallas kernel on a TPU)."""
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.serving.kv_cache import PagedKV

    cfg = model.config
    nb = -(-len(prompt) // block_size)
    dtype = next(iter(model.parameters()))._data.dtype
    shape = (nb + 1, block_size, cfg.kv_heads, cfg.head_dim)
    tables = jnp.arange(1, nb + 1, dtype=jnp.int32)[None]     # 0 = scratch
    views = [PagedKV(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
                     tables, jnp.zeros((1,), jnp.int32))
             for _ in model.model.layers]
    with paddle.no_grad():
        h, _ = model.model(paddle.to_tensor(np.asarray([prompt], np.int32)),
                           caches=views)
        return np.asarray(model._logits(h)._data[0, -1], np.float32)


def _dense_reference(model, max_len):
    """The reference the serving path is held to: the dense `model(ids)`
    forward with XLA attention — no paged cache, no Pallas kernel.
    Returns `logits(seq)`: the logits that choose the token after `seq`
    (one jitted program at `max_len`; causal, so the padding is inert)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor

    names, arrays = zip(*model.raw_state().items())

    @jax.jit
    def forward(arrays, ids, last):
        with paddle.no_grad(), model.use_state(dict(zip(names, arrays))):
            h = model.model(Tensor(ids))._data                  # [1, L, H]
            row = jax.lax.dynamic_slice_in_dim(h, last, 1, axis=1)
            return model._logits(Tensor(row))._data[0, 0].astype(
                jnp.float32)

    def logits(seq):
        ids = np.zeros((1, max_len), np.int32)
        ids[0, :len(seq)] = seq
        with _xla_attention():       # read while the program is traced
            return np.asarray(forward(arrays, ids, len(seq) - 1))

    return logits


def _selectable(ref_logits, token, sampling, tol):
    """Could `token` have been chosen under the reference logits, give or
    take `tol`?  Greedy: within tol of the maximum.  Top-k sampling:
    within tol of the k-th largest (any member of the set can be drawn)."""
    import numpy as np

    if sampling.temperature <= 0:
        floor = ref_logits.max()
    elif sampling.top_k > 0:
        floor = np.partition(ref_logits, -sampling.top_k)[-sampling.top_k]
    else:
        return True
    return bool(ref_logits[token] >= floor - tol)


def _judge_stream(got, expected, prompt, sampling, logits, tol):
    """A stream must equal `expected` token for token — or leave it at a
    position where the reference logits cannot tell the two tokens apart:
    different compiled programs round differently, and on random weights
    a choice between near-equal logits then falls the other way.  After
    such a fork the two streams are different texts and are not compared.
    Returns None for an exact match, else a finding with `benign`."""
    if got == expected:
        return None
    k = next((i for i, (a, b) in enumerate(zip(got, expected)) if a != b),
             None)
    if k is None or len(got) != len(expected):
        return {"first_diff": k, "benign": False,
                "why": f"lengths {len(got)} != {len(expected)}"}
    ref = logits(list(prompt) + list(expected[:k]))
    return {"first_diff": k, "got": got[k], "expected": expected[k],
            "ref_logit_got": float(ref[got[k]]),
            "ref_logit_expected": float(ref[expected[k]]),
            "ref_logit_max": float(ref.max()),
            "sampled": sampling.temperature > 0,
            "benign": (_selectable(ref, got[k], sampling, tol)
                       and _selectable(ref, expected[k], sampling, tol))}


def server_phase(cfg, num_slots, max_seq_len, pool_blocks, prompt_lens,
                 new_tokens, logit_tol, dtype="bfloat16", timeout=900.0):
    import jax
    import numpy as np

    from paddle_tpu.inference import create_llm_engine
    from paddle_tpu.models import LlamaForCausalLM
    from paddle_tpu.serving.gateway import Gateway, GatewayConfig

    model = _seeded_model(LlamaForCausalLM, cfg, dtype)
    model.eval()
    prompts, samplings = _requests(cfg.vocab_size, prompt_lens, new_tokens)
    bodies = []
    for p, sp in zip(prompts, samplings):
        body = {"model": "paddle-tpu", "prompt": p, "stream": True,
                "max_tokens": sp.max_new_tokens}
        if sp.temperature > 0:
            body.update(temperature=sp.temperature, top_k=sp.top_k,
                        seed=sp.seed)
        bodies.append(body)
    # only the sizes a deployment sets; every feature stays at its default
    sizes = dict(num_slots=num_slots, max_seq_len=max_seq_len,
                 kv_pool_blocks=pool_blocks)

    # what the streams are held to: in-process generate() on the same
    # weights.  It runs first and alone, so it is also the cold compile of
    # the programs; the gateway's engine then finds them in the cache.
    t0 = time.perf_counter()
    meter = _CompileMeter()
    ref = create_llm_engine(model, **sizes)
    expected = [[int(t) for t in out]
                for out in ref.generate(prompts, samplings)]
    ref_compiles = meter.since((0, 0.0, 0))
    ref_stats = ref.stats()
    pool = ref_stats["kv_pool"]
    block_size = pool["block_size"]
    pool_bytes = pool["capacity_blocks"] * pool["bytes_per_block"]
    ref.close()
    del ref
    gc.collect()
    ref_seconds = time.perf_counter() - t0

    dense = _dense_reference(model, max_seq_len)
    logit_diff = float(np.abs(_paged_logits(model, prompts[1], block_size)
                              - dense(prompts[1])).max())

    def judge(streams, requests):
        found = []
        for i, (toks, _, _) in zip(requests, streams):
            f = _judge_stream(toks, expected[i], prompts[i], samplings[i],
                              dense, logit_tol)
            if f is not None:
                found.append({"request": i, **f})
        return found

    eng = create_llm_engine(model, **sizes)
    gw_cfg = GatewayConfig(request_timeout_s=timeout,
                           watchdog_timeout_s=timeout)
    with Gateway([eng], gw_cfg) as gw:
        worker = gw.workers[0]

        def programs():
            s = worker.stats()
            return s["decode_compiles"] + s["prefill_compiles"]

        # all requests at once on a cold pool.  What a concurrent pass
        # compiles depends on arrival timing, so the repeat that must
        # compile nothing is the longest prompt sent alone, three times:
        # its schedule is fixed.  The first finds its prompt in the prefix
        # cache and prefills the uncached suffix; the second also finds
        # the first's own tail block (a copy-on-write match, a smaller
        # bucket); the third repeats the second.
        cold = _concurrent_pass(gw.port, bodies, timeout)
        counts = [programs()]
        alone = []
        for _ in range(3):
            alone.append(_stream_completion(gw.port, bodies[-1], timeout))
            counts.append(programs())
        worker.drain()
        stats = worker.stats()

    everyone, last = range(len(prompts)), [len(prompts) - 1]
    passes = {"concurrent": cold, "alone_1": alone[:1],
              "alone_2": alone[1:2], "alone_3": alone[2:]}
    findings = {name: judge(streams,
                            everyone if len(streams) > 1 else last)
                for name, streams in passes.items()}
    every = [s for streams in passes.values() for s in streams]
    checks = {
        "streams_end_in_done_with_reason": all(
            done and reason is not None for _, reason, done in every),
        "streams_equal_generate_or_fork_on_a_near_tie": all(
            f["benign"] for fs in findings.values() for f in fs),
        "repeat_is_bitwise": alone[1][0] == alone[2][0],
        "logits_vs_dense_xla_forward": logit_diff <= logit_tol,
        "no_blocks_in_use_after_drain": stats["kv_blocks_in_use"] == 0,
        "no_new_programs_on_repeat": counts[3] == counts[2],
        "prefix_cache_hit_on_repeat": stats["prefix_hit_tokens"] > 0,
    }
    n_streams = len(every)
    n_exact = n_streams - sum(len(fs) for fs in findings.values())
    return {
        "ok": all(checks.values()), "checks": checks,
        "model": "LlamaForCausalLM @ LLAMA3_8B widths",
        "depth": len(model.model.layers), "hidden": cfg.hidden_size,
        "heads": cfg.num_attention_heads, "kv_heads": cfg.kv_heads,
        "ffn": cfg.intermediate_size, "vocab": cfg.vocab_size,
        "dtype": dtype, "num_slots": num_slots, "max_seq_len": max_seq_len,
        "pool_blocks": pool_blocks, "block_size": block_size,
        "pool_bytes": int(pool_bytes),
        "prompt_lens": list(prompt_lens), "new_tokens": new_tokens,
        "streams": n_streams, "streams_bitwise_equal_generate": n_exact,
        "forks": findings,
        "finish_reasons": sorted({r for _, r, _ in every}),
        "logits_max_abs_diff": logit_diff, "logit_tolerance": logit_tol,
        "paged_attention_route": (
            "pallas kernel, every window (static rule: backend is tpu)"
            if jax.default_backend() == "tpu" and not os.environ.get(
                "PADDLE_TPU_PAGED_ATTN")
            else f"xla scan (backend {jax.default_backend()}, override "
                 f"{os.environ.get('PADDLE_TPU_PAGED_ATTN')})"),
        "programs_after_concurrent_alone_1_2_3": counts,
        "generate_engine_programs": (ref_stats["decode_compiles"]
                                     + ref_stats["prefill_compiles"]),
        # one XLA compile for each program (plus a few one-op programs):
        # the program card reads the executable the call itself built
        "generate_engine_xla_compiles": ref_compiles["xla_compiles"],
        "generate_engine_compile_seconds": ref_compiles["compile_seconds"],
        "generate_engine_seconds": round(ref_seconds, 2),
        "prefill_buckets": stats["prefill"]["buckets"],
        "decode_buckets": [list(b) for b in stats["decode_buckets"]],
        "prefix_hit_tokens": stats["prefix_hit_tokens"],
        "kv_blocks_in_use_after_drain": stats["kv_blocks_in_use"],
    }


# ------------------------------------------------------------------ kernels

def _paged_case(rng, lanes, s, nb, widths, quantized):
    import jax.numpy as jnp

    qh, kh, d, bs = widths
    n_blocks = lanes * nb + 1
    q = jnp.asarray(rng.randn(lanes, s, qh, d), jnp.bfloat16)
    tables = jnp.asarray(
        1 + rng.permutation(lanes * nb).reshape(lanes, nb), jnp.int32)
    # each lane's window starts somewhere in its row and ends inside it
    pos = jnp.asarray(rng.randint(0, nb * bs - s + 1, lanes), jnp.int32)
    if quantized:
        k = jnp.asarray(rng.randint(-127, 128, (n_blocks, bs, kh, d)),
                        jnp.int8)
        v = jnp.asarray(rng.randint(-127, 128, (n_blocks, bs, kh, d)),
                        jnp.int8)
        ks = jnp.asarray(rng.uniform(0.002, 0.02, (n_blocks, bs)),
                         jnp.float32)
        vs = jnp.asarray(rng.uniform(0.002, 0.02, (n_blocks, bs)),
                         jnp.float32)
        return (q, k, v, tables, pos, ks, vs)
    k = jnp.asarray(rng.randn(n_blocks, bs, kh, d), jnp.bfloat16)
    v = jnp.asarray(rng.randn(n_blocks, bs, kh, d), jnp.bfloat16)
    return (q, k, v, tables, pos)


def kernel_phase(paged_widths, windows, norm_shape, gn_shape, tol,
                 lanes=8, nb=64, interpret=False):
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.pallas import norms
    from paddle_tpu.serving.paged_attention import (
        _pallas_paged_attention, _query_tile, _stream_blocks,
        _xla_paged_attention)

    worst = {}

    def record(name, got, want):
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        scale = max(1.0, float(np.abs(want).max()))
        worst[name] = float(np.abs(got - want).max()) / scale

    # every Pallas kernel of the training path, forward and backward,
    # against its XLA twin: kernel_checks.py's gate, as it is (it picks
    # interpret mode from the backend itself)
    import kernel_checks

    for name, err, limit in kernel_checks._kernel_checks():
        # each check has its own limit; scale so all share `tol`
        worst[f"kernel_checks.{name}"] = float(err) / limit * tol

    kernel = jax.jit(functools.partial(_pallas_paged_attention,
                                       interpret=interpret))
    reference = jax.jit(_xla_paged_attention)
    rng = np.random.RandomState(SEED)
    qh, kh, d, block = paged_widths
    geometry = {}
    for s in windows:
        case = _paged_case(rng, lanes, s, nb, paged_widths, False)
        record(f"paged_attention_s{s}", kernel(*case), reference(*case))
        chunk = _stream_blocks(s, qh, block, kh, d, jnp.bfloat16,
                               jnp.bfloat16)
        geometry[s] = (f"stream, {chunk} blocks a chunk" if chunk else
                       f"tile, {_query_tile(s, qh, d, jnp.bfloat16)} rows")
    for s in windows[:2]:
        case = _paged_case(rng, lanes, s, nb, paged_widths, True)
        record(f"paged_attention_int8_s{s}", kernel(*case),
               reference(*case))

    # the norm kernels at the row count and hidden width a training step
    # at these widths has (the row block is sized from the hidden width)
    n, h = norm_shape
    x = jnp.asarray(rng.randn(n, h), jnp.bfloat16)
    w = jnp.asarray(1.0 + 0.1 * rng.randn(h), jnp.bfloat16)
    b = jnp.asarray(0.1 * rng.randn(h), jnp.bfloat16)

    def f32(*xs):
        return [t.astype(jnp.float32) for t in xs]

    def rms_ref(x, w):
        x, w = f32(x, w)
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + 1e-6) * w

    def ln_ref(x, w, b):
        x, w, b = f32(x, w, b)
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + 1e-5) * w + b

    def gn_ref(x, w, b, groups=32):
        x, w, b = f32(x, w, b)
        g = x.reshape(x.shape[0], groups, -1)
        mu = jnp.mean(g, -1, keepdims=True)
        var = jnp.mean((g - mu) ** 2, -1, keepdims=True)
        out = ((g - mu) * jax.lax.rsqrt(var + 1e-5)).reshape(x.shape)
        return out * w[None, :, None, None] + b[None, :, None, None]

    def fwd_and_grads(fn, *args):
        loss = lambda *a: (fn(*a).astype(jnp.float32) ** 2).mean()
        grads = jax.jit(jax.grad(loss, argnums=tuple(range(len(args)))))
        return (jax.jit(fn)(*args),) + tuple(grads(*args))

    xg = jnp.asarray(rng.randn(*gn_shape), jnp.bfloat16)
    wg = jnp.asarray(1.0 + 0.1 * rng.randn(gn_shape[1]), jnp.float32)
    bg = jnp.asarray(0.1 * rng.randn(gn_shape[1]), jnp.float32)
    for name, fn, ref, args, parts in (
            ("rms_norm", lambda x, w: norms.rms_norm(x, w, 1e-6, interpret),
             rms_ref, (x, w), ("y", "dx", "dw")),
            ("layer_norm",
             lambda x, w, b: norms.layer_norm(x, w, b, 1e-5, interpret),
             ln_ref, (x, w, b), ("y", "dx", "dw", "db")),
            ("group_norm",
             lambda x, w, b: norms.group_norm(x, w, b, 32, 1e-5, interpret),
             gn_ref, (xg, wg, bg), ("y", "dx", "dw", "db"))):
        for part, got, want in zip(parts, fwd_and_grads(fn, *args),
                                   fwd_and_grads(ref, *args)):
            record(f"{name}_{part}", got, want)

    bad = {k: v for k, v in worst.items() if not v < tol}
    return {
        "ok": not bad, "tolerance": tol, "failed": bad,
        "compiled": not interpret, "checks": len(worst),
        "worst": max(worst.items(), key=lambda kv: kv[1]),
        "paged_widths_qh_kh_d_block": list(paged_widths),
        "paged_geometry_by_window": geometry,
        "paged_route": "static: backend tpu -> kernel for every window; "
                       "a window whose working set fits the VMEM budget "
                       "streams a lane's live blocks on an unquantized "
                       "pool, the others and int8 pools are cut into "
                       "tiles, none sent to the scan",
        "norm_shape": list(norm_shape),
        "norm_row_block": norms._row_block(n, h, jnp.bfloat16),
        "group_norm_shape": list(gn_shape),
        "errors": {k: round(v, 6) for k, v in sorted(worst.items())},
    }


# --------------------------------------------------------------- four chips

def tp_phase(cfg, tp, num_slots, max_seq_len, pool_blocks, prompt_lens,
             new_tokens, logit_tol, dtype="bfloat16"):
    """`create_llm_engine(model, tp=N)` against the single-chip engine on
    the same weights and prompts.  The repo's stated contract for tensor
    parallelism is equal token streams; where chips break it, the fork's
    position and the reference logits of both tokens are printed, and
    only a fork on a near-tie passes (see `_judge_stream`)."""
    import jax

    from paddle_tpu.inference import create_llm_engine
    from paddle_tpu.models import LlamaForCausalLM

    model = _seeded_model(LlamaForCausalLM, cfg, dtype)
    model.eval()
    prompts, samplings = _requests(cfg.vocab_size, prompt_lens, new_tokens)
    sizes = dict(num_slots=num_slots, max_seq_len=max_seq_len,
                 kv_pool_blocks=pool_blocks)

    one = create_llm_engine(model, **sizes)
    expected = [[int(t) for t in o] for o in one.generate(prompts, samplings)]
    one.close()
    del one
    gc.collect()

    mesh_engine = create_llm_engine(model, tp=tp, **sizes)
    placed = [_bytes_in_use(d) for d in jax.devices()[:tp]]
    got = [[int(t) for t in o]
           for o in mesh_engine.generate(prompts, samplings)]
    mesh_engine.drain()              # releases what the prefix cache holds
    stats = mesh_engine.stats()
    mesh_engine.close()

    dense = _dense_reference(model, max_seq_len)
    forks = [{"request": i, "prompt_len": len(prompts[i]), **f}
             for i, f in enumerate(
                 _judge_stream(g, e, p, sp, dense, logit_tol)
                 for g, e, p, sp in zip(got, expected, prompts, samplings))
             if f is not None]
    checks = {
        "streams_equal_single_chip_or_fork_on_a_near_tie": all(
            f["benign"] for f in forks),
        "every_device_holds_state": all(b for b in placed),
        "no_blocks_in_use": stats["kv_blocks_in_use"] == 0,
    }
    return {
        "ok": all(checks.values()), "checks": checks, "tp": tp,
        "model": "LlamaForCausalLM @ LLAMA3_8B widths",
        "depth": len(model.model.layers), "prompt_lens": list(prompt_lens),
        "new_tokens": new_tokens, "streams": len(got),
        "streams_bitwise_equal_single_chip": len(got) - len(forks),
        "forks": forks, "logit_tolerance": logit_tol,
        "bytes_in_use_by_device_after_placement": placed,
        "mesh": stats["mesh"]["mesh_shape"],
    }


def mesh_train_phase(cfg, batch, seq, steps, lr, tol, dp=2, mp=2,
                     dtype="bfloat16"):
    """A dp x mp `TrainStep` on a Mesh (parameters placed by their
    tensor-parallel hints, the batch over dp — the recipe of
    `__graft_entry__.dryrun_multichip`) against the single-chip losses
    on the same seed and batch."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM

    data = _Tokens(batch, seq, cfg.vocab_size, SEED)
    rows = [data[i] for i in range(batch)]
    ids_np = np.stack([r[0] for r in rows])
    labels_np = np.stack([r[1] for r in rows])

    model = _seeded_model(GPTForCausalLM, cfg, dtype)
    single, step = _train(model, paddle.to_tensor(ids_np),
                          paddle.to_tensor(labels_np), steps, lr)
    del model, step
    gc.collect()

    devices = jax.devices()[:dp * mp]
    mesh = Mesh(np.array(devices).reshape(dp, mp), ("dp", "mp"))
    model = _seeded_model(GPTForCausalLM, cfg, dtype)
    for t in model.state_dict().values():
        axes = getattr(t, "_sharding_axes", None)
        t._data = jax.device_put(
            t._data, NamedSharding(mesh, P(*axes) if axes else P()))
    batch_sharding = NamedSharding(mesh, P("dp", None))
    ids = paddle.Tensor(jax.device_put(ids_np, batch_sharding))
    labels = paddle.Tensor(jax.device_put(labels_np, batch_sharding))
    with mesh:
        sharded, step = _train(model, ids, labels, steps, lr)
    placed = [_bytes_in_use(d) for d in devices]

    diffs = [abs(a - b) for a, b in zip(sharded, single)]
    checks = {
        "losses_finite": bool(np.all(np.isfinite(sharded))),
        "loss_falls": sharded[-1] < sharded[0],
        "losses_vs_single_chip": max(diffs) <= tol,
        "every_device_holds_state": all(b for b in placed),
        "auto_layout_off_for_sharded_state": not step.auto_layout,
    }
    return {
        "ok": all(checks.values()), "checks": checks,
        "mesh": {"dp": dp, "mp": mp},
        "model": "GPTForCausalLM @ LLAMA2_7B widths",
        "depth": len(model.model.layers), "tokens_per_step": batch * seq,
        "losses": sharded, "single_chip_losses": single,
        "max_abs_diff": max(diffs), "tolerance": tol,
        "bytes_in_use_by_device_after_steps": placed,
    }


# --------------------------------------------------------------------- main

def _run_phases(phases, meter, cache_dir):
    from paddle_tpu.utils import compile_cache

    all_ok = True
    for name, fn in phases:
        mark, entries = meter.mark(), compile_cache.entry_count(cache_dir)
        t0 = time.perf_counter()
        try:
            row = fn()
        except Exception as e:
            # the failure is printed in full and the run exits non-zero
            traceback.print_exc()
            row = {"ok": False, "error": f"{type(e).__name__}: {e}"[:2000]}
        row = {"phase": name, **row,
               "seconds": round(time.perf_counter() - t0, 2),
               **meter.since(mark),
               "cache_entries_gained":
                   compile_cache.entry_count(cache_dir) - entries}
        all_ok = all_ok and bool(row["ok"])
        _emit(row)
        gc.collect()
    return all_ok


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: run only the two four-chip paths and "
                             "what each is compared with")
    args = parser.parse_args(argv)

    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s); jax found "
              f"{device}", file=sys.stderr)
        _emit({"ok": False, "device": device})
        return 1

    import jaxlib

    from paddle_tpu import native
    from paddle_tpu.models import LLAMA2_7B, LLAMA3_8B
    from paddle_tpu.utils import compile_cache

    cache_dir = compile_cache.enable()
    entries_before = compile_cache.entry_count(cache_dir)
    meter = _CompileMeter()
    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:
        libtpu = "unknown"
    _emit({"phase": "start", "ok": True, "chips": args.chips,
           "device": device, "jax": jax.__version__,
           "jaxlib": jaxlib.__version__, "libtpu": libtpu,
           "native_library_loaded": native.available(),
           "compile_cache_dir": cache_dir,
           "compile_cache_from_env":
               bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
           "compile_cache_entries_before": entries_before,
           "bytes_in_use": _bytes_in_use(devices[0])})

    train_cfg = dataclasses.replace(
        LLAMA2_7B, num_hidden_layers=TRAIN_DEPTH, fused_lm_loss=True)
    serve_cfg = dataclasses.replace(LLAMA3_8B, num_hidden_layers=SERVE_DEPTH)
    if args.chips == 4:
        phases = [
            ("tp4_engine", lambda: tp_phase(
                serve_cfg, 4, SERVE_SLOTS, SERVE_MAX_SEQ, SERVE_POOL_BLOCKS,
                TP_PROMPT_LENS, SERVE_NEW_TOKENS, SERVE_LOGIT_TOL)),
            ("dp2xmp2_train", lambda: mesh_train_phase(
                train_cfg, TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS, TRAIN_LR,
                MESH_LOSS_TOL)),
        ]
    else:
        phases = [
            ("trainer", lambda: trainer_phase(
                train_cfg, TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS, TRAIN_LR,
                TRAIN_LOSS_TOL)),
            ("server", lambda: server_phase(
                serve_cfg, SERVE_SLOTS, SERVE_MAX_SEQ, SERVE_POOL_BLOCKS,
                SERVE_PROMPT_LENS, SERVE_NEW_TOKENS, SERVE_LOGIT_TOL)),
            ("kernels", lambda: kernel_phase(
                PAGED_WIDTHS, PAGED_WINDOWS,
                (TRAIN_BATCH * TRAIN_SEQ, LLAMA2_7B.hidden_size),
                (2, 320, 64, 64), KERNEL_TOL)),
        ]
    ok = _run_phases(phases, meter, cache_dir)
    _emit({"phase": "end", "ok": ok,
           "compile_cache_entries_after":
               compile_cache.entry_count(cache_dir),
           "compile_cache_entries_before": entries_before,
           **meter.since((0, 0.0, 0))})
    _emit({"ok": ok, "device": device})
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
