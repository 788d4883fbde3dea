#!/usr/bin/env python
"""Explicit-collective multichip configs for the comms ledger.

One module, two consumers: ``bench_models.py multichip_comms`` (which
runs this file in a subprocess on 8 virtual CPU devices and writes the
rows into MULTICHIP_BENCH.json) and ``tests/test_comms_observability.py``
(which asserts the jaxpr walker's counts equal the hand-derived
``expected`` census of every config).

Each config is a small shard_map program written with EXPLICIT lax
collectives — the shapes the MULTICHIP dryruns exercise (dp grad sync,
dp×mp hybrid, pipeline ring, ring attention, ZeRO-3 gather/scatter,
MoE expert-parallel) distilled to their communication skeletons.
Honesty note: the dryruns' pjit/GSPMD variants (auto-sharded dp×mp,
``group_sharded`` ZeRO) get their collectives inserted during XLA SPMD
partitioning, where no jaxpr walker can see them — so the bench gates
the explicit shard_map skeletons, whose censuses are exact by
construction.  The dp4xmp2 config writes BOTH psums by hand (the mp
activation reduce and the dp grad sync) rather than relying on
``jax.grad``'s psum transposition, so the expected counts stay stable
across jax autodiff versions.

Run directly (prints one JSON row per config, then a sentinel):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    JAX_PLATFORMS=cpu python benchmarks/multichip_comms.py
"""

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SENTINEL = "MULTICHIP_COMMS_OK"


def _mesh(axis_sizes):
    import jax
    import numpy as np
    from jax.sharding import Mesh

    n = 1
    for v in axis_sizes.values():
        n *= v
    devs = np.array(jax.devices()[:n]).reshape(tuple(axis_sizes.values()))
    return Mesh(devs, tuple(axis_sizes))


# ---------------------------------------------------------------- configs
def build_dp8():
    """Pure data parallel over 8 ranks: one psum grad sync per step."""
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.distributed.shard_map_compat import NO_CHECK, shard_map

    mesh = _mesh({"dp": 8})

    def step(x):
        g = x * 2.0 + 1.0            # stand-in local gradient
        return lax.psum(g, "dp")

    fn = shard_map(step, mesh=mesh, in_specs=P("dp"), out_specs=P(),
                   **NO_CHECK)
    x = jnp.ones((8, 64), jnp.float32)
    return fn, (x,), {("psum", "dp"): 1}


def build_dp4xmp2():
    """Hybrid dp4×mp2: the mp activation reduce and the dp grad sync,
    both written explicitly."""
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.distributed.shard_map_compat import NO_CHECK, shard_map

    mesh = _mesh({"dp": 4, "mp": 2})

    def step(x, w):
        # x [b_loc, k_loc], w [k_loc, out]: row-parallel matmul — each
        # mp rank holds a K-slice, partial products sum across 'mp'
        y = lax.psum(x @ w, "mp")
        gw = x.T @ y                 # stand-in local weight gradient
        return lax.psum(gw, "dp")    # data-parallel grad sync

    fn = shard_map(step, mesh=mesh, in_specs=(P("dp", "mp"), P("mp", None)),
                   out_specs=P(), **NO_CHECK)
    x = jnp.ones((4, 8), jnp.float32)
    w = jnp.ones((8, 8), jnp.float32) * 0.1
    return fn, (x, w), {("psum", "mp"): 1, ("psum", "dp"): 1}


def build_pp2_1f1b():
    """Pipeline ring at S=2, M=4 microbatches on the 1F1B clock:
    T = M + 2(D-1) = 6 ticks, one boundary ppermute each, one final
    loss psum across 'pp'."""
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.distributed.shard_map_compat import NO_CHECK, shard_map

    S, M = 2, 4
    ticks = M + 2 * (S - 1)          # 1f1b tick count, D = S·V, V=1
    mesh = _mesh({"pp": 2})
    perm = [(i, (i + 1) % S) for i in range(S)]

    def step(h):
        def tick(carry, _):
            carry = lax.ppermute(carry, "pp", perm)
            return carry * 1.01, ()

        h, _ = lax.scan(tick, h, jnp.arange(ticks))
        return lax.psum((h * h).sum(), "pp")

    fn = shard_map(step, mesh=mesh, in_specs=P("pp"), out_specs=P(),
                   **NO_CHECK)
    h = jnp.ones((2, 16), jnp.float32)
    return fn, (h,), {("ppermute", "pp"): ticks, ("psum", "pp"): 1}


def build_ring_sep4():
    """The real ring attention forward over sep=4: the k and v blocks
    each rotate once per ring step, scan length = axis size, so the
    census is exactly 2·sep ppermutes."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.distributed.ring_attention import (
        ring_flash_attention_arrays)
    from paddle_tpu.distributed.shard_map_compat import NO_CHECK, shard_map

    sep = 4
    mesh = _mesh({"sep": sep})

    def step(q, k, v):
        return ring_flash_attention_arrays(q, k, v, causal=True,
                                           axis_name="sep")

    spec = P(None, "sep", None, None)      # [B, S, H, D] sharded on S
    fn = shard_map(step, mesh=mesh, in_specs=(spec, spec, spec),
                   out_specs=spec, **NO_CHECK)
    q = jnp.ones((1, 512, 4, 64), jnp.float32) * 0.02
    return fn, (q, q, q), {("ppermute", "sep"): 2 * sep}


def build_zero3_sharding8():
    """ZeRO-3 skeleton over sharding=8: gather each param shard before
    use, reduce-scatter each grad back — one all_gather + psum_scatter
    pair per parameter."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.distributed.shard_map_compat import NO_CHECK, shard_map

    mesh = _mesh({"sharding": 8})

    def step(x, w1, w2):
        w1f = lax.all_gather(w1, "sharding", axis=0, tiled=True)
        w2f = lax.all_gather(w2, "sharding", axis=0, tiled=True)
        h = jax.nn.relu(x @ w1f)
        y = h @ w2f
        g1f = x.T @ h                # stand-in full grads
        g2f = h.T @ y
        g1 = lax.psum_scatter(g1f, "sharding", scatter_dimension=0,
                              tiled=True)
        g2 = lax.psum_scatter(g2f, "sharding", scatter_dimension=0,
                              tiled=True)
        return g1, g2

    fn = shard_map(
        step, mesh=mesh,
        in_specs=(P("sharding", None), P("sharding", None),
                  P("sharding", None)),
        out_specs=(P("sharding", None), P("sharding", None)), **NO_CHECK)
    x = jnp.ones((8, 64), jnp.float32) * 0.1
    w1 = jnp.ones((64, 32), jnp.float32) * 0.05
    w2 = jnp.ones((32, 16), jnp.float32) * 0.05
    return fn, (x, w1, w2), {("all_gather", "sharding"): 2,
                             ("psum_scatter", "sharding"): 2}


def build_moe_ep4():
    """The real MoELayer expert-parallel path on dp=4 (8 experts, 2 per
    rank): one all_to_all to deal capacity buffers to expert owners, one
    to deal results back."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.distributed.shard_map_compat import NO_CHECK, shard_map
    from paddle_tpu.incubate.distributed.models.moe import MoELayer

    mesh = _mesh({"dp": 4})
    layer = MoELayer(d_model=16, d_hidden=32, num_experts=8,
                     axis_name="dp")
    weights = tuple(p._data for p in (layer.gate_weight, layer.w1,
                                      layer.b1, layer.w2, layer.b2))

    def step(x, gw, w1, b1, w2, b2):
        y, aux, tok = layer._forward_arrays(x, gw, w1, b1, w2, b2, "dp")
        return y, aux, tok

    fn = shard_map(
        step, mesh=mesh,
        in_specs=(P("dp", None),) + (P(None),) * 5,
        out_specs=(P("dp", None), P(), P()), **NO_CHECK)
    x = jnp.ones((64, 16), jnp.float32) * 0.1
    return fn, (x,) + weights, {("all_to_all", "dp"): 2}


def build_sharded_decode_tp2():
    """The REAL sharded-serving decode program: a tp=2 MeshEngine's
    horizon-scanned fused decode (``_decode_fn``, horizon=4) over the
    mesh-sharded paged pool.  Census is the hand-derived per-layer
    count: per scanned step, 1 psum head-combine + 3 all_gathers per
    layer (o_proj, SwiGLU intermediate, down_proj) + 1 all_gather for
    the lm_head logits — L=2, h=4 gives psum@tp=8, all_gather@tp=28.
    Unlike the skeletons above this walks a full engine program
    (shard_map under lax.scan under the sampling/masking machinery), so
    it also pins the walker's scan×shard_map multiplication."""
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving import EngineConfig, MeshEngine

    cfg = GPTConfig(vocab_size=128, hidden_size=64,
                    intermediate_size=128, num_hidden_layers=2,
                    num_attention_heads=4, max_position_embeddings=64)
    paddle.seed(0)
    m = GPTForCausalLM(cfg)
    m.eval()
    eng = MeshEngine(m, EngineConfig(num_slots=2, max_seq_len=32,
                                     max_horizon=4),
                     tp=2, register_profiler=False)
    horizon = 4
    fn, args = eng.decode_census_program(horizon=horizon)
    return fn, args, eng.expected_decode_census(horizon)


CONFIGS = {
    "dp8": build_dp8,
    "dp4xmp2": build_dp4xmp2,
    "pp2_1f1b": build_pp2_1f1b,
    "ring_sep4": build_ring_sep4,
    "zero3_sharding8": build_zero3_sharding8,
    "moe_ep4": build_moe_ep4,
    "sharded_decode_tp2": build_sharded_decode_tp2,
}


# ------------------------------------------------------------------ rows
def measure_config(name, steps=4, windows=2):
    """Build one config, walk its jaxpr, time its dispatches; returns the
    MULTICHIP_BENCH row (sans provenance fields, which the writer in
    bench_models.py stamps)."""
    import jax

    from paddle_tpu.observability import comms

    fn, args, expected = CONFIGS[name]()
    report = comms.analyze_fn(fn, *args)
    got = report.counts()
    if got != expected:
        raise AssertionError(
            f"{name}: walker census {got} != hand-derived {expected}")

    jitted = jax.jit(fn)
    jax.block_until_ready(jitted(*args))     # compile + warm
    best = None
    for _ in range(windows):
        t0 = time.time()
        for _ in range(steps):
            out = jitted(*args)
        jax.block_until_ready(out)
        dt = time.time() - t0
        best = dt if best is None else min(best, dt)
    step_s = best / steps

    kind = jax.devices()[0].device_kind
    comms_s = comms.modeled_comms_seconds(report, kind)
    comms.publish_dispatch("multichip", name, report, step_s, kind)
    by_op = report.calls_by_op()
    row = {
        "metric": f"multichip comms {name} step (cpu8)",
        "value": round(step_s * 1e3, 3),
        "unit": "ms",
        "collective_calls_total": report.total_calls,
        "modeled_wire_bytes_per_step": round(report.total_wire_bytes, 1),
        "comms_roofline_pct": round(100.0 * comms_s / step_s, 2)
        if step_s > 0 else None,
        "counts_by_op_axis": {f"{op}@{ax}": c
                              for (op, ax), c in sorted(got.items())},
    }
    for op in comms.COLLECTIVE_OPS:
        row[f"{op}_calls"] = by_op.get(op, 0)
    return row


def main(argv=None):
    names = [a for a in (argv or sys.argv[1:]) if not a.startswith("-")]
    for name in names or list(CONFIGS):
        try:
            print(json.dumps(measure_config(name)), flush=True)
        except Exception as e:       # report, keep going
            print(json.dumps({
                "metric": f"multichip comms {name} step (cpu8)",
                "error": f"{type(e).__name__}: {e}"[:300]}), flush=True)
    print(SENTINEL, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
