"""Distributed observability (phase 4): collective-comms ledger tests.

Covers the jaxpr comms walker against hand-derived censuses for every
MULTICHIP config (on the conftest's 8 virtual CPU devices), the ring
wire-byte model, the eager world-size-1 collective ticks, group-lifecycle
accounting, the /debug/comms + /debug/mesh telemetry routes, pipeline
bubble and expert-load skew gauges, and ProgramCard comms sections.

The seven collective programs the census is taken of (``build_dp8`` …
``build_sharded_decode_tp2``) live here: small shard_map programs with
EXPLICIT lax collectives — dp grad sync, dp x mp hybrid, pipeline ring,
ring attention, ZeRO-3 gather/scatter, MoE expert parallel — cut down
to their communication, and one real engine program.  GSPMD variants get
their collectives during XLA's partitioning, where no jaxpr walker sees
them, so the census is taken of the explicit programs, whose counts are
exact by construction.  ``build_dp4xmp2`` writes BOTH psums by hand (the
mp activation reduce and the dp grad sync) instead of relying on
``jax.grad``'s transposition, so its counts do not move with jax.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu import observability as obs
from paddle_tpu.distributed.shard_map_compat import NO_CHECK, shard_map
from paddle_tpu.observability import comms
from paddle_tpu.observability import metrics as obs_metrics


# -------------------------------------------------- the census's programs
def _mesh(axis_sizes):
    shape = tuple(axis_sizes.values())
    devs = np.array(jax.devices()[:int(np.prod(shape))]).reshape(shape)
    return Mesh(devs, tuple(axis_sizes))


def build_dp8():
    """Pure data parallel over 8 ranks: one psum grad sync per step."""
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
    from paddle_tpu.distributed.ring_attention import (
        ring_flash_attention_arrays)

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


#: name -> (builder, modeled ring wire bytes a step).  The bytes are
#: ``report.total_wire_bytes``: a pure function of the program's shapes
#: and the mesh, held exact like the census.
CENSUS_PROGRAMS = {
    "dp8": (build_dp8, 448.0),
    "dp4xmp2": (build_dp4xmp2, 224.0),
    "pp2_1f1b": (build_pp2_1f1b, 388.0),
    "ring_sep4": (build_ring_sep4, 1048576.0),
    "zero3_sharding8": (build_zero3_sharding8, 17920.0),
    "moe_ep4": (build_moe_ep4, 6144.0),
    "sharded_decode_tp2": (build_sharded_decode_tp2, 14336.0),
}


# ------------------------------------------------------------- wire model

class TestWireModel:
    def test_world_size_one_is_free(self):
        for op in comms.COLLECTIVE_OPS:
            assert comms.wire_bytes(op, 1, 4096) == 0.0

    def test_ring_allreduce(self):
        # 2(n-1)/n * B
        assert comms.wire_bytes("psum", 8, 16) == pytest.approx(28.0)
        assert comms.wire_bytes("pmax", 4, 100) == pytest.approx(150.0)

    def test_all_gather_counts_shard_bytes(self):
        assert comms.wire_bytes("all_gather", 4, 10) == pytest.approx(30.0)

    def test_scatter_reduce_and_a2a(self):
        assert comms.wire_bytes("psum_scatter", 4, 16) == pytest.approx(12.0)
        assert comms.wire_bytes("all_to_all", 4, 16) == pytest.approx(12.0)

    def test_ppermute_is_one_hop(self):
        assert comms.wire_bytes("ppermute", 8, 123.0) == pytest.approx(123.0)

    def test_modeled_seconds_uses_datasheet(self):
        rep = comms.CommsReport()
        rep.add("psum", "dp", 1, 1 << 30, 8)  # one 1-GiB psum on an 8-ring
        secs = comms.modeled_comms_seconds(rep, "TPU v5 lite")
        bw = comms.interconnect_bandwidth_gbs("TPU v5 lite", tier="ici")
        expect = comms.wire_bytes("psum", 8, 1 << 30) / (bw * 1e9)
        assert secs == pytest.approx(expect)


# ------------------------------------------------------ walker vs configs

class TestWalkerCensus:
    """The jaxpr walker must reproduce the hand-derived collective census
    of every program above exactly, and the wire bytes its ring model
    gives them."""

    @pytest.mark.parametrize("name", list(CENSUS_PROGRAMS))
    def test_census_exact(self, name):
        build, wire_bytes = CENSUS_PROGRAMS[name]
        fn, args, expected = build()
        report = comms.analyze_fn(fn, *args)
        assert report.counts() == expected
        assert report.total_calls == sum(expected.values())
        assert report.unbounded_loops == 0
        # every site resolved its axis size -> the modeled wire bytes
        assert not report.unknown_axes
        assert round(report.total_wire_bytes, 1) == wire_bytes

    def test_scan_multiplies_trip_count(self):
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import Mesh, PartitionSpec as P

        from paddle_tpu.distributed.shard_map_compat import NO_CHECK, shard_map

        mesh = Mesh(np.array(jax.devices()[:4]).reshape(4), ("dp",))

        def body(x):
            def step(c, _):
                return lax.psum(c, "dp"), None
            out, _ = lax.scan(step, x, None, length=5)
            return out

        f = shard_map(body, mesh=mesh, in_specs=P("dp"),
                      out_specs=P("dp"), **NO_CHECK)
        rep = comms.analyze_fn(f, np.ones((4, 8), np.float32))
        assert rep.counts() == {("psum", "dp"): 5}

    def test_report_publish_and_json(self):
        obs.reset()
        rep = comms.CommsReport()
        rep.add("all_gather", "mp", 1, 64, 2)
        rep.publish()
        assert obs_metrics.value("comms.collective_calls",
                                 op="all_gather", axis="mp") == 1
        doc = rep.to_json()
        assert doc["collective_calls"] == 1
        assert doc["by_op_axis"][0]["op"] == "all_gather"
        assert doc["by_op_axis"][0]["axis"] == "mp"


# ----------------------------------------------------- eager world-size-1

class TestEagerCollectiveTicks:
    def test_all_reduce_ticks_psum_world(self):
        # a live HCG (leaked by an earlier test) would re-point the default
        # group at its dp axis; this test asserts the world-size-1 path
        dist.set_hybrid_communicate_group(None)
        obs.reset()
        t = paddle.to_tensor(np.ones((4,), np.float32))
        dist.all_reduce(t)
        assert obs_metrics.value("comms.collective_calls",
                                 op="psum", axis="world") == 1
        # world size 1 -> wire bytes stay 0 under the ring model
        assert obs_metrics.value("comms.wire_bytes",
                                 op="psum", axis="world") == 0

    def test_alltoall_and_shift_tick(self):
        dist.set_hybrid_communicate_group(None)
        obs.reset()
        t = paddle.to_tensor(np.ones((4,), np.float32))
        out = [paddle.to_tensor(np.zeros((4,), np.float32))]
        dist.alltoall(out, [t])
        dist.shift(t, offset=1)
        assert obs_metrics.value("comms.collective_calls",
                                 op="all_to_all", axis="world") == 1
        assert obs_metrics.value("comms.collective_calls",
                                 op="ppermute", axis="world") == 1


# -------------------------------------------------------- group lifecycle

class TestGroupLifecycle:
    def test_create_destroy_cycles_leak_nothing(self):
        from paddle_tpu.distributed import communication as comm

        base_live = len(comm._GROUPS)
        base_created = comm._GROUPS_CREATED
        providers_before = len(obs_metrics.default_registry()._providers) \
            if hasattr(obs_metrics, "default_registry") else None
        for _ in range(3):
            g = comm.new_group(axis_name="dp")
            assert len(comm._GROUPS) == base_live + 1
            comm.destroy_process_group(g)
            assert len(comm._GROUPS) == base_live
        assert comm._GROUPS_CREATED == base_created + 3
        snap = comm._groups_provider()
        assert snap["live_groups"] == base_live
        assert snap["created_total"] == base_created + 3
        if providers_before is not None:
            assert len(obs_metrics.default_registry()._providers) \
                == providers_before

    def test_groups_provider_in_exposition(self):
        text = obs_metrics.render_prometheus()
        assert "distributed" in text and "groups" in text
        # returns the sample count; raises ValueError on any violation
        assert obs_metrics.validate_exposition(text) > 0


# ------------------------------------------------------- telemetry routes

class TestMeshTelemetry:
    def test_debug_comms_route(self):
        from paddle_tpu.observability.server import TelemetryServer

        obs.reset()
        comms.record_collective("psum", "dp", world_size=8, operand_bytes=16)
        srv = TelemetryServer(port=0)
        status, ctype, body = srv.handle("/debug/comms")
        assert status == 200 and ctype == "application/json"
        doc = json.loads(body)
        assert doc["collective_calls_total"] >= 1
        assert "interconnect_gbs" in doc
        _, _, idx = srv.handle("/")
        eps = json.loads(idx)["endpoints"]
        assert "/debug/comms" in eps and "/debug/mesh" in eps

    def test_debug_mesh_route_no_hcg(self):
        from paddle_tpu.observability.server import TelemetryServer

        dist.set_hybrid_communicate_group(None)
        srv = TelemetryServer(port=0)
        status, _, body = srv.handle("/debug/mesh")
        assert status == 200
        assert json.loads(body)["mesh"]["initialized"] is False

    def test_mesh_snapshot_with_hcg(self):
        from paddle_tpu.distributed import fleet
        from paddle_tpu.distributed.fleet import DistributedStrategy

        dist.set_hybrid_communicate_group(None)
        try:
            s = DistributedStrategy()
            s.hybrid_configs = {"dp_degree": 2, "mp_degree": 2,
                                "pp_degree": 2}
            fleet.init(is_collective=True, strategy=s)
            snap = comms.mesh_snapshot()
            assert snap["initialized"] is True
            assert snap["world_size"] == 8
            dims = {a["name"]: a["dim"] for a in snap["axes"]}
            assert dims.get("data") == 2 and dims.get("pipe") == 2
            meta = comms.mesh_meta()
            assert meta and meta.get("world_size") == 8
        finally:
            dist.set_hybrid_communicate_group(None)

    def test_comms_families_validate(self):
        obs.reset()
        comms.record_collective("all_gather", "sharding", world_size=8,
                                operand_bytes=1024)
        text = obs_metrics.render_prometheus()
        assert "comms" in text
        assert obs_metrics.validate_exposition(text) > 0


# ------------------------------------------------------------ skew gauges

class TestSkewGauges:
    def test_pipeline_bubble_formulas(self):
        obs.reset()
        # gpipe S=4 M=8: T=11, bubble 3/11
        b = comms.publish_pipeline_schedule("gpipe", 4, 8)
        assert b == pytest.approx(3 / 11)
        # 1f1b S=4 M=8: T=8+2*3=14, bubble 6/14
        b = comms.publish_pipeline_schedule("1f1b", 4, 8)
        assert b == pytest.approx(6 / 14)
        # interleaved S=4 V=2 M=8: D=8, T=15, bubble 7/15
        b = comms.publish_pipeline_schedule("interleaved", 4, 8, virtual=2)
        assert b == pytest.approx(7 / 15)
        assert obs_metrics.value("comms.pipeline_bubble_ratio",
                                 schedule="interleaved") \
            == pytest.approx(7 / 15)

    def test_expert_load_imbalance(self):
        obs.reset()
        imb = comms.observe_expert_load(np.array([3.0, 1.0]), layer="l0")
        assert imb == pytest.approx(1.5)
        assert obs_metrics.value("comms.moe_expert_load_imbalance",
                                 layer="l0") == pytest.approx(1.5)
        assert comms.observe_expert_load(np.zeros((4,))) is None

    def test_moe_layer_records_tokens_per_expert(self):
        from paddle_tpu.incubate.distributed.models.moe import MoELayer

        layer = MoELayer(d_model=8, d_hidden=16, num_experts=4)
        x = paddle.to_tensor(np.random.default_rng(0).standard_normal(
            (16, 8)).astype(np.float32))
        layer(x)
        tok = layer.tokens_per_expert
        assert tok is not None
        imb = comms.observe_expert_load(tok, layer="moe_test")
        assert imb is None or imb >= 1.0


# ------------------------------------------------- program cards + gating

class TestCards:
    def test_program_card_comms_section(self):
        import jax
        import jax.numpy as jnp

        from paddle_tpu.observability import profiling

        rep = comms.CommsReport()
        rep.add("psum", "dp", 1, 256, 8)
        f = jax.jit(lambda x: x * 2)
        lowered = f.lower(jnp.ones((4,), jnp.float32))
        try:
            card = profiling.capture("test.comms_card", "rk", lowered,
                                     backend="cpu", comms=rep)
            doc = card.to_json()
            assert doc["comms"]["collective_calls"] == 1
            assert doc["comms"]["by_op_axis"][0]["op"] == "psum"
        finally:
            profiling.clear()

    def test_chrome_trace_carries_mesh_meta(self):
        from paddle_tpu.observability import events as obs_events

        doc = json.loads(obs_events.export_chrome_trace())
        assert "mesh" in doc.get("metadata", {})
