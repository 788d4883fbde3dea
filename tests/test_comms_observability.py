"""Distributed observability (phase 4): collective-comms ledger tests.

Covers the jaxpr comms walker against hand-derived censuses for every
MULTICHIP config (on the conftest's 8 virtual CPU devices), the ring
wire-byte model, the eager world-size-1 collective ticks, group-lifecycle
accounting, the /debug/comms + /debug/mesh telemetry routes, pipeline
bubble and expert-load skew gauges, ProgramCard comms sections, and the
check-bench --bench-file override.
"""

import importlib.util
import json
import os
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu import observability as obs
from paddle_tpu.observability import comms
from paddle_tpu.observability import metrics as obs_metrics

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_multichip():
    spec = importlib.util.spec_from_file_location(
        "multichip_comms", os.path.join(_ROOT, "benchmarks",
                                        "multichip_comms.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


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
    of every MULTICHIP config exactly (the check-bench gate relies on it)."""

    @pytest.fixture(scope="class")
    def mc(self):
        return _load_multichip()

    @pytest.mark.parametrize("name", ["dp8", "dp4xmp2", "pp2_1f1b",
                                      "ring_sep4", "zero3_sharding8",
                                      "moe_ep4", "sharded_decode_tp2"])
    def test_census_exact(self, mc, name):
        fn, args, expected = mc.CONFIGS[name]()
        report = comms.analyze_fn(fn, *args)
        assert report.counts() == expected
        assert report.total_calls == sum(expected.values())
        assert report.unbounded_loops == 0
        # every site resolved its axis size -> nonzero modeled wire bytes
        assert report.total_wire_bytes > 0
        assert not report.unknown_axes

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

class TestCardsAndGate:
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

    def test_check_bench_bench_file_override(self, tmp_path):
        from paddle_tpu.observability import regression

        row = {"metric": "multichip comms fake step (cpu8)", "value": 1.0,
               "unit": "ms", "psum_calls": 2, "collective_calls_total": 2}
        alt = tmp_path / "alt_bench.json"
        alt.write_text(json.dumps({"results": [row]}))
        fresh = tmp_path / "fresh.json"
        fresh.write_text(json.dumps({"results": [dict(row, value=1.1)]}))
        rep = regression.check_bench("/nonexistent/baseline.json",
                                     str(fresh), tolerance=0.25,
                                     bench_file=str(alt))
        assert rep["ok"] and rep["bench_file"] == str(alt)
        # deterministic field drift must fail exactly
        bad = tmp_path / "bad.json"
        bad.write_text(json.dumps({"results": [dict(row, psum_calls=3)]}))
        rep = regression.check_bench("/nonexistent/baseline.json",
                                     str(bad), tolerance=0.25,
                                     bench_file=str(alt))
        assert not rep["ok"]

    def test_committed_multichip_bench_schema(self):
        path = os.path.join(_ROOT, "MULTICHIP_BENCH.json")
        with open(path) as f:
            doc = json.load(f)
        rows = doc["results"]
        assert len(rows) >= 6
        for row in rows:
            assert row["schema_version"] == 1
            assert row["git_sha"] and row["run_id"] >= 1
            assert row["collective_calls_total"] >= 1

    def test_chrome_trace_carries_mesh_meta(self):
        from paddle_tpu.observability import events as obs_events

        doc = json.loads(obs_events.export_chrome_trace())
        assert "mesh" in doc.get("metadata", {})
