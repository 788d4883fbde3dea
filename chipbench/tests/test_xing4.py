"""CPU rehearsals of the Xing4.0 family's part of the benchmark: the driver end
to end at a toy of the family (its check passes, and fails when a served
token is altered), a checkout without the family's model failing at once,
the seeded weights and the program builder, the work counts against numbers
worked by hand, and the new readers."""

import importlib
import json
import os
import shutil
import sys
import time
import types

import numpy as np
import pytest

from conftest import DATA, ROOT, TINY_CHAT

TINY_DOCQA = dict(TINY_CHAT, driver="open_loop_http_xing4",
                  outputs={"kind": "pareto", "xm": 4, "alpha": 2, "cap": 10},
                  trace_seconds=1.0, lead_in_s=1.5, order_block=8)
CELL = "tiny-xing4.tiny-docqa"

#: a toy's widths, for the counts by hand
TOY = dict(hidden_size=64, num_attention_heads=4, q_lora_rank=32,
           kv_lora_rank=32, qk_nope_head_dim=24, qk_rope_head_dim=16,
           v_head_dim=24, moe_intermediate_size=48, intermediate_size=160,
           n_shared_experts=1, n_routed_experts=8, num_experts_per_tok=2,
           num_hidden_layers=3, first_k_dense_replace=2, vocab_size=512,
           hc_mult=4)
#: the published widths at the served depth
XING = dict(hidden_size=3584, num_attention_heads=32, q_lora_rank=768,
            kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
            v_head_dim=128, moe_intermediate_size=1024,
            intermediate_size=9216, n_shared_experts=1, n_routed_experts=64,
            num_experts_per_tok=4, num_hidden_layers=6,
            first_k_dense_replace=2, vocab_size=131072, hc_mult=4)


@pytest.fixture
def xing_root(tiny_root):
    """conftest's checkout in miniature, with a toy of the family, a mix
    for the new driver, its limit and the new metrics ADDED."""
    root, bench = tiny_root
    here = os.path.join(root, "chipbench")
    shutil.copy(os.path.join(DATA, "tiny-xing4.json"),
                os.path.join(here, "configs", "tiny-xing4.json"))
    with open(os.path.join(here, "traffic", "tiny-docqa.json"), "w") as f:
        json.dump(TINY_DOCQA, f)
    with open(os.path.join(here, "limits", CELL + ".json"), "w") as f:
        # float32 program against the float32 reference: round-off of a
        # few 1e-6 on logits of about 0.5; 1e-3 is a thousand times that
        # and a tenth of what an altered token reads, and the mean of such
        # round-off lies under a tenth of it
        json.dump({"logit_gap": 1e-3, "logit_gap_mean": 1e-4}, f)
    bench["configs"].append({"name": "tiny-xing4", "source": "none",
                             "file": "chipbench/configs/tiny-xing4.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "tiny-xing4",
                               "traffic": "tiny-docqa", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "gen_tokens_per_s":
            m["workloads"].append(CELL)
    for m in bench["per_layer"]:
        if m["name"].endswith(".xing4"):
            m["workloads"].append(CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root, bench


def _context(xing_root, seed=2_200_000_321, seconds=2.0):
    from chipbench import harness

    root, bench = xing_root
    cell = harness.Cell(root, bench, CELL)
    ctx = harness.Context(cell, seed, seconds, False,
                          {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11},
                          os.path.join(root, "chipbench", ".work"),
                          lambda msg: None)
    driver = importlib.import_module(
        "chipbench.drivers." + cell.traffic["driver"])
    return ctx, driver


def test_driver_end_to_end_and_an_altered_token_fails(xing_root):
    """Set-up with its lead-in, window, drain, end-to-end, check: correct,
    nothing failed, the routing counters in the records, the readers of
    the program's counters find what they read; then one served greedy
    token altered fails the same limit."""
    import jax

    from chipbench import compare, harness, loadgen

    ctx, driver = _context(xing_root)
    result = harness.run_cell(ctx, driver, jax.devices()[:1],
                              time.perf_counter())
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["metrics"]["gen_tokens_per_s"]["value"] > 0
    for name in ("logit_gap", "logit_gap_mean"):
        assert result["compared"][name]["tokens"] > 0
    assert result["metrics"]["setup_s"]["value"] > 1.5       # the lead-in
    recs = ctx.records["requests"]
    assert len(recs) == 14 and any(r["before"] > 0 for r in recs)
    moe = ctx.records["moe"]
    # 1 expert layer, 2 experts a token: no step touches more than 8
    assert moe["rows"] > 0 and moe["decode"] > 0 and moe["prefill"] > 0
    assert moe["decode"] <= 8 * moe["decode_steps"]
    for name in ("model.mfu.xing4", "model.hbm_share.xing4",
                 "engine.lanes_per_decode.xing4"):
        value = harness.read_layer_metric(ctx, name)
        assert value is not None and value > 0, name
    assert harness.read_layer_metric(
        ctx, "engine.queue_at_close.xing4") == \
        ctx.records["queue"]["at_close"]["queue_depth"]
    # a trace's metrics need a trace: nothing to read, nothing raised
    for name in ("moe_experts_roofline.xing4", "mhc.device_share.xing4",
                 "device.idle_share.xing4"):
        assert harness.read_layer_metric(ctx, name) is None
    sample = [r for r in recs if r["greedy"] and r["ok"]][:2]
    sched = loadgen.schedule(ctx.traffic, ctx.seed, 1.5 + ctx.seconds,
                             ctx.cfg["vocab_size"])
    by_index = {q["index"]: q["body"]["prompt"] for q in sched["requests"]}
    logits, tokens, _ = driver.reference_gaps(
        ctx, sample, [by_index[r["index"]] for r in sample])
    good, good_mean = driver.gaps(logits, tokens)
    assert good == compare.widest_logit_gap(logits, tokens)[0]
    assert good <= ctx.limits["logit_gap"]
    assert good_mean <= ctx.limits["logit_gap_mean"]
    altered = list(tokens)
    altered[len(altered) // 2] = (altered[len(altered) // 2] + 1) % 512
    bad, bad_mean = driver.gaps(logits, altered)
    assert bad > ctx.limits["logit_gap"]
    assert bad_mean == pytest.approx(bad / len(tokens), rel=1e-3)


def test_calibrate_emits_the_readings_of_a_limit(xing_root):
    """chipbench/control.py's readings through this driver: one engine for
    two seeds (the second swaps its weights in), a window's tokens/s and
    queue and the program's gap on each, the fp8 control, the reference in
    bfloat16 and the two altered tokens on the first."""
    from chipbench import harness

    root, bench = xing_root
    cell = harness.Cell(root, bench, CELL)
    rows = []
    ctx, driver = _context(xing_root)

    def make_ctx(seed):
        return harness.Context(cell, seed, 1.5, False, ctx.peaks,
                               ctx.work_dir, lambda msg: None)

    driver.calibrate(make_ctx, [2_200_000_401, 2**31 + 77], 1, rows.append)
    kinds = [r["kind"] for r in rows]
    assert kinds == ["program", "control_fp8", "reference_bf16",
                     "fault_token_altered_surest",
                     "fault_token_altered_least_sure", "program"]
    program = [r for r in rows if r["kind"] == "program"]
    for r in program:
        assert r["logit_gap"] <= 1e-3 and r["failed"] == 0
        assert r["logit_gap_mean"] <= 1e-4
        assert r["gen_tokens_per_s"] > 0 and set(r["queue"]) == {
            "at_go", "at_close"}
    assert rows[1]["logit_gap"] > program[0]["logit_gap"]
    assert rows[1]["logit_gap_mean"] > program[0]["logit_gap_mean"]
    assert rows[2]["tokens"] == rows[1]["tokens"] == program[0]["tokens"]
    assert min(rows[3]["logit_gap"], rows[4]["logit_gap"]) > 1e-3


def test_a_checkout_without_the_model_fails_at_once(xing_root, monkeypatch):
    """The parent commit has the benchmark's files of this family laid over
    it and no `paddle_tpu.models.xing4`: set-up raises the import error
    before any weight is made or any client started."""
    ctx, driver = _context(xing_root)
    monkeypatch.setitem(sys.modules, "paddle_tpu.models.xing4", None)
    t = time.perf_counter()
    with pytest.raises(ImportError):
        driver.setup(ctx)
    assert time.perf_counter() - t < 5.0
    assert not os.path.exists(ctx.work_dir) or not os.listdir(ctx.work_dir)


def test_weights_and_program_builder(xing_root):
    """Every leaf lands in the program under its shape (the expert stacks
    joined, the mHC leaves of both sublayers, the router's bias); the same
    seed gives the same weights, another seed others; a large seed is
    taken; the seeded mixing leans on its diagonal."""
    import jax.numpy as jnp

    from chipbench import program_xing4 as program
    from chipbench import weights_xing4 as W

    ctx, _ = _context(xing_root)
    cfg = ctx.cfg
    seed = 2**31 + 12345
    a = W.make_all(cfg, seed, jnp.float32)
    b = W.make_group(cfg, seed, "layer.2", jnp.float32)
    c = W.make_group(cfg, seed + 1, "layer.2", jnp.float32)
    assert np.array_equal(a["layer.2"]["we_down"], b["we_down"])
    assert not np.array_equal(b["we_down"], c["we_down"])
    assert [W.group_kind(cfg, f"layer.{i}") for i in range(3)] == \
        ["dense", "dense", "moe"]
    model = program.build_model(cfg, lambda g: a[g])
    sd = model.state_dict()
    assert sum(int(np.prod(p._data.shape)) for p in sd.values()) \
        == W.n_params(cfg)
    gu = sd["model.layers.2.mlp.experts_gate_up"]._data
    assert np.array_equal(gu[..., :48], a["layer.2"]["we_gate"])
    assert np.array_equal(sd["model.layers.2.mlp.e_score_correction_bias"]
                          ._data, a["layer.2"]["router_bias"])
    assert np.array_equal(sd["model.layers.1.ffn_hc.phi"]._data,
                          a["layer.1"]["hc_ffn_phi"])
    assert np.array_equal(sd["model.layers.0.self_attn.q_b_proj.weight"]
                          ._data, a["layer.0"]["wq_b"])
    res = np.asarray(a["layer.0"]["hc_attn_bias"])[8:].reshape(4, 4)
    assert np.diagonal(res).mean() - res.mean() > 1.0
    alpha = np.asarray(a["layer.0"]["hc_attn_alpha"])
    assert alpha.shape == (3,) and 0.2 < alpha.min() < alpha.max() < 0.8


def test_work_counts_by_hand():
    """The family's counts at a toy's widths, worked here by hand, and at
    the published widths against the arithmetic of the configuration's
    cut (PERF.md section 4)."""
    from chipbench import work_xing4 as work

    attn = 64 * 32 + 32 * 4 * 40 + 64 * 48 + 32 * 4 * 48 + 4 * 24 * 64
    assert work.attn_params(TOY) == attn == 22_528
    assert work.hc_params(TOY) == 4 * 64 * 24 == 6_144
    assert work.dense_layer_active(TOY) == attn + 2 * 6_144 + 3 * 64 * 160
    outside = attn + 2 * 6_144 + 3 * 64 * 48 + 64 * 8
    assert work.moe_layer_outside_experts(TOY) == outside == 44_544
    assert work.moe_layer_active(TOY) == outside + 2 * 9_216
    assert work.active_params_token(TOY) == 2 * 65_536 + 62_976
    assert work.hc_mix_flops(TOY) == 2 * (256 + 1_024 + 256)
    assert work.token_flops(TOY) == 2 * 194_048 + 2 * 3 * 3_072
    assert work.step_weight_bytes(TOY) == 2 * (2 * 65_536 + 44_544
                                               + 64 * 512)
    assert work.hc_stream_bytes_token(TOY) == 3 * 2 * 2 * 256 * 2
    # one request: 100 prompt tokens, 5 generated (4 of them processed)
    pairs = 104 * 105 // 2
    assert work.request_flops(TOY, 100, 0, 5) == (
        104 * 406_528 + 2 * 5 * 32_768
        + 3 * 4 * (2 * 40 + 2 * 24) * pairs)
    # the published widths: 28.41 M of attention, 0.69 M of phi a layer,
    # 128.2 M a dense layer, 745.0 M an expert layer
    assert work.attn_params(XING) == 28_409_856
    assert 2 * work.hc_params(XING) == 688_128
    assert work.dense_layer_active(XING) == 28_409_856 + 688_128 \
        + 99_090_432
    expert = 3 * 3584 * 1024
    layer = work.moe_layer_outside_experts(XING) + 64 * expert
    assert layer == 28_409_856 + 688_128 + 65 * expert + 3584 * 64
    assert round(layer / 1e6, 1) == 745.0
    assert work.step_weight_bytes(XING) == 2 * (
        2 * 128_188_416 + 4 * (layer - 64 * expert) + 3584 * 131_072)


def test_readers_on_made_up_records():
    """The new readers on records and a reduced trace made up by hand: a
    share is the hand count, a reader with nothing to read gives None."""
    from chipbench import work_xing4 as work
    from chipbench.readers import device_share, hbm_share_xing4, mfu_xing4

    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    fresh = {"prompt_len": 100, "generated": 5, "token_s": [1, 2, 3, 4, 25]}
    old = {"prompt_len": 80, "generated": 2, "token_s": [-1.0, 1.0, 2.0]}
    moe = {"prefill": 4 * 64, "decode": 4 * 64 * 10, "rows": 4 * 512 * 10,
           "decode_steps": 10}
    records = {"seconds": 30.0, "requests": [fresh, old], "moe": moe,
               "moe_traced": moe, "engine": {"decode_steps": 10}}
    ctx = types.SimpleNamespace(records=records, reduced=None, cfg=XING,
                                peaks=peaks, log=lambda m: None)
    flops = (work.request_flops(XING, 100, 0, 5)
             + work.request_flops(XING, 81, 80, 2))
    assert mfu_xing4.read(ctx) == pytest.approx(100 * flops / (30 * 197e12))
    nbytes = (10 * work.step_weight_bytes(XING)
              + 2 * moe["decode"] * 3 * 3584 * 1024
              + work.decode_latent_bytes(XING, 100, 5)
              + work.decode_latent_bytes(XING, 80, 3)
              + (4 + 2) * work.hc_stream_bytes_token(XING))
    assert hbm_share_xing4.read(ctx) == pytest.approx(
        100 * nbytes / (30 * 819e9))
    # the mHC share: streams, coefficients and H_res, a tuple, a loop left out
    ops = {"%fusion.1 = bf16[128,1,4,3584]{3,2,1,0} fusion(%a)": 0.3,
           "%fusion.2 = f32[8,1024,24]{2,1,0} fusion(%a)": 0.2,
           "%fusion.3 = (f32[128,1,4,4]{3,2,1,0}, f32[128,1,4]) fusion(%b)":
               0.1,
           "%fusion.4 = bf16[128,1,3584]{2,1,0} fusion(%c)": 1.0,
           "%while.5 = (s32[], bf16[128,1,4,3584]) while(%d)": 5.0,
           "%gmm = bf16[512,2048]{1,0} custom-call(%e), "
           'custom_call_target="tpu_custom_call"': 2.0}
    spec = json.load(open(os.path.join(
        ROOT, "chipbench", "layer_metrics", "mhc.device_share.xing4.json")))
    ctx.reduced = {"busy_s": 4.0, "op_seconds": ops}
    assert device_share.read(ctx, **spec["args"]) == pytest.approx(
        100 * 0.6 / 4.0)
    ctx.reduced = {"busy_s": 4.0, "op_seconds": {k: v for k, v in ops.items()
                                                 if "3584]" not in k}}
    assert device_share.read(ctx, **spec["args"]) == pytest.approx(
        100 * 0.3 / 4.0)
    # the parent: no trace, no counters -- nothing, and no error
    bare = types.SimpleNamespace(
        records={"seconds": 30.0, "requests": [fresh], "moe": None,
                 "engine": {"decode_steps": 10}},
        reduced=None, cfg=XING, peaks=peaks, log=lambda m: None)
    assert hbm_share_xing4.read(bare) is None
    assert device_share.read(bare, **spec["args"]) is None
    bare.reduced = {"busy_s": 4.0, "op_seconds": {"%f = bf16[8,64] f(%a)": 1}}
    assert device_share.read(bare, **spec["args"]) is None


def test_kernel_patterns_select_this_configurations_kernels():
    """The two roofline files select the latent kernel at 32 heads and the
    grouped products at expert width 1024 and hidden 3584, and nothing
    else of the kinds the programs produce."""
    import re

    def pattern(name):
        return json.load(open(os.path.join(
            ROOT, "chipbench", "layer_metrics", name + ".json")))["args"][
                "pattern"]

    kernel = 'custom-call(%a), custom_call_target="tpu_custom_call"'
    mla, gmm = (re.compile(pattern(n)) for n in (
        "mla_paged_attn_roofline.xing4", "moe_experts_roofline.xing4"))
    assert mla.search(f"%k = bf16[128,32,512]{{2,1,0}} {kernel}")
    assert mla.search(f"%k = bf16[8,16384,512]{{2,1,0}} {kernel}")
    assert gmm.search(f"%g = bf16[4096,2048]{{1,0}} {kernel}")
    assert gmm.search(f"%g = bf16[512,3584]{{1,0}} {kernel}")
    for other in (f"%g = bf16[512,2816]{{1,0}} {kernel}",
                  "%f = bf16[512,3584]{1,0} fusion(%a)",
                  "%f = bf16[128,32,512]{2,1,0} fusion(%a)"):
        assert not mla.search(other) and not gmm.search(other)
