"""CPU rehearsals of the DeepSeek-V2 family's part of the benchmark: the new
driver end to end at a toy of the family (its check passes, and fails when a
served token is altered), the seeded weights and the program builder, the
work counts against numbers worked by hand, and the new readers."""

import importlib
import json
import os
import shutil
import time
import types

import numpy as np
import pytest

from conftest import DATA, ROOT, TINY_CHAT

TINY_LONG = dict(TINY_CHAT, driver="open_loop_http_deepseek_v2",
                 outputs={"kind": "pareto", "xm": 6, "alpha": 1.5, "cap": 12},
                 trace_seconds=1.0, lead_in_s=1.5)
CELL = "tiny-dsv2.tiny-longanswer"

#: the published widths, for the hand counts
LITE = dict(hidden_size=2048, num_attention_heads=16, kv_lora_rank=512,
            qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
            moe_intermediate_size=1408, intermediate_size=10944,
            n_shared_experts=2, n_routed_experts=64, num_experts_per_tok=6,
            num_hidden_layers=8, first_k_dense_replace=1, vocab_size=102400)


@pytest.fixture
def dsv2_root(tiny_root):
    """conftest's checkout in miniature, with a toy of the family, a mix
    for the new driver, its limit and two of the new metrics ADDED."""
    root, bench = tiny_root
    here = os.path.join(root, "chipbench")
    shutil.copy(os.path.join(DATA, "tiny-dsv2.json"),
                os.path.join(here, "configs", "tiny-dsv2.json"))
    with open(os.path.join(here, "traffic", "tiny-longanswer.json"), "w") as f:
        json.dump(TINY_LONG, f)
    with open(os.path.join(here, "limits", CELL + ".json"), "w") as f:
        # float32 program against the float32 reference: round-off of a
        # few 1e-6 on logits of about 0.5; 1e-3 is a thousand times that
        # and a tenth of what an altered token reads
        json.dump({"logit_gap": 1e-3}, f)
    bench["configs"].append({"name": "tiny-dsv2", "source": "none",
                             "file": "chipbench/configs/tiny-dsv2.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "tiny-dsv2",
                               "traffic": "tiny-longanswer", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "gen_tokens_per_s":
            m["workloads"].append(CELL)
    for m in bench["per_layer"]:
        if m["name"].endswith(".dsv2"):
            m["workloads"].append(CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root, bench


def _context(dsv2_root, seed=2_200_000_321, seconds=2.0):
    from chipbench import harness

    root, bench = dsv2_root
    cell = harness.Cell(root, bench, CELL)
    ctx = harness.Context(cell, seed, seconds, False,
                          {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11},
                          os.path.join(root, "chipbench", ".work"),
                          lambda msg: None)
    driver = importlib.import_module(
        "chipbench.drivers." + cell.traffic["driver"])
    return ctx, driver


def test_driver_end_to_end_and_an_altered_token_fails(dsv2_root):
    """Set-up with its lead-in, window, drain, end-to-end, check: correct,
    nothing failed, tokens counted where they were streamed, the routing
    counters in the records; then the same records with one served greedy
    token altered fail the same limit."""
    import jax

    from chipbench import compare, harness

    ctx, driver = _context(dsv2_root)
    result = harness.run_cell(ctx, driver, jax.devices()[:1],
                              time.perf_counter())
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["metrics"]["gen_tokens_per_s"]["value"] > 0
    assert result["compared"]["logit_gap"]["tokens"] > 0
    # the lead-in: one trace over 1.5 + 2 s, 4 requests/s, the window its
    # last 2 s; set-up holds the lead-in, the window does not
    recs = ctx.records["requests"]
    assert len(recs) == 14 and 1.9 < ctx.records["seconds"] < 2.5
    assert result["metrics"]["setup_s"]["value"] > 1.5
    early = [r for r in recs if r["due_s"] < 0]
    assert 3 <= len(early) <= 9 and min(r["due_s"] for r in recs) > -1.6
    assert any(r["before"] > 0 for r in early)
    close = ctx.records["seconds"]
    for r in recs:
        inside = [t for t in r["token_s"] if 0 < t <= close]
        assert r["generated"] == len(inside)
        assert r["before"] == sum(1 for t in r["token_s"] if t <= 0)
    streamed = sum(r["generated"] for r in recs)
    assert result["metrics"]["gen_tokens_per_s"]["value"] == pytest.approx(
        streamed / close)
    # what the engine generated inside the window reaches the client a
    # little later: the two counts differ by the tokens in flight at the ends
    assert abs(streamed - ctx.records["engine"]["tokens_generated"]) <= 8
    assert set(ctx.records["queue"]) == {"at_go", "at_close"}
    moe = ctx.records["moe"]
    # 2 expert layers, 2 experts a token: every step routes rows, and no
    # step can touch more than 8 experts a layer
    assert moe["rows"] > 0 and moe["decode"] > 0 and moe["prefill"] > 0
    assert moe["decode"] <= 2 * 8 * moe["decode_steps"]
    # the readers of the program's counters find what they read
    for name in ("model.mfu.dsv2", "model.hbm_share.dsv2",
                 "engine.lanes_per_decode.dsv2"):
        value = harness.read_layer_metric(ctx, name)
        assert value is not None and value > 0, name
    # a kernel's roofline needs a trace: nothing to read, nothing raised
    assert harness.read_layer_metric(ctx, "moe_experts_roofline.dsv2") is None
    # the planted fault, on what this run served
    recs = [r for r in ctx.records["requests"] if r["greedy"] and r["ok"]]
    sample = recs[:2]
    from chipbench import loadgen

    sched = loadgen.schedule(ctx.traffic, ctx.seed, 1.5 + ctx.seconds,
                             ctx.cfg["vocab_size"])
    by_index = {q["index"]: q["body"]["prompt"] for q in sched["requests"]}
    prompts = [by_index[r["index"]] for r in sample]
    logits, tokens, _ = driver.reference_gaps(ctx, sample, prompts)
    good, _ = compare.widest_logit_gap(logits, tokens)
    assert good <= ctx.limits["logit_gap"]
    altered = list(tokens)
    altered[len(altered) // 2] = (altered[len(altered) // 2] + 1) % 512
    bad, _ = compare.widest_logit_gap(logits, altered)
    assert bad > ctx.limits["logit_gap"]
    assert not harness.judge({"logit_gap": {
        "value": bad, "limit": ctx.limits["logit_gap"]}})


def test_weights_and_program_builder(dsv2_root):
    """Every leaf lands in the program under its shape, the two expert
    stacks joined; the same seed gives the same weights, another seed
    others; a large seed is taken."""
    import jax.numpy as jnp

    from chipbench import program_deepseek_v2 as program
    from chipbench import weights_deepseek_v2 as W

    ctx, _ = _context(dsv2_root)
    cfg = ctx.cfg
    seed = 2**31 + 12345
    a = W.make_all(cfg, seed, jnp.float32)
    b = W.make_group(cfg, seed, "layer.2", jnp.float32)
    c = W.make_group(cfg, seed + 1, "layer.2", jnp.float32)
    assert np.array_equal(a["layer.2"]["we_down"], b["we_down"])
    assert not np.array_equal(b["we_down"], c["we_down"])
    assert W.group_kind(cfg, "layer.0") == "dense"
    assert W.group_kind(cfg, "layer.1") == "moe"
    model = program.build_model(cfg, lambda g: a[g])
    sd = model.state_dict()
    assert sum(int(np.prod(p._data.shape)) for p in sd.values()) \
        == W.n_params(cfg)
    gu = sd["model.layers.1.mlp.experts_gate_up"]._data
    assert np.array_equal(gu[..., :48], a["layer.1"]["we_gate"])
    assert np.array_equal(gu[..., 48:], a["layer.1"]["we_up"])
    assert np.array_equal(sd["model.layers.0.mlp.down_proj.weight"]._data,
                          a["layer.0"]["w_down"])


def test_work_counts_by_hand():
    """The family's counts at the published widths against ISSUE 27's
    arithmetic, worked here by hand."""
    from chipbench import work_deepseek_v2 as work

    attn = 2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048
    assert work.attn_params(LITE) == attn == 13_762_560
    assert work.expert_params(LITE) == 3 * 2048 * 1408 == 8_650_752
    assert work.dense_layer_active(LITE) == attn + 3 * 2048 * 10944
    outside = attn + 2 * 8_650_752 + 2048 * 64
    assert work.moe_layer_outside_experts(LITE) == outside
    assert work.moe_layer_active(LITE) == outside + 6 * 8_650_752
    assert work.head_params(LITE) == 209_715_200
    assert work.attn_flops_pair(LITE) == 16 * (2 * 192 + 2 * 128)
    assert work.latent_bytes_token(LITE) == 1152
    # a decode step's fixed stream: 1 dense layer, 7 x outside, the head
    assert work.step_weight_bytes(LITE) == 2 * (
        attn + 3 * 2048 * 10944 + 7 * outside + 209_715_200)
    # one request: 100 prompt tokens, 5 generated (4 of them processed)
    active = attn + 3 * 2048 * 10944 + 7 * (outside + 6 * 8_650_752)
    pairs = 104 * 105 // 2
    assert work.request_flops(LITE, 100, 0, 5) == (
        2 * 104 * active + 2 * 5 * 209_715_200 + 8 * 10240 * pairs)
    f, b = work.request_attn_work(LITE, 100, 0, 5)
    assert f == 8 * 10240 * pairs
    qo = 16 * (192 + 128) * 2
    assert b == 8 * (100 * 1152 + 100 * qo
                     + sum((t + 1) * 1152 + qo for t in range(100, 104)))
    assert work.decode_latent_bytes(LITE, 100, 5) == 8 * 1152 * (
        101 + 102 + 103 + 104)
    # 768 rows of a 128-lane step through 7 layers, 64 experts touched each
    f, b = work.experts_work(LITE, 7 * 768, 7 * 64)
    assert f == 2 * 7 * 768 * 8_650_752
    assert b == 2 * (7 * 64 * 8_650_752 + 7 * 768 * 2 * 2048)


def test_readers_on_made_up_records():
    """The new readers on records and a reduced trace made up by hand: a
    share is the hand count, a reader with nothing to read gives None."""
    from chipbench.readers import (hbm_share_dsv2, kernel_roofline_dsv2,
                                   mfu_dsv2)
    from chipbench import work_deepseek_v2 as work

    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    req = {"prompt_len": 100, "generated": 5, "token_s": [1, 2, 3, 4, 25]}
    moe = {"prefill": 56, "decode": 7 * 64 * 10, "rows": 7 * 768 * 10,
           "decode_steps": 10}
    records = {"seconds": 30.0, "requests": [req], "moe": moe,
               "moe_traced": moe, "engine": {"decode_steps": 10}}
    mla = ('%k.1 = bf16[128,16,512]{2,1,0} custom-call(%a), '
           'custom_call_target="tpu_custom_call"')
    gmm = ('%g.1 = bf16[768,2816]{1,0} custom-call(%a), '
           'custom_call_target="tpu_custom_call"')
    other = "%fusion.3 = bf16[128,2048]{1,0} fusion(%b), kind=kLoop"
    reduced = {"window_s": 6.0,
               "op_seconds": {mla: 0.5, gmm: 2.0, other: 1.0}}
    ctx = types.SimpleNamespace(records=records, reduced=reduced, cfg=LITE,
                                peaks=peaks, log=lambda m: None)
    assert mfu_dsv2.read(ctx) == pytest.approx(
        100 * work.request_flops(LITE, 100, 0, 5) / (30 * 197e12))
    nbytes = (10 * work.step_weight_bytes(LITE)
              + 2 * moe["decode"] * 8_650_752
              + work.decode_latent_bytes(LITE, 100, 5))
    assert hbm_share_dsv2.read(ctx) == pytest.approx(
        100 * nbytes / (30 * 819e9))
    spec = json.load(open(os.path.join(
        ROOT, "chipbench", "layer_metrics",
        "mla_paged_attn_roofline.dsv2.json")))["args"]
    f, b = work.request_attn_work(LITE, 100, 0, 4)     # 4 tokens by 6 s
    least = max(f / 197e12, b / 819e9)
    assert kernel_roofline_dsv2.read(ctx, **spec) == pytest.approx(
        100 * least / 0.5)
    spec = json.load(open(os.path.join(
        ROOT, "chipbench", "layer_metrics",
        "moe_experts_roofline.dsv2.json")))["args"]
    f, b = work.experts_work(LITE, moe["rows"], moe["prefill"] + moe["decode"])
    assert kernel_roofline_dsv2.read(ctx, **spec) == pytest.approx(
        100 * max(f / 197e12, b / 819e9) / 2.0)
    # a program that lacks the counters (the parent): nothing, no error
    bare = types.SimpleNamespace(
        records={"seconds": 30.0, "requests": [req], "moe": None,
                 "moe_traced": None, "engine": {"decode_steps": 10}},
        reduced=reduced, cfg=LITE, peaks=peaks, log=lambda m: None)
    assert mfu_dsv2.read(bare) is not None       # needs no counter
    assert hbm_share_dsv2.read(bare) is None
    assert kernel_roofline_dsv2.read(bare, **spec) is None


def test_a_request_streaming_before_the_window_counts_its_decode_steps():
    """`work.window_part` on a request the lead-in sent: 100 prompt tokens,
    3 tokens streamed before "go", 4 inside the window (one of them after
    the trace's end at 6 s), 1 after the close: the window holds 4 decode
    steps against 102, 103, 104 and 105 cached tokens and no prefill."""
    from chipbench import work_deepseek_v2 as work
    from chipbench.readers import hbm_share_dsv2, mfu_dsv2

    q = {"prompt_len": 100, "generated": 4,
         "token_s": [-3.0, -2.0, -1.0, 1.0, 2.0, 3.0, 7.0, 31.0]}
    assert work.window_part(q, 30.0) == (103, 102, 4, True)
    assert work.window_part(q, 6.0) == (103, 102, 3, True)
    fresh = {"prompt_len": 100, "generated": 2, "token_s": [1.0, 2.0, 31.0]}
    assert work.window_part(fresh, 30.0) == (100, 0, 2, False)
    active = (work.attn_params(LITE) + 3 * 2048 * 10944
              + 7 * work.moe_layer_active(LITE))
    pairs = 103 + 104 + 105 + 106          # keys a step, its own included
    flops = 2 * 4 * active + 2 * 4 * 209_715_200 + 8 * 10240 * pairs
    assert work.request_flops(LITE, 103, 102, 4) == flops
    f, b = work.request_attn_work(LITE, 103, 102, 4)
    qo = 16 * (192 + 128) * 2
    assert f == 8 * 10240 * pairs
    assert b == 8 * ((103 + 104 + 105 + 106) * 1152 + 4 * qo)
    assert work.window_latent_bytes(LITE, q, 30.0) == 8 * 1152 * pairs
    assert work.window_latent_bytes(LITE, fresh, 30.0) == 8 * 1152 * 101
    records = {"seconds": 30.0, "requests": [q], "engine": {"decode_steps": 4},
               "moe": {"prefill": 0, "decode": 7 * 6 * 4, "rows": 7 * 6 * 4,
                       "decode_steps": 4}}
    ctx = types.SimpleNamespace(
        records=records, cfg=LITE, log=lambda m: None,
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})
    assert mfu_dsv2.read(ctx) == pytest.approx(100 * flops / (30 * 197e12))
    nbytes = (4 * work.step_weight_bytes(LITE) + 2 * 7 * 6 * 4 * 8_650_752
              + 8 * 1152 * pairs)
    assert hbm_share_dsv2.read(ctx) == pytest.approx(
        100 * nbytes / (30 * 819e9))
