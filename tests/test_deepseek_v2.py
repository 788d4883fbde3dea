"""DeepSeek-V2 family on the CPU at a small size (hidden 64, 3 layers of
which the first dense, 8 experts top-2 with a shared expert, latent 32 +
rotary 16, vocabulary 512; Pallas in interpret mode), seeded random weights:
the program against the plain reference (`tests/refs/deepseek_v2_reference.py`,
byte-identical to the benchmark's).

Tolerances, each with its reason.  Program and reference are both float32 on
the CPU and differ by the order of their sums only: logits of magnitude 0.6
came out 2e-7 apart, and ``ATOL = 1e-5`` is fifty times that and a hundred
times under what one bfloat16 rounding of the weights moves them (1e-3:
``test_lower_precision_fails``).  The kernel against the gather, the grouped
product against the loop: same story at 1e-5 on values of order one.
"""

import filecmp
import http.client
import importlib.util
import json
import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models.deepseek_v2 import (DeepSeekV2Config,
                                           DeepSeekV2ForCausalLM,
                                           dropless_experts, route,
                                           softmax_scale, yarn_inv_freq)
from paddle_tpu.serving import Engine, EngineConfig, SamplingParams
from paddle_tpu.serving.kv_cache import (CacheLayout, PagedKV, PagedKVPool,
                                         kv_pair_layout)

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
REF_PATH = os.path.join(HERE, "refs", "deepseek_v2_reference.py")
_spec = importlib.util.spec_from_file_location("deepseek_v2_reference",
                                               REF_PATH)
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

ATOL = 1e-5
TINY = DeepSeekV2Config(
    vocab_size=512, hidden_size=64, intermediate_size=160,
    moe_intermediate_size=48, num_hidden_layers=3, num_attention_heads=4,
    kv_lora_rank=32, qk_nope_head_dim=24, qk_rope_head_dim=16, v_head_dim=24,
    n_routed_experts=8, num_experts_per_tok=2, n_shared_experts=1,
    max_position_embeddings=2560, rope_original_max_position=64)
REF_CFG = dict(
    num_hidden_layers=3, num_attention_heads=4, kv_lora_rank=32,
    qk_nope_head_dim=24, qk_rope_head_dim=16, v_head_dim=24,
    num_experts_per_tok=2, rms_norm_eps=1e-6, rope_theta=10000.0,
    routed_scaling_factor=1.0,
    rope_scaling=dict(factor=40.0, original_max_position_embeddings=64,
                      beta_fast=32, beta_slow=1, mscale=0.707,
                      mscale_all_dim=0.707))


def _model(seed=0, config=TINY):
    paddle.seed(seed)
    m = DeepSeekV2ForCausalLM(config)
    m.eval()
    return m


def _ref_weights(m, depth=3):
    """The program's parameters under the reference's leaf names."""
    sd = m.state_dict()

    def g(name):
        return sd[name]._data.astype(jnp.float32)

    w = {"embed": {"embed": g("model.embed_tokens.weight")},
         "final": {"norm_f": g("model.norm.weight"),
                   "lm_head": g("lm_head.weight")}}
    for i in range(depth):
        p = f"model.layers.{i}."
        lw = {"norm_attn": g(p + "input_layernorm.weight"),
              "wq": g(p + "self_attn.q_proj.weight"),
              "wkv_a": g(p + "self_attn.kv_a_proj_with_mqa.weight"),
              "norm_kv": g(p + "self_attn.kv_a_layernorm.weight"),
              "wkv_b": g(p + "self_attn.kv_b_proj.weight"),
              "wo": g(p + "self_attn.o_proj.weight"),
              "norm_mlp": g(p + "post_attention_layernorm.weight")}
        if i == 0:
            lw.update(w_gate=g(p + "mlp.gate_proj.weight"),
                      w_up=g(p + "mlp.up_proj.weight"),
                      w_down=g(p + "mlp.down_proj.weight"))
        else:
            gu = g(p + "mlp.experts_gate_up")
            f = gu.shape[-1] // 2
            lw.update(
                w_router=g(p + "mlp.gate.weight"),
                ws_gate=g(p + "mlp.shared_experts.gate_proj.weight"),
                ws_up=g(p + "mlp.shared_experts.up_proj.weight"),
                ws_down=g(p + "mlp.shared_experts.down_proj.weight"),
                we_gate=gu[..., :f], we_up=gu[..., f:],
                we_down=g(p + "mlp.experts_down"))
        w[f"layer.{i}"] = lw
    return w


def _ref_logits(m, seqs, pad_to=64):
    """Reference logits [n, L, vocab] of sequences of unequal length in
    one padded call (attention is causal: the pad changes nothing before
    it, and one length compiles the reference once)."""
    length = -(-max(len(q) for q in seqs) // pad_to) * pad_to
    ids = np.zeros((len(seqs), length), np.int32)
    for i, q in enumerate(seqs):
        ids[i, :len(q)] = q
    return np.asarray(ref.full_logits(REF_CFG, _ref_weights(m), ids))


def _ids(n, length, seed=0):
    return np.random.default_rng(seed).integers(
        0, 512, (n, length)).astype(np.int32)


@pytest.fixture(params=["xla", "pallas"])
def attn_impl(request, monkeypatch):
    """Both routes of the latent paged attention: the gather, and the
    Pallas kernel in interpret mode."""
    monkeypatch.setenv("PADDLE_TPU_PAGED_ATTN", request.param)
    return request.param


def test_reference_copies_are_identical():
    assert filecmp.cmp(REF_PATH, os.path.join(
        ROOT, "chipbench", "reference", "deepseek_v2.py"), shallow=False)


# ------------------------------------------------ (a) the full forward

def test_full_forward_matches_reference():
    m = _model()
    ids = _ids(2, 64)
    want = _ref_logits(m, ids.tolist())
    got = np.asarray(jax.jit(lambda i: m(i)._data)(ids))
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)


def test_lower_precision_fails():
    """(h) The same comparison with the program's weights and products in
    bfloat16, the nearest precision below the float32 it states here, is
    outside the tolerance by two orders."""
    m = _model()
    ids = _ids(2, 64)
    want = _ref_logits(m, ids.tolist())
    for p in m.state_dict().values():
        p._data = p._data.astype(jnp.bfloat16)
    got = np.asarray(jax.jit(lambda i: m(i)._data)(ids), np.float32)
    assert np.abs(got - want).max() > 50 * ATOL


# ---------------------- (b) prefill, then decode, through the latent pool

def _paged_logits(m, prompts, steps, tokens_after):
    """Prefill lanes of unequal length in one padded batch, then `steps`
    decode steps feeding `tokens_after[lane][step]`; returns the logits at
    each lane's last prompt position and at every decode step."""
    cfg = m.config
    n = len(prompts)
    bs, nb = 16, 4
    layout = m.kv_cache_layout()
    pool = PagedKVPool(cfg.num_hidden_layers, 1 + n * nb, bs, 1,
                       cfg.cache_row_width, jnp.float32, layout=layout)
    assert pool.v == [None] * cfg.num_hidden_layers
    tables = jnp.asarray(1 + np.arange(n * nb).reshape(n, nb), jnp.int32)
    lengths = np.asarray([len(p) for p in prompts])
    width = int(lengths.max())
    ids = np.zeros((n, width), np.int32)
    for i, p in enumerate(prompts):
        ids[i, :len(p)] = p
    @jax.jit
    def forward(ids, pools, pos):
        views = [PagedKV(k, None, tables, pos) for k in pools]
        h, views = m.model(ids, caches=views)
        assert views[1].stats is not None and views[0].stats is None
        return m._logits(h)._data, [v.k for v in views]

    logits, pools = forward(ids, pool.k, jnp.zeros(n, jnp.int32))
    logits = np.asarray(logits)
    out = [[logits[i, lengths[i] - 1]] for i in range(n)]
    pos = jnp.asarray(lengths, jnp.int32)
    for t in range(steps):
        tok = np.asarray([[tokens_after[i][t]] for i in range(n)], np.int32)
        logits, pools = forward(tok, pools, pos)
        for i in range(n):
            out[i].append(np.asarray(logits)[i, 0])
        pos = pos + 1
    return out


def test_prefill_then_decode_matches_reference(attn_impl):
    m = _model()
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 512, k).tolist() for k in (5, 23, 16)]
    after = [rng.integers(0, 512, 6).tolist() for _ in prompts]
    got = _paged_logits(m, prompts, 6, after)
    want = _ref_logits(m, [p + a for p, a in zip(prompts, after)])
    for i, p in enumerate(prompts):
        for t, row in enumerate(got[i]):
            np.testing.assert_allclose(row, want[i, len(p) - 1 + t],
                                       atol=ATOL, rtol=0)


# --------------------------------------- (c) absorbed against expanded

def test_absorbed_matches_expanded(attn_impl):
    """One attention module: the expanded form over the whole sequence
    against the absorbed form through a fresh latent pool."""
    m = _model(1)
    attn = m.model.layers[1].self_attn
    x = jnp.asarray(np.random.default_rng(5).standard_normal((2, 37, 64)),
                    jnp.float32)
    want = jax.jit(attn.forward)(x)
    pool = jnp.zeros((7, 16, 1, TINY.cache_row_width), jnp.float32)
    tables = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)

    @jax.jit
    def absorbed(x, pool):
        out, view = attn(x, PagedKV(pool, None, tables,
                                    jnp.zeros(2, jnp.int32)))
        assert view.v is None
        return out, view.k

    got, written = absorbed(x, pool)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=ATOL, rtol=0)
    # the cache holds 48 values a token and zeros in the lane pad
    written = np.asarray(written[1])
    assert np.abs(written[..., :48]).min() > 0
    assert not written[..., 48:].any()


# ------------------------------------ (d) the kernel against the gather

def _plain_attention(q, pool, tables, pos, scale, v_width):
    """Gather and softmax, lane by lane and row by row, in numpy."""
    b, s, h, w = q.shape
    bs = pool.shape[1]
    out = np.zeros((b, s, h, v_width), np.float32)
    for i in range(b):
        keys = pool[tables[i]].reshape(-1, pool.shape[-1])[:, :w]
        for r in range(s):
            vis = keys[:pos[i] + r + 1]
            sc = q[i, r] @ vis.T * scale
            p = np.exp(sc - sc.max(-1, keepdims=True))
            out[i, r] = (p / p.sum(-1, keepdims=True)) @ vis[:, :v_width]
    return out


@pytest.mark.parametrize("nb", range(1, 9))
@pytest.mark.parametrize("s,h,path", [(1, 4, "stream"), (3, 4, "stream"),
                                      (16, 64, "tile")])
def test_mla_kernel_against_plain_gather(nb, s, h, path):
    """Both geometries at table widths 1-8, with aliased blocks (two lanes
    share their first block, as a prefix hit leases it) and scratch
    entries (block 0 past a lane's length)."""
    from paddle_tpu.serving.mla_paged_attention import (
        _geometry, _pallas_mla_paged_attention)

    bs, w, wp, vw = 16, 48, 128, 32
    assert _geometry(s, h, bs, wp, vw, jnp.float32, jnp.float32)[0] == path
    rng = np.random.default_rng(100 * nb + s)
    b = 3
    pool = rng.standard_normal((1 + b * nb, bs, 1, wp)).astype(np.float32)
    pool[..., w:] = 0
    q = rng.standard_normal((b, s, h, w)).astype(np.float32)
    pos = rng.integers(0, nb * bs - s + 1, b).astype(np.int32)
    tables = np.zeros((b, nb), np.int32)
    for i in range(b):
        need = -(-(pos[i] + s) // bs)
        tables[i, :need] = 1 + i * nb + np.arange(need)
    tables[1, 0] = tables[0, 0]                  # an aliased (leased) block
    got = _pallas_mla_paged_attention(
        jnp.asarray(q), jnp.asarray(pool), jnp.asarray(tables),
        jnp.asarray(pos), scale=0.17, v_width=vw, interpret=True)
    want = _plain_attention(q, pool, tables, pos, 0.17, vw)
    np.testing.assert_allclose(np.asarray(got), want, atol=ATOL, rtol=0)


# ------------------------------ (e) the expert layer against a plain loop

def _loop_experts(x, weights, experts, w_gate_up, w_down):
    f = w_down.shape[1]
    out = np.zeros_like(x)
    for t in range(x.shape[0]):
        for j in range(experts.shape[1]):
            e = experts[t, j]
            gu = x[t] @ w_gate_up[e]
            act = gu[:f] / (1 + np.exp(-gu[:f])) * gu[f:]
            out[t] += weights[t, j] * (act @ w_down[e])
    return out


@pytest.mark.parametrize("routing", ["router", "one_takes_all"])
def test_dropless_experts_against_loop(routing):
    """No capacity: a batch in which one expert takes every row (and the
    others but one none) loses no token."""
    rng = np.random.default_rng(11)
    t, hdim, f, e, k = 40, 64, 48, 8, 2
    x = rng.standard_normal((t, hdim)).astype(np.float32)
    wgu = (rng.standard_normal((e, hdim, 2 * f)) * 0.1).astype(np.float32)
    wd = (rng.standard_normal((e, f, hdim)) * 0.1).astype(np.float32)
    if routing == "router":
        wr = (rng.standard_normal((hdim, e)) * 0.05).astype(np.float32)
        weights, experts = route(jnp.asarray(x), jnp.asarray(wr), k)
        logits = x @ wr
        p = np.exp(logits - logits.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        top = np.argsort(-p, axis=-1)[:, :k]
        assert np.array_equal(np.sort(np.asarray(experts), -1),
                              np.sort(top, -1))
        # the published router does not renormalise its top-k weights
        np.testing.assert_allclose(
            np.sort(np.asarray(weights), -1),
            np.sort(np.take_along_axis(p, top, -1), -1), atol=1e-6)
        assert float(np.asarray(weights).sum(-1).max()) < 0.9
    else:
        experts = jnp.asarray(np.stack([np.full(t, 5), np.full(t, 2)], 1),
                              jnp.int32)
        weights = jnp.asarray(rng.random((t, k)), jnp.float32)
    got, sizes = dropless_experts(jnp.asarray(x), weights, experts,
                                  jnp.asarray(wgu), jnp.asarray(wd))
    want = _loop_experts(x, np.asarray(weights), np.asarray(experts), wgu, wd)
    np.testing.assert_allclose(np.asarray(got), want, atol=ATOL, rtol=0)
    assert int(sizes.sum()) == t * k
    if routing == "one_takes_all":
        assert sizes.tolist() == [0, 0, t, 0, 0, t, 0, 0]


def test_grouped_matmul_kernel_against_ragged_dot():
    """The TPU route of the grouped product (megablox, interpreted) against
    the plain route, with an empty group and rows that do not fill a row
    tile."""
    from paddle_tpu.ops.grouped_matmul import _pallas_grouped_matmul

    rng = np.random.default_rng(2)
    lhs = jnp.asarray(rng.standard_normal((50, 64)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((4, 64, 96)) * 0.1, jnp.float32)
    sizes = jnp.asarray([20, 0, 7, 23], jnp.int32)
    got = _pallas_grouped_matmul(lhs, rhs, sizes, interpret=True)
    want = jax.lax.ragged_dot(lhs, rhs, sizes)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=ATOL, rtol=0)


# ----------------------------------------------------------- (f) YaRN

def test_yarn_numbers_by_hand():
    """The published sizes: 32 pairs of a 64-wide rotary slice, theta 1e4,
    factor 40 over 4096 positions, beta 32 / 1, mscale 0.707 both."""
    c = DeepSeekV2Config()
    # corr(r) = 64 ln(4096 / (2 pi r)) / (2 ln 1e4)
    corr = lambda r: 64 * math.log(4096 / (2 * math.pi * r)) / (
        2 * math.log(1e4))
    assert corr(32) == pytest.approx(10.4722, abs=1e-3)
    assert corr(1) == pytest.approx(22.5133, abs=1e-3)
    low, high = 10, 23                      # floor(10.47), ceil(22.51)
    inv = np.asarray(yarn_inv_freq(c))
    f = 1e4 ** (-2 * np.arange(32) / 64)
    np.testing.assert_allclose(inv[:low + 1], f[:low + 1], rtol=1e-6)
    np.testing.assert_allclose(inv[high:], f[high:] / 40, rtol=1e-6)
    # pair 16 sits 6/13 of the way up the ramp
    ramp = (16 - low) / (high - low)
    assert inv[16] == pytest.approx(f[16] / 40 * ramp + f[16] * (1 - ramp),
                                    rel=1e-6)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert m == pytest.approx(1.2608, abs=1e-4)
    assert softmax_scale(c) == pytest.approx(192 ** -0.5 * m * m, rel=1e-9)
    assert softmax_scale(c) == pytest.approx(0.11472, abs=1e-5)
    # the reference computes the same
    rc = dict(REF_CFG, qk_nope_head_dim=128, qk_rope_head_dim=64,
              rope_scaling=dict(factor=40, beta_fast=32, beta_slow=1,
                                original_max_position_embeddings=4096,
                                mscale=0.707, mscale_all_dim=0.707))
    np.testing.assert_allclose(np.asarray(ref.yarn_inv_freq(rc)), inv,
                               rtol=1e-6)
    assert ref.softmax_scale(rc) == pytest.approx(softmax_scale(c))
    assert ref.table_scale(rc) == 1.0


# ------------------------------------------------- the cache's layout

def test_cache_layout_latent_and_kv_pair():
    """A latent layer keeps one buffer and no values; a k/v model keeps
    the pair it kept before layouts existed, byte for byte."""
    m = _model()
    layout = m.kv_cache_layout()
    assert layout == CacheLayout((1, 128), buffers=1)     # 48 -> 128 lanes
    pool = PagedKVPool(3, 9, 16, 1, 128, jnp.float32, layout=layout)
    assert pool.k[0].shape == (9, 16, 1, 128) and pool.v == [None] * 3
    assert pool.bytes_per_block == 3 * 16 * 128 * 4
    old = PagedKVPool(2, 5, 4, 2, 16, jnp.float32)
    assert old.layout == kv_pair_layout(2, 16)
    assert old.k[0].shape == old.v[0].shape == (5, 4, 2, 16)
    assert old.bytes_per_block == 2 * 2 * 4 * 2 * 16 * 4
    quant = PagedKVPool(2, 5, 4, 2, 16, jnp.float32, quant_dtype="int8")
    assert quant.bytes_per_block == 2 * 2 * 4 * (2 * 16 + 4)
    with pytest.raises(ValueError, match="latent pool"):
        PagedKVPool(3, 9, 16, 1, 128, jnp.float32, quant_dtype="int8",
                    layout=layout)
    # at the published widths: 576 values a token in 640 lanes
    assert DeepSeekV2Config().cache_row_width == 640


def test_engines_that_cannot_hold_a_latent_cache_say_why():
    from paddle_tpu.serving.sharded.mesh_engine import MeshEngine

    m = _model()
    with pytest.raises(ValueError, match="one buffer a layer"):
        MeshEngine(m, EngineConfig(num_slots=2, max_seq_len=64), tp=2)
    with pytest.raises(ValueError, match="latent pool"):
        Engine(m, EngineConfig(num_slots=2, max_seq_len=64,
                               kv_cache_dtype="int8"),
               register_profiler=False)


# ------------------------------------------- (g) the engine, end to end

def _worst_gap(m, prompts, outputs):
    """Widest gap by which a served greedy token's reference logit lies
    below the reference's best."""
    lg = _ref_logits(m, [list(p) + list(o) for p, o in zip(prompts, outputs)])
    worst = 0.0
    for i, (p, o) in enumerate(zip(prompts, outputs)):
        for j, t in enumerate(o):
            row = lg[i, len(p) - 1 + j]
            worst = max(worst, float(row.max() - row[t]))
    return worst


def _post(port, payload):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    conn.request("POST", "/v1/completions", json.dumps(payload),
                 {"Content-Type": "application/json"})
    return json.loads(conn.getresponse().read())


def test_engine_behind_gateway_preemption_and_prefix_hit(monkeypatch):
    """Greedy and sampled requests through `create_llm_engine` behind the
    gateway on a pool too small for all of them: lanes are preempted and
    resumed, a repeated prompt leases its latent blocks from the radix
    store, every greedy token is the reference's choice (to the logits'
    tolerance), the routing counters and the latent gauge are published,
    and no block leaks."""
    from paddle_tpu.inference import create_llm_engine
    from paddle_tpu.observability import metrics
    from paddle_tpu.serving.gateway import Gateway, GatewayConfig

    monkeypatch.setenv("PADDLE_TPU_PAGED_ATTN", "pallas")  # interpreted
    m = _model()
    eng = create_llm_engine(m, num_slots=3, max_seq_len=128, max_horizon=4,
                            kv_pool_blocks=8, prefix_cache_bytes=1 << 20)
    rng = np.random.default_rng(9)
    shared = rng.integers(0, 512, 40).tolist()
    prompts = [shared, rng.integers(0, 512, 21).tolist(),
               rng.integers(0, 512, 30).tolist()]
    with Gateway([eng], GatewayConfig(model_id="tiny-dsv2")) as gw:
        import concurrent.futures as cf

        with cf.ThreadPoolExecutor(3) as ex:
            docs = list(ex.map(lambda p: _post(gw.port, {
                "prompt": p, "max_tokens": 20}), prompts))
        outs = [d["choices"][0]["token_ids"] for d in docs]
        assert all(len(o) == 20 for o in outs)
        again = _post(gw.port, {"prompt": shared, "max_tokens": 20})
        sampled = _post(gw.port, {"prompt": prompts[1], "max_tokens": 8,
                                  "temperature": 0.8, "top_p": 0.9,
                                  "seed": 3})
        assert len(sampled["choices"][0]["token_ids"]) == 8
    c = eng.counters()
    assert c["preemptions"] >= 1
    assert eng.stats()["prefix_hit_tokens"] >= 32       # two leased blocks
    assert _worst_gap(m, prompts + [shared],
                      outs + [again["choices"][0]["token_ids"]]) <= ATOL
    s = eng.stats()
    assert s["kv_pool"]["buffers_per_layer"] == 1
    assert s["kv_pool"]["leased_blocks"] == 0
    # one record: stats() reads back the registry counters the model named
    name, ls = eng._profiler_name, s["layer_stats"]
    assert tuple(ls) == m.layer_stat_names
    for kind in ("prefill", "decode"):
        assert ls["moe.rows"][kind] == sum(
            metrics.value("moe.rows", engine=name, layer=i, kind=kind)
            for i in range(2)) > 0
        # 8 experts: the busiest takes at least an eighth, at most all
        assert ls["moe.rows"][kind] / 8 <= ls["moe.rows_max_expert"][kind] \
            <= ls["moe.rows"][kind]
        assert ls["moe.experts_touched"][kind] > 0
    assert metrics.value("kv.latent_blocks_live", engine=name) is not None
    eng.close()


def test_host_tier_moves_latent_blocks():
    """Preempted lanes swap out to the host arena and back in as latent
    blocks (one buffer a block, a zero-width value plane), and the resumed
    streams are the streams of an engine that recomputed."""
    m = _model()
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 512, 19).tolist() for _ in range(2)]

    def run(**kw):
        eng = Engine(m, EngineConfig(num_slots=2, max_seq_len=128,
                                     max_horizon=2, **kw),
                     register_profiler=False)
        reqs = [eng.submit(p, SamplingParams(max_new_tokens=16))
                for p in prompts]
        for _ in range(3):
            eng.step()
        eng.preempt(reqs[1])
        eng.run()
        c = eng.counters()
        eng.close()
        return [r.output_ids for r in reqs], c

    want, _ = run()
    got, c = run(kv_host_bytes=1 << 20, kv_swap_policy="always")
    assert c["kv_swap_outs"] >= 1 and c["kv_swap_ins"] >= 1
    assert got == want
    assert _worst_gap(m, prompts, got) <= ATOL
