"""Xing4.0 family on the CPU at a small size (hidden 64, four residual
streams, 3 layers of which the first 2 dense, 8 experts top-2 with a shared
expert, query rank 32, latent 32 + rotary 16, 4 heads, vocabulary 512;
Pallas in interpret mode), seeded random weights: the program against the
plain reference `chipbench/reference/xing4.py`, loaded by its path.

Tolerances, each with its reason.  Program and reference are both float32 on
the CPU and differ by the order of their sums only (the streams' products,
Sinkhorn's twenty rounds and the latent kernel included): ``ATOL = 1e-5`` on
logits of magnitude 0.1-1, and one bfloat16 rounding of the weights moves
them by more than fifty times that (``test_lower_precision_fails``).
"""

import http.client
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models.deepseek_v2 import DeepSeekV2MLP
from paddle_tpu.models.xing4 import (HyperConnection, Xing4Attention,
                                     Xing4Config, Xing4DecoderLayer,
                                     Xing4ForCausalLM, route_sigmoid,
                                     sinkhorn)
from paddle_tpu.serving.kv_cache import PagedKV, PagedKVPool

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:         # the reference imports the DeepSeek-V2 one
    sys.path.insert(0, ROOT)
REF_PATH = os.path.join(ROOT, "chipbench", "reference", "xing4.py")
_spec = importlib.util.spec_from_file_location("xing4_reference", REF_PATH)
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

ATOL = 1e-5
TINY = Xing4Config(
    vocab_size=512, hidden_size=64, intermediate_size=160,
    moe_intermediate_size=48, num_hidden_layers=3, num_attention_heads=4,
    q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=24,
    qk_rope_head_dim=16, v_head_dim=24, n_routed_experts=8,
    num_experts_per_tok=2, n_shared_experts=1, first_k_dense_replace=2,
    max_position_embeddings=2560, rope_original_max_position=64)
REF_CFG = dict(
    num_hidden_layers=3, num_attention_heads=4, kv_lora_rank=32,
    qk_nope_head_dim=24, qk_rope_head_dim=16, v_head_dim=24,
    num_experts_per_tok=2, rms_norm_eps=1e-6, rope_theta=10000.0,
    routed_scaling_factor=2.0, hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6,
    mhc_h_res_clamp_min=-30.0, mhc_h_res_clamp_max=30.0,
    rope_scaling=dict(factor=64.0, original_max_position_embeddings=64,
                      beta_fast=32, beta_slow=1, mscale=1.0,
                      mscale_all_dim=1.0))


def _model(seed=0, config=TINY):
    paddle.seed(seed)
    m = Xing4ForCausalLM(config)
    m.eval()
    # the router's bias at the scale of the scores' spread, so that it
    # moves choices in these tests
    rng = np.random.default_rng(seed)
    for layer in m.model.layers:
        if layer.is_moe:
            b = layer.mlp.e_score_correction_bias
            b._data = jnp.asarray(rng.normal(0, 0.1, b._data.shape),
                                  jnp.float32)
    return m


def _ref_weights(m):
    """The program's parameters under the reference's leaf names."""
    sd = m.state_dict()

    def g(name):
        return sd[name]._data.astype(jnp.float32)

    w = {"embed": {"embed": g("model.embed_tokens.weight")},
         "final": {"norm_f": g("model.norm.weight"),
                   "lm_head": g("lm_head.weight")}}
    for i, layer in enumerate(m.model.layers):
        p = f"model.layers.{i}."
        lw = {"norm_attn": g(p + "input_layernorm.weight"),
              "wq_a": g(p + "self_attn.q_a_proj.weight"),
              "norm_q": g(p + "self_attn.q_a_layernorm.weight"),
              "wq_b": g(p + "self_attn.q_b_proj.weight"),
              "wkv_a": g(p + "self_attn.kv_a_proj_with_mqa.weight"),
              "norm_kv": g(p + "self_attn.kv_a_layernorm.weight"),
              "wkv_b": g(p + "self_attn.kv_b_proj.weight"),
              "wo": g(p + "self_attn.o_proj.weight"),
              "norm_mlp": g(p + "post_attention_layernorm.weight")}
        for s in ("attn", "ffn"):
            for leaf in ("phi", "alpha", "bias"):
                lw[f"hc_{s}_{leaf}"] = g(p + f"{s}_hc.{leaf}")
        if layer.is_moe:
            gu = g(p + "mlp.experts_gate_up")
            f = gu.shape[-1] // 2
            lw.update(
                w_router=g(p + "mlp.gate.weight"),
                router_bias=g(p + "mlp.e_score_correction_bias"),
                ws_gate=g(p + "mlp.shared_experts.gate_proj.weight"),
                ws_up=g(p + "mlp.shared_experts.up_proj.weight"),
                ws_down=g(p + "mlp.shared_experts.down_proj.weight"),
                we_gate=gu[..., :f], we_up=gu[..., f:],
                we_down=g(p + "mlp.experts_down"))
        else:
            lw.update(w_gate=g(p + "mlp.gate_proj.weight"),
                      w_up=g(p + "mlp.up_proj.weight"),
                      w_down=g(p + "mlp.down_proj.weight"))
        w[f"layer.{i}"] = lw
    return w


def _ref_logits(m, seqs, pad_to=64):
    """Reference logits [n, L, vocab] of sequences of unequal length in one
    padded call (attention is causal: the pad changes nothing before it)."""
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


# ------------------------------------------------ (a) the full forward

def test_full_forward_matches_reference():
    m = _model()
    ids = _ids(2, 64)
    want = _ref_logits(m, ids.tolist())
    got = np.asarray(jax.jit(lambda i: m(i)._data)(ids))
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)


# ---------------------- (b) prefill, then decode, through the latent pool

def test_prefill_then_decode_matches_reference(attn_impl):
    """The paged forward the engine calls: lanes of unequal length
    prefilled in one padded batch, then six decode steps, each position's
    logits against the reference's full forward."""
    m = _model()
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 512, k).tolist() for k in (5, 23, 16)]
    after = [rng.integers(0, 512, 6).tolist() for _ in prompts]
    n, nb = len(prompts), 4
    pool = PagedKVPool(3, 1 + n * nb, 16, 1, TINY.cache_row_width,
                       jnp.float32, layout=m.kv_cache_layout())
    tables = jnp.asarray(1 + np.arange(n * nb).reshape(n, nb), jnp.int32)

    @jax.jit
    def forward(ids, pools, pos):
        views = [PagedKV(k, None, tables, pos) for k in pools]
        h, views = m.model(ids, caches=views)
        assert [v.stats is None for v in views] == [True, True, False]
        return m._logits(h)._data, [v.k for v in views]

    lengths = np.asarray([len(p) for p in prompts])
    ids = np.zeros((n, lengths.max()), np.int32)
    for i, p in enumerate(prompts):
        ids[i, :len(p)] = p
    logits, pools = forward(ids, pool.k, jnp.zeros(n, jnp.int32))
    got = [[np.asarray(logits)[i, lengths[i] - 1]] for i in range(n)]
    pos = jnp.asarray(lengths, jnp.int32)
    for t in range(6):
        tok = np.asarray([[after[i][t]] for i in range(n)], np.int32)
        logits, pools = forward(tok, pools, pos)
        for i in range(n):
            got[i].append(np.asarray(logits)[i, 0])
        pos = pos + 1
    want = _ref_logits(m, [p + a for p, a in zip(prompts, after)])
    for i, p in enumerate(prompts):
        for t, row in enumerate(got[i]):
            np.testing.assert_allclose(row, want[i, len(p) - 1 + t],
                                       atol=ATOL, rtol=0)


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


def test_engine_behind_gateway_matches_reference(attn_impl):
    """Greedy requests through `create_llm_engine` -> `Engine` behind the
    gateway: every served token is the reference's choice to the logits'
    tolerance, the latent pool is the one the model states, the routing
    counters are published under the names the model gives them, and no
    block leaks."""
    import concurrent.futures as cf

    from paddle_tpu.inference import create_llm_engine
    from paddle_tpu.serving.gateway import Gateway, GatewayConfig

    m = _model()
    eng = create_llm_engine(m, num_slots=3, max_seq_len=128, max_horizon=4)
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, 512, k).tolist() for k in (40, 21, 30)]
    with Gateway([eng], GatewayConfig(model_id="tiny-xing4")) as gw:
        with cf.ThreadPoolExecutor(3) as ex:
            docs = list(ex.map(lambda p: _post(gw.port, {
                "prompt": p, "max_tokens": 12}), prompts))
    outs = [d["choices"][0]["token_ids"] for d in docs]
    assert all(len(o) == 12 for o in outs)
    assert _worst_gap(m, prompts, outs) <= ATOL
    s = eng.stats()
    assert s["kv_pool"]["buffers_per_layer"] == 1
    assert s["kv_pool"]["leased_blocks"] == 0
    ls = s["layer_stats"]
    assert tuple(ls) == m.layer_stat_names
    for kind in ("prefill", "decode"):
        assert ls["moe.rows"][kind] > 0 and ls["moe.experts_touched"][kind] > 0
    eng.close()


# ------------------------------------------------------------ (c) Sinkhorn

def test_sinkhorn_is_doubly_stochastic():
    """Twenty rounds on logits of standard deviation 0.5: rows and columns
    sum to 1 within 1e-5 (each normalisation divides by the sum + 1e-6, so
    1 - 1e-6 is what a converged row reads).  Sharper logits converge more
    slowly: at a spread of 2 the rows of a token in three are still more
    than 1e-5 off after twenty rounds, while the columns, normalised last,
    are not; program and reference run the same twenty rounds."""
    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.normal(0, 0.5, (256, 4, 4)), jnp.float32)
    m = np.asarray(sinkhorn(logits, 20, 1e-6))
    np.testing.assert_allclose(m.sum(-1), 1.0, atol=1e-5)
    np.testing.assert_allclose(m.sum(-2), 1.0, atol=1e-5)
    assert (m > 0).all()
    sharp = jnp.asarray(rng.normal(0, 2, (256, 4, 4)), jnp.float32)
    m = np.asarray(sinkhorn(sharp, 20, 1e-6))
    np.testing.assert_allclose(m.sum(-2), 1.0, atol=1e-5)
    assert np.abs(m.sum(-1) - 1).max() > 1e-3
    np.testing.assert_allclose(
        m, np.asarray(ref.sinkhorn(sharp, 20, 1e-6)), atol=1e-6)


def test_mixing_logits_of_1e4_stay_finite_through_the_clamp():
    """h_res of +-1e4 (exp would overflow to inf and Sinkhorn to nan) is
    clamped to +-30 first: every mapping finite, the columns normalised."""
    c = Xing4Config(hidden_size=8, hc_mult=4, num_hidden_layers=1)
    paddle.seed(0)
    hc = HyperConnection(c)
    signs = np.where(np.random.default_rng(2).random(16) < 0.5, -1.0, 1.0)
    hc.bias._data = jnp.concatenate([jnp.zeros(8), jnp.asarray(1e4 * signs)
                                     ]).astype(jnp.float32)
    x = jnp.asarray(np.random.default_rng(3).normal(size=(2, 5, 4, 8)),
                    jnp.float32)
    pre, post, res = (np.asarray(a) for a in hc.mappings(x))
    for a in (pre, post, res):
        assert np.isfinite(a).all()
    np.testing.assert_allclose(res.sum(-2), 1.0, atol=1e-5)
    assert not np.isfinite(np.asarray(sinkhorn(
        jnp.asarray(1e4 * signs.reshape(4, 4), jnp.float32), 20, 1e-6))).all()


# ------------------ (d) one stream and no mapping is the plain residual

def test_one_stream_without_mappings_is_the_prenorm_residual():
    """n = 1, alpha = b = 0: H_pre = 1/2 (which the sublayer's RMSNorm
    removes), H_post = 1, H_res = 1 less 1e-6 (Sinkhorn's epsilon), so
    the layer is x + F(RMSNorm(x)) for each sublayer.  Tolerance 1e-5 on
    values of order one: the epsilons move them by a few 1e-6."""
    c = Xing4Config(**{**TINY.__dict__, "hc_mult": 1})
    paddle.seed(4)
    layer = Xing4DecoderLayer(c, 2)               # an expert layer
    for hc in (layer.attn_hc, layer.ffn_hc):
        hc.alpha._data = jnp.zeros_like(hc.alpha._data)
        hc.bias._data = jnp.zeros_like(hc.bias._data)
    x = jnp.asarray(np.random.default_rng(5).normal(size=(2, 9, 64)),
                    jnp.float32)

    @jax.jit
    def both(x):
        got, _ = layer(x[:, :, None, :])
        eps = c.rms_norm_eps

        def rms(v, w):
            return v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True)
                                     + eps) * w

        h = x + layer.self_attn(rms(x, layer.input_layernorm.weight._data))
        out, _ = layer.mlp(rms(h, layer.post_attention_layernorm.weight._data))
        return got[:, :, 0], h + out

    got, want = both(x)
    assert float(jnp.abs(want - x).max()) > 0.01     # the sublayers add
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5,
                               rtol=0)


# ------------------------------------- (e) the router against a loop

def test_router_against_a_loop():
    """The bias chooses (the top k of score + bias), the weights are the
    chosen scores, renormalised to 1 and scaled by 2; a bias that changes
    a token's choice changes only which scores are weighed."""
    rng = np.random.default_rng(11)
    t, h, e, k = 40, 64, 8, 2
    x = rng.standard_normal((t, h)).astype(np.float32)
    wr = (rng.standard_normal((h, e)) * 0.05).astype(np.float32)
    bias = (rng.standard_normal(e) * 0.1).astype(np.float32)
    weights, experts = route_sigmoid(jnp.asarray(x), jnp.asarray(wr),
                                     jnp.asarray(bias), k, 2.0)
    weights, experts = np.asarray(weights), np.asarray(experts)
    moved = 0
    for i in range(t):
        s = 1 / (1 + np.exp(-(x[i] @ wr)))
        top = np.argsort(-(s + bias), kind="stable")[:k]
        assert sorted(experts[i]) == sorted(top)
        want = dict(zip(top, 2.0 * s[top] / s[top].sum()))
        for j in range(k):
            assert weights[i, j] == pytest.approx(want[experts[i, j]],
                                                  rel=1e-6)
        moved += sorted(np.argsort(-s, kind="stable")[:k]) != sorted(top)
    assert moved > 0                          # the bias changed choices
    np.testing.assert_allclose(weights.sum(-1), 2.0, rtol=1e-6)


# ----------------------- (f) the seeded mappings: not saturated, not trivial

def test_seeded_mappings_are_neither_saturated_nor_trivial():
    """One sublayer's phi, alpha and bias drawn by the benchmark's rule
    (`chipbench/weights_xing4.py`) at the published widths (4 streams of
    3,584), applied to streams of random direction: H_pre and H_post stay
    off their bounds, and H_res is neither the identity nor uniform."""
    from chipbench import weights_xing4 as W

    cfg = {"hc_mult": 4, "hidden_size": 3584,
           "assumed": {"initializer_std": 0.02}}
    key = jax.random.PRNGKey(7)
    leaves = W._hc_leaves(cfg, "")
    c = Xing4Config(hidden_size=3584, hc_mult=4, num_hidden_layers=1)
    paddle.seed(0)
    hc = HyperConnection(c)
    for j, (leaf, (shape, what)) in enumerate(leaves.items()):
        z = jax.random.normal(jax.random.fold_in(key, j), shape, jnp.float32)
        getattr(hc, leaf)._data = W._draw(cfg, z, what)
    x = jnp.asarray(np.random.default_rng(8).normal(size=(4, 64, 4, 3584)),
                    jnp.float32)
    pre, post, res = (np.asarray(a) for a in hc.mappings(x))
    assert ((pre > 0.02) & (pre < 0.98)).mean() > 0.95
    assert ((post > 0.04) & (post < 1.96)).mean() > 0.95
    assert pre.std() > 0.1 and post.std() > 0.2
    diag = np.diagonal(res, axis1=-2, axis2=-1)
    assert 0.35 < diag.mean() < 0.9           # identity 1, uniform 0.25
    assert np.abs(res - res.mean(0)).mean() > 0.01    # varies by token


# ---------------------------------- (g) a lower precision fails

def test_lower_precision_fails():
    """The comparison of (a) with the program's weights and products in
    bfloat16, the nearest precision below the float32 it states here, is
    outside the tolerance by more than fifty times."""
    m = _model()
    ids = _ids(2, 64)
    want = _ref_logits(m, ids.tolist())
    for p in m.state_dict().values():
        p._data = p._data.astype(jnp.bfloat16)
    got = np.asarray(jax.jit(lambda i: m(i)._data)(ids), np.float32)
    assert np.abs(got - want).max() > 50 * ATOL


# ------------------------------------------------------ the module's shape

def test_query_compression_and_layer_kinds():
    """The attention has no q_proj but q_a -> norm -> q_b; the first two
    layers are dense, the third routes; the cache row is DeepSeek-V2's."""
    m = _model()
    attn = m.model.layers[0].self_attn
    assert isinstance(attn, Xing4Attention) and not hasattr(attn, "q_proj")
    assert attn.q_a_proj.weight._data.shape == (64, 32)
    assert attn.q_b_proj.weight._data.shape == (32, 4 * 40)
    assert [layer.is_moe for layer in m.model.layers] == [False, False, True]
    assert isinstance(m.model.layers[1].mlp, DeepSeekV2MLP)
    assert m.model.layers[0].attn_hc.phi._data.shape == (256, 24)
    assert m.kv_cache_layout().token_shape == (1, 128)
    c = Xing4Config()
    assert (c.hc_width, c.cache_row_width, c.q_lora_rank) == (24, 640, 768)
