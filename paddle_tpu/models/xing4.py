"""Xing4.0 decoder family (XingChen-AGI 2026; `model_type: xing4_0`): the
DeepSeek-V2 block of ``deepseek_v2.py`` with three changes.

* **Manifold-constrained hyper-connections (mHC;** Xie et al. 2025,
  arXiv:2512.24880**).**  Between layers a token is ``n = hc_mult`` residual
  streams X [n, C], not one vector.  Every sublayer F (attention, then the
  FFN) reads one input out of the streams and writes its output back into
  all of them through three mappings computed from the token itself:

      x^ = RMSNorm(vec X)                      (over n·C values, no weight)
      [h_pre | h_post | h_res] = alpha ⊙ (x^ phi) + b     (float32)
      H_pre  = sigmoid(h_pre)                  [1, n]   read-in
      H_post = 2 sigmoid(h_post)               [1, n]   write-back
      H_res  = Sinkhorn(clamp(h_res, ±30))     [n, n]   the streams mixed
      X <- H_res X + H_post^T F(RMSNorm_F(H_pre X))

  Sinkhorn projects exp(h_res) onto the doubly stochastic matrices by
  ``hc_sinkhorn_iters`` rounds of normalising rows, then columns, each
  divided by its sum + ``hc_eps``.  The embedding enters as n copies; after
  the last layer the streams are summed and normed before the head.  The
  streams are stored in the model's dtype; the mappings and the mixing are
  computed in float32.
* **Query compression** (`q_lora_rank`): q = W_qb RMSNorm(W_qa x) instead
  of one ``q_proj``.  Everything after the query is MLA as in
  ``deepseek_v2.py``: the expanded form without a cache, the absorbed form
  through ``serving/mla_paged_attention.py`` with one.
* **Sigmoid routing with a bias correction** (`scoring_func` sigmoid,
  `topk_method` noaux_tc, one group): experts are chosen by score + bias
  (the bias steers the choice only); their scores are renormalised to sum
  1 and multiplied by `routed_scaling_factor`.

Served through ``create_llm_engine`` -> ``serving.Engine`` with the latent
layout, the paged forward and the routing counters of the DeepSeek-V2
family, which this module imports and does not change.  Multi-token
prediction (`num_nextn_predict_layers`) is not here.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..nn.initializer import Constant, Normal
from ..nn.layer.common import Embedding, Linear
from ..nn.layer.container import LayerList
from ..nn.layer.layers import Layer
from ..nn.layer.norm import RMSNorm
from .deepseek_v2 import (DeepSeekV2Attention, DeepSeekV2Config,
                          DeepSeekV2ForCausalLM, DeepSeekV2MLP, DeepSeekV2MoE,
                          _init, _rms, _rope, dropless_experts)


@dataclass
class Xing4Config(DeepSeekV2Config):
    vocab_size: int = 131072
    hidden_size: int = 3584
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 40
    num_attention_heads: int = 32
    q_lora_rank: int = 768
    n_routed_experts: int = 64
    num_experts_per_tok: int = 4
    n_shared_experts: int = 1
    first_k_dense_replace: int = 2
    routed_scaling_factor: float = 2.0
    max_position_embeddings: int = 262144
    rope_factor: float = 64.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    #: mHC: residual streams, Sinkhorn rounds, the epsilon of each
    #: normalisation, and the clamp of h_res
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0

    @property
    def hc_width(self):
        """Outputs of phi: n read-in, n write-back and n x n mixing logits."""
        return 2 * self.hc_mult + self.hc_mult ** 2


# ------------------------------------------------------------------- mHC

def sinkhorn(logits, iters, eps):
    """exp(logits) [..., n, n] projected towards the doubly stochastic
    matrices: `iters` rounds of rows, then columns, each divided by its sum
    + eps.  Float32."""
    m = jnp.exp(logits.astype(jnp.float32))
    for _ in range(iters):
        m = m / (jnp.sum(m, -1, keepdims=True) + eps)
        m = m / (jnp.sum(m, -2, keepdims=True) + eps)
    return m


class HyperConnection(Layer):
    """The three mappings of one sublayer and how they read and write the
    streams.  phi [n·C, 2n + n²], alpha [3] (one scale a mapping), bias
    [2n + n²]."""

    def __init__(self, config: Xing4Config):
        super().__init__()
        c = self.config = config
        n = c.hc_mult
        off = c.initializer_range is None
        self.phi = self.create_parameter(
            [n * c.hidden_size, c.hc_width], default_initializer=_init(c))
        self.alpha = self.create_parameter(
            [3], default_initializer=Constant(0.0 if off else 1.0))
        self.bias = self.create_parameter(
            [c.hc_width], default_initializer=(Constant(0.0) if off
                                               else Normal(0.0, 1.0)))

    def mappings(self, x):
        """x [B, s, n, C] -> H_pre [B, s, n], H_post [B, s, n], H_res
        [B, s, n, n], float32."""
        c = self.config
        n = c.hc_mult
        with jax.named_scope("mhc.coeff"):
            flat = x.reshape(x.shape[:2] + (-1,)).astype(jnp.float32)
            flat = flat * jax.lax.rsqrt(
                jnp.mean(flat * flat, -1, keepdims=True) + c.rms_norm_eps)
            h = jnp.matmul(flat, self.phi._data.astype(jnp.float32),
                           precision=jax.lax.Precision.HIGHEST)
            a = self.alpha._data.astype(jnp.float32)
            scale = jnp.repeat(a, jnp.asarray([n, n, n * n]),
                               total_repeat_length=c.hc_width)
            h = h * scale + self.bias._data.astype(jnp.float32)
            pre = jax.nn.sigmoid(h[..., :n])
            post = 2.0 * jax.nn.sigmoid(h[..., n:2 * n])
            res = jnp.clip(h[..., 2 * n:], c.mhc_h_res_clamp_min,
                           c.mhc_h_res_clamp_max)
            res = sinkhorn(res.reshape(res.shape[:-1] + (n, n)),
                           c.hc_sinkhorn_iters, c.hc_eps)
        return pre, post, res

    # the read-in and the mixing contract over n = 4 streams: elementwise
    # products and sums in float32 (a dot here would be a bf16 pass on the
    # TPU at default precision, and an MXU tile 4 deep)

    @staticmethod
    def read_in(x, pre):
        """H_pre X: the sublayer's input [B, s, C] in the streams' dtype."""
        with jax.named_scope("mhc.mix"):
            xf = x.astype(jnp.float32)
            return jnp.sum(pre[..., None] * xf, 2).astype(x.dtype)

    @staticmethod
    def write_back(x, y, post, res):
        """H_res X + H_post^T y, stored in the streams' dtype."""
        with jax.named_scope("mhc.mix"):
            xf = x.astype(jnp.float32)
            mixed = jnp.sum(res[..., None] * xf[:, :, None], 3)
            out = mixed + post[..., None] * y.astype(jnp.float32)[:, :, None]
            return out.astype(x.dtype)


# ------------------------------------------------------------- attention

class Xing4Attention(DeepSeekV2Attention):
    """MLA with query compression: q_a_proj -> RMSNorm -> q_b_proj.  The
    paged path (write-before-attend, the absorbed form, the latent kernel)
    is DeepSeekV2Attention's."""

    def __init__(self, config: Xing4Config):
        Layer.__init__(self)
        c = self.config = config
        init = _init(c)
        nh = c.num_attention_heads

        def lin(i, o):
            return Linear(i, o, weight_attr=init, bias_attr=False)

        self.q_a_proj = lin(c.hidden_size, c.q_lora_rank)
        self.q_a_layernorm = RMSNorm(c.q_lora_rank, c.rms_norm_eps)
        self.q_b_proj = lin(c.q_lora_rank,
                            nh * (c.qk_nope_head_dim + c.qk_rope_head_dim))
        self.kv_a_proj_with_mqa = lin(c.hidden_size, c.latent_width)
        self.kv_a_layernorm = RMSNorm(c.kv_lora_rank, c.rms_norm_eps)
        self.kv_b_proj = lin(c.kv_lora_rank,
                             nh * (c.qk_nope_head_dim + c.v_head_dim))
        self.o_proj = lin(nh * c.v_head_dim, c.hidden_size)

    def _project(self, x, pos_ids):
        c = self.config
        b, s, _ = x.shape
        nh, dn, r = c.num_attention_heads, c.qk_nope_head_dim, c.kv_lora_rank
        qa = _rms(x @ self.q_a_proj.weight._data,
                  self.q_a_layernorm.weight._data, c.rms_norm_eps)
        q = (qa @ self.q_b_proj.weight._data).reshape(b, s, nh, -1)
        q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], pos_ids, c)],
                            -1)
        a = x @ self.kv_a_proj_with_mqa.weight._data
        lat = _rms(a[..., :r], self.kv_a_layernorm.weight._data,
                   c.rms_norm_eps)
        k_r = _rope(a[..., r:], pos_ids, c)
        w_b = self.kv_b_proj.weight._data.reshape(r, nh, -1)
        return q, lat, k_r, w_b


# ------------------------------------------------------------------ FFN

def route_sigmoid(x, w_router, bias, k, scaling=1.0):
    """The published router on rows x [T, h]: sigmoid scores in float32,
    the k experts of largest score + bias (the bias chooses, it does not
    weigh), their scores renormalised to sum 1 and scaled.  Returns
    (weights [T, k] f32, experts [T, k] int32)."""
    logits = jnp.matmul(x.astype(jnp.float32), w_router.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, e = jax.lax.top_k(scores + bias.astype(jnp.float32), k)
    w = jnp.take_along_axis(scores, e, -1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return w * scaling, e.astype(jnp.int32)


class Xing4MoE(DeepSeekV2MoE):
    """`MoE(x) = SwiGLU_shared(x) + sum_{e in top-k} w_e SwiGLU_e(x)`: the
    DeepSeek-V2 layer's parameters, the router's bias, and its routing."""

    def __init__(self, config: Xing4Config):
        super().__init__(config)
        self.e_score_correction_bias = self.create_parameter(
            [config.n_routed_experts], default_initializer=_init(config))

    def forward(self, x):
        """x [B, s, h] -> ([B, s, h], stats int32 [3]: rows routed, rows to
        the busiest expert, distinct experts touched)."""
        c = self.config
        b, s, h = x.shape
        flat = x.reshape(b * s, h)
        with jax.named_scope("moe.route"):
            weights, experts = route_sigmoid(
                flat, self.gate.weight._data,
                self.e_score_correction_bias._data, c.num_experts_per_tok,
                c.routed_scaling_factor)
        with jax.named_scope("moe.experts"):
            routed, sizes = dropless_experts(
                flat, weights, experts, self.experts_gate_up._data,
                self.experts_down._data)
        with jax.named_scope("moe.shared"):
            shared = self.shared_experts(flat)
        stats = jnp.stack([jnp.sum(sizes), jnp.max(sizes),
                           jnp.sum((sizes > 0).astype(jnp.int32))])
        return (routed + shared).reshape(b, s, h), stats


# -------------------------------------------------------------- the model

class Xing4DecoderLayer(Layer):
    def __init__(self, config: Xing4Config, layer_idx):
        super().__init__()
        c = config
        self.eps = c.rms_norm_eps
        self.attn_hc = HyperConnection(c)
        self.input_layernorm = RMSNorm(c.hidden_size, c.rms_norm_eps)
        self.self_attn = Xing4Attention(c)
        self.ffn_hc = HyperConnection(c)
        self.post_attention_layernorm = RMSNorm(c.hidden_size,
                                                c.rms_norm_eps)
        self.is_moe = layer_idx >= c.first_k_dense_replace
        self.mlp = (Xing4MoE(c) if self.is_moe
                    else DeepSeekV2MLP(c, c.intermediate_size))

    def forward(self, x, cache=None):
        """x: the streams [B, s, n, C]."""
        pre, post, res = self.attn_hc.mappings(x)
        h = _rms(self.attn_hc.read_in(x, pre),
                 self.input_layernorm.weight._data, self.eps)
        new_cache = None
        if cache is not None:
            h, new_cache = self.self_attn(h, cache)
        else:
            h = self.self_attn(h)
        x = self.attn_hc.write_back(x, h, post, res)
        pre, post, res = self.ffn_hc.mappings(x)
        h = _rms(self.ffn_hc.read_in(x, pre),
                 self.post_attention_layernorm.weight._data, self.eps)
        if self.is_moe:
            h, stats = self.mlp(h)
            if new_cache is not None:
                new_cache.stats = stats
        else:
            h = self.mlp(h)
        return self.ffn_hc.write_back(x, h, post, res), new_cache


class Xing4Model(Layer):
    def __init__(self, config: Xing4Config):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=_init(config))
        self.layers = LayerList([Xing4DecoderLayer(config, i)
                                 for i in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, config.rms_norm_eps)

    def forward(self, input_ids, attn_mask=None, caches=None):
        """input_ids [B, s] -> hidden [B, s, h]; with `caches` (one
        ``PagedKV`` view of the latent pool a layer) -> (hidden, new
        views), the contract ``serving.Engine`` calls."""
        c = self.config
        ids = input_ids._data if isinstance(input_ids, Tensor) else input_ids
        e = self.embed_tokens.weight._data[ids]
        x = jnp.broadcast_to(e[:, :, None, :], e.shape[:2] + (
            c.hc_mult, e.shape[-1]))
        new_caches = [] if caches is not None else None
        for i, layer in enumerate(self.layers):
            x, nc = layer(x, None if caches is None else caches[i])
            if caches is not None:
                new_caches.append(nc)
        with jax.named_scope("mhc.mix"):
            merged = jnp.sum(x.astype(jnp.float32), 2)
        h = _rms(merged, self.norm.weight._data, c.rms_norm_eps)
        x = Tensor(h.astype(e.dtype))
        return (x, new_caches) if caches is not None else x


class Xing4ForCausalLM(DeepSeekV2ForCausalLM):
    """The head, the latent cache layout and the routing counters' names
    are DeepSeekV2ForCausalLM's."""

    def __init__(self, config: Xing4Config):
        Layer.__init__(self)
        self.config = config
        self.model = Xing4Model(config)
        self.lm_head = Linear(config.hidden_size, config.vocab_size,
                              weight_attr=_init(config), bias_attr=False)
