"""DeepSeek-V2 decoder family (DeepSeek-AI 2024; `model_type: deepseek_v2`):
multi-head latent attention (MLA), YaRN rotary frequencies, leading dense
SwiGLU layers and then mixture-of-experts layers with shared experts.

Served through ``create_llm_engine`` -> ``serving.Engine``: the model states
its cache layout (``kv_cache_layout``: ONE latent buffer a layer, no value
buffer) and its paged forward keeps ``[c ‖ k_r]`` a token a layer — the
normed compressed latent and the rotated key all heads share.  MLA is one
mathematics in two forms:

* **expanded** (``caches=None``: the plain full forward): keys and values of
  every head are expanded from the latent and attended as plain attention
  with 192-wide keys and 128-wide values;
* **absorbed** (the paged path, prefill windows and decode alike): the key
  up-projection is folded into the query (``q_lat = q_n W_uk^T``), every
  head attends over the one cached latent head through
  ``serving/mla_paged_attention.py``, and the value up-projection is applied
  to the attended latent.  Nothing is ever expanded for a cached token.  A
  prefill window absorbs too, although that costs 2,176 operations a
  query-key-head against the expanded form's 640: a window that follows a
  prefix hit, a chunk or a preemption has cached tokens whose keys the
  expanded form would have to rebuild from the pool, and one kernel for
  every window keeps what a lane wrote and what it reads the same
  arithmetic; at the prompt lengths served the attention is a tenth of a
  layer's work either way.

The expert layer is dropless: rows are sorted by expert and multiplied by a
grouped product (``ops/grouped_matmul.py``); there is no capacity and no
token is dropped at any load.  Training this family (backward of the grouped
product, the balance losses) is not here: the forward runs without a tape.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..nn.initializer import Constant, Normal
from ..nn.layer.common import Embedding, Linear
from ..nn.layer.container import LayerList
from ..nn.layer.layers import Layer
from ..nn.layer.norm import RMSNorm
from ..ops.grouped_matmul import grouped_matmul


@dataclass
class DeepSeekV2Config:
    vocab_size: int = 102400
    hidden_size: int = 2048
    intermediate_size: int = 10944
    moe_intermediate_size: int = 1408
    num_hidden_layers: int = 27
    num_attention_heads: int = 16
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 64
    num_experts_per_tok: int = 6
    n_shared_experts: int = 2
    first_k_dense_replace: int = 1
    routed_scaling_factor: float = 1.0
    max_position_embeddings: int = 163840
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    #: YaRN (`rope_scaling` of the published config); factor 1 is plain RoPE
    rope_factor: float = 40.0
    rope_original_max_position: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 0.707
    rope_mscale_all_dim: float = 0.707
    #: std of the normal initializer; None leaves every parameter zero for
    #: a caller that assigns them all (a checkpoint loader, the benchmark:
    #: drawing 4 billion normals only to drop them is a minute on the chip)
    initializer_range: float | None = 0.02

    @property
    def latent_width(self):
        """Values a token keeps a layer: latent ‖ rotated shared key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def cache_row_width(self):
        """Lanes a cached row is stored in: `latent_width` padded to the
        next multiple of the chip's 128 lanes (640 for 576), the pad
        written as zeros; `serving/mla_paged_attention.py` says why the
        pad is part of the stated layout and not left to the compiler."""
        from ..serving.mla_paged_attention import lane_padded

        return lane_padded(self.latent_width)



# ------------------------------------------------------------------ YaRN

def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(c: DeepSeekV2Config):
    """Frequencies of the rotary pairs [dr / 2] (Peng et al. 2023): pairs
    that turn more than `beta_fast` times over the original context keep
    their frequency, those that turn less than `beta_slow` times are
    slowed by `factor`, a linear ramp between."""
    d, theta = c.qk_rope_head_dim, float(c.rope_theta)
    f = [theta ** (-2.0 * i / d) for i in range(d // 2)]
    if c.rope_factor <= 1:
        return jnp.asarray(f, jnp.float32)

    def corr(rotations):
        return d * math.log(c.rope_original_max_position
                            / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(corr(c.rope_beta_fast)), 0)
    high = min(math.ceil(corr(c.rope_beta_slow)), d - 1)
    if low == high:
        high += 0.001
    ramp = [min(max((i - low) / (high - low), 0.0), 1.0)
            for i in range(d // 2)]
    return jnp.asarray([fi / c.rope_factor * r + fi * (1.0 - r)
                        for fi, r in zip(f, ramp)], jnp.float32)


def softmax_scale(c: DeepSeekV2Config):
    m = yarn_mscale(c.rope_factor, c.rope_mscale_all_dim)
    return (c.qk_nope_head_dim + c.qk_rope_head_dim) ** -0.5 * m * m


def _rope(x, pos_ids, c):
    """x [B, s, ..., dr] at positions pos_ids [B, s], the published
    pairing: the slice is de-interleaved (even members, then odd), then
    half-rotated, and stays in that order (queries and keys alike)."""
    ang = pos_ids.astype(jnp.float32)[..., None] * yarn_inv_freq(c)
    t = (yarn_mscale(c.rope_factor, c.rope_mscale)
         / yarn_mscale(c.rope_factor, c.rope_mscale_all_dim))
    shape = ang.shape[:2] + (1,) * (x.ndim - 3) + ang.shape[-1:]
    cos, sin = (jnp.cos(ang) * t).reshape(shape), \
        (jnp.sin(ang) * t).reshape(shape)
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., 0::2], xf[..., 1::2]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def _init(c):
    if c.initializer_range is None:
        return Constant(0.0)
    return Normal(0.0, c.initializer_range)


def _rms(x, w, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


# ------------------------------------------------------------- attention

class DeepSeekV2Attention(Layer):
    """MLA without query compression (`q_lora_rank` null)."""

    def __init__(self, config: DeepSeekV2Config):
        super().__init__()
        c = self.config = config
        init = _init(c)
        nh = c.num_attention_heads

        def lin(i, o):
            return Linear(i, o, weight_attr=init, bias_attr=False)

        self.q_proj = lin(c.hidden_size,
                          nh * (c.qk_nope_head_dim + c.qk_rope_head_dim))
        self.kv_a_proj_with_mqa = lin(c.hidden_size, c.latent_width)
        self.kv_a_layernorm = RMSNorm(c.kv_lora_rank, c.rms_norm_eps)
        self.kv_b_proj = lin(c.kv_lora_rank,
                             nh * (c.qk_nope_head_dim + c.v_head_dim))
        self.o_proj = lin(nh * c.v_head_dim, c.hidden_size)

    def _project(self, x, pos_ids):
        """q [B, s, H, dn + dr] with its rotary slice rotated, the normed
        latent c [B, s, r], the rotated shared key k_r [B, s, dr], and
        W_kv_b as [r, H, dn + dv]."""
        c = self.config
        b, s, _ = x.shape
        nh, dn, r = c.num_attention_heads, c.qk_nope_head_dim, c.kv_lora_rank
        q = (x @ self.q_proj.weight._data).reshape(b, s, nh, -1)
        q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], pos_ids, c)],
                            -1)
        a = x @ self.kv_a_proj_with_mqa.weight._data
        lat = _rms(a[..., :r], self.kv_a_layernorm.weight._data,
                   c.rms_norm_eps)
        k_r = _rope(a[..., r:], pos_ids, c)
        w_b = self.kv_b_proj.weight._data.reshape(r, nh, -1)
        return q, lat, k_r, w_b

    def forward(self, x, cache=None):
        """x [B, s, h] (a raw array).  Without a cache: the expanded form
        over the whole sequence.  With a ``PagedKV`` view of the latent
        pool: the absorbed form; returns (out, new view)."""
        if cache is not None:
            return self._forward_paged(x, cache)
        c = self.config
        b, s, _ = x.shape
        dn = c.qk_nope_head_dim
        pos_ids = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        with jax.named_scope("mla.project"):
            q, lat, k_r, w_b = self._project(x, pos_ids)
            kv = jnp.einsum("bsr,rhd->bshd", lat, w_b)
            k = jnp.concatenate(
                [kv[..., :dn], jnp.broadcast_to(
                    k_r[:, :, None, :], kv.shape[:3] + k_r.shape[-1:])], -1)
        with jax.named_scope("mla.attend"):
            sc = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                            k.astype(jnp.float32)) * softmax_scale(c)
            causal = jnp.tril(jnp.ones((s, s), bool))
            p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), -1)
            o = jnp.einsum("bhqk,bkhd->bqhd", p,
                           kv[..., dn:].astype(jnp.float32)).astype(x.dtype)
        with jax.named_scope("mla.project"):
            return o.reshape(b, s, -1) @ self.o_proj.weight._data

    def _forward_paged(self, x, cache):
        from ..serving.kv_cache import PagedKV, paged_write
        from ..serving.mla_paged_attention import mla_paged_attention

        c = self.config
        b, s, _ = x.shape
        dn, r = c.qk_nope_head_dim, c.kv_lora_rank
        pos = cache.pos
        pos_ids = pos[:, None] + jnp.arange(s, dtype=pos.dtype)[None, :]
        with jax.named_scope("mla.project"):
            q, lat, k_r, w_b = self._project(x, pos_ids)
            # write-before-attend: the window's own latents are cached
            # first, so a token sees itself; the lane pad is zeros
            pad = cache.k.shape[-1] - c.latent_width
            row = jnp.concatenate(
                [lat, k_r, jnp.zeros((b, s, pad), lat.dtype)], -1)
            pool = paged_write(cache.k, row[:, :, None, :], cache.tables,
                               pos)
            # absorb W_uk into the query: q_lat = q_n W_uk^T  [B, s, H, r]
            q_abs = jnp.concatenate(
                [jnp.einsum("bshd,rhd->bshr", q[..., :dn], w_b[..., :dn]),
                 q[..., dn:]], -1)
        with jax.named_scope("mla.attend"):
            o_lat = mla_paged_attention(q_abs, pool, cache.tables, pos,
                                        scale=softmax_scale(c), v_width=r)
        with jax.named_scope("mla.project"):
            o = jnp.einsum("bshr,rhd->bshd", o_lat, w_b[..., dn:])
            out = o.reshape(b, s, -1) @ self.o_proj.weight._data
        return out, PagedKV(pool, None, cache.tables, pos + s)


# ------------------------------------------------------------------ FFN

class DeepSeekV2MLP(Layer):
    """SwiGLU; the leading dense layers' FFN, and the shared experts as
    ONE SwiGLU of width n_shared x moe_intermediate_size."""

    def __init__(self, config: DeepSeekV2Config, width):
        super().__init__()
        init = _init(config)
        h = config.hidden_size
        self.gate_proj = Linear(h, width, weight_attr=init, bias_attr=False)
        self.up_proj = Linear(h, width, weight_attr=init, bias_attr=False)
        self.down_proj = Linear(width, h, weight_attr=init, bias_attr=False)

    def forward(self, x):
        return _swiglu(x, self.gate_proj.weight._data,
                       self.up_proj.weight._data,
                       self.down_proj.weight._data)


def route(x, w_router, k, scaling=1.0):
    """The published router on rows x [T, h]: softmax over all experts in
    float32, the k largest probabilities (greedy), NOT renormalised.
    Returns (weights [T, k] f32, experts [T, k] int32)."""
    logits = jnp.matmul(x.astype(jnp.float32), w_router.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    p, e = jax.lax.top_k(jax.nn.softmax(logits, -1), k)
    return p * scaling, e.astype(jnp.int32)


def dropless_experts(x, weights, experts, w_gate_up, w_down):
    """sum_k weights[t, k] * SwiGLU_{experts[t, k]}(x[t]) for rows x
    [T, h], with no capacity: every (token, expert) pair becomes a row,
    the rows are sorted by expert and each expert's matrices meet its own
    rows in one grouped product, so cost follows T x k, not T x E, an
    expert nobody chose is never read and one many chose is read once a
    row tile.  Returns ([T, h], rows routed to each expert [E])."""
    t, k = experts.shape
    n_exp, f = w_down.shape[0], w_down.shape[1]
    flat = experts.reshape(-1)
    order = jnp.argsort(flat, stable=True)               # rows by expert
    sizes = jnp.bincount(flat, length=n_exp).astype(jnp.int32)
    rows = x[order // k]                                 # [T*k, h]
    gu = grouped_matmul(rows, w_gate_up, sizes)          # [T*k, 2f]
    act = (jax.nn.silu(gu[:, :f]) * gu[:, f:]).astype(x.dtype)
    y = grouped_matmul(act, w_down, sizes)               # [T*k, h]
    back = jnp.argsort(order)                            # row of pair (t,k)
    y = y[back].reshape(t, k, -1).astype(jnp.float32)
    out = jnp.einsum("tkh,tk->th", y, weights)
    return out.astype(x.dtype), sizes


class DeepSeekV2MoE(Layer):
    """`MoE(x) = SwiGLU_shared(x) + sum_{e in top-k} p_e SwiGLU_e(x)`."""

    def __init__(self, config: DeepSeekV2Config):
        super().__init__()
        c = self.config = config
        init = _init(c)
        h, f, e = c.hidden_size, c.moe_intermediate_size, c.n_routed_experts
        self.gate = Linear(h, e, weight_attr=init, bias_attr=False)
        # every expert's gate and up matrices side by side: one grouped
        # product reads a row once for both
        self.experts_gate_up = self.create_parameter(
            [e, h, 2 * f], default_initializer=init)
        self.experts_down = self.create_parameter(
            [e, f, h], default_initializer=init)
        self.shared_experts = DeepSeekV2MLP(c, c.n_shared_experts * f)

    def forward(self, x):
        """x [B, s, h] -> ([B, s, h], stats int32 [3]: rows routed, rows to
        the busiest expert, distinct experts touched)."""
        c = self.config
        b, s, h = x.shape
        flat = x.reshape(b * s, h)
        with jax.named_scope("moe.route"):
            weights, experts = route(flat, self.gate.weight._data,
                                     c.num_experts_per_tok,
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


class DeepSeekV2DecoderLayer(Layer):
    def __init__(self, config: DeepSeekV2Config, layer_idx):
        super().__init__()
        c = config
        self.eps = c.rms_norm_eps
        self.input_layernorm = RMSNorm(c.hidden_size, c.rms_norm_eps)
        self.self_attn = DeepSeekV2Attention(c)
        self.post_attention_layernorm = RMSNorm(c.hidden_size,
                                                c.rms_norm_eps)
        self.is_moe = layer_idx >= c.first_k_dense_replace
        self.mlp = (DeepSeekV2MoE(c) if self.is_moe
                    else DeepSeekV2MLP(c, c.intermediate_size))

    def forward(self, x, cache=None):
        h = _rms(x, self.input_layernorm.weight._data, self.eps)
        new_cache = None
        if cache is not None:
            h, new_cache = self.self_attn(h, cache)
        else:
            h = self.self_attn(h)
        x = x + h
        h = _rms(x, self.post_attention_layernorm.weight._data, self.eps)
        if self.is_moe:
            h, stats = self.mlp(h)
            if new_cache is not None:
                new_cache.stats = stats
        else:
            h = self.mlp(h)
        return x + h, new_cache


class DeepSeekV2Model(Layer):
    def __init__(self, config: DeepSeekV2Config):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=_init(config))
        self.layers = LayerList([DeepSeekV2DecoderLayer(config, i)
                                 for i in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, config.rms_norm_eps)

    def forward(self, input_ids, attn_mask=None, caches=None):
        """input_ids [B, s] -> hidden [B, s, h]; with `caches` (one
        ``PagedKV`` view of the latent pool a layer) -> (hidden, new
        views), the contract ``serving.Engine`` calls."""
        ids = input_ids._data if isinstance(input_ids, Tensor) else input_ids
        x = self.embed_tokens.weight._data[ids]
        new_caches = [] if caches is not None else None
        for i, layer in enumerate(self.layers):
            x, nc = layer(x, None if caches is None else caches[i])
            if caches is not None:
                new_caches.append(nc)
        x = Tensor(_rms(x, self.norm.weight._data, self.config.rms_norm_eps))
        return (x, new_caches) if caches is not None else x


class DeepSeekV2ForCausalLM(Layer):
    def __init__(self, config: DeepSeekV2Config):
        super().__init__()
        self.config = config
        self.model = DeepSeekV2Model(config)
        self.lm_head = Linear(config.hidden_size, config.vocab_size,
                              weight_attr=_init(config), bias_attr=False)

    def kv_cache_layout(self):
        """One latent buffer a layer, ``[NB, bs, 1, lane_padded(r + dr)]``
        and no value buffer: the values are the first ``kv_lora_rank``
        columns of the same rows (``serving/mla_paged_attention.py``)."""
        from ..serving.kv_cache import CacheLayout

        return CacheLayout((1, self.config.cache_row_width), buffers=1)

    def _logits(self, h):
        return Tensor(h._data @ self.lm_head.weight._data)

    def forward(self, input_ids):
        return self._logits(self.model(input_ids))

    #: the columns of what an expert layer returns with a step's harvest
    #: (``PagedKV.stats``, ``DeepSeekV2MoE.forward``), as the serving
    #: engine's counters are to be named: rows (token, expert pairs)
    #: routed, rows to the step's busiest expert, distinct experts
    #: touched.  They count every row a step computes, the rows of lanes
    #: that hold no request and of a prompt bucket's padding included.
    layer_stat_names = ("moe.rows", "moe.rows_max_expert",
                        "moe.experts_touched")
