"""Decoder-only transformer LM family — the ERNIE-3.5 / LLaMA-2 capability
target (BASELINE.json configs). The reference keeps these in PaddleNLP
(ecosystem); the TPU build ships them in-repo as the flagship models.

TPU-first design decisions:
  * pre-norm RMSNorm + RoPE + SwiGLU (LLaMA recipe, which ERNIE-3.5-class
    models follow) — all shapes static, seq-major-free [B, S, H, D]
  * attention through F.scaled_dot_product_attention → Pallas flash kernel
  * every Parameter carries a `sharding_axes` hint consumed by the fleet
    layer to build pjit shardings: ('mp' on ffn/vocab dims, None elsewhere)
  * no Python-level KV-cache branching inside the train path — decode uses a
    separate cache path, so the training graph stays branch-free for XLA.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field

import jax.numpy as jnp

from ..nn.layer.layers import Layer
from ..nn.layer.common import Linear, Embedding, Dropout
from ..nn.layer.norm import RMSNorm
from ..nn.layer.container import LayerList
from ..nn import functional as F
from ..nn.initializer import Normal
from ..core.tensor import Tensor
from ..tensor import manipulation as M
from ..ops.rope import apply_rotary_emb


@dataclass
class GPTConfig:
    vocab_size: int = 32000
    hidden_size: int = 768
    intermediate_size: int = 2048
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    num_key_value_heads: int = None  # GQA; defaults to MHA
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    hidden_dropout_prob: float = 0.0
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False
    use_recompute: bool = False
    # fused chunked LM-head CE: never materialises [B*S, vocab] f32 logits
    # (forward(labels=...) then returns (loss, None))
    fused_lm_loss: bool = False

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def kv_heads(self):
        return self.num_key_value_heads or self.num_attention_heads


# BASELINE.json model configs
ERNIE_7B = GPTConfig(
    vocab_size=32000, hidden_size=4096, intermediate_size=11008,
    num_hidden_layers=32, num_attention_heads=32, max_position_embeddings=4096,
)
LLAMA2_13B = GPTConfig(
    vocab_size=32000, hidden_size=5120, intermediate_size=13824,
    num_hidden_layers=40, num_attention_heads=40, max_position_embeddings=4096,
)


def _mark(p, axes):
    """Attach a PartitionSpec-style sharding hint, consumed by fleet/pjit."""
    p._sharding_axes = axes
    return p


class GPTAttention(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        c = config
        self.num_heads = c.num_attention_heads
        self.kv_heads = c.kv_heads
        self.head_dim = c.head_dim
        self.rope_theta = c.rope_theta
        init = Normal(0.0, c.initializer_range)
        self.q_proj = Linear(c.hidden_size, self.num_heads * self.head_dim,
                             weight_attr=init, bias_attr=False)
        self.k_proj = Linear(c.hidden_size, self.kv_heads * self.head_dim,
                             weight_attr=init, bias_attr=False)
        self.v_proj = Linear(c.hidden_size, self.kv_heads * self.head_dim,
                             weight_attr=init, bias_attr=False)
        self.o_proj = Linear(self.num_heads * self.head_dim, c.hidden_size,
                             weight_attr=init, bias_attr=False)
        # TP sharding hints: column-parallel qkv, row-parallel out
        _mark(self.q_proj.weight, (None, "mp"))
        _mark(self.k_proj.weight, (None, "mp"))
        _mark(self.v_proj.weight, (None, "mp"))
        _mark(self.o_proj.weight, ("mp", None))

    def forward(self, x, attn_mask=None, cache=None, position_offset=0):
        b, s, _ = x.shape
        q = M.reshape(self.q_proj(x), [b, s, self.num_heads, self.head_dim])
        k = M.reshape(self.k_proj(x), [b, s, self.kv_heads, self.head_dim])
        v = M.reshape(self.v_proj(x), [b, s, self.kv_heads, self.head_dim])
        import numpy as np

        if cache is not None and not isinstance(cache, (tuple, list)):
            # serving paged path: PagedKV — scatter this chunk's k/v
            # into table-mapped pool blocks, ragged paged attention
            # reads only live blocks (paddle_tpu.serving).
            return self._forward_paged(q, k, v, cache, b, s)

        pos = None
        if position_offset:
            pos_ids = jnp.arange(position_offset, position_offset + s)[None, :]
            pos = Tensor(jnp.broadcast_to(pos_ids, (b, s)))
        q = apply_rotary_emb(q, position_ids=pos, base=self.rope_theta)
        k = apply_rotary_emb(k, position_ids=pos, base=self.rope_theta)
        if cache is not None:
            if cache[0] is not None:
                k = M.concat([cache[0], k], axis=1)
                v = M.concat([cache[1], v], axis=1)
            new_cache = (k, v)
        else:
            new_cache = None
        # GQA: kv heads stay narrow — the flash kernel shares them across
        # query groups via its BlockSpec index map; the XLA fallback repeats
        out = F.scaled_dot_product_attention(q, k, v, attn_mask=attn_mask,
                                             is_causal=True, training=self.training)
        out = self.o_proj(M.reshape(out, [b, s, self.num_heads * self.head_dim]))
        if cache is not None:
            return out, new_cache
        return out

    def _forward_paged(self, q, k, v, cache, b, s):
        """Paged-cache attention: rope at the per-row positions, scatter
        k/v into the lane's table-mapped pool blocks (write-before-attend
        so the current token's keys are visible to itself), then ragged
        paged attention over the block table — only blocks below each
        lane's length are read. Same rope and attention math as the concat
        cache's branch, over the same visible keys. A quantized
        pool (cache.k_scale set) quantizes each token at the write and
        dequantizes gathered blocks inside paged attention — same math
        over dequantized values, so parity within a quant config holds."""
        import jax.numpy as jnp

        from ..serving.kv_cache import PagedKV, paged_write, paged_write_quant
        from ..serving.paged_attention import paged_attention

        pos = cache.pos
        pos_ids = Tensor(pos[:, None]
                         + jnp.arange(s, dtype=pos.dtype)[None, :])
        q = apply_rotary_emb(q, position_ids=pos_ids, base=self.rope_theta)
        k = apply_rotary_emb(k, position_ids=pos_ids, base=self.rope_theta)
        if cache.k_scale is not None:
            k_pool, k_scale = paged_write_quant(
                cache.k, cache.k_scale, k._data, cache.tables, pos)
            v_pool, v_scale = paged_write_quant(
                cache.v, cache.v_scale, v._data, cache.tables, pos)
        else:
            k_pool = paged_write(cache.k, k._data, cache.tables, pos)
            v_pool = paged_write(cache.v, v._data, cache.tables, pos)
            k_scale = v_scale = None
        out = paged_attention(q._data, k_pool, v_pool, cache.tables, pos,
                              k_scale, v_scale)
        out = self.o_proj(M.reshape(Tensor(out),
                                    [b, s, self.num_heads * self.head_dim]))
        return out, PagedKV(k_pool, v_pool, cache.tables, pos + s,
                            k_scale, v_scale)


class GPTMLP(Layer):
    """SwiGLU feed-forward."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        c = config
        init = Normal(0.0, c.initializer_range)
        self.gate_proj = Linear(c.hidden_size, c.intermediate_size, weight_attr=init, bias_attr=False)
        self.up_proj = Linear(c.hidden_size, c.intermediate_size, weight_attr=init, bias_attr=False)
        self.down_proj = Linear(c.intermediate_size, c.hidden_size, weight_attr=init, bias_attr=False)
        _mark(self.gate_proj.weight, (None, "mp"))
        _mark(self.up_proj.weight, (None, "mp"))
        _mark(self.down_proj.weight, ("mp", None))

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class GPTDecoderLayer(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.input_layernorm = RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.self_attn = GPTAttention(config)
        self.post_attention_layernorm = RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.mlp = GPTMLP(config)
        self.dropout = Dropout(config.hidden_dropout_prob)

    def forward(self, x, attn_mask=None, cache=None, position_offset=0):
        residual = x
        h = self.input_layernorm(x)
        if cache is not None:
            h, new_cache = self.self_attn(h, attn_mask, cache, position_offset)
        else:
            h = self.self_attn(h, attn_mask)
            new_cache = None
        x = residual + self.dropout(h)
        residual = x
        h = self.mlp(self.post_attention_layernorm(x))
        x = residual + self.dropout(h)
        if cache is not None:
            return x, new_cache
        return x


class GPTModel(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(config.vocab_size, config.hidden_size,
                                      weight_attr=Normal(0.0, config.initializer_range))
        _mark(self.embed_tokens.weight, ("mp", None))  # vocab-parallel
        self.layers = LayerList([GPTDecoderLayer(config) for _ in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, config.rms_norm_eps)

    def forward(self, input_ids, attn_mask=None, caches=None, position_offset=0):
        x = self.embed_tokens(input_ids)
        new_caches = [] if caches is not None else None
        for i, layer in enumerate(self.layers):
            if self.config.use_recompute and self.training and caches is None:
                from ..distributed.recompute import recompute

                x = recompute(layer, x, attn_mask)
            elif caches is not None:
                x, nc = layer(x, attn_mask, caches[i], position_offset)
                new_caches.append(nc)
            else:
                x = layer(x, attn_mask)
        x = self.norm(x)
        if caches is not None:
            return x, new_caches
        return x


class GPTForCausalLM(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.model = GPTModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = Linear(config.hidden_size, config.vocab_size,
                                  weight_attr=Normal(0.0, config.initializer_range),
                                  bias_attr=False)
            _mark(self.lm_head.weight, (None, "mp"))

    def _logits(self, h):
        if self.lm_head is not None:
            return self.lm_head(h)
        from ..tensor.math import matmul

        return matmul(h, M.t(self.model.embed_tokens.weight))

    def forward(self, input_ids, labels=None, attn_mask=None):
        h = self.model(input_ids, attn_mask)
        if labels is not None and self.config.fused_lm_loss:
            from ..incubate.nn.functional import fused_linear_cross_entropy

            hidden = M.reshape(h, [-1, self.config.hidden_size])
            flat_labels = M.reshape(labels, [-1])
            if self.lm_head is not None:
                loss = fused_linear_cross_entropy(
                    hidden, self.lm_head.weight, flat_labels,
                    ignore_index=-100)
            else:  # tied embeddings: weight is [vocab, hidden]
                loss = fused_linear_cross_entropy(
                    hidden, self.model.embed_tokens.weight, flat_labels,
                    ignore_index=-100, transpose_weight=True)
            return loss, None
        logits = self._logits(h)
        if labels is not None:
            loss = F.cross_entropy(
                M.reshape(logits, [-1, self.config.vocab_size]),
                M.reshape(labels, [-1]),
                ignore_index=-100,
            )
            return loss, logits
        return logits

    # -------- decode --------
    def generate(self, input_ids, max_new_tokens=20, temperature=1.0, top_k=0):
        import numpy as np

        import paddle_tpu as paddle

        self.eval()
        from ..core import tape as _tape

        with _tape.no_grad():
            b, s = input_ids.shape
            h, caches = self.model(input_ids, caches=[(None, None)] * len(self.model.layers))
            out_ids = [input_ids]
            last = input_ids[:, -1:]
            logits = self._logits(h)[:, -1]
            for step in range(max_new_tokens):
                if temperature == 0:
                    nxt = paddle.argmax(logits, axis=-1).unsqueeze(-1)
                else:
                    probs = F.softmax(logits / temperature, axis=-1)
                    nxt = paddle.multinomial(probs, 1)
                out_ids.append(nxt)
                h, caches = self.model(nxt, caches=caches, position_offset=s + step)
                logits = self._logits(h)[:, -1]
            return M.concat(out_ids, axis=1)
