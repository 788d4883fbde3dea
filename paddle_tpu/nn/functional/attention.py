"""Attention functionals.

Reference parity: paddle.nn.functional.flash_attention /
scaled_dot_product_attention backed by the vendored FlashAttention-2 CUDA lib
(SURVEY.md §2.1 N5). TPU-native: routes to the Pallas flash-attention kernel
(paddle_tpu.ops.flash_attention) on TPU, with a pure-XLA fallback elsewhere —
same signature, same [batch, seq, heads, head_dim] layout as the reference.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ...core.op_call import apply
from ...core.tensor import Tensor
from ...tensor.creation import _as_t


def _sdpa_ref(q, k, v, mask=None, dropout_p=0.0, causal=False, scale=None, key=None):
    # q,k,v: [B, S, H, D] (paddle flash-attn layout); GQA via shared helper
    from ...ops.flash_attention import expand_kv_heads

    k, v = expand_kv_heads(q, k, v)
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    logits = jnp.einsum("bshd,bthd->bhst", qf, kf) * s
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        cm = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(cm, logits, -1e30)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, -1e30)
        else:
            logits = logits + mask.astype(logits.dtype)
    p = jax.nn.softmax(logits, axis=-1)
    # fully-masked rows (causal q_len > kv_len, or an all-False mask row)
    # must output 0, matching the flash-attn convention of the Pallas path
    # — plain softmax would instead spread uniformly and return mean(v)
    row_has_key = jnp.any(logits > -1e29, axis=-1, keepdims=True)
    p = jnp.where(row_has_key, p, 0.0)
    if dropout_p > 0.0 and key is not None:
        keep = jax.random.bernoulli(key, 1.0 - dropout_p, p.shape)
        p = jnp.where(keep, p / (1.0 - dropout_p), 0.0)
    out = jnp.einsum("bhst,bthd->bshd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def _use_pallas(q_shape, head_dim):
    if jax.default_backend() != "tpu":
        return False
    # long-enough seq; non-lane-aligned head dims (<=256) are padded
    # to 128 lanes by ops.flash_attention (free on the MXU)
    return head_dim <= 256 and q_shape[1] >= 128


def scaled_dot_product_attention(query, key, value, attn_mask=None, dropout_p=0.0,
                                 is_causal=False, training=True, name=None):
    """Inputs [batch, seq, num_heads, head_dim] (paddle parity)."""
    q, k, v = _as_t(query), _as_t(key), _as_t(value)
    rng_key = None
    if dropout_p > 0.0 and training:
        from ...core import random_state

        rng_key = random_state.next_key()

    if (attn_mask is None and _use_pallas(tuple(q.shape), q.shape[-1])
            and dropout_p == 0.0 and q.shape[2] % k.shape[2] == 0):
        # GQA handled natively by the kernel (kv heads shared via index map)
        from ...ops.flash_attention import flash_attention as pallas_flash

        return pallas_flash(q, k, v, causal=is_causal)

    mask_t = _as_t(attn_mask).detach() if attn_mask is not None else None
    args = [q, k, v] + ([mask_t] if mask_t is not None else [])

    def f(qa, ka, va, *m):
        return _sdpa_ref(qa, ka, va, m[0] if m else None,
                         dropout_p if training else 0.0, is_causal, key=rng_key)

    return apply(f, *args, _op_name="scaled_dot_product_attention")


def flash_attention(query, key, value, dropout=0.0, causal=False, return_softmax=False,
                    fixed_seed_offset=None, rng_name="", training=True, name=None):
    """paddle.nn.functional.flash_attention.flash_attention parity."""
    out = scaled_dot_product_attention(query, key, value, None, dropout, causal, training)
    if return_softmax:
        return out, None
    return out, None


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k, max_seqlen_q,
                        max_seqlen_k, scale=None, dropout=0.0, causal=False,
                        return_softmax=False, training=True, name=None):
    """Varlen flash-attn parity: the TPU design keeps static shapes (XLA
    requirement) — callers should batch to max_seqlen with masks instead.
    Provided eagerly for API completeness."""
    q, k, v = _as_t(query), _as_t(key), _as_t(value)
    import numpy as np

    cq = np.asarray(_as_t(cu_seqlens_q)._data)
    ck = np.asarray(_as_t(cu_seqlens_k)._data)
    outs = []
    for i in range(len(cq) - 1):
        qi = q[int(cq[i]):int(cq[i + 1])]
        ki = k[int(ck[i]):int(ck[i + 1])]
        vi = v[int(ck[i]):int(ck[i + 1])]
        o = scaled_dot_product_attention(
            qi.unsqueeze(0), ki.unsqueeze(0), vi.unsqueeze(0), None, dropout, causal, training
        )
        outs.append(o.squeeze(0))
    from ...tensor.manipulation import concat

    out = concat(outs, axis=0)
    return (out, None) if return_softmax else (out, None)


def sdp_kernel(*args, **kwargs):
    import contextlib

    return contextlib.nullcontext()
