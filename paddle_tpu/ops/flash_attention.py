"""Flash attention for TPU.

Reference parity: the vendored FlashAttention-2 CUDA library behind
paddle.nn.functional.flash_attention (SURVEY.md §2.1 N5). TPU-native design:
a Pallas blockwise-softmax kernel (ops/pallas/flash.py, arriving with the
kernel layer) with this XLA fallback — jnp einsum + online-softmax-equivalent
math that XLA already fuses well on the MXU. Layout [B, S, H, D], matching the
reference's flash-attn API.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ..core.op_call import apply
from ..core.tensor import Tensor
from ..tensor.creation import _as_t


def expand_kv_heads(q, k, v):
    """GQA fallback for XLA paths: materialize the kv-head repeat so einsum
    sees matching head counts (the Pallas kernel shares heads natively)."""
    if k.shape[2] != q.shape[2]:
        if q.shape[2] % k.shape[2]:
            raise ValueError(
                f"GQA needs q heads {q.shape[2]} divisible by kv heads "
                f"{k.shape[2]}")
        rep = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return k, v


def _xla_flash(q, k, v, causal, scale):
    s = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    k, v = expand_kv_heads(q, k, v)
    logits = jnp.einsum("bshd,bthd->bhst", q, k, preferred_element_type=jnp.float32) * s
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(mask, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhst,bthd->bshd", p.astype(v.dtype), v)


#: fleet's mesh-axis names (distributed/topology.py): a sharded program
#: splits the batch over the data axes and the heads over tensor parallel
_BATCH_AXES = ("dp", "sharding")
_HEAD_AXIS = "mp"


def _per_shard(kernel, q, k, v):
    """Run ``kernel(q, k, v)`` ([B, S, H, D] in and out) per shard where
    the enclosing program is sharded over a mesh.

    The SPMD partitioner refuses a Mosaic kernel ("cannot be
    automatically partitioned. Please wrap the call in a shard_map"), so
    under a mesh (``jax.set_mesh``; TrainStep enters it when its state is
    sharded) the call is a shard_map that is manual over every axis the
    partitioner would otherwise own: batch over the data axes, heads over
    'mp' — attention is independent along both — and replicated over the
    rest.  An axis that does not divide its dimension is left out.
    Outside a mesh, and inside a shard_map that is already manual over
    every axis, the kernel is called as it is."""
    mesh = jax.sharding.get_abstract_mesh()
    auto = {a: mesh.shape[a] for a in mesh.auto_axes}
    if not auto:
        return kernel(q, k, v)
    batch = tuple(a for a in _BATCH_AXES if a in auto)
    while batch and q.shape[0] % math.prod(auto[a] for a in batch):
        batch = batch[:-1]
    heads = _HEAD_AXIS if (_HEAD_AXIS in auto
                           and k.shape[2] % auto[_HEAD_AXIS] == 0) else None
    spec = jax.sharding.PartitionSpec(batch or None, None, heads, None)
    return jax.shard_map(kernel, in_specs=(spec,) * 3, out_specs=spec,
                         axis_names=set(auto), check_vma=False)(q, k, v)


def flash_attention_arrays(q, k, v, causal=False, scale=None):
    """Array-level entry used by both the Tensor wrapper and jitted models.

    Routes to the Pallas TPU kernel when available, else the XLA path.
    Head dims that aren't lane-aligned (the SD-UNet's 40/80/160) are
    zero-padded to the next multiple of 128: a sub-128 contraction costs a
    full systolic pass on the MXU anyway, so the padding is compute-free,
    the zeros contribute nothing to q·k, and the padded v columns slice
    off — while the kernel keeps the [s, s] score tile out of HBM (the
    XLA path materializes it)."""
    d = q.shape[-1]
    if jax.default_backend() == "tpu" and d <= 256:
        from .pallas.flash import flash_attention as pallas_flash

        if d % 128:
            dp = -(-d // 128) * 128
            scale = scale if scale is not None else 1.0 / math.sqrt(d)
            pad = [(0, 0)] * 3 + [(0, dp - d)]
            q, k, v = (jnp.pad(x, pad) for x in (q, k, v))
        kernel = functools.partial(pallas_flash, causal=causal, scale=scale,
                                   interpret=False)
        return _per_shard(kernel, q, k, v)[..., :d]
    return _xla_flash(q, k, v, causal, scale)


def flash_attention(query, key, value, causal=False, scale=None):
    q, k, v = _as_t(query), _as_t(key), _as_t(value)
    return apply(
        functools.partial(flash_attention_arrays, causal=causal, scale=scale),
        q, k, v, _op_name="flash_attention",
    )
