"""Weight-only PTQ for the serving engine (int8 decode weights).

Decode streams every weight once a step and is bound by that stream
(`PERF.md` section 5), so the cheapest 2x on the bound is
storing matmul weights as int8 and paying a per-channel multiply to
rebuild them inside the program: XLA fuses ``q.astype(f32) * scale``
into the matmul's weight read, so the bytes streamed from HBM per step
halve while the arithmetic stays f32.

Scale layout: one absmax scale per OUTPUT channel.  ``Linear`` stores
its weight ``[in_features, out_features]`` and contracts over axis 0,
so the per-output-channel scale is an absmax over axis 0 with shape
``[1, out_features]`` — it broadcasts over the contraction axis, which
keeps each output column's quantization error independent of every
other column (a single per-tensor scale would let one outlier column
crush the resolution of all of them).

The floor is applied PER CHANNEL (``maximum(absmax, 1e-8)`` on the
[1, out] array, before any division): an all-zero output channel —
common in pruned or freshly-initialized heads — quantizes to exact
zeros instead of propagating ``0/0`` NaNs through the whole column.

``quantize_for_serving`` walks a CausalLM Layer tree and quantizes
every ``Linear`` weight it can map back to a ``state_dict`` name
(q/k/v/o projections, the SwiGLU MLP, the LM head).  Embeddings,
norms, and biases stay in their original dtype: they are a rounding
hazard (embedding rows feed every downstream computation) and a
rounding waste (norm gains and biases are vectors, not byte traffic).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp


#: per-channel absmax floor: an all-zero channel quantizes to zeros
#: (scale floor / qmax) instead of dividing by zero
SCALE_FLOOR = 1e-8


def channelwise_scales(w, channel_axis=-1, quant_bits=8):
    """Per-channel symmetric quantization step for ``w``: absmax over
    every axis except ``channel_axis``, floored at :data:`SCALE_FLOOR`
    per channel, divided by the int range.  Returned with ``keepdims``
    so it broadcasts against ``w`` directly."""
    qmax = float(2 ** (quant_bits - 1) - 1)
    axis = channel_axis % w.ndim
    reduce_axes = tuple(i for i in range(w.ndim) if i != axis)
    absmax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=reduce_axes,
                     keepdims=True)
    return jnp.maximum(absmax, SCALE_FLOOR) / qmax


def quantize_weight(w, channel_axis=-1, quant_bits=8):
    """Symmetric per-channel int8 quantization: returns ``(q, scale)``
    with ``q`` int8 shaped like ``w`` and ``scale`` f32 broadcastable
    against it (``dequantize_weight`` inverts)."""
    qmax = float(2 ** (quant_bits - 1) - 1)
    scale = channelwise_scales(w, channel_axis, quant_bits)
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale), -qmax, qmax)
    return q.astype(jnp.int8), scale


def dequantize_weight(q, scale, dtype=jnp.float32):
    """Rebuild the fp weight inside a traced program.  Under jit the
    multiply fuses into the consuming matmul's weight read, so only the
    int8 bytes (plus the tiny scale vector) cross HBM."""
    return (q.astype(jnp.float32) * scale).astype(dtype)


@dataclass
class QuantizedWeight:
    """One serving-quantized parameter: int8 payload + f32 per-channel
    scale + the dtype ``dequantize()`` must restore."""

    q: jax.Array
    scale: jax.Array
    dtype: object

    @property
    def pair(self):
        """The (q, scale) pytree the engine threads through its jitted
        programs in place of the fp array."""
        return (self.q, self.scale)

    def dequantize(self):
        return dequantize_weight(self.q, self.scale, self.dtype)

    @property
    def nbytes(self):
        return int(self.q.size * self.q.dtype.itemsize
                   + self.scale.size * self.scale.dtype.itemsize)


def quantize_for_serving(model, quant_bits=8):
    """Absmax-calibrate every ``Linear`` weight of ``model`` for
    weight-only serving: returns ``{state_dict name: QuantizedWeight}``
    for the projections worth quantizing (matmul weights), leaving
    embeddings/norms/biases untouched.

    Pure PTQ — no calibration data needed: weight quantization only
    depends on the weights themselves (activations stay fp, so there is
    no activation-range estimation problem).  The caller substitutes
    ``QuantizedWeight.pair`` for the fp array and dequantizes inline
    (the serving engine does this in ``_run_model``)."""
    from ..nn.layer.common import Linear

    by_id = {}
    for name, t in model.state_dict().items():
        by_id[id(t)] = name
    out = {}

    def walk(layer):
        for _, child in layer.named_children():
            if isinstance(child, Linear):
                name = by_id.get(id(child.weight))
                if name is not None:
                    w = child.weight._data
                    q, scale = quantize_weight(w, channel_axis=-1,
                                               quant_bits=quant_bits)
                    out[name] = QuantizedWeight(q, scale, w.dtype)
            walk(child)

    walk(model)
    return out
