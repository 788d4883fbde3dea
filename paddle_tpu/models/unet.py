"""Stable-Diffusion-style conditional UNet (BASELINE.json config 5; the
reference hosts it in ppdiffusers). Kept at SD-1.x topology but
parameterized so that a caller can scale it.

TPU-first layout (r4): the model runs CHANNELS-LAST (NHWC) internally —
an earlier round's device trace (not in the tree any more) showed the
NCHW variant spending 50% of device time in data-formatting ops (2387
transposes/step, 80% HBM-bound) because every TransformerBlock2D hop
between conv [B,C,H,W] and attention [B,HW,C] materializes a physical
transpose. With C already minor, those hops are free reshapes. The
weight layout (OIHW, paddle convention) and the state_dict are
unchanged; `channels_last=False` restores the reference layout
bit-for-bit (parity-tested in tests/test_models.py)."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List

import jax.numpy as jnp

from ..nn.layer.layers import Layer
from ..nn.layer.common import Linear, Dropout, Upsample
from ..nn.layer.conv import Conv2D
from ..nn.layer.norm import GroupNorm, LayerNorm
from ..nn.layer.container import LayerList
from ..nn import functional as F
from ..core.tensor import Tensor
from ..tensor import manipulation as M


@dataclass
class UNetConfig:
    in_channels: int = 4
    out_channels: int = 4
    block_out_channels: tuple = (320, 640, 1280, 1280)
    layers_per_block: int = 2
    cross_attention_dim: int = 768
    attention_head_dim: int = 8
    norm_num_groups: int = 32
    sample_size: int = 64
    channels_last: bool = True


def timestep_embedding(t, dim, max_period=10000):
    half = dim // 2
    freqs = jnp.exp(-jnp.log(max_period) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = t.astype(jnp.float32)[:, None] * freqs[None]
    return jnp.concatenate([jnp.cos(ang), jnp.sin(ang)], axis=-1)


class ResnetBlock2D(Layer):
    def __init__(self, in_c, out_c, temb_c, groups=32, data_format="NCHW"):
        super().__init__()
        self._df = data_format
        self.norm1 = GroupNorm(min(groups, in_c), in_c, data_format=data_format)
        self.conv1 = Conv2D(in_c, out_c, 3, padding=1, data_format=data_format)
        self.time_emb_proj = Linear(temb_c, out_c)
        self.norm2 = GroupNorm(min(groups, out_c), out_c, data_format=data_format)
        self.conv2 = Conv2D(out_c, out_c, 3, padding=1, data_format=data_format)
        self.shortcut = Conv2D(in_c, out_c, 1, data_format=data_format) \
            if in_c != out_c else None

    def forward(self, x, temb):
        h = self.conv1(F.silu(self.norm1(x)))
        tshape = ([temb.shape[0], 1, 1, -1] if self._df == "NHWC"
                  else [temb.shape[0], -1, 1, 1])
        h = h + M.reshape(self.time_emb_proj(F.silu(temb)), tshape)
        h = self.conv2(F.silu(self.norm2(h)))
        sc = self.shortcut(x) if self.shortcut is not None else x
        return h + sc


class CrossAttention(Layer):
    def __init__(self, query_dim, context_dim, heads):
        super().__init__()
        self.heads = heads
        self.head_dim = query_dim // heads
        self.to_q = Linear(query_dim, query_dim, bias_attr=False)
        self.to_k = Linear(context_dim, query_dim, bias_attr=False)
        self.to_v = Linear(context_dim, query_dim, bias_attr=False)
        self.to_out = Linear(query_dim, query_dim)

    def forward(self, x, context=None):
        b, s, _ = x.shape
        if context is None:
            # self-attention: ONE [D, 3D] GEMM (r5 — same in-trace weight
            # concat as nn.MultiHeadAttention; state_dict unchanged)
            w = M.concat([self.to_q.weight, self.to_k.weight,
                          self.to_v.weight], axis=1)
            qkv = M.reshape(F.linear(x, w),
                            [b, s, 3, self.heads, self.head_dim])
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        else:
            sk = context.shape[1]
            q = M.reshape(self.to_q(x), [b, s, self.heads, self.head_dim])
            # cross-attention: K/V share the context — one [C, 2D] GEMM
            wkv = M.concat([self.to_k.weight, self.to_v.weight], axis=1)
            kv = M.reshape(F.linear(context, wkv),
                           [b, sk, 2, self.heads, self.head_dim])
            k, v = kv[:, :, 0], kv[:, :, 1]
        out = F.scaled_dot_product_attention(q, k, v, training=self.training)
        return self.to_out(M.reshape(out, [b, s, self.heads * self.head_dim]))


class TransformerBlock2D(Layer):
    def __init__(self, dim, context_dim, heads, groups=32,
                 data_format="NCHW"):
        super().__init__()
        self._df = data_format
        self.norm_in = GroupNorm(min(groups, dim), dim, data_format=data_format)
        self.proj_in = Conv2D(dim, dim, 1, data_format=data_format)
        self.norm1 = LayerNorm(dim)
        self.attn1 = CrossAttention(dim, dim, heads)
        self.norm2 = LayerNorm(dim)
        self.attn2 = CrossAttention(dim, context_dim, heads)
        self.norm3 = LayerNorm(dim)
        self.ff1 = Linear(dim, dim * 4)
        self.ff2 = Linear(dim * 4, dim)
        self.proj_out = Conv2D(dim, dim, 1, data_format=data_format)

    def forward(self, x, context):
        residual = x
        y = self.proj_in(self.norm_in(x))
        if self._df == "NHWC":
            # channels already minor: [B,H,W,C] <-> [B,HW,C] is a free
            # reshape — the whole point of the channels-last layout
            b, h, w, c = x.shape
            y = M.reshape(y, [b, h * w, c])
        else:
            b, c, h, w = x.shape
            y = M.reshape(M.transpose(y, [0, 2, 3, 1]), [b, h * w, c])
        y = y + self.attn1(self.norm1(y))
        y = y + self.attn2(self.norm2(y), context)
        y = y + self.ff2(F.gelu(self.ff1(self.norm3(y))))
        if self._df == "NHWC":
            y = M.reshape(y, [b, h, w, c])
        else:
            y = M.transpose(M.reshape(y, [b, h, w, c]), [0, 3, 1, 2])
        return self.proj_out(y) + residual


class Downsample2D(Layer):
    def __init__(self, c, data_format="NCHW"):
        super().__init__()
        self.conv = Conv2D(c, c, 3, stride=2, padding=1,
                           data_format=data_format)

    def forward(self, x):
        return self.conv(x)


class Upsample2D(Layer):
    def __init__(self, c, data_format="NCHW"):
        super().__init__()
        self._df = data_format
        self.conv = Conv2D(c, c, 3, padding=1, data_format=data_format)

    def forward(self, x):
        x = F.interpolate(x, scale_factor=2, mode="nearest",
                          data_format=self._df)
        return self.conv(x)


class UNet2DConditionModel(Layer):
    def __init__(self, config: UNetConfig = None):
        super().__init__()
        c = config or UNetConfig()
        self.config = c
        df = "NHWC" if getattr(c, "channels_last", False) else "NCHW"
        self._df = df
        ch = c.block_out_channels
        temb_c = ch[0] * 4
        self.conv_in = Conv2D(c.in_channels, ch[0], 3, padding=1,
                              data_format=df)
        self.time_proj_dim = ch[0]
        self.time_mlp1 = Linear(ch[0], temb_c)
        self.time_mlp2 = Linear(temb_c, temb_c)

        heads = c.attention_head_dim

        # down
        self.down_resnets = LayerList()
        self.down_attns = LayerList()
        self.downsamplers = LayerList()
        self._down_plan = []
        in_c = ch[0]
        for i, out_c in enumerate(ch):
            use_attn = i < len(ch) - 1  # SD: attn on all but the last down block
            for j in range(c.layers_per_block):
                self.down_resnets.append(ResnetBlock2D(
                    in_c, out_c, temb_c, c.norm_num_groups, data_format=df))
                self.down_attns.append(
                    TransformerBlock2D(out_c, c.cross_attention_dim, heads,
                                       c.norm_num_groups, data_format=df)
                    if use_attn else _Identity()
                )
                self._down_plan.append(use_attn)
                in_c = out_c
            if i < len(ch) - 1:
                self.downsamplers.append(Downsample2D(out_c, data_format=df))

        # mid
        self.mid_res1 = ResnetBlock2D(ch[-1], ch[-1], temb_c,
                                      c.norm_num_groups, data_format=df)
        self.mid_attn = TransformerBlock2D(ch[-1], c.cross_attention_dim,
                                           heads, c.norm_num_groups,
                                           data_format=df)
        self.mid_res2 = ResnetBlock2D(ch[-1], ch[-1], temb_c,
                                      c.norm_num_groups, data_format=df)

        # up
        self.up_resnets = LayerList()
        self.up_attns = LayerList()
        self.upsamplers = LayerList()
        self._up_plan = []
        rev = list(reversed(ch))
        prev_c = ch[-1]
        for i, out_c in enumerate(rev):
            use_attn = i > 0
            skip_ch_list = self._skip_channels(ch, i, c.layers_per_block)
            for j in range(c.layers_per_block + 1):
                skip_c = skip_ch_list[j]
                self.up_resnets.append(ResnetBlock2D(
                    prev_c + skip_c, out_c, temb_c, c.norm_num_groups,
                    data_format=df))
                self.up_attns.append(
                    TransformerBlock2D(out_c, c.cross_attention_dim, heads,
                                       c.norm_num_groups, data_format=df)
                    if use_attn else _Identity()
                )
                self._up_plan.append(use_attn)
                prev_c = out_c
            if i < len(rev) - 1:
                self.upsamplers.append(Upsample2D(out_c, data_format=df))

        self.conv_norm_out = GroupNorm(c.norm_num_groups, ch[0],
                                       data_format=df)
        self.conv_out = Conv2D(ch[0], c.out_channels, 3, padding=1,
                               data_format=df)

    @staticmethod
    def _skip_channels(ch, up_idx, layers_per_block):
        """Channels of skip connections consumed by up-block `up_idx`."""
        # build the stack the down path produces
        stack = [ch[0]]
        for i, out_c in enumerate(ch):
            for _ in range(layers_per_block):
                stack.append(out_c)
            if i < len(ch) - 1:
                stack.append(out_c)
        # up blocks pop layers_per_block+1 each, in reverse
        start = len(stack) - (up_idx * (layers_per_block + 1))
        return [stack[start - 1 - j] for j in range(layers_per_block + 1)]

    def forward(self, sample, timestep, encoder_hidden_states):
        temb_raw = timestep_embedding(
            timestep._data if isinstance(timestep, Tensor) else jnp.asarray(timestep),
            self.time_proj_dim,
        )
        # sinusoidal embedding is f32; follow the model's compute dtype so a
        # bf16-cast model stays bf16 end to end
        temb_raw = temb_raw.astype(self.time_mlp1.weight._data.dtype)
        temb = self.time_mlp2(F.silu(self.time_mlp1(Tensor(temb_raw))))

        if self._df == "NHWC":
            # one boundary transpose each way; everything inside is
            # channels-last so conv<->attention hops are free reshapes
            sample = M.transpose(sample, [0, 2, 3, 1])
        x = self.conv_in(sample)
        skips = [x]
        ri = 0
        di = 0
        ch = self.config.block_out_channels
        for i in range(len(ch)):
            for j in range(self.config.layers_per_block):
                x = self.down_resnets[ri](x, temb)
                if self._down_plan[ri]:
                    x = self.down_attns[ri](x, encoder_hidden_states)
                skips.append(x)
                ri += 1
            if i < len(ch) - 1:
                x = self.downsamplers[di](x)
                skips.append(x)
                di += 1

        x = self.mid_res1(x, temb)
        x = self.mid_attn(x, encoder_hidden_states)
        x = self.mid_res2(x, temb)

        ri = 0
        ui = 0
        for i in range(len(ch)):
            for j in range(self.config.layers_per_block + 1):
                skip = skips.pop()
                x = M.concat([x, skip],
                             axis=-1 if self._df == "NHWC" else 1)
                x = self.up_resnets[ri](x, temb)
                if self._up_plan[ri]:
                    x = self.up_attns[ri](x, encoder_hidden_states)
                ri += 1
            if i < len(ch) - 1:
                x = self.upsamplers[ui](x)
                ui += 1

        x = F.silu(self.conv_norm_out(x))
        x = self.conv_out(x)
        if self._df == "NHWC":
            x = M.transpose(x, [0, 3, 1, 2])
        return x


class _Identity(Layer):
    def forward(self, x, *a, **k):
        return x
