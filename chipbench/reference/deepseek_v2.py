"""Plain reference of the DeepSeek-V2 decoder: latent attention (MLA) in its
expanded form, YaRN rotary frequencies, a leading dense SwiGLU layer, then
mixture-of-experts layers with shared experts.

Written from the published equations (DeepSeek-AI 2024, "DeepSeek-V2: A
Strong, Economical, and Efficient Mixture-of-Experts Language Model", and
the `config.json` / `modeling_deepseek.py` of `deepseek-ai/DeepSeek-V2-Lite`:
no query compression, softmax router in float32, greedy top-k without
renormalisation, shared experts as one SwiGLU added to every token, the
rotary slice de-interleaved and then half-rotated, YaRN with
`mscale == mscale_all_dim` so that only the softmax scale is changed), in
float32 `jax.numpy` with `precision=highest`.  No kernel, no cache, no
absorbed form, no sort and no grouped product: every cached key and value
is expanded from its latent, and every expert runs over every token under a
mask.  Its top-k is its own (a loop of argmax).  It imports nothing from
the program and nothing else of the benchmark.

`mode` selects the arithmetic of every matrix product:
  "f32"  float32 operands, precision highest  — the reference
  "fp8"  both operands rounded to float8_e4m3fn with one scale a tensor (an
         expert's matrix is a tensor), accumulated in float32 — the control
         for a configuration that states bfloat16
  "bf16" operands rounded to bfloat16 — for the tests
The router's product is in float32 in every mode but "fp8" and "bf16",
which round its operands like any other product's: the control has to be
a lower precision everywhere.

Weights arrive as {leaf: array} groups ("embed", "layer.<i>", "final"); a
matrix is stored [in, out].  Leaves of a layer: norm_attn, wq [h, H*(dn+dr)],
wkv_a [h, r+dr], norm_kv [r], wkv_b [r, H*(dn+dv)], wo [H*dv, h], norm_mlp,
then either w_gate/w_up/w_down (a dense layer) or w_router [h, E],
ws_gate/ws_up/ws_down (the shared experts as one SwiGLU) and
we_gate/we_up [E, h, f], we_down [E, f, h].
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F8_MAX = 448.0  # largest finite float8_e4m3fn


def _round(x, mode):
    if mode == "f32":
        return x
    if mode == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if mode == "fp8":
        scale = jnp.max(jnp.abs(x)) / F8_MAX + 1e-30
        return (x / scale).astype(jnp.float8_e4m3fn).astype(
            jnp.float32) * scale
    raise ValueError(f"unknown arithmetic mode {mode!r}")


def mm(x, w, mode):
    return jnp.matmul(_round(x, mode), _round(w, mode),
                      precision=jax.lax.Precision.HIGHEST)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


# ------------------------------------------------------------------ YaRN

def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg):
    """The rotary pairs' frequencies [dr / 2] (Peng et al. 2023, as the
    published model computes them)."""
    d, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    sc = cfg["rope_scaling"]
    factor, orig = sc["factor"], sc["original_max_position_embeddings"]
    f = [theta ** (-2.0 * i / d) for i in range(d // 2)]

    def corr(rotations):
        return d * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(corr(sc["beta_fast"])), 0)
    high = min(math.ceil(corr(sc["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    out = []
    for i, fi in enumerate(f):
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(fi / factor * ramp + fi * (1.0 - ramp))
    return jnp.asarray(out, jnp.float32)


def softmax_scale(cfg):
    sc = cfg["rope_scaling"]
    m = yarn_mscale(sc["factor"], sc["mscale_all_dim"])
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def table_scale(cfg):
    """What the cos/sin tables are multiplied by (1 for this model)."""
    sc = cfg["rope_scaling"]
    return (yarn_mscale(sc["factor"], sc["mscale"])
            / yarn_mscale(sc["factor"], sc["mscale_all_dim"]))


def rope(x, cfg):
    """x [S, ..., dr] at positions 0..S-1: the slice is de-interleaved
    (even members, then odd), then half-rotated; the result stays in that
    order, for queries and keys alike."""
    s = x.shape[0]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * yarn_inv_freq(cfg)[None]
    shape = (s,) + (1,) * (x.ndim - 2) + (ang.shape[-1],)
    cos = (jnp.cos(ang) * table_scale(cfg)).reshape(shape)
    sin = (jnp.sin(ang) * table_scale(cfg)).reshape(shape)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# ------------------------------------------------------------- attention

def _attend_head(q, k, v, scale, mode):
    """Causal attention of one head: q, k [S, dn + dr], v [S, dv]."""
    s = q.shape[0]
    scores = mm(q, k.T, mode) * scale
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
    return mm(probs, v, mode)


def attention(x, lw, cfg, mode):
    """x [B, S, h] -> [B, S, h]: MLA, expanded."""
    b, s, _ = x.shape
    nh, dn, dr = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                  cfg["qk_rope_head_dim"])
    dv, r = cfg["v_head_dim"], cfg["kv_lora_rank"]
    q = mm(x, lw["wq"], mode).reshape(b, s, nh, dn + dr)
    a = mm(x, lw["wkv_a"], mode)
    c = rms_norm(a[..., :r], lw["norm_kv"], cfg["rms_norm_eps"])
    kv = mm(c, lw["wkv_b"], mode).reshape(b, s, nh, dn + dv)
    k_n, v = kv[..., :dn], kv[..., dn:]
    rot = jax.vmap(lambda t: rope(t, cfg))
    q_r = rot(q[..., dn:])                               # [B, S, H, dr]
    k_r = rot(a[..., r:])                                # [B, S, dr]
    qf = jnp.concatenate([q[..., :dn], q_r], -1)
    kf = jnp.concatenate(
        [k_n, jnp.broadcast_to(k_r[:, :, None, :], (b, s, nh, dr))], -1)

    def heads_first(t):
        return t.transpose(0, 2, 1, 3).reshape((b * nh, s, t.shape[-1]))

    head = jax.checkpoint(functools.partial(
        _attend_head, scale=softmax_scale(cfg), mode=mode))
    o = jax.lax.map(lambda t: head(*t),
                    (heads_first(qf), heads_first(kf), heads_first(v)))
    o = o.reshape(b, nh, s, dv).transpose(0, 2, 1, 3).reshape(b, s, nh * dv)
    return mm(o, lw["wo"], mode)


# ------------------------------------------------------------------ FFN

def swiglu(x, w_gate, w_up, w_down, mode):
    return mm(jax.nn.silu(mm(x, w_gate, mode)) * mm(x, w_up, mode),
              w_down, mode)


def top_k(p, k):
    """The k largest of each row of p [T, E], by k rounds of argmax (ties
    to the lower index): (values [T, k], indices [T, k])."""
    vals, idxs = [], []
    for _ in range(k):
        i = jnp.argmax(p, -1)
        vals.append(jnp.take_along_axis(p, i[:, None], 1)[:, 0])
        idxs.append(i)
        p = jnp.where(jax.nn.one_hot(i, p.shape[-1], dtype=bool), -1.0, p)
    return jnp.stack(vals, 1), jnp.stack(idxs, 1)


def router(x, w_router, cfg, mode):
    """Weights [T, E] a token gives each expert: its softmax probability
    where the expert is among its top k (not renormalised), else 0."""
    p = jax.nn.softmax(mm(x, w_router, mode), -1)
    vals, idxs = top_k(p, cfg["num_experts_per_tok"])
    hot = jax.nn.one_hot(idxs, p.shape[-1], dtype=jnp.float32)  # [T, k, E]
    w = jnp.einsum("tk,tke->te", vals, hot)
    return w * cfg.get("routed_scaling_factor", 1.0)


def moe(x, lw, cfg, mode):
    """x [T, h]: the shared experts on every token plus each token's chosen
    experts, every expert run over every token and weighted (0 where it
    was not chosen).  No capacity: no token is dropped."""
    w = router(x, lw["w_router"], cfg, mode)                     # [T, E]

    def one(acc, t):
        wg, wu, wd, col = t
        return acc + col[:, None] * swiglu(x, wg, wu, wd, mode), None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (lw["we_gate"], lw["we_up"], lw["we_down"], w.T))
    return routed + swiglu(x, lw["ws_gate"], lw["ws_up"], lw["ws_down"], mode)


def layer(x, lw, cfg, mode="f32"):
    eps = cfg["rms_norm_eps"]
    x = x + attention(rms_norm(x, lw["norm_attn"], eps), lw, cfg, mode)
    h = rms_norm(x, lw["norm_mlp"], eps)
    if "w_router" in lw:
        b, s, d = h.shape
        return x + moe(h.reshape(b * s, d), lw, cfg, mode).reshape(b, s, d)
    return x + swiglu(h, lw["w_gate"], lw["w_up"], lw["w_down"], mode)


def f32(group):
    return {k: v.astype(jnp.float32) for k, v in group.items()}


def logits_at(cfg, weights_of, ids, rows, cols, mode="f32"):
    """Logits [len(rows), vocab] at positions (rows[i], cols[i]) of the
    padded token matrix `ids` [N, L], layer by layer: `weights_of(group)`
    gives one group at a time, so that only one layer's weights are held."""
    x = f32(weights_of("embed"))["embed"][jnp.asarray(ids)]
    steps = {}
    for i in range(cfg["num_hidden_layers"]):
        lw = f32(weights_of(f"layer.{i}"))
        kind = "w_router" in lw
        if kind not in steps:
            steps[kind] = jax.jit(functools.partial(layer, cfg=cfg,
                                                    mode=mode))
        x = steps[kind](x, lw)
        del lw
    fin = f32(weights_of("final"))

    @jax.jit
    def head(x, fin):
        picked = x[jnp.asarray(rows), jnp.asarray(cols)]
        return mm(rms_norm(picked, fin["norm_f"], cfg["rms_norm_eps"]),
                  fin["lm_head"], mode)

    return head(x, fin)


def full_logits(cfg, weights, ids, mode="f32"):
    """Logits [N, L, vocab] of the whole token matrix (the tests' sizes);
    `weights` is {group: {leaf: array}}."""
    n, length = ids.shape
    rows = jnp.repeat(jnp.arange(n), length)
    cols = jnp.tile(jnp.arange(length), n)
    out = logits_at(cfg, lambda g: weights[g], ids, rows, cols, mode)
    return out.reshape(n, length, -1)
