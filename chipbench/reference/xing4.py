"""Plain reference of the Xing4.0 decoder: the DeepSeek-V2 block of
`reference/deepseek_v2.py` with query compression, sigmoid routing with a
bias correction, and manifold-constrained hyper-connections (mHC) round
every sublayer.

Written from the published `config.json` of `XingChen-AGI/Xing4.0-29B-A4B`
and the mHC paper (Xie et al. 2025, "mHC: Manifold-Constrained
Hyper-Connections", arXiv:2512.24880), in float32 `jax.numpy` with
`precision=highest`:

* the token's embedding enters as `hc_mult` = n copies, X [n, C];
* round each sublayer F (attention, then the FFN):
      x^ = X flattened to n*C values and RMS-normed (no weight)
      [h_pre | h_post | h_res] = alpha (x^ phi) + b, one alpha a mapping
      H_pre = sigmoid(h_pre), H_post = 2 sigmoid(h_post),
      H_res = Sinkhorn(clamp(h_res)): exp, then `hc_sinkhorn_iters` rounds
              of rows, then columns, each divided by its sum + `hc_eps`
      X <- H_res X + H_post^T F(RMSNorm_F(H_pre X));
* after the last layer the streams are summed, normed and fed to the head;
* queries: q = W_qb RMSNorm(W_qa x); MLA expanded as the DeepSeek-V2
  reference computes it;
* router: scores sigmoid(x W_r) in float32, the top k of score + bias (its
  own loop of argmax, ties to the lower index), the chosen scores
  renormalised to sum 1 and multiplied by `routed_scaling_factor`; every
  expert runs over every token under that weight (0 where not chosen); one
  shared expert on every token.

No kernel, no cache, no absorbed form, no sort and no grouped product.  It
imports nothing of the program: the pieces the DeepSeek-V2 block shares
(rotary, norms, the expanded attention of a head, SwiGLU, the arithmetic
modes) come from `reference/deepseek_v2.py`.  `mode` is that module's:
"f32" the reference, "fp8" the control (every product's operands rounded,
the router's and phi's included), "bf16" for the tests.

Leaves of a layer: norm_attn, wq_a [h, q_lora], norm_q [q_lora], wq_b
[q_lora, H*(dn+dr)], wkv_a, norm_kv, wkv_b, wo, norm_mlp, and per sublayer
(prefix hc_attn_ / hc_ffn_) phi [n*C, 2n + n*n], alpha [3], bias [2n + n*n];
then w_gate/w_up/w_down (a dense layer) or w_router [h, E], router_bias [E],
ws_gate/ws_up/ws_down and we_gate/we_up [E, h, f], we_down [E, f, h].
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.reference.deepseek_v2 import (_attend_head, f32, mm, rms_norm,
                                             rope, softmax_scale, swiglu)

HIGHEST = jax.lax.Precision.HIGHEST


# ------------------------------------------------------------------- mHC

def sinkhorn(logits, iters, eps):
    m = jnp.exp(logits)
    for _ in range(iters):
        m = m / (m.sum(-1, keepdims=True) + eps)
        m = m / (m.sum(-2, keepdims=True) + eps)
    return m


def hc_mappings(x, hw, cfg, mode):
    """x [B, S, n, C] -> H_pre [B, S, n], H_post [B, S, n], H_res
    [B, S, n, n]."""
    n = cfg["hc_mult"]
    b, s = x.shape[:2]
    flat = x.reshape(b, s, -1)
    flat = flat * jax.lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True)
                                + cfg["rms_norm_eps"])
    h = mm(flat, hw["phi"], mode)
    a, bias = hw["alpha"], hw["bias"]
    pre = jax.nn.sigmoid(a[0] * h[..., :n] + bias[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * h[..., n:2 * n] + bias[n:2 * n])
    res = jnp.clip(a[2] * h[..., 2 * n:] + bias[2 * n:],
                   cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"])
    res = sinkhorn(res.reshape(b, s, n, n), cfg["hc_sinkhorn_iters"],
                   cfg["hc_eps"])
    return pre, post, res


def hyper_connected(x, sublayer, hw, cfg, mode):
    """One sublayer F round the streams x [B, S, n, C]."""
    pre, post, res = hc_mappings(x, hw, cfg, mode)
    y = sublayer(jnp.einsum("bsn,bsnc->bsc", pre, x, precision=HIGHEST))
    return (jnp.einsum("bsmn,bsnc->bsmc", res, x, precision=HIGHEST)
            + post[..., None] * y[:, :, None, :])


def sub(lw, prefix):
    return {k[len(prefix):]: v for k, v in lw.items() if k.startswith(prefix)}


# ------------------------------------------------------------- attention

def attention(x, lw, cfg, mode):
    """x [B, S, h] -> [B, S, h]: MLA with query compression, expanded."""
    b, s, _ = x.shape
    nh, dn, dr = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                  cfg["qk_rope_head_dim"])
    dv, r, eps = cfg["v_head_dim"], cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    qa = rms_norm(mm(x, lw["wq_a"], mode), lw["norm_q"], eps)
    q = mm(qa, lw["wq_b"], mode).reshape(b, s, nh, dn + dr)
    a = mm(x, lw["wkv_a"], mode)
    c = rms_norm(a[..., :r], lw["norm_kv"], eps)
    kv = mm(c, lw["wkv_b"], mode).reshape(b, s, nh, dn + dv)
    k_n, v = kv[..., :dn], kv[..., dn:]
    rot = jax.vmap(lambda t: rope(t, cfg))
    qf = jnp.concatenate([q[..., :dn], rot(q[..., dn:])], -1)
    k_r = rot(a[..., r:])
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

def top_k(p, k):
    """The k largest of each row of p [T, E] by k rounds of argmax (ties to
    the lower index): indices [T, k]."""
    idxs = []
    for _ in range(k):
        i = jnp.argmax(p, -1)
        idxs.append(i)
        p = jnp.where(jax.nn.one_hot(i, p.shape[-1], dtype=bool), -jnp.inf, p)
    return jnp.stack(idxs, 1)


def router(x, lw, cfg, mode):
    """Weights [T, E] a token gives each expert: its renormalised, scaled
    sigmoid score where the expert is among the top k of score + bias,
    else 0."""
    scores = jax.nn.sigmoid(mm(x, lw["w_router"], mode))
    idxs = top_k(scores + lw["router_bias"], cfg["num_experts_per_tok"])
    hot = jax.nn.one_hot(idxs, scores.shape[-1], dtype=jnp.float32)
    chosen = jnp.einsum("te,tke->tk", scores, hot)
    chosen = chosen / (chosen.sum(-1, keepdims=True) + 1e-20)
    return (jnp.einsum("tk,tke->te", chosen, hot)
            * cfg["routed_scaling_factor"])


def moe(x, lw, cfg, mode):
    """x [T, h]: the shared expert on every token plus each token's chosen
    experts, every expert over every token, weighted (0 where not chosen)."""
    w = router(x, lw, cfg, mode)

    def one(acc, t):
        wg, wu, wd, col = t
        return acc + col[:, None] * swiglu(x, wg, wu, wd, mode), None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (lw["we_gate"], lw["we_up"], lw["we_down"], w.T))
    return routed + swiglu(x, lw["ws_gate"], lw["ws_up"], lw["ws_down"], mode)


def layer(x, lw, cfg, mode="f32"):
    """The streams x [B, S, n, C] through one decoder layer."""
    eps = cfg["rms_norm_eps"]

    def attend(h):
        return attention(rms_norm(h, lw["norm_attn"], eps), lw, cfg, mode)

    def ffn(h):
        h = rms_norm(h, lw["norm_mlp"], eps)
        if "w_router" in lw:
            b, s, d = h.shape
            return moe(h.reshape(b * s, d), lw, cfg, mode).reshape(b, s, d)
        return swiglu(h, lw["w_gate"], lw["w_up"], lw["w_down"], mode)

    x = hyper_connected(x, attend, sub(lw, "hc_attn_"), cfg, mode)
    return hyper_connected(x, ffn, sub(lw, "hc_ffn_"), cfg, mode)


def logits_at(cfg, weights_of, ids, rows, cols, mode="f32"):
    """Logits [len(rows), vocab] at positions (rows[i], cols[i]) of the
    padded token matrix `ids` [N, L], layer by layer: `weights_of(group)`
    gives one group at a time, so that only one layer's weights are held."""
    e = f32(weights_of("embed"))["embed"][jnp.asarray(ids)]
    x = jnp.broadcast_to(e[:, :, None, :], e.shape[:2] + (cfg["hc_mult"],
                                                         e.shape[-1]))
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
        picked = x[jnp.asarray(rows), jnp.asarray(cols)].sum(1)
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
