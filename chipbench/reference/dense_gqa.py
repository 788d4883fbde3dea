"""Plain reference of the pre-norm RMSNorm + RoPE + GQA + SwiGLU decoder.

Written from the published equations (Mistral-7B: Jiang et al. 2023; the
Hugging Face `MistralForCausalLM` layout: half-rotation RoPE, untied LM head,
no biases, no sliding window in v0.3), in float32 `jax.numpy` with
`jax.default_matmul_precision("highest")`.  No kernel, no cache, no batching
tricks; attention runs one (row, kv head) block at a time so that the score
matrix fits.  It imports nothing from the program.

`mode` selects the arithmetic of every matrix product:
  "f32"  float32 operands, precision highest  — the reference
  "fp8"  both operands rounded to float8_e4m3fn with one scale a tensor, the
         product accumulated in float32                 — the control for a
         configuration that states bfloat16 (the nearest precision below)
  "bf16" operands rounded to bfloat16                   — for the tests
Weights arrive as {leaf: array} groups from `chipbench.weights`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F8_MAX = 448.0  # largest finite float8_e4m3fn


def _round(x, mode):
    """x as the operand a product in `mode` sees, with a straight-through
    gradient."""
    if mode == "f32":
        return x
    if mode == "bf16":
        q = x.astype(jnp.bfloat16).astype(jnp.float32)
    elif mode == "fp8":
        scale = jnp.max(jnp.abs(x)) / F8_MAX + 1e-30
        q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    else:
        raise ValueError(f"unknown arithmetic mode {mode!r}")
    return x + jax.lax.stop_gradient(q - x)


def mm(x, w, mode):
    return jnp.matmul(_round(x, mode), _round(w, mode),
                      precision=jax.lax.Precision.HIGHEST)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta):
    """Half-rotation rotary embedding of x [S, heads, D] at positions 0..S-1."""
    s, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend_block(q, k, v, mode):
    """Causal attention of one kv head: q [S, G, D], k and v [S, D]."""
    s, g, d = q.shape
    scores = mm(q.transpose(1, 0, 2).reshape(g * s, d), k.T, mode)
    scores = scores.reshape(g, s, s) / jnp.sqrt(jnp.float32(d))
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
    out = mm(probs.reshape(g * s, s), v, mode).reshape(g, s, d)
    return out.transpose(1, 0, 2)


def attention(x, lw, cfg, mode):
    """x [B, S, H] -> [B, S, H]."""
    b, s, _ = x.shape
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    g = nh // nkv
    q = mm(x, lw["wq"], mode).reshape(b, s, nh, d)
    k = mm(x, lw["wk"], mode).reshape(b, s, nkv, d)
    v = mm(x, lw["wv"], mode).reshape(b, s, nkv, d)
    q = jax.vmap(lambda t: rope(t, cfg["rope_theta"]))(q)
    k = jax.vmap(lambda t: rope(t, cfg["rope_theta"]))(k)
    # blocks of (row, kv head): [B * KV, S, G, D]
    qb = q.reshape(b, s, nkv, g, d).transpose(0, 2, 1, 3, 4).reshape(
        b * nkv, s, g, d)
    kb = k.transpose(0, 2, 1, 3).reshape(b * nkv, s, d)
    vb = v.transpose(0, 2, 1, 3).reshape(b * nkv, s, d)
    block = jax.checkpoint(functools.partial(_attend_block, mode=mode))
    ob = jax.lax.map(lambda t: block(*t), (qb, kb, vb))   # [B*KV, S, G, D]
    o = ob.reshape(b, nkv, s, g, d).transpose(0, 2, 1, 3, 4).reshape(
        b, s, nh * d)
    return mm(o, lw["wo"], mode)


def mlp(x, lw, mode):
    gate = mm(x, lw["w_gate"], mode)
    return mm(jax.nn.silu(gate) * mm(x, lw["w_up"], mode), lw["w_down"], mode)


def layer(x, lw, cfg, mode="f32"):
    eps = cfg["rms_norm_eps"]
    x = x + attention(rms_norm(x, lw["norm_attn"], eps), lw, cfg, mode)
    return x + mlp(rms_norm(x, lw["norm_mlp"], eps), lw, mode)


def f32(group):
    return {k: v.astype(jnp.float32) for k, v in group.items()}


# ---------------------------------------------------------------- serving

def logits_at(cfg, weights_of, ids, rows, cols, mode="f32"):
    """Logits [len(rows), vocab] at positions (rows[i], cols[i]) of the
    padded token matrix `ids` [N, L], layer by layer: `weights_of(group)`
    gives one group at a time, so that only one layer's weights are held."""
    from chipbench import weights as W

    x = f32(weights_of("embed"))["embed"][jnp.asarray(ids)]
    step = jax.jit(functools.partial(layer, cfg=cfg, mode=mode))
    for g in W.groups(cfg):
        if g.startswith("layer."):
            x = step(x, f32(weights_of(g)))
    fin = f32(weights_of("final"))

    @jax.jit
    def head(x, fin):
        picked = x[jnp.asarray(rows), jnp.asarray(cols)]
        return mm(rms_norm(picked, fin["norm_f"], cfg["rms_norm_eps"]),
                  fin["lm_head"], mode)

    return head(x, fin)


# --------------------------------------------------------------- training

def loss_fn(params, ids, labels, cfg, mode="f32", row_block=2048):
    """Mean next-token cross entropy; params is {group: {leaf: f32 array}}."""
    from chipbench import weights as W

    x = params["embed"]["embed"][ids]
    step = jax.checkpoint(functools.partial(layer, cfg=cfg, mode=mode))
    for g in W.groups(cfg):
        if g.startswith("layer."):
            x = step(x, params[g])
    fin = params["final"]
    h = rms_norm(x, fin["norm_f"], cfg["rms_norm_eps"])
    h = h.reshape(-1, h.shape[-1])
    y = labels.reshape(-1)
    nb = max(1, h.shape[0] // row_block)

    @jax.checkpoint
    def block_nll(t):
        hb, yb = t
        logp = jax.nn.log_softmax(mm(hb, fin["lm_head"], mode), -1)
        return -jnp.take_along_axis(logp, yb[:, None], 1)[:, 0].sum()

    hb = h.reshape(nb, -1, h.shape[-1])
    yb = y.reshape(nb, -1)
    return jax.lax.map(block_nll, (hb, yb)).sum() / y.shape[0]


def leaf_norms(tree):
    return jax.tree.map(lambda a: jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32)))), tree)


def make_train_step(cfg, hyper, mode="f32"):
    """AdamW (Loshchilov & Hutter 2019, decay decoupled, bias-corrected
    moments, decay on every leaf as the configuration states) round
    `loss_fn`; the state is donated so one copy of it lives."""
    lr, b1, b2 = hyper["learning_rate"], hyper["beta1"], hyper["beta2"]
    eps, wd = hyper["epsilon"], hyper["weight_decay"]

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, m, v, t, ids, labels):
        loss, grads = jax.value_and_grad(loss_fn)(params, ids, labels, cfg,
                                                  mode)
        t = t + 1
        m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
        v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)

        def upd(p, m_, v_):
            mh, vh = m_ / (1 - b1 ** t), v_ / (1 - b2 ** t)
            return p * (1 - lr * wd) - lr * mh / (jnp.sqrt(vh) + eps)

        return (jax.tree.map(upd, params, m, v), m, v, t, loss,
                leaf_norms(grads))

    return step


def train_steps(cfg, hyper, params, batches, mode="f32"):
    """`len(batches)` AdamW steps from `params` (f32, consumed).  Returns
    (losses, first-step gradient norm of each leaf, final params)."""
    step = make_train_step(cfg, hyper, mode)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    t = jnp.zeros((), jnp.float32)
    losses, first = [], None
    for ids, labels in batches:
        params, m, v, t, loss, gn = step(params, m, v, t, jnp.asarray(ids),
                                         jnp.asarray(labels))
        losses.append(float(loss))
        if first is None:
            first = jax.tree.map(float, gn)
    return losses, first, params
