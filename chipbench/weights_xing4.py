"""Seeded weights for the Xing4.0 family (`family: xing4`), made on the
device, with weights_deepseek_v2.py's conventions and its groups, group
kinds and seed folding (imported from there): leaves are group.leaf, a
matrix is stored [in, out] (an expert stack [E, in, out]), a group is made
in one jitted call of its own, and nothing here imports the program.

What this family adds to a layer: query compression (wq_a, norm_q, wq_b in
place of wq), the router's bias, and per sublayer the mHC leaves phi,
alpha and bias.  How each kind of leaf is drawn (`assumed` of the
configuration names the same rules):

  matrix        initializer_std x normal
  norm          1 + 0.1 x normal
  hc_alpha      0.5 + 0.1 x normal (one scale a mapping)
  hc_bias       0.5 x normal, and 2 on the diagonal of the n x n mixing
                logits: the streams keep most of themselves and still mix
  router_bias   0.1 x normal
"""

from __future__ import annotations

import functools
import json

from chipbench.weights_deepseek_v2 import (_group_key, group_kind, groups,
                                           is_moe)

__all__ = ["groups", "is_moe", "group_kind", "kind_shapes", "group_shapes",
           "n_params", "make_group", "make_all"]


def _hc_leaves(cfg, prefix):
    n, h = cfg["hc_mult"], cfg["hidden_size"]
    width = 2 * n + n * n
    return {prefix + "phi": ((n * h, width), "matrix"),
            prefix + "alpha": ((3,), "hc_alpha"),
            prefix + "bias": ((width,), "hc_bias")}


def kind_shapes(cfg, kind):
    """{leaf: (shape, how it is drawn)} of one kind of group."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    if kind == "embed":
        return {"embed": ((v, h), "matrix")}
    if kind == "final":
        return {"norm_f": ((h,), "norm"), "lm_head": ((h, v), "matrix")}
    nh, r, ql = (cfg["num_attention_heads"], cfg["kv_lora_rank"],
                 cfg["q_lora_rank"])
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    out = {"norm_attn": ((h,), "norm"),
           "wq_a": ((h, ql), "matrix"), "norm_q": ((ql,), "norm"),
           "wq_b": ((ql, nh * (dn + dr)), "matrix"),
           "wkv_a": ((h, r + dr), "matrix"), "norm_kv": ((r,), "norm"),
           "wkv_b": ((r, nh * (dn + dv)), "matrix"),
           "wo": ((nh * dv, h), "matrix"), "norm_mlp": ((h,), "norm")}
    out.update(_hc_leaves(cfg, "hc_attn_"))
    out.update(_hc_leaves(cfg, "hc_ffn_"))
    if kind == "dense":
        f = cfg["intermediate_size"]
        out.update(w_gate=((h, f), "matrix"), w_up=((h, f), "matrix"),
                   w_down=((f, h), "matrix"))
        return out
    e, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    fs = cfg["n_shared_experts"] * f
    out.update(w_router=((h, e), "matrix"),
               router_bias=((e,), "router_bias"),
               ws_gate=((h, fs), "matrix"), ws_up=((h, fs), "matrix"),
               ws_down=((fs, h), "matrix"),
               we_gate=((e, h, f), "matrix"), we_up=((e, h, f), "matrix"),
               we_down=((e, f, h), "matrix"))
    return out


def group_shapes(cfg, group):
    return kind_shapes(cfg, group_kind(cfg, group))


def n_params(cfg):
    total = 0
    for g in groups(cfg):
        for shape, _ in group_shapes(cfg, g).values():
            n = 1
            for s in shape:
                n *= s
            total += n
    return total


def _draw(cfg, z, what):
    import jax.numpy as jnp

    if what == "matrix":
        return z * cfg["assumed"]["initializer_std"]
    if what == "norm":
        return 1.0 + 0.1 * z
    if what == "hc_alpha":
        return 0.5 + 0.1 * z
    if what == "router_bias":
        return 0.1 * z
    if what == "hc_bias":
        n = cfg["hc_mult"]
        eye = jnp.eye(n, dtype=z.dtype).reshape(-1)
        return 0.5 * z + jnp.concatenate([jnp.zeros(2 * n, z.dtype), 2 * eye])
    raise ValueError(what)


def _make_kind(cfg, kind, key, dtype):
    import jax
    import jax.numpy as jnp

    out = {}
    for j, (leaf, (shape, what)) in enumerate(kind_shapes(cfg, kind).items()):
        z = jax.random.normal(jax.random.fold_in(key, j), shape, jnp.float32)
        out[leaf] = _draw(cfg, z, what).astype(dtype)
    return out


@functools.lru_cache(maxsize=8)
def _kind_maker(cfg_json, kind, dtype_name):
    import jax
    import jax.numpy as jnp

    return jax.jit(functools.partial(_make_kind, json.loads(cfg_json), kind,
                                     dtype=jnp.dtype(dtype_name)))


def make_group(cfg, seed, group, dtype):
    """One group's leaves, in one jitted call."""
    import jax.numpy as jnp

    fn = _kind_maker(json.dumps(cfg, sort_keys=True), group_kind(cfg, group),
                     jnp.dtype(dtype).name)
    return fn(_group_key(seed, groups(cfg).index(group)))


def make_all(cfg, seed, dtype):
    """{group: {leaf: array}} for the whole model, a group a call."""
    return {g: make_group(cfg, seed, g, dtype) for g in groups(cfg)}
