"""Seeded weights for the DeepSeek-V2 family (`family: deepseek_v2`), made on
the device, beside chipbench/weights.py (the dense decoder's) and with its
conventions: leaves are group.leaf, a matrix is stored [in, out] (an expert
stack [E, in, out]), groups are "embed", "layer.<i>", "final", and nothing
here imports the program.  Layers below `first_k_dense_replace` are dense,
the others hold the router, the shared experts (one SwiGLU) and the expert
stacks.  A group is made in one jitted call of its own (a layer of the
serving configuration is 1.17 GB in bfloat16 and twice that while its
float32 draws live: all groups in one call would not fit beside each other).
"""

from __future__ import annotations

import functools
import json


def groups(cfg):
    return (["embed"] + [f"layer.{i}" for i in range(cfg["num_hidden_layers"])]
            + ["final"])


def is_moe(cfg, group):
    return int(group.split(".")[1]) >= cfg["first_k_dense_replace"]


def group_kind(cfg, group):
    if not group.startswith("layer."):
        return group
    return "moe" if is_moe(cfg, group) else "dense"


def kind_shapes(cfg, kind):
    """{leaf: (shape, "matrix" | "norm")} of one kind of group."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    if kind == "embed":
        return {"embed": ((v, h), "matrix")}
    if kind == "final":
        return {"norm_f": ((h,), "norm"), "lm_head": ((h, v), "matrix")}
    nh, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    out = {"norm_attn": ((h,), "norm"),
           "wq": ((h, nh * (dn + dr)), "matrix"),
           "wkv_a": ((h, r + dr), "matrix"), "norm_kv": ((r,), "norm"),
           "wkv_b": ((r, nh * (dn + dv)), "matrix"),
           "wo": ((nh * dv, h), "matrix"), "norm_mlp": ((h,), "norm")}
    if kind == "dense":
        f = cfg["intermediate_size"]
        out.update(w_gate=((h, f), "matrix"), w_up=((h, f), "matrix"),
                   w_down=((f, h), "matrix"))
        return out
    e, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    fs = cfg["n_shared_experts"] * f
    out.update(w_router=((h, e), "matrix"),
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


def _group_key(seed, group_index):
    import jax

    # --seed may be a little over 2**31: fold the halves in separately
    key = jax.random.PRNGKey(int(seed) & 0x7FFFFFFF)
    key = jax.random.fold_in(key, int(seed) >> 31)
    return jax.random.fold_in(key, group_index)


def _make_kind(cfg, kind, key, dtype):
    import jax
    import jax.numpy as jnp

    std = cfg["assumed"]["initializer_std"]
    out = {}
    for j, (leaf, (shape, what)) in enumerate(kind_shapes(cfg, kind).items()):
        z = jax.random.normal(jax.random.fold_in(key, j), shape, jnp.float32)
        w = z * std if what == "matrix" else 1.0 + 0.1 * z
        out[leaf] = w.astype(dtype)
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
