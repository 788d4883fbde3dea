"""Seeded weights for the dense GQA decoder, made on the device.

The benchmark makes the weights, hands them to the program in the dtype they
are served in, and makes them again (group by group) for the plain reference
after the program's state is freed.  Nothing here imports the program.

Leaves are named group.leaf; a matrix is stored [in, out] (y = x @ W).
Groups: "embed", "layer.<i>", "final" (last norm + LM head), so that the
reference can hold one layer at a time.
"""

from __future__ import annotations

import functools

LAYER_LEAVES = ("norm_attn", "wq", "wk", "wv", "wo",
                "norm_mlp", "w_gate", "w_up", "w_down")


def groups(cfg):
    return (["embed"] + [f"layer.{i}" for i in range(cfg["num_hidden_layers"])]
            + ["final"])


def group_shapes(cfg, group):
    """{leaf: (shape, kind)} of one group; kind is "matrix" or "norm"."""
    h, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    d = cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    if group == "embed":
        return {"embed": ((v, h), "matrix")}
    if group == "final":
        return {"norm_f": ((h,), "norm"), "lm_head": ((h, v), "matrix")}
    return {"norm_attn": ((h,), "norm"), "wq": ((h, q), "matrix"),
            "wk": ((h, kv), "matrix"), "wv": ((h, kv), "matrix"),
            "wo": ((q, h), "matrix"), "norm_mlp": ((h,), "norm"),
            "w_gate": ((h, f), "matrix"), "w_up": ((h, f), "matrix"),
            "w_down": ((f, h), "matrix")}


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


def _make_group(cfg, group, key, dtype):
    import jax
    import jax.numpy as jnp

    std = cfg["assumed"]["initializer_std"]
    out = {}
    for j, (leaf, (shape, kind)) in enumerate(group_shapes(cfg, group).items()):
        z = jax.random.normal(jax.random.fold_in(key, j), shape, jnp.float32)
        w = z * std if kind == "matrix" else 1.0 + 0.1 * z
        out[leaf] = w.astype(dtype)
    return out


@functools.lru_cache(maxsize=8)
def _group_maker(cfg_json, kind, dtype_name):
    """One jitted maker a kind of group (every layer has the same shapes),
    so the reference's walk over the layers traces it once."""
    import json

    import jax
    import jax.numpy as jnp

    return jax.jit(functools.partial(_make_group, json.loads(cfg_json), kind,
                                     dtype=jnp.dtype(dtype_name)))


def make_group(cfg, seed, group, dtype):
    """One group's leaves, jitted; the values `make_all` gives for it."""
    import json

    import jax.numpy as jnp

    kind = "layer.0" if group.startswith("layer.") else group
    fn = _group_maker(json.dumps(cfg, sort_keys=True), kind,
                      jnp.dtype(dtype).name)
    return fn(_group_key(seed, groups(cfg).index(group)))


def make_all(cfg, seed, dtype):
    """{group: {leaf: array}} for the whole model in one jitted call."""
    import jax

    names = groups(cfg)

    @jax.jit
    def build(keys):
        return {g: _make_group(cfg, g, keys[i], dtype)
                for i, g in enumerate(names)}

    return build([_group_key(seed, i) for i in range(len(names))])
