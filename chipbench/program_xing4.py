"""The Xing4.0 family's part of what chipbench/program.py is for the dense
decoder: it builds the program's own model class
(`paddle_tpu.models.xing4.Xing4ForCausalLM`) round the benchmark's seeded
weights one decoder layer at a time, with the constructors' zeros in host
memory, as program_deepseek_v2.py does for its family (whose
configuration mapping, leaf names of the embedding, the head and the dense
FFN, and `assign` it imports), and maps this family's leaf names.
"""

from __future__ import annotations

import dataclasses

from chipbench import program_deepseek_v2 as dsv2
from chipbench import weights_xing4 as W
from chipbench.program_deepseek_v2 import assign

ATTN_PATHS = {
    "norm_attn": "input_layernorm.weight",
    "wq_a": "self_attn.q_a_proj.weight",
    "norm_q": "self_attn.q_a_layernorm.weight",
    "wq_b": "self_attn.q_b_proj.weight",
    "wkv_a": "self_attn.kv_a_proj_with_mqa.weight",
    "norm_kv": "self_attn.kv_a_layernorm.weight",
    "wkv_b": "self_attn.kv_b_proj.weight", "wo": "self_attn.o_proj.weight",
    "norm_mlp": "post_attention_layernorm.weight"}
HC_PATHS = {f"hc_{s}_{p}": f"{s}_hc.{p}" for s in ("attn", "ffn")
            for p in ("phi", "alpha", "bias")}
MOE_PATHS = dict(dsv2.MOE_PATHS, router_bias="mlp.e_score_correction_bias")


def model_config(cfg, **over):
    from paddle_tpu.models.xing4 import Xing4Config

    base = dataclasses.asdict(dsv2.model_config(cfg))
    base.update(
        q_lora_rank=cfg["q_lora_rank"], hc_mult=cfg["hc_mult"],
        hc_sinkhorn_iters=cfg["hc_sinkhorn_iters"], hc_eps=cfg["hc_eps"],
        mhc_h_res_clamp_min=cfg["mhc_h_res_clamp_min"],
        mhc_h_res_clamp_max=cfg["mhc_h_res_clamp_max"], **over)
    return Xing4Config(**base)


def program_leaves(cfg, group, leaves):
    """{parameter path inside the group's module: array} of one group."""
    import jax.numpy as jnp

    if not group.startswith("layer."):
        return dsv2.program_leaves(cfg, group, leaves)
    out = {path: leaves[leaf] for leaf, path in ATTN_PATHS.items()}
    out.update({path: leaves[leaf] for leaf, path in HC_PATHS.items()})
    if W.is_moe(cfg, group):
        joined = dict(leaves, we_gate_up=jnp.concatenate(
            [leaves["we_gate"], leaves["we_up"]], axis=-1))
        out.update({path: joined[leaf] for leaf, path in MOE_PATHS.items()})
    else:
        out.update({path: leaves[leaf]
                    for leaf, path in dsv2.DENSE_PATHS.items()})
    return out


def build_model(cfg, weights_of, **config_over):
    """`Xing4ForCausalLM` at `cfg` holding the benchmark's weights in their
    own dtype; `weights_of(group)` gives one group at a time."""
    import jax

    from paddle_tpu.models.xing4 import Xing4DecoderLayer, Xing4ForCausalLM

    # the benchmark assigns every parameter (initializer_range None), and
    # the zeros the constructors leave are made in the host's memory
    mcfg = model_config(cfg, initializer_range=None, **config_over)
    depth, mcfg.num_hidden_layers = mcfg.num_hidden_layers, 0
    host = jax.devices("cpu")[0]
    with jax.default_device(host):
        model = Xing4ForCausalLM(mcfg)
    for g in ("embed", "final"):
        assign(model, program_leaves(cfg, g, weights_of(g)))
    for i in range(depth):
        with jax.default_device(host):
            layer = Xing4DecoderLayer(mcfg, i)
        assign(layer, program_leaves(cfg, f"layer.{i}",
                                     weights_of(f"layer.{i}")))
        model.model.layers.append(layer)
    mcfg.num_hidden_layers = depth
    return model
