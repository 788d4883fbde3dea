"""The DeepSeek-V2 family's part of what chipbench/program.py is for the
dense decoder: it builds the program's own model class
(`paddle_tpu.models.deepseek_v2.DeepSeekV2ForCausalLM`) round the benchmark's
seeded weights, one decoder layer at a time (`Layer` initialises every
parameter in float32 on the device: an expert layer is 2.3 GB that way, the
whole model would be 18 GB), and maps leaf names.  The program keeps every
expert's gate and up matrices side by side in one stack: the two benchmark
leaves are joined here.
"""

from __future__ import annotations

from chipbench import weights_deepseek_v2 as W

ATTN_PATHS = {
    "norm_attn": "input_layernorm.weight",
    "wq": "self_attn.q_proj.weight",
    "wkv_a": "self_attn.kv_a_proj_with_mqa.weight",
    "norm_kv": "self_attn.kv_a_layernorm.weight",
    "wkv_b": "self_attn.kv_b_proj.weight", "wo": "self_attn.o_proj.weight",
    "norm_mlp": "post_attention_layernorm.weight"}
DENSE_PATHS = {"w_gate": "mlp.gate_proj.weight", "w_up": "mlp.up_proj.weight",
               "w_down": "mlp.down_proj.weight"}
MOE_PATHS = {"w_router": "mlp.gate.weight",
             "ws_gate": "mlp.shared_experts.gate_proj.weight",
             "ws_up": "mlp.shared_experts.up_proj.weight",
             "ws_down": "mlp.shared_experts.down_proj.weight",
             "we_gate_up": "mlp.experts_gate_up",
             "we_down": "mlp.experts_down"}


def model_config(cfg, **over):
    from paddle_tpu.models.deepseek_v2 import DeepSeekV2Config

    sc = cfg["rope_scaling"]
    return DeepSeekV2Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        n_routed_experts=cfg["n_routed_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        n_shared_experts=cfg["n_shared_experts"],
        first_k_dense_replace=cfg["first_k_dense_replace"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        rope_factor=sc["factor"],
        rope_original_max_position=sc["original_max_position_embeddings"],
        rope_beta_fast=sc["beta_fast"], rope_beta_slow=sc["beta_slow"],
        rope_mscale=sc["mscale"], rope_mscale_all_dim=sc["mscale_all_dim"],
        **over)


def program_leaves(cfg, group, leaves):
    """{parameter path inside the group's module: array} of one group."""
    import jax.numpy as jnp

    if group == "embed":
        return {"model.embed_tokens.weight": leaves["embed"]}
    if group == "final":
        return {"model.norm.weight": leaves["norm_f"],
                "lm_head.weight": leaves["lm_head"]}
    out = {path: leaves[leaf] for leaf, path in ATTN_PATHS.items()}
    if W.is_moe(cfg, group):
        joined = dict(leaves, we_gate_up=jnp.concatenate(
            [leaves["we_gate"], leaves["we_up"]], axis=-1))
        out.update({path: joined[leaf] for leaf, path in MOE_PATHS.items()})
    else:
        out.update({path: leaves[leaf] for leaf, path in DENSE_PATHS.items()})
    return out


def assign(module, values):
    params = dict(module.named_parameters())
    for path, value in values.items():
        p = params[path]
        if tuple(p._data.shape) != tuple(value.shape):
            raise ValueError(f"{path}: program {p._data.shape}, benchmark "
                             f"{value.shape}")
        p._data = value


def build_model(cfg, weights_of, **config_over):
    """`DeepSeekV2ForCausalLM` at `cfg` holding the benchmark's weights in
    their own dtype; `weights_of(group)` gives one group at a time."""
    import jax

    from paddle_tpu.models.deepseek_v2 import (DeepSeekV2DecoderLayer,
                                               DeepSeekV2ForCausalLM)

    # the benchmark assigns every parameter: none is drawn by the program
    # (initializer_range None), and the zeros the constructors leave are
    # made in the host's memory, not in 2.3 GB of the chip's a layer
    mcfg = model_config(cfg, initializer_range=None, **config_over)
    depth, mcfg.num_hidden_layers = mcfg.num_hidden_layers, 0
    host = jax.devices("cpu")[0]
    with jax.default_device(host):
        model = DeepSeekV2ForCausalLM(mcfg)
    for g in ("embed", "final"):
        assign(model, program_leaves(cfg, g, weights_of(g)))
    for i in range(depth):
        with jax.default_device(host):
            layer = DeepSeekV2DecoderLayer(mcfg, i)
        assign(layer, program_leaves(cfg, f"layer.{i}",
                                     weights_of(f"layer.{i}")))
        model.model.layers.append(layer)
    mcfg.num_hidden_layers = depth
    return model
