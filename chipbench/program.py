"""The only file of the benchmark that touches the system under test.

It builds the program's own model class from the benchmark's seeded weights
and names the entry points the drivers time.  The model is built through its
public constructors one decoder layer at a time: `Layer` initialises every
parameter in float32 on the device, which for the 16-layer serving
configuration would be 15 GB at once.
"""

from __future__ import annotations

from chipbench import weights as W

#: benchmark leaf -> parameter path inside one GPTDecoderLayer
LAYER_PATHS = {
    "norm_attn": "input_layernorm.weight", "wq": "self_attn.q_proj.weight",
    "wk": "self_attn.k_proj.weight", "wv": "self_attn.v_proj.weight",
    "wo": "self_attn.o_proj.weight",
    "norm_mlp": "post_attention_layernorm.weight",
    "w_gate": "mlp.gate_proj.weight", "w_up": "mlp.up_proj.weight",
    "w_down": "mlp.down_proj.weight"}


def param_name(group, leaf):
    """The program's state_dict name of a benchmark leaf."""
    if group == "embed":
        return "model.embed_tokens.weight"
    if group == "final":
        return "model.norm.weight" if leaf == "norm_f" else "lm_head.weight"
    return f"model.layers.{group.split('.')[1]}.{LAYER_PATHS[leaf]}"


def leaf_of(name, cfg):
    """(group, leaf) of a state_dict name."""
    for g in W.groups(cfg):
        for leaf in W.group_shapes(cfg, g):
            if param_name(g, leaf) == name:
                return g, leaf
    raise KeyError(name)


def gpt_config(cfg, **over):
    from paddle_tpu.models.gpt import GPTConfig

    return GPTConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        tie_word_embeddings=cfg["tie_word_embeddings"], **over)


def _assign(layer, values, paths):
    params = dict(layer.named_parameters())
    for leaf, path in paths.items():
        p = params[path]
        if tuple(p._data.shape) != tuple(values[leaf].shape):
            raise ValueError(f"{path}: program {p._data.shape}, benchmark "
                             f"{values[leaf].shape}")
        p._data = values[leaf]


def build_model(cfg, weights, **config_over):
    """`GPTForCausalLM` at `cfg`, holding the benchmark's `weights`
    ({group: {leaf: array}}) in their own dtype."""
    from paddle_tpu.models.gpt import GPTDecoderLayer, GPTForCausalLM

    if cfg["head_dim"] * cfg["num_attention_heads"] != cfg["hidden_size"]:
        raise ValueError("models/gpt.py derives head_dim from hidden_size")
    gcfg = gpt_config(cfg, **config_over)
    depth, gcfg.num_hidden_layers = gcfg.num_hidden_layers, 0
    model = GPTForCausalLM(gcfg)
    _assign(model, {**weights["embed"], **weights["final"]},
            {"embed": "model.embed_tokens.weight",
             "norm_f": "model.norm.weight", "lm_head": "lm_head.weight"})
    for i in range(depth):
        layer = GPTDecoderLayer(gcfg)
        _assign(layer, weights[f"layer.{i}"], LAYER_PATHS)
        model.model.layers.append(layer)
    gcfg.num_hidden_layers = depth
    return model
