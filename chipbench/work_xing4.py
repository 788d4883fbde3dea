"""Operations and bytes the Xing4.0 family needs, with work_deepseek_v2.py's
rules (from shapes and from the window's records; a multiply-add is two
operations; attention in the EXPANDED form whatever the kernel computes).
The counts of attention, of the routed experts, of the latents and of a
request's part of the window hold unchanged and are imported from there;
this family adds query compression to the attention's parameters, and mHC:
phi's product per sublayer (a matrix every token meets and every decode
step streams), the read-in, the mixing and the write-back (operations a
token), and the streams a decode token reads and writes at each sublayer.
"""

from __future__ import annotations

from chipbench.work import least_seconds  # noqa: F401  (readers use it)
from chipbench.work_deepseek_v2 import (  # noqa: F401  (readers use them)
    attn_flops_pair, causal_pairs, decode_latent_bytes, expert_params,
    experts_work, head_params, latent_bytes_token, moe_layers,
    request_attn_work, window_latent_bytes, window_part)


def attn_params(cfg):
    """W_qa, W_qb, W_kv_a, W_kv_b, W_o of one layer."""
    h, nh, r, ql = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["kv_lora_rank"], cfg["q_lora_rank"])
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    return (h * ql + ql * nh * (dn + dr) + h * (r + dr) + r * nh * (dn + dv)
            + nh * dv * h)


def hc_params(cfg):
    """phi of one sublayer, [n*C, 2n + n*n] (alpha and bias are 27 scalars
    that no product meets)."""
    n = cfg["hc_mult"]
    return n * cfg["hidden_size"] * (2 * n + n * n)


def hc_mix_flops(cfg):
    """A token's operations of one sublayer outside phi's product: the
    read-in H_pre X (n x C multiply-adds), the mixing H_res X (n x n x C)
    and the write-back H_post^T y (n x C)."""
    n, c = cfg["hc_mult"], cfg["hidden_size"]
    return 2 * (n * c + n * n * c + n * c)


def hc_stream_bytes_token(cfg, bytes_per=2):
    """The streams a token reads and writes round all sublayers: X in and
    X out, n x C values each, twice a layer."""
    n, c = cfg["hc_mult"], cfg["hidden_size"]
    return 2 * cfg["num_hidden_layers"] * 2 * n * c * bytes_per


def dense_layer_active(cfg):
    return (attn_params(cfg) + 2 * hc_params(cfg)
            + 3 * cfg["hidden_size"] * cfg["intermediate_size"])


def moe_layer_outside_experts(cfg):
    """Attention, both sublayers' phi, the shared experts and the router of
    an expert layer."""
    return (attn_params(cfg) + 2 * hc_params(cfg)
            + cfg["n_shared_experts"] * expert_params(cfg)
            + cfg["hidden_size"] * cfg["n_routed_experts"])


def moe_layer_active(cfg):
    """Matmul parameters one token meets in an expert layer."""
    return (moe_layer_outside_experts(cfg)
            + cfg["num_experts_per_tok"] * expert_params(cfg))


def active_params_token(cfg):
    """Matmul parameters a token meets in all layers (the head apart)."""
    return (cfg["first_k_dense_replace"] * dense_layer_active(cfg)
            + moe_layers(cfg) * moe_layer_active(cfg))


def token_flops(cfg):
    """A processed token's operations outside attention and the head."""
    return (2 * active_params_token(cfg)
            + 2 * cfg["num_hidden_layers"] * hc_mix_flops(cfg))


def request_flops(cfg, prompt, cached, generated):
    """Model operations for one request (work.request_flops's rule)."""
    layers = cfg["num_hidden_layers"]
    processed = (prompt - cached) + max(generated - 1, 0)
    total = prompt + max(generated - 1, 0)
    return (processed * token_flops(cfg) + 2 * generated * head_params(cfg)
            + layers * attn_flops_pair(cfg) * causal_pairs(total, cached))


def step_weight_bytes(cfg, bytes_per=2):
    """Weights every decode step streams whatever the routing: all layers'
    attention and phi, the dense FFN, shared experts and routers, and the
    head."""
    return bytes_per * (
        cfg["first_k_dense_replace"] * dense_layer_active(cfg)
        + moe_layers(cfg) * moe_layer_outside_experts(cfg)
        + head_params(cfg))


def window_decode_tokens(q, upto):
    """Tokens of request record `q` that decode steps made between the
    window's "go" and `upto` (a fresh request's first token is its
    prefill's)."""
    _, _, done, decoding = window_part(q, upto)
    return done if decoding else max(done - 1, 0)
