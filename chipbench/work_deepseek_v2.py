"""Operations and bytes the DeepSeek-V2 family needs, beside chipbench/work.py
(the dense decoder's) and with its rules: from shapes and from the window's
request records and the program's routing counters, never from dispatch
shapes; a multiply-add is two operations; attention is counted in the
EXPANDED form (2 x 192 for a score and 2 x 128 for a value, a head a
query-key pair) whatever the kernel computes — the absorbed form the program
runs does 2,176 and is not credited for the difference.
"""

from __future__ import annotations

from chipbench.work import least_seconds  # noqa: F401  (readers use it)


def attn_params(cfg):
    """W_q, W_kv_a, W_kv_b, W_o of one layer."""
    h, nh, r = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    return (h * nh * (dn + dr) + h * (r + dr) + r * nh * (dn + dv)
            + nh * dv * h)


def expert_params(cfg):
    """One routed expert: gate, up, down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def moe_layers(cfg):
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def dense_layer_active(cfg):
    return attn_params(cfg) + 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def moe_layer_outside_experts(cfg):
    """Attention, the shared experts and the router of an expert layer."""
    return (attn_params(cfg) + cfg["n_shared_experts"] * expert_params(cfg)
            + cfg["hidden_size"] * cfg["n_routed_experts"])


def moe_layer_active(cfg):
    """Matmul parameters one token meets in an expert layer."""
    return (moe_layer_outside_experts(cfg)
            + cfg["num_experts_per_tok"] * expert_params(cfg))


def head_params(cfg):
    return cfg["hidden_size"] * cfg["vocab_size"]


def active_params_token(cfg):
    """Matmul parameters a token meets in all layers (the head apart)."""
    return (cfg["first_k_dense_replace"] * dense_layer_active(cfg)
            + moe_layers(cfg) * moe_layer_active(cfg))


def attn_flops_pair(cfg):
    """One layer, one query against one key, all heads, expanded form."""
    return cfg["num_attention_heads"] * (
        2 * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"])
        + 2 * cfg["v_head_dim"])


def causal_pairs(length, cached=0):
    n = length - cached
    return n * cached + n * (n + 1) // 2


def latent_bytes_token(cfg, bytes_per=2):
    """What one token keeps in one layer: latent ‖ rotated shared key."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * bytes_per


def request_flops(cfg, prompt, cached, generated):
    """Model operations for one request (work.request_flops's rule)."""
    layers = cfg["num_hidden_layers"]
    processed = (prompt - cached) + max(generated - 1, 0)
    total = prompt + max(generated - 1, 0)
    flops = 2 * processed * active_params_token(cfg)
    flops += 2 * generated * head_params(cfg)
    flops += layers * attn_flops_pair(cfg) * causal_pairs(total, cached)
    return flops


def request_attn_work(cfg, prompt, cached, generated, bytes_per=2):
    """(operations, bytes) of one request's attention over all layers: the
    prefill reads its latents once, every decode token its whole context
    again; queries in (16 x 192) and outputs out (16 x 128) a token."""
    layers = cfg["num_hidden_layers"]
    total = prompt + max(generated - 1, 0)
    flops = layers * attn_flops_pair(cfg) * causal_pairs(total, cached)
    lat = latent_bytes_token(cfg, bytes_per)
    nh = cfg["num_attention_heads"]
    qo = nh * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
               + cfg["v_head_dim"]) * bytes_per
    nbytes = prompt * lat + (prompt - cached) * qo
    for t in range(prompt, total):
        nbytes += (t + 1) * lat + qo
    return flops, layers * nbytes


def decode_latent_bytes(cfg, prompt, generated, bytes_per=2):
    """Latent bytes this request's decode steps read."""
    lat = latent_bytes_token(cfg, bytes_per) * cfg["num_hidden_layers"]
    total = prompt + max(generated - 1, 0)
    return lat * sum(t + 1 for t in range(prompt, total))


def step_weight_bytes(cfg, bytes_per=2):
    """Weights every decode step streams whatever the routing: all layers'
    attention, the dense FFN, shared experts and routers, and the head."""
    return bytes_per * (
        cfg["first_k_dense_replace"] * dense_layer_active(cfg)
        + moe_layers(cfg) * moe_layer_outside_experts(cfg)
        + head_params(cfg))


def experts_work(cfg, rows, touched, bytes_per=2):
    """(operations, bytes) of the routed experts for `rows` (token, expert)
    pairs over all expert layers and steps, `touched` distinct experts
    summed over layers and steps: each touched expert's matrices once, each
    row in (hidden) and out (hidden)."""
    flops = 2 * rows * expert_params(cfg)
    nbytes = bytes_per * (touched * expert_params(cfg)
                          + rows * 2 * cfg["hidden_size"])
    return flops, nbytes


def window_part(q, upto):
    """The part of request record `q` between the window's "go" (time 0)
    and `upto` seconds, as (prompt, cached, generated, decoding) for the
    counts above.  A request whose first token came inside the window
    counts whole: its prompt, then a decode step a further token.  One that
    was streaming before "go" (the driver's lead-in sent it: `b` tokens at
    times <= 0) only decodes inside the window: each of its `g` tokens is
    one token processed against the prompt + b - 1 + j cached before it,
    which is what the counts give for a prompt of `prompt_len + b` of which
    all but the last token are cached."""
    before = sum(1 for t in q["token_s"] if t <= 0.0)
    done = sum(1 for t in q["token_s"] if 0.0 < t <= upto)
    if not before:
        return q["prompt_len"], q.get("cached", 0), done, False
    prompt = q["prompt_len"] + before
    return prompt, prompt - 1, done, True


def window_latent_bytes(cfg, q, upto):
    """Latent bytes the decode steps of `q` inside the window read."""
    prompt, _, done, decoding = window_part(q, upto)
    if decoding:                     # `done` steps, the first over `prompt`
        return decode_latent_bytes(cfg, prompt - 1, done + 1)
    return decode_latent_bytes(cfg, prompt, done)
