"""Operations and bytes the algorithm needs, from shapes and from the
window's step or request records — never from dispatch shapes, so that a
kernel's roofline reads the same work whatever implements it.

A multiply-add is two operations.  Recomputed work is not counted.
"""

from __future__ import annotations


def layer_matmul_params(cfg):
    h, f, d = cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    return h * q + 2 * h * kv + q * h + 3 * h * f


def head_params(cfg):
    return cfg["hidden_size"] * cfg["vocab_size"]


def weight_bytes(cfg, bytes_per=2):
    """Bytes of weights one decode step streams: every layer and the LM
    head; of the embedding only the rows looked up (not counted)."""
    return bytes_per * (cfg["num_hidden_layers"] * layer_matmul_params(cfg)
                        + head_params(cfg))


def attn_flops_token(cfg, context):
    """One layer's attention for one query token that sees `context` keys:
    q.K^T and p.V, each 2 * context * head_dim a head."""
    return 4 * cfg["num_attention_heads"] * cfg["head_dim"] * context


def attn_flops_causal(cfg, length, cached=0):
    """One layer's causal attention over positions cached..length-1."""
    n = length - cached
    keys = n * cached + n * (n + 1) // 2
    return 4 * cfg["num_attention_heads"] * cfg["head_dim"] * keys


def kv_bytes_token(cfg, bytes_per=2):
    """Keys and values of one token in one layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * bytes_per


# ---------------------------------------------------------------- training

def train_step_flops(cfg, batch, seq):
    """Forward + backward of one step: 3 x forward (recompute not counted)."""
    tokens = batch * seq
    matmul = 2 * tokens * (cfg["num_hidden_layers"] * layer_matmul_params(cfg)
                           + head_params(cfg))
    attn = cfg["num_hidden_layers"] * batch * attn_flops_causal(cfg, seq)
    return 3 * (matmul + attn)


def flash_train_work(cfg, batch, seq, bytes_per=2):
    """(operations, bytes) of one step's causal attention: forward q.K^T and
    p.V; backward dV, dP, dQ, dK (the recomputed q.K^T is not counted).
    Bytes: forward reads q, k, v and writes o; backward reads q, k, v, o, do
    and writes dq, dk, dv."""
    layers = cfg["num_hidden_layers"]
    flops = 3 * layers * batch * attn_flops_causal(cfg, seq)
    d = cfg["head_dim"]
    q = batch * seq * cfg["num_attention_heads"] * d * bytes_per
    kv = batch * seq * cfg["num_key_value_heads"] * d * bytes_per
    fwd = 2 * q + 2 * kv
    bwd = 4 * q + 4 * kv
    return flops, layers * (fwd + bwd)


# ----------------------------------------------------------------- serving

def request_flops(cfg, prompt, cached, generated):
    """Model operations for one request: the prompt's uncached tokens and
    each generated token but the last go through every layer; the LM head
    runs once a generated token."""
    layers = cfg["num_hidden_layers"]
    processed = (prompt - cached) + max(generated - 1, 0)
    total = prompt + max(generated - 1, 0)
    flops = 2 * processed * layers * layer_matmul_params(cfg)
    flops += 2 * generated * head_params(cfg)
    flops += layers * attn_flops_causal(cfg, total, cached)
    return flops


def request_attn_work(cfg, prompt, cached, generated, bytes_per=2):
    """(operations, bytes) of one request's attention over all layers.  The
    prefill reads its keys and values once; every decode token reads its
    whole context again."""
    layers = cfg["num_hidden_layers"]
    total = prompt + max(generated - 1, 0)
    flops = layers * attn_flops_causal(cfg, total, cached)
    kv = kv_bytes_token(cfg, bytes_per)
    qo = 2 * cfg["num_attention_heads"] * cfg["head_dim"] * bytes_per
    nbytes = prompt * kv + (prompt - cached) * qo
    for t in range(prompt, total):
        nbytes += (t + 1) * kv + qo
    return flops, layers * nbytes


def decode_kv_bytes(cfg, prompt, generated, bytes_per=2):
    """Bytes of keys and values this request's decode steps read."""
    kv = kv_bytes_token(cfg, bytes_per) * cfg["num_hidden_layers"]
    total = prompt + max(generated - 1, 0)
    return kv * sum(t + 1 for t in range(prompt, total))


def least_seconds(flops, nbytes, peaks):
    """(seconds, bound) the chip needs at the least."""
    tc, tm = flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
