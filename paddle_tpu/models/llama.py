"""LLaMA model family (BASELINE.json: LLaMA-2-13B stage-3+recompute config).

The decoder recipe (pre-norm RMSNorm, RoPE, SwiGLU, optional GQA) is shared
with the flagship implementation in models/gpt.py; this module gives it the
LLaMA naming plus the standard config presets so users of the reference's
ecosystem (PaddleNLP `LlamaForCausalLM`) find the same surface here.

Because the attention layer is shared, LlamaAttention accepts the serving
subsystem's cache view (the paged pool's `PagedKV`: block tables and
per-lane positions) anywhere the legacy `(k, v)` concat
cache is accepted — a LlamaForCausalLM drops straight into
paddle_tpu.serving.Engine:

    from paddle_tpu.serving import Engine, EngineConfig
    engine = Engine(LlamaForCausalLM(LLAMA2_7B), EngineConfig(...))
"""

from .gpt import (
    LLAMA2_13B,
    GPTConfig as LlamaConfig,
    GPTAttention as LlamaAttention,
    GPTMLP as LlamaMLP,
    GPTDecoderLayer as LlamaDecoderLayer,
    GPTModel as LlamaModel,
    GPTForCausalLM as LlamaForCausalLM,
)

LLAMA2_7B = LlamaConfig(
    vocab_size=32000, hidden_size=4096, intermediate_size=11008,
    num_hidden_layers=32, num_attention_heads=32,
    max_position_embeddings=4096,
)
# LLaMA-3-style GQA preset (8 kv heads) — exercises the grouped-query path
LLAMA3_8B = LlamaConfig(
    vocab_size=128256, hidden_size=4096, intermediate_size=14336,
    num_hidden_layers=32, num_attention_heads=32, num_key_value_heads=8,
    max_position_embeddings=8192, rope_theta=500000.0,
)

__all__ = [
    "LlamaConfig", "LlamaAttention", "LlamaMLP", "LlamaDecoderLayer",
    "LlamaModel", "LlamaForCausalLM",
    "LLAMA2_7B", "LLAMA2_13B", "LLAMA3_8B",
]
