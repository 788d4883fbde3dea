"""paddle_tpu.serving — continuous-batching LLM inference engine.

The serving subsystem the reference ships as AnalysisPredictor + the
fused CUDA decode ops (fused_multi_transformer), rebuilt TPU-native
around these ideas:

* a **unified paged KV pool** (kv_cache.py) — all KV in ONE per-layer
  ``[num_blocks, block_size, kv_heads, head_dim]`` pool (vLLM-style
  fixed blocks) addressed through per-slot block tables; table entries
  are allocated lazily, so HBM scales with live tokens, and every
  block is host-refcounted (table entries and the prefix store each
  hold a reference);
* **ragged paged-attention decode** (paged_attention.py) — decode
  attention reads ONLY each lane's table-mapped blocks (Pallas kernel
  on TPU, an XLA online-softmax fallback on CPU whose exact-zero
  masking makes outputs bitwise-invariant to the static table width),
  so per-step KV bandwidth tracks live sequence length, not
  ``max_seq_len``;
* a **prefill/decode split** with power-of-two prefill buckets — one
  compiled prefill per (lane-bucket, length-bucket) pair (engine.py);
* **batched fused prefill** — admission groups same-bucket queued
  requests (``Scheduler.pop_batch``, bounded reorder window so FIFO
  order is never violated by more than ``reorder_window`` overtakes)
  and prefills the whole group in ONE compiled dispatch;
* a **copy-free prefix KV cache** (prefix_cache.py) — a block-granular
  radix store over prompt token ids (RadixAttention-style reuse over
  vLLM-style fixed blocks) holding refcounted blocks of the unified
  pool: a hit leases cached blocks straight into the slot's block
  table (zero copies; a partial tail match is copy-on-write), caching
  new content is ``adopt()`` refcounting, and unpinned blocks are
  LRU-evicted under ``prefix_cache_bytes``;
* **continuous batching + preemption** — FIFO admission into a fixed
  slot pool, requests join at horizon boundaries and release their
  blocks on EOS or max-tokens (scheduler.py), with greedy/temperature/
  top-k/top-p sampling under per-request seeded PRNG (sampling.py);
  under block pressure the engine preempts the youngest lane
  (``Engine.preempt``: blocks released, request requeued at the front,
  re-admission reproduces its stream bitwise);
* **horizon-scanned fused decode** — ``Engine.step(horizon=H)`` runs H
  decode steps as one compiled ``lax.scan`` over device-resident engine
  state with the pool as donated carry: one dispatch and one host sync
  per horizon instead of per token, with per-slot EOS/max-token masking
  inside the scan.  An adaptive policy shrinks the horizon to 1 while
  requests are queued and grows it toward ``EngineConfig.max_horizon``
  when the slot mix is stable.  ``fold_in(seed, n_generated)`` PRNG
  keeps every horizon bitwise-equal to per-step decode;
* **self-drafting speculative decode** (drafter.py + engine.py) — with
  ``EngineConfig.spec_k = K > 0`` each fused step verifies a
  ``K+1``-token window per lane: a traced prompt-lookup drafter
  proposes K tokens from the lane's own history, one forward scores
  all K+1 positions through the same ragged paged-attention path, and
  the lane emits the longest matching draft prefix plus the model's
  own next token — 1..K+1 tokens per forward, greedy and seeded
  output bitwise-equal to ``spec_k=0``.  ``spec_adaptive`` gates
  low-acceptance lanes off and shrinks the dispatch back to plain
  decode when nobody's drafts are landing;
* **tensor-parallel sharded serving** (sharded/) — ``MeshEngine`` runs
  the whole engine over a ``("dp", "tp")`` device mesh: every Linear
  column-parallel (output-sharded), the paged KV pool sharded over
  kv_heads so each chip's block pool holds its head slice, per-layer
  attention combined through ONE disjoint-support psum, everything
  else through tiled all_gathers — greedy AND seeded output
  bitwise-equal to the single-chip engine under continuous batching,
  prefix hits, preemption and speculative decoding
  (:class:`~.sharded.ServingSpecLayout` holds the placement rules);
* an **HTTP/SSE front door** (gateway/) — an OpenAI-style
  ``/v1/completions`` endpoint with per-horizon SSE streaming, priority
  + deadline + per-tenant-quota admission (429/503 + Retry-After load
  shedding), and a prefix-affinity router over N in-process engine
  replicas (rendezvous-hashed radix-cache-block keys; SLO-unhealthy
  replicas stop receiving sessions).  Import from
  ``paddle_tpu.serving.gateway``;
* **structured generation** (structured/ + engine.py + drafter.py) —
  grammar-constrained decoding: a regex or JSON-schema request grammar
  compiles to a token-level DFA over the vocab (regex → NFA →
  minimized char DFA → vocab crossproduct, dense transitions + packed
  legality bitmask), per-lane DFA states ride the donated decode-scan
  carry like ``pos``/``counts``, and disallowed logits drop to a
  finite floor inside ``sample_window`` BEFORE the greedy fast path /
  categorical — constrained output is always grammar-valid, bitwise
  batched-vs-sequential under the same ``fold_in`` PRNG, and free
  lanes ride an accept-all sentinel state at zero cost.  States whose
  sole legal token is forced (JSON skeleton punctuation) feed the
  drafter ahead of its n-gram guesses (``forced_chain``), turning
  grammar structure into ~free speculative accepts.  With
  ``grammar_max_states=0`` every grammar argument threads ``None`` and
  the compiled programs are the unconstrained ones;
* **fault tolerance** (faults.py + gateway/router.py) — deterministic
  seeded fault injection (:class:`FaultPlan`/:class:`FaultInjector`:
  schedules keyed by dispatch ordinals, never wall clocks), a
  per-worker heartbeat watchdog, capped-exponential retry/backoff with
  deterministic jitter (:class:`RetryPolicy`), a graceful-degradation
  ladder (spec off → horizon 1 → shed) with hysteresis, and mid-stream
  replica **failover**: a dead replica's in-flight requests re-dispatch
  to survivors carrying prompt + tokens-already-streamed, resumed via
  re-prefill under the same ``fold_in(seed, n_generated)`` discipline —
  the continued stream is bitwise-identical to an uninterrupted run.

Quick start::

    from paddle_tpu.models import GPTForCausalLM, GPTConfig
    from paddle_tpu.serving import Engine, EngineConfig, SamplingParams

    engine = Engine(GPTForCausalLM(cfg),
                    EngineConfig(num_slots=8, max_seq_len=512,
                                 max_horizon=8))
    req = engine.submit(prompt_ids, SamplingParams(max_new_tokens=64))
    while engine.scheduler.has_work:
        engine.step()          # other submits land at horizon boundaries
    print(req.output_ids)

Counters (queue depth, TTFT, tokens/s, slot utilization, compile-cache
hits) are exposed through ``paddle_tpu.profiler.counters()``.
"""

from .drafter import draft_tokens, forced_chain
from .engine import CompiledFn, Engine, EngineConfig
from .faults import (FaultInjector, FaultPlan, FaultSpec, RetryPolicy,
                     TransientSubmitError, WorkerCrash, WorkerDeadError)
from .gateway import (EngineWorker, FleetSupervisor, Gateway,
                      GatewayConfig, PrefixAffinityRouter, TenantQuotas)
from .kv_cache import PagedKV, PagedKVCache, PagedKVPool
from .kv_host_tier import HostKVTier
from .paged_attention import paged_attention
from .prefix_cache import PrefixCache, PrefixLease
from .sampling import SamplingParams
from .scheduler import Request, Scheduler
from .sharded import MeshEngine, ServingSpecLayout
from .structured import (GrammarError, GrammarSlab, GrammarSpec,
                         TokenDFA, compile_grammar, compile_regex,
                         schema_to_regex)

__all__ = [
    "Engine", "EngineConfig", "CompiledFn",
    "PagedKV", "PagedKVCache", "PagedKVPool", "paged_attention",
    "HostKVTier",
    "PrefixCache", "PrefixLease",
    "SamplingParams", "Request", "Scheduler",
    "draft_tokens", "forced_chain",
    "GrammarError", "GrammarSlab", "GrammarSpec", "TokenDFA",
    "compile_grammar", "compile_regex", "schema_to_regex",
    "Gateway", "GatewayConfig", "EngineWorker", "PrefixAffinityRouter",
    "TenantQuotas", "FleetSupervisor",
    "FaultPlan", "FaultSpec", "FaultInjector", "RetryPolicy",
    "WorkerCrash", "TransientSubmitError", "WorkerDeadError",
    "MeshEngine", "ServingSpecLayout",
]
