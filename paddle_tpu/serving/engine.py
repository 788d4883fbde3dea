"""The continuous-batching LLM inference engine.

Architecture (prefill/decode split over ONE paged KV block pool):

* **Unified paged KV pool** — all KV lives in a single per-layer
  ``[num_blocks, block_size, kv_heads, head_dim]`` pool
  (``kv_cache.PagedKVCache``): each slot addresses it through a
  host-authoritative ``[num_slots, max_blocks_per_slot]`` block table
  whose live prefix is uploaded before a dispatch only when dirty.
  Blocks are refcounted — a table entry and a prefix-store node each
  hold one reference — so prefix sharing is copy-free and preemption is
  bookkeeping.  Table entries are allocated lazily (admission covers
  the prompt, ``_ensure_blocks`` extends coverage per horizon), so HBM
  scales with LIVE tokens, not ``num_slots * max_seq_len``.
* **Batched fused prefill** — admission groups queued requests that
  share a prefill bucket (``Scheduler.pop_batch``, bounded reorder
  window) and prefills the whole group in ONE ``[lanes, bucket]``
  compiled dispatch: each lane scatters its suffix k/v through its
  block-table row and samples its first token.  Suffixes are
  right-padded to power-of-two length buckets and the lane count is
  bucketed the same way — one compiled prefill program per
  (lane-bucket, length-bucket) pair.  Padding lanes carry an all-zero
  table row, so their writes land in the reserved scratch block 0 and
  no validity masking or spare-slot machinery is needed.
* **Copy-free prefix reuse** — the radix store (``prefix_cache.py``,
  unified-pool mode) holds refcounted blocks of the SAME pool.  A hit
  leases the matched blocks straight into the slot's table
  (``lease_block``: one ``pool.share`` per entry, zero copies); a
  partial tail match is served copy-on-write — the prefill program
  copies that ONE block into the slot's private tail block, then
  overwrites from the divergence offset on.  After prefill, ``adopt()``
  takes shared references on the slot's freshly written private blocks
  — caching new content is host-side refcounting, no gather/scatter
  dispatches at all.
* **Horizon-scanned ragged decode** — ONE compiled program advances ALL
  slots by ``H`` fused steps: a ``lax.scan`` carrying the donated pool,
  whose body embeds the last token of every slot, scatter-writes k/v
  through the (loop-invariant) block tables, runs paged attention over
  ONLY the ``nb`` table-mapped blocks per lane
  (``paged_attention.py``: Pallas kernel on TPU, the nb-invariant XLA
  online-softmax fallback on CPU), samples per-request tokens under
  ``fold_in(seed, n_generated)`` PRNG, and masks retired lanes (EOS /
  max-tokens detected INSIDE the scan: their ``pos``/``counts`` freeze
  and their sampled tokens harvest as ``-1``).  ``nb`` is bucketed to a
  power of two of the deepest live row, so per-step KV traffic tracks
  live sequence length instead of ``max_seq_len`` and the program
  compiles once per ``(horizon, nb)`` bucket (``stats()``:
  ``decode_buckets``); the fallback's exact-zero masking makes outputs
  bitwise-invariant to ``nb``, so re-bucketing as sequences grow never
  perturbs a token.
* **Device-resident engine state** — the per-slot decode state
  (``tokens/pos/counts/active`` plus the loop-invariant
  ``seeds/temps/top_ks/top_ps/eos_ids/limits``) lives on device and is
  updated inside the compiled program; the host re-uploads it only when
  admission changes it (dirty flag), never per step.  Host mirrors are
  maintained from the harvested tokens alone — no extra device reads.
* **Self-drafting speculative decode** — with ``spec_k > 0`` every
  fused step verifies a ``K+1``-token window per lane instead of one
  token: a traced prompt-lookup drafter (``drafter.py``) proposes K
  continuation tokens from the lane's own device-resident token history,
  the model scores all K+1 positions in ONE forward (the verify step is
  a short ragged prefill through the same paged-attention kernel), and
  the lane emits the longest draft prefix whose sampled tokens match,
  plus the model's own next token — 1..K+1 tokens per forward.  Token k
  of a request is ALWAYS sampled from position k's logits under
  ``fold_in(seed, k)``, so greedy and seeded-sampled outputs stay
  bitwise-equal to sequential ``generate()`` for every K.  Rejected
  draft positions write garbage KV at ``pos+n..pos+K`` — but the next
  step's window writes at ``pos' = pos+n`` BEFORE any read reaches
  those positions (write-before-attend), so the garbage is dead on
  arrival.  ``K`` is a static compile bucket like ``horizon``
  (``decode_buckets`` becomes ``(horizon, nb, K)`` triples) and an
  adaptive policy shrinks the dispatch to K=0 (plain decode) when no
  running lane's recent acceptance EMA clears ``spec_accept_floor``.
* **Continuous batching + preemption** — requests join at horizon
  boundaries and release their blocks on EOS/max-tokens; an adaptive
  policy shrinks the horizon toward 1 when the queue is non-empty or a
  lane is near its token budget, and grows it toward ``max_horizon``
  while the batch is stable.  Under block pressure the engine first
  reclaims unpinned prefix blocks, then **preempts** the youngest
  running request (``preempt()``: release blocks + requeue at the
  front; re-admission re-prefills prompt + generated-so-far and the
  fold_in PRNG reproduces its next token bitwise, so swapping an idle
  sequence out and back is invisible in its output).

Every horizon partition of a request's token stream is bitwise-equal:
the scan body is the same jaxpr as a standalone single step, and a
request's k-th token depends only on (its seed, k, its logits).

The engine reuses the model's own Layer code (functionalized through
``use_state``, the TrainStep pattern), so paged decode is numerically
the decode path models/gpt.py already ships — just with a cache the
compiler can keep static.
"""

from __future__ import annotations

import time
import weakref
from dataclasses import dataclass

import numpy as np
import jax
import jax.numpy as jnp

from ..core import tape as _tape
from ..core.tensor import Tensor
from ..observability import events as _obs_events
from ..observability import memory as _obs_memory
from ..observability import metrics as _obs_metrics
from ..observability import peaks as _obs_peaks
from ..observability import profiling as _obs_profiling
from ..observability import tracing as _obs_tracing
from ..observability.span import build as _build, span as _span
from .drafter import draft_tokens, forced_chain
from .faults import (DEGRADE_LEVELS, FAULT_POOL_EXHAUSTED,
                     SITE_ENGINE_ADMIT, _SRV_DEGRADATION, _SRV_SHED)
from .kv_cache import PagedKV, PagedKVCache, model_cache_layout
from .kv_host_tier import HostKVTier
from .prefix_cache import PrefixCache
from .sampling import (MASK_FLOOR, SamplingParams, sample_batch,
                       sample_window)
from .scheduler import Scheduler
from .structured.grammar import (GrammarSlab, as_grammar_spec,
                                 compile_grammar)

# typed registry families the engine publishes into (labeled by engine
# instance so two engines in one process stay distinguishable); the
# legacy flat counters() dict stays as the profiler-facade back-compat
# surface
_SRV_TOKENS = _obs_metrics.counter(
    "serving.tokens_generated", "tokens sampled across prefill+decode")
_SRV_REQS = _obs_metrics.counter(
    "serving.requests_finished", "requests retired (EOS or max-tokens)")
_SRV_DECODE_STEPS = _obs_metrics.counter(
    "serving.decode_steps", "fused decode steps executed")
_SRV_PREFILL = _obs_metrics.counter(
    "serving.prefill_calls", "batched prefill dispatches")
_SRV_PREFILL_REQS = _obs_metrics.counter(
    "serving.prefill_requests", "requests prefilled (across batches)")
_SRV_PREFIX_HIT = _obs_metrics.counter(
    "serving.prefix_hit_tokens",
    "prompt tokens served from the prefix KV cache instead of recomputed")
_SRV_PREFIX_RATIO = _obs_metrics.gauge(
    "serving.prefix_hit_ratio",
    "cumulative prefix-cache hit tokens / admitted prompt tokens")
_SRV_PREFILL_BATCH = _obs_metrics.histogram(
    "serving.prefill_batch_size", "requests co-prefilled per dispatch",
    buckets=(1, 2, 4, 8, 16, 32))
_SRV_WASTED = _obs_metrics.counter(
    "serving.wasted_lane_tokens",
    "masked tokens scanned for lanes that retired mid-horizon")
_SRV_QUEUE = _obs_metrics.gauge(
    "serving.queue_depth", "requests waiting for a slot")
_SRV_ACTIVE = _obs_metrics.gauge(
    "serving.active_slots", "slots currently decoding")
_SRV_UTIL = _obs_metrics.gauge(
    "serving.slot_utilization", "mean active/total slots over decode steps")
_SRV_TPS = _obs_metrics.gauge(
    "serving.tokens_per_s", "generated tokens per engine-busy second")
_SRV_TTFT = _obs_metrics.histogram(
    "serving.ttft_seconds", "submit-to-first-token wall seconds")
_SRV_STEP = _obs_metrics.histogram(
    "serving.step_seconds", "wall seconds per engine step()")
_SRV_HORIZON = _obs_metrics.histogram(
    "serving.horizon", "fused decode steps per compiled horizon dispatch",
    buckets=(1, 2, 4, 8, 16, 32, 64, 128))
_SRV_KV_BLOCKS = _obs_metrics.gauge(
    "serving.kv_blocks_in_use",
    "unified-pool KV blocks currently referenced (tables + prefix store)")
_SRV_KV_BYTES = _obs_metrics.counter(
    "serving.kv_bytes_read",
    "KV bytes gathered by decode attention (table-mapped blocks only)")
_SRV_PREEMPTIONS = _obs_metrics.counter(
    "serving.preemptions",
    "running requests swapped out under KV block pressure")
_SRV_SWAP_OUT_BYTES = _obs_metrics.counter(
    "serving.kv_swap_out_bytes",
    "KV bytes moved device->host by the tiered cache (kind=\"lane\" "
    "preempted-lane chains, kind=\"demote\" evicted prefix blocks)")
_SRV_SWAP_IN_BYTES = _obs_metrics.counter(
    "serving.kv_swap_in_bytes",
    "KV bytes uploaded host->device by tiered-cache swap-ins instead "
    "of being recomputed")
_SRV_SWAP_AVERTED = _obs_metrics.counter(
    "serving.kv_swaps_averted_flops",
    "estimated prefill FLOPs swap-ins avoided (averted tokens x the "
    "program-card per-token prefill cost)")
_SRV_HOST_OCC = _obs_metrics.gauge(
    "serving.host_arena_occupancy_ratio",
    "host spill-arena blocks in use / arena capacity")
_SRV_SPEC_ACCEPT = _obs_metrics.histogram(
    "serving.spec_accept_len",
    "tokens emitted per speculative verify window (accepted prefix + 1)",
    buckets=(1, 2, 3, 4, 5, 6, 8, 12, 17))
_SRV_SPEC_DRAFTED = _obs_metrics.counter(
    "serving.spec_draft_tokens",
    "draft tokens proposed to the verify forward")
_SRV_SPEC_ACCEPTED = _obs_metrics.counter(
    "serving.spec_accepted_tokens",
    "draft tokens whose sampled verification matched")
_SRV_SPEC_RATE = _obs_metrics.gauge(
    "serving.spec_accept_rate",
    "cumulative accepted / drafted speculative tokens")
_SRV_SPEC_EMA = _obs_metrics.gauge(
    "serving.spec_lane_accept_ema",
    "per-lane speculative acceptance EMA driving the adaptive gates")
_SRV_SPEC_FORCED = _obs_metrics.counter(
    "serving.spec_forced_tokens",
    "accepted draft tokens proposed by the grammar's forced-token "
    "chains (a subset of serving.spec_accepted_tokens)")
_SRV_GRAMMAR_MASKED = _obs_metrics.histogram(
    "serving.grammar_masked_fraction",
    "fraction of the vocab masked per constrained emitted token",
    buckets=(0.5, 0.9, 0.99, 0.999, 0.9999, 1.0))
_SRV_KV_OCC = _obs_metrics.gauge(
    "serving.kv_pool_occupancy_ratio",
    "unified KV pool blocks in use / pool capacity")
def _layer_stat_counters(model):
    """One registry counter a column of what the model's layers return
    with a step's harvest (``PagedKV.stats``), under the names the model
    gives them (``model.layer_stat_names``, beside ``kv_cache_layout``:
    an expert model names its routing counts); () for every other model.
    The engine sums into them by layer and kind and owns no name."""
    return tuple(_obs_metrics.counter(
        n, "summed from the layers' PagedKV.stats, by layer and by kind "
        "(prefill|decode)") for n in getattr(model, "layer_stat_names", ()))
_SAMPLER_DISPATCH = _obs_metrics.counter(
    "sampler.dispatch",
    "decode and verify dispatches, by the filters whose search the "
    "sampler runs in them (filters=none|top_p|top_k|top_k+top_p)")
_KV_LATENT_LIVE = _obs_metrics.gauge(
    "kv.latent_blocks_live", "blocks in use of a latent (MLA) pool")
_SRV_BUCKETS = _obs_metrics.gauge(
    "serving.decode_bucket_count",
    "distinct compiled decode programs ((horizon, nb, K) triples)")
_SRV_ABORTS = _obs_metrics.counter(
    "serving.requests_aborted", "requests cancelled by the caller")
_SRV_DEADLINE = _obs_metrics.counter(
    "serving.deadline_expired",
    "queued requests aborted because their admission deadline passed "
    "(a subset of serving.requests_aborted)")
_SRV_QUEUE_WAIT = _obs_metrics.histogram(
    "serving.queue_wait_seconds",
    "submit-to-admission wall seconds, observed when a request claims "
    "a slot (re-admissions after preemption observe again)")
_SRV_PREFILL_CHUNKS = _obs_metrics.histogram(
    "serving.prefill_chunks",
    "chunk dispatches per chunked-prefill request, observed when its "
    "final chunk samples the first token",
    buckets=(1, 2, 4, 8, 16, 32, 64))
_SRV_PREFILL_INTERFERE = _obs_metrics.counter(
    "serving.prefill_interference_seconds",
    "wall seconds decode horizons were delayed by interleaved prefill "
    "chunk dispatches (chunk dispatches issued while decode lanes were "
    "active)")
# compile/cache families SHARED with jit/api.py: one place answers
# "which function retraced" for both to_static and serving programs
_COMPILE_COUNT = _obs_metrics.counter(
    "jit.compile_count", "to_static trace+compile builds, by function")
_CACHE_HIT = _obs_metrics.counter(
    "jit.cache_hit", "to_static calls served from the jit cache")
_COMPILE_SECONDS = _obs_metrics.histogram(
    "jit.compile_seconds",
    "wall seconds from cache miss to first result, by function")


class CompiledFn:
    """jax.jit wrapper that counts compile-cache hits/misses by input
    signature (shape+dtype of every array leaf, plus the VALUES of any
    static args — a new static horizon bucket is a new program).  The
    miss counter is the engine's observable proof of static-shape
    serving: a multi-request run with heterogeneous prompt lengths must
    show decode misses == number of distinct horizon buckets and prefill
    misses == number of distinct length buckets.  Hits/misses also land
    on the typed registry (``jit.compile_count`` / ``jit.cache_hit``
    labeled ``fn=name``) and every miss leaves a retrace-cause event plus
    a compile begin/end pair on the timeline, and ONE record in the
    span log's build table (``observability.span.build``): the program's
    name and bucket key (``meta_fn(args)``), the miss's total seconds,
    and the part of them jax reports as tracing, lowering, backend
    compile and persistent-cache retrieval, the rest being executable
    load, the first run and the card probe below.  (``jit.compile``'s
    one number is the first call's seconds: from a warm compile cache
    everything but compiling.)

    With ``capture_cards=True`` every miss also probes the lowered
    program for a :class:`~paddle_tpu.observability.profiling
    .ProgramCard` — XLA cost/memory analysis, compile seconds, donated
    bytes, and whatever static metadata ``meta_fn(args)`` supplies
    (the engine passes the bucket key).  The probe reads the
    executable the dispatch itself compiled (see
    ``profiling.analyze_lowered``), and cards are memoized PROCESS-WIDE
    by (name, signature): a second engine with the same shapes pays
    nothing.  ``self.last_card`` tracks the card of the most recent
    dispatch (hit or miss) — the engine's per-dispatch cost model."""

    def __init__(self, fn, donate_argnums=(), name=None, static_argnums=(),
                 capture_cards=False, meta_fn=None):
        self._jit = jax.jit(fn, donate_argnums=donate_argnums,
                            static_argnums=static_argnums)
        self._name = name or getattr(fn, "__name__", "fn")
        self._static = tuple(static_argnums)
        self._donate = tuple(donate_argnums)
        self._capture_cards = bool(capture_cards)
        self._meta_fn = meta_fn
        self._seen = set()
        self.cards = {}              # signature -> ProgramCard
        self.last_card = None
        self.misses = 0
        self.hits = 0

    @property
    def calls(self):
        return self.hits + self.misses

    def _signature(self, args):
        static = tuple(args[i] for i in self._static if i < len(args))
        dynamic = [a for i, a in enumerate(args) if i not in self._static]
        return static + tuple(
            (tuple(jnp.shape(a)), str(jnp.result_type(a)))
            for a in jax.tree.leaves(dynamic))

    @staticmethod
    def _card_key(sig):
        """Short stable card key for one input signature (the human-
        readable bucket semantics live in the card's meta)."""
        import hashlib

        return hashlib.sha1(repr(sig).encode()).hexdigest()[:12]

    def _donated_bytes(self, args):
        """Bytes of the argument leaves a dispatch donates (aval
        metadata — safe to compute even around donation)."""
        total = 0
        for i in self._donate:
            if i < len(args):
                for leaf in jax.tree.leaves(args[i]):
                    total += int(np.prod(jnp.shape(leaf), dtype=np.int64)
                                 * jnp.dtype(jnp.result_type(leaf)).itemsize)
        return total

    def _meta(self, args):
        if self._meta_fn is None:
            return {}
        try:
            return dict(self._meta_fn(args))
        except Exception:            # pragma: no cover - defensive
            return {}

    def __call__(self, *args):
        sig = self._signature(args)
        if sig in self._seen:
            self.hits += 1
            _CACHE_HIT.inc(fn=self._name)
            card = self.cards.get(sig)
            if card is not None:
                card.dispatches += 1
            self.last_card = card
            return self._jit(*args)
        self._seen.add(sig)
        self.misses += 1
        _obs_events.instant(
            "jit.retrace", cat="serving", fn=self._name,
            cause=("first_call" if self.misses == 1
                   else "new_input_signature"),
            cached_signatures=len(self._seen) - 1)
        _obs_events.begin("jit.compile", cat="serving", fn=self._name)
        meta = self._meta(args)
        with _build(self._name, meta):
            # lower BEFORE the call: on donating backends the call deletes
            # the donated buffers, after which tracing them would fail.  A
            # process-wide card for this exact program skips the probe.
            lowered = card = None
            donated = 0
            if self._capture_cards:
                key = self._card_key(sig)
                card = _obs_profiling.default_registry().get(self._name, key)
                if card is None:
                    donated = self._donated_bytes(args)
                    lowered = self._jit.lower(*args)
            t0 = time.perf_counter()
            try:
                out = self._jit(*args)
            finally:
                dt = time.perf_counter() - t0
                _COMPILE_COUNT.inc(fn=self._name)
                _COMPILE_SECONDS.observe(dt, fn=self._name)
                _obs_events.end("jit.compile", cat="serving", fn=self._name,
                                seconds=round(dt, 9))
            if self._capture_cards:
                if card is None:
                    # after the call, so the card reads the executable the
                    # call compiled and nothing compiles twice
                    card = _obs_profiling.capture(
                        self._name, key, lowered, compile_seconds=dt,
                        donated_bytes=donated, meta=meta,
                        backend=_obs_peaks.current_device_kind())
                card.dispatches += 1
                self.cards[sig] = card
                self.last_card = card
            return out


@dataclass
class EngineConfig:
    num_slots: int = 8
    max_seq_len: int = 256
    #: smallest prefill bucket; prompts pad up to the next power of two
    min_prefill_bucket: int = 8
    #: chunked prefill (Sarathi-style): split every prefill whose
    #: suffix exceeds this many tokens into fixed-size chunks dispatched
    #: one per step boundary, interleaved with decode horizons, so a
    #: long prompt can no longer monopolize the engine (TPOT spikes for
    #: the active decode batch shrink to one chunk-bucket program per
    #: boundary).  Normalized to a power of two >= min_prefill_bucket
    #: (the compile-cache discipline: every chunk dispatch reuses ONE
    #: program per lane bucket), and the per-dispatch token budget is
    #: chunk_tokens per lane.  The lane's block table grows chunk by
    #: chunk, partial progress is adopted into the prefix radix store at
    #: every chunk boundary (preemption mid-prefill resumes from the
    #: boundary via an ordinary prefix hit), and the final chunk samples
    #: the request's first token under the unchanged
    #: ``request_key(seed, counts)`` PRNG — so chunked output is
    #: BITWISE-equal to whole-prompt prefill, greedy and seeded.
    #: 0 disables (whole-prompt prefill).
    prefill_chunk_tokens: int = 0
    #: kv cache dtype; None = the model's parameter dtype
    cache_dtype: object = None
    #: largest number of fused decode steps one compiled dispatch may
    #: scan (power of two; 1 disables horizon decode).  The adaptive
    #: policy picks a bucket in [1, max_horizon] at every boundary.
    max_horizon: int = 8
    #: prefix-cache block size in tokens: full blocks of every admitted
    #: prompt are cached and reused by later prompts sharing the prefix
    #: (0 disables prefix caching)
    prefix_block_size: int = 16
    #: device-byte budget for the prefix-cache block pool; the pool
    #: holds budget // bytes_per_block blocks, LRU-evicted when full
    prefix_cache_bytes: int = 8 << 20
    #: admission reorder window: a queued request is never overtaken by
    #: more than this many later-submitted requests when admission
    #: groups same-bucket prompts into one prefill dispatch (0 = strict
    #: FIFO, co-batching only contiguous same-bucket runs)
    reorder_window: int = 8
    #: total blocks in the unified paged KV pool (incl. the reserved
    #: scratch block 0).  0 = auto: every slot can grow to a full row
    #: plus prefix-cache headroom — no request can ever starve.  A
    #: smaller explicit value oversubscribes HBM: admission defers and
    #: decode preempts the youngest lane when the pool runs dry.
    kv_pool_blocks: int = 0
    #: ragged decode attention: bucket the decode program's block-table
    #: width to a power of two of the deepest live row, so per-step KV
    #: reads track live sequence length.  False pins the width to
    #: max_blocks_per_slot: every decode step then reads the full table
    #: width (tests/test_serving_counts.py holds both counts, in bytes).
    ragged_attention: bool = True
    #: speculative decoding: max draft tokens per lane per fused step.
    #: 0 = plain decode.  K > 0 self-drafts K tokens per lane from its
    #: prompt+output history (prompt-lookup n-gram matching, traced into
    #: the decode program), verifies all K+1 positions in one forward,
    #: and emits the longest matching prefix plus one — greedy and
    #: seeded-sampled output stays bitwise-equal to spec_k=0.
    spec_k: int = 0
    #: shrink the dispatch draft width to 0 (plain decode) when no
    #: running lane's recent acceptance EMA clears spec_accept_floor;
    #: lanes below the floor are also gated off inside a K-wide dispatch
    #: (they draft nothing and emit exactly one token per step)
    spec_adaptive: bool = True
    #: trailing-suffix length the self-drafter matches on
    spec_ngram: int = 2
    #: per-lane acceptance-rate floor (EMA of accepted/K per verify
    #: window) below which adaptive drafting turns off for that lane
    spec_accept_floor: float = 0.125
    #: weight-only serving quantization: "int8" absmax-calibrates
    #: per-output-channel scales for every Linear projection at engine
    #: construction (quantization.quantize_for_serving) and stores int8
    #: weights + f32 scales; prefill/decode dequantize inline (fused by
    #: XLA into the matmul weight read), halving decode's weight-byte
    #: roofline.  None keeps fp weights — and the compiled programs
    #: bitwise-identical to an unquantized engine.
    weight_dtype: object = None
    #: KV-cache storage dtype for the unified paged pool: "int8" stores
    #: quantized blocks with one f32 absmax scale per token beside the
    #: block table (quantize at append/COW, dequantize after the
    #: attention gather), halving per-step serving.kv_bytes_read and
    #: ~2x-ing how many sequences fit a fixed kv_pool_blocks byte
    #: budget.  None keeps the fp pool (cache_dtype).
    kv_cache_dtype: object = None
    #: tiered KV cache: host-RAM byte budget for the spill arena under
    #: the device pool (serving/kv_host_tier.py).  LRU-evicted prefix
    #: blocks demote into it instead of dropping, preempted lanes save
    #: their whole block chain, and re-admission swaps state back in
    #: with one batched host->device upload instead of re-prefilling.
    #: int8 pools spill at their quantized density (~4x more contexts
    #: per host byte).  0 disables the tier entirely.
    kv_host_bytes: int = 0
    #: swap-vs-recompute policy: "auto" swaps when estimated upload
    #: seconds (bytes / measured host<->device bandwidth) beat the
    #: estimated re-prefill seconds (measured per-token prefill
    #: throughput over this engine's own dispatches); "always"/"never"
    #: pin the decision (the bench's crossover sweep and the parity
    #: tests use the pinned modes).
    kv_swap_policy: str = "auto"
    #: request-scoped tracing: attach a RequestTrace flight record to
    #: every request at submit, retained by a bounded FlightRecorder
    #: (all live traces + the last ``flight_recorder_capacity``
    #: finished ones) and served at /debug/requests.  Appends are O(1)
    #: per lifecycle transition, so the decode path cost is bounded
    #: (what it costs on the chip: not measured).
    request_tracing: bool = True
    flight_recorder_capacity: int = 256
    #: program cards: capture XLA cost/memory analysis, compile seconds,
    #: donated bytes, and the bucket key at the first compile of every
    #: decode/prefill program (observability.profiling).  Cards feed the
    #: compile.* gauges, /debug/programs, per-request cost attribution,
    #: and the live roofline gauge.  The probe may cost one extra XLA
    #: compile per DISTINCT program per process (cards are memoized
    #: process-wide, so same-shape engines re-use them); False turns the
    #: observatory off entirely.
    program_cards: bool = True
    #: start a TelemetryServer (observability.server) on this port at
    #: engine construction, stopped by close().  0 binds an ephemeral
    #: port (engine.telemetry.port reports it); None disables.
    telemetry_port: int | None = None
    #: SLO objectives over step-sized rolling windows (observability
    #: .slo): per-request TTFT seconds, per-request mean TPOT seconds,
    #: and abort rate.  None disables an objective; with all three None
    #: no tracker is created and /readyz is always ready.
    slo_ttft_s: float | None = None
    slo_tpot_s: float | None = None
    slo_abort_rate: float | None = None
    #: compliance target shared by the latency objectives (e.g. 0.95 =
    #: "p95 under the threshold") and the burn-rate denominator
    slo_target: float = 0.95
    #: rolling window sizes in OBSERVATIONS (retired requests), not
    #: wall-clock — deterministic under test; unhealthy requires both
    #: windows burning above 1x budget
    slo_fast_window: int = 64
    slo_slow_window: int = 640
    #: graceful-degradation ladder: under sustained SLO burn or pool
    #: pressure the engine steps down one level per ``degrade_patience``
    #: consecutive burning steps — 1 disables speculative decoding,
    #: 2 shrinks the decode horizon to 1 (admission at every boundary),
    #: 3 sheds lowest-priority queued requests down to ``num_slots``
    #: queued — and recovers one level per ``degrade_recover_patience``
    #: consecutive calm steps (hysteresis: recovery is deliberately
    #: slower than escalation, so the ladder can't flap).  Transitions
    #: ride the event ring and the serving.degradation_level gauge.
    degrade_enabled: bool = True
    #: pool occupancy fraction that counts as block-pool pressure
    degrade_pool_ratio: float = 0.92
    degrade_patience: int = 4
    degrade_recover_patience: int = 16
    #: structured generation (grammar-constrained decoding): capacity of
    #: the token-DFA state slab, in states.  0 (default) disables the
    #: subsystem entirely — every grammar argument threads ``None``
    #: (an empty pytree) through the compiled programs, so the knobs-off
    #: decode/prefill programs are structurally the unconstrained ones.
    #: Row 0 of the slab is the accept-all sentinel state unconstrained
    #: lanes ride; request grammars are compiled to token DFAs and
    #: installed at refcounted offsets >= 1, so mixed constrained /
    #: free-text batches share one program (no per-grammar retracing).
    grammar_max_states: int = 0
    #: the tokenizer vocabulary as a sequence of token STRINGS indexed
    #: by token id (ids >= len() are unreachable fillers).  Required to
    #: compile grammars: the compiler walks every token's characters
    #: through the grammar's character DFA to build the token-level
    #: transition table and legality bitmask.
    grammar_vocab: object = None
    #: propose the grammar's forced-token chains (states with exactly
    #: one legal token — JSON skeleton punctuation) ahead of the n-gram
    #: drafter's guesses.  Forced proposals are ~100%-acceptance drafts;
    #: the PR 7 acceptance rule and EMA gating are unchanged.
    grammar_forced_drafting: bool = True
    #: host compile-cache bound: a compiled token DFA stays cached per
    #: (grammar, eos) while any live request references its slab
    #: segment (pinned — admission walks resume histories through it),
    #: plus up to this many RETIRED entries kept LRU after the last
    #: reference drops, so repeat grammars skip recompilation without
    #: the host cache growing unboundedly under a stream of unique
    #: gateway grammars (each entry holds a dense [states, vocab]
    #: int32 table).
    grammar_cache_keep: int = 8


def _layer_stats(views):
    """What the model's layers asked to have counted with this step's
    harvest (``PagedKV.stats``), stacked [layers that set one, n]; None —
    an empty pytree, the program unchanged — when no layer did."""
    rows = [v.stats for v in views if v.stats is not None]
    return jnp.stack(rows) if rows else None


def _unpack_mask(rows, vocab):
    """Unpack packed legality-bitmask rows to a boolean mask.

    rows [..., W32] uint32   bit ``t % 32`` of word ``t // 32`` set
                             means token ``t`` is legal
    Returns [..., vocab] bool.  A pure shift/compare — XLA fuses it
    into the ``where`` that applies the mask, so the dense [S, vocab]
    boolean form never materializes in HBM per state table."""
    bits = jnp.arange(32, dtype=jnp.uint32)
    b = (rows[..., :, None] >> bits) & jnp.uint32(1)
    flat = b.reshape(rows.shape[:-1] + (rows.shape[-1] * 32,))
    return flat[..., :vocab].astype(bool)


@dataclass
class _ChunkProgress:
    """Host ledger of one in-flight chunked prefill.  The request holds
    its slot (scheduler RUNNING, decode-INACTIVE — the horizon scan
    masks the lane like a retired one) while fixed-size chunks of its
    admission token sequence dispatch one per step boundary.  ``covered``
    tokens are already written into the lane's KV blocks; every chunk
    boundary adopts the newly completed full blocks into the prefix
    radix store, so the boundary doubles as the preemption resume point
    (re-admission finds the progress as an ordinary prefix hit)."""

    req: object
    slot: int
    lease: object
    toks: list
    covered: int              # tokens written into the lane's KV so far
    chunks: int = 0           # chunk dispatches taken (incl. admission)


class Engine:
    """Submit/step/generate over a causal-LM Layer (GPTForCausalLM /
    LlamaForCausalLM or anything with ``.model``, ``.config`` and
    ``._logits``)."""

    _instances = 0

    def __init__(self, model, config=None, register_profiler=True):
        self.model = model
        self.config = config or EngineConfig()
        model.eval()
        mc = model.config
        self._weight_dtype = self._norm_quant_knob(
            self.config.weight_dtype, "weight_dtype")
        self._kv_quant = self._norm_quant_knob(
            self.config.kv_cache_dtype, "kv_cache_dtype")
        self._state_names = list(model.state_dict().keys())
        sd = model.state_dict()
        if self._weight_dtype:
            # weight-only PTQ: matmul weights ride the jitted programs
            # as (int8, f32-scale) pairs and _run_model dequantizes them
            # inline — XLA fuses the multiply into the weight read, so
            # only int8 bytes stream from HBM per decode step
            from ..quantization import quantize_for_serving

            qmap = quantize_for_serving(model)
            self._wq_dtypes = {n: qw.dtype for n, qw in qmap.items()}
            self._state_arrays = [
                qmap[n].pair if n in qmap else sd[n]._data
                for n in self._state_names]
        else:
            self._wq_dtypes = {}
            self._state_arrays = [sd[n]._data for n in self._state_names]
        cache_dtype = (self.config.cache_dtype
                       or model.model.embed_tokens.weight._data.dtype)
        # ONE paged block pool backs every slot's table AND the prefix
        # store; the pool block size doubles as the prefix block size.
        # With kv_pool_blocks=0 the pool is sized so no request can
        # starve (full row per slot) plus prefix-budget headroom.
        self._block_size = max(1, int(self.config.prefix_block_size) or 16)
        budget = (self.config.prefix_cache_bytes
                  if self.config.prefix_block_size else 0)
        # the model states what a layer keeps of a token (a k/v pair, or a
        # latent layer's one buffer): pool, radix store, host tier use it
        layout = model_cache_layout(model)
        token_shape = layout.token_shape
        bytes_per_block = layout.block_bytes(
            len(model.model.layers), self._block_size,
            1 if self._kv_quant else jnp.dtype(cache_dtype).itemsize,
            bool(self._kv_quant))
        prefix_capacity = int(budget) // bytes_per_block
        self.cache = PagedKVCache(
            num_layers=len(model.model.layers),
            num_slots=self.config.num_slots,
            max_seq_len=self.config.max_seq_len,
            block_size=self._block_size,
            kv_heads=token_shape[0], head_dim=token_shape[1],
            dtype=cache_dtype,
            num_blocks=int(self.config.kv_pool_blocks),
            extra_blocks=prefix_capacity,
            quant_dtype=self._kv_quant, layout=layout)
        self.pool = self.cache.pool
        self.scheduler = Scheduler(self.config.num_slots,
                                   reorder_window=self.config.reorder_window)

        # prefix KV reuse in unified-pool mode: the radix store holds
        # refcounted blocks of self.pool — hits lease blocks straight
        # into slot tables, caching is adopt() refcounting, and the
        # byte budget bounds how many pool blocks the store may pin.
        self.prefix = PrefixCache(
            num_layers=len(model.model.layers),
            block_size=self._block_size,
            kv_heads=token_shape[0], head_dim=token_shape[1],
            dtype=cache_dtype, budget_bytes=budget, pool=self.pool,
            bytes_per_block=self.pool.bytes_per_block)
        self._max_blocks = self.cache.max_blocks_per_slot
        self._leases = {}            # request_id -> PrefixLease

        # tiered KV: the host-RAM spill arena under the device pool.
        # Prefix eviction demotes into it (the spill hook runs while
        # the victim's pool block is still live), preemption saves lane
        # images, and admission promotes matching host blocks back into
        # the radix store via one batched upload (_swap_in) — so the
        # swap-in path IS the ordinary prefix-hit path and inherits its
        # bitwise guarantees.
        policy = str(self.config.kv_swap_policy)
        if policy not in ("auto", "always", "never"):
            raise ValueError(
                f"unsupported kv_swap_policy {policy!r} "
                "(supported: 'auto', 'always', 'never')")
        self._swap_policy = policy
        host_budget = int(self.config.kv_host_bytes or 0)
        if host_budget < 0:
            raise ValueError(
                f"kv_host_bytes must be >= 0, got {host_budget}")
        self.host_tier = None
        if host_budget:
            self.host_tier = HostKVTier(
                num_layers=len(model.model.layers),
                block_size=self._block_size,
                kv_heads=token_shape[0], head_dim=token_shape[1],
                store_dtype=np.dtype(jnp.dtype(self.pool.store_dtype)),
                budget_bytes=host_budget,
                bytes_per_block=self.pool.bytes_per_block,
                quantized=bool(self._kv_quant), layout=layout)
            self.prefix.spill = self._demote_block
            self.prefix.spill_batch = self._demote_blocks
        # what the model's layers have counted with a step's harvest
        self._layer_counters = _layer_stat_counters(model)
        self._stat_layers = 0            # layers that returned stats
        self._swap_ins = 0               # lane/prefix swap-in passes
        self._swap_outs = 0              # lane images saved at preempt
        self._swap_in_blocks = 0
        self._swap_out_blocks = 0
        self._swap_in_bytes = 0
        self._swap_out_bytes = 0         # lane-save bytes (trace-exact)
        self._demote_bytes = 0           # prefix-demotion bytes
        self._swaps_averted_tokens = 0
        self._swaps_averted_flops = 0.0
        # measured inputs the "auto" swap policy compares: per-token
        # prefill seconds over this engine's own non-compiling
        # dispatches, and per-token prefill FLOPs from program cards
        self._prefill_dispatch_s = 0.0
        self._prefill_tokens_dispatched = 0
        self._prefill_card_flops = 0.0
        self._prefill_card_tokens = 0

        # chunked prefill: normalize the chunk size to a power of two in
        # [min_prefill_bucket, max_seq_len] so every chunk dispatch hits
        # one compiled program per lane bucket (0 = whole-prompt prefill)
        ct = int(self.config.prefill_chunk_tokens or 0)
        if ct < 0:
            raise ValueError(
                f"prefill_chunk_tokens must be >= 0, got {ct}")
        if ct:
            ct = min(self._pow2_ceil(max(ct,
                                         self.config.min_prefill_bucket)),
                     self.config.max_seq_len)
        self._chunk_tokens = ct
        self._chunking = {}          # request_id -> _ChunkProgress
        self._chunk_dispatches = 0   # compiled chunk-continuation calls
        self._chunked_requests = 0   # requests admitted chunk-wise
        self._chunk_count_total = 0  # chunk dispatches across requests
        self._prefill_interference_s = 0.0
        self._prefill_buckets = set()   # (lanes, bucket) per dispatch
        self._context_high_water = 0    # deepest prefilled position

        # host MIRRORS of the per-slot decode state.  The authoritative
        # copy lives on device between horizons (updated inside the
        # compiled scan); the mirrors exist so admission can rebuild the
        # device arrays when it dirties them, and are maintained from
        # harvested tokens alone — retirement is detected inside the
        # scan, so it never dirties the device state.
        n = self.config.num_slots
        self._tokens = np.zeros(n, np.int32)        # last token per slot
        self._pos = np.zeros(n, np.int32)           # row length per slot
        self._seeds = np.zeros(n, np.uint32)
        self._counts = np.zeros(n, np.int32)        # tokens sampled so far
        self._temps = np.zeros(n, np.float32)
        self._top_ks = np.zeros(n, np.int32)
        self._top_ps = np.ones(n, np.float32)
        self._eos_ids = np.full(n, -1, np.int32)    # -1 = no EOS token
        self._limits = np.zeros(n, np.int32)        # max_new_tokens
        self._active = np.zeros(n, bool)
        # speculative-decode state: the per-lane token history (prompt +
        # emitted tokens — the drafter's corpus; device copy rides the
        # scan carry), the per-lane acceptance EMA, and the draft gates
        # the adaptive policy feeds into the compiled program
        self._hist = np.zeros((n, self.config.max_seq_len), np.int32)
        self._spec_ema = np.ones(n, np.float32)
        self._spec_gates = np.ones(n, bool)
        self._state_dirty = True
        self._d_tokens = self._d_pos = self._d_counts = None
        self._d_active = None
        self._d_hist = None
        self._d_gates = None
        self._d_params = None
        # device copy of the live block-table prefix ([num_slots, nb]);
        # re-uploaded when the host tables dirty or nb re-buckets
        self._d_tables = None
        self._d_tables_nb = -1

        # structured generation: per-lane DFA state ids (0 = the
        # accept-all sentinel free lanes ride) mirror + the host-master
        # slab of token-DFA tables.  The state column rides the donated
        # decode carry exactly like pos/counts; the slab tables are
        # loop-invariant operands re-uploaded only when installs or
        # releases dirty them (like the block tables).
        cap = int(self.config.grammar_max_states or 0)
        if cap < 0:
            raise ValueError(
                f"grammar_max_states must be >= 0, got {cap}")
        self._structured = cap > 0
        self._grammar_slab = (GrammarSlab(cap, mc.vocab_size)
                              if self._structured else None)
        self._dfa_state = np.zeros(n, np.int32)
        self._d_dfa_state = None
        self._d_dfa_next = self._d_dfa_mask = self._d_dfa_forced = None
        self._grammar_cache = {}     # (spec key, eos id) -> TokenDFA;
                                     # pinned while slab-installed, then
                                     # LRU-bounded (grammar_cache_keep)
        self._grammar_keys = {}      # request_id -> slab segment key
        self._grammar_cache_hits = 0
        self._grammar_cache_misses = 0

        # donation buys in-place HBM pool updates on accelerators; CPU
        # would only warn that donation is unimplemented.  The scale
        # pools (args 16/17 decode, 10/11 prefill) are donated only when
        # they carry arrays — donating the fp path's None placeholders
        # is a no-op but keeping the tuples identical to the pre-quant
        # engine documents that nothing changed with the knobs off.
        donate = jax.default_backend() not in ("cpu",)
        decode_donate = (1, 2, 3, 4, 5, 14, 15)
        prefill_donate = (8, 9)
        if self._kv_quant:
            decode_donate += (16, 17)
            prefill_donate += (10, 11)
        if self._structured:
            # the per-lane DFA state (arg 20) rides the scan carry like
            # pos — donated; the slab tables (21-23) are loop-invariant
            # inputs shared by every lane and are NOT donated
            decode_donate += (20,)
        # program-card metadata: the human-readable bucket key of each
        # compiled program, read off the dispatch's own arguments
        # (decode: tables arg 13, horizon/k statics 18/19; prefill: the
        # padded ids arg 1)
        def _decode_meta(args):
            return {"horizon": int(args[18]), "k_draft": int(args[19]),
                    "nb": int(args[13].shape[1]),
                    "num_slots": int(args[13].shape[0])}

        def _prefill_meta(args):
            return {"lanes": int(args[1].shape[0]),
                    "bucket": int(args[1].shape[1])}

        cards = bool(self.config.program_cards)
        self._decode = CompiledFn(
            self._decode_fn,
            donate_argnums=decode_donate if donate else (),
            static_argnums=(18, 19), name="serving.decode",
            capture_cards=cards, meta_fn=_decode_meta)
        self._prefill = CompiledFn(self._prefill_fn,
                                   donate_argnums=(prefill_donate
                                                   if donate else ()),
                                   name="serving.prefill",
                                   capture_cards=cards,
                                   meta_fn=_prefill_meta)
        # tiered-KV swap upload: scatter n host blocks into the pool at
        # freshly allocated ids — ONE compiled call per swap-in pass,
        # n padded to a power of two (padding rows target scratch block
        # 0) so the compile cache stays bounded by log2(max chain)
        def _upload_meta(args):
            return {"blocks": int(args[4].shape[0])}

        self._upload = CompiledFn(
            self._upload_fn,
            donate_argnums=(((0, 1) + ((2, 3) if self._kv_quant else ()))
                            if donate else ()),
            name="serving.swap_upload", capture_cards=cards,
            meta_fn=_upload_meta)

        # observability
        self._decode_steps = 0
        self._decode_horizons = 0
        self._host_syncs = 0
        self._decode_harvested = 0
        self._wasted_lane_tokens = 0
        self._horizon_buckets = set()
        self._grow = 1                   # adaptive-horizon growth state
        self._decode_buckets = set()     # compiled (horizon, nb, K)
        self._spec_draft_tokens = 0
        self._spec_accepted_tokens = 0
        self._spec_windows = 0           # verify windows of drafting lanes
        self._spec_accept_hist = {}      # tokens-emitted-per-window -> n
        self._spec_forced_tokens = 0     # accepted forced-chain drafts
        self._kv_bytes_read = 0
        # engine-local cost-model totals: card FLOPs/bytes summed over
        # THIS engine's dispatches (card.dispatches is process-global
        # across engines, so it can't serve as a per-engine total).
        # Per-request attribution must reconstruct these within 1%.
        self._program_flops = 0.0
        self._program_bytes = 0.0
        self._cow_copies = 0
        self._preemptions = 0
        self._aborted = 0
        self._deadline_expired = 0
        self._tenants = {}               # tenant -> accounting dict
        self._draining = False
        # fault injection (faults.install_faults) + degradation ladder
        self.faults = None               # FaultInjector or None
        self._fault_scope = ""
        self._admit_deferred = False     # injected pool-exhaustion pass
        self._degrade_level = 0
        self._burn_streak = 0            # consecutive burning steps
        self._calm_streak = 0            # consecutive calm steps
        self._degrade_transitions = 0
        self._degrade_history = []       # last 64 transitions
        self._degrade_sheds = 0
        self._prefill_calls = 0          # compiled prefill DISPATCHES
        self._prefill_requests = 0       # requests prefilled (>= calls)
        self._prefix_hit_tokens = 0
        self._prompt_tokens = 0
        self._tokens_generated = 0
        self._busy_s = 0.0
        self._slot_busy_integral = 0.0   # sum over steps of used/num
        self._finished = 0
        self._ttft_sum = 0.0
        self._ttft_n = 0

        Engine._instances += 1
        self._profiler_name = f"serving.engine{Engine._instances}"
        # the radix store's eviction-destination counter labels by
        # engine instance like every other serving.* family
        self.prefix.metric_label = self._profiler_name
        self._finalizer = None
        if register_profiler:
            from .. import profiler as _profiler

            # the provider must NOT keep the engine alive (a bound method
            # in a process-global registry pins the engine — and its full
            # KV cache — forever): register a weakref-backed provider and
            # let GC unregister it, so repeated engine construction in
            # one process never leaks stale providers into
            # profiler.counters()
            ref = weakref.ref(self)

            def _provider():
                eng = ref()
                return eng.counters() if eng is not None else {}

            _profiler.register_counter_provider(self._profiler_name,
                                                _provider)
            self._finalizer = weakref.finalize(
                self, _profiler.unregister_counter_provider,
                self._profiler_name)

        # observability phase 3: the device-memory ledger reconciles
        # what the engine KNOWS it holds (paged KV pool, weights,
        # device decode state) against jax.live_arrays() at stats()
        # time; live bytes NOBODY accounts for growing past the first
        # snapshot is the leak signature (memory.leak_delta_bytes).
        # Engine-owned, so the accounting closures can't outlive it.
        self.ledger = _obs_memory.MemoryLedger(self._profiler_name)
        self.ledger.register("kv_pool", self._kv_pool_bytes)
        self.ledger.register("weights", self._weight_device_bytes)
        self.ledger.register("engine_state", self._state_device_bytes)
        if self.host_tier is not None:
            # host arena: accounted SEPARATELY from the device ledger
            # (numpy buffers never appear in jax.live_arrays(), so
            # folding them into the device sum would poison
            # leak_delta_bytes) — register_host keeps the reconciliation
            # exact while memory.host_arena_bytes reports the pinned
            # footprint
            self.ledger.register_host("kv_host_arena",
                                      self._host_arena_bytes)

        # observability phase 2: per-request flight records, declared
        # SLOs over the retirement stream, and the HTTP telemetry
        # endpoint.  The server holds the recorder/tracker (not the
        # engine), so it can never pin the engine's KV pool alive.
        self.recorder = (
            _obs_tracing.FlightRecorder(
                self.config.flight_recorder_capacity)
            if self.config.request_tracing else None)
        self.slo = None
        cfg = self.config
        if (cfg.slo_ttft_s is not None or cfg.slo_tpot_s is not None
                or cfg.slo_abort_rate is not None):
            from ..observability.slo import SLOTracker

            self.slo = SLOTracker(self._profiler_name)
            windows = dict(fast_window=cfg.slo_fast_window,
                           slow_window=cfg.slo_slow_window)
            if cfg.slo_ttft_s is not None:
                self.slo.declare("ttft", cfg.slo_ttft_s,
                                 target=cfg.slo_target, **windows)
            if cfg.slo_tpot_s is not None:
                self.slo.declare("tpot", cfg.slo_tpot_s,
                                 target=cfg.slo_target, **windows)
            if cfg.slo_abort_rate is not None:
                # 0/1 observations per retirement; "abort rate < Z"
                # is "1 - Z of observations must be 0"
                self.slo.declare("abort", 0.5,
                                 target=1.0 - cfg.slo_abort_rate,
                                 unit="bool", **windows)
        self.telemetry = None
        if cfg.telemetry_port is not None:
            from ..observability.server import TelemetryServer

            self.telemetry = TelemetryServer(
                port=cfg.telemetry_port, recorder=self.recorder,
                slo=self.slo).start()

    def close(self):
        """Stop the telemetry server and unregister this engine's
        counter provider (idempotent; the provider unregistration also
        runs automatically when the engine is garbage-collected)."""
        if self.telemetry is not None:
            self.telemetry.stop()
        if self._finalizer is not None:
            self._finalizer()

    def install_faults(self, injector, scope=""):
        """Arm deterministic fault injection (faults.FaultInjector) on
        this engine's ``engine.admit`` site; None disarms.  ``scope``
        names this engine in the plan (usually the worker name)."""
        self.faults = injector
        self._fault_scope = scope or self._profiler_name

    @staticmethod
    def _norm_quant_knob(value, name):
        """Normalize a serving quant knob to None or "int8"
        (case-insensitive)."""
        key = value if value is None else str(value).lower()
        if key in (None, "", "none"):
            return None
        if key in ("int8", "i8"):
            return "int8"
        raise ValueError(
            f"unsupported {name} {value!r} (supported: None, 'int8')")

    # ------------------------------------------------------------ pure fns
    def _run_model(self, state_arrays, ids, views, rows=None):
        """Functionalized forward: raw param arrays + token ids + PagedKV
        views -> (logits, new views): every position's [B, S, vocab] for
        the horizon scan and verify windows; with ``rows`` [B] (prefill:
        the one position a lane samples) the LM head projects those rows
        alone, [B, vocab].  Weight-quantized entries arrive as (int8,
        f32-scale) pairs dequantized HERE, inside the traced program —
        XLA fuses the scale into the consuming matmul's weight read, so
        every caller streams int8 weight bytes with no code of its own."""
        arrays = {}
        for name, a in zip(self._state_names, state_arrays):
            if type(a) is tuple:
                q, scale = a
                a = (q.astype(jnp.float32)
                     * scale).astype(self._wq_dtypes[name])
            arrays[name] = a
        with _tape.no_grad():
            with self.model.use_state(arrays):
                h, new_views = self.model.model(Tensor(ids), caches=views)
                logits = self.model._logits(h) if rows is None else \
                    _head_rows(self.model, h, rows)
        return logits._data, new_views

    def _prefill_fn(self, state_arrays, ids, lengths, prefix_lens,
                    tables, cow_src, cow_dst, counts, pool_k, pool_v,
                    pool_ks, pool_vs, seeds, temps, top_ks, top_ps,
                    dfa_state=None, dfa_mask=None):
        """Batched fused prefill over the paged pool: one compiled
        dispatch prefills a whole admission batch.

        ids [L, bucket]      right-padded prompt SUFFIXES (the part not
                             served by the prefix cache)
        lengths [L]          suffix lengths (>= 1: an exact-hit prompt
                             still prefills its final token)
        prefix_lens [L]      cached-prefix lengths incl. a COW tail
                             match (0 on a miss)
        tables [L, MB]       each lane's block-table row: leased prefix
                             blocks first, then private blocks covering
                             the rest of the prompt.  Padding lanes are
                             all-zero — their writes land in scratch.
        cow_src/cow_dst [L]  copy-on-write: cached tail block to copy
                             into the lane's private tail block before
                             the model runs (0/0 = no-op scratch copy)
        counts [L]           tokens already sampled (0 on first
                             admission; preemption re-admission passes
                             ``n_generated - 1`` so the PRNG reproduces
                             the in-flight token bitwise)

        No gathers: cached prefix blocks are ALREADY in the lane's table,
        so attention reads them in place.  The only data motion is the
        single-block COW copy; the model then scatters suffix k/v at
        ``prefix_lens`` (overwriting the COW block from the divergence
        offset on), the LM head projects each lane's last valid position
        alone, and the first token is sampled from that row with
        ``request_key(seed, count)``.

        ``pool_ks``/``pool_vs`` are the quantized pool's per-token scale
        buffers (None on the fp path — an empty pytree, so the traced
        program is unchanged when the knob is off).  The COW copy moves
        a block's scales with its bytes, keeping every stored token's
        dequantization step attached to it.

        ``dfa_state``/``dfa_mask`` are the structured-generation lane
        states ([L] slab-global row ids) and the slab legality bitmask —
        the first sampled token of a constrained lane is masked to its
        admission state's legal set.  Free and padding lanes ride row 0
        (the accept-all sentinel), whose all-ones mask makes the
        ``where`` a bitwise identity; with ``grammar_max_states=0`` both
        thread None, leaving the traced program unchanged."""
        # COW first: duplicate-dst lanes (all no-COW lanes share dst 0)
        # write identical values, so the scatter is collision-safe
        pool_k = [pk.at[cow_dst].set(pk[cow_src]) for pk in pool_k]
        pool_v = [pv if pv is None else pv.at[cow_dst].set(pv[cow_src])
                  for pv in pool_v]       # None: a latent layer, no values
        if pool_ks is not None:
            pool_ks = [s.at[cow_dst].set(s[cow_src]) for s in pool_ks]
            pool_vs = [s.at[cow_dst].set(s[cow_src]) for s in pool_vs]
        else:
            pool_ks = [None] * len(pool_k)
            pool_vs = [None] * len(pool_v)
        views = [PagedKV(pk, pv, tables, prefix_lens, ks, vs)
                 for pk, pv, ks, vs in zip(pool_k, pool_v,
                                           pool_ks, pool_vs)]
        last, new_views = self._run_model(state_arrays, ids, views,
                                          rows=lengths - 1)
        if dfa_mask is not None:
            allowed = _unpack_mask(dfa_mask[dfa_state], last.shape[-1])
            last = jnp.where(allowed, last, MASK_FLOOR)
        first = sample_batch(last, seeds, counts, temps, top_ks, top_ps)
        return (first, [nv.k for nv in new_views],
                [nv.v for nv in new_views],
                [nv.k_scale for nv in new_views],
                [nv.v_scale for nv in new_views], _layer_stats(new_views))

    def _decode_fn(self, state_arrays, tokens, pos, counts, active, hist,
                   gates, seeds, temps, top_ks, top_ps, eos_ids, limits,
                   tables, pool_k, pool_v, pool_ks, pool_vs, horizon,
                   k_draft, dfa_state=None, dfa_next=None,
                   dfa_mask=None, dfa_forced=None):
        """The horizon-scanned fused decode: ``lax.scan`` over ``horizon``
        fused steps, all slots, static shapes everywhere — the pool is
        the scan carry (donated on accelerators, so writes are in-place
        HBM updates) and the block tables are loop-invariant (block
        coverage for the whole horizon's write window is ensured before
        dispatch).  Retirement is detected inside the scan — a lane that
        hits its EOS id or exhausts its token budget freezes
        (``pos``/``counts`` stop advancing, its carried token stops
        changing) and harvests ``-1`` from then on.  Frozen lanes still
        run the model: their writes land at a frozen position of a
        still-held block (or in scratch once the row is zeroed), which
        the masking contract makes invisible.

        With ``k_draft > 0`` every step is a draft-and-verify window of
        ``W = k_draft + 1`` positions: the traced drafter proposes K
        continuation tokens from the lane's history buffer (``-1`` where
        it has no proposal, which no sampled token can equal), ONE
        forward scores all W positions through the paged path (the
        verify is a W-token ragged prefill against the lane's block
        table), and position j is sampled under ``fold_in(seed, cnt+j)``
        — the exact key and logits sequential decode would use for that
        token, PROVIDED the draft prefix before it matched.  The lane
        emits positions ``0..n_acc`` where ``n_acc`` is the longest
        draft prefix whose sampled verification matched, truncated at
        the first EOS/budget stop; unemitted positions harvest ``-1``.
        Rejected-position KV is garbage, but the next step writes at
        ``pos + n_emit`` onward before anything reads there, so it is
        never observed.  ``horizon`` and ``k_draft`` are static and
        ``nb = tables.shape[1]`` re-buckets by shape: one compiled
        program per (horizon, nb, K) triple.

        A quantized pool's scale buffers (``pool_ks``/``pool_vs``) ride
        the scan carry beside the pools they describe; the fp path
        carries tuples of None — empty pytrees, so the scan's jaxpr is
        unchanged with the knob off.

        Structured generation adds the per-lane DFA state ``dfa_state``
        to the carry (advanced only by EMITTED tokens, so it freezes
        with the lane) and three loop-invariant slab tables:
        ``dfa_next`` [S, V] dense transitions, ``dfa_mask`` [S, W32]
        packed legality bits, ``dfa_forced`` [S] the state's sole legal
        token or -1.  Verify-window position j is masked by the state
        reached by walking ``drafts[:j]`` through ``dfa_next``; for
        every emitted position that walk equals the true state over the
        actually-emitted tokens (the acceptance chain only survives
        position j when ``drafts[j]`` matched the mask-constrained
        sample, so the first illegal or absent draft breaks the chain
        there, and later positions — whose walked states are
        garbage-but-in-bounds rows, REJECT storing row 0 — are never
        emitted).  Masking happens before sampling inside
        ``sample_window``, so the ``fold_in(seed, count)`` key
        discipline and bitwise batched-vs-sequential parity carry over
        verbatim; free lanes ride the accept-all sentinel row 0 whose
        mask is the identity.  With ``grammar_max_states=0`` all four
        grammar arguments thread None — empty pytrees, the
        unconstrained program."""
        n, s = hist.shape
        lanes = jnp.arange(n)[:, None]
        j_idx = jnp.arange(k_draft + 1, dtype=counts.dtype)[None, :]
        if pool_ks is None:
            pool_ks = [None] * len(pool_k)
            pool_vs = [None] * len(pool_v)

        def body(carry, _):
            tok, p, cnt, act, hb, ds, pk, pv, pks, pvs = carry
            if k_draft:
                drafts = draft_tokens(hb, p + 1, k_draft,
                                      self.config.spec_ngram)
                if (dfa_next is not None
                        and self.config.grammar_forced_drafting):
                    # constraint-aware drafting: forced-token chains
                    # override the n-gram guesses BEFORE the gate mask,
                    # so the EMA gating semantics are unchanged
                    fd = forced_chain(ds, dfa_next, dfa_forced, k_draft)
                    drafts = jnp.where(fd >= 0, fd, drafts)
                drafts = jnp.where(gates[:, None], drafts, -1)
                ids = jnp.concatenate(
                    [tok[:, None], jnp.maximum(drafts, 0)], axis=1)
            else:
                ids = tok[:, None]
            views = [PagedKV(k, v, tables, p, ks, vs)
                     for k, v, ks, vs in zip(pk, pv, pks, pvs)]
            logits, new_views = self._run_model(state_arrays, ids, views)
            if dfa_mask is not None:
                sts = [ds]
                for j in range(k_draft):
                    sts.append(dfa_next[sts[-1],
                                        jnp.maximum(drafts[:, j], 0)])
                win_states = jnp.stack(sts, axis=1)
                allowed = _unpack_mask(dfa_mask[win_states],
                                       logits.shape[-1])
                e = sample_window(logits, seeds, cnt, temps, top_ks,
                                  top_ps, allowed=allowed)
            else:
                e = sample_window(logits, seeds, cnt, temps, top_ks,
                                  top_ps)
            if k_draft:
                chain = jnp.cumprod(
                    (drafts == e[:, :k_draft]).astype(jnp.int32), axis=1)
                n_acc = jnp.sum(chain, axis=1)
            else:
                n_acc = jnp.zeros_like(cnt)
            # emit the accepted prefix plus the bonus token, truncated
            # at the first position that retires the lane (EOS or
            # budget) — positions past a stop must not be emitted
            stop = (e == eos_ids[:, None]) | \
                   (cnt[:, None] + j_idx + 1 >= limits[:, None])
            keep = jnp.cumprod(1 - stop.astype(jnp.int32), axis=1)
            prev_ok = jnp.concatenate(
                [jnp.ones_like(keep[:, :1]), keep[:, :-1]], axis=1)
            emitted = (j_idx <= n_acc[:, None]) & (prev_ok > 0) \
                & act[:, None]
            n_emit = jnp.sum(emitted.astype(cnt.dtype), axis=1)
            if dfa_next is not None:
                # advance each lane's DFA by its emitted tokens only —
                # frozen lanes emit nothing and keep their state
                nds = ds
                for j in range(k_draft + 1):
                    nds = jnp.where(emitted[:, j],
                                    dfa_next[nds, e[:, j]], nds)
            else:
                nds = ds
            done = act & jnp.any(emitted & stop, axis=1)
            last = jnp.take_along_axis(
                e, jnp.maximum(n_emit - 1, 0)[:, None], axis=1)[:, 0]
            nxt = jnp.where(act, last, tok)
            new_cnt = cnt + n_emit       # n_emit is 0 for frozen lanes
            new_p = p + n_emit
            # append the emitted tokens to the history buffer (column S
            # is the drop target for unemitted positions)
            cols = jnp.where(emitted, p[:, None] + 1 + j_idx, s)
            hb = hb.at[lanes, cols].set(e, mode="drop")
            harvest = jnp.where(emitted, e, -1)
            return ((nxt, new_p, new_cnt, act & ~done, hb, nds,
                     tuple(v.k for v in new_views),
                     tuple(v.v for v in new_views),
                     tuple(v.k_scale for v in new_views),
                     tuple(v.v_scale for v in new_views)),
                    (harvest, _layer_stats(new_views)))

        init = (tokens, pos, counts, active, hist, dfa_state,
                tuple(pool_k), tuple(pool_v),
                tuple(pool_ks), tuple(pool_vs))
        (tok, p, cnt, act, hb, ds, pk, pv, pks, pvs), (toks, stats) = \
            jax.lax.scan(body, init, None, length=horizon)
        return ((tok, p, cnt, act, hb, ds), list(pk), list(pv),
                list(pks), list(pvs), toks, stats)

    # ------------------------------------------------------------ buckets
    def _bucket(self, prompt_len):
        b = self.config.min_prefill_bucket
        while b < prompt_len:
            b *= 2
        return min(b, self.config.max_seq_len)

    def _lane_bucket(self, n):
        """Static lane count for an n-request prefill batch: the next
        power of two, capped at num_slots (so slot ids stay unique)."""
        lanes = 1
        while lanes < n:
            lanes *= 2
        return min(lanes, self.config.num_slots)

    @staticmethod
    def _admission_tokens(req):
        """The token sequence a prefill must cover for this request.
        First admission: the prompt.  Re-admission after preemption:
        prompt + all-but-the-last generated token — the last one is
        reproduced by the prefill's own sampling (count
        ``n_generated - 1`` under the fold_in PRNG), which doubles as a
        bitwise consistency check on the swap-in."""
        if req.output_ids:
            return req.prompt_ids + req.output_ids[:-1]
        return req.prompt_ids

    def _admission_bucket(self, req):
        """The prefill length bucket a request would dispatch in right
        now: its suffix past the cached prefix, padded to a power of
        two, clamped so prefix + bucket fits the slot row.  With
        chunked prefill on, the bucket is additionally capped at the
        chunk size — the admission dispatch covers only the FIRST chunk
        of a long suffix, so no compiled prefill program is ever wider
        than the chunk bucket (the whole-prompt context cap is gone).
        Used both for co-batch grouping (Scheduler.pop_batch) and for
        sizing the actual dispatch."""
        toks = self._admission_tokens(req)
        matched = self.prefix.lookup(toks)
        bucket = min(self._bucket(len(toks) - matched),
                     self.config.max_seq_len - matched)
        if self._chunk_tokens:
            bucket = min(bucket, self._chunk_tokens)
        return bucket

    def _blocks_needed(self, req):
        """Fresh pool blocks this request's admission would allocate:
        its table entries minus the full-block prefix hits it would
        lease (a COW tail match still needs its own private block).
        Under chunked prefill only the FIRST chunk's coverage is
        allocated at admission — later chunks grow the table chunk by
        chunk, with their own pool-pressure handling."""
        toks = self._admission_tokens(req)
        matched = self.prefix.lookup(toks)
        full = matched // self._block_size
        cover = len(toks)
        if self._chunk_tokens:
            cover = min(cover, matched + self._admission_bucket(req))
        return -(-cover // self._block_size) - full

    @staticmethod
    def _pow2_floor(x):
        return 1 << (int(x).bit_length() - 1)

    @staticmethod
    def _pow2_ceil(x):
        return 1 << max(0, int(x) - 1).bit_length()

    def _attn_blocks(self, h, w=1):
        """The decode program's static block-table width ``nb`` for an
        ``h``-step horizon of ``w``-position verify windows: enough
        entries to cover the deepest live row's write window (up to
        ``h*w`` new positions when every draft is accepted), bucketed to
        a power of two and clamped to ``max_blocks_per_slot``.  With
        ``ragged_attention=False`` it pins to the full width (the
        every-step-reads-everything slotted ablation).  Attention output
        is bitwise-invariant to ``nb`` (see paged_attention.py), so
        re-bucketing never perturbs a token — it only changes how many
        blocks each step reads."""
        if not self.config.ragged_attention:
            return self._max_blocks
        mx = max((int(self._pos[s]) for s in self.scheduler.running),
                 default=0)
        need = -(-(mx + h * w) // self._block_size)
        return min(self._max_blocks, max(1, self._pow2_ceil(need)))

    def _resolve_spec_k(self):
        """The draft width for the next decode dispatch.  ``spec_k`` is
        a static compile bucket (like horizon), so the adaptive choice
        is dispatch-level: drafting stays on while ANY running lane's
        acceptance EMA clears the floor — below-floor lanes are gated
        off INSIDE the K-wide program (they draft nothing and emit one
        token per step, i.e. plain decode), and once every lane is
        below the floor the dispatch itself shrinks to K=0 so the
        verify window costs nothing at all."""
        if self._degrade_level >= 1:
            return 0                 # ladder level 1+: spec decoding off
        k = max(0, int(self.config.spec_k))
        if not k or not self.config.spec_adaptive:
            return k
        if any(self._spec_gates[s] for s in self.scheduler.running):
            return k
        return 0

    def _resolve_horizon(self, requested=None):
        """Pick the horizon bucket for the next decode dispatch.

        Explicit ``requested`` is clamped to ``[1, max_horizon]`` and
        rounded down to a power of two (the static compile buckets).
        Adaptive (``requested=None``): 1 while the queue is non-empty
        (admit at every boundary) or a lane is within one step of its
        token budget; otherwise grow multiplicatively from the last
        stable horizon toward ``max_horizon``, capped by the smallest
        remaining budget so length-retirement never wastes lane steps
        (EOS remains unpredictable — mid-horizon EOS waste is measured
        by ``serving.wasted_lane_tokens``)."""
        if self._degrade_level >= 2:
            return 1                 # ladder level 2+: admit at every
                                     # boundary, shortest commit unit
        max_h = max(1, int(self.config.max_horizon))
        if requested is not None:
            return self._pow2_floor(min(max(1, int(requested)), max_h))
        if self.scheduler.queue_depth or self._chunking:
            # pending work at the boundary (queued requests, or prompts
            # mid-chunked-prefill): tightest interleave
            return 1
        rem = min(r.remaining_budget
                  for r in self.scheduler.running.values())
        return self._pow2_floor(max(1, min(max_h, self._grow, rem)))

    # ------------------------------------------------ structured decoding
    def _norm_grammar(self, grammar, sampling):
        """Validate and eagerly compile a request grammar; returns the
        ``GrammarSpec`` or None.  All failures surface HERE — at
        submit(), before anything queues — as ``GrammarError`` (for
        unsupported grammar features, naming them) or ``ValueError``
        (for engine-configuration problems)."""
        if grammar is None:
            return None
        spec = as_grammar_spec(grammar)
        if not self._structured:
            raise ValueError(
                "grammar-constrained request on an engine without "
                "structured generation (set "
                "EngineConfig.grammar_max_states > 0 and grammar_vocab)")
        if sampling.eos_token_id is None:
            raise ValueError(
                "grammar-constrained requests require "
                "sampling.eos_token_id: EOS is legal exactly in the "
                "grammar's accept states, so without one the lane "
                "could never legally stop")
        key = (spec.key, int(sampling.eos_token_id))
        if key in self._grammar_cache:
            # LRU touch: re-insertion order is eviction order for
            # retired (refcount-zero) entries in _trim_grammar_cache
            self._grammar_cache[key] = self._grammar_cache.pop(key)
            self._grammar_cache_hits += 1
        else:
            if self.config.grammar_vocab is None:
                raise ValueError(
                    "EngineConfig.grammar_vocab is required for "
                    "structured generation: the compiler walks every "
                    "vocab token's characters through the grammar")
            self._grammar_cache[key] = compile_grammar(
                spec, self.config.grammar_vocab,
                int(sampling.eos_token_id),
                vocab_size=self.model.config.vocab_size)
            self._grammar_cache_misses += 1
        return spec

    def _walk_grammar(self, dfa, tokens):
        """Advance the compiled ``TokenDFA`` through ``tokens`` from its
        start state; returns the final grammar-LOCAL state id.  The walk
        uses the cached TokenDFA, where REJECT is ``-1`` — NOT the slab,
        which stores REJECT as row 0 (the accept-all sentinel), so a
        slab walk over an illegal token would silently un-constrain the
        lane instead of surfacing it.  Raises ``ValueError`` naming the
        first illegal transition."""
        st = 0
        for i, t in enumerate(tokens):
            t = int(t)
            nxt = (int(dfa.next_state[st, t])
                   if 0 <= t < dfa.vocab_size else -1)
            if nxt < 0:
                raise ValueError(
                    f"token {t} at output position {i} is illegal "
                    f"under the request grammar (DFA state {st})")
            st = nxt
        return st

    def _dfa_admission_state(self, req):
        """The slab-global DFA state a (re-)admitted constrained lane
        samples its next token from: the grammar's start row advanced
        by every token already emitted EXCEPT the last — the prefill
        itself re-samples that one under the masked logits, the same
        bitwise boundary check the PRNG resume path performs.  Fresh
        admissions have no output yet and get the start row.

        The cache entry is pinned while the request holds its slab
        reference (see ``_trim_grammar_cache``), and an illegal token
        in the history is an invariant violation here — preempted
        lanes emitted under the mask, and cross-engine ``resume_ids``
        were validated at ``submit()``."""
        key = self._grammar_keys[req.request_id]
        try:
            st = self._walk_grammar(self._grammar_cache[key],
                                    req.output_ids[:-1])
        except ValueError as e:
            raise RuntimeError(
                f"request {req.request_id} diverged from its grammar "
                f"mid-admission — {e}") from None
        return self._grammar_slab.offset(key) + st

    def _release_grammar(self, req):
        """Drop a finished/aborted request's slab segment reference and
        park its lane back on the accept-all sentinel."""
        key = self._grammar_keys.pop(req.request_id, None)
        if key is not None:
            self._grammar_slab.release(key)
            self._trim_grammar_cache()
        if req.slot is not None:
            self._dfa_state[req.slot] = 0

    def _trim_grammar_cache(self):
        """Bound the host compile cache.  Entries whose slab segment is
        live are pinned — some request still references the grammar and
        admission walks its history through the cached TokenDFA — and
        retired entries survive as an LRU of ``grammar_cache_keep``, so
        repeat grammars stay a dict hit while a stream of unique
        gateway grammars cannot grow host memory without bound."""
        keep = max(0, int(self.config.grammar_cache_keep))
        retired = [k for k in self._grammar_cache
                   if not self._grammar_slab.installed(k)]
        for k in retired[:len(retired) - keep]:
            del self._grammar_cache[k]

    def _sync_grammar_tables(self):
        """Upload the grammar slab tables — only when an install or
        release dirtied them.  Loop-invariant within a dispatch, like
        the block tables."""
        if not self._structured or not self._grammar_slab.dirty:
            return
        self._d_dfa_next = jnp.asarray(self._grammar_slab.next)
        self._d_dfa_mask = jnp.asarray(self._grammar_slab.mask)
        self._d_dfa_forced = jnp.asarray(self._grammar_slab.forced)
        self._grammar_slab.dirty = False

    def _grammar_prefill_args(self, dfa):
        """The prefill dispatch's (dfa_state, dfa_mask) tail — Nones
        with the knob off, so the fp/unconstrained program is traced
        with empty pytrees exactly as before."""
        if not self._structured:
            return (None, None)
        self._sync_grammar_tables()
        return (jnp.asarray(dfa), self._d_dfa_mask)

    def _grammar_program_args(self):
        """The decode dispatch's grammar argument tail (dfa_state,
        dfa_next, dfa_mask, dfa_forced) for a representative program
        trace — used by the sharded engine's collective census so smoke
        traces stay in lockstep with real dispatches.  Nones when
        structured generation is off."""
        if not self._structured:
            return (None, None, None, None)
        self._sync_grammar_tables()
        return (jnp.zeros(self.config.num_slots, jnp.int32),
                self._d_dfa_next, self._d_dfa_mask, self._d_dfa_forced)

    # ------------------------------------------------------------ API
    def submit(self, prompt_ids, sampling=None, priority=0,
               deadline_s=None, tenant=None, resume_ids=None,
               grammar=None):
        """Queue one request; returns the Request handle (its
        ``output_ids`` fill in as the engine steps).

        The gateway-era admission fields are optional and inert for
        plain in-process callers: ``priority`` widens the scheduler's
        overtake budget (see ``Scheduler.overtake_cap``; a NEGATIVE
        priority is the offline batch lane — interactive traffic
        overtakes it without bound, shedding and preemption pick it
        first), ``deadline_s`` bounds queue wait — a request still
        QUEUED when the deadline passes is aborted at the next
        admission pass (``finish_reason="abort"``) — and ``tenant``
        tags the request for per-tenant accounting in
        ``stats()['tenants']``.

        ``resume_ids`` is the failover entry point: tokens this request
        already generated **on another engine** before its replica
        died.  The request queues as ``resumed`` and admission takes
        the preemption-resume path — re-prefill ``prompt + resume_ids``
        with ``counts = len(resume_ids) - 1``, so the boundary token is
        re-sampled and checked bitwise against ``resume_ids[-1]``
        (sampling is a pure function of ``fold_in(seed, n_generated)``,
        identical across replicas holding the same weights) — then
        decode continues the stream exactly where the dead replica left
        off.  Requires ``len(resume_ids) < max_new_tokens`` (a resume
        with nothing left to generate is the caller's to finish).

        ``grammar`` constrains the request's output: a regex string, a
        JSON-schema dict, or a prebuilt ``GrammarSpec``.  Validation
        and compilation happen HERE, eagerly — an unsupported grammar
        raises ``GrammarError`` (and the gateway maps it to a 400
        ``invalid_grammar``) before anything queues.  Requires
        ``grammar_max_states > 0``, ``grammar_vocab``, and a
        ``sampling.eos_token_id`` (EOS is legal exactly in the
        grammar's accept states; without it the lane could never
        legally stop).  Compiled token DFAs are cached per
        ``(grammar, eos)`` and installed into the slab refcounted, so
        repeat grammars cost a dict hit; slab exhaustion (more live
        grammar states than ``grammar_max_states``) raises
        ``RuntimeError`` here, before anything queues, and a grammar +
        ``resume_ids`` combination is refused (``ValueError``) when the
        resumed tokens don't walk the grammar legally."""
        if self._draining:
            raise RuntimeError("engine is draining; submissions refused")
        prompt_ids = [int(t) for t in np.asarray(prompt_ids).reshape(-1)]
        if not prompt_ids:
            raise ValueError("empty prompt")
        if deadline_s is not None and not float(deadline_s) > 0:
            raise ValueError(
                f"deadline_s must be > 0 or None, got {deadline_s}")
        sampling = sampling or SamplingParams()
        if len(prompt_ids) + sampling.max_new_tokens > self.config.max_seq_len:
            raise ValueError(
                f"prompt_len {len(prompt_ids)} + max_new_tokens "
                f"{sampling.max_new_tokens} exceeds max_seq_len "
                f"{self.config.max_seq_len}")
        resume_ids = ([int(t) for t in resume_ids]
                      if resume_ids else None)
        if resume_ids and len(resume_ids) >= sampling.max_new_tokens:
            raise ValueError(
                f"resume_ids already holds {len(resume_ids)} tokens, "
                f">= max_new_tokens {sampling.max_new_tokens}: nothing "
                "left to generate")
        grammar = self._norm_grammar(grammar, sampling)
        key = None
        if grammar is not None:
            key = (grammar.key, int(sampling.eos_token_id))
            if resume_ids:
                # cross-engine resume under a grammar: the dead replica
                # generated these under the same mask, so any illegal
                # transition means corrupt resume data — refused HERE,
                # eagerly, not silently un-constrained at admission
                try:
                    self._walk_grammar(self._grammar_cache[key],
                                       resume_ids)
                except ValueError as e:
                    raise ValueError(
                        f"resume_ids diverged from the request "
                        f"grammar: {e}") from None
            # install BEFORE the scheduler sees the request: slab
            # exhaustion is a documented, recoverable submit() error,
            # and raising it after queueing would strand a request
            # with req.grammar set but no _grammar_keys entry — the
            # next admission pass would then KeyError the step loop
            try:
                self._grammar_slab.install(key, self._grammar_cache[key])
            except Exception:
                # the freshly compiled entry is unpinned; trim so a
                # stream of refused grammars can't grow the cache
                self._trim_grammar_cache()
                raise
        try:
            req = self.scheduler.submit(prompt_ids, sampling,
                                        priority=priority,
                                        deadline_s=deadline_s,
                                        tenant=tenant, grammar=grammar)
        except BaseException:
            if key is not None:
                self._grammar_slab.release(key)
                self._trim_grammar_cache()
            raise
        if key is not None:
            self._grammar_keys[req.request_id] = key
        if resume_ids:
            # cross-engine resume: admission re-prefills this history
            # through the preemption path (resumed => queue-head anchor
            # exemption + the bitwise boundary-token check)
            req.output_ids = list(resume_ids)
            req.resumed = True
        t = self._tenants.setdefault(
            tenant if tenant is not None else "",
            {"submitted": 0, "finished": 0, "aborted": 0,
             "tokens_generated": 0})
        t["submitted"] += 1
        if self.recorder is not None:
            req.trace = _obs_tracing.RequestTrace(
                req.request_id, engine=self._profiler_name)
            gw = {}
            if req.priority:
                gw["priority"] = req.priority
            if req.deadline_s is not None:
                gw["deadline_s"] = req.deadline_s
            if req.tenant is not None:
                gw["tenant"] = req.tenant
            if resume_ids:
                gw["resumed_tokens"] = len(resume_ids)
            if grammar is not None:
                gw["grammar"] = grammar.kind
            req.trace.add(_obs_tracing.QUEUED,
                          prompt_len=req.prompt_len,
                          max_new_tokens=sampling.max_new_tokens, **gw)
            self.recorder.attach(req.trace)
        _SRV_QUEUE.set(self.scheduler.queue_depth,
                       engine=self._profiler_name)
        return req

    def admit(self):
        """Run admission + prefill for queued requests without decoding
        (step() calls this; exposed so latency-sensitive callers and
        benchmarks can separate prefill from the decode window).

        Admission pops co-bucketed batches (same suffix bucket after
        prefix matching, bounded reorder window) and prefills each batch
        in ONE compiled dispatch — N same-bucket admissible requests
        cost 1 prefill dispatch, not N.

        Block-pool capacity gates admission: a batch whose table
        entries don't fit first reclaims unpinned prefix blocks, and if
        the pool is still short the whole batch goes back to the queue
        front (order preserved) to retry after running requests retire.
        An oversubscribed pool therefore defers admission instead of
        failing mid-prefill."""
        with _span("engine.admit", requests=0) as sp:
            before = self._prefill_requests
            self._expire_deadlines()
            self._admit_deferred = False
            if self.scheduler.queue_depth:
                if self._degrade_level >= 3:
                    # ladder level 3: shed lowest-priority queued requests
                    # down to num_slots queued (resumed requests are never
                    # shed — their tokens are already streamed)
                    for req in self.scheduler.shed_victims(
                            self.cache.num_slots):
                        self._degrade_sheds += 1
                        _SRV_SHED.inc(engine=self._profiler_name)
                        self.abort(req, cause="shed")
                if self.faults is not None:
                    spec = self.faults.fire(SITE_ENGINE_ADMIT,
                                            scope=self._fault_scope)
                    if (spec is not None
                            and spec.kind == FAULT_POOL_EXHAUSTED):
                        # behave exactly like a dry pool: defer this whole
                        # admission pass to the next horizon boundary
                        self._admit_deferred = True
                        return
            # continuation chunks first: in-flight chunked prefills advance
            # one chunk per boundary ahead of new admissions (their blocks
            # are already partly written — finishing them frees capacity
            # soonest and keeps TTFT ordering honest)
            self._advance_chunks()
            # tiered KV: promote host-arena state for the requests this
            # admission pass could plausibly pop (the free slots plus the
            # reorder window it may look past), so their admission becomes
            # a prefix hit instead of a re-prefill
            if self.host_tier is not None and self.scheduler.queue_depth:
                window = self.cache.free_slots + self.config.reorder_window
                for req in list(self.scheduler.queue)[:window]:
                    if self._swap_in(req) is None:
                        # pool dry even after reclaim — a later request's
                        # swap-in can't fare better, and pressing on would
                        # only churn (each attempt's reclaim retry eats
                        # LRU radix blocks, possibly an earlier request's
                        # freshly grafted chain).  Host state is intact:
                        # swap-in consumes nothing before its device
                        # blocks are allocated.
                        break
            # live lanes never wait on the compile of a wider prefill
            live = self._prefill_buckets if self.scheduler.running else ()
            cap = max(live, default=(0,))[0]       # 0: every free lane
            while self.cache.free_slots and self.scheduler.queue_depth:
                batch = self.scheduler.pop_batch(
                    min(self.cache.free_slots, cap or self.cache.num_slots),
                    bucket_of=self._admission_bucket)
                if not batch:
                    break
                need = sum(self._blocks_needed(r) for r in batch)
                short = need - self.pool.free_blocks
                while short > 0 and self.prefix.reclaim(short):
                    # reclaim may have evicted unpinned blocks this very
                    # batch counted as prefix hits (promoted or cached
                    # chains are fair LRU victims until acquire pins them),
                    # so re-derive the need against the post-reclaim radix
                    # and keep reclaiming until it stabilizes — each pass
                    # either closes the gap or strictly shrinks the set of
                    # unpinned blocks, so this terminates
                    need = sum(self._blocks_needed(r) for r in batch)
                    short = need - self.pool.free_blocks
                if short > 0:
                    self.scheduler.queue.extendleft(reversed(batch))
                    if self.scheduler.running:
                        break            # retry after retirements free blocks
                    # nothing running to wait for: admit the longest
                    # queue-head prefix of the batch that fits (same bucket,
                    # so it still prefills as one dispatch)
                    fit, free = [], self.pool.free_blocks
                    for r in batch:
                        nb = self._blocks_needed(r)
                        if nb > free:
                            break
                        free -= nb
                        fit.append(r)
                    if not fit:
                        raise RuntimeError(
                            f"KV pool too small: the queue head alone needs "
                            f"{self._blocks_needed(batch[0])} blocks, pool "
                            f"has {self.pool.free_blocks} free and nothing "
                            "is running to retire (raise kv_pool_blocks or "
                            "free the prefix budget)")
                    for _ in fit:
                        self.scheduler.queue.popleft()
                    batch = fit
                self._prefill_batch(batch)
                sp.args["requests"] = self._prefill_requests - before

    _admit = admit      # pre-horizon internal name, kept for callers

    def _expire_deadlines(self):
        """Abort every still-QUEUED request whose admission deadline
        passed (the gateway's deadline enforcement point: deadlines
        bound *queue wait*, so a request that already claimed a slot
        runs to completion).  Runs at the top of every admission pass;
        expired requests finish with ``finish_reason="abort"`` and are
        counted in both ``serving.requests_aborted`` and
        ``serving.deadline_expired``."""
        expired = [r for r in self.scheduler.queue if r.deadline_expired]
        for req in expired:
            self._deadline_expired += 1
            _SRV_DEADLINE.inc(engine=self._profiler_name)
            self.abort(req, cause="deadline")

    def _prefill_batch(self, batch):
        """One compiled prefill dispatch for a co-bucketed admission
        batch: allocate slots, lease cached prefix blocks straight into
        the block tables, allocate private blocks for the rest, COW +
        suffix-prefill every lane, adopt the new blocks into the radix
        store (refcounting only), then harvest first tokens and arm the
        decode state.

        With chunked prefill on, a lane whose suffix exceeds the batch
        bucket dispatches only its FIRST chunk here; the rest of its
        prompt continues one chunk per step boundary in
        :meth:`_advance_chunks`, and its first token is sampled by the
        final chunk."""
        n = len(batch)
        bucket = max(self._admission_bucket(r) for r in batch)
        lanes = self._lane_bucket(n)
        bs = self._block_size
        entries = []
        admit_events = []            # per-request trace args, for cost
        for req in batch:
            slot = self.cache.alloc()
            was_resumed = req.resumed
            self.scheduler.start(req, slot)
            _SRV_QUEUE_WAIT.observe(req.queue_seconds,
                                    engine=self._profiler_name)
            toks = self._admission_tokens(req)
            lease = self.prefix.acquire(toks)
            self._leases[req.request_id] = lease
            req.prefix_hit_tokens = lease.matched_tokens
            start = lease.matched_tokens
            take = len(toks) - start
            if self._chunk_tokens:
                take = min(take, bucket)
            cover = start + take
            # table row: leased full-match blocks first (copy-free,
            # shared), then private blocks out to the last covered token
            # (the COW tail copy, if any, lands in the first private one)
            full = len(lease.block_ids)
            for j, bid in enumerate(lease.block_ids):
                self.cache.lease_block(slot, j, bid)
            for j in range(full, -(-cover // bs)):
                if self.cache.alloc_entry(slot, j) is None:
                    # the pre-check's reclaim (or a batch-mate's
                    # acquire) may have evicted unpinned blocks this
                    # lane's lookup counted as hits — every lease taken
                    # so far is pinned, so reclaiming here only drops
                    # blocks nobody in this batch holds yet
                    if (not self.prefix.reclaim(1)
                            or self.cache.alloc_entry(slot, j) is None):
                        raise RuntimeError(
                            "KV pool exhausted mid-admission — "
                            "admit()'s capacity pre-check diverged "
                            "from the blocks actually allocated")
            cow = None
            if lease.tail_tokens:
                cow = (lease.tail_block,
                       self.cache.tables[slot, len(lease.block_ids)])
                self._cow_copies += 1
            entries.append(dict(req=req, slot=slot, lease=lease,
                                toks=toks, start=start, take=take,
                                final=cover == len(toks), cow=cow))
            _obs_events.instant("serving.slot_alloc", cat="serving",
                                slot=slot, request=req.request_id,
                                prompt_len=req.prompt_len, bucket=bucket,
                                prefix_hit=lease.matched_tokens)
            if req.trace is not None:
                # keep the event's args dict: the prefill program card
                # isn't known until the dispatch below, so its cost
                # share is patched in afterwards
                admit_events.append(req.trace.add(
                    _obs_tracing.RESUME if (req.output_ids or was_resumed)
                    else _obs_tracing.PREFILL,
                    slot=slot, bucket=bucket,
                    prefill_tokens=len(toks),
                    prefix_hit_tokens=lease.matched_tokens))
            else:
                admit_events.append(None)
            if not req.output_ids and not was_resumed:
                # async span: a request's life overlaps other requests
                # on this thread, so it pairs by id, not by B/E nesting
                # (a preempted request's span is already open)
                _obs_events.record(
                    "serving.request", phase=_obs_events.ASYNC_BEGIN,
                    cat="serving", id=req.request_id,
                    args={"slot": slot, "prompt_len": req.prompt_len,
                          "prefix_hit_tokens": lease.matched_tokens})

        first_np, dfa = self._dispatch_prefill(entries, bucket, lanes)
        with _span("engine.prefill.harvest"):
            name = self._profiler_name
            self._prefill_requests += n
            _SRV_PREFILL_REQS.inc(n, engine=name)
            _SRV_PREFILL_BATCH.observe(n, engine=name)

            # cost attribution: the dispatch's program-card totals split
            # evenly over the n REAL requests (padding lanes ride free but
            # their work is part of serving these n), so per-request shares
            # sum back to the engine's _program_* totals exactly
            card = self._prefill.last_card
            if card is not None:
                for ev in admit_events:
                    if ev is not None:
                        if card.flops is not None:
                            ev["flops_est"] = card.flops / n
                        if card.bytes_accessed is not None:
                            ev["bytes_est"] = card.bytes_accessed / n

            # cache the new full blocks of every admitted prompt (chunked
            # lanes: the blocks their first chunk just completed): the radix
            # store takes shared references on the slot's freshly written
            # private blocks — pure host-side refcounting, no data motion
            for e in entries:
                row = self.cache.tables[e["slot"]]
                self.prefix.adopt(e["toks"][:e["start"] + e["take"]],
                                  e["lease"],
                                  block_of=lambda j, row=row: row[j])

            for i, e in enumerate(entries):
                req, lease, slot = e["req"], e["lease"], e["slot"]
                hit = lease.matched_tokens
                self._prefix_hit_tokens += hit
                self._prompt_tokens += len(e["toks"])
                if hit:
                    _SRV_PREFIX_HIT.inc(hit, engine=name)
                if not e["final"]:
                    # chunked admission: first chunk written, no token
                    # sampled yet — register the continuation ledger and
                    # leave the lane decode-inactive
                    cover = e["start"] + e["take"]
                    self._chunked_requests += 1
                    self._chunk_count_total += 1
                    self._chunking[req.request_id] = _ChunkProgress(
                        req, slot, lease, e["toks"], cover, chunks=1)
                    self._pos[slot] = cover
                    self._active[slot] = False
                    self._state_dirty = True
                    self._context_high_water = max(
                        self._context_high_water, cover)
                    continue
                self._finish_prefill_lane(req, slot, e["toks"],
                                          int(first_np[i]), int(dfa[i]))

    def _dispatch_prefill(self, entries, bucket, lanes):
        """Build the lane arrays for a prefill dispatch (admission
        batches and chunk continuations share this) and run the ONE
        compiled call.  Returns ``(first_np, dfa)`` — the sampled
        first-token array after the host sync, and the per-lane DFA
        admission states the dispatch ran with (callers advance the
        armed lanes' mirrors through them)."""
        with _span("engine.prefill.build", bucket=bucket, lanes=lanes):
            # lane arrays: real requests first, then padding lanes whose
            # all-zero table rows route every write to scratch block 0
            ids = np.zeros((lanes, bucket), np.int32)
            lengths = np.ones(lanes, np.int32)
            prefix_lens = np.zeros(lanes, np.int32)
            tables = np.zeros((lanes, self._max_blocks), np.int32)
            cow_src = np.zeros(lanes, np.int32)
            cow_dst = np.zeros(lanes, np.int32)
            counts = np.zeros(lanes, np.int32)
            seeds = np.zeros(lanes, np.uint32)
            temps = np.zeros(lanes, np.float32)
            top_ks = np.zeros(lanes, np.int32)
            top_ps = np.ones(lanes, np.float32)
            # per-lane DFA admission states; 0 (accept-all sentinel) for
            # free, padding, and non-final chunk lanes (whose sampled token
            # is discarded)
            dfa = np.zeros(lanes, np.int32)
            for i, e in enumerate(entries):
                req = e["req"]
                if e["final"] and req.grammar is not None:
                    dfa[i] = self._dfa_admission_state(req)
                window = e["toks"][e["start"]:e["start"] + e["take"]]
                ids[i, :len(window)] = window
                lengths[i] = len(window)
                prefix_lens[i] = e["start"]
                tables[i] = self.cache.tables[e["slot"]]
                if e["cow"] is not None:
                    cow_src[i], cow_dst[i] = e["cow"]
                if e["final"]:
                    counts[i] = max(0, req.n_generated - 1)
                s = req.sampling
                seeds[i] = np.uint32(s.seed)
                temps[i] = s.temperature
                top_ks[i] = s.top_k
                top_ps[i] = s.top_p
            call = (self._state_arrays, jnp.asarray(ids),
                    jnp.asarray(lengths), jnp.asarray(prefix_lens),
                    jnp.asarray(tables), jnp.asarray(cow_src),
                    jnp.asarray(cow_dst), jnp.asarray(counts),
                    self.pool.k, self.pool.v,
                    self.pool.k_scale, self.pool.v_scale,
                    jnp.asarray(seeds), jnp.asarray(temps),
                    jnp.asarray(top_ks), jnp.asarray(top_ps),
                    *self._grammar_prefill_args(dfa))

        miss0 = self._prefill.misses
        t0 = time.perf_counter()
        with _span("engine.prefill.enqueue", bucket=bucket, lanes=lanes,
                   requests=len(entries)):
            first, new_k, new_v, new_ks, new_vs, stats = \
                self._prefill(*call)
            self.pool.rebind(new_k, new_v, new_ks, new_vs)
        if self.while_in_flight is not None:
            self.while_in_flight()       # the driver's turn: device busy
        with _span("engine.prefill.wait"):
            first_np = np.asarray(first)     # the one prefill host sync
        if stats is not None:
            self._count_layer_stats(np.asarray(stats)[None], "prefill")
        if self._prefill.misses == miss0:
            # feeds the "auto" swap-vs-recompute policy (not when compiling)
            self._prefill_dispatch_s += time.perf_counter() - t0
            self._prefill_tokens_dispatched += int(
                lengths[:len(entries)].sum())
        self._prefill_calls += 1
        self._prefill_buckets.add((lanes, bucket))
        _SRV_PREFILL.inc(engine=self._profiler_name)
        card = self._prefill.last_card
        if card is not None:
            self._program_flops += card.flops or 0.0
            self._program_bytes += card.bytes_accessed or 0.0
            # per-token prefill FLOPs (over the program's padded token
            # grid) — the unit kv_swaps_averted_flops bills in
            self._prefill_card_flops += card.flops or 0.0
            self._prefill_card_tokens += lanes * bucket
        return first_np, dfa

    def _finish_prefill_lane(self, req, slot, toks, tok, dfa_i):
        """Arm one lane whose prefill just completed — whole-prompt, or
        the final chunk of a chunked one: verify/record the sampled
        first token and bring the lane's decode mirrors live."""
        name = self._profiler_name
        if req.output_ids:
            # preemption swap-in: the prefill re-sampled the token
            # that was in flight when the request was swapped out —
            # fold_in(seed, n-1) must reproduce it bitwise
            if tok != req.output_ids[-1]:
                raise RuntimeError(
                    f"preemption resume diverged for request "
                    f"{req.request_id}: re-prefill sampled {tok}, "
                    f"expected {req.output_ids[-1]}")
        else:
            self._tokens_generated += 1
            _SRV_TOKENS.inc(engine=name)
            done = req.record_token(tok)
            if req.trace is not None:
                req.trace.add(_obs_tracing.FIRST_TOKEN, token=tok,
                              ttft_s=round(req.ttft, 6))
            if done:
                self._retire(req)
                return
        s = req.sampling
        self._tokens[slot] = tok
        self._pos[slot] = len(toks)
        self._context_high_water = max(self._context_high_water,
                                       len(toks))
        # the drafter's corpus: prompt (plus regenerated tokens on a
        # preemption resume) followed by the first sampled token —
        # the tail past the valid length is never matched, but zero
        # it so a reused slot carries nothing of its previous tenant
        self._hist[slot, :len(toks)] = toks
        self._hist[slot, len(toks)] = tok
        self._hist[slot, len(toks) + 1:] = 0
        self._spec_ema[slot] = 1.0   # optimistic: draft until shown
        self._spec_gates[slot] = True  # not to pay off
        # the lane's DFA state AFTER the prefill-sampled token: the
        # admission state advanced one transition (sentinel row 0
        # self-loops, so free lanes stay at 0)
        self._dfa_state[slot] = (
            int(self._grammar_slab.next[dfa_i, tok])
            if req.grammar is not None else 0)
        self._seeds[slot] = np.uint32(s.seed)
        self._counts[slot] = req.n_generated
        self._temps[slot] = s.temperature
        self._top_ks[slot] = s.top_k
        self._top_ps[slot] = s.top_p
        self._eos_ids[slot] = -1 if s.eos_token_id is None \
            else int(s.eos_token_id)
        self._limits[slot] = s.max_new_tokens
        self._active[slot] = True
        self._state_dirty = True     # admission is the ONLY host
        # write into device-resident state; retirement is detected
        # inside the scan, so it needs no re-upload

    def _advance_chunks(self):
        """Dispatch one continuation chunk for every in-flight chunked
        prefill — called at each step boundary, BEFORE admission, so a
        decode horizon runs between consecutive chunks of the same
        prompt (the interleave policy; the per-boundary prefill budget
        is one chunk-bucket program).  Each lane's block table grows to
        cover its next chunk first (reclaiming prefix blocks, then
        preempting the lowest-priority/youngest other running request
        under pool pressure — the `_ensure_blocks` ladder); all pending
        lanes then ride ONE compiled dispatch at the chunk bucket.
        Completed full blocks are adopted into the radix store at every
        boundary, so mid-prefill preemption resumes from the chunk
        boundary as an ordinary prefix hit.  A lane's final chunk
        samples its first token and arms decode."""
        if not self._chunking:
            return
        decode_live = any(bool(self._active[s])
                          for s in self.scheduler.running)
        entries = []
        for prog in list(self._chunking.values()):
            req, slot = prog.req, prog.slot
            if self.scheduler.running.get(slot) is not req:
                continue             # preempted/aborted meanwhile
            remaining = len(prog.toks) - prog.covered
            take = min(remaining, self._chunk_tokens)
            preempted_self = False
            while not self.cache.ensure_blocks(slot,
                                               prog.covered + take):
                if self.prefix.reclaim(1):
                    continue
                victim = max(
                    (r for r in self.scheduler.running.values()
                     if r is not req),
                    key=lambda r: (-r.priority, r.request_id),
                    default=None)
                if victim is None:
                    raise RuntimeError(
                        f"KV pool exhausted: chunked prefill for "
                        f"request {req.request_id} needs blocks and "
                        "there is nothing left to reclaim or preempt "
                        "(raise kv_pool_blocks)")
                self.preempt(victim)
                if self.scheduler.running.get(slot) is not req:
                    preempted_self = True
                    break
            if preempted_self:
                continue
            entries.append(dict(req=req, slot=slot, lease=prog.lease,
                                toks=prog.toks, start=prog.covered,
                                take=take, final=take == remaining,
                                cow=None, prog=prog))
        # a later lane's pressure loop may have preempted an earlier
        # lane in `entries` — its blocks are gone, drop the entry
        entries = [e for e in entries
                   if self.scheduler.running.get(e["slot"]) is e["req"]]
        if not entries:
            return
        lanes = self._lane_bucket(len(entries))
        t0 = time.perf_counter()
        first_np, dfa = self._dispatch_prefill(entries,
                                               self._chunk_tokens, lanes)
        dt = time.perf_counter() - t0
        with _span("engine.prefill.harvest"):
            name = self._profiler_name
            self._chunk_dispatches += 1
            self._chunk_count_total += len(entries)
            if decode_live:
                # decode lanes were live: this boundary's horizon was
                # delayed by exactly this dispatch
                self._prefill_interference_s += dt
                _SRV_PREFILL_INTERFERE.inc(dt, engine=name)
            for i, e in enumerate(entries):
                req, lease, slot = e["req"], e["lease"], e["slot"]
                prog = e["prog"]
                cover = e["start"] + e["take"]
                row = self.cache.tables[slot]
                self.prefix.adopt(e["toks"][:cover], lease,
                                  block_of=lambda j, row=row: row[j])
                prog.covered = cover
                prog.chunks += 1
                self._context_high_water = max(self._context_high_water,
                                               cover)
                _obs_events.instant("serving.prefill_chunk", cat="serving",
                                    slot=slot, request=req.request_id,
                                    chunk=prog.chunks, covered=cover,
                                    total=len(prog.toks))
                if e["final"]:
                    del self._chunking[req.request_id]
                    _SRV_PREFILL_CHUNKS.observe(prog.chunks, engine=name)
                    if req.trace is not None:
                        req.trace.add("prefill_chunked",
                                      chunks=prog.chunks,
                                      prefill_tokens=len(prog.toks))
                    self._finish_prefill_lane(req, slot, e["toks"],
                                              int(first_np[i]), int(dfa[i]))
                else:
                    self._pos[slot] = cover
                    self._state_dirty = True

    def _retire(self, req):
        # release every table entry: private blocks return to the pool
        # (block-leak invariant: leased_blocks == 0 once all requests
        # retire), blocks the radix store adopted live on under its
        # references, and the zeroed row routes any still-masked lane
        # writes to scratch
        if self._structured:
            self._release_grammar(req)
        if self.host_tier is not None:
            # an unconsumed lane image is dead weight once the request
            # retires — free its pinned host blocks
            self.host_tier.drop_lane(req.request_id)
        self.cache.release_slot_blocks(req.slot)
        self.cache.free(req.slot)
        self.scheduler.finish(req)
        lease = self._leases.pop(req.request_id, None)
        if lease is not None:
            self.prefix.release(lease)   # blocks become evictable again
        self._finished += 1
        self._ttft_sum += req.ttft
        self._ttft_n += 1
        tn = self._tenants.get(req.tenant if req.tenant is not None
                               else "")
        if tn is not None:
            tn["finished"] += 1
            tn["tokens_generated"] += req.n_generated
        _SRV_REQS.inc(engine=self._profiler_name)
        _SRV_TTFT.observe(req.ttft, engine=self._profiler_name)
        _obs_events.instant("serving.slot_retire", cat="serving",
                            slot=req.slot, request=req.request_id,
                            reason=req.finish_reason,
                            n_generated=req.n_generated)
        _obs_events.record(
            "serving.request", phase=_obs_events.ASYNC_END,
            cat="serving", id=req.request_id,
            args={"reason": req.finish_reason,
                  "n_generated": req.n_generated,
                  "ttft_s": round(req.ttft, 6)})
        if req.trace is not None:
            req.trace.add(_obs_tracing.FINISH, reason=req.finish_reason,
                          n_generated=req.n_generated,
                          ttft_s=round(req.ttft, 6))
            self.recorder.finish(req.trace)
        if self.slo is not None:
            self.slo.observe("ttft", req.ttft)
            if req.n_generated > 1:
                self.slo.observe(
                    "tpot", (time.time() - req.first_token_time)
                    / (req.n_generated - 1))
            self.slo.observe("abort", 0.0)
        # the freed lane keeps its frozen state (matching the device
        # copy, which masked it inside the scan); the mirror only drops
        # the active bit — no re-upload, no parking
        self._active[req.slot] = False

    def preempt(self, req):
        """Swap a RUNNING request out: release its slot, table entries,
        and prefix lease, and requeue it at the queue front with its
        generated tokens intact.  Re-admission re-prefills prompt +
        generated-so-far and the fold_in PRNG reproduces its next token
        bitwise, so the output stream is unaffected.  Called by the
        engine under KV block pressure; also public for schedulers that
        want to swap idle sequences explicitly."""
        from .scheduler import RUNNING

        if req.status != RUNNING:
            raise ValueError(
                f"cannot preempt request {req.request_id}: {req.status}")
        slot = req.slot
        # tiered KV: save the lane's block chain into the host arena
        # BEFORE the pool references drop (the device bytes must still
        # be live to device_get); re-admission swaps it back in
        self._swap_out_lane(req, slot)
        # mid-chunked-prefill: drop the continuation ledger — the chunks
        # already adopted into the radix store survive (refcounted), so
        # re-admission resumes from the last chunk boundary as an
        # ordinary prefix hit
        self._chunking.pop(req.request_id, None)
        self.cache.release_slot_blocks(slot)
        lease = self._leases.pop(req.request_id, None)
        if lease is not None:
            self.prefix.release(lease)
        self._active[slot] = False
        # the vacated lane rides the accept-all sentinel; the request
        # KEEPS its slab segment reference (it is still live and will
        # re-admit), so its grammar tables stay installed
        self._dfa_state[slot] = 0
        self._state_dirty = True
        self.scheduler.requeue_front(req)
        self.cache.free(slot)
        self._preemptions += 1
        _SRV_PREEMPTIONS.inc(engine=self._profiler_name)
        _obs_events.instant("serving.preempt", cat="serving", slot=slot,
                            request=req.request_id,
                            n_generated=req.n_generated)
        if req.trace is not None:
            req.trace.add(_obs_tracing.PREEMPT, slot=slot,
                          n_generated=req.n_generated)

    def abort(self, req, cause=None):
        """Cancel a request: a QUEUED one leaves the queue, a RUNNING
        one releases its slot, table entries, and prefix lease (the
        preemption teardown) without requeueing.  The request finishes
        with ``finish_reason="abort"`` and keeps whatever tokens it had
        generated; aborts feed the ``abort`` SLO objective and the
        flight record ends with an ``abort`` event.  ``cause`` (e.g.
        ``"deadline"``, ``"drain"``, ``"client_disconnect"``) is
        recorded on the trace event and the process event ring; the
        caller-facing ``finish_reason`` stays ``"abort"``."""
        from .scheduler import FINISHED, FINISH_ABORT, RUNNING, WAITING

        if req.status == FINISHED:
            raise ValueError(
                f"cannot abort request {req.request_id}: already "
                f"finished ({req.finish_reason})")
        if self.host_tier is not None:
            self.host_tier.drop_lane(req.request_id)
        if req.status == WAITING:
            try:
                self.scheduler.queue.remove(req)
            except ValueError:
                raise ValueError(
                    f"cannot abort request {req.request_id}: waiting "
                    "but not queued on this engine") from None
            req.status = FINISHED
            if self._structured:
                self._release_grammar(req)
        else:
            assert req.status == RUNNING
            slot = req.slot
            self._chunking.pop(req.request_id, None)
            if self._structured:
                self._release_grammar(req)
            self.cache.release_slot_blocks(slot)
            lease = self._leases.pop(req.request_id, None)
            if lease is not None:
                self.prefix.release(lease)
            self._active[slot] = False
            self._state_dirty = True
            self.scheduler.finish(req)
            self.cache.free(slot)
        req.finish_reason = FINISH_ABORT
        self._aborted += 1
        tn = self._tenants.get(req.tenant if req.tenant is not None
                               else "")
        if tn is not None:
            tn["aborted"] += 1
            tn["tokens_generated"] += req.n_generated
        name = self._profiler_name
        _SRV_ABORTS.inc(engine=name)
        _SRV_QUEUE.set(self.scheduler.queue_depth, engine=name)
        if req.admit_time is not None:
            # only requests that prefilled opened an async span
            _obs_events.record(
                "serving.request", phase=_obs_events.ASYNC_END,
                cat="serving", id=req.request_id,
                args={"reason": FINISH_ABORT, "cause": cause,
                      "n_generated": req.n_generated})
        if req.trace is not None:
            extra = {} if cause is None else {"cause": cause}
            req.trace.add(_obs_tracing.ABORT,
                          n_generated=req.n_generated, **extra)
            self.recorder.finish(req.trace)
        if self.slo is not None:
            self.slo.observe("abort", 1.0)
        return req

    def _ensure_blocks(self, h, w=1):
        """Extend every running slot's block table to cover its next
        ``h * w`` write positions — ``w = K+1`` when drafting, so a
        fully-accepted horizon's tail-block overflow spills into table
        entries that already exist when the compiled program scatters
        through them (lazy allocation: rows only hold blocks they have
        reached).  Under pool pressure: reclaim unpinned prefix blocks
        first, then preempt the LOWEST-PRIORITY other running request
        (the offline batch lane, priority < 0, is the designated
        preemption fodder), youngest within a priority (most recently
        submitted — it has the least sunk decode work and re-prefills
        cheapest), until the allocation fits.  Runs BEFORE the step()
        harvest snapshot, so a preempted lane is never mistaken for a
        mid-horizon retirement."""
        for slot, req in sorted(self.scheduler.running.items()):
            if self.scheduler.running.get(slot) is not req:
                continue                 # preempted earlier in this loop
            if not self._active[slot]:
                continue                 # mid-chunked-prefill lane: its
                                         # table grows chunk-wise in
                                         # _advance_chunks, not by decode
            need = min(int(self._pos[slot]) + h * w,
                       self.config.max_seq_len)
            while not self.cache.ensure_blocks(slot, need):
                if self.prefix.reclaim(1):
                    continue
                victim = max(
                    (r for r in self.scheduler.running.values()
                     if r is not req),
                    key=lambda r: (-r.priority, r.request_id),
                    default=None)
                if victim is None:
                    raise RuntimeError(
                        f"KV pool exhausted: slot {slot} needs blocks "
                        "for its decode window and there is nothing "
                        "left to reclaim or preempt (raise "
                        "kv_pool_blocks)")
                self.preempt(victim)

    # ---------------------------------------------------------- tiered KV
    def _upload_fn(self, pool_k, pool_v, pool_ks, pool_vs, ids,
                   kd, vd, ksd, vsd):
        """Swap-in upload program: scatter ``n`` whole host blocks into
        the pool arrays at freshly allocated ``ids``.  ``kd``/``vd``
        are ``[n, num_layers, block_size, kv_heads, head_dim]`` at the
        pool's storage dtype; scale planes ride beside them on
        quantized pools (``None`` placeholders otherwise, keeping the
        fp program structurally scale-free).  Pure byte movement — the
        uploaded bytes ARE the bytes the pool once held, which is what
        makes a swap-in bitwise-indistinguishable from recompute."""
        new_k, new_v, new_ks, new_vs = [], [], [], []
        for l in range(len(pool_k)):
            new_k.append(pool_k[l].at[ids].set(kd[:, l]))
            new_v.append(pool_v[l] if pool_v[l] is None
                         else pool_v[l].at[ids].set(vd[:, l]))
            if ksd is not None:
                new_ks.append(pool_ks[l].at[ids].set(ksd[:, l]))
                new_vs.append(pool_vs[l].at[ids].set(vsd[:, l]))
        if ksd is None:
            new_ks, new_vs = pool_ks, pool_vs
        return new_k, new_v, new_ks, new_vs

    def _place_pool(self):
        """Re-place the pool arrays after a swap upload rebinds them.
        No-op here; MeshEngine overrides to restore the head-sharded
        placement before the next dispatch."""

    def _host_arena_bytes(self):
        """Pinned host-RAM footprint of the spill arena (the ledger's
        host-component accounting: the payload arrays are allocated in
        full at construction, so this is constant while the tier
        lives)."""
        t = self.host_tier
        if t is None:
            return 0
        total = t.k.nbytes + t.v.nbytes
        if t.quantized:
            total += t.k_scale.nbytes + t.v_scale.nbytes
        return total

    def _fetch_blocks(self, bids):
        """Host copies of device pool blocks: ``[n, L, bs, kvh, hd]``
        k/v plus ``[n, L, bs]`` scale planes (None on fp pools).  One
        gather + device_get per layer array; on a sharded pool the get
        assembles the full block across shards (pure byte movement —
        re-sharding on the way back up is the upload's problem)."""
        idx = jnp.asarray(np.asarray(bids, np.int32))
        L = len(self.pool.k)
        k = np.stack([np.asarray(jax.device_get(self.pool.k[l][idx]))
                      for l in range(L)], axis=1)
        if self.pool.v[0] is None:       # latent pool: zero-width values
            v = np.zeros(k.shape[:3] + (0,), k.dtype)
        else:
            v = np.stack([np.asarray(jax.device_get(self.pool.v[l][idx]))
                          for l in range(L)], axis=1)
        if not self._kv_quant:
            return k, v, None, None
        ks = np.stack(
            [np.asarray(jax.device_get(self.pool.k_scale[l][idx]))
             for l in range(L)], axis=1)
        vs = np.stack(
            [np.asarray(jax.device_get(self.pool.v_scale[l][idx]))
             for l in range(L)], axis=1)
        return k, v, ks, vs

    def _demote_block(self, path, block_id):
        """``PrefixCache.spill`` hook: device_get one evicted radix
        block into the host arena (called BEFORE the pool block is
        released, while its bytes are still live).  True means the
        arena kept it — the eviction is a demotion, not a loss."""
        return self._demote_blocks([path], [block_id])[0]

    def _demote_blocks(self, paths, bids):
        """``PrefixCache.spill_batch`` hook: demote a whole eviction
        pass's victims with ONE batched gather + device_get (called
        BEFORE the pool blocks are released, while their bytes are
        still live).  Bulk reclaims — admission evicting many blocks
        to fit a batch — would otherwise serialize one synchronous
        device round-trip per victim on the admission hot path; this
        bounds the copy cost per reclaim pass instead.  One bool per
        block: True means the arena kept it."""
        tier = self.host_tier
        if tier is None or not tier.capacity:
            return [False] * len(bids)
        k, v, ks, vs = self._fetch_blocks(bids)
        name = self._profiler_name
        nbytes = self.pool.bytes_per_block
        out = []
        for i, path in enumerate(paths):
            ok = tier.store_prefix(path, k[i], v[i],
                                   None if ks is None else ks[i],
                                   None if vs is None else vs[i])
            if ok:
                self._demote_bytes += nbytes
                _SRV_SWAP_OUT_BYTES.inc(nbytes, engine=name,
                                        kind="demote")
            out.append(ok)
        return out

    def _swap_worthwhile(self, n_blocks, n_tokens):
        """The swap-vs-recompute crossover model: estimated upload
        seconds (bytes / measured host<->device bandwidth) vs estimated
        re-prefill seconds (this engine's own measured per-token
        prefill throughput).  ``always``/``never`` pin the decision;
        ``auto`` with no throughput sample yet swaps optimistically
        (the first measurement lands with the first prefill)."""
        if self._swap_policy == "always":
            return True
        if self._swap_policy == "never":
            return False
        if n_blocks <= 0 or n_tokens <= 0:
            return False
        if not self._prefill_tokens_dispatched:
            return True
        recompute_s = (self._prefill_dispatch_s
                       / self._prefill_tokens_dispatched) * n_tokens
        bw = _obs_memory.host_device_bandwidth_gbs(
            _obs_peaks.current_device_kind())
        upload_s = n_blocks * self.pool.bytes_per_block / (bw * 1e9)
        return upload_s < recompute_s

    def _swap_out_lane(self, req, slot):
        """Tiered KV at preempt: save the lane's whole block chain into
        the host arena BEFORE its pool blocks are released, so
        re-admission can swap it back in instead of re-prefilling.
        Skipped for mid-chunked-prefill lanes (their completed chunks
        already live in the radix store and resume as a prefix hit) and
        when the policy prefers recompute; ``save_lane`` failing (arena
        full of pinned images) silently falls back to recompute."""
        tier = self.host_tier
        if tier is None or not tier.capacity:
            return False
        if req.request_id in self._chunking or not self._active[slot]:
            return False
        pos = int(self._pos[slot])
        bs = self._block_size
        nb = -(-pos // bs)
        if pos <= 0 or not self._swap_worthwhile(nb, pos):
            return False
        row = self.cache.tables[slot]
        bids = [int(row[j]) for j in range(nb)]
        if any(b == 0 for b in bids):
            return False             # defensive: chain has a hole
        k, v, ks, vs = self._fetch_blocks(bids)
        blocks = [(k[i], v[i],
                   None if ks is None else ks[i],
                   None if vs is None else vs[i]) for i in range(nb)]
        if not tier.save_lane(req.request_id, pos, blocks):
            return False
        nbytes = nb * self.pool.bytes_per_block
        self._swap_outs += 1
        self._swap_out_blocks += nb
        self._swap_out_bytes += nbytes
        name = self._profiler_name
        _SRV_SWAP_OUT_BYTES.inc(nbytes, engine=name, kind="lane")
        _obs_events.instant("serving.swap_out", cat="serving",
                            slot=slot, request=req.request_id,
                            blocks=nb, bytes=nbytes, n_tokens=pos)
        if req.trace is not None:
            req.trace.add(_obs_tracing.SWAP_OUT, blocks=nb,
                          bytes=nbytes, n_tokens=pos)
        return True

    def _swap_in(self, req):
        """Promote a QUEUED request's host-arena KV into the device
        radix store so the coming admission serves it as an ordinary
        prefix hit — no new prefill plumbing, and sharded parity is
        automatic because promotion is pure byte movement feeding the
        already-parity-gated prefill path.

        A lane image (preempt swap-out) restores the full chain
        including the partial tail block, grafted under its SHORT token
        key that only copy-on-write matching can hit — so the resume
        prefill still computes >= 1 suffix token and the engine's
        bitwise resume-divergence check stays the parity gate.  Without
        an image, demoted prefix blocks extending the device-side radix
        match are promoted instead.  Any failure (policy says
        recompute, pool dry, graft refused) degrades to recompute —
        never an error.  Returns True on a landed swap-in, None when
        the pool was dry (the admission promotion loop stops on that —
        no host state is consumed before device blocks are secured),
        False otherwise."""
        tier = self.host_tier
        if tier is None:
            return False
        toks = self._admission_tokens(req)
        bs = self._block_size
        before = self.prefix.lookup(toks)
        chain = self.prefix._walk(toks, len(toks) - 1)
        have = len(chain)
        # pin the matched parent chain for the duration of the swap-in:
        # the pool.alloc() reclaim fallback below evicts LRU unpinned
        # radix blocks, and eating this request's own parents would
        # break every graft ("promotions must land in path order") —
        # under pool pressure that turns swap-in into pure churn
        for n in chain:
            n.refcount += 1
        try:
            return self._swap_in_pinned(req, tier, toks, bs, before,
                                        have)
        finally:
            for n in chain:
                if n.refcount > 0:
                    n.refcount -= 1

    def _swap_in_pinned(self, req, tier, toks, bs, before, have):
        img = tier.peek_lane(req.request_id)
        lane = img is not None and img.n_tokens == len(toks)
        if img is not None and not lane:
            tier.drop_lane(req.request_id)   # stale: tokens moved on
        paths = []
        if lane:
            nb_chain = -(-len(toks) // bs)
            idxs = list(range(have, nb_chain))
            if not idxs or not self._swap_worthwhile(
                    len(idxs), len(toks) - have * bs):
                return False
        else:
            paths = tier.match_prefix(toks, have)
            if not paths or not self._swap_worthwhile(
                    len(paths), len(paths) * bs):
                return False
            idxs = [have + j for j in range(len(paths))]
        # allocate the device blocks BEFORE consuming any host state:
        # a dry pool then leaves the lane image / demoted entries
        # intact for the next admission pass (under a preemption storm
        # the first attempts routinely race a full pool — consuming
        # first would destroy the saved KV and force recompute forever).
        # The matched arena entries are pinned across the loop: the
        # reclaim(1) fallback fires the spill hook, and store_prefix
        # making room for a NEW demotion must not LRU-evict the entries
        # this swap-in is about to pop (device-pool-dry + arena-full is
        # exactly the pressure regime the tier serves).
        tier.pin_prefix(paths)
        try:
            dev_ids = []
            for _ in idxs:
                bid = self.pool.alloc()
                if bid is None and self.prefix.reclaim(1):
                    bid = self.pool.alloc()
                if bid is None:
                    for b in dev_ids:
                        self.pool.release(b)
                    # pool dry: recompute covers it.  None (vs False)
                    # tells the admission promotion loop to stop trying
                    # — no host state was consumed, so the next
                    # boundary retries.
                    return None
                dev_ids.append(bid)
            if lane:
                img = tier.take_lane(req.request_id)
                plan = [(i, img.hbs[i]) for i in idxs]
                consumed = list(img.hbs)
            else:
                # defense in depth: should an entry be gone anyway,
                # stop at the break (later blocks could not graft
                # without their parent), return the unused device
                # blocks, and leave the unconsumed entries resident
                plan = []
                for i, p in zip(idxs, paths):
                    hb = tier.pop_prefix(p)
                    if hb is None:
                        break
                    plan.append((i, hb))
                for b in dev_ids[len(plan):]:
                    self.pool.release(b)
                dev_ids = dev_ids[:len(plan)]
                if not plan:
                    return False
                consumed = [hb for _, hb in plan]
        finally:
            tier.unpin_prefix(paths)
        n = len(plan)
        kd = np.empty((n,) + tier.k.shape[1:], tier.k.dtype)
        vd = np.empty((n,) + tier.v.shape[1:], tier.v.dtype)
        ksd = vsd = None
        if tier.quantized:
            ksd = np.empty((n,) + tier.k_scale.shape[1:], np.float32)
            vsd = np.empty_like(ksd)
        for j, (_, hb) in enumerate(plan):
            bk, bv, bks, bvs = tier.read_block(hb)
            kd[j], vd[j] = bk, bv
            if ksd is not None:
                ksd[j], vsd[j] = bks, bvs
        for hb in consumed:
            tier.release(hb)
        # pad to a power of two so the compile cache stays bounded;
        # padding rows scatter zeros into scratch block 0, whose
        # content is meaningless by design
        lanes = self._pow2_ceil(n)
        ids = np.zeros(lanes, np.int32)
        ids[:n] = dev_ids
        if lanes > n:
            pad = lanes - n
            kd = np.concatenate(
                [kd, np.zeros((pad,) + kd.shape[1:], kd.dtype)])
            vd = np.concatenate(
                [vd, np.zeros((pad,) + vd.shape[1:], vd.dtype)])
            if ksd is not None:
                ksd = np.concatenate(
                    [ksd, np.zeros((pad,) + ksd.shape[1:], np.float32)])
                vsd = np.concatenate(
                    [vsd, np.zeros((pad,) + vsd.shape[1:], np.float32)])
        new_k, new_v, new_ks, new_vs = self._upload(
            self.pool.k, self.pool.v,
            self.pool.k_scale, self.pool.v_scale,
            jnp.asarray(ids), jnp.asarray(kd), jnp.asarray(vd),
            None if ksd is None else jnp.asarray(ksd),
            None if vsd is None else jnp.asarray(vsd))
        self.pool.rebind(new_k, new_v, new_ks, new_vs)
        self._place_pool()
        grafted = 0
        for (idx, _), bid in zip(plan, dev_ids):
            if self.prefix.graft(toks, idx, bid):
                grafted += 1
            else:
                self.pool.release(bid)   # chain broke: recompute covers
        if not grafted:
            return False
        averted = max(0, self.prefix.lookup(toks) - before)
        nbytes = n * self.pool.bytes_per_block
        name = self._profiler_name
        self._swap_ins += 1
        self._swap_in_blocks += n
        self._swap_in_bytes += nbytes
        _SRV_SWAP_IN_BYTES.inc(nbytes, engine=name)
        self._swaps_averted_tokens += averted
        if self._prefill_card_tokens:
            fl = averted * (self._prefill_card_flops
                            / self._prefill_card_tokens)
            self._swaps_averted_flops += fl
            _SRV_SWAP_AVERTED.inc(fl, engine=name)
        _obs_events.instant("serving.swap_in", cat="serving",
                            request=req.request_id, blocks=n,
                            bytes=nbytes, averted_tokens=averted,
                            source="lane" if lane else "prefix")
        if req.trace is not None:
            req.trace.add(_obs_tracing.SWAP_IN, blocks=n, bytes=nbytes,
                          averted_tokens=averted,
                          source="lane" if lane else "prefix")
        return True

    def _sync_device_state(self):
        """Upload the per-slot state mirrors — only when admission
        dirtied them.  In steady-state decode the device arrays returned
        by the previous horizon are passed straight back in."""
        if not self._state_dirty:
            return
        self._d_tokens = jnp.asarray(self._tokens)
        self._d_pos = jnp.asarray(self._pos)
        self._d_counts = jnp.asarray(self._counts)
        self._d_active = jnp.asarray(self._active)
        self._d_hist = jnp.asarray(self._hist)
        self._d_gates = jnp.asarray(self._spec_gates)
        self._d_params = tuple(
            jnp.asarray(a) for a in (self._seeds, self._temps,
                                     self._top_ks, self._top_ps,
                                     self._eos_ids, self._limits))
        if self._structured:
            self._d_dfa_state = jnp.asarray(self._dfa_state)
        self._state_dirty = False

    def _sync_tables(self, nb):
        """Upload the live ``[:, :nb]`` prefix of the host block tables
        — only when a table changed (lease/alloc/release) or ``nb``
        re-bucketed.  In steady-state decode nothing is uploaded and the
        tables stay loop-invariant across horizons."""
        if self.cache.tables_dirty or nb != self._d_tables_nb:
            self._d_tables = jnp.asarray(self.cache.tables[:, :nb])
            self._d_tables_nb = nb
            self.cache.tables_dirty = False

    def _dispatch_horizon(self, h, k=None):
        """One compiled decode dispatch over ``h`` fused steps of
        ``k+1``-position verify windows; adopts the returned device
        state and returns the harvested ``[h, n, k+1]`` token array
        AFTER the one blocking host sync.  The block-table width ``nb``
        is bucketed per dispatch (ragged attention), and the decode
        program re-compiles only on a new (h, nb, k) triple."""
        if k is None:
            k = self._resolve_spec_k()
        with _span("engine.decode.prepare") as sp:
            # idempotent; step() already ran it
            self._ensure_blocks(h, k + 1)
            nb = self._attn_blocks(h, k + 1)
            sp.args.update(
                state=bool(self._state_dirty),
                tables=bool(self.cache.tables_dirty
                            or nb != self._d_tables_nb))
            self._sync_device_state()
            self._sync_tables(nb)
            self._sync_grammar_tables()
        seeds, temps, top_ks, top_ps, eos_ids, limits = self._d_params
        with _span("engine.decode.enqueue", horizon=h, width=nb, k=k,
                   lanes=int(np.count_nonzero(self._active))):
            (tok, p, cnt, act, hb, nds), new_k, new_v, new_ks, new_vs, \
                toks, stats = self._decode(
                    self._state_arrays, self._d_tokens, self._d_pos,
                    self._d_counts, self._d_active, self._d_hist,
                    self._d_gates, seeds, temps, top_ks, top_ps, eos_ids,
                    limits, self._d_tables, self.pool.k, self.pool.v,
                    self.pool.k_scale, self.pool.v_scale, h, k,
                    self._d_dfa_state, self._d_dfa_next, self._d_dfa_mask,
                    self._d_dfa_forced)
            self.pool.rebind(new_k, new_v, new_ks, new_vs)
        self._d_tokens, self._d_pos = tok, p
        self._d_counts, self._d_active = cnt, act
        self._d_hist = hb
        self._d_dfa_state = nds
        self._decode_buckets.add((h, nb, k))
        # KV traffic actually gathered by the fallback scan (and the
        # upper bound for the block-culling Pallas kernel): every lane
        # reads its nb table-mapped blocks, all layers, per step.
        # bytes_per_block is the pool's ACTUAL footprint (int8 payload
        # + per-token f32 scales when quantized).
        step_bytes = self.cache.num_slots * nb * self.pool.bytes_per_block
        self._kv_bytes_read += step_bytes * h
        _SRV_KV_BYTES.inc(step_bytes * h, engine=self._profiler_name)
        # the sampler's batch predicates, from the arrays' host copies
        sampling = self._temps > 0
        _SAMPLER_DISPATCH.inc(filters="+".join(
            f for f, asks in (("top_k", self._top_ks > 0),
                              ("top_p", self._top_ps < 1.0))
            if (sampling & asks).any()) or "none")
        if self.while_in_flight is not None:
            self.while_in_flight()       # the driver's turn: device busy
        with _span("engine.decode.wait"):
            toks = np.asarray(toks)      # the ONE host sync per horizon
        self._host_syncs += 1
        if stats is not None:
            self._count_layer_stats(np.asarray(stats), "decode")
        card = self._decode.last_card
        if card is not None:
            self._program_flops += card.flops or 0.0
            self._program_bytes += card.bytes_accessed or 0.0
        return toks

    def step(self, horizon=None):
        """One engine iteration: admit queued requests into free slots
        (prefill), then run ONE compiled horizon of fused decode steps
        over every slot.  ``horizon=None`` lets the adaptive policy pick
        the bucket; an explicit value is bucketed to a power of two
        (scanning past a request's retirement is correct — masked — just
        wasteful).  Returns the requests that finished during this step.

        The span log gets the step by phase: ``engine.admit`` (with the
        ``engine.prefill.build`` / ``.enqueue`` / ``.wait`` / ``.harvest``
        spans of each prefill dispatch inside it), then
        ``engine.decode.prepare`` (twice: block coverage here, the
        uploads in :meth:`_dispatch_horizon`) / ``.enqueue`` / ``.wait``
        / ``.harvest`` and ``engine.step.publish`` (the gauges).  The device
        certainly has work between an ``enqueue``'s start and its ``wait``'s
        end; elsewhere the host alone decides.  So between each ``enqueue``
        and its ``wait`` the driver gets its turn (``while_in_flight``, unset
        by default): the worker hands the LAST step's tokens to the
        streaming handlers there, beside the device and not instead of it."""
        t0 = time.time()
        finished = []
        self._update_degradation()
        self.admit()
        # mid-chunked-prefill lanes are RUNNING but decode-inactive —
        # they hold a slot and blocks but emit nothing until their final
        # chunk arms them, so the decode snapshot excludes them (their
        # masked -1 rows must never reach the harvest walk)
        if any(self._active[s] for s in self.scheduler.running):
            with _span("engine.decode.prepare"):
                h = self._resolve_horizon(horizon)
                k = self._resolve_spec_k()
                # block coverage (and any pressure preemption) BEFORE the
                # harvest snapshot: a lane preempted here simply isn't in
                # `active`, so its -1 harvest rows are never misread
                self._ensure_blocks(h, k + 1)
        active = {s: r for s, r in self.scheduler.running.items()
                  if self._active[s]}
        if active:
            self._horizon_buckets.add(h)
            toks = self._dispatch_horizon(h, k)
            with _span("engine.decode.harvest") as sp:
                harvested, wasted = self._harvest(toks, active, h, k,
                                                  finished)
                sp.args.update(tokens=harvested, retired=len(finished))
                self._decode_steps += h
                self._decode_horizons += 1
                self._slot_busy_integral += \
                    h * len(active) / self.cache.num_slots
                name = self._profiler_name
                _SRV_DECODE_STEPS.inc(h, engine=name)
                _SRV_HORIZON.observe(h, engine=name)
                _SRV_TOKENS.inc(harvested, engine=name)
                if wasted:
                    _SRV_WASTED.inc(wasted, engine=name)
                # adaptive growth: stable horizon (nothing retired,
                # nothing waiting) doubles the next one; churn resets
                # to 1
                if finished or self.scheduler.queue_depth:
                    self._grow = 1
                else:
                    self._grow = min(max(1, int(self.config.max_horizon)),
                                     max(self._grow, h) * 2)
        dt = time.time() - t0
        self._busy_s += dt
        with _span("engine.step.publish"):
            _SRV_STEP.observe(dt, engine=self._profiler_name)
            self._publish_gauges()
        return finished

    def _count_layer_stats(self, stats, kind):
        """What the model's layers returned with one dispatch's tokens
        (already on the host's side of its one sync): ``stats`` [steps,
        layers that set one, columns].  Column j goes to the counter the
        model named j-th (``layer_stat_names``), summed over the steps,
        by layer and by kind of dispatch; ``stats()["layer_stats"]`` reads
        the same counters back (``_layer_stat_sums``): one record, and the
        engine knows no column's meaning."""
        name = self._profiler_name
        per_layer = stats.sum(axis=0)
        self._stat_layers = per_layer.shape[0]
        for j, counter in enumerate(self._layer_counters):
            for i, amount in enumerate(per_layer[:, j]):
                counter.inc(int(amount), engine=name, layer=i,
                            kind=kind)

    def _harvest(self, toks, active, h, k_draft, finished):
        """Walk the ``[h, num_slots, k_draft+1]`` harvested token
        windows, replaying each running request's stream in order:
        record the 1..K+1 emitted tokens of every live window (the
        ``-1`` tail of a window marks rejected/unemitted positions),
        retire on EOS/limit (the host check mirrors the in-scan mask),
        count post-retirement lane STEPS as waste (one per scan step,
        matching the K=0 meaning), and keep the host mirrors — last
        token, row length, sample count, token history — equal to the
        frozen device state.  Drafting lanes also update their
        acceptance EMA here, which drives the adaptive gates (a gate
        flip dirties the device state for the next upload)."""
        harvested = wasted = 0
        w = k_draft + 1
        # cost attribution: the dispatch's program-card totals split
        # evenly over the active lanes (every active lane — including
        # one that retires mid-horizon — rides the whole compiled scan),
        # so lane shares sum back to the engine's _program_* totals
        card = self._decode.last_card
        flops_share = bytes_share = None
        if card is not None and active:
            if card.flops is not None:
                flops_share = card.flops / len(active)
            if card.bytes_accessed is not None:
                bytes_share = card.bytes_accessed / len(active)
        drafted = accepted = 0
        forced_total = 0
        slab = self._grammar_slab
        vocab = int(self.model.config.vocab_size)
        floor = float(self.config.spec_accept_floor)
        gated = self._spec_gates.copy()  # gates the dispatch ran with
        for slot, req in active.items():
            done = False
            lane_tokens = lane_accept = lane_forced = 0
            # replay the lane's DFA walk on the host mirror: the same
            # slab tables the device walked, advanced by the same
            # emitted tokens, so the mirror state stays equal to the
            # (frozen) device carry — and yields per-token telemetry
            # (masked fraction, forced-draft hits) with no extra
            # device outputs
            st = int(self._dfa_state[slot]) if self._structured else 0
            constrained = st != 0
            fd_on = (constrained and k_draft
                     and bool(self.config.grammar_forced_drafting))
            for step_i in range(h):
                row = toks[step_i, slot]
                if done:
                    wasted += 1
                    continue
                if int(row[0]) < 0:
                    raise RuntimeError(
                        f"horizon mask retired slot {slot} at step "
                        f"{step_i} but the scheduler still runs its "
                        "request — in-scan EOS/limit logic diverged "
                        "from record_token")
                n_emit = 0
                # a forced-chain draft counts only while the window's
                # chain from its START state held: the device proposed
                # forced[st] at position j iff every earlier position
                # was forced too (forced_chain breaks at the first
                # non-forced state)
                win_chain = fd_on and bool(gated[slot])
                for j in range(w):
                    t = int(row[j])
                    if t < 0:
                        break            # rejected/unemitted window tail
                    n_emit += 1
                    harvested += 1
                    self._tokens_generated += 1
                    self._tokens[slot] = t
                    self._pos[slot] += 1
                    self._hist[slot, self._pos[slot]] = t
                    if constrained:
                        _SRV_GRAMMAR_MASKED.observe(
                            1.0 - float(slab.popcount[st]) / vocab,
                            engine=self._profiler_name)
                        if (win_chain and j < k_draft
                                and int(slab.forced[st]) == t):
                            lane_forced += 1
                        else:
                            win_chain = False
                        st = int(slab.next[st, t])
                    if req.record_token(t):
                        done = True      # retire AFTER the lane's trace
                        break            # event, below
                lane_tokens += n_emit
                self._counts[slot] = req.n_generated
                if k_draft and gated[slot]:
                    drafted += k_draft
                    accepted += n_emit - 1
                    lane_accept += n_emit - 1
                    self._spec_windows += 1
                    self._spec_accept_hist[n_emit] = \
                        self._spec_accept_hist.get(n_emit, 0) + 1
                    _SRV_SPEC_ACCEPT.observe(
                        n_emit, engine=self._profiler_name)
                    ema = 0.5 * float(self._spec_ema[slot]) \
                        + 0.5 * (n_emit - 1) / k_draft
                    self._spec_ema[slot] = ema
                    if self.config.spec_adaptive and \
                            (ema >= floor) != bool(self._spec_gates[slot]):
                        self._spec_gates[slot] = ema >= floor
                        self._state_dirty = True
            if constrained:
                self._dfa_state[slot] = st
                forced_total += lane_forced
            if req.trace is not None and lane_tokens:
                extra = {"forced": lane_forced} if constrained else {}
                ev = req.trace.add(_obs_tracing.DECODE, horizon=h,
                                   spec_k=k_draft, tokens=lane_tokens,
                                   accepted=lane_accept, **extra)
                if flops_share is not None:
                    ev["flops_est"] = flops_share
                if bytes_share is not None:
                    ev["bytes_est"] = bytes_share
            if done:
                self._retire(req)
                finished.append(req)
        if forced_total:
            self._spec_forced_tokens += forced_total
            _SRV_SPEC_FORCED.inc(forced_total,
                                 engine=self._profiler_name)
        if drafted:
            self._spec_draft_tokens += drafted
            self._spec_accepted_tokens += accepted
            name = self._profiler_name
            _SRV_SPEC_DRAFTED.inc(drafted, engine=name)
            _SRV_SPEC_ACCEPTED.inc(accepted, engine=name)
            _SRV_SPEC_RATE.set(
                self._spec_accepted_tokens / self._spec_draft_tokens,
                engine=name)
        self._decode_harvested += harvested
        self._wasted_lane_tokens += wasted
        return harvested, wasted

    # ------------------------------------------------- degradation ladder
    def _degrade_signal(self):
        """The pressure signal driving the ladder: the reason string
        while the engine is burning (any SLO objective unhealthy, or
        pool occupancy at/above ``degrade_pool_ratio``), else None."""
        if self.slo is not None and not self.slo.healthy:
            return "slo_burn"
        if (self.pool.blocks_in_use / self.pool.capacity
                >= float(self.config.degrade_pool_ratio)):
            return "pool_pressure"
        return None

    def _update_degradation(self):
        """One ladder tick (called every step): ``degrade_patience``
        consecutive burning steps escalate one level,
        ``degrade_recover_patience`` consecutive calm steps step back
        down one level — asymmetric on purpose (hysteresis), so a
        marginal signal can't flap the ladder."""
        if not self.config.degrade_enabled:
            return
        reason = self._degrade_signal()
        if reason is not None:
            self._calm_streak = 0
            self._burn_streak += 1
            if (self._degrade_level < len(DEGRADE_LEVELS) - 1
                    and self._burn_streak
                    >= int(self.config.degrade_patience)):
                self._set_degrade_level(self._degrade_level + 1, reason)
                self._burn_streak = 0
        else:
            self._burn_streak = 0
            if self._degrade_level == 0:
                return
            self._calm_streak += 1
            if (self._calm_streak
                    >= int(self.config.degrade_recover_patience)):
                self._set_degrade_level(self._degrade_level - 1,
                                        "recovered")
                self._calm_streak = 0

    def _set_degrade_level(self, level, reason):
        prev, level = self._degrade_level, int(level)
        self._degrade_level = level
        self._degrade_transitions += 1
        self._degrade_history.append(
            {"from": prev, "to": level,
             "level": DEGRADE_LEVELS[level], "reason": reason,
             "decode_horizons": self._decode_horizons})
        del self._degrade_history[:-64]
        name = self._profiler_name
        _SRV_DEGRADATION.set(level, engine=name)
        _obs_events.instant("serving.degrade", cat="serving",
                            engine=name, level=level,
                            level_name=DEGRADE_LEVELS[level],
                            from_level=prev, reason=reason)

    def _publish_gauges(self):
        """Refresh the point-in-time typed gauges (once per step — the
        counters/histograms above accumulate incrementally)."""
        name = self._profiler_name
        _SRV_DEGRADATION.set(self._degrade_level, engine=name)
        _SRV_QUEUE.set(self.scheduler.queue_depth, engine=name)
        _SRV_ACTIVE.set(self.cache.used_slots, engine=name)
        _SRV_KV_BLOCKS.set(self.pool.blocks_in_use, engine=name)
        _SRV_KV_OCC.set(self.pool.blocks_in_use / self.pool.capacity,
                        engine=name)
        if self.pool.layout.buffers == 1:
            _KV_LATENT_LIVE.set(self.pool.blocks_in_use, engine=name)
        if self.host_tier is not None:
            _SRV_HOST_OCC.set(self.host_tier.occupancy, engine=name)
        _SRV_BUCKETS.set(len(self._decode_buckets), engine=name)
        if self.config.spec_k:
            for slot in range(self.cache.num_slots):
                _SRV_SPEC_EMA.set(float(self._spec_ema[slot]),
                                  engine=name, lane=slot)
        if self._decode_steps:
            _SRV_UTIL.set(self._slot_busy_integral / self._decode_steps,
                          engine=name)
        if self._busy_s > 0:
            _SRV_TPS.set(self._tokens_generated / self._busy_s,
                         engine=name)
        if self._prompt_tokens:
            _SRV_PREFIX_RATIO.set(
                self._prefix_hit_tokens / self._prompt_tokens,
                engine=name)

    def run(self):
        """Drain the queue: step until every submitted request finished.
        Returns all requests retired during the drain."""
        out = []
        while self.scheduler.has_work:
            before = self._finished
            out.extend(self.step())
            if self._finished == before and not self.scheduler.running \
                    and self.scheduler.queue_depth \
                    and not self._admit_deferred:
                raise RuntimeError("engine stalled with queued work")
        return out

    def drain(self):
        """Graceful shutdown of admission: refuse new submissions, abort
        every still-QUEUED request (``finish_reason="abort"``, cause
        ``"drain"`` — they never claimed a slot), run the in-flight
        lanes to completion, then release every pool block the engine
        still references (the radix prefix store's unpinned chains are
        reclaimed) and verify ``kv_blocks_in_use == 0`` — the block-leak
        invariant a replica must satisfy before the router removes it.

        Returns every request retired during the drain (aborted queued
        requests first, then lanes in retirement order).  The engine is
        empty but fully usable afterwards: ``submit()`` works again once
        ``drain()`` returns."""
        self._draining = True
        try:
            out = [self.abort(req, cause="drain")
                   for req in list(self.scheduler.queue)]
            # every running lane makes progress each step (preempted
            # lanes requeue as `resumed` and re-admit as slots free),
            # so this loop terminates within the remaining token budget
            while self.scheduler.has_work:
                out.extend(self.step())
        finally:
            self._draining = False
        # all leases are back, so every prefix chain is unpinned and
        # reclaimable; anything the reclaim cannot free is a leak.
        # The spill hook is disabled for this final sweep — shutdown
        # eviction is disposal, not demotion (demoting here would just
        # copy soon-to-be-cleared bytes into the host arena)
        spill, self.prefix.spill = self.prefix.spill, None
        spill_batch = self.prefix.spill_batch
        self.prefix.spill_batch = None
        try:
            self.prefix.reclaim(self.prefix._held)
        finally:
            self.prefix.spill = spill
            self.prefix.spill_batch = spill_batch
        if self.pool.blocks_in_use != 0:
            raise RuntimeError(
                f"drain() left {self.pool.blocks_in_use} KV pool blocks "
                f"referenced ({self.cache.leased_blocks} leased by slot "
                f"tables, {self.prefix._held} pinned by the prefix "
                "store) — block-leak invariant violated")
        if self.host_tier is not None:
            # the host-tier extension of the block-leak invariant:
            # demoted prefix entries are disposable cache content, but
            # any block still referenced after clearing them is a
            # leaked lane image (every request retired or aborted above
            # dropped its image)
            self.host_tier.clear_prefixes()
            if self.host_tier.blocks_in_use != 0:
                raise RuntimeError(
                    f"drain() left {self.host_tier.blocks_in_use} host "
                    f"arena blocks referenced "
                    f"({len(self.host_tier._lanes)} lane images) — "
                    "host block-leak invariant violated")
        self._publish_gauges()
        return out

    def generate(self, prompts, sampling=None):
        """Convenience wrapper: one prompt (list of ids) or a batch
        (list of lists).  Submits, drains, and returns the generated ids
        — a list per prompt, in submission order."""
        single = bool(prompts) and np.isscalar(prompts[0])
        batch = [prompts] if single else list(prompts)
        if isinstance(sampling, (list, tuple)):
            reqs = [self.submit(p, s) for p, s in zip(batch, sampling)]
        else:
            reqs = [self.submit(p, sampling) for p in batch]
        self.run()
        outs = [r.output_ids for r in reqs]
        return outs[0] if single else outs

    # ------------------------------------------------------------ bench
    def measure_decode_seconds(self, horizon, iters=3):
        """Benchmark hook: best wall seconds for ONE compiled horizon
        dispatch (including its single host sync) over the engine's
        current device state.  Advances the cache/state buffers, so call
        it only after draining — it exists to separate device time from
        the engine's host-side per-horizon overhead."""
        h = self._resolve_horizon(horizon)
        best = None
        for _ in range(iters):
            t0 = time.perf_counter()
            self._dispatch_horizon(h)
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        return best

    # ------------------------------------------------------------ metrics
    # ------------------------------------------------- memory accounting
    @staticmethod
    def _tree_bytes(tree):
        """Device bytes over a pytree of arrays (None leaves drop out of
        jax.tree.leaves; a deleted buffer still reports its aval size)."""
        total = 0
        for leaf in jax.tree.leaves(tree):
            try:
                total += int(leaf.nbytes)
            except Exception:        # pragma: no cover - defensive
                continue
        return total

    def _kv_pool_bytes(self):
        p = self.pool
        return self._tree_bytes([p.k, p.v, p.k_scale, p.v_scale])

    def _weight_device_bytes(self):
        return self._tree_bytes(self._state_arrays)

    def _state_device_bytes(self):
        return self._tree_bytes([
            self._d_tokens, self._d_pos, self._d_counts, self._d_active,
            self._d_hist, self._d_gates, self._d_params, self._d_tables,
            self._d_dfa_state, self._d_dfa_next, self._d_dfa_mask,
            self._d_dfa_forced])

    def counters(self):
        """Observability snapshot (also exposed via
        paddle_tpu.profiler.counters())."""
        c = {
            "queue_depth": self.scheduler.queue_depth,
            "active_slots": self.cache.used_slots,
            "num_slots": self.cache.num_slots,
            "requests_finished": self._finished,
            "tokens_generated": self._tokens_generated,
            "decode_steps": self._decode_steps,
            "decode_horizons": self._decode_horizons,
            "decode_calls": self._decode.calls,
            "decode_host_syncs": self._host_syncs,
            "wasted_lane_tokens": self._wasted_lane_tokens,
            "prefill_calls": self._prefill_calls,
            "prefill_requests": self._prefill_requests,
            "prefill_chunk_dispatches": self._chunk_dispatches,
            "prefill_chunked_requests": self._chunked_requests,
            "prefix_hit_tokens": self._prefix_hit_tokens,
            "prompt_tokens": self._prompt_tokens,
            "prefix_hit_ratio": (
                self._prefix_hit_tokens / self._prompt_tokens
                if self._prompt_tokens else 0.0),
            "decode_compiles": self._decode.misses,
            "decode_cache_hits": self._decode.hits,
            "prefill_compiles": self._prefill.misses,
            "prefill_cache_hits": self._prefill.hits,
            # unified pool: caching new prefix blocks is adopt()
            # refcounting, so the old scatter-insert dispatch is gone
            "prefix_insert_calls": 0,
            "kv_blocks_in_use": self.pool.blocks_in_use,
            "kv_bytes_read": self._kv_bytes_read,
            "cow_copies": self._cow_copies,
            "preemptions": self._preemptions,
            "kv_swap_ins": self._swap_ins,
            "kv_swap_outs": self._swap_outs,
            "kv_swap_in_bytes": self._swap_in_bytes,
            "kv_swap_out_bytes": self._swap_out_bytes,
            "requests_aborted": self._aborted,
            "deadline_expired": self._deadline_expired,
            "spec_draft_tokens": self._spec_draft_tokens,
            "spec_accepted_tokens": self._spec_accepted_tokens,
            "spec_accept_rate": (
                self._spec_accepted_tokens / self._spec_draft_tokens
                if self._spec_draft_tokens else 0.0),
            "spec_forced_tokens": self._spec_forced_tokens,
            "degradation_level": self._degrade_level,
            "degradation_sheds": self._degrade_sheds,
        }
        if self._decode_steps:
            c["slot_utilization"] = (self._slot_busy_integral
                                     / self._decode_steps)
        if self._ttft_n:
            c["ttft_avg_s"] = self._ttft_sum / self._ttft_n
        if self._busy_s > 0:
            c["tokens_per_s"] = self._tokens_generated / self._busy_s
        return c

    def tenant_ledger(self):
        """The per-tenant accounting ledger (tenant None bills to "")
        as a cheap copy — the gateway republishes it as
        ``gateway.tenant_tokens_served`` gauges and the fleet replay
        harness reconciles streamed tokens against it, without paying
        for a full ``stats()`` pass."""
        return {k: dict(v) for k, v in self._tenants.items()}

    def stats(self):
        """counters() plus derived stats: the distinct compiled horizon
        buckets, the fraction of scanned lane steps wasted on lanes that
        had already retired mid-horizon, prefix-cache internals, and
        exact TTFT percentiles from the observability reservoir."""
        s = dict(self.counters())
        lane_steps = self._decode_harvested + self._wasted_lane_tokens
        s["wasted_lane_fraction"] = (
            self._wasted_lane_tokens / lane_steps if lane_steps else 0.0)
        s["horizon_buckets"] = sorted(self._horizon_buckets)
        s["decode_buckets"] = sorted(self._decode_buckets)
        s["next_horizon_growth"] = self._grow
        s["prefill"] = {
            "chunk_tokens": self._chunk_tokens,
            "chunks_in_flight": len(self._chunking),
            "chunk_dispatches": self._chunk_dispatches,
            "chunked_requests": self._chunked_requests,
            "chunk_count_total": self._chunk_count_total,
            "interference_seconds": self._prefill_interference_s,
            "context_high_water": self._context_high_water,
            # every (lanes, bucket) prefill program this engine ran —
            # with chunking on, no bucket exceeds chunk_tokens, which is
            # what bounds a long prompt's hold on the engine
            "buckets": sorted(self._prefill_buckets),
        }
        s["prefix"] = self.prefix.stats()
        # gateway-era admission fields: per-tenant accounting (tenant
        # None bills to "") and the deadline-abort tally; priorities
        # live on the requests themselves and in their QUEUED trace
        # events
        s["tenants"] = self.tenant_ledger()
        s["draining"] = self._draining
        s["degradation"] = {
            "level": self._degrade_level,
            "level_name": DEGRADE_LEVELS[self._degrade_level],
            "transitions": self._degrade_transitions,
            "sheds": self._degrade_sheds,
            "history": list(self._degrade_history[-8:]),
        }
        s["kv_pool"] = {
            "block_size": self._block_size,
            "capacity_blocks": self.pool.capacity,
            "free_blocks": self.pool.free_blocks,
            "blocks_in_use": self.pool.blocks_in_use,
            "leased_blocks": self.cache.leased_blocks,
            "cached_blocks": self.prefix._held,
            "bytes_per_block": self.pool.bytes_per_block,
            "kv_bytes_read": self._kv_bytes_read,
            "cow_copies": self._cow_copies,
            "preemptions": self._preemptions,
            "dtype": str(jnp.dtype(self.pool.store_dtype)),
            "quant_dtype": self.pool.quant_dtype,
            "buffers_per_layer": self.pool.layout.buffers,
        }
        # what the model's layers had counted (PagedKV.stats), under the
        # model's names: {name: {prefill|decode: sum over layers, steps}}
        s["layer_stats"] = _layer_stat_sums(self)
        # tiered KV: the host spill arena under the pool.  Counters are
        # trace-exact per kind: kv_swap_out_bytes covers lane saves
        # (paired SWAP_OUT trace events), demote_bytes covers prefix
        # demotions (engine-level, no owning request).
        tier = self.host_tier
        s["kv_pool"].update({
            "host_capacity_blocks": tier.capacity if tier else 0,
            "host_blocks_in_use": tier.blocks_in_use if tier else 0,
            "host_arena_bytes": self._host_arena_bytes(),
            "host_occupancy_ratio": tier.occupancy if tier else 0.0,
            "kv_swap_ins": self._swap_ins,
            "kv_swap_outs": self._swap_outs,
            "kv_swap_in_blocks": self._swap_in_blocks,
            "kv_swap_out_blocks": self._swap_out_blocks,
            "kv_swap_in_bytes": self._swap_in_bytes,
            "kv_swap_out_bytes": self._swap_out_bytes,
            "kv_demote_bytes": self._demote_bytes,
            "kv_swaps_averted_tokens": self._swaps_averted_tokens,
            "kv_swaps_averted_flops": self._swaps_averted_flops,
            "swap_policy": self._swap_policy,
        })
        if tier is not None:
            s["kv_pool"]["host_tier"] = tier.stats()
        s["quant"] = {
            "weight_dtype": self._weight_dtype,
            "kv_cache_dtype": self._kv_quant,
            "quantized_weights": len(self._wq_dtypes),
            # actual bytes the decode step streams for parameters —
            # int8 payload + scale vectors for quantized entries, fp
            # bytes for the rest
            "weight_bytes": int(sum(
                sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(a))
                for a in self._state_arrays)),
        }
        s["spec"] = {
            "k": int(self.config.spec_k),
            "adaptive": bool(self.config.spec_adaptive),
            "ngram": int(self.config.spec_ngram),
            "draft_tokens": self._spec_draft_tokens,
            "accepted_tokens": self._spec_accepted_tokens,
            "accept_rate": (
                self._spec_accepted_tokens / self._spec_draft_tokens
                if self._spec_draft_tokens else 0.0),
            # tokens emitted per verify window (accepted prefix + the
            # bonus token) -> number of windows, drafting lanes only
            "accept_len_hist": {
                int(n): c
                for n, c in sorted(self._spec_accept_hist.items())},
            "mean_accept_len": (
                sum(n * c for n, c in self._spec_accept_hist.items())
                / self._spec_windows if self._spec_windows else 0.0),
            "lane_accept_ema": [round(float(x), 4)
                                for x in self._spec_ema],
        }
        slab = self._grammar_slab
        s["structured"] = {
            "enabled": self._structured,
            # lanes currently decoding under a grammar: active with a
            # non-sentinel DFA state
            "constrained_lanes": int(sum(
                1 for slot in range(self.cache.num_slots)
                if self._active[slot] and self._dfa_state[slot] != 0)),
            "capacity_states": slab.capacity if slab else 0,
            "states_used": slab.states_used if slab else 0,
            "grammars_installed": slab.grammars_installed if slab else 0,
            "table_bytes": slab.device_bytes if slab else 0,
            "compile_cache_hits": self._grammar_cache_hits,
            "compile_cache_misses": self._grammar_cache_misses,
            "compile_cache_entries": len(self._grammar_cache),
            "forced_tokens": self._spec_forced_tokens,
        }
        # observability phase 3: program-card cost model + memory ledger
        s["cost"] = {
            "program_flops_total": self._program_flops,
            "program_bytes_total": self._program_bytes,
            "decode_cards": len({id(c) for c in
                                 self._decode.cards.values()}),
            "prefill_cards": len({id(c) for c in
                                  self._prefill.cards.values()}),
        }
        s["memory"] = self.ledger.snapshot()
        qp50 = _SRV_QUEUE_WAIT.percentile(50, engine=self._profiler_name)
        if qp50 is not None:
            s["queue_wait_p50_s"] = qp50
            s["queue_wait_p95_s"] = _SRV_QUEUE_WAIT.percentile(
                95, engine=self._profiler_name)
        if self._ttft_n:
            s["ttft_p50_s"] = _SRV_TTFT.percentile(
                50, engine=self._profiler_name)
            s["ttft_p95_s"] = _SRV_TTFT.percentile(
                95, engine=self._profiler_name)
        if self.slo is not None:
            s["slo"] = self.slo.snapshot()
        if self.recorder is not None:
            s["tracing"] = {
                "live_traces": len(self.recorder.live()),
                "finished_retained": len(self.recorder.recent()),
                "dropped_finished": self.recorder.dropped,
                "capacity": self.recorder.capacity,
            }
        if self.telemetry is not None:
            s["telemetry_port"] = self.telemetry.port
        return s

    #: The driver's turn while the device works.  Whoever drives the engine
    #: may set this to a callable of no arguments (``EngineWorker`` sets its
    #: flush to the streaming handlers); ``_dispatch_horizon`` and
    #: ``_dispatch_prefill`` call it once a dispatch, after the program's
    #: ``.enqueue`` span has closed and before its ``.wait`` opens, so host
    #: work that needs nothing of this dispatch runs beside the device and
    #: not in its idle time.  ``None`` (the default): no call, nothing
    #: changes.  It must not touch the engine.  (Down here, not beside
    #: ``_instances``: lines above the traced functions stay where the
    #: compile cache's keys have them, ROADMAP debt D9.)
    while_in_flight = None


def _layer_stat_sums(engine):
    """{counter name: {prefill|decode: sum over layers}} of one engine,
    read back from the registry counters ``_count_layer_stats`` feeds."""
    name, n = engine._profiler_name, engine._stat_layers
    return {c.name: {kind: int(sum(c.value(engine=name, layer=i, kind=kind)
                                   for i in range(n)))
                     for kind in ("prefill", "decode")}
            for c in engine._layer_counters}


# (Down here, not beside the other families: lines above the traced
# functions stay where the compile cache's keys have them, ROADMAP D9.)
_PREFILL_HEAD = _obs_metrics.counter(
    "prefill.head.trace", "traces of a prefill program, by the rows its LM "
    "head projects (one a lane) and the positions it runs (lanes x bucket)")


def _lane_rows(x, rows):
    """``x`` [L, S, ...] -> [L, ...]: position ``rows[i]`` of lane i."""
    return jax.vmap(lambda a, r: jax.lax.dynamic_index_in_dim(
        a, r, axis=0, keepdims=False))(x, rows)


def _head_rows(model, h, rows):
    """Prefill's LM head: hidden states ``h`` [L, bucket, hidden] ->
    logits [L, vocab] of one position a lane (``rows`` [L], the last
    valid one), the row chosen BEFORE the head, so the other positions
    are never projected."""
    lanes, bucket = h.shape[0], h.shape[1]
    _PREFILL_HEAD.inc(rows=lanes, positions=lanes * bucket)
    return model._logits(Tensor(_lane_rows(h._data, rows)))
