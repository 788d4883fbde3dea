"""paddle_tpu.serving tests: paged-engine decode parity with the legacy
concat cache, continuous batching vs sequential generation, bucketed
prefill compilation counters, sampling determinism.  The exact counts of
each mechanism (dispatches, compiles, KV bytes, swap bytes, accept lengths,
collective calls) are in test_serving_counts.py: a file of its own, so
that another worker takes it (this file is the suite's longest)."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.core import tape as _tape
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.models.llama import LlamaForCausalLM
from paddle_tpu.serving import (
    Engine, EngineConfig, HostKVTier, PagedKVCache, PagedKVPool,
    PrefixCache, SamplingParams, Scheduler,
)
from paddle_tpu.quantization import (
    PerChannelAbsmaxObserver, channelwise_scales, dequantize_weight,
    quantize_for_serving, quantize_weight,
)
from paddle_tpu.serving.kv_cache import paged_write, paged_write_quant
from paddle_tpu.serving.paged_attention import (
    _pallas_paged_attention, _xla_paged_attention,
)

TINY = GPTConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                 num_hidden_layers=2, num_attention_heads=4,
                 max_position_embeddings=64)
TINY_GQA = GPTConfig(vocab_size=96, hidden_size=64, intermediate_size=128,
                     num_hidden_layers=2, num_attention_heads=8,
                     num_key_value_heads=2, max_position_embeddings=64)


def _model(cfg=TINY, seed=0):
    paddle.seed(seed)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


class TestEngine:
    def test_greedy_matches_legacy_generate(self):
        m = _model()
        prompt = [1, 5, 9, 2, 7]
        eng = Engine(m, EngineConfig(num_slots=2, max_seq_len=32),
                     register_profiler=False)
        out = eng.generate(prompt, SamplingParams(max_new_tokens=6))
        gen = m.generate(paddle.to_tensor(np.asarray([prompt], np.int64)),
                         max_new_tokens=6, temperature=0)
        assert out == gen.numpy()[0, len(prompt):].tolist()

    @pytest.mark.slow
    def test_continuous_batching_matches_sequential(self):
        """Staggered submits/EOS with mixed sampling params produce the
        SAME tokens as one-request-at-a-time generation: a request's
        stream depends only on (its prompt, its params, its seed), never
        on batch composition."""
        m = _model()
        prompts = [[1, 5, 9], [2, 7, 4, 11], [3, 3, 8, 1, 2, 9],
                   [10, 20, 30, 40, 50]]
        samp = [SamplingParams(max_new_tokens=5),
                SamplingParams(temperature=0.8, top_k=20, seed=7,
                               max_new_tokens=6),
                SamplingParams(temperature=1.0, top_p=0.9, seed=123,
                               max_new_tokens=4),
                SamplingParams(temperature=0.5, top_k=5, top_p=0.8,
                               seed=42, max_new_tokens=7)]
        sequential = []
        for p, s in zip(prompts, samp):
            e = Engine(m, EngineConfig(num_slots=2, max_seq_len=32),
                       register_profiler=False)
            sequential.append(e.generate(p, s))

        eng = Engine(m, EngineConfig(num_slots=2, max_seq_len=32),
                     register_profiler=False)
        reqs = [eng.submit(prompts[0], samp[0])]
        eng.step()
        eng.step()
        reqs.append(eng.submit(prompts[1], samp[1]))
        eng.step()
        reqs.append(eng.submit(prompts[2], samp[2]))
        reqs.append(eng.submit(prompts[3], samp[3]))   # queued: slots full
        eng.run()
        assert [r.output_ids for r in reqs] == sequential
        # 4 requests through 2 slots: slots were reused
        assert eng.counters()["requests_finished"] == 4

    def test_single_decode_compilation_heterogeneous_prompts(self):
        """The acceptance criterion: a multi-request run with
        heterogeneous prompt lengths compiles the fused decode program
        exactly ONCE PER HORIZON BUCKET, and prefill once per
        (lane-bucket, length-bucket) pair — with same-bucket requests
        co-admitted into a single batched dispatch."""
        m = _model()
        eng = Engine(m, EngineConfig(num_slots=3, max_seq_len=48,
                                     min_prefill_bucket=4),
                     register_profiler=False)
        # length buckets: 3->4, 4->4, 6->8, 5->8, 9->16
        for p in ([1, 2, 3], [1, 2, 3, 4], [5, 6, 7, 8, 9, 1],
                  [9, 8, 7, 6, 5], [1] * 9):
            eng.submit(p, SamplingParams(max_new_tokens=4))
        eng.run()
        s = eng.stats()
        assert s["decode_compiles"] == len(s["decode_buckets"])
        # dispatch shapes: (2 lanes, 4), (1, 8) twice, (1, 16)
        assert s["prefill_compiles"] == 3
        assert s["prefill_calls"] == 4       # first two share ONE dispatch
        assert s["prefill_requests"] == 5    # ...but all 5 were prefilled
        assert s["decode_cache_hits"] == \
            s["decode_horizons"] - s["decode_compiles"]
        assert s["tokens_generated"] == 5 * 4

    def test_eos_frees_slot_early(self):
        m = _model()
        prompt = [4, 8, 15, 16, 23, 42]
        eng = Engine(m, EngineConfig(num_slots=1, max_seq_len=32),
                     register_profiler=False)
        ref = eng.generate(prompt, SamplingParams(max_new_tokens=8))
        eos = ref[3]
        stop = ref.index(eos)  # greedy streams can cycle: truncate at
        # the FIRST occurrence, which is where the engine must stop
        eng2 = Engine(m, EngineConfig(num_slots=1, max_seq_len=32),
                      register_profiler=False)
        req = eng2.submit(prompt, SamplingParams(max_new_tokens=8,
                                                 eos_token_id=eos))
        eng2.run()
        assert req.output_ids == ref[:stop + 1]
        assert req.finish_reason == "eos"
        assert eng2.cache.free_slots == 1

    def test_sampling_determinism_under_fixed_seeds(self):
        m = _model()
        prompt = [3, 1, 4, 1, 5]
        sp = dict(temperature=0.9, top_k=30, top_p=0.95, max_new_tokens=8)

        def run(seed):
            e = Engine(m, EngineConfig(num_slots=2, max_seq_len=32),
                       register_profiler=False)
            return e.generate(prompt, SamplingParams(seed=seed, **sp))

        a, b, c = run(11), run(11), run(99)
        assert a == b                      # same seed: bitwise replay
        assert a != c                      # different seed: new stream

    def test_submit_validates_budget(self):
        m = _model()
        eng = Engine(m, EngineConfig(num_slots=1, max_seq_len=16),
                     register_profiler=False)
        with pytest.raises(ValueError):
            eng.submit(list(range(10)), SamplingParams(max_new_tokens=10))
        with pytest.raises(ValueError):
            eng.submit([], SamplingParams())

    def test_llama_alias_serves(self):
        paddle.seed(2)
        m = LlamaForCausalLM(TINY)
        m.eval()
        eng = Engine(m, EngineConfig(num_slots=1, max_seq_len=32),
                     register_profiler=False)
        out = eng.generate([7, 7, 7], SamplingParams(max_new_tokens=3))
        assert len(out) == 3

    def test_inference_bridge_and_lazy_submodule(self):
        import paddle_tpu
        import paddle_tpu.inference as inference

        assert paddle_tpu.serving.Engine is Engine  # lazy attr resolves
        m = _model()
        eng = inference.create_llm_engine(m, num_slots=1, max_seq_len=32)
        try:
            direct = Engine(m, EngineConfig(num_slots=1, max_seq_len=32),
                            register_profiler=False)
            sp = SamplingParams(max_new_tokens=3)
            assert eng.generate([5, 6, 7], sp) == \
                direct.generate([5, 6, 7], sp)
        finally:
            eng.close()

    def test_counters_exposed_via_profiler(self):
        import paddle_tpu.profiler as profiler

        m = _model()
        eng = Engine(m, EngineConfig(num_slots=1, max_seq_len=32))
        try:
            eng.generate([1, 2, 3], SamplingParams(max_new_tokens=2))
            snap = profiler.counters()
            assert eng._profiler_name in snap
            got = snap[eng._profiler_name]
            assert got["decode_compiles"] == 1
            assert got["tokens_generated"] == 2
            assert "tokens_per_s" in got and got["tokens_per_s"] > 0
            assert "ttft_avg_s" in got
        finally:
            eng.close()
        assert eng._profiler_name not in profiler.counters()


class TestHorizonDecode:
    """Horizon-scanned fused decode: one compiled dispatch and one host
    sync advance every slot by H steps, with in-scan EOS/limit masking.
    Every horizon partition of a request's stream must be bitwise-equal
    to horizon=1 and to sequential generation."""

    MIXED_PROMPTS = [[1, 5, 9], [2, 7, 4, 11], [3, 3, 8, 1, 2, 9]]
    MIXED_SAMP = [
        SamplingParams(max_new_tokens=9),
        SamplingParams(temperature=0.8, top_k=20, seed=7,
                       max_new_tokens=12),
        SamplingParams(temperature=1.0, top_p=0.9, seed=123,
                       max_new_tokens=10),
    ]

    @staticmethod
    def _sequential(m, prompts, samp):
        outs = []
        for p, s in zip(prompts, samp):
            e = Engine(m, EngineConfig(num_slots=2, max_seq_len=32,
                                       max_horizon=1),
                       register_profiler=False)
            outs.append(e.generate(p, s))
        return outs

    @pytest.mark.slow
    def test_horizon8_bitwise_equals_horizon1_and_sequential(self):
        m = _model()
        seq = self._sequential(m, self.MIXED_PROMPTS, self.MIXED_SAMP)
        e1 = Engine(m, EngineConfig(num_slots=3, max_seq_len=32,
                                    max_horizon=1),
                    register_profiler=False)
        e8 = Engine(m, EngineConfig(num_slots=3, max_seq_len=32,
                                    max_horizon=8),
                    register_profiler=False)
        out1 = e1.generate(self.MIXED_PROMPTS, self.MIXED_SAMP)
        out8 = e8.generate(self.MIXED_PROMPTS, self.MIXED_SAMP)
        assert out8 == out1 == seq
        s1, s8 = e1.stats(), e8.stats()
        assert s1["horizon_buckets"] == [1]
        assert max(s8["horizon_buckets"]) > 1       # adaptive growth ran
        # the horizon engine did the same work in fewer dispatches/syncs
        assert s8["decode_horizons"] < s1["decode_horizons"]
        assert s8["decode_host_syncs"] < s1["decode_host_syncs"]

    def test_one_dispatch_and_one_sync_per_horizon(self):
        """The dispatch-count probe: compiled decode calls == horizon
        dispatches == blocking host syncs (the per-step np.asarray sync
        is gone from the decode path)."""
        m = _model()
        eng = Engine(m, EngineConfig(num_slots=1, max_seq_len=64,
                                     max_horizon=8),
                     register_profiler=False)
        eng.submit([2, 4, 6], SamplingParams(max_new_tokens=17))
        while eng.scheduler.has_work:
            eng.step(horizon=8)
        c = eng.counters()
        # 16 decode tokens through horizon-8 dispatches: exactly 2
        assert c["decode_horizons"] == 2
        assert c["decode_calls"] == 2
        assert c["decode_host_syncs"] == 2
        assert c["decode_steps"] == 16
        assert c["tokens_generated"] == 17

    def test_mid_horizon_eos_masks_lane(self):
        """A lane hitting EOS inside the scan freezes: its tokens stop
        at the EOS, the rest of the horizon is discarded (-1 harvest),
        and the co-resident request is unaffected bitwise."""
        m = _model()
        prompt = [4, 8, 15, 16, 23, 42]
        other_prompt = [9, 1, 7, 3]
        ref_engine = Engine(m, EngineConfig(num_slots=1, max_seq_len=32,
                                            max_horizon=1),
                            register_profiler=False)
        ref = ref_engine.generate(prompt, SamplingParams(max_new_tokens=12))
        other_ref = Engine(
            m, EngineConfig(num_slots=1, max_seq_len=32, max_horizon=1),
            register_profiler=False).generate(
                other_prompt, SamplingParams(max_new_tokens=14))
        # pick an EOS whose FIRST occurrence lands mid-horizon (decode
        # scan step 0..6 of the first horizon-8 dispatch)
        eos = stop = None
        for k in range(1, 8):
            if 1 <= ref.index(ref[k]) <= 7:
                eos, stop = ref[k], ref.index(ref[k])
                break
        assert eos is not None, "greedy stream had no usable EOS token"
        eng = Engine(m, EngineConfig(num_slots=2, max_seq_len=32,
                                     max_horizon=8),
                     register_profiler=False)
        victim = eng.submit(prompt, SamplingParams(max_new_tokens=12,
                                                   eos_token_id=eos))
        other = eng.submit(other_prompt, SamplingParams(max_new_tokens=14))
        while eng.scheduler.has_work:
            eng.step(horizon=8)
        assert victim.output_ids == ref[:stop + 1]
        assert victim.finish_reason == "eos"
        assert other.output_ids == other_ref
        s = eng.stats()
        assert s["wasted_lane_tokens"] > 0          # masked EOS tail
        assert 0.0 < s["wasted_lane_fraction"] < 1.0

    def test_slot_free_and_reuse_across_horizon_boundary(self):
        """One slot, two queued requests: the second is admitted at a
        horizon boundary into the slot the first freed mid-horizon, and
        both streams match their sequential references."""
        m = _model()
        prompts = [[5, 3, 1], [8, 8, 2, 6]]
        samp = [SamplingParams(max_new_tokens=6),
                SamplingParams(temperature=0.7, top_k=16, seed=31,
                               max_new_tokens=7)]
        seq = self._sequential(m, prompts, samp)
        eng = Engine(m, EngineConfig(num_slots=1, max_seq_len=32,
                                     max_horizon=4),
                     register_profiler=False)
        reqs = [eng.submit(p, s) for p, s in zip(prompts, samp)]
        while eng.scheduler.has_work:
            eng.step(horizon=4)
        assert [r.output_ids for r in reqs] == seq
        assert reqs[0].slot == reqs[1].slot         # the slot was reused
        c = eng.counters()
        assert c["requests_finished"] == 2
        assert eng.cache.free_slots == 1

    @pytest.mark.slow
    def test_staggered_admission_with_horizons(self):
        """Requests joining at horizon boundaries mid-stream reproduce
        sequential generation bitwise (continuous batching preserved)."""
        m = _model()
        seq = self._sequential(m, self.MIXED_PROMPTS, self.MIXED_SAMP)
        eng = Engine(m, EngineConfig(num_slots=2, max_seq_len=32,
                                     max_horizon=8),
                     register_profiler=False)
        reqs = [eng.submit(self.MIXED_PROMPTS[0], self.MIXED_SAMP[0])]
        eng.step(horizon=2)
        reqs.append(eng.submit(self.MIXED_PROMPTS[1], self.MIXED_SAMP[1]))
        eng.step(horizon=4)
        reqs.append(eng.submit(self.MIXED_PROMPTS[2], self.MIXED_SAMP[2]))
        eng.run()
        assert [r.output_ids for r in reqs] == seq

    def test_one_compile_per_horizon_bucket(self):
        """Forced horizon sequence 1,8,8,4,2,8: exactly one compile per
        distinct (horizon, table-width, spec-K) bucket, cache hits for
        every repeat.  Ragged paged attention re-buckets the static
        table width nb as the sequence grows (block_size 16, so nb steps
        1 -> 2 -> 4 here), so the compile key is the TRIPLE — the
        repeated 8s land on different nb and are real compiles, not
        hits (K stays 0 with speculative decoding off)."""
        m = _model()
        eng = Engine(m, EngineConfig(num_slots=1, max_seq_len=64,
                                     max_horizon=8),
                     register_profiler=False)
        eng.submit([3, 1, 4], SamplingParams(max_new_tokens=26))
        for h in (1, 8, 8, 4, 2, 8):
            assert eng.scheduler.has_work
            eng.step(horizon=h)
        assert not eng.scheduler.has_work
        s = eng.stats()
        assert s["horizon_buckets"] == [1, 2, 4, 8]
        assert s["decode_buckets"] == [(1, 1, 0), (2, 2, 0), (4, 2, 0),
                                       (8, 1, 0), (8, 2, 0), (8, 4, 0)]
        assert s["decode_compiles"] == len(s["decode_buckets"])
        assert s["decode_horizons"] == 6
        assert s["decode_cache_hits"] == \
            s["decode_horizons"] - s["decode_compiles"]
        assert s["decode_host_syncs"] == 6
        # 25 decode tokens out of 1+8+8+4+2+8=31 scanned lane steps
        assert s["tokens_generated"] == 26
        assert s["wasted_lane_tokens"] == 6

    def test_adaptive_horizon_growth_and_budget_cap(self):
        """Stable single-request decode grows 1->2->4->8 and the budget
        cap retires the lane exactly at a horizon edge: zero waste,
        4 dispatches for 15 decode tokens."""
        m = _model()
        eng = Engine(m, EngineConfig(num_slots=1, max_seq_len=64,
                                     max_horizon=8),
                     register_profiler=False)
        ref = Engine(m, EngineConfig(num_slots=1, max_seq_len=64,
                                     max_horizon=1),
                     register_profiler=False).generate(
            [11, 7, 5], SamplingParams(max_new_tokens=16))
        out = eng.generate([11, 7, 5], SamplingParams(max_new_tokens=16))
        assert out == ref
        s = eng.stats()
        assert s["horizon_buckets"] == [1, 2, 4, 8]
        assert s["decode_horizons"] == 4
        assert s["decode_steps"] == 15
        assert s["wasted_lane_tokens"] == 0
        assert s["wasted_lane_fraction"] == 0.0
        assert s["decode_host_syncs"] == 4

    def test_device_state_not_rebuilt_between_horizons(self):
        """Steady-state decode never re-uploads per-slot state: the
        dirty flag is set by admission only, and the device arrays the
        scan returns are fed straight back in."""
        m = _model()
        eng = Engine(m, EngineConfig(num_slots=1, max_seq_len=64,
                                     max_horizon=4),
                     register_profiler=False)
        eng.submit([1, 2, 3], SamplingParams(max_new_tokens=12))
        eng.step(horizon=2)          # admission dirtied, then uploaded
        assert eng._state_dirty is False
        first = eng._d_tokens
        eng.step(horizon=2)
        assert eng._state_dirty is False
        assert eng._d_tokens is not first    # advanced by the scan...
        eng.run()                            # ...never rebuilt from host
        assert eng._state_dirty is False


class TestSamplingPrimitives:
    def test_greedy_ignores_key(self):
        from paddle_tpu.serving.sampling import request_key, sample_token

        logits = jnp.asarray(np.random.RandomState(0).randn(64), jnp.float32)
        t0 = sample_token(logits, request_key(1, 0), 0.0, 0, 1.0)
        t1 = sample_token(logits, request_key(2, 5), 0.0, 0, 1.0)
        assert int(t0) == int(t1) == int(np.argmax(np.asarray(logits)))

    def test_top_k_restricts_support(self):
        from paddle_tpu.serving.sampling import request_key, sample_token

        rng = np.random.RandomState(3)
        logits = jnp.asarray(rng.randn(64) * 3, jnp.float32)
        top2 = set(np.argsort(np.asarray(logits))[-2:].tolist())
        draws = {int(sample_token(logits, request_key(0, i), 1.0, 2, 1.0))
                 for i in range(20)}
        assert draws <= top2

    def test_top_p_restricts_support(self):
        from paddle_tpu.serving.sampling import request_key, sample_token

        # one dominant token: tiny top_p must always return it
        logits = jnp.asarray([10.0] + [0.0] * 31, jnp.float32)
        draws = {int(sample_token(logits, request_key(0, i), 1.0, 0, 0.5))
                 for i in range(10)}
        assert draws == {0}

    def test_validate(self):
        with pytest.raises(ValueError):
            SamplingParams(max_new_tokens=0).validate()
        with pytest.raises(ValueError):
            SamplingParams(top_p=0.0).validate()


class TestPrefixCacheUnit:
    """Host-side radix-store bookkeeping: byte-budget capacity, LRU
    eviction of unpinned leaves, refcount pinning while leased."""

    @staticmethod
    def _cache(blocks, bs=4):
        # bytes_per_block = 2 (k+v) * 1 layer * bs * 1 head * 2 * 4B
        c = PrefixCache(num_layers=1, block_size=bs, kv_heads=1,
                        head_dim=2, budget_bytes=blocks * 2 * bs * 2 * 4)
        assert c.capacity == blocks
        return c

    def test_insert_then_match_is_block_granular(self):
        c = self._cache(4)
        p = [7, 3, 9, 1, 4, 4, 2, 8, 5]           # 9 tokens -> 2 blocks
        lease = c.acquire(p)
        assert lease.matched_tokens == 0           # cold cache
        assert [i for i, _ in c.insert(p, lease)] == [0, 1]
        c.release(lease)
        assert c.lookup(p + [1]) == 8              # both blocks reusable
        assert c.lookup(p) == 8                    # cap: len-1 = 8 -> 2
        assert c.lookup(p[:8]) == 4                # cap: len-1 = 7 -> 1
        assert c.lookup([1] + p) == 0              # no shared prefix

    def test_eviction_under_byte_budget(self):
        c = self._cache(2)
        a, b = [1] * 8, [2] * 8
        la = c.acquire(a)
        c.insert(a, la)
        c.release(la)
        lb = c.acquire(b)
        c.insert(b, lb)
        c.release(lb)
        s = c.stats()
        assert s["used_blocks"] <= s["capacity_blocks"] == 2
        assert s["evictions"] == 2                 # A aged out, leaf first
        assert c.lookup(a + [0]) == 0
        assert c.lookup(b + [0]) == 8

    def test_refcount_pins_leased_blocks(self):
        c = self._cache(2)
        a, b = [1] * 8, [2] * 8
        la = c.acquire(a)
        c.insert(a, la)                            # NOT released: pinned
        lb = c.acquire(b)
        assert c.insert(b, lb) == []               # nothing evictable
        assert c.stats()["evictions"] == 0
        assert c.lookup(a + [0]) == 8              # A untouched
        c.release(la)
        c.release(la)                              # idempotent unpin
        lb2 = c.acquire(b)
        assert len(c.insert(b, lb2)) == 2          # now A ages out
        assert c.lookup(b + [0]) == 8
        assert c.stats()["evictions"] == 2


class TestPrefixReuse:
    """The tentpole acceptance gates: cached-prefix + suffix-only
    prefill is bitwise-equal to full uncached prefill and to sequential
    generation; same-bucket admission is ONE compiled dispatch."""

    SHARED = [7, 3, 9, 1, 4, 4, 2, 8]              # 2 blocks of 4

    @staticmethod
    def _cfg(**kw):
        kw.setdefault("num_slots", 4)
        kw.setdefault("max_seq_len", 48)
        kw.setdefault("min_prefill_bucket", 4)
        kw.setdefault("prefix_block_size", 4)
        return EngineConfig(**kw)

    @classmethod
    def _sequential(cls, m, prompts, samp):
        outs = []
        for p, s in zip(prompts, samp):
            e = Engine(m, cls._cfg(num_slots=1, prefix_block_size=0),
                       register_profiler=False)
            outs.append(e.generate(p, s))
        return outs

    @pytest.mark.slow
    def test_shared_prefix_parity_on_off_sequential(self):
        """Warm-cache suffix prefill == cache-off prefill == one-at-a-
        time generation, bitwise, with hit/miss lanes co-batched."""
        m = _model()
        prompts = [self.SHARED + [5, 6, 7],
                   self.SHARED + [1, 2],
                   [2, 2, 1],                      # unrelated: cold miss
                   self.SHARED + [9, 9, 9, 9, 2]]
        samp = [SamplingParams(max_new_tokens=5),
                SamplingParams(temperature=0.8, top_k=20, seed=7,
                               max_new_tokens=6),
                SamplingParams(max_new_tokens=4),
                SamplingParams(temperature=0.6, top_p=0.9, seed=3,
                               max_new_tokens=5)]
        seq = self._sequential(m, prompts, samp)
        on = Engine(m, self._cfg(), register_profiler=False)
        warm = on.submit(prompts[0], samp[0])
        on.run()                                   # caches SHARED blocks
        reqs = [on.submit(p, s) for p, s in zip(prompts[1:], samp[1:])]
        on.run()
        assert warm.output_ids == seq[0]
        assert [r.output_ids for r in reqs] == seq[1:]
        assert warm.prefix_hit_tokens == 0         # cold cache
        assert reqs[0].prefix_hit_tokens == 8
        assert reqs[1].prefix_hit_tokens == 0
        assert reqs[2].prefix_hit_tokens == 8
        s = on.stats()
        assert s["prefix"]["hit_tokens"] >= 16
        assert 0.0 < s["prefix_hit_ratio"] < 1.0

        off = Engine(m, self._cfg(prefix_block_size=0),
                     register_profiler=False)
        offs = [off.submit(p, sp) for p, sp in zip(prompts, samp)]
        off.run()
        assert [r.output_ids for r in offs] == seq
        assert off.stats()["prefix"]["capacity_blocks"] == 0

    def test_exact_resubmit_and_mid_block_extension(self):
        m = _model()
        a = self.SHARED + [5, 6, 7, 1]             # 12 tokens: 3 blocks
        b = self.SHARED + [5, 6, 9, 9, 3]          # diverges IN block 3
        sp = SamplingParams(max_new_tokens=5)
        seq = self._sequential(m, [a, b], [sp, sp])
        eng = Engine(m, self._cfg(), register_profiler=False)
        assert eng.generate(a, sp) == seq[0]       # warm: caches 3 blocks
        again = eng.submit(a, sp)
        eng.run()
        assert again.output_ids == seq[0]          # exact-hit resubmit
        # 2 full-block leases (8) + a 3-token copy-on-write tail match
        # against cached block 3, capped at len(a) - 1 = 11
        assert again.prefix_hit_tokens == 11
        mid = eng.submit(b, sp)
        eng.run()
        assert mid.output_ids == seq[1]
        # 8 leased + COW tail: [5, 6] of cached [5, 6, 7, 1] matches
        assert mid.prefix_hit_tokens == 10

    @pytest.mark.slow
    def test_same_bucket_batch_is_one_dispatch(self):
        """The dispatch-count probe: N co-bucketed admissions prefill in
        ONE compiled call (plus at most one block-insert scatter)."""
        m = _model()
        prompts = [[3, 1, 4, 1, 5], [9, 2, 6, 5, 3, 5],
                   [8, 9, 7, 9, 1], [2, 3, 8, 4, 6, 2, 6]]  # buckets: 8
        samp = [SamplingParams(max_new_tokens=4, seed=i,
                               temperature=0.7 if i % 2 else 0.0)
                for i in range(4)]
        seq = self._sequential(m, prompts, samp)
        eng = Engine(m, self._cfg(), register_profiler=False)
        reqs = [eng.submit(p, s) for p, s in zip(prompts, samp)]
        eng.run()
        c = eng.counters()
        assert c["prefill_calls"] == 1             # ONE prefill dispatch
        assert c["prefill_requests"] == 4
        assert c["prefix_insert_calls"] <= 1       # plus <= one scatter
        assert eng.stats()["prefill_compiles"] == 1
        assert [r.output_ids for r in reqs] == seq

    def test_leases_released_on_retirement(self):
        m = _model()
        eng = Engine(m, self._cfg(num_slots=2), register_profiler=False)
        for p in (self.SHARED + [1], self.SHARED + [2], [4, 4, 1]):
            eng.submit(p, SamplingParams(max_new_tokens=3))
        eng.run()
        assert eng._leases == {}                   # every lease released
        stack = [eng.prefix._root]
        while stack:                               # ...and nothing pinned
            n = stack.pop()
            stack.extend(n.children.values())
            assert n.refcount == 0


class TestTTFT:
    def test_ttft_includes_queue_and_prefill(self):
        """TTFT clock starts at submit(): a request that waited for a
        slot carries its queue time inside its TTFT."""
        m = _model()
        eng = Engine(m, EngineConfig(num_slots=1, max_seq_len=32),
                     register_profiler=False)
        first = eng.submit([1, 2, 3], SamplingParams(max_new_tokens=6))
        queued = eng.submit([4, 5, 6], SamplingParams(max_new_tokens=4))
        eng.run()
        assert first.ttft is not None and queued.ttft is not None
        assert queued.queue_seconds > 0            # waited for the slot
        assert queued.ttft >= queued.queue_seconds
        s = eng.stats()
        assert s["ttft_p50_s"] >= 0.0
        assert s["ttft_p95_s"] >= s["ttft_p50_s"]


class TestPopBatch:
    """Bounded-reorder co-bucketed admission: the head always anchors,
    and no request is overtaken more than ``reorder_window`` times."""

    @staticmethod
    def _sched(window, lens):
        s = Scheduler(4, reorder_window=window)
        return s, [s.submit([0] * n, SamplingParams(max_new_tokens=2))
                   for n in lens]

    @staticmethod
    def _bucket(r):
        return r.prompt_len

    def test_contiguous_same_bucket_batches_fully(self):
        s, reqs = self._sched(2, [3, 3, 3, 3])
        assert s.pop_batch(8, bucket_of=self._bucket) == reqs
        assert s.queue_depth == 0

    def test_head_always_anchors(self):
        s, reqs = self._sched(2, [5, 3, 3, 3])
        assert s.pop_batch(8, bucket_of=self._bucket)[0] is reqs[0]

    def test_no_request_starved_past_window(self):
        w = 3
        s, reqs = self._sched(w, [3, 5, 3, 3, 3, 3, 3, 3])
        odd = reqs[1]                              # the lone bucket-5
        pops = []
        while s.queue_depth:
            pops.append(s.pop_batch(8, bucket_of=self._bucket))
            assert all(r.bypassed <= w for r in reqs)
        flat = [r for b in pops for r in b]
        assert sorted(r.request_id for r in flat) == \
            [r.request_id for r in reqs]           # nobody dropped
        # overtaken at most w times => admitted by the second batch
        k = next(i for i, b in enumerate(pops) if odd in b)
        assert k <= 1 and odd.bypassed <= w

    def test_window_zero_is_strict_fifo(self):
        s, reqs = self._sched(0, [3, 5, 3])
        assert s.pop_batch(8, bucket_of=self._bucket) == [reqs[0]]
        assert s.pop_batch(8, bucket_of=self._bucket) == [reqs[1]]
        assert s.pop_batch(8, bucket_of=self._bucket) == [reqs[2]]

    def test_free_slot_cap_and_fifo_fallback(self):
        s, reqs = self._sched(4, [3, 3, 3])
        assert s.pop_batch(2, bucket_of=self._bucket) == reqs[:2]
        assert s.pop_batch(0, bucket_of=self._bucket) == []
        assert s.pop_batch(4) == [reqs[2]]         # bucket_of=None: FIFO

class TestPagedPool:
    """Unified-pool host bookkeeping: refcounted blocks, the reserved
    scratch block 0, lazy table growth, and write routing."""

    @staticmethod
    def _pool(num_blocks=6, bs=4):
        return PagedKVPool(num_layers=1, num_blocks=num_blocks,
                           block_size=bs, kv_heads=1, head_dim=2)

    def test_refcounts_and_scratch_block(self):
        p = self._pool()
        assert p.capacity == 5 and p.free_blocks == 5
        a = p.alloc()
        assert a != 0                              # scratch never handed out
        assert p.refcount(a) == 1 and p.blocks_in_use == 1
        p.share(a)
        assert p.refcount(a) == 2
        p.release(a)
        assert p.blocks_in_use == 1                # still one ref held
        p.release(a)
        assert p.blocks_in_use == 0 and p.free_blocks == 5
        with pytest.raises(ValueError):
            p.release(a)                           # over-release is a bug
        p.release(0)                               # scratch release: no-op
        assert p.refcount(0) == 1

    def test_pool_exhaustion_returns_none(self):
        p = self._pool(num_blocks=3)
        assert p.alloc() is not None and p.alloc() is not None
        assert p.alloc() is None                   # dry, not an exception

    def test_cache_lazy_growth_and_release(self):
        c = PagedKVCache(num_layers=1, num_slots=2, max_seq_len=16,
                         block_size=4, kv_heads=1, head_dim=2)
        s = c.alloc()
        assert c.ensure_blocks(s, 5)               # 5 tokens -> 2 blocks
        row = c.tables[s]
        assert (row[:2] > 0).all() and (row[2:] == 0).all()
        assert c.pool.blocks_in_use == 2
        assert c.ensure_blocks(s, 6)               # same need: no growth
        assert c.pool.blocks_in_use == 2
        c.release_slot_blocks(s)
        assert (c.tables[s] == 0).all()
        assert c.pool.blocks_in_use == 0
        c.free(s)

    def test_lease_block_shares_refcount(self):
        c = PagedKVCache(num_layers=1, num_slots=2, max_seq_len=16,
                         block_size=4, kv_heads=1, head_dim=2)
        donor = c.pool.alloc()                     # e.g. a prefix block
        s = c.alloc()
        c.lease_block(s, 0, donor)
        assert c.pool.refcount(donor) == 2 and c.leased_blocks == 1
        c.release_slot_blocks(s)
        assert c.pool.refcount(donor) == 1         # table ref dropped...
        c.pool.release(donor)                      # ...owner ref remains

    def test_paged_write_roundtrip_and_scratch_clip(self):
        bs, kh, d = 4, 1, 2
        pool = jnp.zeros((4, bs, kh, d), jnp.float32)
        tables = jnp.array([[1, 2]], jnp.int32)    # one lane, two blocks
        new = jnp.arange(2 * kh * d, dtype=jnp.float32).reshape(1, 2, kh, d)
        # write 2 tokens straddling the block boundary (pos 3, 4)
        out = np.asarray(paged_write(pool, new, tables, jnp.array([3])))
        assert (out[1, 3] == new[0, 0]).all()      # block 1, offset 3
        assert (out[2, 0] == new[0, 1]).all()      # block 2, offset 0
        # out-of-table positions route to scratch block 0, real blocks
        # untouched (this is what makes bench overflow writes harmless)
        far = np.asarray(paged_write(pool, new, tables, jnp.array([8])))
        assert (far[1:] == 0).all()


class TestPagedAttention:
    """The XLA fallback is the parity reference: bitwise-invariant to
    the static table width and equal to dense softmax attention."""

    @staticmethod
    def _case(b=2, s=1, qh=4, kh=2, d=8, bs=4, nb=3, seed=0):
        r = np.random.RandomState(seed)
        q = jnp.asarray(r.randn(b, s, qh, d).astype(np.float32))
        num_blocks = 1 + b * nb
        k = jnp.asarray(r.randn(num_blocks, bs, kh, d).astype(np.float32))
        v = jnp.asarray(r.randn(num_blocks, bs, kh, d).astype(np.float32))
        tables = jnp.asarray(
            1 + np.arange(b * nb, dtype=np.int32).reshape(b, nb))
        pos = jnp.asarray(np.array([5, 9], np.int32)[:b])
        return q, k, v, tables, pos

    def test_bitwise_invariant_to_table_width(self):
        """Padding the table with scratch columns must not change ONE
        bit of the output — this is what lets the engine re-bucket nb
        as sequences grow without breaking decode determinism."""
        q, k, v, tables, pos = self._case()
        out = np.asarray(_xla_paged_attention(q, k, v, tables, pos))
        for pad in (1, 3, 8):
            wide = jnp.concatenate(
                [tables, jnp.zeros((tables.shape[0], pad), jnp.int32)],
                axis=1)
            out_w = np.asarray(_xla_paged_attention(q, k, v, wide, pos))
            np.testing.assert_array_equal(out, out_w)

    def test_matches_dense_attention(self):
        q, k, v, tables, pos = self._case(s=1)
        b, s, qh, d = q.shape
        bs, kh = k.shape[1], k.shape[2]
        g = qh // kh
        out = np.asarray(_xla_paged_attention(q, k, v, tables, pos))
        kn, vn, tn, pn = (np.asarray(x) for x in (k, v, tables, pos))
        for i in range(b):
            keys = kn[tn[i]].reshape(-1, kh, d)[:pn[i] + 1]   # [T, KH, D]
            vals = vn[tn[i]].reshape(-1, kh, d)[:pn[i] + 1]
            for h in range(qh):
                qv = np.asarray(q)[i, 0, h] / np.sqrt(d)
                sc = keys[:, h // g] @ qv
                w = np.exp(sc - sc.max())
                w /= w.sum()
                ref = w @ vals[:, h // g]
                np.testing.assert_allclose(out[i, 0, h], ref, atol=1e-5)

    def test_multi_token_prefill_is_causal(self):
        """s > 1 (prefill): each query row attends to keys <= its own
        position only; row s-1 must equal a fresh s=1 decode query."""
        q, k, v, tables, pos = self._case(s=3)
        pos0 = pos - 2                             # 3 queries end at pos
        out = np.asarray(_xla_paged_attention(q, k, v, tables, pos0))
        last = np.asarray(_xla_paged_attention(
            q[:, 2:], k, v, tables, pos0 + 2))
        np.testing.assert_array_equal(out[:, 2:], last)


class TestPreemptionSwap:
    """Preempt-and-resume: an idle lane's blocks are released, the
    request requeues at the FRONT, and re-admission (re-prefill of
    prompt + generated-so-far) reproduces its stream bitwise."""

    @staticmethod
    def _cfg(**kw):
        kw.setdefault("num_slots", 2)
        kw.setdefault("max_seq_len", 32)
        kw.setdefault("max_horizon", 4)
        kw.setdefault("prefix_block_size", 4)
        kw.setdefault("prefix_cache_bytes", 0)     # isolate pool effects
        return EngineConfig(**kw)

    @classmethod
    def _sequential(cls, m, prompts, samp):
        return [Engine(m, cls._cfg(num_slots=1), register_profiler=False)
                .generate(p, s) for p, s in zip(prompts, samp)]

    @pytest.mark.slow
    def test_explicit_preempt_resume_parity(self):
        m = _model()
        prompts = [[3, 1, 4, 1, 5], [9, 2, 6]]
        samp = [SamplingParams(max_new_tokens=10),
                SamplingParams(temperature=0.8, top_k=20, seed=11,
                               max_new_tokens=10)]
        seq = self._sequential(m, prompts, samp)
        eng = Engine(m, self._cfg(), register_profiler=False)
        reqs = [eng.submit(p, s) for p, s in zip(prompts, samp)]
        eng.step(horizon=2)                        # both lanes decoding
        victim = reqs[1]
        held = int(np.count_nonzero(eng.cache.tables[victim.slot]))
        assert held > 0
        before = eng.pool.blocks_in_use
        eng.preempt(victim)
        assert victim.status == "waiting" and victim.slot is None
        assert eng.scheduler.queue[0] is victim    # front of the queue
        assert eng.pool.blocks_in_use == before - held
        eng.run()                                  # re-admit + finish
        assert [r.output_ids for r in reqs] == seq
        assert eng.counters()["preemptions"] == 1
        assert eng.pool.blocks_in_use == 0         # nothing leaked

    @pytest.mark.slow
    def test_auto_preempt_under_block_pressure(self):
        """An explicitly undersized pool: decode growth runs the pool
        dry, the engine preempts the youngest lane, and every request
        still finishes with sequential parity."""
        m = _model()
        prompts = [[7, 3, 9, 1, 4, 4, 2, 8], [5, 6, 7, 8, 9, 1, 2, 3]]
        samp = [SamplingParams(max_new_tokens=12) for _ in prompts]
        seq = self._sequential(m, prompts, samp)
        # capacity 7 blocks of 4: both admit (2+2) but cannot both grow
        # to 20 tokens (5+5)
        eng = Engine(m, self._cfg(kv_pool_blocks=8),
                     register_profiler=False)
        reqs = [eng.submit(p, s) for p, s in zip(prompts, samp)]
        eng.run()
        assert [r.output_ids for r in reqs] == seq
        assert eng.counters()["preemptions"] >= 1
        assert eng.pool.blocks_in_use == 0

    def test_block_leak_invariant(self):
        """After every request retires: zero leased table entries, and
        the only live blocks are the prefix cache's (none when it's
        off).  This is the CI smoke invariant."""
        m = _model()
        prompts = [[1, 2, 3, 4, 5], [1, 2, 3, 4, 5, 6, 7], [9, 9]]
        for bs, budget in ((4, 0), (4, 1 << 20)):
            eng = Engine(m, self._cfg(num_slots=2, prefix_block_size=bs,
                                      prefix_cache_bytes=budget),
                         register_profiler=False)
            for p in prompts:
                eng.submit(p, SamplingParams(max_new_tokens=4))
            eng.run()
            s = eng.stats()["kv_pool"]
            assert s["leased_blocks"] == 0
            assert s["blocks_in_use"] == s["cached_blocks"]
            if budget == 0:
                assert s["blocks_in_use"] == 0


class TestPopBatchResume:
    """The ``resumed`` head-anchor exemption: re-admitting a preempted
    request restores FIFO order rather than violating it, so it must
    neither spend the reorder window nor charge bypassed counters —
    even from behind requests that are at their overtake cap."""

    @staticmethod
    def _sched(window, lens):
        s = Scheduler(4, reorder_window=window)
        return s, [s.submit([0] * n, SamplingParams(max_new_tokens=2))
                   for n in lens]

    @staticmethod
    def _bucket(r):
        return r.prompt_len

    def test_resumed_admitted_from_behind_capped_skips(self):
        # window 1: normally nothing same-bucket can be admitted from
        # behind a skipped request at index >= 1
        s, reqs = self._sched(1, [3, 5, 3])
        reqs[2].resumed = True
        batch = s.pop_batch(8, bucket_of=self._bucket)
        assert batch == [reqs[0], reqs[2]]
        assert reqs[1].bypassed == 0       # exemption: no overtake charged

    def test_resumed_does_not_consume_window_for_others(self):
        # [A(3), B(5), C(5), D(3,resumed), E(3)] with window 2: D rides
        # the exemption, but E is a genuine overtake past the window cap
        s, reqs = self._sched(2, [3, 5, 5, 3, 3])
        reqs[3].resumed = True
        batch = s.pop_batch(8, bucket_of=self._bucket)
        assert batch == [reqs[0], reqs[3]]
        assert reqs[1].bypassed == 0 and reqs[2].bypassed == 0

    def test_non_resumed_same_shape_is_still_bounded(self):
        # identical queue WITHOUT the resumed flag: the bucket-3 request
        # behind the skip is not admitted (control for the test above)
        s, reqs = self._sched(1, [3, 5, 3])
        batch = s.pop_batch(8, bucket_of=self._bucket)
        assert batch == [reqs[0]]

    def test_requeue_front_marks_and_start_clears(self):
        s = Scheduler(2)
        r = s.submit([1, 2, 3], SamplingParams(max_new_tokens=2))
        assert r.resumed is False
        s.start(r, 0)
        s.requeue_front(r)
        assert r.resumed is True and s.queue[0] is r
        s.start(r, 1)
        assert r.resumed is False

    def test_resume_ordering_under_load(self):
        """Preempt under a full queue: the resumed request re-admits
        FIRST (front of queue, head anchor) and co-buckets with same-
        bucket resumes; queued newcomers never jump it."""
        s, reqs = self._sched(2, [3, 3, 5, 3])
        s.start(reqs[0], 0)
        s.start(reqs[1], 1)
        s.queue = __import__("collections").deque(reqs[2:])
        s.requeue_front(reqs[1])
        s.requeue_front(reqs[0])
        batch = s.pop_batch(2, bucket_of=self._bucket)
        assert batch == [reqs[0], reqs[1]]  # both resumes, before all
        assert reqs[2].bypassed == 0 and reqs[3].bypassed == 0


class TestDrafter:
    """draft_tokens unit behavior: the -1 sentinel contract and the
    runway-then-recency match ranking."""

    @staticmethod
    def _draft(row, length, k=3, ngram=2, width=16):
        from paddle_tpu.serving import draft_tokens

        hist = np.zeros((1, width), np.int32)
        hist[0, :len(row)] = row
        out = draft_tokens(jnp.asarray(hist),
                           jnp.asarray([length], jnp.int32), k, ngram)
        return np.asarray(out)[0].tolist()

    def test_history_shorter_than_ngram_plus_one_is_sentinel(self):
        assert self._draft([7, 7], 2) == [-1, -1, -1]
        from paddle_tpu.serving import draft_tokens
        out = draft_tokens(jnp.zeros((2, 2), jnp.int32),
                           jnp.asarray([2, 2], jnp.int32), 4)
        assert np.asarray(out).tolist() == [[-1] * 4] * 2

    def test_no_earlier_match_is_sentinel(self):
        assert self._draft([1, 2, 3, 4, 5, 6], 6) == [-1, -1, -1]

    def test_match_with_full_runway_drafts_continuation(self):
        # suffix [1,2] matched at start 0; continuation 3, 9, 1
        assert self._draft([1, 2, 3, 9, 1, 2], 6) == [3, 9, 1]

    def test_runway_beats_recency(self):
        # suffix [1,2] occurs at 0 (runway 5) and 3 (runway 2): the
        # early match drafts k=3 tokens, the late one only 2
        assert self._draft([1, 2, 3, 1, 2, 1, 2], 7) == [3, 1, 2]

    def test_recency_breaks_runway_ties(self):
        # both matches have >= k runway; the later one wins
        assert self._draft([1, 2, 5, 5, 5, 1, 2, 8, 8, 8, 1, 2], 12) \
            == [8, 8, 8]

    def test_drafts_clamped_to_known_history(self):
        # the only match sits 2 tokens from the end: the third draft
        # would read past known history and must be the sentinel
        assert self._draft([7, 1, 2, 1, 2], 5) == [1, 2, -1]

    def test_tail_never_matches_itself(self):
        # the trailing window is the only occurrence: no proposal
        assert self._draft([5, 1, 2], 3) == [-1, -1, -1]

    def test_lanes_are_independent(self):
        from paddle_tpu.serving import draft_tokens

        hist = np.zeros((2, 16), np.int32)
        hist[0, :6] = [1, 2, 3, 9, 1, 2]
        hist[1, :6] = [4, 5, 6, 7, 8, 9]
        out = draft_tokens(jnp.asarray(hist),
                           jnp.asarray([6, 6], jnp.int32), 3)
        assert np.asarray(out).tolist() == [[3, 9, 1], [-1, -1, -1]]

    def test_validates_static_args(self):
        from paddle_tpu.serving import draft_tokens

        h = jnp.zeros((1, 8), jnp.int32)
        with pytest.raises(ValueError):
            draft_tokens(h, jnp.asarray([4], jnp.int32), 0)
        with pytest.raises(ValueError):
            draft_tokens(h, jnp.asarray([4], jnp.int32), 2, ngram=0)


class TestSpeculativeDecode:
    """Self-drafting speculative decoding: every K and every workload
    must reproduce the spec_k=0 stream bitwise — drafting is a pure
    perf lever, invisible in outputs, PRNG, EOS, and budgets."""

    REP_PROMPT = [3, 17, 42, 9] * 4          # repeated pattern
    RND_PROMPT = [11, 62, 97, 23, 5, 81, 40, 108]
    #: cached sequential K=0 greedy stream for REP_PROMPT (computed
    #: once; greedy decode of a prefix is a prefix of the stream, so
    #: every shorter-budget reference is a slice of this one)
    _REP_STREAM = None

    @classmethod
    def _rep_stream(cls, m, n):
        if cls._REP_STREAM is None:
            sp = SamplingParams(max_new_tokens=16)
            ref, _ = cls._run(m, cls.REP_PROMPT, sp, 0)
            cls._REP_STREAM = list(ref.output_ids)
        assert n <= len(cls._REP_STREAM)
        return cls._REP_STREAM[:n]

    @staticmethod
    def _engine(m, k, adaptive=False, **kw):
        kw.setdefault("num_slots", 2)
        kw.setdefault("max_seq_len", 48)
        kw.setdefault("max_horizon", 4)
        return Engine(m, EngineConfig(spec_k=k, spec_adaptive=adaptive,
                                      **kw), register_profiler=False)

    @classmethod
    def _run(cls, m, prompt, sp, k, adaptive=False, **kw):
        eng = cls._engine(m, k, adaptive, **kw)
        req = eng.submit(list(prompt), sp)
        while eng.scheduler.has_work:
            eng.step()
        stats = eng.stats()
        eng.close()
        return req, stats

    def test_greedy_parity_repetitive_prompt(self):
        m = _model()
        sp = SamplingParams(max_new_tokens=16)
        ref = self._rep_stream(m, 16)
        out, stats = self._run(m, self.REP_PROMPT, sp, 4)
        assert out.output_ids == ref
        assert stats["spec"]["draft_tokens"] > 0

    def test_greedy_parity_random_prompt(self):
        m = _model()
        sp = SamplingParams(max_new_tokens=16)
        ref, _ = self._run(m, self.RND_PROMPT, sp, 0)
        out, _ = self._run(m, self.RND_PROMPT, sp, 4)
        assert out.output_ids == ref.output_ids

    def test_parity_across_draft_widths(self):
        m = _model()
        sp = SamplingParams(max_new_tokens=12)
        ref = self._rep_stream(m, 12)
        # extreme widths; K=4 is exercised by every other test here
        for k in (1, 8):
            out, _ = self._run(m, self.REP_PROMPT, sp, k)
            assert out.output_ids == ref, f"K={k} diverged"

    def test_seeded_sampling_parity(self):
        m = _model()
        sp = SamplingParams(temperature=0.9, top_k=20, top_p=0.9,
                            seed=7, max_new_tokens=10)
        ref, _ = self._run(m, self.REP_PROMPT, sp, 0)
        out, _ = self._run(m, self.REP_PROMPT, sp, 4)
        assert out.output_ids == ref.output_ids

    def test_mid_window_eos(self):
        """EOS landing inside a verify window must truncate the emitted
        run exactly where sequential decode stops."""
        m = _model()
        sp = SamplingParams(max_new_tokens=12)
        stream = self._rep_stream(m, 12)
        # an EOS whose FIRST occurrence is interior (not window-aligned)
        idx = next(i for i in range(2, 9) if stream.index(stream[i]) == i)
        eos = stream[idx]
        sp_eos = SamplingParams(max_new_tokens=12, eos_token_id=eos)
        out, _ = self._run(m, self.REP_PROMPT, sp_eos, 4)
        assert out.output_ids == stream[:idx + 1]
        assert out.finish_reason == "eos"

    def test_budget_truncation_mid_window(self):
        """max_new_tokens that is no multiple of any window size: the
        lane must stop at EXACTLY the budget even when the accepted
        window would overshoot it."""
        m = _model()
        for budget in (1, 7):
            sp = SamplingParams(max_new_tokens=budget)
            ref = self._rep_stream(m, budget)
            out, _ = self._run(m, self.REP_PROMPT, sp, 4)
            assert out.output_ids == ref
            assert len(out.output_ids) == budget
            assert out.finish_reason == "length"

    @pytest.mark.slow
    def test_staggered_admission_parity(self):
        """Requests joining at horizon boundaries mid-flight see the
        same streams as sequential runs, drafting included."""
        m = _model()
        prompts = [self.REP_PROMPT, [2, 7, 4, 11], [9, 9, 9, 9, 9, 9]]
        samp = [SamplingParams(max_new_tokens=10),
                SamplingParams(max_new_tokens=8),
                SamplingParams(temperature=0.9, top_k=16, seed=3,
                               max_new_tokens=9)]
        seq = []
        for p, s in zip(prompts, samp):
            r, _ = self._run(m, p, s, 0)
            seq.append(r.output_ids)
        eng = self._engine(m, 4)
        reqs = [eng.submit(prompts[0], samp[0])]
        eng.step()
        reqs.append(eng.submit(prompts[1], samp[1]))
        eng.step()
        reqs.append(eng.submit(prompts[2], samp[2]))
        while eng.scheduler.has_work:
            eng.step()
        eng.close()
        assert [r.output_ids for r in reqs] == seq

    @pytest.mark.slow
    def test_preempt_resume_parity_with_spec(self):
        """Preemption mid-draft: blocks released, request re-admitted
        (resumed exemption), stream still bitwise-sequential."""
        m = _model()
        prompts = [self.REP_PROMPT, [9, 2, 6, 1]]
        samp = [SamplingParams(max_new_tokens=10),
                SamplingParams(max_new_tokens=10)]
        seq = []
        for p, s in zip(prompts, samp):
            r, _ = self._run(m, p, s, 0, num_slots=1)
            seq.append(r.output_ids)
        eng = self._engine(m, 4)
        reqs = [eng.submit(p, s) for p, s in zip(prompts, samp)]
        eng.step(horizon=2)
        eng.preempt(reqs[1])
        assert reqs[1].resumed is True
        while eng.scheduler.has_work:
            eng.step()
        eng.close()
        assert [r.output_ids for r in reqs] == seq

    def test_one_compile_per_horizon_width_k_bucket(self):
        """Decode programs are keyed by (horizon, table-width, K): one
        compile per distinct triple, cache hits for every repeat."""
        m = _model()
        eng = self._engine(m, 4, num_slots=1)
        sp = SamplingParams(max_new_tokens=8)
        for _ in range(2):
            eng.submit(self.REP_PROMPT, sp)
            while eng.scheduler.has_work:
                eng.step(horizon=4)
        s = eng.stats()
        eng.close()
        assert all(b[2] == 4 for b in s["decode_buckets"])
        assert s["decode_compiles"] == len(s["decode_buckets"])
        assert s["decode_cache_hits"] == \
            s["decode_horizons"] - s["decode_compiles"]

    def test_accept_stats_exported(self):
        m = _model()
        sp = SamplingParams(max_new_tokens=16)
        _, s = self._run(m, self.REP_PROMPT, sp, 4)
        spec = s["spec"]
        assert spec["k"] == 4 and spec["adaptive"] is False
        assert spec["draft_tokens"] > 0
        assert 0.0 <= spec["accept_rate"] <= 1.0
        hist = spec["accept_len_hist"]
        windows = sum(hist.values())
        assert windows > 0
        assert all(1 <= n <= 5 for n in hist)      # emits 1..K+1
        got = sum(n * c for n, c in hist.items())
        assert abs(spec["mean_accept_len"] - got / windows) < 1e-9
        # counters() mirrors the totals
        eng = self._engine(m, 4)
        req = eng.submit(self.REP_PROMPT, sp)
        while eng.scheduler.has_work:
            eng.step()
        c = eng.counters()
        eng.close()
        assert c["spec_draft_tokens"] == eng.stats()["spec"]["draft_tokens"]
        assert "spec_accept_rate" in c
        assert req.output_ids  # the run actually decoded

    def test_adaptive_gate_shrinks_dispatch_to_k0(self):
        """A lane whose drafts never land falls below the acceptance
        floor, flips its gate off, and — when no gated lane remains —
        the next dispatch compiles/reuses the plain K=0 program."""
        m = _model()
        sp = SamplingParams(max_new_tokens=16)
        ref, _ = self._run(m, self.RND_PROMPT, sp, 0)
        eng = self._engine(m, 4, adaptive=True, num_slots=1)
        eng.config.spec_accept_floor = 1.1         # unreachable: always off
        req = eng.submit(self.RND_PROMPT, sp)
        while eng.scheduler.has_work:
            eng.step()
        s = eng.stats()
        eng.close()
        assert req.output_ids == ref.output_ids    # parity through the flip
        ks = {b[2] for b in s["decode_buckets"]}
        assert 0 in ks and 4 in ks                 # shrank mid-request
        assert all(e < 1.0 for e in s["spec"]["lane_accept_ema"][:1])

    def test_k0_engine_reports_no_spec_activity(self):
        m = _model()
        sp = SamplingParams(max_new_tokens=8)
        _, s = self._run(m, self.REP_PROMPT, sp, 0)
        assert s["spec"]["draft_tokens"] == 0
        assert s["spec"]["accepted_tokens"] == 0
        assert s["spec"]["accept_len_hist"] == {}
        assert all(b[2] == 0 for b in s["decode_buckets"])

    def test_spec_with_prefix_cache_and_gqa(self):
        """Drafting composes with prefix-cache hits and GQA models."""
        paddle.seed(3)
        m = GPTForCausalLM(TINY_GQA)
        m.eval()
        sp = SamplingParams(max_new_tokens=10)
        prompt = [5, 9, 5, 9, 5, 9, 5, 9]
        ref, _ = self._run(m, prompt, sp, 0,
                           prefix_cache_bytes=1 << 20)
        out, _ = self._run(m, prompt, sp, 4,
                           prefix_cache_bytes=1 << 20)
        assert out.output_ids == ref.output_ids


class TestPagedAttentionVerify:
    """Multi-position (verify-window) queries through the paged kernel:
    each row must equal the single-token decode at that position, and
    the whole window must match a dense causal reference."""

    @staticmethod
    def _case(b=2, s=1, qh=4, kh=2, d=8, bs=4, nb=4, seed=0,
              pos_vals=(9, 13)):
        r = np.random.RandomState(seed)
        q = jnp.asarray(r.randn(b, s, qh, d).astype(np.float32))
        num_blocks = 1 + b * nb
        k = jnp.asarray(r.randn(num_blocks, bs, kh, d).astype(np.float32))
        v = jnp.asarray(r.randn(num_blocks, bs, kh, d).astype(np.float32))
        tables = jnp.asarray(
            1 + np.arange(b * nb, dtype=np.int32).reshape(b, nb))
        pos = jnp.asarray(np.array(pos_vals, np.int32)[:b])
        return q, k, v, tables, pos

    @pytest.mark.parametrize("w", [1, 2, 4, 8])
    def test_window_rows_bitwise_match_single_queries(self, w):
        """Row j of an s=w window at base position p equals an s=1 call
        at position p+j — the property that makes verify-as-prefill
        bitwise-safe, across block boundaries (bs=4, windows straddle
        them for w >= 2)."""
        q, k, v, tables, pos = self._case(s=w)
        base = pos - (w - 1)
        out = np.asarray(_xla_paged_attention(q, k, v, tables, base))
        for j in range(w):
            one = np.asarray(_xla_paged_attention(
                q[:, j:j + 1], k, v, tables, base + j))
            np.testing.assert_array_equal(out[:, j:j + 1], one)

    def test_window_matches_dense_causal_reference(self):
        w = 4
        q, k, v, tables, pos = self._case(s=w)
        base = pos - (w - 1)
        out = np.asarray(_xla_paged_attention(q, k, v, tables, base))
        kn, vn, tn, bn = (np.asarray(x) for x in (k, v, tables, base))
        b, _, qh, d = q.shape
        kh = kn.shape[2]
        g = qh // kh
        for i in range(b):
            keys = kn[tn[i]].reshape(-1, kh, d)
            vals = vn[tn[i]].reshape(-1, kh, d)
            for j in range(w):
                t = int(bn[i]) + j + 1             # visible prefix length
                for h in range(qh):
                    qv = np.asarray(q)[i, j, h] / np.sqrt(d)
                    sc = keys[:t, h // g] @ qv
                    ww = np.exp(sc - sc.max())
                    ww /= ww.sum()
                    ref = ww @ vals[:t, h // g]
                    np.testing.assert_allclose(out[i, j, h], ref,
                                               atol=1e-5)

    def test_shared_prefix_cow_tail_blocks(self):
        """Two lanes share a prefix block (COW-style table aliasing);
        their divergent tails must not bleed into each other, and each
        lane's window must equal a private-copy run."""
        r = np.random.RandomState(1)
        bs, kh, d, qh, w = 4, 2, 8, 4, 2
        k = jnp.asarray(r.randn(6, bs, kh, d).astype(np.float32))
        v = jnp.asarray(r.randn(6, bs, kh, d).astype(np.float32))
        q = jnp.asarray(r.randn(2, w, qh, d).astype(np.float32))
        # lanes alias block 1 as their shared prefix, own tails 2/3
        shared = jnp.asarray([[1, 2], [1, 3]], jnp.int32)
        base = jnp.asarray([4, 4], jnp.int32)      # window rows 4,5
        out_shared = np.asarray(
            _xla_paged_attention(q, k, v, shared, base))
        # private copies of the prefix (blocks 4/5 = copies of block 1)
        k2 = k.at[4].set(k[1]).at[5].set(k[1])
        v2 = v.at[4].set(v[1]).at[5].set(v[1])
        private = jnp.asarray([[4, 2], [5, 3]], jnp.int32)
        out_private = np.asarray(
            _xla_paged_attention(q, k2, v2, private, base))
        np.testing.assert_array_equal(out_shared, out_private)


class TestPallasMultiToken:
    """The generalized Pallas ragged kernel (interpret mode on CPU) vs
    the XLA fallback for every query window size s >= 1, on fp32 and
    int8-quantized pools, including COW-aliased tables.  The kernel's
    tile geometry (int8 pools, windows too long to stream) runs the
    fallback's exact per-block recurrence; its streaming geometry (every
    fp case of these toy shapes) makes one softmax update a chunk of
    blocks, the same sums in another order.  Either way the kernel and
    the fallback's scan compile separately, so raw outputs match to the
    last ulps, asserted here with a tight tolerance.  Whole-engine
    stream equality under ``PADDLE_TPU_PAGED_ATTN=pallas`` is bitwise
    where both sides run one geometry (see
    ``test_tp2_chunked_prefill_pallas_kernel_parity``) and holds to a
    near-tie where they mix two (``test_engine_mixed_geometries``).
    Kernel-vs-kernel comparisons (same program, different tables) stay
    exact."""

    ATOL = 1e-5

    @staticmethod
    def _case(b=2, s=1, qh=4, kh=2, d=8, bs=4, nb=4, seed=0,
              pos_vals=(9, 13)):
        return TestPagedAttentionVerify._case(b, s, qh, kh, d, bs, nb,
                                              seed, pos_vals)

    @pytest.mark.parametrize("w", [1, 2, 4, 8])
    def test_kernel_matches_fallback(self, w):
        q, k, v, tables, pos = self._case(s=w)
        base = pos - (w - 1)
        ref = np.asarray(_xla_paged_attention(q, k, v, tables, base))
        out = np.asarray(_pallas_paged_attention(q, k, v, tables, base,
                                                 interpret=True))
        np.testing.assert_allclose(out, ref, rtol=0, atol=self.ATOL)

    # lanes of the serving cell's decode shape (KH=8, G=4, D=128, blocks
    # of 16, bf16), by the deepest position each holds: both sides of a
    # block edge, a lane one token long, a retired lane (position 0, a
    # table of scratch entries only), and live-block counts on both
    # sides of a multiple of the 8 blocks a streaming cell takes
    REAL_WIDTH_LANES = {
        "below_block_edge": (15, 127, 255, 16 * 37 - 1),
        "at_block_edge": (16, 128, 256, 16 * 37),
        "length_one_and_retired": (0, None, 1, 300),
        "ragged_last_chunk": (16 * 7, 16 * 8, 16 * 9 - 1, 16 * 11 + 5),
        "table_full": (0, 500, None, -1),
    }

    @pytest.mark.parametrize("nb", [64, 128])
    @pytest.mark.parametrize("lanes", sorted(REAL_WIDTH_LANES))
    def test_kernel_matches_fallback_real_widths(self, lanes, nb):
        kh, g, d, bs = 8, 4, 128, 16
        r = np.random.RandomState(7)
        held = [nb * bs - 1 if p == -1 else p
                for p in self.REAL_WIDTH_LANES[lanes]]
        tables = np.zeros((len(held), nb), np.int32)
        n_blocks = 1                                 # block 0 is scratch
        for i, p in enumerate(held):
            if p is not None:
                live = p // bs + 1
                tables[i, :live] = n_blocks + r.permutation(live)
                n_blocks += live
        pos = jnp.asarray([p or 0 for p in held], jnp.int32)
        q = jnp.asarray(r.randn(len(held), 1, kh * g, d), jnp.bfloat16)
        k = jnp.asarray(r.randn(n_blocks, bs, kh, d), jnp.bfloat16)
        v = jnp.asarray(r.randn(n_blocks, bs, kh, d), jnp.bfloat16)
        tables = jnp.asarray(tables)
        ref = np.asarray(_xla_paged_attention(q, k, v, tables, pos),
                         np.float32)
        out = np.asarray(_pallas_paged_attention(q, k, v, tables, pos,
                                                 interpret=True),
                         np.float32)
        # bf16 outputs of two programs: two ulps
        np.testing.assert_allclose(out, ref, rtol=2.0 ** -7,
                                   atol=2.0 ** -9)

    def test_kernel_traced_once_a_program(self):
        """Behind its module-level jit a stack of layers traces the
        kernel once (``paged_attn.trace`` counts a trace); the unwrapped
        function traces a layer, and the results are bitwise equal."""
        from paddle_tpu.observability import metrics

        q, k, v, tables, pos = self._case(b=3, nb=5, pos_vals=(9, 13, 2))
        # whatever this process traced before, the count starts cold
        _pallas_paged_attention.clear_cache()

        def stack(attention, q):
            for _ in range(4):
                q = attention(q, k, v, tables, pos, interpret=True)
            return q

        def traces():
            return metrics.value("paged_attn.trace", path="stream",
                                 blocks_per_cell=8)

        before = traces()
        out = jax.jit(functools.partial(stack, _pallas_paged_attention))(q)
        assert traces() - before == 1
        unwrapped = jax.jit(functools.partial(
            stack, _pallas_paged_attention.__wrapped__))(q)
        assert traces() - before == 1 + 4
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(unwrapped))

    def test_engine_mixed_geometries(self, monkeypatch):
        """An engine whose prefill windows tile while its decode steps
        stream.  At the toy widths everything fits the real VMEM budget
        and streams, so the budget is cut to put the line between s=2
        and the first prefill bucket, where the serving widths have it
        between s=9 and their buckets.  Preemption then replays through
        the tile geometry KV that decode wrote streaming: the
        geometries differ in the output's last ulps, so this is no
        bitwise guarantee, and the greedy streams of this toy model
        still equal the scan-routed engine's, preempted or not."""
        import sys

        from paddle_tpu.observability import metrics

        # the package exports the router under the module's own name
        pa = sys.modules["paddle_tpu.serving.paged_attention"]

        def traces(path, blocks):
            return metrics.value("paged_attn.trace", path=path,
                                 blocks_per_cell=blocks)

        def run(preempt):
            eng = Engine(m, cfg, register_profiler=False)
            reqs = [eng.submit(p, s) for p, s in zip(prompts, samp)]
            eng.step(horizon=2)                    # both lanes decoding
            if preempt:
                eng.preempt(reqs[1])
            eng.run()
            assert eng.counters()["preemptions"] == int(preempt)
            return [r.output_ids for r in reqs]

        m = _model(TINY_GQA)
        cfg = EngineConfig(num_slots=2, max_seq_len=32, max_horizon=4,
                           prefix_block_size=4, prefix_cache_bytes=0)
        prompts = [[3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5], [9, 2, 6, 5, 3, 5, 8,
                                                       9, 7]]
        samp = [SamplingParams(max_new_tokens=10)] * 2
        ref = run(preempt=False)                   # the scan, off TPU
        monkeypatch.setenv("PADDLE_TPU_PAGED_ATTN", "pallas")
        monkeypatch.setattr(pa, "VMEM_BUDGET_BYTES", 64 * 1024)
        # the module-level jit keys on avals alone: drop what it traced
        # under the real budget, and afterwards what it traces under this
        _pallas_paged_attention.clear_cache()
        before = traces("tile", 1), traces("stream", 8)
        try:
            assert run(preempt=False) == ref
            assert run(preempt=True) == ref
        finally:
            _pallas_paged_attention.clear_cache()
        assert traces("tile", 1) > before[0]       # the prefill buckets
        assert traces("stream", 8) > before[1]     # the decode programs

    @pytest.mark.parametrize("w", [1, 4])
    def test_kernel_matches_fallback_quantized(self, w):
        q, kf, vf, tables, pos = self._case(s=w)
        r = np.random.RandomState(3)
        k = jnp.asarray(r.randint(-127, 128, kf.shape).astype(np.int8))
        v = jnp.asarray(r.randint(-127, 128, vf.shape).astype(np.int8))
        ks = jnp.asarray(
            r.uniform(0.01, 0.1, kf.shape[:2]).astype(np.float32))
        vs = jnp.asarray(
            r.uniform(0.01, 0.1, vf.shape[:2]).astype(np.float32))
        base = pos - (w - 1)
        ref = np.asarray(
            _xla_paged_attention(q, k, v, tables, base, ks, vs))
        out = np.asarray(_pallas_paged_attention(q, k, v, tables, base,
                                                 ks, vs, interpret=True))
        np.testing.assert_allclose(out, ref, rtol=0, atol=self.ATOL)

    def test_kernel_cow_aliased_tail_blocks(self):
        """Two lanes alias a shared prefix block through their tables;
        the kernel must read it once per lane without bleed, matching
        both the fallback and a private-copy run bitwise."""
        r = np.random.RandomState(1)
        bs, kh, d, qh, w = 4, 2, 8, 4, 2
        k = jnp.asarray(r.randn(6, bs, kh, d).astype(np.float32))
        v = jnp.asarray(r.randn(6, bs, kh, d).astype(np.float32))
        q = jnp.asarray(r.randn(2, w, qh, d).astype(np.float32))
        shared = jnp.asarray([[1, 2], [1, 3]], jnp.int32)
        base = jnp.asarray([4, 4], jnp.int32)
        out = np.asarray(_pallas_paged_attention(q, k, v, shared, base,
                                                 interpret=True))
        ref = np.asarray(_xla_paged_attention(q, k, v, shared, base))
        np.testing.assert_allclose(out, ref, rtol=0, atol=self.ATOL)
        k2 = k.at[4].set(k[1]).at[5].set(k[1])
        v2 = v.at[4].set(v[1]).at[5].set(v[1])
        private = jnp.asarray([[4, 2], [5, 3]], jnp.int32)
        out_p = np.asarray(_pallas_paged_attention(q, k2, v2, private,
                                                   base, interpret=True))
        # same compiled kernel, different tables: aliasing itself is
        # BITWISE-neutral
        np.testing.assert_array_equal(out, out_p)

    def test_router_env_override_runs_kernel_on_cpu(self, monkeypatch):
        """``PADDLE_TPU_PAGED_ATTN=pallas`` off-TPU routes to the kernel
        in interpret mode — the switch the whole-engine and shard_map
        kernel tests ride — and stays bitwise with the fallback."""
        from paddle_tpu.serving.paged_attention import paged_attention

        q, k, v, tables, pos = self._case(s=2)
        base = pos - 1
        monkeypatch.setenv("PADDLE_TPU_PAGED_ATTN", "pallas")
        out = np.asarray(paged_attention(q, k, v, tables, base))
        monkeypatch.setenv("PADDLE_TPU_PAGED_ATTN", "xla")
        ref = np.asarray(paged_attention(q, k, v, tables, base))
        np.testing.assert_allclose(out, ref, rtol=0, atol=self.ATOL)


class TestQuantServing:
    """``EngineConfig(weight_dtype="int8", kv_cache_dtype="int8")``:
    int8 weight-only decode + int8 paged KV.

    Knobs OFF is asserted structurally here (fp state arrays, fp pool,
    no scale planes — the engine threads ``None`` where the quant path
    threads scale pytrees, so the traced programs are the pre-quant
    ones) and behaviorally by every other class in this file running
    the same engine code.  Quantized-vs-fp parity is tolerance-based BY
    DESIGN: PTQ rounds weights, logits move ~1e-3, and a greedy argmax
    near a tie can legitimately flip — after which the streams diverge.
    What must stay BITWISE is everything within one quant config:
    batched-vs-sequential scheduling, preemption/resume replay, and
    spec-decode K>0 vs K=0 (the verify-window guarantee)."""

    # ---- pure-function paths (fast: no engine compile) ----

    def test_zero_channel_scale_floor(self):
        """Satellite regression: an all-zero output channel quantizes
        without NaN because the 1e-8 floor is applied PER CHANNEL before
        the divide — not to the post-max per-tensor scale."""
        w = jnp.zeros((8, 4), jnp.float32).at[:, 1:].set(3.0)
        scale = np.asarray(channelwise_scales(w)).ravel()
        assert np.isfinite(scale).all() and (scale > 0).all()
        assert scale[0] == pytest.approx(1e-8 / 127.0)  # floored channel
        assert scale[1] == pytest.approx(3.0 / 127.0)   # untouched by it
        q, s = quantize_weight(w)
        dq = np.asarray(dequantize_weight(q, s))
        assert np.isfinite(dq).all()
        np.testing.assert_array_equal(dq[:, 0], 0.0)    # exact zeros
        np.testing.assert_allclose(dq[:, 1:], 3.0, atol=3.0 / 254.0)
        # the observer the serving path is built on: same per-channel
        # floor inside fake_quant
        fq = np.asarray(PerChannelAbsmaxObserver().fake_quant(w))
        assert np.isfinite(fq).all()
        np.testing.assert_array_equal(fq[:, 0], 0.0)

    def test_paged_write_quant_roundtrip(self):
        """Quantize-at-append: dequantized blocks are within a half
        quantization step of the written vectors, zero vectors store
        exact zeros (matching the fp pool's zero init), and untouched
        blocks stay untouched."""
        r = np.random.RandomState(0)
        pool = jnp.zeros((4, 4, 2, 8), jnp.int8)
        scales = jnp.zeros((4, 4), jnp.float32)
        new = jnp.asarray(r.randn(1, 5, 2, 8).astype(np.float32))
        new = new.at[0, 2].set(0.0)                     # a zero token
        tables = jnp.asarray([[1, 2]], jnp.int32)
        pos = jnp.asarray([0], jnp.int32)
        pool2, scales2 = paged_write_quant(pool, scales, new, tables, pos)
        deq = (np.asarray(pool2, np.float32)
               * np.asarray(scales2)[:, :, None, None])
        ref = np.asarray(new[0])
        for t in range(5):
            got = deq[tables[0, t // 4], t % 4]
            bound = np.abs(ref[t]).max() / 254.0 + 1e-12
            np.testing.assert_allclose(got, ref[t], atol=bound)
        np.testing.assert_array_equal(deq[0, :, :, :], 0.0)  # scratch
        np.testing.assert_array_equal(deq[1, 2], 0.0)   # zero token exact
        np.testing.assert_array_equal(np.asarray(pool2[3]), 0)

    def test_kv8_xla_fallback_nb_invariant_and_matches_fp(self):
        """The int8 XLA fallback keeps the fp fallback's load-bearing
        property — bitwise invariance to table width — AND equals the
        fp path run on the dequantized pool bitwise (the scale multiply
        commutes with the gather)."""
        r = np.random.RandomState(3)
        b, qh, kh, d, bs, nb = 2, 4, 2, 8, 4, 3
        q = jnp.asarray(r.randn(b, 1, qh, d).astype(np.float32))
        num_blocks = 1 + b * nb
        k = jnp.asarray(r.randint(-127, 128, (num_blocks, bs, kh, d)),
                        jnp.int8)
        v = jnp.asarray(r.randint(-127, 128, (num_blocks, bs, kh, d)),
                        jnp.int8)
        ks = jnp.asarray((r.rand(num_blocks, bs) * 0.05 + 1e-3)
                         .astype(np.float32))
        vs = jnp.asarray((r.rand(num_blocks, bs) * 0.05 + 1e-3)
                         .astype(np.float32))
        tables = jnp.asarray(
            1 + np.arange(b * nb, dtype=np.int32).reshape(b, nb))
        pos = jnp.asarray(np.array([5, 9], np.int32))
        out = np.asarray(_xla_paged_attention(q, k, v, tables, pos,
                                              ks, vs))
        assert np.isfinite(out).all()
        for pad in (1, 4):
            wide = jnp.concatenate(
                [tables, jnp.zeros((b, pad), jnp.int32)], axis=1)
            out_w = np.asarray(_xla_paged_attention(q, k, v, wide, pos,
                                                    ks, vs))
            np.testing.assert_array_equal(out, out_w)
        kf = k.astype(jnp.float32) * ks[:, :, None, None]
        vf = v.astype(jnp.float32) * vs[:, :, None, None]
        out_fp = np.asarray(_xla_paged_attention(q, kf, vf, tables, pos))
        np.testing.assert_array_equal(out, out_fp)

    def test_pool_bytes_per_block_accounting(self):
        """bytes_per_block is the telemetry, prefix-budget, and bench
        unit: int8 storage charges the int8 payload plus the 4-byte
        per-token scale reads — about 3.8x under the f32 pool, the
        capacity headroom the quant bench's capacity row measures."""
        mk = dict(num_layers=2, num_blocks=4, block_size=4, kv_heads=2,
                  head_dim=8)
        fp = PagedKVPool(**mk)
        q8 = PagedKVPool(**mk, quant_dtype="int8")
        assert fp.bytes_per_block == 2 * 2 * 4 * (2 * 8 * 4)
        assert q8.bytes_per_block == 2 * 2 * 4 * (2 * 8 * 1 + 4)
        assert fp.bytes_per_block / q8.bytes_per_block > 3
        assert str(jnp.dtype(q8.k[0].dtype)) == "int8"
        assert q8.k_scale[0].shape == (4, 4)
        # zero scales dequantize zero-init blocks to the fp pool's 0.0
        np.testing.assert_array_equal(np.asarray(q8.k_scale[0]), 0.0)

    def test_w8_weight_and_logit_error_bounds(self):
        """The documented PTQ bounds behind the tolerance thresholds:
        per-channel symmetric rounding keeps |W - deq(W)| <= scale/2
        elementwise (exact), and the end-to-end greedy logit error on
        the tiny model stays ~1e-2 — small against typical logit gaps,
        which is why the slow parity test can demand a high greedy
        token-match fraction."""
        m = _model()
        ids = paddle.randint(0, TINY.vocab_size, [1, 8])
        with _tape.no_grad():
            h, _ = m.model(ids, caches=[(None, None)] * 2)
            ref = m._logits(h).numpy()
        qmap = quantize_for_serving(m)
        # every matmul projection (q/k/v/o + SwiGLU gate/up/down) plus
        # the LM head got calibrated
        assert len(qmap) == 7 * TINY.num_hidden_layers + 1, sorted(qmap)
        sd = m.state_dict()
        orig = {}
        for name, qw in qmap.items():
            orig[name] = sd[name]._data
            err = np.abs(np.asarray(orig[name])
                         - np.asarray(qw.dequantize()))
            assert err.max() <= float(np.asarray(qw.scale).max()) / 2 + 1e-9
            sd[name]._data = qw.dequantize()
        try:
            with _tape.no_grad():
                h, _ = m.model(ids, caches=[(None, None)] * 2)
                got = m._logits(h).numpy()
        finally:
            for name, a in orig.items():
                sd[name]._data = a
        lerr = np.abs(got - ref).max()
        assert lerr < 0.05, lerr

    def test_quant_knob_normalization(self):
        norm = Engine._norm_quant_knob
        for off in (None, "", "none", "NONE"):
            assert norm(off, "weight_dtype") is None
        for on in ("int8", "INT8", "i8"):
            assert norm(on, "weight_dtype") == "int8"
        with pytest.raises(ValueError, match="kv_cache_dtype"):
            norm("fp8", "kv_cache_dtype")

    def test_knobs_off_engine_structure_is_fp(self):
        """Knobs off: no (q, scale) tuples in the threaded state, fp
        pool, no scale planes — the decode/prefill traces are the
        pre-quant programs.  Knobs on: int8 where promised, and the
        resident weight bytes actually shrink."""
        m = _model()
        cfg = dict(num_slots=2, max_seq_len=32)
        fp = Engine(m, EngineConfig(**cfg), register_profiler=False)
        w8 = Engine(m, EngineConfig(**cfg, weight_dtype="int8"),
                    register_profiler=False)
        kv8 = Engine(m, EngineConfig(**cfg, kv_cache_dtype="int8"),
                     register_profiler=False)
        try:
            assert all(type(a) is not tuple for a in fp._state_arrays)
            assert fp.pool.quant_dtype is None
            assert fp.pool.k_scale is None
            assert str(jnp.dtype(fp.pool.store_dtype)) == "float32"
            assert fp.stats()["quant"]["quantized_weights"] == 0

            sq = w8.stats()["quant"]
            assert sq["quantized_weights"] > 0
            assert sq["weight_bytes"] < fp.stats()["quant"]["weight_bytes"]
            assert any(type(a) is tuple for a in w8._state_arrays)
            assert w8.pool.quant_dtype is None   # KV untouched by w8

            assert str(jnp.dtype(kv8.pool.store_dtype)) == "int8"
            assert kv8.pool.k_scale is not None
            assert kv8.stats()["kv_pool"]["dtype"] == "int8"
            assert (kv8.pool.bytes_per_block
                    < fp.pool.bytes_per_block)
        finally:
            fp.close()
            w8.close()
            kv8.close()

    # ---- engine end-to-end (slow: several compiled engines) ----

    @pytest.mark.slow
    def test_w8kv8_greedy_parity_under_batching(self):
        """The satellite workload: continuous batching + prefix hits +
        forced preemption/resume, fp vs int8.  Within the quant config
        the batched/preempted run must equal per-request sequential runs
        BITWISE (scheduling never changes tokens); across configs the
        greedy streams must agree on a documented fraction of tokens
        (mean longest-common-prefix; PTQ can flip a near-tie argmax,
        after which greedy divergence is permanent, so this is a
        tolerance threshold, not a bug budget)."""
        m = _model()
        system = list(range(1, 13))              # 3 shared prefix blocks
        prompts = [system + [20 + i, 40 + i] for i in range(4)]
        sp = SamplingParams(max_new_tokens=12)

        def run(wq, kq):
            eng = Engine(m, EngineConfig(
                num_slots=2, max_seq_len=48, max_horizon=4,
                prefix_block_size=4, kv_pool_blocks=12,
                weight_dtype=wq, kv_cache_dtype=kq),
                register_profiler=False)
            reqs = [eng.submit(list(p), sp) for p in prompts]
            eng.run()
            c = eng.stats()
            eng.close()
            return [r.output_ids for r in reqs], c

        fp_out, fp_c = run(None, None)
        off_out, _ = run("none", "")             # spelled-out "off" knobs
        assert off_out == fp_out                 # bitwise: same programs
        q_out, q_c = run("int8", "int8")

        for c in (fp_c, q_c):
            assert c["preemptions"] >= 1         # pool pressure was real
            assert c["prefix_hit_tokens"] > 0    # prefix cache was live

        # within-config determinism: sequential singles, same knobs
        eng = Engine(m, EngineConfig(
            num_slots=1, max_seq_len=48, max_horizon=4,
            prefix_block_size=0, weight_dtype="int8",
            kv_cache_dtype="int8"), register_profiler=False)
        seq_out = [eng.generate(list(p), sp) for p in prompts]
        eng.close()
        assert q_out == seq_out

        # cross-config tolerance: mean LCP fraction of the fp stream
        def lcp(a, b):
            n = 0
            for x, y in zip(a, b):
                if x != y:
                    break
                n += 1
            return n / max(1, len(a))

        match = sum(lcp(a, b) for a, b in zip(fp_out, q_out)) / len(fp_out)
        assert match >= 0.75, f"greedy token match {match:.3f} < 0.75"

        # the tentpole byte claim: int8 KV + scales cut per-step KV
        # traffic to <= 0.55x fp (measured ~0.27x at f32)
        fp_per = fp_c["kv_bytes_read"] / max(1, fp_c["decode_steps"])
        q_per = q_c["kv_bytes_read"] / max(1, q_c["decode_steps"])
        assert q_per <= 0.55 * fp_per, (q_per, fp_per)

    @pytest.mark.slow
    @pytest.mark.parametrize("kq", [None, "int8"], ids=["w8", "w8kv8"])
    def test_spec_decode_bitwise_under_quant(self, kq):
        """Draft-verify must stay EXACT under quantization: the verify
        window scores drafted tokens with the same quantized weights and
        same stored KV bytes the sequential path would produce, so K=4
        output equals K=0 output bitwise — not within tolerance."""
        m = _model()
        prompt = [5, 6, 7, 8] * 4
        sp = SamplingParams(max_new_tokens=16)
        outs = []
        for k in (0, 4):
            eng = Engine(m, EngineConfig(
                num_slots=1, max_seq_len=64, max_horizon=4,
                spec_k=k, spec_adaptive=False,
                weight_dtype="int8", kv_cache_dtype=kq),
                register_profiler=False)
            outs.append(eng.generate(list(prompt), sp))
            eng.close()
        assert outs[0] == outs[1]


class TestRequestTracing:
    """Flight records vs engine ground truth: every request's trace must
    reconstruct the engine's own counters — under continuous batching
    with preemption AND speculative decoding enabled — and abort must
    tear down cleanly from both the queued and the running state."""

    @staticmethod
    def _cfg(**kw):
        kw.setdefault("num_slots", 2)
        kw.setdefault("max_seq_len", 32)
        kw.setdefault("max_horizon", 4)
        kw.setdefault("prefix_block_size", 4)
        kw.setdefault("prefix_cache_bytes", 0)
        return EngineConfig(**kw)

    @pytest.mark.slow
    def test_trace_matches_counters_under_preempt_and_spec(self):
        m = _model()
        # the auto-preempt recipe (undersized pool forces at least one
        # swap round-trip) with self-drafting speculation on top
        prompts = [[7, 3, 9, 1, 4, 4, 2, 8], [5, 6, 7, 8, 9, 1, 2, 3]]
        samp = [SamplingParams(max_new_tokens=12) for _ in prompts]
        eng = Engine(m, self._cfg(kv_pool_blocks=8, spec_k=2),
                     register_profiler=False)
        reqs = [eng.submit(p, s) for p, s in zip(prompts, samp)]
        eng.run()
        c = eng.counters()
        assert c["preemptions"] >= 1
        for r in reqs:
            assert r.trace is not None and r.trace.finished
            tc = r.trace.counts()
            assert tc["tokens_emitted"] == r.n_generated == 12
            assert tc["prefix_hit_tokens"] == r.prefix_hit_tokens
            kinds = [k for k, _, _ in r.trace.events]
            assert kinds[0] == "queued" and kinds[-1] == "finish"
            assert kinds.count("first_token") == 1
            # every preempt pairs with a resume; FIRST_TOKEN only once
            assert (kinds.count("preempt") == kinds.count("resume")
                    == tc["preemptions"])
            ts = [t for _, t, _ in r.trace.events]
            assert ts == sorted(ts)
        # trace sums ARE the engine counters restated per request
        tcs = [r.trace.counts() for r in reqs]
        assert (sum(t["tokens_emitted"] for t in tcs)
                == c["tokens_generated"])
        assert (sum(t["preemptions"] for t in tcs) == c["preemptions"])
        assert (sum(t["spec_accepted_tokens"] for t in tcs)
                == c["spec_accepted_tokens"])
        # recorder retained both finished flight records
        assert ({t.request_id for t in eng.recorder.recent()}
                == {r.request_id for r in reqs})
        assert not eng.recorder.live()

    def test_counts_reconcile_deadline_aborts(self):
        """The counts() reconciliation must also hold when requests die
        to the admission deadline: per-trace ``aborted`` tallies sum to
        the engine's requests_aborted, and aborted requests contribute
        zero emitted tokens."""
        import time as _time

        m = _model()
        eng = Engine(m, self._cfg(num_slots=1), register_profiler=False)
        runner = eng.submit([1, 2, 3, 4],
                            SamplingParams(max_new_tokens=6))
        doomed = eng.submit([5, 6, 7, 8],
                            SamplingParams(max_new_tokens=6),
                            deadline_s=0.01)
        _time.sleep(0.03)                # deadline passes while queued
        eng.run()
        c = eng.counters()
        assert c["deadline_expired"] == 1 == c["requests_aborted"]
        tcs = [r.trace.counts() for r in (runner, doomed)]
        assert sum(t["aborted"] for t in tcs) == c["requests_aborted"]
        assert (sum(t["tokens_emitted"] for t in tcs)
                == c["tokens_generated"] == 6)
        assert doomed.trace.counts()["tokens_emitted"] == 0
        eng.close()

    def test_prefix_hit_tokens_in_trace(self):
        m = _model()
        eng = Engine(m, self._cfg(num_slots=1,
                                  prefix_cache_bytes=1 << 20),
                     register_profiler=False)
        prompt = [1, 2, 3, 4, 5, 6, 7, 8]
        r1 = eng.submit(prompt, SamplingParams(max_new_tokens=4))
        eng.run()
        r2 = eng.submit(prompt, SamplingParams(max_new_tokens=4))
        eng.run()
        assert r2.prefix_hit_tokens > 0          # served from the cache
        for r in (r1, r2):
            assert (r.trace.counts()["prefix_hit_tokens"]
                    == r.prefix_hit_tokens)
        assert (r1.prefix_hit_tokens + r2.prefix_hit_tokens
                == eng.counters()["prefix_hit_tokens"])

    def test_abort_queued_and_running(self):
        m = _model()
        eng = Engine(m, self._cfg(num_slots=1), register_profiler=False)
        running = eng.submit([1, 2, 3, 4], SamplingParams(max_new_tokens=8))
        queued = eng.submit([5, 6, 7], SamplingParams(max_new_tokens=8))
        eng.step(horizon=2)
        assert running.status == "running" and queued.status == "waiting"

        eng.abort(queued)
        assert queued.status == "finished"
        assert queued.finish_reason == "abort"
        # never admitted: the flight record is queued -> abort, nothing else
        assert [k for k, _, _ in queued.trace.events] == ["queued", "abort"]

        had = running.n_generated
        assert had >= 1
        eng.abort(running)
        assert running.finish_reason == "abort"
        assert running.n_generated == had        # keeps its tokens
        kinds = [k for k, _, _ in running.trace.events]
        assert kinds[-1] == "abort" and "prefill" in kinds
        # full teardown: no queue, no running lane, no leaked blocks
        assert eng.scheduler.queue_depth == 0
        assert not eng.scheduler.running
        assert eng.pool.blocks_in_use == 0
        c = eng.counters()
        assert c["requests_aborted"] == 2
        assert ({t.request_id for t in eng.recorder.recent()}
                == {queued.request_id, running.request_id})
        with pytest.raises(ValueError):
            eng.abort(running)                   # already finished
        # the engine keeps serving after aborts
        r3 = eng.submit([9, 9], SamplingParams(max_new_tokens=3))
        eng.run()
        assert r3.n_generated == 3 and r3.finish_reason == "length"

    def test_tracing_disabled(self):
        m = _model()
        eng = Engine(m, self._cfg(num_slots=1, request_tracing=False),
                     register_profiler=False)
        r = eng.submit([1, 2, 3], SamplingParams(max_new_tokens=2))
        eng.run()
        assert r.trace is None and eng.recorder is None
        assert "tracing" not in eng.stats()
        eng.abort is not None                    # abort path still works
        r2 = eng.submit([4, 5], SamplingParams(max_new_tokens=4))
        eng.abort(r2)
        assert r2.finish_reason == "abort"


class TestPerformanceObservatory:
    """Observability phase 3 at the engine level: every compiled
    program has a cost card, per-request attribution reconstructs the
    engine's dispatch totals, the memory ledger reconciles, and the
    queue-wait histogram feeds stats()."""

    @staticmethod
    def _cfg(**kw):
        kw.setdefault("num_slots", 2)
        kw.setdefault("max_seq_len", 32)
        kw.setdefault("max_horizon", 4)
        kw.setdefault("prefix_block_size", 4)
        kw.setdefault("prefix_cache_bytes", 0)
        return EngineConfig(**kw)

    def test_every_compiled_program_has_a_card(self):
        m = _model()
        eng = Engine(m, self._cfg(), register_profiler=False)
        reqs = [eng.submit([1 + i, 2, 3, 4, 5][:3 + i % 3],
                           SamplingParams(max_new_tokens=6, seed=i))
                for i in range(4)]
        eng.run()
        assert all(r.finish_reason is not None for r in reqs)
        # one card per distinct compiled program, on both fns
        assert len(eng._decode.cards) == eng._decode.misses > 0
        assert len(eng._prefill.cards) == eng._prefill.misses > 0
        for fn in (eng._decode, eng._prefill):
            for card in fn.cards.values():
                assert card.flops and card.flops > 0
                assert card.bytes_accessed and card.bytes_accessed > 0
                assert card.compile_seconds > 0
                assert card.dispatches >= 1
        # decode cards carry the bucket semantics in meta
        metas = [c.meta for c in eng._decode.cards.values()]
        assert all({"horizon", "nb", "k_draft"} <= set(mt)
                   for mt in metas)
        assert ({(mt["horizon"], mt["nb"], mt["k_draft"])
                 for mt in metas}
                == set(eng.stats()["decode_buckets"]))
        # ...and prefill cards the (lanes, bucket) pair
        assert all({"lanes", "bucket"} <= set(mt.keys())
                   for mt in (c.meta for c in eng._prefill.cards.values()))
        # the dispatch ledger: every call rode a card (cards are
        # process-wide, so other engines may have bumped them too)
        assert (sum(c.dispatches for c in eng._decode.cards.values())
                >= eng._decode.calls)
        st = eng.stats()
        assert st["cost"]["decode_cards"] == len(
            {id(c) for c in eng._decode.cards.values()})
        eng.close()

    @pytest.mark.slow
    def test_attribution_reconciles_under_preempt_and_spec(self):
        """Sum of per-request flops/bytes estimates == the engine's own
        dispatch-weighted card totals, within 1%, under continuous
        batching with preemption and speculative decoding."""
        m = _model()
        prompts = [[7, 3, 9, 1, 4, 4, 2, 8], [5, 6, 7, 8, 9, 1, 2, 3],
                   [2, 4, 6, 8], [1, 3, 5, 7, 9, 2]]
        eng = Engine(m, self._cfg(kv_pool_blocks=8, spec_k=2),
                     register_profiler=False)
        reqs = [eng.submit(p, SamplingParams(max_new_tokens=10, seed=i))
                for i, p in enumerate(prompts)]
        eng.run()
        assert eng.counters()["preemptions"] >= 1
        assert eng.counters()["spec_accepted_tokens"] >= 0
        st = eng.stats()
        assert st["cost"]["program_flops_total"] > 0
        assert st["cost"]["program_bytes_total"] > 0
        got_f = sum(r.trace.counts()["flops_est"] for r in reqs)
        got_b = sum(r.trace.counts()["bytes_est"] for r in reqs)
        assert got_f == pytest.approx(st["cost"]["program_flops_total"],
                                      rel=0.01)
        assert got_b == pytest.approx(st["cost"]["program_bytes_total"],
                                      rel=0.01)
        # attribution is per-request meaningful, not all-on-one
        assert all(r.trace.counts()["flops_est"] > 0 for r in reqs)
        # /debug/requests carries the same numbers
        doc = eng.recorder.to_json()
        assert (sum(t["counts"]["flops_est"] for t in doc["recent"])
                == pytest.approx(got_f))
        eng.close()

    def test_memory_ledger_reconciles_in_stats(self):
        import gc

        gc.collect()                 # settle foreign arrays first
        m = _model()
        eng = Engine(m, self._cfg(), register_profiler=False)
        eng.submit([1, 2, 3], SamplingParams(max_new_tokens=4))
        eng.run()
        mem = eng.stats()["memory"]
        assert set(mem["components"]) == {"kv_pool", "weights",
                                          "engine_state"}
        assert all(v > 0 for v in mem["components"].values())
        assert (mem["accounted_total_bytes"]
                == sum(mem["components"].values()))
        # live_arrays is process-wide (other tests' arrays included),
        # but it must at least cover what this engine accounts for
        assert mem["live_bytes"] >= mem["accounted_total_bytes"]
        # steady state: the unaccounted residue does not grow between
        # snapshots of the same engine (the leak-detector contract)
        eng.submit([4, 5, 6], SamplingParams(max_new_tokens=4))
        eng.run()
        gc.collect()
        assert eng.stats()["memory"]["leak_delta_bytes"] <= 1 << 16
        eng.close()

    def test_queue_wait_histogram_in_stats(self):
        m = _model()
        eng = Engine(m, self._cfg(num_slots=1), register_profiler=False)
        # second request queues behind the first -> nonzero wait
        eng.submit([1, 2, 3], SamplingParams(max_new_tokens=6))
        eng.submit([4, 5, 6], SamplingParams(max_new_tokens=2))
        eng.run()
        st = eng.stats()
        assert "queue_wait_p50_s" in st and "queue_wait_p95_s" in st
        assert st["queue_wait_p95_s"] >= st["queue_wait_p50_s"] >= 0.0
        eng.close()

    def test_program_cards_disabled(self):
        m = _model()
        eng = Engine(m, self._cfg(num_slots=1, program_cards=False),
                     register_profiler=False)
        r = eng.submit([1, 2, 3], SamplingParams(max_new_tokens=3))
        eng.run()
        assert r.finish_reason == "length"
        assert eng._decode.cards == {} and eng._prefill.cards == {}
        st = eng.stats()
        assert st["cost"]["program_flops_total"] == 0.0
        assert st["cost"]["decode_cards"] == 0
        # tracing still works, just without cost estimates
        assert r.trace.counts()["flops_est"] == 0.0
        eng.close()

    def test_abort_storm_flight_recorder_retention(self):
        """Satellite: N submits then abort everything — the recorder's
        ring retains only the last `capacity` finished traces, counts
        the drops, and pins zero live traces afterwards."""
        m = _model()
        eng = Engine(m, self._cfg(num_slots=2,
                                  flight_recorder_capacity=3),
                     register_profiler=False)
        reqs = [eng.submit([1 + i, 2, 3], SamplingParams(
            max_new_tokens=8, seed=i)) for i in range(8)]
        eng.step(horizon=2)          # two admitted + decoding, six queued
        for r in reqs:
            if r.finish_reason is None:
                eng.abort(r)
        assert all(r.finish_reason is not None for r in reqs)
        aborted = [r for r in reqs if r.finish_reason == "abort"]
        assert len(aborted) >= 6
        for r in aborted:
            assert [k for k, _, _ in r.trace.events][-1] == "abort"
        rec = eng.recorder
        assert rec.live() == []                  # nothing pinned
        doc = rec.to_json()
        assert doc["live_count"] == 0
        assert doc["finished_total"] == len(reqs)
        assert doc["finished_retained"] == 3
        assert rec.dropped == len(reqs) - 3
        assert ([t.request_id for t in rec.recent()]
                == [r.request_id for r in reqs[-3:]])
        # the engine is fully torn down and still serviceable
        assert eng.scheduler.queue_depth == 0
        assert not eng.scheduler.running
        assert eng.pool.blocks_in_use == 0
        r9 = eng.submit([7, 7], SamplingParams(max_new_tokens=2))
        eng.run()
        assert r9.finish_reason == "length"
        eng.close()


# ----------------------------------------------------------- sharded serving
class TestServingSpecLayout:
    """The sharded layout's placement rules: every decode-model
    parameter gets a spec, projections are column-parallel, and
    unshardable shapes are rejected EAGERLY (before any device work)."""

    def test_every_param_gets_a_spec(self):
        from jax.sharding import PartitionSpec as P
        from paddle_tpu.serving import ServingSpecLayout

        layout = ServingSpecLayout()
        m = _model()
        names = list(m.state_dict().keys())
        specs = layout.state_specs(names)
        assert len(specs) == len(names)
        for n, sp in zip(names, specs):
            if layout.is_tp_sharded(n):
                # column-parallel: LAST axis sharded, never the first
                # (sharding the contraction dim would break bitwise)
                assert sp == P(None, "tp"), n
            else:
                assert sp == P(), n
        # the decode-model projections really are in the sharded set
        sharded = [n for n in names if layout.is_tp_sharded(n)]
        for proj in ("q_proj", "k_proj", "v_proj", "o_proj", "gate_proj",
                     "up_proj", "down_proj", "lm_head"):
            assert any(proj in n for n in sharded), proj
        # engine scan state and the KV pool have placements too
        assert layout.engine_state() == P()
        assert layout.kv_pool() == P(None, None, "tp", None)
        assert layout.kv_scales() == P()

    def test_divisibility_errors_are_eager_and_name_offenders(self):
        from paddle_tpu.serving import ServingSpecLayout

        layout = ServingSpecLayout()
        # TINY: 4 heads / hidden 64 / vocab 128 — tp=3 divides nothing
        with pytest.raises(ValueError, match="num_attention_heads=4"):
            layout.validate(TINY, 3)
        # TINY_GQA: 8 q-heads divide by 4 but the 2 kv_heads do not
        with pytest.raises(ValueError, match="kv_heads"):
            layout.validate(TINY_GQA, 4)
        layout.validate(TINY_GQA, 2)            # and tp=2 is fine

    def test_tied_embeddings_rejected(self):
        from paddle_tpu.serving import ServingSpecLayout

        tied = GPTConfig(vocab_size=128, hidden_size=64,
                         intermediate_size=128, num_hidden_layers=2,
                         num_attention_heads=4,
                         max_position_embeddings=64,
                         tie_word_embeddings=True)
        with pytest.raises(ValueError, match="tie_word_embeddings"):
            ServingSpecLayout().validate(tied, 2)

    def test_mesh_engine_rejects_bad_shapes_before_compiling(self):
        from paddle_tpu.serving import MeshEngine

        m = _model()
        with pytest.raises(ValueError, match="not divisible"):
            MeshEngine(m, EngineConfig(num_slots=2, max_seq_len=32),
                       tp=3, register_profiler=False)
        with pytest.raises(ValueError, match="mesh_shape"):
            MeshEngine._norm_mesh_knob(None, None)
        with pytest.raises(ValueError, match="contradicts"):
            MeshEngine._norm_mesh_knob((1, 2), 4)
        with pytest.raises(ValueError, match="disaggregated"):
            MeshEngine._norm_mesh_knob((2, 2), None)
        with pytest.raises(ValueError, match="tp must be"):
            MeshEngine._norm_mesh_knob(None, 0)
        assert MeshEngine._norm_mesh_knob(None, 2) == (1, 2)
        assert MeshEngine._norm_mesh_knob((1, 4), None) == (1, 4)


class TestChunkedPrefill:
    """Chunked prefill (``prefill_chunk_tokens``) vs whole-prompt
    prefill: the token streams must be BITWISE equal — greedy and
    seeded — under continuous batching, prefix hits at and across chunk
    boundaries, preemption (mid-prefill and mid-decode), speculative
    decoding, and int8 KV.  Chunking is pure scheduling: each chunk is
    an iterated prefix-extension of the same lane, so the streams can
    only diverge if the interleave machinery breaks."""

    _rng = np.random.default_rng(11)
    BASE = list(map(int, _rng.integers(1, 127, 26)))
    # phase-2 prompts: shared prefix ending exactly AT a chunk boundary
    # (16 = 2 chunks of 8) and ACROSS one (20 straddles chunk 3)
    PROMPTS1 = [BASE,
                list(map(int, _rng.integers(1, 127, 9))),
                list(map(int, _rng.integers(1, 127, 23)))]
    PROMPTS2 = [BASE[:16] + list(map(int, _rng.integers(1, 127, 7))),
                BASE[:20] + list(map(int, _rng.integers(1, 127, 5)))]
    SAMP1 = [SamplingParams(max_new_tokens=8),
             SamplingParams(max_new_tokens=7, temperature=0.9, seed=5),
             SamplingParams(max_new_tokens=8, temperature=1.2, top_k=13,
                            seed=2)]
    SAMP2 = [SamplingParams(max_new_tokens=6),
             SamplingParams(max_new_tokens=6, temperature=0.8, seed=9)]

    @staticmethod
    def _engine(m, chunk, **kw):
        kw.setdefault("num_slots", 4)
        kw.setdefault("max_seq_len", 64)
        kw.setdefault("kv_pool_blocks", 96)
        return Engine(m, EngineConfig(prefill_chunk_tokens=chunk, **kw),
                      register_profiler=False)

    @classmethod
    def _run(cls, chunk, **kw):
        eng = cls._engine(_model(), chunk, **kw)
        out = [eng.generate(cls.PROMPTS1, cls.SAMP1),
               eng.generate(cls.PROMPTS2, cls.SAMP2)]
        return eng, out

    _whole1 = None

    @classmethod
    def _whole_phase1(cls):
        """Phase-1 whole-prompt reference, computed once per session."""
        if cls._whole1 is None:
            eng = cls._engine(_model(), 0)
            cls._whole1 = eng.generate(cls.PROMPTS1, cls.SAMP1)
            eng.close()
        return cls._whole1

    def test_parity_greedy_seeded_prefix_hits(self):
        """The core gate: chunk=8 streams bitwise-equal whole-prompt
        across two phases, where phase 2's prompts hit the radix cache
        at and across chunk boundaries; the compiled prefill programs
        never exceed the chunk bucket, yet a 26-token prompt (> any
        single 8-wide dispatch) completes — the context cap the chunking
        lifts."""
        e0, whole = self._run(0)
        e1, chunked = self._run(8)
        assert chunked == whole
        st = e1.stats()["prefill"]
        assert st["chunked_requests"] >= 3
        assert st["chunks_in_flight"] == 0
        assert st["context_high_water"] == len(self.BASE)
        assert all(b <= st["chunk_tokens"] for _, b in st["buckets"])
        # whole-prompt compiled a 32-wide program for the same work
        assert max(b for _, b in e0.stats()["prefill"]["buckets"]) == 32
        assert e1.stats()["prefix"]["hit_tokens"] > 0
        e1.drain()                   # radix store may still hold blocks
        assert e1.pool.blocks_in_use == 0
        e0.close()
        e1.close()

    @pytest.mark.slow
    def test_interleave_schedule_is_deterministic(self):
        """Identical workload -> identical chunk/dispatch counters, so
        the interleave schedule can't silently drift (the counts
        themselves: test_serving_counts.py)."""
        e1, out1 = self._run(8)
        e2, out2 = self._run(8)
        keys = ("prefill_calls", "prefill_chunk_dispatches",
                "prefill_chunked_requests")
        c1, c2 = e1.counters(), e2.counters()
        assert out1 == out2
        assert {k: c1[k] for k in keys} == {k: c2[k] for k in keys}
        s1, s2 = e1.stats()["prefill"], e2.stats()["prefill"]
        assert s1["chunk_count_total"] == s2["chunk_count_total"]
        assert s1["buckets"] == s2["buckets"]
        e1.close()
        e2.close()

    def test_mid_prefill_preempt_resumes_at_chunk_boundary(self):
        """Preempting a lane mid-chunked-prefill drops its ledger; the
        blocks its finished chunks adopted survive in the radix store,
        so re-admission resumes from the chunk boundary as an ordinary
        prefix hit — and the stream stays bitwise."""
        whole = self._whole_phase1()
        eng = self._engine(_model(), 8)
        reqs = [eng.submit(p, s)
                for p, s in zip(self.PROMPTS1, self.SAMP1)]
        eng.admit()                  # first chunks dispatched
        eng.step()                   # chunk 2: 16 tokens = 1 full block
        victim = reqs[0]             # 26-token prompt, mid-prefill
        assert victim.request_id in eng._chunking
        eng.preempt(victim)
        assert victim.request_id not in eng._chunking
        eng.run()
        assert [r.output_ids for r in reqs] == whole
        assert victim.prefix_hit_tokens >= 16
        assert eng.counters()["preemptions"] == 1
        eng.close()

    @pytest.mark.slow
    def test_decode_preempt_reprefills_through_chunks(self):
        """A lane preempted mid-DECODE re-prefills prompt + generated
        tokens through chunked dispatches; the final chunk re-samples
        the in-flight token and the PR 6 bitwise consistency check runs
        against it."""
        whole = self._whole_phase1()
        eng = self._engine(_model(), 8)
        reqs = [eng.submit(p, s)
                for p, s in zip(self.PROMPTS1, self.SAMP1)]
        while not all(r.output_ids for r in reqs):
            eng.step()
        eng.preempt(reqs[0])
        eng.run()
        assert [r.output_ids for r in reqs] == whole
        eng.close()

    @pytest.mark.slow
    def test_spec_k4_parity(self):
        m = _model()
        e0 = self._engine(m, 0, spec_k=4)
        whole = e0.generate(self.PROMPTS1, self.SAMP1)
        e0.close()
        e1 = self._engine(m, 8, spec_k=4)
        assert e1.generate(self.PROMPTS1, self.SAMP1) == whole
        assert e1.stats()["prefill"]["chunked_requests"] >= 1
        e1.close()

    @pytest.mark.slow
    def test_int8_kv_parity(self):
        m = _model()
        e0 = self._engine(m, 0, kv_cache_dtype="int8")
        whole = e0.generate(self.PROMPTS1, self.SAMP1)
        e0.close()
        e1 = self._engine(m, 8, kv_cache_dtype="int8")
        assert e1.generate(self.PROMPTS1, self.SAMP1) == whole
        e1.close()

    def test_chunk_size_normalization(self):
        """The knob normalizes to a power of two in
        [min_prefill_bucket, max_seq_len] (compile-cache discipline);
        negative rejects."""
        m = _model()
        eng = self._engine(m, 10)
        assert eng._chunk_tokens == 16
        eng.close()
        eng = self._engine(m, 2)     # below min_prefill_bucket (8)
        assert eng._chunk_tokens == 8
        eng.close()
        with pytest.raises(ValueError):
            self._engine(m, -4)

    def test_abort_mid_chunked_prefill_releases_blocks(self):
        eng = self._engine(_model(), 8)
        reqs = [eng.submit(p, s)
                for p, s in zip(self.PROMPTS1, self.SAMP1)]
        eng.admit()
        victim = reqs[0]
        assert victim.request_id in eng._chunking
        eng.abort(victim)
        assert victim.request_id not in eng._chunking
        assert victim.finish_reason == "abort"
        eng.run()
        assert all(r.output_ids for r in reqs[1:])
        eng.drain()
        assert eng.pool.blocks_in_use == 0
        eng.close()


class TestShardedServing:
    """MeshEngine vs single-chip Engine: greedy AND seeded streams must
    be bitwise-equal under continuous batching, prefix hits, preemption
    and speculative decoding (8 virtual CPU devices, tp=2)."""

    PROMPTS = [[3, 1, 4, 1, 5], [9, 2, 6]]
    SAMP = [SamplingParams(max_new_tokens=10),
            SamplingParams(temperature=0.8, top_k=20, seed=11,
                           max_new_tokens=10)]

    @staticmethod
    def _cfg(**kw):
        kw.setdefault("num_slots", 2)
        kw.setdefault("max_seq_len", 32)
        kw.setdefault("max_horizon", 4)
        kw.setdefault("prefix_block_size", 4)
        kw.setdefault("prefix_cache_bytes", 0)
        return EngineConfig(**kw)

    @classmethod
    def _ref(cls, m, prompts, samp, **kw):
        eng = Engine(m, cls._cfg(**kw), register_profiler=False)
        out = eng.generate(prompts, samp)
        eng.close()
        return out

    @classmethod
    def _mesh(cls, m, tp=2, **kw):
        from paddle_tpu.serving import MeshEngine

        return MeshEngine(m, cls._cfg(**kw), tp=tp,
                          register_profiler=False)

    def test_tp2_bitwise_parity_greedy_and_seeded(self):
        """The core acceptance test: continuous batching over a greedy
        and a seeded lane, tp=2 vs single chip, bitwise."""
        m = _model()
        ref = self._ref(m, self.PROMPTS, self.SAMP)
        eng = self._mesh(m)
        assert eng.generate(self.PROMPTS, self.SAMP) == ref
        assert eng.pool.blocks_in_use == 0
        s = eng.stats()["mesh"]
        assert s["mesh_shape"] == {"dp": 1, "tp": 2}
        assert len(s["devices"]) == 2
        eng.close()

    def test_tp1_is_the_degenerate_mesh(self):
        m = _model()
        ref = self._ref(m, self.PROMPTS, self.SAMP)
        eng = self._mesh(m, tp=1)
        assert eng.generate(self.PROMPTS, self.SAMP) == ref
        eng.close()

    def test_decode_census_matches_hand_formula(self):
        """The comms walker's census of the REAL compiled decode
        program equals the hand-derived per-layer count, exactly (the
        same program is a case of test_comms_observability's census)."""
        m = _model()
        eng = self._mesh(m)
        rep = eng.decode_comms_report(horizon=4)   # asserts internally
        L, h = 2, 4
        assert rep.counts() == {("psum", "tp"): L * h,
                                ("all_gather", "tp"): (3 * L + 1) * h}
        eng.close()

    @pytest.mark.slow
    def test_tp2_chunked_prefill_pallas_kernel_parity(self, monkeypatch):
        """Chunked prefill over the mesh WITH the Pallas ragged kernel
        running inside shard_map on each shard's head slice (interpret
        mode on CPU): streams bitwise vs the single-chip whole-prompt
        engine, and the decode collective census stays EXACT — the
        kernel adds no collectives."""
        m = _model()
        prompts = [[3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9, 7, 9, 3, 2,
                    3, 8, 4, 6, 2], [9, 2, 6]]
        samp = [SamplingParams(max_new_tokens=8),
                SamplingParams(temperature=0.8, top_k=20, seed=11,
                               max_new_tokens=8)]
        ref = self._ref(m, prompts, samp)
        monkeypatch.setenv("PADDLE_TPU_PAGED_ATTN", "pallas")
        eng = self._mesh(m, prefill_chunk_tokens=8)
        assert eng.generate(prompts, samp) == ref
        st = eng.stats()["prefill"]
        assert st["chunked_requests"] >= 1
        assert all(b <= st["chunk_tokens"] for _, b in st["buckets"])
        rep = eng.decode_comms_report(horizon=4)   # asserts internally
        L, h = 2, 4
        assert rep.counts() == {("psum", "tp"): L * h,
                                ("all_gather", "tp"): (3 * L + 1) * h}
        assert eng.pool.blocks_in_use == 0
        eng.close()

    @pytest.mark.slow
    def test_tp2_parity_gqa(self):
        m = _model(TINY_GQA)
        ref = self._ref(m, self.PROMPTS, self.SAMP)
        eng = self._mesh(m)
        assert eng.generate(self.PROMPTS, self.SAMP) == ref
        eng.close()

    @pytest.mark.slow
    def test_tp2_prefix_hit_parity(self):
        """A shared-prefix workload over the mesh-sharded pool: leases,
        COW and the radix store run host-side and unchanged; the leased
        blocks hold sharded KV.  Streams stay bitwise and the second
        submission actually hits the cache."""
        m = _model()
        shared = [5, 5, 7, 7, 1, 2, 3, 4]
        prompts = [shared + [9], shared + [8]]
        samp = [SamplingParams(max_new_tokens=8),
                SamplingParams(max_new_tokens=8)]
        kw = dict(prefix_cache_bytes=1 << 20)
        # sequential submissions so the second prompt can actually hit
        # the blocks the first one's retirement adopted
        refeng = Engine(m, self._cfg(**kw), register_profiler=False)
        ref = [refeng.generate(p, s) for p, s in zip(prompts, samp)]
        refeng.close()
        eng = self._mesh(m, **kw)
        out = [eng.generate(p, s) for p, s in zip(prompts, samp)]
        assert out == ref
        assert eng.stats()["prefix"]["hit_tokens"] > 0
        eng.drain()
        assert eng.pool.blocks_in_use == 0
        eng.close()

    @pytest.mark.slow
    def test_tp2_preempt_resume_parity(self):
        """Explicit preemption of a seeded lane mid-decode: blocks
        released, request re-admitted at the queue front, stream still
        bitwise vs the single-chip run of the same scenario."""
        m = _model()
        ref = self._ref(m, self.PROMPTS, self.SAMP)
        eng = self._mesh(m)
        reqs = [eng.submit(p, s)
                for p, s in zip(self.PROMPTS, self.SAMP)]
        eng.step(horizon=2)
        victim = reqs[1]
        eng.preempt(victim)
        assert victim.status == "waiting"
        eng.run()
        assert [r.output_ids for r in reqs] == ref
        assert eng.counters()["preemptions"] == 1
        assert eng.pool.blocks_in_use == 0
        eng.close()

    @pytest.mark.slow
    def test_tp2_spec_decode_parity(self):
        """Speculative decoding (K=4) over the mesh: drafts verified
        through the sharded forward, output bitwise vs the single-chip
        engine with the same knob — greedy and seeded."""
        m = _model()
        rep = TestSpeculativeDecode.REP_PROMPT
        samp = [SamplingParams(max_new_tokens=10),
                SamplingParams(temperature=0.9, top_k=20, top_p=0.9,
                               seed=7, max_new_tokens=10)]
        prompts = [rep, rep]
        kw = dict(max_seq_len=48, spec_k=4)
        ref = self._ref(m, prompts, samp, **kw)
        eng = self._mesh(m, **kw)
        assert eng.generate(prompts, samp) == ref
        assert eng.stats()["spec"]["draft_tokens"] > 0
        eng.close()

    @pytest.mark.slow
    def test_tp2_kv_quant_parity(self):
        """int8 paged KV over the mesh: the pmax'ed absmax gives every
        shard the full-head scale, so streams match the single-chip
        int8 engine bitwise (and census grows the 2L pmaxes)."""
        m = _model()
        kw = dict(kv_cache_dtype="int8")
        ref = self._ref(m, self.PROMPTS, self.SAMP, **kw)
        eng = self._mesh(m, **kw)
        assert eng.generate(self.PROMPTS, self.SAMP) == ref
        assert eng.decode_comms_report(horizon=4).counts()[
            ("pmax", "tp")] == 2 * 2 * 4
        eng.close()

    def test_create_llm_engine_knobs(self):
        """The predictor-style entry point: tp picks the engine class,
        knob contradictions raise like _norm_quant_knob does."""
        from paddle_tpu.inference import create_llm_engine
        from paddle_tpu.serving import MeshEngine

        m = _model()
        eng = create_llm_engine(m, num_slots=2, max_seq_len=32)
        assert type(eng) is Engine
        eng.close()
        eng = create_llm_engine(m, tp=1, num_slots=2, max_seq_len=32)
        assert type(eng) is Engine
        eng.close()
        eng = create_llm_engine(m, tp=2, num_slots=2, max_seq_len=32)
        assert isinstance(eng, MeshEngine)
        assert eng.mesh_shape == (1, 2)
        eng.close()
        with pytest.raises(ValueError, match="contradicts"):
            create_llm_engine(m, mesh_shape=(1, 2), tp=4)
        with pytest.raises(ValueError, match="disaggregated"):
            create_llm_engine(m, mesh_shape=(2, 2))


class TestHostKVTier:
    """Tiered KV: the host-RAM spill arena (kv_host_tier.py).
    Preempted lanes swap back in with one batched upload instead of
    re-prefilling, LRU-evicted prefix blocks demote to host and
    re-match later — and every path must be bitwise-equal to the
    recompute it replaces (the engine's resume-divergence check is the
    standing parity gate)."""

    PROMPTS = [[3, 1, 4, 1, 5], [9, 2, 6]]
    SAMP = [SamplingParams(max_new_tokens=10),
            SamplingParams(temperature=0.8, top_k=20, seed=11,
                           max_new_tokens=10)]

    @staticmethod
    def _cfg(**kw):
        kw.setdefault("num_slots", 2)
        kw.setdefault("max_seq_len", 48)
        kw.setdefault("max_horizon", 4)
        kw.setdefault("prefix_block_size", 4)
        kw.setdefault("prefix_cache_bytes", 1 << 20)
        kw.setdefault("kv_host_bytes", 1 << 20)
        kw.setdefault("kv_swap_policy", "always")
        return EngineConfig(**kw)

    @classmethod
    def _preempt_run(cls, eng):
        """Both lanes decode, both get preempted mid-stream, the run
        finishes through re-admission (swap-in when a tier is on,
        re-prefill otherwise)."""
        reqs = [eng.submit(list(p), s)
                for p, s in zip(cls.PROMPTS, cls.SAMP)]
        eng.step(horizon=2)
        eng.preempt(reqs[0])
        eng.preempt(reqs[1])
        eng.run()
        return reqs

    def test_preempt_swap_in_resume_bitwise(self):
        """The core acceptance: a greedy AND a seeded lane preempted
        mid-decode finish bitwise-equal whether their KV came back via
        host-arena swap-in or recompute, per-request traces restate the
        engine's swap counters exactly, and drain leaves zero host
        blocks."""
        m = _model()
        ref = Engine(m, self._cfg(kv_host_bytes=0),
                     register_profiler=False)
        r0 = self._preempt_run(ref)
        ref.close()
        eng = Engine(m, self._cfg(), register_profiler=False)
        r1 = self._preempt_run(eng)
        assert [r.output_ids for r in r1] == [r.output_ids for r in r0]
        c = eng.counters()
        assert c["kv_swap_outs"] >= 1 and c["kv_swap_ins"] >= 1
        tcs = [r.trace.counts() for r in r1]
        assert sum(t["swap_outs"] for t in tcs) == c["kv_swap_outs"]
        assert sum(t["swap_ins"] for t in tcs) == c["kv_swap_ins"]
        assert (sum(t["swap_out_bytes"] for t in tcs)
                == c["kv_swap_out_bytes"])
        assert (sum(t["swap_in_bytes"] for t in tcs)
                == c["kv_swap_in_bytes"])
        eng.drain()
        s = eng.stats()["kv_pool"]
        assert s["host_blocks_in_use"] == 0
        assert s["kv_swaps_averted_tokens"] > 0
        eng.close()

    def test_demoted_prefix_rematch_beats_drop(self):
        """A tight device radix budget plus churn evicts a warm
        prompt's chain; with the host tier the eviction is a demotion,
        so a later identical prompt re-matches at least as many tokens
        as a never-evicted control does under an ample budget (the
        budget is 8 blocks — enough to graft the promoted chain back,
        small enough that 12 blocks of churn still evicts it)."""
        m = _model()
        P = [5, 5, 7, 7, 1, 2, 3, 4, 9, 8, 7, 6,
             1, 3, 5, 7, 2, 4, 6, 8]
        churn = [[c] * 12 for c in (11, 22, 33)]
        samp = SamplingParams(max_new_tokens=4)

        def warm_probe(eng):
            eng.generate(list(P), samp)
            for q in churn:
                eng.generate(list(q), samp)
            r = eng.submit(list(P), samp)
            eng.run()
            return r

        ctrl = Engine(m, self._cfg(kv_host_bytes=0),
                      register_profiler=False)
        bpb = ctrl.pool.bytes_per_block
        ctrl_hit = warm_probe(ctrl).prefix_hit_tokens
        ctrl.close()
        eng = Engine(m, self._cfg(prefix_cache_bytes=8 * bpb),
                     register_profiler=False)
        probe = warm_probe(eng)
        st = eng.stats()
        assert st["prefix"]["evictions_demoted"] > 0
        assert st["kv_pool"]["host_tier"]["promotions"] > 0
        assert probe.prefix_hit_tokens >= ctrl_hit > 0
        eng.drain()
        assert eng.stats()["kv_pool"]["host_blocks_in_use"] == 0
        eng.close()

    @pytest.mark.slow
    def test_int8_roundtrip_stored_bytes_identical(self):
        """int8 KV swaps at quantized density: the device bytes of the
        re-bound blocks after a swap round-trip equal the pre-preempt
        pool bytes exactly — payloads AND scale planes — and the
        resumed stream matches the no-tier recompute engine."""
        m = _model()
        prompt = [3, 1, 4, 1, 5, 9, 2, 6]
        samp = SamplingParams(temperature=0.8, top_k=20, seed=11,
                              max_new_tokens=16)
        ref = Engine(m, self._cfg(kv_host_bytes=0,
                                  kv_cache_dtype="int8"),
                     register_profiler=False)
        r0 = ref.submit(list(prompt), samp)
        ref.step(horizon=4)
        ref.preempt(r0)
        ref.run()
        ref.close()
        eng = Engine(m, self._cfg(kv_cache_dtype="int8"),
                     register_profiler=False)
        r = eng.submit(list(prompt), samp)
        eng.step(horizon=4)
        assert r.status == "running"
        slot, bs = r.slot, eng._block_size
        pos = int(eng._pos[slot])
        nb = -(-pos // bs)
        bids = [int(eng.cache.tables[slot][j]) for j in range(nb)]
        k0, v0, ks0, vs0 = eng._fetch_blocks(bids)
        eng.preempt(r)
        assert eng.host_tier.stats()["lane_images"] == 1
        assert eng._swap_in(r)
        toks = eng._admission_tokens(r)
        chain = eng.prefix._walk(toks, len(toks))
        fb = pos // bs
        assert len(chain) == fb
        k1, v1, ks1, vs1 = eng._fetch_blocks([n.block for n in chain])
        assert k1.dtype == np.int8              # quantized density
        assert np.array_equal(k1, k0[:fb])
        assert np.array_equal(v1, v0[:fb])
        assert np.array_equal(ks1, ks0[:fb])
        assert np.array_equal(vs1, vs0[:fb])
        eng.run()
        assert r.output_ids == r0.output_ids
        eng.drain()
        assert eng.stats()["kv_pool"]["host_blocks_in_use"] == 0
        eng.close()

    @pytest.mark.slow
    def test_tp2_swap_parity(self):
        """Swap-in over the mesh-sharded pool: device_get gathers the
        full block, the upload re-places through the layout, and the
        stream stays bitwise vs the single-chip NO-tier engine (swap ==
        recompute across both axes at once)."""
        from paddle_tpu.serving import MeshEngine

        m = _model()
        ref = Engine(m, self._cfg(kv_host_bytes=0),
                     register_profiler=False)
        r0 = self._preempt_run(ref)
        ref.close()
        eng = MeshEngine(m, self._cfg(), tp=2, register_profiler=False)
        r1 = self._preempt_run(eng)
        assert [r.output_ids for r in r1] == [r.output_ids for r in r0]
        assert eng.counters()["kv_swap_ins"] >= 1
        eng.drain()
        assert eng.stats()["kv_pool"]["host_blocks_in_use"] == 0
        eng.close()

    def test_arena_exhaustion_and_policy_never_fall_back(self):
        """A one-byte arena (capacity 0 blocks) and policy "never" both
        degrade to plain recompute — same bitwise output, zero swap
        counters, no errors.  Bad knob values raise at construction."""
        m = _model()
        ref = Engine(m, self._cfg(kv_host_bytes=0),
                     register_profiler=False)
        r0 = self._preempt_run(ref)
        ref.close()
        for kw in (dict(kv_host_bytes=1), dict(kv_swap_policy="never")):
            eng = Engine(m, self._cfg(**kw), register_profiler=False)
            rs = self._preempt_run(eng)
            assert ([r.output_ids for r in rs]
                    == [r.output_ids for r in r0])
            c = eng.counters()
            assert c["kv_swap_ins"] == 0 and c["kv_swap_outs"] == 0
            if "kv_host_bytes" in kw:
                assert eng.host_tier.capacity == 0
            eng.drain()
            assert eng.stats()["kv_pool"]["host_blocks_in_use"] == 0
            eng.close()
        with pytest.raises(ValueError, match="kv_swap_policy"):
            Engine(m, self._cfg(kv_swap_policy="sometimes"),
                   register_profiler=False)

    def test_host_block_leak_invariant(self):
        """After preempt + abort + drain: zero host blocks in use and
        zero retained lane images — aborting a swapped-out request must
        drop its pinned image (the host-side leak smoke invariant)."""
        m = _model()
        eng = Engine(m, self._cfg(), register_profiler=False)
        reqs = [eng.submit(list(p), s)
                for p, s in zip(self.PROMPTS, self.SAMP)]
        eng.step(horizon=2)
        eng.preempt(reqs[1])
        assert eng.host_tier.stats()["lane_images"] == 1
        eng.abort(reqs[1])
        eng.submit([7, 7, 7, 7, 2], SamplingParams(max_new_tokens=6))
        eng.run()
        eng.drain()
        s = eng.stats()["kv_pool"]
        assert s["host_blocks_in_use"] == 0
        assert s["host_tier"]["lane_images"] == 0
        assert s["host_tier"]["lane_drops"] >= 1
        eng.close()

    def test_host_tier_unit(self):
        """HostKVTier in isolation: refresh-in-place demotion,
        consecutive-run matching capped at len-1, all-or-nothing lane
        saves with LRU prefix eviction, refcount guards."""
        L, bs, kvh, hd = 2, 4, 2, 8
        bpb = 2 * L * bs * kvh * hd * 4
        tier = HostKVTier(L, bs, kvh, hd, np.float32,
                          budget_bytes=3 * bpb, bytes_per_block=bpb)
        assert tier.capacity == 3

        def blk(x):
            return np.full((L, bs, kvh, hd), x, np.float32)

        toks = list(range(12))
        assert tier.store_prefix(tuple(toks[:4]), blk(1), blk(-1))
        assert tier.store_prefix(tuple(toks[:8]), blk(2), blk(-2))
        # re-demotion of a held path refreshes in place — no new block
        in_use = tier.blocks_in_use
        assert tier.store_prefix(tuple(toks[:4]), blk(9), blk(-9))
        assert tier.blocks_in_use == in_use and tier.demotions == 3
        # consecutive-run match; a block covering exactly len(tokens)
        # is still promotable (served partially via COW after graft)
        assert (tier.match_prefix(toks[:8] + [99], 0)
                == [tuple(toks[:4]), tuple(toks[:8])])
        assert (tier.match_prefix(toks[:8], 0)
                == [tuple(toks[:4]), tuple(toks[:8])])
        assert tier.match_prefix(toks[:7], 0) == [tuple(toks[:4])]
        assert tier.match_prefix([99] + toks[1:8], 0) == []
        # promotion consumes the entry; roundtrip bytes identical
        hb = tier.pop_prefix(tuple(toks[:4]))
        k, v, ks, vs = tier.read_block(hb)
        assert np.array_equal(k, blk(9)) and ks is None
        tier.release(hb)
        # lane save fits by spending the free list
        payload = [(blk(7), blk(-7), None, None)] * 2
        assert tier.save_lane("r1", 8, payload)
        assert tier.blocks_in_use == 3
        # all-or-nothing: evicting every prefix entry still isn't
        # enough room, so nothing is kept
        assert not tier.save_lane("r2", 16, [payload[0]] * 4)
        assert tier.peek_lane("r2") is None
        assert tier.blocks_in_use == 2          # just r1's pinned image
        img = tier.take_lane("r1")
        assert img.n_tokens == 8 and tier.peek_lane("r1") is None
        for h in img.hbs:
            tier.release(h)
        with pytest.raises(ValueError, match="over-released"):
            tier.release(img.hbs[0])
        assert not tier.drop_lane("r1")         # idempotent
        assert tier.blocks_in_use == 0

    def test_pinned_match_survives_midswap_spill(self):
        """Regression: between match_prefix and pop_prefix the engine
        allocates device blocks, and that reclaim path can spill NEW
        victims into the arena — with the arena full, store_prefix
        making room must not LRU-evict the pinned match (that used to
        KeyError pop_prefix and crash the engine under exactly the
        device-dry + arena-full pressure the tier serves).  Unpinned
        entries stay fair victims, and a pop that lost the race
        returns None (degrade to recompute) instead of raising."""
        L, bs, kvh, hd = 2, 4, 2, 8
        bpb = 2 * L * bs * kvh * hd * 4
        tier = HostKVTier(L, bs, kvh, hd, np.float32,
                          budget_bytes=2 * bpb, bytes_per_block=bpb)

        def blk(x):
            return np.full((L, bs, kvh, hd), x, np.float32)

        toks = list(range(8))
        assert tier.store_prefix(tuple(toks[:4]), blk(1), blk(-1))
        assert tier.store_prefix(tuple(toks[:8]), blk(2), blk(-2))
        paths = tier.match_prefix(toks, 0)
        assert len(paths) == 2
        tier.pin_prefix(paths)
        # the mid-swap spill finds everything pinned: refused (counted
        # as a dropped demotion), the matched entries stay resident
        assert not tier.store_prefix((9, 9, 9, 9), blk(3), blk(-3))
        assert tier.demotions_dropped == 1
        for p in paths:
            hb = tier.pop_prefix(p)
            assert hb is not None
            tier.release(hb)
        tier.unpin_prefix(paths)                # no-op after the pops
        assert tier.blocks_in_use == 0
        # an UNPINNED matched entry can still lose the race to later
        # spills; the pop then reports None instead of raising
        assert tier.store_prefix(tuple(toks[:4]), blk(4), blk(-4))
        stale = tier.match_prefix(toks[:5], 0)
        assert stale == [tuple(toks[:4])]
        assert tier.store_prefix((7, 7, 7, 7), blk(5), blk(-5))
        assert tier.store_prefix((6, 6, 6, 6), blk(6), blk(-6))
        assert tier.prefix_evictions == 1       # the stale match
        assert tier.pop_prefix(stale[0]) is None
        # mixed arena: the pinned entry is skipped, the unpinned
        # sibling is the victim
        tier.pin_prefix([(7, 7, 7, 7)])
        assert tier.store_prefix((5, 5, 5, 5), blk(7), blk(-7))
        assert tier.pop_prefix((6, 6, 6, 6)) is None
        hb = tier.pop_prefix((7, 7, 7, 7))
        assert hb is not None
        tier.release(hb)
        tier.unpin_prefix([(7, 7, 7, 7)])

    def test_bulk_reclaim_batches_demotion_copies(self):
        """A bulk radix reclaim demotes ALL its victims through ONE
        spill_batch pass — one gather + device_get per reclaim pass,
        not one synchronous device round-trip per block on the
        admission hot path."""
        m = _model()
        eng = Engine(m, self._cfg(), register_profiler=False)
        assert eng.prefix.spill_batch == eng._demote_blocks
        eng.generate([5, 5, 7, 7, 1, 2, 3, 4, 9, 8, 7, 6],
                     SamplingParams(max_new_tokens=4))
        held = eng.prefix._held
        assert held > 1
        calls = []
        orig = eng._fetch_blocks
        eng._fetch_blocks = (
            lambda bids: calls.append(list(bids)) or orig(bids))
        try:
            assert eng.prefix.reclaim(held) == held
        finally:
            eng._fetch_blocks = orig
        assert len(calls) == 1 and len(calls[0]) > 1
        st = eng.stats()
        assert st["prefix"]["evictions_demoted"] >= len(calls[0])
        assert (st["kv_pool"]["host_tier"]["demotions"]
                >= len(calls[0]))
        eng.drain()
        assert eng.stats()["kv_pool"]["host_blocks_in_use"] == 0
        eng.close()
