"""The counts of each serving mechanism, held exact: dispatches, compiles,
host syncs, KV bytes a step, pool blocks under a byte budget, swap bytes,
accept lengths, forced tokens, collective calls.

A count is a pure function of the code (shapes, schedule, seeds), so every
case asserts the number the code gives, reads no clock and says nothing of
speed.  The widths are those the counts were first read at (PRs 4-20's CPU
sections, deleted in PR 31): two layers of 256, f32.  A file of its own
beside test_serving.py because `--dist loadfile` gives a file to one
worker and test_serving.py is already the suite's longest.
"""

import functools

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.serving import Engine, EngineConfig, SamplingParams

COUNT_WIDTHS = dict(vocab_size=1024, hidden_size=256, intermediate_size=512,
                    num_hidden_layers=2, num_attention_heads=4)


def _count_model(max_pos=128):
    paddle.seed(0)
    m = GPTForCausalLM(GPTConfig(max_position_embeddings=max_pos,
                                 **COUNT_WIDTHS))
    m.eval()
    return m


def _decode_window(eng, prompt, sp, horizon):
    """One request: prefill outside the window, then decode at a fixed
    horizon.  Returns the KV bytes the window's decode steps read."""
    eng.submit(prompt, sp)
    eng.admit()
    kv0 = eng.counters()["kv_bytes_read"]
    while eng.scheduler.has_work:
        eng.step(horizon=horizon)
    return eng.counters()["kv_bytes_read"] - kv0


@functools.lru_cache(maxsize=None)
def _self_repeating_prompt():
    """A prompt whose greedy continuation repeats itself, so that the
    n-gram drafter's proposals land: two pilot generations find the cycle
    a seeded random-weight model's greedy stream falls into, and the
    prompt is cut a few tokens into the longest constant run."""
    model, rng = _count_model(), np.random.RandomState(0)
    max_seq, new_tokens = 96, 32

    def stream(prompt):
        eng = Engine(model, EngineConfig(num_slots=1, max_seq_len=max_seq,
                                         max_horizon=8),
                     register_profiler=False)
        req = eng.submit(list(prompt), SamplingParams(max_new_tokens=48))
        while eng.scheduler.has_work:
            eng.step(horizon=8)
        eng.close()
        return list(prompt) + req.output_ids

    tail = stream((rng.randint(0, 1024, 4).tolist() * 4)[:16])[-8:]
    period = next(p for p in (1, 2, 3, 4)
                  if p == 4 or all(tail[i] == tail[i - p]
                                   for i in range(p, 8)))
    s2 = stream((tail[-period:] * 16)[:16])
    run_start, run_len, i = 0, 1, 0
    while i < len(s2):
        j = i
        while j < len(s2) and s2[j] == s2[i]:
            j += 1
        if j - i > run_len:
            run_start, run_len = i, j - i
        i = j
    return tuple(s2[:min(run_start + 4, max_seq - new_tokens)])


class TestExactCounts:
    """The counts of each serving mechanism, held exact: dispatches,
    compiles, host syncs, KV bytes a step, pool blocks, swap bytes,
    accept lengths, forced tokens, collective calls."""

    @pytest.mark.parametrize("horizon,horizons,steps,kv_bytes", [
        (1, 31, 31, 6029312), (4, 8, 32, 6291456), (8, 4, 32, 6291456),
        (16, 2, 32, 6291456)])
    def test_fixed_horizon_dispatches_syncs_kv_bytes(
            self, horizon, horizons, steps, kv_bytes):
        """b1, prefill 16 + 32 new: a horizon-h engine dispatches
        ceil(31 / h) horizons, syncs with the host once a horizon,
        compiles one program a (horizon, table width) bucket, and its
        decode steps read these KV bytes (a scanned horizon runs its
        frozen last step too: 32 steps for 31 tokens)."""
        prompt = np.random.RandomState(0).randint(0, 1024, 16).tolist()
        eng = Engine(_count_model(), EngineConfig(
            num_slots=1, max_seq_len=64, max_horizon=16),
            register_profiler=False)
        kv = _decode_window(eng, prompt, SamplingParams(max_new_tokens=32),
                            horizon)
        c = eng.stats()
        eng.close()
        assert c["decode_horizons"] == c["decode_host_syncs"] == horizons
        assert c["decode_buckets"] == [(horizon, 2, 0), (horizon, 4, 0)]
        assert c["decode_compiles"] == 2 and c["prefill_compiles"] == 1
        assert c["decode_steps"] == steps
        assert kv == kv_bytes

    def test_staggered_batch_compiles_one_program_a_bucket(self):
        """8 slots, 16 requests of prefill 32 + 8 new joining at step
        boundaries: one decode program a horizon bucket, fewer horizons
        than steps, one host sync a horizon."""
        rng = np.random.RandomState(0)
        eng = Engine(_count_model(),
                     EngineConfig(num_slots=8, max_seq_len=64),
                     register_profiler=False)
        prompts = [rng.randint(0, 1024, 32).tolist() for _ in range(16)]
        sp = SamplingParams(max_new_tokens=8)
        eng.generate(prompts[0], sp)
        kv0 = eng.counters()["kv_bytes_read"]
        for p in prompts[:8]:
            eng.submit(p, sp)
        pending = prompts[8:]
        while eng.scheduler.has_work:
            finished = eng.step()
            if pending and finished:
                eng.submit(pending.pop(0), sp)
        c = eng.stats()
        kv = c["kv_pool"]["kv_bytes_read"] - kv0
        eng.close()
        assert c["horizon_buckets"] == [1, 2, 4]
        assert c["decode_compiles"] == len(c["horizon_buckets"])
        assert c["prefill_compiles"] == 3
        assert c["decode_horizons"] == 30 < c["decode_steps"]
        assert c["decode_host_syncs"] == c["decode_horizons"]
        assert kv // c["decode_steps"] == 1887436

    @pytest.mark.parametrize("mode,dispatches,hit_ratio", [
        ("per-request", 8, 0.0), ("batched", 1, 0.0), ("prefix", 1, 0.889)])
    def test_shared_prefix_prefill_dispatches(self, mode, dispatches,
                                              hit_ratio):
        """8 requests extending one 64-token system prompt by 8 tokens:
        a prefill dispatch a request when admitted one at a time, ONE for
        all when co-bucketed, and with a warm prefix cache 64 of each
        72 prompt tokens leased and not computed."""
        rng = np.random.RandomState(0)
        system = rng.randint(0, 1024, 64).tolist()
        prompts = [system + rng.randint(0, 1024, 8).tolist()
                   for _ in range(8)]
        sp = SamplingParams(max_new_tokens=4)
        eng = Engine(_count_model(), EngineConfig(
            num_slots=8, max_seq_len=128,
            prefix_block_size=16 if mode == "prefix" else 0),
            register_profiler=False)

        def drive():
            for p in prompts:
                eng.submit(p, sp)
            if mode == "per-request":
                while eng.scheduler.queue_depth and eng.cache.free_slots:
                    eng._prefill_batch(eng.scheduler.admissible(1))
            while eng.scheduler.has_work:
                eng.step()

        drive()                      # fills the prefix cache
        before = eng.counters()
        drive()
        after = eng.counters()
        eng.close()
        delta = {k: after[k] - before[k] for k in (
            "prefill_calls", "prefill_requests", "prefix_hit_tokens",
            "prompt_tokens")}
        assert delta["prefill_calls"] == dispatches
        assert delta["prefill_requests"] == 8
        assert delta["prompt_tokens"] == 8 * 72
        assert round(delta["prefix_hit_tokens"] / delta["prompt_tokens"],
                     3) == hit_ratio

    @pytest.mark.parametrize("ragged,prompt_len,widths,kv_bytes_per_step", [
        (True, 8, [1, 2], 98304), (True, 40, [4], 262144),
        (False, 8, [4], 262144), (False, 40, [4], 262144)])
    def test_ragged_decode_reads_live_blocks_only(
            self, ragged, prompt_len, widths, kv_bytes_per_step):
        """b1 + 16 new at horizon 8: the ragged table width follows the
        deepest live row (a short row reads 3/8 of the full width's
        bytes), the full-width table reads max_blocks_per_slot always."""
        prompt = np.random.RandomState(0).randint(
            0, 1024, prompt_len).tolist()
        eng = Engine(_count_model(), EngineConfig(
            num_slots=1, max_seq_len=64, max_horizon=8,
            ragged_attention=ragged), register_profiler=False)
        kv = _decode_window(eng, prompt, SamplingParams(max_new_tokens=16),
                            8)
        c = eng.stats()
        eng.close()
        assert sorted({bk[1] for bk in c["decode_buckets"]}) == widths
        assert kv // 16 == kv_bytes_per_step

    @pytest.mark.parametrize("k,hist,mean,horizons", [
        (0, {}, 0.0, 4),
        (2, {1: 4, 3: 9}, 2.385, 2),
        (4, {1: 4, 3: 1, 4: 1, 5: 4}, 3.1, 2),
        (8, {1: 4, 4: 1, 6: 1, 8: 1, 9: 1}, 3.875, 1)])
    def test_speculation_accept_lengths(self, k, hist, mean, horizons):
        """Greedy, b1, 32 new on the self-repeating prompt: the accept
        lengths of every verify window at draft width K (31 tokens come
        from decode; K = 0 drafts nothing)."""
        eng = Engine(_count_model(), EngineConfig(
            num_slots=1, max_seq_len=96, max_horizon=8, spec_k=k,
            spec_adaptive=False), register_profiler=False)
        _decode_window(eng, list(_self_repeating_prompt()),
                       SamplingParams(max_new_tokens=32), 8)
        c = eng.stats()
        eng.close()
        spec = c["spec"]
        assert spec["accept_len_hist"] == hist
        assert sum(n * v for n, v in hist.items()) == (31 if k else 0)
        assert round(spec["mean_accept_len"], 3) == mean
        assert c["decode_horizons"] == horizons

    @pytest.mark.parametrize("wq,kq,block_bytes,weight_bytes,kv_per_step", [
        (None, None, 65536, 7345152, 262144),
        ("int8", None, 65536, 2649088, 262144),
        ("int8", "int8", 16640, 2649088, 66560)])
    def test_int8_bytes(self, wq, kq, block_bytes, weight_bytes,
                        kv_per_step):
        """b1, prefill 40 + 16 new: int8 weights are 2,649,088 resident
        bytes of f32's 7,345,152; an int8 block with its f32 scales is
        16,640 bytes of 65,536, and a decode step reads that share."""
        prompt = np.random.RandomState(0).randint(0, 1024, 40).tolist()
        eng = Engine(_count_model(), EngineConfig(
            num_slots=1, max_seq_len=64, max_horizon=8, weight_dtype=wq,
            kv_cache_dtype=kq), register_profiler=False)
        assert eng.pool.bytes_per_block == block_bytes
        kv = _decode_window(eng, prompt, SamplingParams(max_new_tokens=16),
                            8)
        c = eng.stats()
        eng.close()
        assert c["quant"]["weight_bytes"] == weight_bytes
        assert kv // 16 == kv_per_step

    @pytest.mark.parametrize("kq,blocks,peak,preemptions", [
        (None, 17, 5, 3), ("int8", 66, 21, 5)])
    def test_int8_kv_pool_under_a_fixed_byte_budget(self, kq, blocks, peak,
                                                    preemptions):
        """1,114,112 bytes of pool (17 f32 blocks) hold 66 int8 blocks:
        of 24 requests of prefill 40 + 16 new, 5 run at once on the f32
        pool and 21 on the int8 one."""
        budget = (1 + 4 * 4) * 65536
        assert budget == 1114112
        prompt = np.random.RandomState(0).randint(0, 1024, 40).tolist()
        sp = SamplingParams(max_new_tokens=16)
        assert budget // (16640 if kq else 65536) == blocks
        eng = Engine(_count_model(), EngineConfig(
            num_slots=24, max_seq_len=64, max_horizon=4,
            kv_cache_dtype=kq, kv_pool_blocks=blocks, prefix_block_size=0),
            register_profiler=False)
        for _ in range(24):
            eng.submit(prompt, sp)
        running = 0
        while eng.scheduler.has_work:
            eng.step(horizon=4)
            running = max(running, len(eng.scheduler.running))
        c = eng.counters()
        eng.close()
        assert running == peak
        assert c["preemptions"] == preemptions

    @pytest.mark.parametrize(
        "chunk_tokens,prefill_calls,chunk_dispatches,max_bucket",
        [(0, 2, 0, 256), (64, 5, 3, 64)])
    def test_long_arrival_chunk_counts(self, chunk_tokens, prefill_calls,
                                       chunk_dispatches, max_bucket):
        """A 256-token prompt arriving into eight decoding lanes (horizon
        1): whole, it is ONE 256-bucket dispatch; at chunk_tokens 64 no
        dispatch is wider than 64 and the prompt takes 3 chunk
        dispatches after its first."""
        rng = np.random.RandomState(0)
        eng = Engine(_count_model(384), EngineConfig(
            num_slots=9, max_seq_len=384, max_horizon=1,
            prefill_chunk_tokens=chunk_tokens, kv_pool_blocks=128),
            register_profiler=False)
        decoders = [eng.submit(rng.randint(0, 1024, 16).tolist(),
                               SamplingParams(max_new_tokens=12))
                    for _ in range(8)]
        while any(len(r.output_ids) < 4 for r in decoders):
            eng.step()
        eng.submit(rng.randint(0, 1024, 256).tolist(),
                   SamplingParams(max_new_tokens=4))
        eng.run()
        c, st = eng.counters(), eng.stats()["prefill"]
        eng.close()
        assert c["prefill_calls"] == prefill_calls
        assert c["prefill_chunk_dispatches"] == chunk_dispatches
        assert max(b for _, b in st["buckets"]) == max_bucket
        assert st["chunk_tokens"] == chunk_tokens

    @pytest.mark.parametrize("ctx,swap_bytes", [
        (32, 196608), (64, 327680), (128, 589824)])
    def test_swap_in_bytes_by_context(self, ctx, swap_bytes):
        """A preempted lane of ``ctx`` tokens whose blocks were demoted to
        the host tier comes back by ONE swap-in of its whole chain:
        ctx/16 + 1 blocks of 65,536 bytes out, the same bytes in."""
        rng = np.random.RandomState(0)
        eng = Engine(_count_model(256), EngineConfig(
            num_slots=2, max_seq_len=ctx + 24, max_horizon=4,
            prefix_block_size=16, prefix_cache_bytes=4 << 20,
            kv_host_bytes=64 << 20, kv_swap_policy="always"),
            register_profiler=False)
        r = eng.submit(rng.randint(0, 1024, ctx).tolist(),
                       SamplingParams(max_new_tokens=8))
        eng.step(horizon=2)
        eng.preempt(r)
        eng.prefix.reclaim(10 ** 6)       # demote the whole chain
        eng.run()
        c = eng.counters()
        eng.close()
        assert c["kv_swap_ins"] == c["kv_swap_outs"] == 1
        assert c["kv_swap_in_bytes"] == c["kv_swap_out_bytes"] == swap_bytes
        assert swap_bytes == (ctx // 16 + 1) * 65536

    def test_preemption_storm_counts(self):
        """8 requests of 128 tokens on 4 slots and a pool of 36 blocks,
        every running lane preempted and the radix store reclaimed at
        the first boundary: the preemptions, swaps and bytes of the
        schedule that follows."""
        rng = np.random.RandomState(0)
        eng = Engine(_count_model(256), EngineConfig(
            num_slots=4, max_seq_len=152, max_horizon=4,
            prefix_block_size=16, prefix_cache_bytes=4 << 20,
            kv_pool_blocks=36, kv_host_bytes=64 << 20,
            kv_swap_policy="always"), register_profiler=False)
        for _ in range(8):
            eng.submit(rng.randint(0, 1024, 128).tolist(),
                       SamplingParams(max_new_tokens=8))
        first = True
        while eng.scheduler.has_work:
            eng.step()
            if first:
                for r in list(eng.scheduler.running.values()):
                    eng.preempt(r)
                eng.prefix.reclaim(10 ** 6)
                first = False
        c = eng.counters()
        eng.close()
        assert c["preemptions"] == 7
        assert (c["kv_swap_ins"], c["kv_swap_outs"]) == (6, 7)
        assert c["kv_swap_in_bytes"] == 3473408
        assert c["kv_swap_out_bytes"] == 3997696

    @pytest.mark.parametrize("k,schema,forced_drafting,forced,hist", [
        (0, None, True, 0, {}),
        (4, None, True, 0, {1: 96, 2: 16, 3: 8, 4: 24}),
        (0, "object", True, 0, {}),
        (4, "object", False, 0, {1: 104}),
        (4, "object", True, 56, {1: 24, 2: 8, 3: 8, 5: 8}),
        (4, "array", True, 8, {1: 16})])
    def test_grammar_forced_tokens(self, k, schema, forced_drafting,
                                   forced, hist):
        """8 greedy lanes, 32 new at the most, under a JSON schema: only
        forced drafting at K > 0 drafts the skeleton's sole-legal tokens
        (7 of an object's 14, so 6 verify windows a lane where plain
        n-gram drafts take 13); an array of such objects is closed at
        once by this model and forces its one end token a lane."""
        vocab = [chr(32 + i) for i in range(95)] + ["<eos>"] + [
            '{"', '":', '",', '"}', '": "', '", "', '},{"', 'true',
            'false', 'null', '["', '"]', '":"']
        vocab += [f"<unused{i}>" for i in range(len(vocab), 1024)]
        grammar = {"type": "object",
                   "properties": {"a": {"enum": ["x", "y"]},
                                  "b": {"type": "boolean"}},
                   "required": ["a", "b"]}
        if schema == "array":
            grammar = {"type": "array", "items": grammar}
        prompt = np.random.RandomState(0).randint(0, 1024, 16).tolist()
        eng = Engine(_count_model(), EngineConfig(
            num_slots=8, max_seq_len=96, max_horizon=8, spec_k=k,
            spec_adaptive=False,
            grammar_max_states=256 if schema else 0,
            grammar_vocab=vocab if schema else None,
            grammar_forced_drafting=forced_drafting),
            register_profiler=False)
        sp = SamplingParams(max_new_tokens=32,
                            eos_token_id=95 if schema else None)
        reqs = [eng.submit(list(prompt), sp,
                           grammar=grammar if schema else None)
                for _ in range(8)]
        eng.admit()
        while eng.scheduler.has_work:
            eng.step(horizon=8)
        s = eng.stats()
        eng.close()
        assert s["structured"]["forced_tokens"] == forced
        assert s["spec"]["accept_len_hist"] == hist
        text = "".join(vocab[t] for t in reqs[0].output_ids)
        if schema:
            assert text == {"object": '{"a":"y","b":true}<eos>',
                            "array": "[]<eos>"}[schema]

    def test_tp2_collectives_and_kv_bytes_a_shard(self):
        """tp=2 on virtual devices, 4 lanes of prefill 16 + 32 new at
        horizon 8: 16 psums and 56 all_gathers a dispatch (L·h and
        (3L+1)·h), and the same KV bytes read as the single chip, half
        of them a shard (the pool is sharded by kv head)."""
        from paddle_tpu.serving import MeshEngine

        rng = np.random.RandomState(0)
        prompts = [rng.randint(0, 1024, 16).tolist() for _ in range(4)]
        sp = SamplingParams(max_new_tokens=32)
        cfg = dict(num_slots=4, max_seq_len=96, max_horizon=8)

        def window(eng):
            for p in prompts:
                eng.submit(list(p), sp)
            eng.admit()
            kv0 = eng.counters()["kv_bytes_read"]
            while eng.scheduler.has_work:
                eng.step(horizon=8)
            return eng.counters()["kv_bytes_read"] - kv0

        one = Engine(_count_model(), EngineConfig(**cfg),
                     register_profiler=False)
        read_one = window(one)
        one.close()
        tp2 = MeshEngine(_count_model(), EngineConfig(**cfg), tp=2,
                         register_profiler=False)
        read_tp2 = window(tp2)
        census = tp2.decode_comms_report(horizon=8).counts()
        mesh = tp2.stats()["mesh"]
        pool_bytes = tp2.pool.bytes_per_block * tp2.pool.num_blocks
        tp2.close()
        assert census == {("psum", "tp"): 16, ("all_gather", "tp"): 56}
        assert read_one == read_tp2 == 25165824
        assert mesh["kv_heads_per_shard"] * 2 == 4
        assert mesh["kv_pool_bytes_per_shard"] * 2 == pool_bytes
