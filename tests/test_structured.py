"""Structured (grammar-constrained) decoding: engine-level
acceptance — constrained greedy output is ALWAYS grammar-valid,
batched-vs-sequential and K=0-vs-K=4 streams are bitwise-equal (greedy
AND seeded), forced-token drafting beats plain n-gram drafting on a
JSON workload, and the knobs-off engine threads ``None`` for every
grammar argument.  Compiler-level unit tests (regex -> char DFA ->
token DFA, schema lowering, GrammarSlab) live in test_grammar_dfa.py."""

import json
import types

import pytest

import paddle_tpu as paddle
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.serving import (
    Engine, EngineConfig, GrammarError, SamplingParams, compile_regex,
)

TINY = GPTConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                 num_hidden_layers=2, num_attention_heads=4,
                 max_position_embeddings=128)


def _model(seed=0):
    paddle.seed(seed)
    m = GPTForCausalLM(TINY)
    m.eval()
    return m


def make_vocab(size=128, eos_id=95):
    """Printable-ASCII single chars (ids 0..94), <eos> at 95, then a
    handful of multi-char tokens exercising tokenizer boundaries."""
    vocab = [chr(32 + i) for i in range(95)]
    vocab.append("<eos>")
    vocab.extend(['{"', '":', '",', '"}', 'true', 'false', 'null',
                  '": "', '", "', 'ab', 'abc', '0', '12'])
    while len(vocab) < size:
        vocab.append(f"<unused{len(vocab)}>")
    return vocab


VOCAB = make_vocab()
EOS = 95
SCHEMA = {"type": "object",
          "properties": {"a": {"enum": ["x", "y"]},
                         "b": {"type": "boolean"}},
          "required": ["a", "b"]}

GREEDY = SamplingParams(max_new_tokens=48, eos_token_id=EOS)
SEEDED = SamplingParams(temperature=0.9, top_k=20, seed=7,
                        max_new_tokens=48, eos_token_id=EOS)


def _cfg(**kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_seq_len", 96)
    kw.setdefault("max_horizon", 4)
    kw.setdefault("prefix_block_size", 4)
    kw.setdefault("prefix_cache_bytes", 0)
    kw.setdefault("grammar_max_states", 256)
    kw.setdefault("grammar_vocab", VOCAB)
    return EngineConfig(**kw)


def _drive(eng):
    while eng.scheduler.has_work:
        eng.step()


def _text(req):
    return "".join(VOCAB[t] for t in req.output_ids if t != EOS)


# ------------------------------------------------------------ engine
class TestStructuredEngine:
    """Constrained decode through the fused horizon scan: validity,
    bitwise parity across batching and spec_k, forced drafting,
    knobs-off structure."""

    def test_constrained_greedy_is_schema_valid(self):
        m = _model()
        eng = Engine(m, _cfg(), register_profiler=False)
        req = eng.submit([3, 1, 4], sampling=GREEDY, grammar=SCHEMA)
        free = eng.submit([9, 2, 6],
                          sampling=SamplingParams(max_new_tokens=8))
        _drive(eng)
        obj = json.loads(_text(req))
        assert set(obj) == {"a", "b"}
        assert obj["a"] in ("x", "y") and isinstance(obj["b"], bool)
        assert req.output_ids[-1] == EOS and req.finish_reason == "eos"
        st = eng.stats()["structured"]
        assert st["enabled"] and st["grammars_installed"] == 0
        assert st["compile_cache_misses"] == 1
        eng.close()
        # the free lane is untouched by its constrained neighbour:
        # bitwise-equal to a solo run on an unconstrained engine
        solo = Engine(m, _cfg(), register_profiler=False)
        ref = solo.submit([9, 2, 6],
                          sampling=SamplingParams(max_new_tokens=8))
        _drive(solo)
        solo.close()
        assert free.output_ids == ref.output_ids

    def test_seeded_constrained_valid_and_deterministic(self):
        m = _model()
        outs = []
        for _ in range(2):
            eng = Engine(m, _cfg(), register_profiler=False)
            r = eng.submit([3, 1, 4], sampling=SEEDED, grammar=SCHEMA)
            _drive(eng)
            eng.close()
            json.loads(_text(r))                  # always schema-valid
            outs.append(r.output_ids)
        assert outs[0] == outs[1]

    def test_k4_bitwise_equals_k0_and_forces_tokens(self):
        """Speculative decode with forced-token drafting must not change
        a single emitted token — greedy AND seeded — while the JSON
        skeleton's forced states land as draft accepts."""
        m = _model()
        ref = {}
        for name, sp in (("greedy", GREEDY), ("seeded", SEEDED)):
            eng = Engine(m, _cfg(), register_profiler=False)
            r = eng.submit([3, 1, 4], sampling=sp, grammar=SCHEMA)
            _drive(eng)
            eng.close()
            ref[name] = r.output_ids
        eng = Engine(m, _cfg(spec_k=4), register_profiler=False)
        reqs = {name: eng.submit([3, 1, 4], sampling=sp, grammar=SCHEMA)
                for name, sp in (("greedy", GREEDY), ("seeded", SEEDED))}
        _drive(eng)
        for name, r in reqs.items():
            assert r.output_ids == ref[name], name
        st = eng.stats()["structured"]
        assert st["forced_tokens"] > 0
        assert eng.counters()["spec_forced_tokens"] == st["forced_tokens"]
        # flight records restate the counter per request
        traced = sum(r.trace.counts()["spec_forced_tokens"]
                     for r in reqs.values())
        assert traced == st["forced_tokens"]
        eng.close()

    def test_batched_vs_sequential_bitwise(self):
        """Two constrained lanes (seeded schema + greedy regex) batched
        together equal their solo runs token-for-token."""
        m = _model()
        eng = Engine(m, _cfg(), register_profiler=False)
        ra = eng.submit([3, 1, 4], sampling=SEEDED, grammar=SCHEMA)
        rb = eng.submit([9, 2, 6], sampling=GREEDY,
                        grammar="(ab|abc)*c")
        _drive(eng)
        eng.close()
        solo = []
        for prompt, sp, g in ([3, 1, 4], SEEDED, SCHEMA), \
                             ([9, 2, 6], GREEDY, "(ab|abc)*c"):
            e = Engine(m, _cfg(), register_profiler=False)
            r = e.submit(prompt, sampling=sp, grammar=g)
            _drive(e)
            e.close()
            solo.append(r.output_ids)
        assert [ra.output_ids, rb.output_ids] == solo
        json.loads(_text(ra))
        # the greedy lane may run out of tokens before an accept state
        # ("abab..."): what the grammar guarantees is a live DFA prefix
        assert compile_regex("(ab|abc)*c").walk(0, _text(rb)) >= 0

    def test_forced_drafting_beats_plain_ngram_on_json(self):
        """The acceptance bar: on a JSON workload, grammar-forced
        drafting's mean accept length >= the plain n-gram drafter's."""
        m = _model()
        accept = {}
        for forced in (True, False):
            eng = Engine(m, _cfg(spec_k=4, num_slots=2,
                                 grammar_forced_drafting=forced),
                         register_profiler=False)
            for p in ([3, 1, 4], [9, 2, 6]):
                eng.submit(p, sampling=GREEDY, grammar=SCHEMA)
            _drive(eng)
            accept[forced] = eng.stats()["spec"]["mean_accept_len"]
            eng.close()
        assert accept[True] >= accept[False]

    def test_slab_released_on_retire_and_abort(self):
        m = _model()
        eng = Engine(m, _cfg(num_slots=1), register_profiler=False)
        done = eng.submit([3, 1, 4], sampling=GREEDY, grammar=SCHEMA)
        queued = eng.submit([9, 2, 6], sampling=GREEDY, grammar=SCHEMA)
        assert eng.stats()["structured"]["grammars_installed"] == 1
        eng.abort(queued)                    # released from WAITING
        _drive(eng)
        assert done.finish_reason == "eos"
        st = eng.stats()["structured"]
        assert st["grammars_installed"] == 0 and st["states_used"] == 1
        assert st["compile_cache_hits"] == 1
        running = eng.submit([3, 1, 4], sampling=GREEDY, grammar=SCHEMA)
        eng.step()
        eng.abort(running)                   # released from RUNNING
        assert eng.stats()["structured"]["grammars_installed"] == 0
        assert eng.pool.blocks_in_use == 0
        eng.close()

    def test_slab_exhaustion_refused_before_queueing(self):
        """An over-capacity grammar raises at submit() with NOTHING
        queued — the engine keeps serving.  (Regression: install() used
        to run after scheduler.submit(), stranding a request with
        ``grammar`` set but no slab segment, and the next admission
        pass crashed the step loop for every request.)"""
        m = _model()
        eng = Engine(m, _cfg(grammar_max_states=8),
                     register_profiler=False)
        with pytest.raises(RuntimeError, match="slab exhausted"):
            eng.submit([3, 1, 4], sampling=GREEDY, grammar=SCHEMA)
        assert eng.scheduler.queue_depth == 0
        assert eng.stats()["structured"]["grammars_installed"] == 0
        # still healthy: a small grammar and a free lane decode fine
        r = eng.submit([3, 1, 4], sampling=GREEDY, grammar="a{2}")
        free = eng.submit([9, 2, 6],
                          sampling=SamplingParams(max_new_tokens=4))
        _drive(eng)
        assert _text(r) == "aa" and r.finish_reason == "eos"
        assert len(free.output_ids) == 4
        assert eng.stats()["structured"]["grammars_installed"] == 0
        eng.close()

    def test_compile_cache_bounded_lru(self):
        """A stream of unique gateway grammars cannot grow the host DFA
        cache without bound: retired entries trim to
        ``grammar_cache_keep`` LRU, a repeat inside the window is still
        a hit, and an evicted grammar recompiles."""
        m = _model()
        eng = Engine(m, _cfg(grammar_cache_keep=2),
                     register_profiler=False)
        pats = ["a{%d}" % n for n in (1, 2, 3, 4)]
        for p in pats:
            eng.submit([3], sampling=GREEDY, grammar=p)
            _drive(eng)
        st = eng.stats()["structured"]
        assert st["compile_cache_entries"] == 2
        assert st["compile_cache_misses"] == 4
        eng.submit([3], sampling=GREEDY, grammar=pats[-1])  # kept: hit
        _drive(eng)
        assert eng.stats()["structured"]["compile_cache_hits"] == 1
        eng.submit([3], sampling=GREEDY, grammar=pats[0])   # evicted
        _drive(eng)
        st = eng.stats()["structured"]
        assert st["compile_cache_misses"] == 5
        assert st["compile_cache_entries"] == 2
        eng.close()
        # live grammars are PINNED even at keep=0 (the admission walk
        # reads the cached TokenDFA), and fully evict once retired
        eng = Engine(m, _cfg(grammar_cache_keep=0, num_slots=1),
                     register_profiler=False)
        eng.submit([3, 1, 4], sampling=GREEDY, grammar=SCHEMA)
        eng.submit([9, 2, 6], sampling=GREEDY, grammar="a{2}")
        assert eng.stats()["structured"]["compile_cache_entries"] == 2
        _drive(eng)
        assert eng.stats()["structured"]["compile_cache_entries"] == 0
        eng.close()

    def test_resume_ids_must_walk_grammar(self):
        """Cross-engine resume tokens that are illegal under the
        request grammar are refused at submit() — not silently
        un-constrained at admission (the slab stores REJECT as the
        accept-all sentinel row, so only the eager cache walk can see
        the divergence)."""
        m = _model()
        eng = Engine(m, _cfg(), register_profiler=False)
        for bad in ([90, 1],      # 'z' can't open the schema's object
                    [5000]):      # beyond the vocab entirely
            with pytest.raises(ValueError, match="illegal"):
                eng.submit([3, 1, 4], sampling=SEEDED, grammar=SCHEMA,
                           resume_ids=bad)
        assert eng.scheduler.queue_depth == 0
        assert eng.stats()["structured"]["grammars_installed"] == 0
        eng.close()

    def test_cross_engine_constrained_resume_bitwise(self):
        """A constrained seeded stream cut mid-generation resumes
        bitwise on a fresh engine via resume_ids (the failover path)."""
        m = _model()
        ref = Engine(m, _cfg(), register_profiler=False)
        want = ref.submit([3, 1, 4], sampling=SEEDED, grammar=SCHEMA)
        _drive(ref)
        ref.close()
        cut = 5
        assert len(want.output_ids) > cut
        eng = Engine(m, _cfg(), register_profiler=False)
        r = eng.submit([3, 1, 4], sampling=SEEDED, grammar=SCHEMA,
                       resume_ids=want.output_ids[:cut])
        _drive(eng)
        eng.close()
        assert r.output_ids == want.output_ids
        json.loads(_text(r))

    def test_submit_validation(self):
        m = _model()
        eng = Engine(m, _cfg(), register_profiler=False)
        with pytest.raises(ValueError, match="eos"):
            eng.submit([1, 2], sampling=SamplingParams(max_new_tokens=4),
                       grammar=SCHEMA)
        with pytest.raises(GrammarError):
            eng.submit([1, 2], sampling=GREEDY, grammar=17)
        eng.close()
        off = Engine(m, EngineConfig(num_slots=2, max_seq_len=96,
                                     prefix_block_size=4,
                                     prefix_cache_bytes=0),
                     register_profiler=False)
        with pytest.raises(ValueError, match="grammar_max_states"):
            off.submit([1, 2], sampling=GREEDY, grammar=SCHEMA)
        off.close()
        novocab = Engine(m, _cfg(grammar_vocab=None),
                         register_profiler=False)
        with pytest.raises(ValueError, match="grammar_vocab"):
            novocab.submit([1, 2], sampling=GREEDY, grammar=SCHEMA)
        novocab.close()
        with pytest.raises(ValueError, match="grammar_max_states"):
            Engine(m, EngineConfig(num_slots=2, max_seq_len=96,
                                   grammar_max_states=-1),
                   register_profiler=False)

    def test_knobs_off_engine_threads_none(self):
        """grammar_max_states=0 (the default): no slab, no device
        tables, and the compiled programs carry no grammar operands."""
        m = _model()
        eng = Engine(m, EngineConfig(num_slots=2, max_seq_len=96,
                                     max_horizon=4, prefix_block_size=4,
                                     prefix_cache_bytes=0),
                     register_profiler=False)
        r = eng.submit([3, 1, 4],
                       sampling=SamplingParams(max_new_tokens=8))
        _drive(eng)
        assert len(r.output_ids) == 8
        assert eng._grammar_slab is None
        assert eng._d_dfa_state is None and eng._d_dfa_next is None
        assert eng._d_dfa_mask is None and eng._d_dfa_forced is None
        assert eng.stats()["structured"]["enabled"] is False
        eng.close()

    @pytest.mark.slow
    def test_preempt_resume_parity(self):
        """A constrained seeded lane preempted mid-decode resumes
        bitwise: the DFA admission walk replays its emitted tokens."""
        m = _model()
        ref = Engine(m, _cfg(), register_profiler=False)
        want = ref.submit([3, 1, 4], sampling=SEEDED, grammar=SCHEMA)
        _drive(ref)
        ref.close()
        eng = Engine(m, _cfg(), register_profiler=False)
        r = eng.submit([3, 1, 4], sampling=SEEDED, grammar=SCHEMA)
        eng.step(horizon=2)
        eng.step(horizon=2)
        eng.preempt(r)
        assert r.resumed is True
        assert eng.stats()["structured"]["grammars_installed"] == 1
        _drive(eng)
        assert r.output_ids == want.output_ids
        json.loads(_text(r))
        assert eng.stats()["structured"]["grammars_installed"] == 0
        eng.close()

    @pytest.mark.slow
    def test_prefix_hit_parity(self):
        """Constrained decode over a prefix-cache hit: leased blocks
        change nothing about the stream."""
        m = _model()
        shared = [5, 5, 7, 7, 1, 2, 3, 4]
        outs = []
        for bytes_ in (0, 1 << 20):
            eng = Engine(m, _cfg(prefix_cache_bytes=bytes_),
                         register_profiler=False)
            # sequential so the second prompt can hit the blocks the
            # first one's retirement adopted
            pair = []
            for extra in (9, 8):
                pair.append(eng.submit(shared + [extra], sampling=GREEDY,
                                       grammar=SCHEMA))
                _drive(eng)
            if bytes_:
                assert eng.stats()["prefix"]["hit_tokens"] > 0
            outs.append([r.output_ids for r in pair])
            eng.close()
        assert outs[0] == outs[1]

    @pytest.mark.slow
    def test_int8_kv_constrained_still_valid(self):
        """Quantized KV changes logits, not legality: constrained
        greedy under int8 KV is still schema-valid and deterministic."""
        m = _model()
        outs = []
        for _ in range(2):
            eng = Engine(m, _cfg(kv_cache_dtype="int8"),
                         register_profiler=False)
            r = eng.submit([3, 1, 4], sampling=GREEDY, grammar=SCHEMA)
            _drive(eng)
            eng.close()
            json.loads(_text(r))
            assert r.finish_reason == "eos"
            outs.append(r.output_ids)
        assert outs[0] == outs[1]


# ------------------------------------------------------------- sharded
class TestStructuredSharded:
    """tp=2 MeshEngine under grammar constraints: bitwise parity with
    the single-chip engine, and the layout's placement rule."""

    def test_layout_dfa_tables_replicated(self):
        from jax.sharding import PartitionSpec as P

        from paddle_tpu.serving import ServingSpecLayout

        layout = ServingSpecLayout()
        assert layout.dfa_tables() == P()
        assert layout.dfa_tables() == layout.engine_state()

    @pytest.mark.slow
    def test_tp2_constrained_bitwise_parity(self):
        from paddle_tpu.serving import MeshEngine

        m = _model()
        ref = Engine(m, _cfg(), register_profiler=False)
        wa = ref.submit([3, 1, 4], sampling=GREEDY, grammar=SCHEMA)
        wb = ref.submit([9, 2, 6], sampling=SEEDED, grammar=SCHEMA)
        _drive(ref)
        ref.close()
        eng = MeshEngine(m, _cfg(), tp=2, register_profiler=False)
        ra = eng.submit([3, 1, 4], sampling=GREEDY, grammar=SCHEMA)
        rb = eng.submit([9, 2, 6], sampling=SEEDED, grammar=SCHEMA)
        _drive(eng)
        assert ra.output_ids == wa.output_ids
        assert rb.output_ids == wb.output_ids
        json.loads(_text(ra))
        json.loads(_text(rb))
        assert eng.pool.blocks_in_use == 0
        eng.close()

    @pytest.mark.slow
    def test_tp2_constrained_spec_k4_parity(self):
        from paddle_tpu.serving import MeshEngine

        m = _model()
        ref = Engine(m, _cfg(), register_profiler=False)
        want = ref.submit([3, 1, 4], sampling=GREEDY, grammar=SCHEMA)
        _drive(ref)
        ref.close()
        eng = MeshEngine(m, _cfg(spec_k=4), tp=2,
                         register_profiler=False)
        r = eng.submit([3, 1, 4], sampling=GREEDY, grammar=SCHEMA)
        _drive(eng)
        assert r.output_ids == want.output_ids
        assert eng.stats()["structured"]["forced_tokens"] > 0
        eng.close()


# ------------------------------------------------------------- gateway
class TestGatewayProtocol:
    """/v1/completions structured fields: eager validation, typed
    invalid_grammar 400s naming the unsupported feature."""

    @staticmethod
    def _parse(payload):
        from paddle_tpu.serving.gateway import Gateway, GatewayConfig

        gw = types.SimpleNamespace(config=GatewayConfig())
        base = {"prompt": [1, 2, 3], "eos_token_id": EOS}
        return Gateway.parse_completion(gw, dict(base, **payload))

    def _reject(self, payload):
        from paddle_tpu.serving.gateway.protocol import _Reject

        with pytest.raises(_Reject) as e:
            self._parse(payload)
        return e.value

    def test_response_format_json_schema(self):
        parsed = self._parse({"response_format": {
            "type": "json_schema",
            "json_schema": {"schema": SCHEMA}}})
        assert parsed["grammar"].kind == "json_schema"
        # bare schema (no OpenAI "schema" nesting) accepted too
        parsed = self._parse({"response_format": {
            "type": "json_schema", "json_schema": SCHEMA}})
        assert parsed["grammar"].kind == "json_schema"
        assert self._parse({"response_format": {"type": "text"}})[
            "grammar"] is None
        assert self._parse({})["grammar"] is None

    def test_grammar_regex_forms(self):
        assert self._parse({"grammar": "a+b"})["grammar"].kind == "regex"
        parsed = self._parse(
            {"grammar": {"type": "regex", "pattern": "a+b"}})
        assert parsed["grammar"].pattern == "a+b"

    def test_invalid_grammar_400s_name_the_feature(self):
        e = self._reject({"response_format": {
            "type": "json_schema",
            "json_schema": {"schema": {"anyOf": []}}}})
        assert e.status == 400 and e.code == "invalid_grammar"
        assert "anyOf" in str(e)
        e = self._reject({"response_format": {"type": "json_object"}})
        assert e.code == "invalid_grammar" and "json_object" in str(e)
        e = self._reject({"grammar": "(a"})
        assert e.status == 400 and e.code == "invalid_grammar"
        e = self._reject({"grammar": {"type": "bnf", "rules": []}})
        assert e.code == "invalid_grammar"
        e = self._reject({"grammar": "a+", "response_format": {
            "type": "json_schema", "json_schema": SCHEMA}})
        assert e.code == "invalid_grammar" and "exclusive" in str(e)

    def test_constrained_requires_eos(self):
        from paddle_tpu.serving.gateway import Gateway, GatewayConfig
        from paddle_tpu.serving.gateway.protocol import _Reject

        gw = types.SimpleNamespace(config=GatewayConfig())
        with pytest.raises(_Reject) as e:
            Gateway.parse_completion(
                gw, {"prompt": [1, 2], "grammar": "a+"})
        assert e.value.code == "invalid_grammar"
        assert "eos_token_id" in str(e.value)

    @pytest.mark.slow
    def test_http_end_to_end_constrained(self):
        """POST a json_schema response_format through a live gateway:
        the streamed tokens are the engine's constrained stream."""
        import http.client

        from paddle_tpu.serving.gateway import Gateway, GatewayConfig

        m = _model()
        ref = Engine(m, _cfg(), register_profiler=False)
        want = ref.submit([3, 1, 4], sampling=GREEDY, grammar=SCHEMA)
        _drive(ref)
        ref.close()
        eng = Engine(m, _cfg(), register_profiler=False)
        gw = Gateway([eng], GatewayConfig(model_id="tiny")).start()
        try:
            conn = http.client.HTTPConnection("127.0.0.1", gw.port,
                                              timeout=60)
            body = json.dumps({
                "prompt": [3, 1, 4], "max_tokens": 48,
                "eos_token_id": EOS,
                "response_format": {"type": "json_schema",
                                    "json_schema": {"schema": SCHEMA}}})
            conn.request("POST", "/v1/completions", body,
                         {"Content-Type": "application/json"})
            r = conn.getresponse()
            doc = json.loads(r.read())
            assert r.status == 200, doc
            choice = doc["choices"][0]
            assert choice["token_ids"] == want.output_ids
            assert choice["finish_reason"] == "stop"   # OpenAI eos word
            # malformed grammar 400s before anything queues
            conn.request("POST", "/v1/completions",
                         json.dumps({"prompt": [1], "eos_token_id": EOS,
                                     "grammar": "(a"}),
                         {"Content-Type": "application/json"})
            r = conn.getresponse()
            doc = json.loads(r.read())
            assert r.status == 400
            assert doc["error"]["code"] == "invalid_grammar"
        finally:
            gw.shutdown()
        assert eng.pool.blocks_in_use == 0
