"""Prefill projects one row a lane through the LM head
(``Engine._run_model(..., rows=)``), for the three served families' toys:
GPT (vocabulary widened to 256 so that the head's shapes are told apart
from the MLP's in the lowered text), DeepSeek-V2 and Xing4.0 at
``tests/test_deepseek_v2.py``'s and ``tests/test_xing4.py``'s sizes.

The reference is in this file: the same engine with every position
through the head and the row indexed afterwards (``_FullHead``).  Each
family serves one scenario on both: a batch with a padding lane and a
grammar-masked lane, an exact prefix hit (suffix length 1) and a chunked
prompt's final chunk.  The sampled tokens must be equal, and every
prefill's logits within ``ATOL = 1e-5`` (float32 on the CPU: the one
row's product and the whole slab's differ by the order of their sums
only).  The mesh engine keeps its sharded head and indexes its logits:
its prefill logits are bitwise those of the full head.
"""

import re

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.models.deepseek_v2 import (DeepSeekV2Config,
                                           DeepSeekV2ForCausalLM)
from paddle_tpu.models.xing4 import Xing4Config, Xing4ForCausalLM
from paddle_tpu.observability import metrics
from paddle_tpu.serving import Engine, EngineConfig, SamplingParams
from paddle_tpu.serving import engine as engine_mod

ATOL = 1e-5
EOS = 95

FAMILIES = {
    "gpt": (GPTForCausalLM, GPTConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4,
        max_position_embeddings=64)),
    "deepseek_v2": (DeepSeekV2ForCausalLM, DeepSeekV2Config(
        vocab_size=512, hidden_size=64, intermediate_size=160,
        moe_intermediate_size=48, num_hidden_layers=3, num_attention_heads=4,
        kv_lora_rank=32, qk_nope_head_dim=24, qk_rope_head_dim=16,
        v_head_dim=24, n_routed_experts=8, num_experts_per_tok=2,
        n_shared_experts=1, max_position_embeddings=2560,
        rope_original_max_position=64)),
    "xing4": (Xing4ForCausalLM, Xing4Config(
        vocab_size=512, hidden_size=64, intermediate_size=160,
        moe_intermediate_size=48, num_hidden_layers=3, num_attention_heads=4,
        q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=24,
        qk_rope_head_dim=16, v_head_dim=24, n_routed_experts=8,
        num_experts_per_tok=2, n_shared_experts=1, first_k_dense_replace=2,
        max_position_embeddings=2560, rope_original_max_position=64)),
}


def _vocab(size):
    """Printable single characters for ids 0..94, <eos> at 95, the rest
    unused: the grammar compiles against it."""
    words = [chr(32 + i) for i in range(95)] + ["<eos>"]
    return words + [f"<unused{i}>" for i in range(len(words), size)]


def _full_head(base):
    class _FullHead(base):
        """Every position through the LM head, then each lane's last
        valid row indexed: the plain path the engine's prefill is held
        to.  The row is ``lengths - 1`` of the prefill's own arguments,
        not the ``rows`` the engine passes, so a wrong row shows."""

        def _prefill_fn(self, state_arrays, ids, lengths, *args, **kw):
            self._lengths = lengths              # read while tracing
            return base._prefill_fn(self, state_arrays, ids, lengths,
                                    *args, **kw)

        def _run_model(self, state_arrays, ids, views, rows=None):
            logits, new_views = base._run_model(self, state_arrays, ids,
                                                views)
            if rows is not None:
                logits = jax.vmap(lambda lg, n: jax.lax.dynamic_index_in_dim(
                    lg, n - 1, axis=0, keepdims=False))(logits, self._lengths)
            return logits, new_views

    return _FullHead


class _Lowering:
    """Stands in for a ``CompiledFn``'s jitted function and keeps the
    StableHLO of every program it runs with the shape of its ids."""

    def __init__(self, jitted):
        self.jitted, self.programs = jitted, []

    def lower(self, *args):
        return self.jitted.lower(*args)

    def __call__(self, *args):
        self.programs.append((tuple(args[1].shape),
                              self.jitted.lower(*args).as_text()))
        return self.jitted(*args)


def _model(family):
    cls, cfg = FAMILIES[family]
    paddle.seed(0)
    m = cls(cfg)
    m.eval()
    return m


def _prompt(rng, n, vocab):
    return [int(t) for t in rng.integers(1, vocab, n)]


def _drive(eng, reqs):
    while eng.scheduler.has_work:
        eng.step()
    return [list(r.output_ids) for r in reqs]


def _serve(eng, vocab, grammar):
    """The scenario: three prompts of one bucket in one dispatch of four
    lanes (one padding lane), the last masked by ``grammar`` if given; a
    prompt of two whole blocks, then the same prompt again (an exact
    prefix hit, suffix length 1); a prompt of 40 tokens in chunks of 16."""
    rng = np.random.default_rng(5)
    greedy = SamplingParams(max_new_tokens=5)
    seeded = SamplingParams(max_new_tokens=5, temperature=0.9, seed=7)
    masked = SamplingParams(max_new_tokens=5, eos_token_id=EOS)
    out = _drive(eng, [
        eng.submit(_prompt(rng, 9, vocab), sampling=greedy),
        eng.submit(_prompt(rng, 12, vocab), sampling=seeded),
        eng.submit(_prompt(rng, 14, vocab), sampling=masked,
                   grammar=grammar)])
    whole = _prompt(rng, 16, vocab)
    out += _drive(eng, [eng.submit(whole, sampling=greedy)])
    hit = eng.submit(whole, sampling=greedy)
    out += _drive(eng, [hit])
    out += _drive(eng, [eng.submit(_prompt(rng, 40, vocab),
                                   sampling=seeded)])
    return out, hit.prefix_hit_tokens


def _head_traces():
    c = metrics.default_registry().get("prefill.head.trace")
    return {} if c is None else dict(c.snapshot_values())


def _run(family, make_engine, grammar=None):
    """Serve the scenario on a fresh engine, keeping every prefill's
    logits (a callback beside the sampler), every program's text and
    what ``prefill.head.trace`` counted meanwhile."""
    vocab = FAMILIES[family][1].vocab_size
    config = EngineConfig(
        num_slots=4, max_seq_len=64, max_horizon=4, prefix_block_size=8,
        prefill_chunk_tokens=16, grammar_max_states=64 if grammar else 0,
        grammar_vocab=_vocab(vocab) if grammar else None)
    eng = make_engine(_model(family), config)
    spies = {}
    for name in ("_prefill", "_decode"):
        fn = getattr(eng, name)
        spies[name] = fn._jit = _Lowering(fn._jit)
    logits = []
    sample_batch = engine_mod.sample_batch

    def recording(last, *args):
        jax.debug.callback(lambda x: logits.append(np.asarray(x)), last)
        return sample_batch(last, *args)

    before = _head_traces()
    engine_mod.sample_batch = recording
    try:
        out, hit = _serve(eng, vocab, grammar)
    finally:
        engine_mod.sample_batch = sample_batch
    traces = {k: v - before.get(k, 0) for k, v in _head_traces().items()
              if v != before.get(k, 0)}
    res = dict(out=out, hit=hit, logits=logits, traces=traces,
               programs={k: s.programs for k, s in spies.items()},
               prefill=eng.stats()["prefill"], misses=eng._prefill.misses)
    eng.close()
    return res


def _engine(cls, **kw):
    return lambda m, config: cls(m, config, register_profiler=False, **kw)


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def served(request):
    family = request.param
    return family, tuple(_run(family, _engine(cls), grammar="[abc]{2,8}")
                         for cls in (Engine, _full_head(Engine)))


def test_scenario_reaches_every_case(served):
    _, (new, _) = served
    assert new["hit"] == 15                       # suffix length 1
    assert new["prefill"]["chunked_requests"] == 1
    shapes = [s for s, _ in new["programs"]["_prefill"]]
    assert (4, 16) in shapes                      # three lanes and a pad


def test_tokens_equal_full_head(served):
    _, (new, ref) = served
    assert new["out"] == ref["out"]
    assert all(len(o) == 5 for o in new["out"][:2] + new["out"][3:])
    masked = new["out"][2]
    assert masked and all(chr(32 + t) in "abc" for t in masked
                          if t != EOS)


def test_prefill_logits_match_full_head(served):
    _, (new, ref) = served
    dispatches = new["programs"]["_prefill"]
    assert len(new["logits"]) == len(ref["logits"]) == len(dispatches)
    vocab = new["logits"][0].shape[-1]
    assert [x.shape for x in new["logits"]] == [
        (lanes, vocab) for (lanes, _), _ in dispatches]
    assert [x.shape for x in ref["logits"]] == [
        x.shape for x in new["logits"]]
    for got, want in zip(new["logits"], ref["logits"]):
        assert np.abs(want).max() > 0.01
        np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)


def test_lowered_prefill_projects_one_row_a_lane(served):
    family, (new, ref) = served
    vocab = FAMILIES[family][1].vocab_size
    for side, has_slab in ((new, False), (ref, True)):
        for (lanes, bucket), text in side["programs"]["_prefill"]:
            slab = f"tensor<{lanes}x{bucket}x{vocab}x"
            head = re.search(rf"dot_general .*-> tensor<{lanes}x{vocab}xf32>",
                             text)
            assert (slab in text) == has_slab, (lanes, bucket)
            assert (head is not None) != has_slab, (lanes, bucket)


def test_decode_programs_unchanged(served):
    """The horizon scan keeps every window position: no ``rows``, the
    same programs as the full-head engine's."""
    _, (new, ref) = served
    assert new["programs"]["_decode"] == ref["programs"]["_decode"]


def test_head_trace_counter(served):
    """``prefill.head.trace{rows, positions}``: one a prefill program,
    rows the lanes, positions lanes x bucket; the full head counts none."""
    _, (new, ref) = served
    shapes = {s for s, _ in new["programs"]["_prefill"]}
    assert len(shapes) == new["misses"]
    assert new["traces"] == {f"positions={lanes * bucket},rows={lanes}": 1
                             for lanes, bucket in shapes}
    assert ref["traces"] == {}


# ------------------------------------------------------------- mesh engine

def test_mesh_engine_prefill_indexes_its_logits():
    """MeshEngine's sharded head projects every position and ``rows``
    indexes its logits: prefill logits bitwise those of the full head
    (tp=2 on virtual CPU devices), the tokens those of the one-chip
    engine."""
    from paddle_tpu.serving import MeshEngine

    new, ref = (_run("gpt", _engine(cls, tp=2))
                for cls in (MeshEngine, _full_head(MeshEngine)))
    one = _run("gpt", _engine(Engine))
    assert new["out"] == ref["out"] == one["out"]
    assert len(new["logits"]) == len(ref["logits"]) > 0
    for got, want in zip(new["logits"], ref["logits"]):
        np.testing.assert_array_equal(got, want)
    assert new["traces"] == {}            # no program took the one-row head
