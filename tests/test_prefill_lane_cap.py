"""While lanes are live, one prefill dispatch takes no more queued requests
than the widest lane bucket the engine has already run.  Without that rule
a queue that builds up behind one long step is admitted in one batch whose
lane bucket (the next power of two) can be a program no warm-up compiled,
and every live stream waits on its compile.  An idle engine still admits
every free lane at once, which is how a warm-up compiles the lane buckets.
The rule changes the schedule only: every request's tokens stay those of
an engine that admits them all at once."""

import pytest

import paddle_tpu as paddle
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.serving import Engine, EngineConfig, SamplingParams

TINY = GPTConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                 num_hidden_layers=2, num_attention_heads=4,
                 max_position_embeddings=64)

# five prompts of one length bucket (8), so admission co-batches them all
PROMPTS = [[3, 1, 4, 1, 5], [9, 2, 6, 5, 3, 5], [8, 9, 7, 9, 1],
           [2, 3, 8, 4, 6, 2, 6], [7, 1, 8, 2, 8, 1]]
# requests admitted first and still decoding when PROMPTS are queued
LEAD = [[5, 5, 2, 1], [6, 4, 3, 3, 9], [1, 1, 7, 2, 4]]


def _serve(lead):
    paddle.seed(0)
    m = GPTForCausalLM(TINY)
    m.eval()
    eng = Engine(m, EngineConfig(num_slots=8, max_seq_len=32),
                 register_profiler=False)
    for p in LEAD[:lead]:
        eng.submit(p, SamplingParams(max_new_tokens=12, seed=99))
    eng.admit()
    before = eng.counters()["prefill_calls"]
    samp = [SamplingParams(max_new_tokens=4, seed=i,
                           temperature=0.7 if i % 2 else 0.0)
            for i in range(len(PROMPTS))]
    reqs = [eng.submit(p, s) for p, s in zip(PROMPTS, samp)]
    eng.admit()
    calls = eng.counters()["prefill_calls"] - before
    eng.run()
    widest = max(b[0] for b in eng.stats()["prefill"]["buckets"])
    return calls, widest, [r.output_ids for r in reqs]


@pytest.mark.parametrize("lead,dispatches,widest", [
    (0, 1, 8),            # idle: all five in one dispatch of 8 lanes
    (1, 5, 1),            # a 1-lane prefill live: 1 + 1 + 1 + 1 + 1
    (2, 3, 2),            # a 2-lane prefill live: 2 + 2 + 1
    (3, 2, 4),            # 3 live in a lane bucket of 4: 4 + 1
])
def test_a_prefill_takes_at_most_the_cap(lead, dispatches, widest):
    calls, wide, outs = _serve(lead)
    assert calls == dispatches
    assert wide == widest
    _, _, at_once = _serve(0)
    assert outs == at_once
