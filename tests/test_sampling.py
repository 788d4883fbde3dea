"""The sampler's filters by threshold search (``serving/sampling.py``)
against the sort they replaced, which stays here as the plain
reference: same kept sets, same tokens, no ``sort`` in a serving
program, and the ``sampler.dispatch`` counter."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.observability import metrics as obs_metrics
from paddle_tpu.serving import Engine, EngineConfig, SamplingParams
from paddle_tpu.serving import sampling
from paddle_tpu.serving.sampling import (MASK_FLOOR, request_key,
                                         sample_batch, sample_token,
                                         sample_window)


def _sorted_filter(logits, temperature, top_k, top_p):
    """The reference's filters on one [vocab] row: the scaled logits
    with every cut token at -inf, both thresholds read from a sort."""
    vocab = logits.shape[-1]
    t = jnp.where(temperature > 0, temperature, 1.0)
    scaled = logits.astype(jnp.float32) / t
    sorted_desc = jnp.sort(scaled)[::-1]
    kth = jnp.take(sorted_desc, jnp.clip(top_k, 1, vocab) - 1)
    scaled = jnp.where((top_k > 0) & (scaled < kth), -jnp.inf, scaled)
    probs = jax.nn.softmax(scaled)
    sp = jnp.sort(probs)[::-1]
    threshold = jnp.take(sp, jnp.argmax(jnp.cumsum(sp) >= top_p))
    return jnp.where((top_p < 1.0) & (probs < threshold), -jnp.inf, scaled)


def sorted_sample_token(logits, key, temperature, top_k, top_p):
    """The sort-based ``sample_token`` this repo served with until the
    search replaced it."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = _sorted_filter(logits, temperature, top_k, top_p)
    sampled = jax.random.categorical(key, scaled).astype(jnp.int32)
    return jnp.where(temperature > 0, sampled, greedy)


@jax.jit
def _searched_filter(logits, temperatures, top_ks, top_ps):
    """The search's filters on [N, vocab] rows, predicates left out."""
    t = jnp.where(temperatures > 0, temperatures, 1.0)
    scaled = logits.astype(jnp.float32) / t[:, None]
    return sampling._cut_top_p(sampling._cut_top_k(scaled, top_ks), top_ps)


_sorted_filter_rows = jax.jit(jax.vmap(_sorted_filter))
_sorted_rows = jax.jit(jax.vmap(sorted_sample_token))
_token_rows = jax.jit(jax.vmap(sample_token))
_batch = jax.jit(sample_batch)
_window = jax.jit(sample_window)


def _rows(vocab, seed):
    """Eight [vocab] rows a serving program can meet: plain, tied
    maxima, tied everywhere (bf16-rounded), a third at -inf, a grammar
    row at MASK_FLOOR but for a few tokens and one but for one token, a
    peaked row and a flat one."""
    rng = np.random.RandomState(seed)
    x = (rng.randn(8, vocab) * 3).astype(np.float32)
    x[1, rng.choice(vocab, 3, replace=False)] = x[1].max() + 1.0
    x[2] = np.asarray(jnp.asarray(x[2], jnp.bfloat16).astype(jnp.float32))
    x[3, rng.choice(vocab, vocab // 3, replace=False)] = -np.inf
    few = rng.choice(vocab, 7, replace=False)
    for r, live in ((4, few), (5, few[:1])):
        masked = np.full(vocab, MASK_FLOOR, np.float32)
        masked[live] = x[r, live]
        x[r] = masked
    x[6, rng.randint(vocab)] += 12.0
    x[7] *= 0.01
    return x


def _mass_near(probs64, kept, top_p):
    """Whether the float64 mass of a kept set, with or without its
    smallest member, lies within 1e-5 of top_p: the one place where two
    f32 summation orders may part."""
    return abs(probs64[kept].sum() - top_p) < 1e-5 or \
        abs(probs64[kept].sum() - probs64[kept].min() - top_p) < 1e-5


EXCEPTED = []           # rows let off by the 1e-5 rule, over the module


@pytest.mark.parametrize("top_p", [1.0, 0.95, 0.5, 1e-3])
@pytest.mark.parametrize("top_k", [0, 1, 5, "vocab"])
@pytest.mark.parametrize("temperature", [0.0, 0.7, 1.3])
@pytest.mark.parametrize("vocab", [64, 32768, 102400])
def test_search_keeps_what_the_sort_kept(vocab, temperature, top_k, top_p):
    top_k = vocab if top_k == "vocab" else top_k
    logits = jnp.asarray(_rows(vocab, seed=vocab % 97 + top_k % 7))
    n = logits.shape[0]
    temps = jnp.full(n, temperature, jnp.float32)
    ks = jnp.full(n, top_k, jnp.int32)
    ps = jnp.full(n, top_p, jnp.float32)
    seeds = jnp.arange(n, dtype=jnp.uint32) + 11
    counts = jnp.arange(n, dtype=jnp.int32) * 3
    keys = jax.vmap(request_key)(seeds, counts)

    want = np.asarray(_sorted_filter_rows(logits, temps, ks, ps))
    got = np.asarray(_searched_filter(logits, temps, ks, ps))
    want_tok = np.asarray(_sorted_rows(logits, keys, temps, ks, ps))
    toks = {
        "sample_token": np.asarray(_token_rows(logits, keys, temps, ks, ps)),
        "sample_batch": np.asarray(_batch(logits, seeds, counts, temps,
                                          ks, ps)),
    }
    # the distribution top-p cuts, in float64: after top-k alone
    after_k = np.asarray(_sorted_filter_rows(logits, temps, ks,
                                             jnp.ones(n)), np.float64)
    for r in range(n):
        kept_w, kept_g = want[r] > -np.inf, got[r] > -np.inf
        if (kept_w == kept_g).all():
            np.testing.assert_array_equal(got[r], want[r])
            for name, t in toks.items():
                assert t[r] == want_tok[r], (name, r)
            continue
        e = np.exp(after_k[r] - after_k[r].max())
        p64 = e / e.sum()
        assert _mass_near(p64, kept_w, top_p) or \
            _mass_near(p64, kept_g, top_p), (r, kept_w.sum(), kept_g.sum())
        EXCEPTED.append((vocab, temperature, top_k, top_p, r))
    assert len(EXCEPTED) <= 4, EXCEPTED


def test_whole_mass_below_top_p_keeps_the_row():
    """The defined corner: where a row's whole f32 mass rounds below
    top_p the search keeps every token (the sort fell to ``argmax`` of
    an all-false vector and kept the top token alone)."""
    top_p = np.nextafter(np.float32(1.0), np.float32(0.0))
    n, vocab = 64, 1024
    # every token weighs 1e-6 and more, top_p lacks 6e-8 of the whole:
    # a row needs all its tokens, whether its f32 mass reaches top_p or not
    logits = jnp.asarray(np.random.RandomState(1).randn(n, vocab), jnp.float32)
    args = (jnp.ones(n), jnp.zeros(n, jnp.int32),
            jnp.full(n, top_p, jnp.float32))
    got = (np.asarray(_searched_filter(logits, *args)) > -np.inf).sum(-1)
    want = (np.asarray(_sorted_filter_rows(logits, *args)) > -np.inf).sum(-1)
    np.testing.assert_array_equal(got, vocab)
    corner = want != got
    assert corner.any(), "no row's mass rounded below top_p in the sort"
    np.testing.assert_array_equal(want[corner], 1)


def _mixed(n, vocab, seed=5):
    rng = np.random.RandomState(seed)
    logits = jnp.asarray(rng.randn(n, vocab) * 2, jnp.float32)
    temps = jnp.asarray(rng.choice([0.0, 0.7, 1.3], n), jnp.float32)
    ks = jnp.asarray(rng.choice([0, 0, 3, 40], n), jnp.int32)
    ps = jnp.asarray(rng.choice([1.0, 0.95, 0.5], n), jnp.float32)
    seeds = jnp.asarray(rng.randint(0, 2**31, n), jnp.uint32)
    counts = jnp.asarray(rng.randint(0, 50, n), jnp.int32)
    return logits, seeds, counts, temps, ks, ps


@pytest.mark.parametrize("mix", ["all_greedy", "no_filter", "top_k_only",
                                 "top_p_only", "mixed"])
def test_batch_and_window_equal_row_by_row(mix):
    """Every arm of the three predicates gives what the reference gives
    a row at a time, through ``sample_batch`` and ``sample_window``."""
    n, w, vocab = 6, 3, 96
    logits, seeds, counts, temps, ks, ps = _mixed(n * w, vocab)
    if mix == "all_greedy":
        temps = jnp.zeros_like(temps)
    elif mix != "mixed":
        temps = temps.at[0].set(0.7)      # one sampling lane at least
        if mix != "top_k_only":
            ks = jnp.zeros_like(ks)
        if mix != "top_p_only":
            ps = jnp.ones_like(ps)
    keys = jax.vmap(request_key)(seeds, counts)
    want = np.asarray(_sorted_rows(logits, keys, temps, ks, ps))
    np.testing.assert_array_equal(
        np.asarray(_batch(logits, seeds, counts, temps, ks, ps)), want)

    # a verify window: lane i's position j under request_key(seed, cnt + j)
    lane = lambda a: a[:n]
    allowed = jnp.asarray(
        np.random.RandomState(2).rand(n, w, vocab) < 0.3).at[:, :, 7].set(True)
    for mask in (None, allowed):
        win = logits.reshape(n, w, vocab)
        got = _window(win, lane(seeds), lane(counts), lane(temps),
                      lane(ks), lane(ps), allowed=mask)
        if mask is not None:
            win = jnp.where(mask, win, MASK_FLOOR)
        for j in range(w):
            kj = jax.vmap(request_key)(lane(seeds), lane(counts) + j)
            np.testing.assert_array_equal(
                np.asarray(got[:, j]),
                np.asarray(_sorted_rows(win[:, j], kj, lane(temps),
                                        lane(ks), lane(ps))))
            if mask is not None:
                assert np.asarray(mask)[np.arange(n), j,
                                        np.asarray(got[:, j])].all()


def test_bf16_logits_and_top_k_beyond_vocab():
    logits, seeds, counts, temps, ks, ps = _mixed(8, 160)
    logits = logits.astype(jnp.bfloat16)
    ks = ks.at[1].set(10_000).at[2].set(160)
    temps = temps.at[1].set(0.9)
    keys = jax.vmap(request_key)(seeds, counts)
    np.testing.assert_array_equal(
        np.asarray(_batch(logits, seeds, counts, temps, ks, ps)),
        np.asarray(_sorted_rows(logits, keys, temps, ks, ps)))


TINY = GPTConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                 num_hidden_layers=2, num_attention_heads=4,
                 max_position_embeddings=64)


def _engine():
    paddle.seed(0)
    model = GPTForCausalLM(TINY)
    model.eval()
    return Engine(model, EngineConfig(num_slots=2, max_seq_len=64,
                                      max_horizon=4),
                  register_profiler=False)


class _Lowering:
    """Stands in for a ``CompiledFn``'s jitted function and keeps the
    StableHLO of every program it is asked to run."""

    def __init__(self, jitted):
        self.jitted, self.texts = jitted, []

    def lower(self, *args):
        return self.jitted.lower(*args)

    def __call__(self, *args):
        self.texts.append(self.jitted.lower(*args).as_text())
        return self.jitted(*args)


def test_no_sort_in_a_serving_program():
    eng = _engine()
    spies = {}
    for name in ("_prefill", "_decode"):
        fn = getattr(eng, name)
        spies[name] = fn._jit = _Lowering(fn._jit)
    eng.generate([5, 6, 7, 8, 9, 10], SamplingParams(
        max_new_tokens=8, temperature=0.7, top_k=5, top_p=0.9, seed=3))
    for name, spy in spies.items():
        assert spy.texts, name
        for text in spy.texts:
            assert "stablehlo.sort" not in text, name


def _dispatches():
    c = obs_metrics.default_registry().get("sampler.dispatch")
    return {f: c.value(filters=f)
            for f in ("none", "top_p", "top_k", "top_k+top_p")}


@pytest.mark.parametrize("sp, filters", [
    (dict(temperature=0.7, top_p=0.95), "top_p"),      # the cells' mix
    (dict(), "none"),                                  # all greedy
    (dict(temperature=0.7), "none"),
    (dict(temperature=0.7, top_k=5), "top_k"),
    (dict(temperature=1.0, top_k=5, top_p=0.5), "top_k+top_p"),
    (dict(top_k=5, top_p=0.5), "none"),                # greedy: no search
])
def test_sampler_dispatch_counter(sp, filters):
    eng = _engine()
    before = _dispatches()
    eng.generate([5, 6, 7, 8], SamplingParams(max_new_tokens=6, seed=1, **sp))
    moved = {f: v - before[f] for f, v in _dispatches().items()
             if v != before[f]}
    assert moved == {filters: eng.stats()["decode_host_syncs"]}, moved
