"""Token sampling for the serving engine.

Greedy / temperature / top-k / top-p under a per-request seeded PRNG,
as pure jnp over the slot rows ``[N, vocab]`` of the fused decode step.
Each row is sampled on its own: a request's k-th sampled token depends
only on (its seed, k, its logits) — never on which slot it occupies or
what else is in the batch.  That independence is what makes continuous
batching reproduce sequential ``generate()`` token-for-token.

Neither filter sorts the vocabulary.  What top-k needs of a row is its
k-th largest scaled logit, what top-p needs is the probability at which
the descending cumulative mass first reaches ``top_p``: each is an
element of the row and the answer to a monotone question, found by
bisection on the float's bit pattern — 32 compare-and-count passes, 30
masked-sum passes.  The kept sets are the sort's (``tests/
test_sampling.py`` keeps the sort as the reference); only the f32
summation order differs where a mass lies within rounding of ``top_p``.
What a batch does not ask for does not run, by three batch-level
``lax.cond``s on the lanes' own parameters: every lane greedy -> argmax
alone; no sampling lane with ``top_k > 0`` -> no top-k search; none
with ``top_p < 1`` -> no softmax and no top-p search.

The rows' independence also makes horizon-scanned decode exact: the
engine keeps a per-slot sample counter in the scan carry and derives
each step's key as ``request_key(seed, counter)`` — i.e.
``fold_in(seed, n_generated)`` — so whether H tokens come from one fused
``lax.scan`` dispatch or H separate step dispatches, token k of a
request is sampled with the identical key and is bitwise-equal across
horizons.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

#: logit floor for grammar-masked (disallowed) tokens: finite (so the
#: temperature divide and softmax stay NaN-free at any temperature) but
#: far below every real logit, so neither argmax nor categorical can
#: pick a masked token.  Matches the established masking floor used by
#: the attention kernels.
MASK_FLOOR = -1.0e30


@dataclass
class SamplingParams:
    """Per-request decoding controls (paddle parity: the generate()
    kwargs of PaddleNLP's GenerationMixin, reduced to the serving set).

    temperature <= 0 selects greedy argmax decoding; top_k <= 0 and
    top_p >= 1.0 disable their respective filters.
    """

    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    max_new_tokens: int = 16
    eos_token_id: int | None = None
    seed: int = 0

    def validate(self):
        if self.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if not 0.0 < self.top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {self.top_p}")
        return self


def request_key(seed, n_sampled):
    """The PRNG key for a request's n_sampled-th token: a pure function
    of (seed, token index), so replays and re-batchings are bitwise
    deterministic."""
    return jax.random.fold_in(jax.random.PRNGKey(seed), n_sampled)


_SIGN = np.uint32(0x80000000)
#: a probability is at most 1.0 = 0x3F800000: bits 31 and 30 are clear
_PROB_BITS = 30


def _order_key(x):
    """Order-preserving map of f32 to u32: ``a < b`` as floats iff
    ``key(a) < key(b)`` as integers (``-inf`` lowest, ``-0.0`` just
    under ``+0.0``: the total order ``jnp.sort`` uses).  Non-negative
    floats get their sign bit set, negative ones all bits flipped."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(bits >> 31 == 0, bits | _SIGN, ~bits)


def _key_value(key):
    """The float ``_order_key`` mapped to ``key``."""
    bits = jnp.where(key >> 31 == 1, key ^ _SIGN, ~key)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _largest_key(reaches, rows, bits):
    """Per row the largest u32 below ``2**bits`` for which the monotone
    ``reaches(t[rows]) -> bool[rows]`` holds (it holds at 0): the key is
    built from its highest bit down, one pass of ``reaches`` over the
    rows a bit.  Where ``reaches`` asks how much of a row lies at or
    above ``t``, the answer is an element of the row: between two
    elements the question's answer does not change."""
    def step(i, t):
        cand = t | (np.uint32(1) << (bits - 1 - i).astype(jnp.uint32))
        return jnp.where(reaches(cand), cand, t)

    return jax.lax.fori_loop(0, bits, step, jnp.zeros(rows, jnp.uint32))


def _cut_top_k(scaled, top_ks):
    """top-k: keep logits >= the k-th largest (ties widen the pool).
    The k-th largest is the largest key with at least k keys at or
    above it — bit for bit what ``sort(row)[::-1][k - 1]`` reads."""
    n, vocab = scaled.shape
    keys = _order_key(scaled)
    k = jnp.clip(top_ks, 1, vocab)
    kth = _key_value(_largest_key(
        lambda t: jnp.sum(keys >= t[:, None], axis=-1,
                          dtype=jnp.int32) >= k, n, 32))
    return jnp.where((top_ks > 0)[:, None] & (scaled < kth[:, None]),
                     -jnp.inf, scaled)


def _cut_top_p(scaled, top_ps):
    """top-p (nucleus): keep the smallest set of most probable tokens
    whose mass reaches top_p, i.e. every probability >= the largest one
    with a mass of at least top_p at or above it (non-negative floats
    order as their bits do).  A row whose whole mass rounds below top_p
    keeps every token."""
    probs = jax.nn.softmax(scaled, axis=-1)

    as_prob = lambda t: jax.lax.bitcast_convert_type(t, jnp.float32)

    def reaches(t):
        above = probs >= as_prob(t)[:, None]
        return jnp.sum(jnp.where(above, probs, 0.0), axis=-1) >= top_ps

    threshold = as_prob(_largest_key(reaches, scaled.shape[0], _PROB_BITS))
    return jnp.where((top_ps < 1.0)[:, None]
                     & (probs < threshold[:, None]), -jnp.inf, scaled)


def _draw_rows(logits, keys, temperatures, top_ks, top_ps):
    """One categorical draw a row of [N, vocab] logits under keys [N]:
    temperature, then the two filters, each found by a search over the
    row and not by sorting it, and each skipped by a runtime ``lax.cond``
    when no sampling row asks for it (a per-row ``cond`` under ``vmap``
    would become a ``select`` and skip nothing).  A greedy row's draw is
    the caller's to discard."""
    sampling = temperatures > 0
    # temperature scale (guard the greedy rows against divide-by-zero)
    t = jnp.where(sampling, temperatures, 1.0)
    scaled = logits.astype(jnp.float32) / t[:, None]
    scaled = jax.lax.cond(jnp.any(sampling & (top_ks > 0)),
                          _cut_top_k, lambda s, _: s, scaled, top_ks)
    scaled = jax.lax.cond(jnp.any(sampling & (top_ps < 1.0)),
                          _cut_top_p, lambda s, _: s, scaled, top_ps)
    return jax.vmap(jax.random.categorical)(keys, scaled).astype(jnp.int32)


def sample_token(logits, key, temperature, top_k, top_p):
    """Sample one token id from a single [vocab] logits row: the one-row
    view of what ``sample_batch`` computes.

    All four controls are traced values, so one compiled program serves
    every request mix.  Greedy rows still draw nothing from ``key`` —
    the argmax branch is selected by ``where``.
    """
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    row = lambda a: jnp.asarray(a)[None]
    sampled = _draw_rows(row(logits), row(key), row(temperature),
                         row(top_k), row(top_p))[0]
    return jnp.where(jnp.asarray(temperature) > 0, sampled, greedy)


def sample_batch(logits, seeds, counts, temperatures, top_ks, top_ps):
    """Vectorized sampling across slot rows: logits [N, vocab] plus
    per-slot parameter arrays [N] -> token ids [N] int32.

    When EVERY row is greedy (temperature <= 0) the whole
    filter/categorical pipeline is provably dead — each row reduces to
    ``argmax`` — so a runtime ``lax.cond`` skips it.  The branch
    predicate is data-dependent, not traced shape, so one compiled
    program still serves every request mix; the greedy branch returns
    exactly what the full pipeline's ``where(temperature > 0, ...)``
    would have picked, so outputs are bitwise unchanged."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def full(_):
        keys = jax.vmap(request_key)(seeds, counts)
        sampled = _draw_rows(logits, keys, temperatures, top_ks, top_ps)
        return jnp.where(temperatures > 0, sampled, greedy)

    return jax.lax.cond(jnp.any(temperatures > 0), full,
                        lambda _: greedy, None)


def sample_window(logits, seeds, counts, temperatures, top_ks, top_ps,
                  allowed=None):
    """Sampling across a speculative verify window: logits [N, W, vocab]
    -> token ids [N, W], where window position j of lane i is sampled
    with key ``request_key(seeds[i], counts[i] + j)`` — the exact key
    sequential decode would use for that request's (counts+j)-th token.
    Keys are pure functions of (seed, index), so the verify forward
    consumes no PRNG state for positions the acceptance rule discards:
    emitted token k of a request is bitwise the token sequential
    ``generate()`` samples, whatever W the engine verified with.

    ``allowed`` (optional, [N, W, vocab] bool) is the grammar mask:
    disallowed logits drop to ``MASK_FLOOR`` BEFORE the all-greedy fast
    path / categorical pipeline, so constrained sampling inherits the
    same key discipline and stays bitwise-reproducible; an all-True row
    (the accept-all sentinel state unconstrained lanes ride) is the
    identity — ``where(True, x, floor)`` is bitwise ``x``."""
    n, w, vocab = logits.shape
    if allowed is not None:
        logits = jnp.where(allowed, logits, MASK_FLOOR)
    js = jnp.arange(w, dtype=counts.dtype)
    rep = lambda a: jnp.repeat(a, w, axis=0)
    flat_counts = (counts[:, None] + js[None, :]).reshape(-1)
    out = sample_batch(logits.reshape(n * w, vocab), rep(seeds),
                       flat_counts, rep(temperatures), rep(top_ks),
                       rep(top_ps))
    return out.reshape(n, w)
