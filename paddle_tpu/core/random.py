"""Stateful RNG over jax's functional PRNG.

Reference parity: paddle.seed + per-parallel-axis `get_rng_state_tracker`
(SURVEY.md §2.2 P12, upstream fleet/layers/mpu/random.py). TPU-native design:
a global counter-based key stream. Under `jax.jit` the key becomes a traced
argument (injected by paddle_tpu.jit.to_static) so compiled programs stay
stochastic across calls; named tracker states give deterministic, distinct
streams per parallelism axis (e.g. dropout that is identical across tensor-
parallel ranks vs. distinct per rank).
"""

from __future__ import annotations

import contextlib
import threading

import jax
import jax.numpy as jnp


class _KeyStream:
    """fold_in-counter key stream: cheap, traceable, replayable.

    A stream made from an int seed builds its base key on first use, not
    at construction: the module-level default stream is made by
    ``import paddle_tpu``, and building a key initializes the backend —
    a parent process that only imports the package (the launcher, a
    DataLoader worker, a bench driver) must not take the chip."""

    def __init__(self, seed_or_key):
        if isinstance(seed_or_key, int):
            self._seed, self._base = seed_or_key, None
        else:
            self._seed, self._base = None, seed_or_key
        self.counter = 0

    @property
    def base(self):
        if self._base is None:
            # concrete even when first touched inside a trace
            with jax.ensure_compile_time_eval():
                self._base = jax.random.PRNGKey(self._seed)
        return self._base

    def next_key(self):
        k = jax.random.fold_in(self.base, self.counter)
        self.counter += 1
        return k

    def state(self):
        return (self.base, self.counter)

    def set_state(self, st):
        self._base, self.counter = st


class _RandomState(threading.local):
    def __init__(self):
        self.stream = _KeyStream(0)


_STATE = _RandomState()


def seed(s: int):
    """paddle.seed parity."""
    _STATE.stream = _KeyStream(int(s))
    default_tracker().reset(int(s))
    return _STATE.stream


def next_key():
    return _STATE.stream.next_key()


def get_rng_state():
    return _STATE.stream.state()


def set_rng_state(st):
    _STATE.stream.set_state(st)


@contextlib.contextmanager
def fork_rng(base_key):
    """Swap the global stream for one derived from `base_key` (used by
    jit.to_static to thread a traced key through a compiled step)."""
    prev = _STATE.stream
    _STATE.stream = _KeyStream(base_key)
    try:
        yield
    finally:
        _STATE.stream = prev


class RNGStatesTracker:
    """Named RNG states for hybrid parallelism (parity with
    fleet get_rng_state_tracker: 'global_seed' vs 'local_seed' streams)."""

    def __init__(self):
        self.states = {}

    def reset(self, base_seed=0):
        self.states = {}
        self._base = base_seed

    def add(self, name, seed_):
        self.states[name] = _KeyStream(int(seed_))

    def get_states_tracker(self):
        return {k: v.state() for k, v in self.states.items()}

    def set_states_tracker(self, states):
        for k, st in states.items():
            self.states.setdefault(k, _KeyStream(0)).set_state(st)

    @contextlib.contextmanager
    def rng_state(self, name="global_seed"):
        if name not in self.states:
            self.add(name, hash(name) % (2**31))
        prev = _STATE.stream
        _STATE.stream = self.states[name]
        try:
            yield
        finally:
            _STATE.stream = prev


_TRACKER = RNGStatesTracker()


def default_tracker() -> RNGStatesTracker:
    return _TRACKER


def get_rng_state_tracker() -> RNGStatesTracker:
    return _TRACKER
