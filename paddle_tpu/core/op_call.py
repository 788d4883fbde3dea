"""Eager op dispatch: wrap a jnp/lax function so it consumes/produces Tensors
and records a vjp closure on the tape.

This is the TPU-native replacement for the reference's generated
`xxx_ad_func()` C++ layer + PHI kernel dispatch (SURVEY.md §3.1 steps 2-3):
one generic `apply()` instead of 1000 generated bindings, because jax.vjp
derives every gradient.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import tape as _tape
from .tensor import Tensor


def _is_float(dtype) -> bool:
    return jnp.issubdtype(dtype, jnp.floating) or jnp.issubdtype(dtype, jnp.complexfloating)


# AMP integration point: paddle_tpu.amp installs a lookup op_name -> dtype
# (or None) here when an auto_cast scope may be active. Kept as a hook so the
# hot eager path pays nothing when AMP was never imported.
_AMP_LOOKUP = None

# Static-graph integration point: paddle.enable_static() installs a handler
# (static/graph.py) that records ops touching symbolic placeholders into the
# current Program instead of executing them. None (the default) keeps the
# eager hot path untouched.
_STATIC_HANDLER = None


def set_amp_lookup(fn):
    global _AMP_LOOKUP
    _AMP_LOOKUP = fn


def set_static_handler(fn):
    global _STATIC_HANDLER
    _STATIC_HANDLER = fn


def amp_cast_arrays(arrays, jd):
    """The one AMP cast rule (shared by the eager autocast wrapper and the
    static meta-optimizer's program rewrite): real floats only — complex
    inputs must never be truncated to a real half dtype, and integers pass
    through untouched."""
    return [
        a.astype(jd)
        if hasattr(a, "dtype") and jnp.issubdtype(a.dtype, jnp.floating) and a.dtype != jd
        else a
        for a in arrays
    ]


def _maybe_amp_wrap(fn, op_name):
    if _AMP_LOOKUP is None:
        return fn
    jd = _AMP_LOOKUP(op_name)
    if jd is None:
        return fn

    def wrapped(*arrays, **kw):
        return fn(*amp_cast_arrays(arrays, jd), **kw)

    return wrapped


def apply(fn, *args, _op_name: str = "", **kwargs):
    """Run `fn(*arrays, **kwargs)` where Tensor args are unwrapped.

    If the tape is active and any input Tensor requires grad, the primal is
    computed through `jax.vjp` and the pullback recorded. Non-Tensor args
    pass through untouched (treated as constants).
    """
    fn = _maybe_amp_wrap(fn, _op_name)
    if _STATIC_HANDLER is not None:
        staged = _STATIC_HANDLER(fn, args, kwargs, _op_name)
        if staged is not None:
            return staged
    tensor_idx = [i for i, a in enumerate(args) if isinstance(a, Tensor)]
    arrays = list(args)
    in_tensors = []
    for i in tensor_idx:
        in_tensors.append(args[i])
        arrays[i] = args[i]._data

    need_grad = (
        _tape.tape_enabled()
        and any(not t.stop_gradient for t in in_tensors)
    )

    if not need_grad:
        out = fn(*arrays, **kwargs)
        return _wrap_outputs(out, stop_gradient=True)

    # differentiate only w.r.t. floating-point tensor inputs
    diff_idx = [i for i in tensor_idx if _is_float(args[i]._data.dtype)]
    if not diff_idx:
        out = fn(*arrays, **kwargs)
        return _wrap_outputs(out, stop_gradient=True)

    def primal(*diff_arrays):
        full = list(arrays)
        for j, i in enumerate(diff_idx):
            full[i] = diff_arrays[j]
        return fn(*full, **kwargs)

    diff_data = [args[i]._data for i in diff_idx]
    out_data, vjp_fn = jax.vjp(primal, *diff_data)
    outs, structure = _flatten_out(out_data)
    out_tensors = [Tensor(o, stop_gradient=not _is_float(o.dtype)) for o in outs]
    diff_tensors = [args[i] for i in diff_idx]
    if any(not t.stop_gradient for t in out_tensors):
        _tape.global_tape().record(
            diff_tensors,
            out_tensors,
            _VjpAdapter(vjp_fn, [jax.typeof(o) for o in outs]),
            name=_op_name or getattr(fn, "__name__", "op"),
            replay=primal,
            in_data=diff_data,
        )
    return _unflatten_out(out_tensors, structure)


def _match_vma(ct, expected_aval):
    """Inside shard_map, primal outputs carry varying-manual-axes (vma) types
    (e.g. float32[...]{V:mp}); a cotangent built outside that op (ones_like,
    or the pullback of a replicating collective like psum) may be replicated.
    Promote it with pcast so jax.vjp accepts it — mathematically a no-op."""
    vma = getattr(expected_aval, "vma", None)
    if not vma:
        return ct
    have = getattr(jax.typeof(ct), "vma", frozenset())
    missing = tuple(vma - have)
    if missing:
        ct = jax.lax.pcast(ct, missing, to="varying")
    return ct


class _VjpAdapter:
    __slots__ = ("vjp_fn", "out_avals")

    def __init__(self, vjp_fn, out_avals):
        self.vjp_fn = vjp_fn
        self.out_avals = out_avals

    def __call__(self, cotangents):
        # cotangents: list aligned with flattened outputs
        cts = [_match_vma(ct, av) for ct, av in zip(cotangents, self.out_avals)]
        if len(self.out_avals) == 1:
            return self.vjp_fn(cts[0])
        return self.vjp_fn(tuple(cts))


def _out_type(out):
    # namedtuples (e.g. jnp.linalg results) collapse to plain tuple
    t = type(out)
    return tuple if hasattr(out, "_fields") else t


def _flatten_out(out):
    if isinstance(out, (tuple, list)):
        return list(out), _out_type(out)
    return [out], None


def _unflatten_out(tensors, structure):
    if structure is None:
        return tensors[0]
    return structure(tensors)


def _wrap_outputs(out, stop_gradient=True):
    if isinstance(out, (tuple, list)):
        return _out_type(out)(Tensor(o, stop_gradient=stop_gradient) for o in out)
    return Tensor(out, stop_gradient=stop_gradient)


def wrap_op(fn, name=None):
    """Lift a jnp-level function into a Tensor-level op."""

    @functools.wraps(fn)
    def op(*args, **kwargs):
        return apply(fn, *args, _op_name=name or fn.__name__, **kwargs)

    return op
