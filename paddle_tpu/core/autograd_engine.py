"""Backward engine: reverse-creation-order walk over the tape.

Reference parity: `egr::Backward()`'s topological queue over GradNodes
(SURVEY.md §3.1 step 4; upstream paddle/fluid/eager/backward.cc). Here
creation order IS a topological order, so the walk is a single reversed scan —
no ready-queue bookkeeping needed. Fully traceable: running this under
`jax.jit` emits one XLA program for the whole backward pass.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from . import tape as _tape
from .tensor import Tensor, _GRAD_HOOKS, _GRAD_HOOK_OWNERS


def _zeros_like_meta(shape, dtype):
    if jnp.issubdtype(dtype, jnp.floating) or jnp.issubdtype(dtype, jnp.complexfloating):
        return jnp.zeros(shape, dtype)
    return np.zeros(shape, jax.dtypes.float0)


def backward(loss: Tensor, grad_tensor=None, retain_graph: bool = False,
             targets=None, create_graph: bool = False):
    """Reverse walk from `loss`. `targets` (used by paddle.grad) is an optional
    set of tensor ids for which gradients must be materialized even when the
    tensor is an intermediate rather than a leaf. With create_graph=True the
    walk RECORDS itself: each node's vjp is re-derived as a taped op of
    (original inputs, cotangents), so the produced gradients are themselves
    differentiable (higher-order autograd — ref eager backward's
    create_graph, SURVEY.md §2.1 N8)."""
    if loss.stop_gradient:
        raise RuntimeError(
            "Tensor.backward() on a tensor with stop_gradient=True — nothing to differentiate."
        )
    targets = targets or {}
    if create_graph:
        return _backward_tensors(loss, grad_tensor, targets)
    tape = _tape.global_tape()
    start = loss._tape_node
    if start is None:
        if id(loss) in targets:
            t = targets[id(loss)]
            seed0 = jnp.ones(loss._data.shape, loss._data.dtype) if grad_tensor is None else (
                grad_tensor._data if isinstance(grad_tensor, Tensor) else jnp.asarray(grad_tensor))
            t.grad = Tensor(seed0) if t.grad is None else Tensor(t.grad._data + seed0)
        return

    if grad_tensor is None:
        seed = jnp.ones(loss._data.shape, loss._data.dtype)
    else:
        seed = grad_tensor._data if isinstance(grad_tensor, Tensor) else jnp.asarray(grad_tensor)

    # cotangents keyed by id(tensor)
    cot = {id(loss): seed}
    # keep loss alive and map ids we may need
    leaf_accum = {}  # id -> (tensor, grad array)

    if id(loss) in targets:
        t = targets[id(loss)]
        t.grad = Tensor(seed) if t.grad is None else Tensor(t.grad._data + seed)

    nodes = [n for n in tape.nodes if n.idx <= start.idx]
    with _tape.no_grad():
        for node in reversed(nodes):
            if not any(oid in cot for oid in node.out_ids):
                continue
            cots = []
            for oid, (shape, dtype) in zip(node.out_ids, node.out_meta):
                c = cot.pop(oid, None)
                if c is None:
                    c = _zeros_like_meta(shape, dtype)
                else:
                    for hook in _GRAD_HOOKS.get(oid, ()):  # intermediate-grad hooks
                        r = hook(Tensor(c))
                        if r is not None:
                            c = r._data if isinstance(r, Tensor) else jnp.asarray(r)
                    if oid in targets and oid != id(loss):
                        # materialize intermediate grads requested by paddle.grad
                        t = targets[oid]
                        t.grad = Tensor(c) if t.grad is None else Tensor(t.grad._data + c)
                cots.append(c)
            in_cots = node.vjp_fn(cots)
            for t, g in zip(node.inputs, in_cots):
                if g is None or (hasattr(g, "dtype") and g.dtype == jax.dtypes.float0):
                    continue
                if t._tape_node is not None and t._tape_node.idx < node.idx:
                    # intermediate produced by an earlier node: keep propagating
                    tid = id(t)
                    cot[tid] = cot[tid] + g if tid in cot else g
                elif t._tape_node is None:
                    if not t.stop_gradient:
                        tid = id(t)
                        if tid in leaf_accum:
                            leaf_accum[tid] = (t, leaf_accum[tid][1] + g)
                        else:
                            leaf_accum[tid] = (t, g)
                else:
                    # t produced by this very node (in-place style) — treat as leaf
                    if not t.stop_gradient:
                        tid = id(t)
                        if tid in leaf_accum:
                            leaf_accum[tid] = (t, leaf_accum[tid][1] + g)
                        else:
                            leaf_accum[tid] = (t, g)

        for tid, (t, g) in leaf_accum.items():
            for hook in _GRAD_HOOKS.get(tid, ()):
                r = hook(Tensor(g))
                if r is not None:
                    g = r._data if isinstance(r, Tensor) else jnp.asarray(r)
            if t.grad is None:
                t.grad = Tensor(g, stop_gradient=True)
            else:
                t.grad._data = t.grad._data + g

    if not retain_graph:
        # free the graph (reference frees GradNodes after backward too)
        kept = [n for n in tape.nodes if n.idx > start.idx]
        tape.nodes = kept


def _make_replay_bw(node):
    """Lift a node's backward into a re-recordable op: given the node's
    original diff inputs followed by the output cotangents, re-linearize
    the forward (node.replay) at those inputs and pull the cotangents
    back. Routed through op_call.apply, this records a tape node whose own
    vjp gives second-order gradients."""
    from .op_call import _match_vma

    replay = node.replay
    k = len(node.inputs)

    def bw(*vals):
        prim = vals[:k]
        cots = list(vals[k:])
        out_data, vjp = jax.vjp(replay, *prim)
        flat = (list(out_data) if isinstance(out_data, (tuple, list))
                else [out_data])
        cts = [_match_vma(c, jax.typeof(o)) for c, o in zip(cots, flat)]
        res = vjp(cts[0]) if len(flat) == 1 else vjp(tuple(cts))
        # apply()'s convention: single outputs are bare, not 1-tuples
        # (_VjpAdapter keys its cotangent structure on that)
        return res[0] if len(res) == 1 else tuple(res)

    bw.__name__ = "grad_" + (node.name or "op")
    return bw


def _backward_tensors(loss: Tensor, grad_tensor, targets):
    """The create_graph walk: cotangents are live Tensors and every vjp
    application is itself a recorded op, so the resulting .grad tensors
    carry a tape history (differentiable). Implies retain_graph."""
    from . import op_call as _op_call

    tape = _tape.global_tape()
    start = loss._tape_node

    if grad_tensor is None:
        seed = Tensor(jnp.ones(loss._data.shape, loss._data.dtype),
                      stop_gradient=True)
    else:
        seed = (grad_tensor if isinstance(grad_tensor, Tensor)
                else Tensor(jnp.asarray(grad_tensor)))

    def accum_target(t, g):
        t.grad = g if t.grad is None else t.grad + g

    if start is None:
        if id(loss) in targets:
            accum_target(targets[id(loss)], seed)
        return
    if id(loss) in targets:
        accum_target(targets[id(loss)], seed)

    cot = {id(loss): seed}
    leaf_accum = {}
    nodes = [n for n in tape.nodes if n.idx <= start.idx]
    for node in reversed(nodes):
        if not any(oid in cot for oid in node.out_ids):
            continue
        if node.replay is None:
            raise NotImplementedError(
                f"create_graph=True through op {node.name!r}: this node "
                "has a custom backward (PyLayer) with no replayable "
                "forward, so its gradient cannot be differentiated again. "
                "Express the op with standard tensor ops, or use "
                "paddle.autograd.hessian/jvp (jax-transform based).")
        cots = []
        for oid, (shape, dtype) in zip(node.out_ids, node.out_meta):
            c = cot.pop(oid, None)
            if c is None:
                c = _zeros_like_meta(shape, dtype)
                if not isinstance(c, np.ndarray):   # float arrays -> Tensor
                    c = Tensor(c, stop_gradient=True)
            else:
                for hook in _GRAD_HOOKS.get(oid, ()):
                    r = hook(c)
                    if r is not None:
                        c = r if isinstance(r, Tensor) \
                            else Tensor(jnp.asarray(r))
                if oid in targets and oid != id(loss):
                    accum_target(targets[oid], c)
            cots.append(c)
        bw = _make_replay_bw(node)
        # replay must linearize at the FORWARD-time arrays: an input whose
        # ._data was rebound between forward and backward (in-place style)
        # is temporarily restored around the recorded bw apply, so the
        # linearization point matches the create_graph=False saved vjp
        # (advisor r4). Tracer-valued data stays — under an outer trace
        # the symbolic flow is the correct value.
        swapped = []
        if node.in_data is not None:
            for t, s in zip(node.inputs, node.in_data):
                if t._data is not s \
                        and not isinstance(t._data, jax.core.Tracer):
                    swapped.append((t, t._data))
                    t._data = s
        try:
            in_cots = _op_call.apply(bw, *(list(node.inputs) + cots),
                                     _op_name=bw.__name__)
        finally:
            for t, d in swapped:
                t._data = d
        if not isinstance(in_cots, (tuple, list)):
            in_cots = (in_cots,)
        for t, g in zip(node.inputs, in_cots):
            if g is None:
                continue
            gd = getattr(g, "_data", g)
            if hasattr(gd, "dtype") and gd.dtype == jax.dtypes.float0:
                continue
            if not isinstance(g, Tensor):
                g = Tensor(jnp.asarray(g))
            tid = id(t)
            if t._tape_node is not None and t._tape_node.idx < node.idx:
                cot[tid] = cot[tid] + g if tid in cot else g
            elif not t.stop_gradient:
                if tid in leaf_accum:
                    leaf_accum[tid] = (t, leaf_accum[tid][1] + g)
                else:
                    leaf_accum[tid] = (t, g)

    for tid, (t, g) in leaf_accum.items():
        for hook in _GRAD_HOOKS.get(tid, ()):
            r = hook(g)
            if r is not None:
                g = r if isinstance(r, Tensor) else Tensor(jnp.asarray(r))
        if t.grad is None:
            t.grad = g
        else:
            t.grad = t.grad + g
    # create_graph implies the graph stays (second backward needs it)


def grad(outputs, inputs, grad_outputs=None, retain_graph=None, create_graph=False, allow_unused=False):
    """paddle.grad parity (ref: python/paddle/autograd/ (U)) — functional form."""
    if isinstance(outputs, Tensor):
        outputs = [outputs]
    if isinstance(inputs, Tensor):
        inputs = [inputs]
    if grad_outputs is not None and isinstance(grad_outputs, Tensor):
        grad_outputs = [grad_outputs]

    saved = [(t, t.grad) for t in inputs]
    for t in inputs:
        t.grad = None
    targets = {id(t): t for t in inputs}
    try:
        for i, o in enumerate(outputs):
            g = grad_outputs[i] if grad_outputs is not None else None
            backward(o, grad_tensor=g,
                     retain_graph=True if retain_graph is None else retain_graph,
                     targets=targets, create_graph=create_graph)
        results = []
        for t in inputs:
            if t.grad is None:
                if not allow_unused:
                    raise RuntimeError(
                        "One of the differentiated tensors appears unused; pass allow_unused=True."
                    )
                results.append(None)
            else:
                results.append(t.grad)
        return results
    finally:
        for t, g in saved:
            t.grad = g
