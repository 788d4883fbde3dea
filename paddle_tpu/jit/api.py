"""paddle.jit parity: to_static, save, load, TrainStep.

Reference mapping (SURVEY.md §3.4): the dy2static AST/bytecode translator +
ProgramDesc + InterpreterCore + CINN pipeline collapses to `jax.jit` — the
tape-based eager ops are themselves traceable, so tracing the user's Python
callable once yields the whole fwd(+bwd+step) as one XLA program. What remains
of the subsystem is the ergonomics: input-spec caching, state
functionalization (parameters/buffers in, updated buffers out), RNG threading,
and save/load of compiled artifacts via jax.export (the .pdmodel analog is a
serialized StableHLO module).
"""

from __future__ import annotations

import functools
import os
import pickle
import time

import numpy as np
import jax
import jax.numpy as jnp

from ..core.tensor import Tensor, Parameter
from ..core import tape as _tape
from ..core import random_state
from ..nn.layer.layers import Layer
from ..observability import events as _obs_events
from ..observability import metrics as _obs_metrics

# per-function compile/cache telemetry: the acceptance invariant is that
# calling a jitted fn twice with identical avals shows cache_hit += 1 and
# compile_count unchanged (see tests/test_observability.py)
_COMPILE_COUNT = _obs_metrics.counter(
    "jit.compile_count", "to_static trace+compile builds, by function")
_CACHE_HIT = _obs_metrics.counter(
    "jit.cache_hit", "to_static calls served from the jit cache")
_COMPILE_SECONDS = _obs_metrics.histogram(
    "jit.compile_seconds",
    "wall seconds from cache miss to first result, by function")


class InputSpec:
    """paddle.static.InputSpec parity."""

    def __init__(self, shape, dtype="float32", name=None, stop_gradient=True):
        from ..core.dtype import to_jax_dtype

        self.shape = list(shape)
        self.dtype = to_jax_dtype(dtype)
        self.name = name
        self.stop_gradient = stop_gradient

    @classmethod
    def from_tensor(cls, tensor, name=None):
        return cls(tensor.shape, str(tensor.dtype), name)

    def __repr__(self):
        return f"InputSpec(shape={self.shape}, dtype={self.dtype}, name={self.name})"


def _spec_key(args, kwargs):
    def leaf_key(a):
        if isinstance(a, Tensor):
            return ("T", tuple(a._data.shape), str(a._data.dtype))
        if isinstance(a, (np.ndarray,)):
            return ("A", a.shape, str(a.dtype))
        if isinstance(a, (list, tuple)):
            return tuple(leaf_key(x) for x in a)
        return ("S", repr(a))

    return (tuple(leaf_key(a) for a in args),
            tuple(sorted((k, leaf_key(v)) for k, v in kwargs.items())))


class StaticFunction:
    """Callable wrapping fn (optionally bound to a Layer) with jit caching."""

    def __init__(self, function, layer=None, input_spec=None, full_graph=True):
        self._fn = function
        if full_graph:
            # dy2static-lite (ref dy2static AST transform, SURVEY.md §2.2
            # P8): if/while over traced tensors stage via lax cond/while;
            # falls back to the original fn when nothing converts
            from .dy2static import convert_to_static

            self._fn = convert_to_static(function)
        self._layer = layer
        self._input_spec = input_spec
        self._cache = {}
        functools.update_wrapper(self, function)

    @property
    def function(self):
        return self._fn

    def concrete_program_specified_input_spec(self, *a, **k):
        return None

    def _build(self, tree_args, tree_kwargs):
        layer = self._layer
        fn = self._fn

        state_names = list(layer.state_dict().keys()) if layer is not None else []

        def array_fn(rng_key, state_arrays, *flat_arrays):
            args, kwargs = _unflatten_args(tree_args, tree_kwargs, flat_arrays)
            with random_state.fork_rng(rng_key):
                if layer is not None:
                    arrays = dict(zip(state_names, state_arrays))
                    with layer.use_state(arrays):
                        out = fn(*args, **kwargs)
                        new_state = [layer.state_dict()[k]._data for k in state_names]
                else:
                    out = fn(*args, **kwargs)
                    new_state = []
            # trace-time mutation detection: a state entry the forward
            # leaves alone is the SAME tracer object it was handed — only
            # genuinely rewritten entries need writing back at call time
            mutated = tuple(i for i, (n, s)
                            in enumerate(zip(new_state, state_arrays))
                            if n is not s)
            out_flat, out_tree = _flatten_out(out)
            return (tuple(o._data if isinstance(o, Tensor) else o for o in out_flat),
                    tuple(new_state), out_tree, mutated)

        # out_tree / mutation set are trace-time static, captured per
        # TRACE: one StaticFunction cache entry can hold several jax.jit
        # traces (state arrays are not part of _spec_key — e.g. amp
        # rebinds a buffer's dtype), so the capture is a dict keyed by
        # the full input aval signature. A single last-trace box would
        # apply a stale mutated-index set when calls alternate between
        # cached signatures (ADVICE r5).
        out_tree_box = {}

        def jittable(rng_key, state_arrays, *flat_arrays):
            outs, new_state, out_tree, mutated = array_fn(
                rng_key, state_arrays, *flat_arrays)
            out_tree_box[_aval_sig(state_arrays, flat_arrays)] = {
                "tree": out_tree, "mutated": mutated}
            return outs, new_state

        return jax.jit(jittable), out_tree_box, state_names

    def __call__(self, *args, **kwargs):
        key = _spec_key(args, kwargs)
        fn_name = getattr(self, "__name__", None) \
            or getattr(self._fn, "__name__", "fn")
        if key in self._cache:
            _CACHE_HIT.inc(fn=fn_name)
            return self._call_impl(key, args, kwargs)
        # miss: a fresh trace+compile — record WHY (first call vs a new
        # input signature, the retrace cause) and how long the whole
        # miss-path call takes (trace + XLA compile + first execution:
        # the user-felt time-to-first-result)
        _obs_events.instant(
            "jit.retrace", cat="jit", fn=fn_name,
            cause=("first_call" if not self._cache
                   else "new_input_signature"),
            cached_signatures=len(self._cache),
            signature=repr(key)[:300])
        _obs_events.begin("jit.compile", cat="jit", fn=fn_name,
                          signature=repr(key)[:300])
        t0 = time.perf_counter()
        try:
            return self._call_impl(key, args, kwargs)
        finally:
            dt = time.perf_counter() - t0
            _COMPILE_COUNT.inc(fn=fn_name)
            _COMPILE_SECONDS.observe(dt, fn=fn_name)
            _obs_events.end("jit.compile", cat="jit", fn=fn_name,
                            seconds=round(dt, 9))

    def _call_impl(self, key, args, kwargs):
        if key not in self._cache:
            tree_args, tree_kwargs = _make_tree(args, kwargs)
            self._cache[key] = self._build(tree_args, tree_kwargs)
        jitted, out_tree_box, state_names = self._cache[key]

        flat, flat_tensors = _flatten_pairs(args, kwargs)
        rng_key = random_state.next_key()
        if self._layer is not None:
            sd = self._layer.state_dict()
            state_tensors = [sd[k] for k in state_names]
        else:
            state_tensors = []
        state_arrays = [t._data for t in state_tensors]
        sig = _aval_sig(state_arrays, flat)

        # ---- grad-aware path (paddle parity: a to_static model trains
        # with eager loss.backward()): the WHOLE jitted forward records as
        # ONE tape node — jax.vjp through the jit call gives the pullback,
        # so grads flow to the layer's parameters and to differentiable
        # inputs exactly as in the unjitted forward.
        from ..core import tape as _tape
        from ..core.op_call import _is_float, apply as _apply

        diff_state_idx = [i for i, t in enumerate(state_tensors)
                         if not t.stop_gradient
                         and _is_float(t._data.dtype)]
        diff_arg_idx = [i for i, t in enumerate(flat_tensors)
                        if t is not None and not t.stop_gradient
                        and _is_float(t._data.dtype)]
        if _tape.tape_enabled() and (diff_state_idx or diff_arg_idx):
            n_s = len(diff_state_idx)

            def call_fn(*arrays):
                st = list(state_arrays)
                fl = list(flat)
                for j, i in enumerate(diff_state_idx):
                    st[i] = arrays[j]
                for j, i in enumerate(diff_arg_idx):
                    fl[i] = arrays[n_s + j]
                outs, new_state = jitted(rng_key, st, *fl)
                return tuple(outs) + tuple(new_state)

            call_fn.__name__ = "to_static_" + getattr(self._fn, "__name__",
                                                      "fn")
            diff_tensors = ([state_tensors[i] for i in diff_state_idx]
                            + [flat_tensors[i] for i in diff_arg_idx])
            res = _apply(call_fn, *diff_tensors, _op_name=call_fn.__name__)
            if not isinstance(res, tuple):
                res = (res,)
            n_out = len(res) - len(state_names)
            out_tensors = list(res[:n_out])
            box = out_tree_box[sig]
            mutated = set(box["mutated"])
            for si, (t, new) in enumerate(zip(state_tensors, res[n_out:])):
                if t.stop_gradient or si in mutated:
                    # buffers (BN stats, ...) update in place; params write
                    # back ONLY when the traced forward actually rewrote
                    # them (advisor r4: dropping a param mutation here
                    # diverged from the no-grad path). Grads still flow
                    # w.r.t. the forward-time values.
                    t._data = new._data
            return _unflatten_tree(box["tree"], out_tensors)

        outs, new_state = jitted(rng_key, state_arrays, *flat)
        for t, arr in zip(state_tensors, new_state):
            t._data = arr
        out_tensors = [Tensor(o) for o in outs]
        return _unflatten_tree(out_tree_box[sig]["tree"], out_tensors)

    # paddle API surface
    def get_concrete_program(self, *args, **kwargs):
        return None

    @property
    def program_cache(self):
        return self._cache


def _aval_sig(state_arrays, flat_arrays):
    """Shape/dtype signature of one jitted-call's inputs — works on both
    concrete arrays (call time) and tracers (trace time), so the capture
    written under trace is found again by the call that triggered it."""
    return (tuple((tuple(a.shape), str(a.dtype)) for a in state_arrays),
            tuple((tuple(a.shape), str(a.dtype)) for a in flat_arrays))


def _make_tree(args, kwargs):
    """Record positions of Tensors; everything else is a static constant."""

    def conv(a):
        if isinstance(a, Tensor):
            return ("leaf",)
        if isinstance(a, np.ndarray):
            return ("leaf_np",)
        if isinstance(a, (list, tuple)):
            return ("seq", type(a).__name__, [conv(x) for x in a])
        return ("const", a)

    return [conv(a) for a in args], {k: conv(v) for k, v in kwargs.items()}


def _flatten_pairs(args, kwargs):
    """ONE walk producing aligned (arrays, tensor-objects-or-None) lists —
    the grad-aware call path maps indices between them, so they must never
    diverge by leaf kind."""
    arrays, tensors = [], []

    def walk(a):
        if isinstance(a, Tensor):
            arrays.append(a._data)
            tensors.append(a)
        elif isinstance(a, np.ndarray):
            arrays.append(jnp.asarray(a))
            tensors.append(None)
        elif isinstance(a, (list, tuple)):
            for x in a:
                walk(x)

    for a in args:
        walk(a)
    for k in sorted(kwargs):
        walk(kwargs[k])
    return arrays, tensors


def _flatten_args(args, kwargs):
    return _flatten_pairs(args, kwargs)[0]


def _flatten_arg_tensors(args, kwargs):
    """Tensor OBJECTS aligned with _flatten_args (None for non-Tensor
    leaves) — the grad-aware call path needs them as vjp targets."""
    return _flatten_pairs(args, kwargs)[1]


def _unflatten_args(tree_args, tree_kwargs, flat):
    it = iter(flat)

    def build(node):
        tag = node[0]
        if tag in ("leaf", "leaf_np"):
            return Tensor(next(it))
        if tag == "seq":
            seq = [build(x) for x in node[2]]
            return tuple(seq) if node[1] == "tuple" else seq
        return node[1]

    args = [build(n) for n in tree_args]
    kwargs = {}
    for k in sorted(tree_kwargs):
        kwargs[k] = build(tree_kwargs[k])
    return args, kwargs


def _flatten_out(out):
    flat, tree = [], None

    def conv(o):
        if isinstance(o, Tensor):
            flat.append(o)
            return ("leaf", len(flat) - 1)
        if isinstance(o, (list, tuple)):
            return ("seq", type(o).__name__, [conv(x) for x in o])
        if isinstance(o, dict):
            return ("dict", {k: conv(v) for k, v in o.items()})
        return ("const", o)

    tree = conv(out)
    return flat, tree


def _unflatten_tree(tree, tensors):
    def build(node):
        tag = node[0]
        if tag == "leaf":
            return tensors[node[1]]
        if tag == "seq":
            seq = [build(x) for x in node[2]]
            return tuple(seq) if node[1] == "tuple" else seq
        if tag == "dict":
            return {k: build(v) for k, v in node[1].items()}
        return node[1]

    return build(tree)


def to_static(function=None, input_spec=None, build_strategy=None, backend=None,
              full_graph=True, check=False, **kwargs):
    """Decorator/wrapper: compile a function or a Layer's forward with XLA.

    check=True runs the trace-safety linter (paddle_tpu.analysis.check)
    over the function at DECORATION time and emits each finding as a
    TraceSafetyWarning — hazards surface before the first trace."""

    def _run_check(fn):
        import warnings

        from ..analysis import check as _lint_check
        from ..analysis.diagnostics import TraceSafetyWarning

        try:
            diags = _lint_check(fn)
        except TypeError:
            return
        for d in diags:
            warnings.warn(d.format(), TraceSafetyWarning, stacklevel=4)

    def decorate(obj):
        if isinstance(obj, Layer):
            if check:
                _run_check(obj.forward)
            static = StaticFunction(obj.forward, layer=obj,
                                    input_spec=input_spec,
                                    full_graph=full_graph)
            obj.forward = static
            return obj
        if check:
            _run_check(obj)
        return StaticFunction(obj, layer=None, input_spec=input_spec,
                              full_graph=full_graph)

    if function is not None:
        return decorate(function)
    return decorate


def not_to_static(fn):
    fn._not_to_static = True
    return fn


def ignore_module(modules):
    pass


def enable_to_static(flag):
    pass


# ---------------------------------------------------------------- save/load
def save(layer, path, input_spec=None, **configs):
    """jit.save parity: weights (.pdiparams analog) + a serialized StableHLO
    inference function via jax.export (.pdmodel analog)."""
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    from ..framework.io import save as fsave

    if isinstance(layer, Layer):
        fsave(layer.state_dict(), path + ".pdiparams")
        if input_spec:
            sd = layer.state_dict()
            names = list(sd.keys())
            # the export trace must see the dy2static-CONVERTED forward
            # (early exits / staged control flow), exactly like __call__
            # through to_static does — shadow the bound forward for the
            # duration of the export (hooks still run via layer(...))
            from .dy2static import convert_to_static

            orig_fwd = layer.forward
            conv_fwd = convert_to_static(orig_fwd)

            def infer_fn(state_arrays, *arg_arrays):
                arrays = dict(zip(names, state_arrays))
                with _tape.no_grad():
                    with layer.use_state(arrays):
                        out = layer(*[Tensor(a) for a in arg_arrays])
                outs = out if isinstance(out, (list, tuple)) else [out]
                return tuple(o._data for o in outs)

            in_names = [getattr(sp, "name", None) or f"input_{i}"
                        for i, sp in enumerate(input_spec)]
            if len(set(in_names)) != len(in_names):
                raise ValueError(
                    f"jit.save: input_spec names must be unique, got "
                    f"{in_names}")
            state_arrays = [sd[k]._data for k in names]
            # restore EXACTLY the prior instance state: a user's own
            # instance-level forward (monkey-patch, to_static wrapper)
            # must survive the export shadow
            had_inst = "forward" in layer.__dict__
            prev_inst = layer.__dict__.get("forward")
            if conv_fwd is not orig_fwd:
                object.__setattr__(layer, "forward", conv_fwd)
            try:
                exported = export_with_dynamic_dims(
                    jax.jit(infer_fn), [state_arrays],
                    [(tuple(spec.shape), spec.dtype)
                     for spec in input_spec])
            finally:
                if conv_fwd is not orig_fwd:
                    if had_inst:
                        object.__setattr__(layer, "forward", prev_inst)
                    else:
                        object.__delattr__(layer, "forward")
            write_artifact(
                path, exported,
                [(list(s.shape),
                  str(np.dtype(s.dtype) if s.dtype != jnp.bfloat16
                      else "bfloat16")) for s in input_spec],
                in_names, names)
    else:
        raise TypeError("jit.save expects a Layer")


def export_with_dynamic_dims(jit_fn, leading_args, specs):
    """jax.export with dynamic (None/-1) spec dims as SYMBOLIC dims so the
    served program accepts any size there (batch polymorphism). Shared by
    jit.save and static.save_inference_model. specs: [(shape, dtype)]
    where shape entries are int | None | -1. Symbols start fully
    independent; if shape-polymorphic tracing cannot relate them (e.g.
    two inputs whose batch dims must be equal: a + b), retry with ONE
    symbol per axis index — the common shared-batch contract."""
    def build(share_by_axis):
        sym = {}
        example, dynamic = [], False
        for shape, dtype in specs:
            dims = []
            for ax, s in enumerate(shape):
                if s is None or (isinstance(s, int) and s < 0):
                    dynamic = True
                    key = ax if share_by_axis else len(sym)
                    if key not in sym:
                        (sym[key],) = jax.export.symbolic_shape(
                            f"d{len(sym)}")
                    dims.append(sym[key])
                else:
                    dims.append(int(s))
            example.append(jax.ShapeDtypeStruct(tuple(dims), dtype))
        return example, dynamic

    example, dynamic = build(False)
    if not dynamic:
        concrete = [jnp.zeros(tuple(s.shape), s.dtype) for s in example]
        return jax.export.export(jit_fn)(*leading_args, *concrete)
    try:
        return jax.export.export(jit_fn)(*leading_args, *example)
    except Exception:
        example, _ = build(True)
        return jax.export.export(jit_fn)(*leading_args, *example)


def write_artifact(path, exported, input_spec, input_names, state_names,
                   output_names=None):
    """The ONE .pdmodel blob schema — shared by jit.save and
    static.save_inference_model so jit.load / inference.Predictor never
    see divergent producers. Output metadata (names + avals) is persisted
    so the Predictor exposes REAL fetch names instead of fabricating
    output_{i} (VERDICT r3 item 7)."""
    n_out = len(exported.out_avals)
    if output_names is None:
        output_names = [f"output_{i}" for i in range(n_out)]
    if len(output_names) != n_out:
        raise ValueError(
            f"write_artifact: {len(output_names)} output names for "
            f"{n_out} exported outputs")
    if len(set(output_names)) != len(output_names):
        raise ValueError(
            f"write_artifact: duplicate output names {output_names}")
    with open(path + ".pdmodel", "wb") as f:
        pickle.dump({
            "stablehlo": exported.serialize(),
            "input_spec": input_spec,
            "input_names": input_names,
            "state_names": state_names,
            "output_names": list(output_names),
            # symbolic (batch-polymorphic) dims pickle as -1
            "output_spec": [([d if isinstance(d, int) else -1
                              for d in a.shape], str(a.dtype))
                            for a in exported.out_avals],
        }, f)


class TranslatedLayer(Layer):
    """jit.load result: runs the deserialized StableHLO program."""

    def __init__(self, exported, state_arrays, input_spec=None,
                 input_names=None, output_names=None):
        super().__init__()
        self._exported = exported
        self._state_arrays = state_arrays
        self._input_spec = input_spec or []
        self._input_names = input_names or [
            f"input_{i}" for i in range(len(self._input_spec))]
        self._output_names = output_names or [
            f"output_{i}" for i in range(len(exported.out_avals))]

    def forward(self, *args):
        arrs = [a._data if isinstance(a, Tensor) else jnp.asarray(a) for a in args]
        outs = self._exported.call(self._state_arrays, *arrs)
        outs = [Tensor(o) for o in outs]
        return outs[0] if len(outs) == 1 else tuple(outs)


def load(path, **configs):
    with open(path + ".pdmodel", "rb") as f:
        blob = pickle.load(f)
    exported = jax.export.deserialize(blob["stablehlo"])
    from ..framework.io import load as fload

    sd = fload(configs.get("params_path") or path + ".pdiparams")
    state_arrays = [sd[k]._data for k in blob["state_names"]]
    return TranslatedLayer(exported, state_arrays,
                           input_spec=blob.get("input_spec"),
                           input_names=blob.get("input_names"),
                           output_names=blob.get("output_names"))
