"""Graph doctor: post-build analysis of a recorded `static.graph` Program
or a traced jaxpr (ref: the Program validation/prune passes around the
reference Executor — prune_backward, feed/fetch checking, and the
InterpreterCore's D2H-sync detection; here the same questions are asked of
the recorded _Node DAG and of the jaxpr that IS the program).

Findings (see diagnostics.RULES):

- PTA501  dead node — recorded/traced but unreachable from any fetch
- PTA502  unused feed — placeholder/input no fetch depends on
- PTA503  silent dtype widening (bf16/f16 operand promoted to f32+,
          f32 promoted to f64)
- PTA504  host-callback/sync point compiled into the program
- PTA505  collective over an axis name that is not bound in the mesh

Entry points:

- ``diagnose_program(fetch_list, program=None)`` — inspect a static-mode
  Program (uses ``Program.nodes``, the creation-order op record).
- ``diagnose_jaxpr(closed_jaxpr, mesh_axes=None)`` — inspect any jaxpr.
- ``doctor(fn, *example_args, mesh_axes=None)`` — trace ``fn`` abstractly
  (no FLOPs run) and diagnose the resulting jaxpr.
"""

from __future__ import annotations

import numpy as np

from .diagnostics import make

__all__ = ["diagnose_program", "diagnose_jaxpr", "doctor"]

_NARROW = ("bfloat16", "float16")
_WIDE = ("float32", "float64")
_CALLBACK_PRIMS = ("pure_callback", "debug_callback", "io_callback",
                   "host_callback", "outside_call", "debug_print")


def _widening(in_dtype, out_dtype):
    i, o = str(in_dtype), str(out_dtype)
    if i in _NARROW and o in _WIDE:
        return True
    return i == "float32" and o == "float64"


# --------------------------------------------------------------------------
# Program doctor


def _aval_of(x):
    """(dtype, weak_type) of a recorded node input, or None for
    non-arrays."""
    from ..static.graph import _SymArr, _ParamRef

    if isinstance(x, _SymArr):
        return x.aval.dtype, bool(getattr(x.aval, "weak_type", False))
    if isinstance(x, _ParamRef):
        d = getattr(x.t._data, "dtype", None)
        return (d, bool(getattr(x.t._data, "weak_type", False))) \
            if d is not None else None
    d = getattr(x, "dtype", None)
    if d is not None and not isinstance(x, (bool, int, float)):
        return d, bool(getattr(x, "weak_type", False))
    return None


def diagnose_program(fetch_list, program=None, file="<static.Program>"):
    """Diagnose a recorded static Program against the given fetches.
    ``fetch_list`` holds symbolic Tensors (as passed to Executor.run).
    Line numbers are 1-based positions in the program's creation-order
    node record."""
    from ..core.tensor import Tensor
    from ..static import graph as G

    prog = program if program is not None else G.default_main_program()
    syms = []
    for f in fetch_list:
        s = f._data if isinstance(f, Tensor) else f
        if not isinstance(s, G._SymArr):
            raise TypeError("diagnose_program: fetch_list entries must be "
                            "static-program Tensors")
        syms.append(s)

    # reachability from the fetches
    live, used_feeds = set(), set()
    stack = [s.node for s in syms if s.node is not None]
    used_feeds |= {s.feed_name for s in syms if s.feed_name is not None}
    while stack:
        n = stack.pop()
        if id(n) in live:
            continue
        live.add(id(n))
        for x in n.inputs:
            if isinstance(x, G._SymArr):
                if x.feed_name is not None:
                    used_feeds.add(x.feed_name)
                elif x.node is not None:
                    stack.append(x.node)

    diags = []
    nodes = list(getattr(prog, "nodes", ()) or ())
    for pos, n in enumerate(nodes, start=1):
        if id(n) not in live:
            diags.append(make(
                "PTA501", file, pos,
                message=f"dead node: op {n.op_name!r} (recorded op #{pos}) "
                        "is unreachable from the fetch_list"))
            continue
        out_avals = getattr(n, "out_avals", None) or ()
        in_avals = [a for a in map(_aval_of, n.inputs) if a is not None]
        for out in out_avals:
            odt = getattr(out, "dtype", None)
            if odt is None:
                continue
            for idt, weak in in_avals:
                if not weak and _widening(idt, odt):
                    diags.append(make(
                        "PTA503", file, pos,
                        message=f"op {n.op_name!r} (recorded op #{pos}) "
                                f"silently widens {idt} operand to {odt}"))
                    break
            else:
                continue
            break
    for pos, (name, ph) in enumerate(sorted(prog.placeholders.items()),
                                     start=1):
        if name not in used_feeds:
            diags.append(make(
                "PTA502", file, 0,
                message=f"unused feed: placeholder {name!r} is never "
                        "consumed by the fetched subgraph"))
    diags.sort(key=lambda d: (d.line, d.code))
    return diags


# --------------------------------------------------------------------------
# jaxpr doctor


def _eqn_frame(eqn):
    """The user's frame an eqn was traced from, or None (jax keeps the
    frame filter private; there is no public spelling)."""
    from jax._src import source_info_util

    return source_info_util.user_frame(eqn.source_info.traceback)


def _eqn_line(eqn, default=0):
    frame = _eqn_frame(eqn)
    return default if frame is None else frame.start_line


def _eqn_file(eqn, default="<jaxpr>"):
    frame = _eqn_frame(eqn)
    return default if frame is None else frame.file_name


def _axis_names(params):
    """str axis names mentioned by a collective eqn's params."""
    names = []
    for key in ("axes", "axis_name", "axis_index_groups_axis"):
        v = params.get(key)
        if v is None:
            continue
        for a in (v if isinstance(v, (tuple, list)) else (v,)):
            if isinstance(a, str):
                names.append(a)
    return names


def _sub_jaxprs(params):
    from jax.extend.core import ClosedJaxpr

    for v in params.values():
        if isinstance(v, ClosedJaxpr):
            yield v.jaxpr
        elif hasattr(v, "eqns") and hasattr(v, "outvars"):
            yield v
        elif isinstance(v, (tuple, list)):
            for w in v:
                if isinstance(w, ClosedJaxpr):
                    yield w.jaxpr
                elif hasattr(w, "eqns") and hasattr(w, "outvars"):
                    yield w


def diagnose_jaxpr(closed_jaxpr, mesh_axes=None, file="<jaxpr>"):
    """Diagnose a (Closed)Jaxpr. ``mesh_axes``: the axis names the program
    will run under (e.g. fleet topology dims); collectives over other
    names report PTA505. With mesh_axes=None the axis check is skipped."""
    from jax.extend.core import Literal

    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    mesh_axes = set(mesh_axes) if mesh_axes is not None else None
    diags = []

    # ---- liveness, walked backward; effectful eqns stay live ----
    live_vars = {v for v in jaxpr.outvars
                 if not isinstance(v, Literal)}
    live_eqns = [False] * len(jaxpr.eqns)
    for i in range(len(jaxpr.eqns) - 1, -1, -1):
        eqn = jaxpr.eqns[i]
        effectful = bool(getattr(eqn, "effects", None)) \
            or eqn.primitive.name in _CALLBACK_PRIMS
        if effectful or any(v in live_vars for v in eqn.outvars):
            live_eqns[i] = True
            for v in eqn.invars:
                if not isinstance(v, Literal):
                    live_vars.add(v)

    for i, eqn in enumerate(jaxpr.eqns):
        f = _eqn_file(eqn, file)
        ln = _eqn_line(eqn, i + 1)
        pname = eqn.primitive.name
        if not live_eqns[i]:
            diags.append(make(
                "PTA501", f, ln,
                message=f"dead compute: {pname!r} (eqn #{i + 1}) does not "
                        "feed any program output"))
            continue
        # host callbacks / sync points
        if pname in _CALLBACK_PRIMS or "callback" in pname:
            diags.append(make(
                "PTA504", f, ln,
                message=f"host callback {pname!r} compiled into the "
                        "program serializes the device pipeline"))
        # silent dtype widening at promotion sites
        if pname == "convert_element_type":
            src = eqn.invars[0]
            odt = eqn.params.get("new_dtype")
            sdt = getattr(src.aval, "dtype", None)
            weak = bool(getattr(src.aval, "weak_type", False))
            if sdt is not None and odt is not None and not weak \
                    and _widening(sdt, odt):
                diags.append(make(
                    "PTA503", f, ln,
                    message=f"implicit promotion widens {sdt} to {odt}"))
        # collectives over unbound axes
        if mesh_axes is not None:
            for name in _axis_names(eqn.params):
                if name not in mesh_axes:
                    diags.append(make(
                        "PTA505", f, ln,
                        message=f"collective {pname!r} runs over axis "
                                f"{name!r}, not bound in the mesh "
                                f"(axes: {sorted(mesh_axes)})"))
        sub_axes = mesh_axes
        if mesh_axes is not None and "shard_map" in pname:
            # shard_map binds its mesh's axis names for the body, even
            # when the shard_map itself sits under lax.scan (the
            # MeshEngine decode shape) — collectives over those axes
            # are well-bound, not PTA505.
            mesh = eqn.params.get("mesh")
            if mesh is not None:
                shape = getattr(mesh, "shape", None)
                if shape:
                    sub_axes = mesh_axes | set(dict(shape))
        for sub in _sub_jaxprs(eqn.params):
            diags.extend(diagnose_jaxpr(sub, mesh_axes=sub_axes, file=f))

    # ---- unused invars ----
    for j, v in enumerate(jaxpr.invars):
        if v not in live_vars:
            diags.append(make(
                "PTA502", file, 0,
                message=f"unused input: argument #{j + 1} never reaches "
                        "any program output"))
    diags.sort(key=lambda d: (d.file, d.line, d.code))
    return diags


def doctor(fn, *example_args, mesh_axes=None, axis_env=None, **kwargs):
    """Trace ``fn`` abstractly over example args (paddle Tensors, arrays,
    or ShapeDtypeStructs — no FLOPs run) and diagnose the jaxpr. Extra
    ``kwargs`` pass through to ``fn``. ``axis_env``: [(name, size)] pairs
    binding collective axes for tracing (defaults to mesh_axes with a
    dummy size of 1... sizes only matter for axis_index)."""
    import jax

    from ..core.tensor import Tensor

    def to_spec(a):
        if isinstance(a, Tensor):
            d = a._data
            return jax.ShapeDtypeStruct(tuple(d.shape), d.dtype)
        if isinstance(a, jax.ShapeDtypeStruct):
            return a
        arr = np.asarray(a)
        return jax.ShapeDtypeStruct(arr.shape, arr.dtype)

    specs = [to_spec(a) for a in example_args]
    target = getattr(fn, "forward", None) if not callable(fn) else fn
    inner = fn if callable(fn) else target

    def wrapped(*arrays):
        args = [Tensor(a) for a in arrays]
        out = inner(*args, **kwargs)
        leaves = out if isinstance(out, (tuple, list)) else [out]
        return tuple(o._data if isinstance(o, Tensor) else o
                     for o in leaves)

    if axis_env is None and mesh_axes:
        axis_env = [(name, 2) for name in mesh_axes]
    closed = jax.make_jaxpr(wrapped, axis_env=axis_env or None)(*specs)
    srcfile = getattr(getattr(inner, "__code__", None), "co_filename",
                      "<jaxpr>")
    return diagnose_jaxpr(closed, mesh_axes=mesh_axes, file=srcfile)
