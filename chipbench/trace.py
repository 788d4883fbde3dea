"""From a profiler trace (`.xplane.pb`) to what the per-layer readers read.

`reduce(path)` gives, for the traced window: the seconds in which an
operation ran on each device (union of the op intervals), the time of every
named device operation, the operations that took most time, and the longest
idle gaps of device 0 by the host span (a `jax.profiler.TraceAnnotation` the
harness put round its calls) that covered them.

How a v5e trace looks under jax 0.9 (looked at by hand, PR 23): one plane
`/device:TPU:<n>` a chip, with the lines "XLA Modules" (one event a program
run, named `jit_<fn>(<fingerprint>)`), "XLA Ops" (one event an HLO op, named
by the whole text of its HLO instruction, `%fusion.12 = bf16[..] fusion(..),
kind=kOutput, calls=..`; a Pallas kernel is a `custom-call` whose text holds
`custom_call_target="tpu_custom_call"` and whose instruction name is made by
the tracer, `%jvp__.2`, `%transpose_jvp___.5`: the kernel's own name is not
in it, so a reader finds Pallas kernels by the target and tells them apart
by their shapes) and "Steps".
Host threads are lines of the plane `/host:CPU`; TraceAnnotation spans are
events there under their own name.  All start times are on one clock.
"""

from __future__ import annotations

import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "chipbench.window"


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path):
    """Plain lists out of the trace: {"devices": {plane: {line: [(name,
    start_ns, dur_ns)]}}, "host": [(name, start_ns, dur_ns)]}."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {}
            for line in plane.lines:
                lines[line.name] = [(e.name, float(e.start_ns),
                                     float(e.duration_ns))
                                    for e in line.events]
            devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    host.append((e.name, float(e.start_ns),
                                 float(e.duration_ns)))
    return {"devices": devices, "host": host}


def _union(intervals):
    """Merged, sorted [start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def _clip(events, lo, hi):
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def short_name(name, limit=160):
    """A device op's event name is its whole HLO instruction; keep the
    instruction's name, its opcode (with a custom call's target) and the
    type it produces, without layouts."""
    lhs, _, rhs = name.partition(" = ")
    if not rhs:
        return name[:limit]
    m = re.search(r"\s([a-z][a-z\-]*)\(", " " + rhs)
    opcode = m.group(1) if m else "?"
    produced = re.sub(r"\{[^{}]*\}", "", rhs[:max(m.start() - 1, 0)] if m
                      else "").strip()
    target = re.search(r'custom_call_target="([^"]+)"', rhs)
    if target:
        opcode += ":" + target.group(1)
    return f"{lhs} {opcode} -> {produced}"[:limit]


def reduce(loaded, top=10):
    """The reduction.  Times in seconds."""
    host = loaded["host"]
    windows = [(s, s + d) for n, s, d in host if n == WINDOW_SPAN]
    ops_by_dev = {p: lines.get(OPS_LINE, [])
                  for p, lines in sorted(loaded["devices"].items())}
    all_ops = [e for ops in ops_by_dev.values() for e in ops]
    if not all_ops:
        raise ValueError("the trace holds no device operation")
    if windows:
        lo, hi = windows[0]
    else:
        lo = min(s for _, s, _ in all_ops)
        hi = max(s + d for _, s, d in all_ops)
    busy, op_seconds, modules = [], {}, {}
    for plane, ops in ops_by_dev.items():
        ops = _clip(ops, lo, hi)
        merged = _union([(s, s + d) for _, s, d in ops])
        busy.append(sum(e - s for s, e in merged) / 1e9)
        for name, _, d in ops:
            op_seconds[name] = op_seconds.get(name, 0.0) + d / 1e9
        for name, _, d in _clip(loaded["devices"][plane].get(
                MODULES_LINE, []), lo, hi):
            modules[name] = modules.get(name, 0.0) + d / 1e9
    n_dev = max(1, len(ops_by_dev))
    # aggregate identical HLO op kinds for the breakdown
    kinds = {}
    for name, sec in op_seconds.items():
        short = short_name(name)
        kinds[short] = kinds.get(short, 0.0) + sec / n_dev
    device_ops = sorted(kinds.items(), key=lambda kv: -kv[1])[:top]

    # idle gaps of the first device, by the innermost host span over them
    first = next(iter(ops_by_dev.values()))
    merged = _union([(s, s + d) for _, s, d in _clip(first, lo, hi)])
    gaps, prev = [], lo
    for s, e in merged:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    if hi > prev:
        gaps.append((prev, hi))
    spans = sorted(((s, s + d, n) for n, s, d in host
                    if n.startswith("chipbench.") and n != WINDOW_SPAN),
                   key=lambda t: t[0])
    by_span = {}
    for a, b in gaps:
        mid, owner = (a + b) / 2, "host: no span"
        for s, e, n in spans:
            if s > mid:
                break
            if e >= mid:
                owner = n          # later start wins: the innermost
        by_span[owner] = by_span.get(owner, 0.0) + (b - a) / 1e9
    idle_gaps = sorted(by_span.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / n_dev,
        "busy_s_by_device": busy,
        "op_seconds": {k: v / n_dev for k, v in op_seconds.items()},
        "module_seconds": {k: v / n_dev for k, v in modules.items()},
        "device_ops": [[k, v] for k, v in device_ops],
        "idle_gaps": [[k, v] for k, v in idle_gaps],
        "window_ns": (lo, hi),
    }


def seconds_matching(reduced, pattern):
    """Device seconds of the ops whose name matches `pattern`, or None
    where none does: a reader that finds nothing returns nothing."""
    rx = re.compile(pattern)
    hits = [v for k, v in reduced["op_seconds"].items() if rx.search(k)]
    return sum(hits) if hits else None
