"""Program cards: per-compiled-program cost dossiers for the serving
engine (observability phase 3).

Every compiled serving program — each prefill ``(lanes, bucket)`` pair,
each decode ``(horizon, nb, K)`` triple — gets ONE card at its first
compile, capturing what the compiler itself knows about the program:

* XLA ``cost_analysis()`` — FLOPs and bytes accessed per dispatch
  (the probe pattern jit/train_step.py established: prefer the
  compiled executable's analysis, fall back to the HLO-level one, and
  record honest ``None`` when a backend offers neither);
* ``memory_analysis()`` — argument/output/temp/code bytes of the
  executable (``CompiledMemoryStats``), i.e. the program's static
  device-memory footprint;
* wall-clock compile seconds and static metadata the caller supplies
  (bucket key, donated bytes, lane count, ...).

Cards live in a process-wide :class:`ProgramCardRegistry` keyed by
``(fn, signature-hash)`` so repeated engine construction with the same
shapes never re-probes.  The registry publishes ``compile.*`` gauges per card
(``NaN`` where an analysis is unavailable on the backend — the
exposition format has a spelling for that, and dashboards should see
"unknown", not 0), feeds the ``/debug/programs`` telemetry endpoint,
and renders as ``python -m paddle_tpu.observability programs``.

The cards are also the engine's cost model: per-dispatch FLOP/byte
totals divided over the lanes that rode the dispatch become the
per-request cost attribution in ``RequestTrace`` (engine.py), and
bytes-accessed over dispatch wall time becomes the live
achieved-vs-roofline gauge (memory.py supplies the bandwidth).
"""

from __future__ import annotations

import threading
import time

from . import events as _events
from . import metrics as _metrics

#: per-program gauges, labeled (fn, key); value NaN = analysis
#: unavailable on this backend
_CARD_FLOPS = _metrics.gauge(
    "compile.program_flops",
    "XLA cost-analysis FLOPs per dispatch of a compiled program")
_CARD_BYTES = _metrics.gauge(
    "compile.program_bytes_accessed",
    "XLA cost-analysis bytes accessed per dispatch of a compiled program")
_CARD_SECONDS = _metrics.gauge(
    "compile.program_compile_seconds",
    "wall seconds the first compile of this program took")
_CARD_ARG_BYTES = _metrics.gauge(
    "compile.program_argument_bytes",
    "executable argument bytes (memory_analysis)")
_CARD_TEMP_BYTES = _metrics.gauge(
    "compile.program_temp_bytes",
    "executable scratch/temp bytes (memory_analysis)")
_CARD_COUNT = _metrics.gauge(
    "compile.programs", "program cards captured, by function")


def _nan_if_none(v):
    return float("nan") if v is None else float(v)


class ProgramCard:
    """The cost dossier of ONE compiled program.  ``backend`` is the
    ``device_kind`` of the device it was compiled for ("cpu" on the
    CPU) — the key ``peaks.PEAKS`` and the bandwidth lookups take."""

    __slots__ = ("fn", "key", "backend", "flops", "bytes_accessed",
                 "compile_seconds", "donated_bytes", "argument_bytes",
                 "output_bytes", "temp_bytes", "generated_code_bytes",
                 "meta", "created_wall", "dispatches", "analysis_source",
                 "comms")

    def __init__(self, fn, key, backend="", flops=None,
                 bytes_accessed=None, compile_seconds=0.0,
                 donated_bytes=0, argument_bytes=None, output_bytes=None,
                 temp_bytes=None, generated_code_bytes=None, meta=None,
                 analysis_source=None, comms=None):
        self.fn = fn
        self.key = key
        self.backend = backend
        self.flops = None if flops is None else float(flops)
        self.bytes_accessed = (None if bytes_accessed is None
                               else float(bytes_accessed))
        self.compile_seconds = float(compile_seconds)
        self.donated_bytes = int(donated_bytes)
        self.argument_bytes = argument_bytes
        self.output_bytes = output_bytes
        self.temp_bytes = temp_bytes
        self.generated_code_bytes = generated_code_bytes
        self.meta = dict(meta or {})
        self.created_wall = time.time()
        self.dispatches = 0          # bumped by the owner per call
        self.analysis_source = analysis_source
        # phase 4: comms.analyze_jaxpr(...).to_json() of the traced
        # program, when the caller ran the walker; None = not analyzed
        self.comms = comms

    def to_json(self):
        return {
            "fn": self.fn,
            "key": self.key,
            "backend": self.backend,
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "compile_seconds": round(self.compile_seconds, 6),
            "donated_bytes": self.donated_bytes,
            "argument_bytes": self.argument_bytes,
            "output_bytes": self.output_bytes,
            "temp_bytes": self.temp_bytes,
            "generated_code_bytes": self.generated_code_bytes,
            "analysis_source": self.analysis_source,
            "comms": self.comms,
            "dispatches": self.dispatches,
            "created_wall": self.created_wall,
            "meta": dict(self.meta),
        }


class ProgramCardRegistry:
    """Process-wide card store keyed by ``(fn, key)``.

    ``record()`` publishes the card's ``compile.*`` gauges; ``get()``
    lets a CompiledFn skip the probe when an identical program (same
    function, same signature) was already carded by an earlier engine
    in this process."""

    def __init__(self):
        self._lock = threading.Lock()
        self._cards = {}             # (fn, key) -> ProgramCard

    def record(self, card):
        with self._lock:
            self._cards[(card.fn, card.key)] = card
        labels = dict(fn=card.fn, key=card.key)
        _CARD_FLOPS.set(_nan_if_none(card.flops), **labels)
        _CARD_BYTES.set(_nan_if_none(card.bytes_accessed), **labels)
        _CARD_SECONDS.set(card.compile_seconds, **labels)
        _CARD_ARG_BYTES.set(_nan_if_none(card.argument_bytes), **labels)
        _CARD_TEMP_BYTES.set(_nan_if_none(card.temp_bytes), **labels)
        with self._lock:
            per_fn = sum(1 for f, _ in self._cards if f == card.fn)
        _CARD_COUNT.set(per_fn, fn=card.fn)
        return card

    def get(self, fn, key):
        with self._lock:
            return self._cards.get((fn, key))

    def cards(self, fn=None):
        with self._lock:
            out = list(self._cards.values())
        if fn is not None:
            out = [c for c in out if c.fn == fn]
        return sorted(out, key=lambda c: (c.fn, c.key))

    def __len__(self):
        with self._lock:
            return len(self._cards)

    def clear(self):
        with self._lock:
            self._cards.clear()

    def to_json(self):
        cards = self.cards()
        return {
            "count": len(cards),
            "total_flops_dispatched": sum(
                c.flops * c.dispatches for c in cards
                if c.flops is not None),
            "total_bytes_dispatched": sum(
                c.bytes_accessed * c.dispatches for c in cards
                if c.bytes_accessed is not None),
            "cards": [c.to_json() for c in cards],
        }

    def render_text(self):
        """Human-readable table for the CLI."""
        cards = self.cards()
        if not cards:
            return "no program cards captured\n"
        rows = [("fn", "key", "flops", "bytes", "compile_s",
                 "dispatches", "meta")]
        for c in cards:
            rows.append((
                c.fn, c.key,
                _fmt_quantity(c.flops), _fmt_quantity(c.bytes_accessed),
                f"{c.compile_seconds:.3f}", str(c.dispatches),
                ",".join(f"{k}={v}" for k, v in sorted(c.meta.items()))))
        widths = [max(len(r[i]) for r in rows) for i in range(len(rows[0]))]
        lines = ["  ".join(cell.ljust(w) for cell, w in zip(r, widths))
                 for r in rows]
        return "\n".join(lines) + "\n"


def _fmt_quantity(v):
    if v is None:
        return "n/a"
    for unit, div in (("G", 1e9), ("M", 1e6), ("K", 1e3)):
        if v >= div:
            return f"{v / div:.2f}{unit}"
    return f"{v:.0f}"


def _scalar_analysis(analysis):
    """Normalize jax's cost_analysis return shape: a dict, or a
    per-device list of dicts (take device 0), or None."""
    if isinstance(analysis, (list, tuple)):
        analysis = analysis[0] if analysis else None
    return analysis if isinstance(analysis, dict) else None


def analyze_lowered(lowered, deep=False):
    """Extract (flops, bytes_accessed, memory-stats dict, source) from a
    ``jax.stages.Lowered``.

    ``deep=True`` reads the executable's analyses (optimized HLO plus
    ``memory_analysis``).  ``lowered.compile()`` does not run XLA again
    when the jitted function has already been called with these
    arguments: the ``Lowered`` and the call share one lowering, which
    keeps its executable — so callers lower, call, then analyze.
    ``deep=False`` stays on the HLO-level ``lowered.cost_analysis()`` —
    same flops/bytes-accessed numbers on CPU, but no memory stats.  jax
    returns None for an analysis the backend does not offer; so does
    this (all-None when it offers neither).  Anything else raises."""
    cost = mem = source = None
    if deep:
        compiled = lowered.compile()
        cost = _scalar_analysis(compiled.cost_analysis())
        source = "compiled" if cost is not None else None
        mem = compiled.memory_analysis()
    if cost is None:
        try:
            cost = _scalar_analysis(lowered.cost_analysis())
        except NotImplementedError:   # a Lowered with no analysis at all
            cost = None
        source = "lowered" if cost is not None else None
    flops = bytes_accessed = None
    if cost:
        flops = cost.get("flops")
        bytes_accessed = cost.get("bytes accessed",
                                  cost.get("bytes_accessed"))
    stats = {}
    if mem is not None:
        for field in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes",
                      "generated_code_size_in_bytes"):
            stats[field] = getattr(mem, field, None)
    return flops, bytes_accessed, stats, source


def capture(fn_name, key, lowered, compile_seconds=0.0, donated_bytes=0,
            meta=None, backend="", registry=None, deep=None, comms=None):
    """Build + record one ProgramCard from a ``Lowered``.  A backend
    without analyses still yields a card with Nones; a probe that fails
    for any other reason raises.  ``deep=None`` auto-selects: the
    executable's analyses (memory stats, optimized-HLO cost) on
    accelerator backends, the HLO-level estimate on cpu.

    ``comms`` (phase 4) attaches a collective census to the card: pass
    the ``comms.CommsReport`` of the traced program (its ``comms.*``
    counters are published once, here) or an already-rendered dict."""
    reg = registry if registry is not None else _default_registry
    if deep is None:
        deep = backend not in ("", "cpu")
    flops, bytes_accessed, stats, source = analyze_lowered(
        lowered, deep=deep)
    if comms is not None and hasattr(comms, "to_json"):
        try:
            comms = comms.publish().to_json()
        except Exception:            # pragma: no cover - defensive
            comms = None
    card = ProgramCard(
        fn_name, key, backend=backend, flops=flops,
        bytes_accessed=bytes_accessed, compile_seconds=compile_seconds,
        donated_bytes=donated_bytes,
        argument_bytes=stats.get("argument_size_in_bytes"),
        output_bytes=stats.get("output_size_in_bytes"),
        temp_bytes=stats.get("temp_size_in_bytes"),
        generated_code_bytes=stats.get("generated_code_size_in_bytes"),
        meta=meta, analysis_source=source, comms=comms)
    reg.record(card)
    _events.instant("compile.program_card", cat="observability",
                    fn=fn_name, key=key,
                    flops=flops, bytes_accessed=bytes_accessed,
                    seconds=round(float(compile_seconds), 6))
    return card


_default_registry = ProgramCardRegistry()


def default_registry():
    return _default_registry


def cards(fn=None):
    return _default_registry.cards(fn)


def to_json():
    return _default_registry.to_json()


def render_text():
    return _default_registry.render_text()


def clear():
    _default_registry.clear()
