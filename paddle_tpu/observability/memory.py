"""Device-memory ledger + the backend-bandwidth lookup (observability phase 3).

Two answers this module owns:

**Where did the HBM go?**  :class:`MemoryLedger` holds one byte-
accounting callable per named component (the engine registers its paged
KV pool, its weight arrays, and its device-resident decode state) and
reconciles their sum against what JAX actually holds alive
(``jax.live_arrays()``).  ``snapshot()`` publishes the result as
``memory.*`` gauges:

* ``memory.accounted_bytes{ledger,component}`` — each component's own
  claim;
* ``memory.accounted_total_bytes`` / ``memory.live_bytes`` — the two
  sides of the reconciliation;
* ``memory.unaccounted_bytes`` — live minus accounted (rotary tables,
  scratch, anything nobody claims);
* ``memory.leak_delta_bytes`` — the leak detector: growth of the
  unaccounted residue since the baseline mark.  Pool-accounted bytes
  are allowed to grow (admission allocates blocks); bytes NOBODY
  accounts for growing monotonically is a leak signature.

Reconciliation walks every live array, so it runs on demand
(``Engine.stats()``, tests, dashboards) — not per decode step.

**What bandwidth does this backend have?**  The lookup lives here (so
the swap policy, the fleet simulator and the benches share one number):
the published row of ``peaks.PEAKS`` for the chip's ``device_kind``, a
one-shot 64 MiB memcpy probe for the CPU.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from . import metrics as _metrics
from . import peaks as _peaks

_ACCT = _metrics.gauge(
    "memory.accounted_bytes",
    "device bytes each registered component claims to hold")
_ACCT_TOTAL = _metrics.gauge(
    "memory.accounted_total_bytes",
    "sum of all component-accounted device bytes")
_LIVE = _metrics.gauge(
    "memory.live_bytes",
    "total bytes of jax.live_arrays() at the last reconcile")
_UNACCT = _metrics.gauge(
    "memory.unaccounted_bytes",
    "live bytes no registered component accounts for")
_LEAK = _metrics.gauge(
    "memory.leak_delta_bytes",
    "growth of the unaccounted residue since the baseline mark")
_HOST_ACCT = _metrics.gauge(
    "memory.host_arena_bytes",
    "pinned host-RAM bytes each registered host component holds (the "
    "tiered-KV spill arena); deliberately OUTSIDE the device "
    "reconciliation — host numpy buffers never appear in "
    "jax.live_arrays(), so folding them into accounted_total_bytes "
    "would poison unaccounted/leak_delta")
_BW_PROBED = {}
_BW_LOCK = threading.Lock()


def backend_bandwidth_gbs(device_kind):
    """Roofline memory bandwidth for ``device_kind`` in GB/s: the
    published HBM figure of that chip (``peaks.PEAKS``; an accelerator
    without a row raises).  The CPU — the test path, which claims no
    device peak — gets a one-shot streaming-memcpy probe instead (64 MiB
    source, read+write counted, best of 4 passes — DRAM speed, not L3,
    at that footprint).  Memoized: the probe runs at most once per
    process so every caller agrees on the number."""
    row = _peaks.chip_peaks(device_kind)
    if row is not None:
        return row.hbm_gbs
    return _memcpy_probe_gbs(device_kind)


def host_device_bandwidth_gbs(device_kind):
    """Host<->device transfer bandwidth for ``device_kind`` in GB/s —
    what a tiered-KV swap's upload seconds divide by (the
    swap-vs-recompute policy and bench crossover both normalize with
    this one number).  The chip's host-link row for accelerators; on the
    CPU a host->device "transfer" is a memcpy, so the memcpy probe IS
    the honest figure (keyed apart from the HBM probe so the two
    memoized figures never alias)."""
    row = _peaks.chip_peaks(device_kind)
    if row is not None:
        return row.host_link_gbs
    return _memcpy_probe_gbs(("host", device_kind))


def _memcpy_probe_gbs(key):
    with _BW_LOCK:
        if key not in _BW_PROBED:
            src = np.ones(1 << 26, np.uint8)          # 64 MiB
            dst = np.empty_like(src)
            np.copyto(dst, src)                       # fault pages in
            best = None
            for _ in range(4):
                t0 = time.perf_counter()
                np.copyto(dst, src)
                dt = time.perf_counter() - t0
                best = dt if best is None else min(best, dt)
            _BW_PROBED[key] = round(2.0 * src.nbytes / best / 1e9, 1)
        return _BW_PROBED[key]


def live_device_bytes():
    """Total bytes of every live jax array in the process (0 when the
    runtime doesn't expose live_arrays)."""
    try:
        import jax

        arrays = jax.live_arrays()
    except Exception:                # pragma: no cover - defensive
        return 0
    total = 0
    for a in arrays:
        try:
            total += int(a.nbytes)
        except Exception:            # deleted/donated buffers
            continue
    return total


class MemoryLedger:
    """Named byte-accounting components reconciled against
    ``jax.live_arrays()``.

    Components are zero-arg callables returning their current device
    bytes; they are polled at ``snapshot()`` time.  The ledger never
    holds device arrays itself — callables typically close over the
    pools they account, and the engine owns the ledger, so its
    lifetime is the engine's."""

    def __init__(self, name=""):
        self.name = name
        self._lock = threading.Lock()
        self._components = {}
        self._host_components = {}
        self._baseline_unaccounted = None

    def register(self, component, fn):
        if not callable(fn):
            raise TypeError("component accounting fn must be callable")
        with self._lock:
            self._components[component] = fn
        return self

    def register_host(self, component, fn):
        """Register a HOST-memory component (pinned numpy arenas — the
        tiered-KV spill tier).  Host bytes are published as
        ``memory.host_arena_bytes`` and reported in the snapshot, but
        NEVER summed into the device reconciliation: they are invisible
        to ``jax.live_arrays()``, so counting them as accounted would
        drive ``unaccounted_bytes`` negative and break the
        ``leak_delta_bytes`` exactness the leak detector rests on."""
        if not callable(fn):
            raise TypeError("component accounting fn must be callable")
        with self._lock:
            self._host_components[component] = fn
        return self

    def unregister(self, component):
        with self._lock:
            self._components.pop(component, None)

    def components(self):
        with self._lock:
            return list(self._components)

    def account(self):
        """Poll every component: {component: bytes} (a component that
        raises reports 0 rather than poisoning the snapshot)."""
        with self._lock:
            items = list(self._components.items())
        out = {}
        for name, fn in items:
            try:
                out[name] = int(fn())
            except Exception:        # pragma: no cover - defensive
                out[name] = 0
        return out

    def account_host(self):
        """Poll every host component: {component: bytes}."""
        with self._lock:
            items = list(self._host_components.items())
        out = {}
        for name, fn in items:
            try:
                out[name] = int(fn())
            except Exception:        # pragma: no cover - defensive
                out[name] = 0
        return out

    def mark_baseline(self):
        """Re-anchor the leak detector at the current residue (called
        automatically by the first snapshot)."""
        acct = self.account()
        self._baseline_unaccounted = (live_device_bytes()
                                      - sum(acct.values()))
        return self._baseline_unaccounted

    def snapshot(self):
        """Reconcile + publish the ``memory.*`` gauges; returns the
        ledger state as a JSON-able dict."""
        acct = self.account()
        accounted = sum(acct.values())
        live = live_device_bytes()
        unaccounted = live - accounted
        if self._baseline_unaccounted is None:
            self._baseline_unaccounted = unaccounted
        leak = unaccounted - self._baseline_unaccounted
        labels = dict(ledger=self.name)
        for comp, b in acct.items():
            _ACCT.set(b, component=comp, **labels)
        _ACCT_TOTAL.set(accounted, **labels)
        _LIVE.set(live, **labels)
        _UNACCT.set(unaccounted, **labels)
        _LEAK.set(leak, **labels)
        host = self.account_host()
        for comp, b in host.items():
            _HOST_ACCT.set(b, component=comp, **labels)
        out = {
            "ledger": self.name,
            "components": acct,
            "accounted_total_bytes": accounted,
            "live_bytes": live,
            "unaccounted_bytes": unaccounted,
            "leak_delta_bytes": leak,
        }
        if host:
            # reported alongside, summed into NOTHING above: see
            # register_host for why host bytes stay out of the device
            # reconciliation
            out["host_components"] = host
            out["host_total_bytes"] = sum(host.values())
        return out
