"""The harness: finds a cell's files by the names in BENCHMARK.json, runs
its driver, reads the per-layer metrics through their readers, and prints
the result line.  It is driven by data: a new configuration, traffic mix,
driver, reader or per-layer metric is a new file (chipbench/README.md).
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import importlib
import json
import os
import shutil
import sys
import threading
import time


def load_json(path):
    with open(path) as f:
        return json.load(f)


class CompileMeter:
    """Counts XLA backend compiles (persistent-cache look-ups included) and
    cache hits through jax.monitoring (copied from chip_smoke.py, PR 21)."""

    def __init__(self):
        import jax.monitoring as mon

        self.compiles, self.seconds, self.hits = 0, 0.0, 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, seconds, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += seconds

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def mark(self):
        return (self.compiles, self.seconds, self.hits)

    def since(self, mark):
        return {"xla_compiles": self.compiles - mark[0],
                "compile_seconds": round(self.seconds - mark[1], 2),
                "persistent_cache_hits": self.hits - mark[2]}


class Cell:
    """One entry of `workloads` with the files its names point at."""

    def __init__(self, root, bench, name):
        self.root, self.bench = root, bench
        try:
            self.entry = next(w for w in bench["workloads"]
                              if w["name"] == name)
        except StopIteration:
            raise SystemExit(f"chipbench: no workload {name!r} in "
                             f"BENCHMARK.json") from None
        self.name = name
        self.chips = self.entry["chips"]
        conf = next(c for c in bench["configs"]
                    if c["name"] == self.entry["config"])
        self.config = load_json(os.path.join(root, conf["file"]))
        here = os.path.join(root, "chipbench")
        self.traffic = load_json(os.path.join(
            here, "traffic", self.entry["traffic"] + ".json"))
        self.limits = load_json(os.path.join(here, "limits", name + ".json"))
        self.peaks_table = load_json(os.path.join(here, "peaks.json"))

    def reports(self, metric):
        """Does this cell report `metric` (an entry of end_to_end or
        per_layer)?  Without a `workloads` key every cell that reports the
        metric it moves does."""
        if "workloads" in metric:
            return self.name in metric["workloads"]
        moved = metric.get("moves")
        if moved is None:
            return True
        target = next(m for m in self.bench["end_to_end"]
                      if m["name"] == moved)
        return self.reports(target)

    def end_to_end(self):
        return [m for m in self.bench["end_to_end"] if self.reports(m)]

    def per_layer(self):
        return [m for m in self.bench["per_layer"] if self.reports(m)]

    def peaks(self, device_kind):
        try:
            return self.peaks_table[device_kind]
        except KeyError:
            raise SystemExit(
                f"chipbench: no published peaks for device kind "
                f"{device_kind!r}; add a row with its source to "
                f"chipbench/peaks.json") from None


class Context:
    """What a driver and a reader are given."""

    def __init__(self, cell, seed, seconds, trace, peaks, work_dir, log):
        self.cell, self.cfg, self.traffic = cell, cell.config, cell.traffic
        self.limits = cell.limits
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.peaks, self.work_dir, self.log = peaks, work_dir, log
        self.records = None       # what the window recorded
        self.reduced = None       # trace.reduce() of a traced run
        self.extra = {}           # driver's counters for the readers

    @contextlib.contextmanager
    def span(self, name):
        """A host span on the profiler's clock round a call into a layer."""
        import jax

        with jax.profiler.TraceAnnotation("chipbench." + name):
            yield


def device_info():
    import jax

    devices = jax.devices()
    return devices, {"platform": devices[0].platform,
                     "kind": devices[0].device_kind, "count": len(devices)}


def enable_compile_cache(root):
    """JAX's persistent cache at JAX_COMPILATION_CACHE_DIR if set, else at
    the fixed path <checkout>/.jax_cache."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def require_chip(cell, who):
    """The devices the cell runs on; without a TPU, or with fewer chips than
    the cell asks for: say why and exit 3.  There is no CPU branch."""
    devices, info = device_info()
    if info["platform"] != "tpu" or info["count"] < cell.chips:
        print(f"{who}: {cell.name} needs {cell.chips} TPU chip(s); jax "
              f"found {info}. There is no CPU branch.", file=sys.stderr)
        raise SystemExit(3)
    return devices[:cell.chips], info


def peak_memory(devices):
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def judge(compared):
    """`compared` is {name: {"value": v, "limit": l}}; every value has to
    be a number at or under its limit."""
    ok = bool(compared)
    for row in compared.values():
        v, lim = row["value"], row["limit"]
        if v is None or v != v or v > lim:
            ok = False
    return ok


class Tracer:
    """The profiler round the window.  A mix may trace only the window's
    first `trace_seconds`: a long serving window is millions of device
    events.  `stop` may be called twice (by the timer and at the end)."""

    def __init__(self, trace_dir, limit=None):
        self.trace_dir, self.limit = trace_dir, limit
        self._lock, self._running, self._timer = threading.Lock(), False, None

    def start(self):
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        self._running = True
        if self.limit:
            self._timer = threading.Timer(self.limit, self.stop)
            self._timer.start()

    def stop(self):
        import jax

        with self._lock:
            if self._running:
                self._running = False
                jax.profiler.stop_trace()

    def close(self):
        if self._timer is not None:
            self._timer.cancel()
            self._timer.join()
        self.stop()


def run_cell(ctx, driver, devices, t0, meter=None):
    """Everything after the look for a chip: set-up, window, check,
    readers.  Returns the result object."""
    from chipbench import trace as trace_mod

    state = driver.setup(ctx)
    in_setup = meter.mark() if meter else None
    setup_s = time.perf_counter() - t0
    ctx.log(f"set-up done in {setup_s:.1f} s")
    trace_dir = os.path.join(ctx.work_dir, "trace", ctx.cell.name)
    tracer = None
    if ctx.trace:
        limit = ctx.traffic.get("trace_seconds")
        tracer = Tracer(trace_dir, limit if limit and limit < ctx.seconds
                        else None)
        tracer.start()
    try:
        with ctx.span("window"):
            ctx.records = driver.window(ctx, state, ctx.seconds)
    finally:
        if tracer is not None:
            tracer.close()
    if hasattr(driver, "drain"):
        driver.drain(ctx, state)     # answers still due after the close
    if meter:
        ctx.extra["compiles_in_window"] = meter.since(in_setup)
        ctx.log(f"compiles inside the window: {ctx.extra['compiles_in_window']}")
    memory_peak = peak_memory(devices)
    metrics = driver.end_to_end(ctx, state)
    metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    attempted, failed = driver.counts(ctx)
    compared = driver.check(ctx, state)      # frees the program's state
    del state
    gc.collect()
    result = {"correct": judge(compared), "attempted": attempted,
              "failed": failed}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": memory_peak}
    wanted = {m["name"]: m for m in ctx.cell.end_to_end()}
    if ctx.trace:
        xplane = trace_mod.find_xplane(trace_dir)
        keep = os.environ.get("CHIPBENCH_KEEP_TRACE")
        if keep:                     # for a look by hand; see README.md
            os.makedirs(keep, exist_ok=True)
            shutil.copy(xplane, os.path.join(
                keep, f"{ctx.cell.name}.{ctx.seed}.xplane.pb"))
        loaded = trace_mod.load(xplane)
        ctx.reduced = trace_mod.reduce(loaded)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = ctx.reduced["busy_s"]
        device["window_s"] = ctx.reduced["window_s"]
        out = {}
        for m in ctx.cell.per_layer():
            value = read_layer_metric(ctx, m["name"])
            if value is not None:
                out[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = out
        result["breakdown"] = {"device_ops": ctx.reduced["device_ops"],
                               "idle_gaps": ctx.reduced["idle_gaps"]}
    else:
        result["metrics"] = {k: v for k, v in metrics.items() if k in wanted}
    result["device"] = device
    result["compared"] = compared
    return result


def read_layer_metric(ctx, name):
    """chipbench/layer_metrics/<name>.json names the reader (a module under
    chipbench/readers/) and its arguments."""
    spec = load_json(os.path.join(ctx.cell.root, "chipbench",
                                  "layer_metrics", name + ".json"))
    reader = importlib.import_module("chipbench.readers." + spec["reader"])
    return reader.read(ctx, **spec.get("args", {}))


def print_compared(compared, correct):
    lines = [f"chipbench: correct={str(correct).lower()}"]
    for name, row in compared.items():
        lines.append(f"  {name} = {row['value']!r}  limit {row['limit']!r}")
    print("\n".join(lines), file=sys.stderr, flush=True)


def main(argv, t0, root):
    p = argparse.ArgumentParser(prog="chipbench/run.py")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    cell = Cell(root, load_json(os.path.join(root, "BENCHMARK.json")),
                args.workload)
    devices, info = require_chip(cell, "chipbench")
    peaks = cell.peaks(info["kind"])
    cache_dir = enable_compile_cache(root)
    meter = CompileMeter()

    def log(msg):
        print(f"[chipbench {time.perf_counter() - t0:7.1f}s] {msg}",
              flush=True)

    log(f"{cell.name} seed={args.seed} seconds={args.seconds} "
        f"trace={args.trace} device={info} cache={cache_dir}")
    ctx = Context(cell, args.seed, args.seconds, bool(args.trace), peaks,
                  os.path.join(root, "chipbench", ".work"), log)
    driver = importlib.import_module(
        "chipbench.drivers." + cell.traffic["driver"])
    result = run_cell(ctx, driver, devices, t0, meter)
    result["device"]["count"] = cell.chips
    log(f"compiles in the whole run: {meter.since((0, 0.0, 0))}")
    print_compared(result["compared"], result["correct"])
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0
