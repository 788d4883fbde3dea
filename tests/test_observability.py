"""paddle_tpu.observability tests: typed registry semantics, histogram
percentiles vs a numpy reference, chrome-trace export validity, the
jit compile-counter invariant, span nesting, the profiler facade and its
satellite fixes (tuple scheduler, n=1 summary, engine provider GC), and
a CLI smoke via ``python -m``."""

import gc
import json
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu import profiler
from paddle_tpu.observability import events as obs_events
from paddle_tpu.observability import metrics as obs_metrics
from paddle_tpu.observability.metrics import (
    Counter, Gauge, Histogram, Registry,
)
from paddle_tpu.observability.span import current_span, span, span_depth


class TestRegistry:
    def test_counter_labels_and_monotonicity(self):
        reg = Registry()
        c = reg.counter("requests", "total requests")
        c.inc()
        c.inc(2, route="a")
        c.inc(route="a")
        assert c.value() == 1
        assert c.value(route="a") == 3
        assert c.value(route="missing") == 0
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_gauge_set_inc_dec(self):
        reg = Registry()
        g = reg.gauge("depth")
        g.set(7, q="main")
        g.inc(q="main")
        g.dec(3, q="main")
        assert g.value(q="main") == 5

    def test_get_or_create_returns_same_family(self):
        reg = Registry()
        a = reg.counter("x")
        b = reg.counter("x")
        assert a is b

    def test_type_conflict_raises(self):
        reg = Registry()
        reg.counter("x")
        with pytest.raises(ValueError):
            reg.gauge("x")

    def test_label_order_is_canonical(self):
        reg = Registry()
        c = reg.counter("c")
        c.inc(a=1, b=2)
        c.inc(b=2, a=1)
        assert c.value(b=2, a=1) == 2

    def test_snapshot_shape(self):
        reg = Registry()
        reg.counter("n", "help text").inc(5)
        reg.gauge("g").set(1.5)
        reg.histogram("h").observe(0.2)
        snap = reg.snapshot()
        assert snap["metrics"]["n"]["type"] == "counter"
        assert snap["metrics"]["n"]["help"] == "help text"
        assert snap["metrics"]["n"]["values"][""] == 5
        assert snap["metrics"]["g"]["values"][""] == 1.5
        assert snap["metrics"]["h"]["values"][""]["count"] == 1
        json.dumps(snap)  # must be JSON-able as-is

    def test_reset_keeps_families(self):
        reg = Registry()
        c = reg.counter("c")
        c.inc(10)
        reg.reset()
        assert c.value() == 0
        assert reg.get("c") is c
        c.inc()
        assert c.value() == 1


class TestHistogram:
    def test_percentiles_match_numpy(self):
        reg = Registry()
        h = reg.histogram("lat")
        rng = np.random.default_rng(0)
        samples = rng.lognormal(-3, 1.0, size=500)
        for s in samples:
            h.observe(s)
        for q in (50, 95, 99):
            assert h.percentile(q) == pytest.approx(
                float(np.percentile(samples, q)))
        st = h.stats()
        assert st["count"] == 500
        assert st["sum"] == pytest.approx(samples.sum())
        assert st["mean"] == pytest.approx(samples.mean())
        assert st["p50"] == pytest.approx(np.percentile(samples, 50))

    def test_buckets_are_cumulative(self):
        reg = Registry()
        h = reg.histogram("lat", buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.5, 0.5, 5.0, 50.0):
            h.observe(v)
        b = h.stats()["buckets"]
        assert b[repr(0.1)] == 1
        assert b[repr(1.0)] == 3
        assert b[repr(10.0)] == 4
        assert b["+Inf"] == 5

    def test_reservoir_is_bounded(self):
        reg = Registry()
        h = reg.histogram("lat", reservoir=16)
        for i in range(100):
            h.observe(float(i))
        st = h.stats()
        assert st["count"] == 100          # exact totals survive
        # percentiles slide to the most recent window
        assert h.percentile(50) >= 84.0

    def test_labelled_slots_are_independent(self):
        reg = Registry()
        h = reg.histogram("lat")
        h.observe(1.0, op="a")
        h.observe(100.0, op="b")
        assert h.percentile(50, op="a") == 1.0
        assert h.percentile(50, op="b") == 100.0
        assert h.percentile(50, op="c") is None


class TestPrometheusRendering:
    def test_exposition_format(self):
        reg = Registry()
        reg.counter("jit.compile_count", "compiles").inc(3, fn="f")
        reg.gauge("queue.depth").set(2)
        reg.histogram("lat", buckets=(0.1, 1.0)).observe(0.5)
        text = reg.render_prometheus()
        assert "# TYPE jit_compile_count counter" in text
        assert '# HELP jit_compile_count compiles' in text
        assert 'jit_compile_count{fn="f"} 3' in text
        assert "# TYPE queue_depth gauge" in text
        assert "# TYPE lat histogram" in text
        assert 'lat_bucket{le="0.1"} 0' in text
        assert 'lat_bucket{le="1.0"} 1' in text
        assert 'lat_bucket{le="+Inf"} 1' in text
        assert "lat_sum" in text and "lat_count" in text

    def test_providers_render_as_gauges(self):
        reg = Registry()
        reg.register_provider("serving.engine0",
                              lambda: {"tokens": 42, "note": "text"})
        text = reg.render_prometheus()
        assert '# TYPE serving_engine0 gauge' in text
        assert 'serving_engine0{counter="tokens"} 42' in text
        assert "note" not in text          # non-numeric values skipped

    def test_default_registry_render_nonempty(self):
        text = obs.render_prometheus()
        assert "# TYPE " in text


class TestProviders:
    def test_register_snapshot_unregister(self):
        reg = Registry()
        reg.register_provider("sub", lambda: {"a": 1})
        assert reg.provider_counters() == {"sub": {"a": 1}}
        assert reg.snapshot()["providers"] == {"sub": {"a": 1}}
        reg.unregister_provider("sub")
        assert reg.provider_counters() == {}

    def test_raising_provider_is_isolated(self):
        reg = Registry()

        def bad():
            raise RuntimeError("boom")

        reg.register_provider("bad", bad)
        reg.register_provider("good", lambda: {"x": 1})
        out = reg.provider_counters()
        assert out["good"] == {"x": 1}
        assert "RuntimeError" in out["bad"]["error"]

    def test_non_callable_rejected(self):
        reg = Registry()
        with pytest.raises(TypeError):
            reg.register_provider("x", {"not": "callable"})


class TestEvents:
    def test_ring_is_bounded_and_counts_drops(self):
        log = obs_events.EventLog(capacity=8)
        for i in range(20):
            log.instant(f"e{i}")
        evs = log.events()
        assert len(evs) == 8
        assert evs[0].name == "e12"        # oldest 12 fell off
        assert log.dropped == 12

    def test_chrome_trace_valid_json_monotonic_ts(self, tmp_path):
        log = obs_events.EventLog()
        log.begin("outer", cat="test", k=1)
        log.instant("mark", cat="test")
        log.end("outer", cat="test")
        path = tmp_path / "trace.json"
        text = log.export_chrome_trace(file=str(path))
        with open(path) as f:
            doc = json.load(f)             # must be loadable by json.load
        assert json.loads(text) == doc
        evs = doc["traceEvents"]
        assert len(evs) == 3
        ts = [e["ts"] for e in evs]
        assert ts == sorted(ts)            # monotonically ordered
        assert {e["ph"] for e in evs} == {"B", "i", "E"}
        assert all("pid" in e and "tid" in e for e in evs)
        assert evs[0]["args"] == {"k": 1}

    def test_filtering(self):
        log = obs_events.EventLog()
        log.instant("a", cat="x")
        log.instant("b", cat="y")
        assert [e.name for e in log.events(cat="x")] == ["a"]
        assert [e.name for e in log.events(name="b")] == ["b"]


class TestSpan:
    def test_nesting_and_totals(self):
        reg_before = obs_metrics.value("span.seconds", name="outer-span")
        n_before = reg_before["count"] if reg_before else 0
        assert current_span() is None
        with span("outer-span", cat="test"):
            assert current_span() == "outer-span"
            d = span_depth()
            with span("inner-span", cat="test"):
                assert current_span() == "inner-span"
                assert span_depth() == d + 1
            assert current_span() == "outer-span"
        assert current_span() is None
        st = obs_metrics.value("span.seconds", name="outer-span")
        assert st["count"] == n_before + 1
        # ONE record a span, written when it ends, with its cause
        inner = obs_events.events(name="inner-span")
        assert [e.phase for e in inner] == [obs_events.COMPLETE]
        assert inner[-1].cause == "outer-span"
        assert obs_events.events(name="outer-span")[-1].cause is None

    def test_elapsed_and_error_annotation(self):
        s = span("failing-span", cat="test")
        with pytest.raises(ValueError):
            with s:
                raise ValueError("x")
        assert s.elapsed is not None and s.elapsed >= 0
        rec = obs_events.events(name="failing-span")[-1]
        assert rec.args["error"] == "ValueError"
        assert rec.dur_ns >= 0 and rec.start_ns > 0

    def test_args_reach_the_record_only(self):
        with span("arg-span", cat="test", path="/tmp/x") as sp:
            sp.args["n"] = 3             # until the span ends
        st = obs_metrics.value("span.seconds", name="arg-span")
        assert st["count"] >= 1            # the family: by name only
        assert obs_metrics.default_registry().get(
            "span.seconds").label_sets().count((("name", "arg-span"),)) == 1
        rec = obs_events.events(name="arg-span")[-1]
        assert rec.args == {"path": "/tmp/x", "n": 3}


class TestJitInstrumentation:
    def test_compile_counter_invariant(self):
        """Two calls with identical avals = one compile + one cache hit;
        a new input signature = a second compile, not a hit."""
        import paddle_tpu.jit as jit

        @jit.to_static
        def obs_fn(x):
            return x * 2 + 1

        def vals():
            c = obs.value("jit.compile_count", fn="obs_fn") or 0
            h = obs.value("jit.cache_hit", fn="obs_fn") or 0
            return c, h

        c0, h0 = vals()
        a = paddle.to_tensor(np.ones((2, 3), np.float32))
        obs_fn(a)
        obs_fn(paddle.to_tensor(np.zeros((2, 3), np.float32)))
        c1, h1 = vals()
        assert c1 == c0 + 1
        assert h1 == h0 + 1
        obs_fn(paddle.to_tensor(np.ones((4, 3), np.float32)))
        c2, h2 = vals()
        assert c2 == c0 + 2
        assert h2 == h0 + 1
        # compile begin/end pairs match the compile count
        begins = [e for e in obs_events.events(name="jit.compile")
                  if e.phase == obs_events.BEGIN
                  and e.args.get("fn") == "obs_fn"]
        ends = [e for e in obs_events.events(name="jit.compile")
                if e.phase == obs_events.END
                and e.args.get("fn") == "obs_fn"]
        assert len(begins) == len(ends) == 2
        assert all(e.args["seconds"] >= 0 for e in ends)
        # the miss also explains itself on the timeline
        causes = [e.args["cause"] for e in
                  obs_events.events(name="jit.retrace")
                  if e.args.get("fn") == "obs_fn"]
        assert causes == ["first_call", "new_input_signature"]
        st = obs.value("jit.compile_seconds", fn="obs_fn")
        assert st["count"] >= 2


class TestProfilerSatellites:
    def test_make_scheduler_tuple_records_once(self):
        """(start, end) = record [start, end) ONCE — regression for the
        repeat=0 form that cycled the window forever."""
        from paddle_tpu.profiler import Profiler, ProfilerState

        p = Profiler(scheduler=(2, 5), timer_only=True)
        states = [p._scheduler(i) for i in range(12)]
        assert states[:2] == [ProfilerState.CLOSED] * 2
        assert states[2:4] == [ProfilerState.RECORD] * 2
        assert states[4] == ProfilerState.RECORD_AND_RETURN
        # the old bug: step 7 re-entered RECORD; now closed forever
        assert states[5:] == [ProfilerState.CLOSED] * 7

    def test_summary_single_step(self):
        from paddle_tpu.profiler import Profiler

        p = Profiler(timer_only=True)
        p.start()
        p.step()
        text = p.summary()
        assert "steps: 1" in text
        assert "p50" in text and "p99" in text

    def test_summary_includes_observability_histograms(self):
        from paddle_tpu.profiler import Profiler

        obs_metrics.histogram("test.profiler_summary").observe(0.25)
        p = Profiler(timer_only=True)
        p.start()
        p.step()
        p.step()
        assert "test.profiler_summary" in p.summary()

    def test_facade_register_and_counters(self):
        profiler.register_counter_provider("facade.test",
                                           lambda: {"v": 7})
        try:
            assert profiler.counters()["facade.test"] == {"v": 7}
            # one registry: visible through observability too
            assert obs_metrics.provider_counters()["facade.test"] == \
                {"v": 7}
            assert obs.snapshot()["providers"]["facade.test"] == {"v": 7}
        finally:
            profiler.unregister_counter_provider("facade.test")
        assert "facade.test" not in profiler.counters()


class TestEngineProviderLifecycle:
    """Repeated engine construction must not leak stale providers
    (regression: bound-method providers pinned engines forever)."""

    def _tiny_engine(self, register=True):
        from paddle_tpu.models import GPTConfig, GPTForCausalLM
        from paddle_tpu.serving import Engine, EngineConfig

        cfg = GPTConfig(vocab_size=64, hidden_size=32,
                        intermediate_size=64, num_hidden_layers=1,
                        num_attention_heads=2,
                        max_position_embeddings=32)
        paddle.seed(0)
        m = GPTForCausalLM(cfg)
        m.eval()
        return Engine(m, EngineConfig(num_slots=1, max_seq_len=16),
                      register_profiler=register)

    def test_close_unregisters_provider(self):
        eng = self._tiny_engine()
        name = eng._profiler_name
        assert name in profiler.counters()
        eng.close()
        assert name not in profiler.counters()

    def test_gc_unregisters_provider(self):
        eng = self._tiny_engine()
        name = eng._profiler_name
        assert name in profiler.counters()
        del eng
        gc.collect()
        assert name not in profiler.counters()

    def test_live_engine_counters_unchanged_via_facade(self):
        eng = self._tiny_engine()
        try:
            via_facade = profiler.counters()[eng._profiler_name]
            assert via_facade == eng.counters()
        finally:
            eng.close()


class TestCLI:
    def test_snapshot_smoke(self, tmp_path):
        script = tmp_path / "load.py"
        script.write_text(
            "from paddle_tpu.observability import metrics, events\n"
            "metrics.counter('cli.test').inc(3)\n"
            "events.instant('cli.mark')\n")
        out = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.observability",
             "snapshot", "--exec", str(script)],
            capture_output=True, text=True, timeout=120,
            env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
        assert out.returncode == 0, out.stderr
        snap = json.loads(out.stdout)
        assert snap["metrics"]["cli.test"]["values"][""] == 3

    def test_trace_and_prometheus_modes(self, tmp_path):
        script = tmp_path / "load.py"
        script.write_text(
            "from paddle_tpu.observability import metrics, events\n"
            "metrics.histogram('cli.h').observe(0.1)\n"
            "events.instant('cli.mark')\n")
        env = {**__import__("os").environ, "JAX_PLATFORMS": "cpu"}
        trace_file = tmp_path / "t.json"
        out = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.observability", "trace",
             "--exec", str(script), "-o", str(trace_file)],
            capture_output=True, text=True, timeout=120, env=env)
        assert out.returncode == 0, out.stderr
        with open(trace_file) as f:
            doc = json.load(f)
        assert any(e["name"] == "cli.mark" for e in doc["traceEvents"])
        out = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.observability",
             "prometheus", "--exec", str(script)],
            capture_output=True, text=True, timeout=120, env=env)
        assert out.returncode == 0, out.stderr
        assert "# TYPE cli_h histogram" in out.stdout

    def test_modes_are_dumps_and_serve(self, capsys):
        """The CLI dumps live state or serves it, and nothing else: it
        gates no timing and runs no simulator."""
        from paddle_tpu.observability.__main__ import main

        with pytest.raises(SystemExit) as exc:
            main(["--help"])
        assert exc.value.code == 0
        assert ("{snapshot,prometheus,trace,programs,mesh,serve}"
                in capsys.readouterr().out)
        with pytest.raises(SystemExit) as exc:
            main(["fleet"])
        assert exc.value.code == 2


class TestFlightRecorder:
    """RequestTrace flight records + bounded FlightRecorder retention."""

    @staticmethod
    def _finished_trace(rid, tokens=3):
        from paddle_tpu.observability import tracing

        tr = tracing.RequestTrace(rid, engine="e0")
        tr.add(tracing.QUEUED, prompt_len=4)
        tr.add(tracing.PREFILL, slot=0, prefill_tokens=4,
               prefix_hit_tokens=2)
        tr.add(tracing.FIRST_TOKEN, token=7)
        tr.add(tracing.DECODE, horizon=4, tokens=tokens - 1, accepted=1)
        tr.add(tracing.FINISH, reason="eos", n_generated=tokens)
        return tr

    def test_counts_reconstruct_lifecycle(self):
        from paddle_tpu.observability import tracing

        tr = tracing.RequestTrace(5)
        tr.add(tracing.QUEUED)
        tr.add(tracing.PREFILL, prefix_hit_tokens=4)
        tr.add(tracing.FIRST_TOKEN, token=1)
        tr.add(tracing.DECODE, tokens=3, accepted=2, horizon=4)
        tr.add(tracing.PREEMPT)
        tr.add(tracing.SWAP_OUT, blocks=2, bytes=4096, n_tokens=8)
        tr.add(tracing.SWAP_IN, blocks=2, bytes=4096,
               averted_tokens=6, source="lane")
        tr.add(tracing.RESUME, prefix_hit_tokens=6)
        tr.add(tracing.DECODE, tokens=2, accepted=0, horizon=2)
        tr.add(tracing.FAILOVER, from_replica="r0", resumed_tokens=6)
        tr.add(tracing.FINISH, reason="length")
        c = tr.counts()
        # resumed tokens are NOT tokens_emitted: per-engine trace sums
        # must still reconcile against engine counters exactly
        assert c == {"tokens_emitted": 6, "prefix_hit_tokens": 6,
                     "preemptions": 1, "decode_horizons": 2,
                     "spec_accepted_tokens": 2, "spec_forced_tokens": 0,
                     "aborted": 0, "failovers": 1, "resumed_tokens": 6,
                     "swap_ins": 1, "swap_outs": 1,
                     "swap_in_bytes": 4096, "swap_out_bytes": 4096,
                     "flops_est": 0.0, "bytes_est": 0.0}
        assert tr.finished
        # monotonic event times
        ts = [t for _, t, _ in tr.events]
        assert ts == sorted(ts) and all(t >= 0 for t in ts)

    def test_bounded_retention_drops_oldest_finished(self):
        from paddle_tpu.observability import tracing

        rec = tracing.FlightRecorder(capacity=3)
        for i in range(10):
            tr = self._finished_trace(i)
            rec.attach(tr)
            rec.finish(tr)
        assert [t.request_id for t in rec.recent()] == [7, 8, 9]
        assert rec.dropped == 7
        assert rec.to_json()["finished_total"] == 10
        assert rec.get(9) is not None and rec.get(0) is None

    def test_live_traces_are_pinned(self):
        from paddle_tpu.observability import tracing

        rec = tracing.FlightRecorder(capacity=2)
        live = tracing.RequestTrace(100)
        live.add(tracing.QUEUED)
        rec.attach(live)
        for i in range(8):          # churn far past capacity
            tr = self._finished_trace(i)
            rec.attach(tr)
            rec.finish(tr)
        assert rec.get(100) is live          # still reachable
        assert [t.request_id for t in rec.live()] == [100]
        doc = rec.to_json()
        assert doc["live_count"] == 1
        assert doc["finished_retained"] == 2
        assert not doc["live"][0]["finished"]
        json.dumps(doc)                      # fully JSON-able

    def test_chrome_async_span_export(self):
        from paddle_tpu.observability import tracing

        rec = tracing.FlightRecorder()
        tr = self._finished_trace(42)
        rec.attach(tr)
        rec.finish(tr)
        doc = json.loads(rec.export_chrome_trace())
        evs = [e for e in doc["traceEvents"] if e["id"] == "42"]
        phases = [e["ph"] for e in evs]
        assert phases[0] == "b" and phases[-1] == "e"
        assert phases.count("n") == 5        # one per lifecycle event
        ts = [e["ts"] for e in doc["traceEvents"]]
        assert ts == sorted(ts)
        # mergeable into the process event ring export
        merged = json.loads(obs_events.EventLog().export_chrome_trace(
            extra=rec.chrome_events()))
        assert len(merged["traceEvents"]) == 7


class TestSLO:
    """Deterministic step-window burn-rate math (no clocks)."""

    def _tracker(self, **kw):
        from paddle_tpu.observability.slo import SLOTracker

        reg = Registry()
        t = SLOTracker("t", registry=reg)
        kw.setdefault("target", 0.9)
        kw.setdefault("fast_window", 4)
        kw.setdefault("slow_window", 8)
        t.declare("ttft", 0.5, **kw)
        return t, reg

    def test_empty_window_is_compliant(self):
        t, _ = self._tracker()
        obj = t.objective("ttft")
        assert obj.compliance("fast") == 1.0
        assert obj.burn_rate("slow") == 0.0
        assert t.healthy

    def test_window_math_exact(self):
        t, _ = self._tracker()
        obj = t.objective("ttft")
        for v in (0.1, 0.1, 2.0, 0.1):       # 1 breach in 4
            t.observe("ttft", v)
        assert obj.compliance("fast") == pytest.approx(0.75)
        # burn = (1 - 0.75) / (1 - 0.9) = 2.5x budget
        assert obj.burn_rate("fast") == pytest.approx(2.5)
        assert obj.compliance("slow") == pytest.approx(0.75)

    def test_multiwindow_and_breach_and_recovery(self):
        t, reg = self._tracker()
        obj = t.objective("ttft")
        # one bad observation: fast window burns, slow doesn't -> healthy
        for _ in range(7):
            t.observe("ttft", 0.1)
        t.observe("ttft", 2.0)
        assert obj.burn_rate("fast") > 1.0
        assert obj.burn_rate("slow") > 1.0  # 1/8 breach > 10% budget
        # sustained outage: both windows burn -> unhealthy
        for _ in range(8):
            t.observe("ttft", 2.0)
        assert not obj.healthy and not t.healthy
        assert reg.value("slo.healthy", tracker="t") == 0
        assert reg.value("slo.burn_rate", tracker="t", objective="ttft",
                         window="fast") == pytest.approx(10.0)
        # recovery: the fast window forgives as soon as it refills
        for _ in range(4):
            t.observe("ttft", 0.1)
        assert obj.burn_rate("fast") == 0.0
        assert obj.healthy and t.healthy
        assert reg.value("slo.healthy", tracker="t") == 1
        assert reg.value("slo.compliance", tracker="t", objective="ttft",
                         window="fast") == 1

    def test_unknown_objective_ignored(self):
        t, _ = self._tracker()
        t.observe("nope", 1.0)               # must not raise
        assert t.healthy

    def test_invalid_declarations_rejected(self):
        from paddle_tpu.observability.slo import Objective

        with pytest.raises(ValueError):
            Objective("x", 1.0, target=1.0)
        with pytest.raises(ValueError):
            Objective("x", 1.0, fast_window=8, slow_window=4)


class TestExpositionConformance:
    """validate_exposition: the renderer's output parses, and the
    validator actually rejects malformed documents."""

    def test_renderer_output_parses(self):
        from paddle_tpu.observability.metrics import validate_exposition

        reg = Registry()
        reg.counter("c.plain", "simple").inc(3)
        g = reg.gauge("g.hard", 'help with "quotes", \\slash\nnewline')
        g.set(1.5, path='va"l\\ue', msg="line\nbreak")
        g.set(float("inf"), k="inf")
        g.set(float("nan"), k="nan")
        h = reg.histogram("h.lat", "lat", buckets=(0.1, 1.0))
        h.observe(0.5, op="a")
        reg.register_provider("sub.sys", lambda: {"n": 2})
        n = validate_exposition(reg.render_prometheus())
        assert n >= 9       # every emitted sample line parsed
        text = reg.render_prometheus()
        assert "NaN" in text and "+Inf" in text
        assert "\\n" in text          # newlines escaped, never raw

    def test_default_registry_conforms(self):
        from paddle_tpu.observability.metrics import validate_exposition

        with span("expo-conform", cat="test"):
            pass
        assert validate_exposition(obs.render_prometheus()) > 0

    @pytest.mark.parametrize("doc", [
        "9bad_name 1\n",                       # name starts with digit
        'm{l="unterminated} 1\n',              # unbalanced quote
        'm{l="x"} notanumber\n',               # bad value
        'm{l="x"}\n',                          # missing value
        'm{bad-label="x"} 1\n',                # bad label name
        "# TYPE m wrongtype\nm 1\n",           # unknown type
        "m 1\nm 1\n",                          # duplicate sample
        "# TYPE h histogram\nh_bucket 1\n",    # bucket without le
    ])
    def test_rejects_malformed(self, doc):
        from paddle_tpu.observability.metrics import validate_exposition

        with pytest.raises(ValueError):
            validate_exposition(doc)


class TestSpanErrorPath:
    """Regression: the span's totals must be kept on the exception path
    too, even if the event ring itself raises."""

    def test_error_counted_under_the_name(self):
        st0 = obs_metrics.value("span.seconds", name="err-span")
        n0 = st0["count"] if st0 else 0
        with pytest.raises(RuntimeError):
            with span("err-span", cat="test"):
                raise RuntimeError("boom")
        with span("err-span", cat="test"):
            pass
        # two numbers a name, no label series beside it: the record says
        # which of the two raised
        assert obs_metrics.value("span.seconds",
                                 name="err-span")["count"] == n0 + 2
        assert obs_metrics.value("span.seconds", name="err-span",
                                 error="1") is None
        errs = [e.args.get("error")
                for e in obs_events.events(name="err-span")[-2:]]
        assert errs == ["RuntimeError", None]

    def test_totals_kept_even_if_event_ring_raises(self, monkeypatch):
        import importlib

        span_mod = importlib.import_module(
            "paddle_tpu.observability.span")

        def boom(*a, **k):
            raise RuntimeError("sink down")

        st0 = obs_metrics.value("span.seconds", name="sink-span")
        n0 = st0["count"] if st0 else 0
        s = span_mod.span("sink-span", cat="test")
        s.__enter__()
        monkeypatch.setattr(obs_events.default_log(), "append", boom)
        with pytest.raises(RuntimeError):
            s.__exit__(None, None, None)
        st = obs_metrics.value("span.seconds", name="sink-span")
        assert st["count"] == n0 + 1       # counted despite the raise
        assert s.elapsed is not None
        assert current_span() is None


class TestChromeTraceMetadata:
    def test_header_has_process_identity_and_drops(self):
        log = obs_events.EventLog(capacity=2)
        for i in range(5):
            log.instant(f"e{i}")
        doc = json.loads(log.export_chrome_trace())
        meta = doc["metadata"]
        assert meta["dropped_events"] == 3
        assert meta["process_name"].startswith("python:")
        assert meta["git_sha"]          # short sha or "unknown"


class TestTelemetryEndpoint:
    """Scrape a LIVE engine's telemetry endpoint."""

    def _engine(self, **extra):
        from paddle_tpu.models import GPTConfig, GPTForCausalLM
        from paddle_tpu.serving import Engine, EngineConfig

        cfg = GPTConfig(vocab_size=64, hidden_size=32,
                        intermediate_size=64, num_hidden_layers=1,
                        num_attention_heads=2,
                        max_position_embeddings=32)
        paddle.seed(0)
        m = GPTForCausalLM(cfg)
        m.eval()
        kw = dict(num_slots=1, max_seq_len=16, telemetry_port=0,
                  slo_ttft_s=60.0, slo_target=0.9,
                  slo_fast_window=4, slo_slow_window=8)
        kw.update(extra)
        return Engine(m, EngineConfig(**kw), register_profiler=False)

    @staticmethod
    def _get(url):
        import urllib.error
        import urllib.request

        try:
            with urllib.request.urlopen(url, timeout=10) as r:
                return r.status, r.read().decode()
        except urllib.error.HTTPError as e:
            return e.code, e.read().decode()

    @pytest.mark.slow
    def test_scrape_running_engine(self):
        from paddle_tpu.observability.metrics import validate_exposition
        from paddle_tpu.serving import SamplingParams

        eng = self._engine()
        try:
            eng.generate([3, 1, 4], SamplingParams(max_new_tokens=4))
            assert eng.telemetry.port > 0
            code, body = self._get(eng.telemetry.url("/metrics"))
            assert code == 200
            assert validate_exposition(body) > 0
            assert "serving_kv_pool_occupancy_ratio" in body
            assert "serving_decode_bucket_count" in body
            assert "slo_burn_rate" in body
            code, body = self._get(eng.telemetry.url("/healthz"))
            assert (code, body) == (200, "ok\n")
            code, body = self._get(eng.telemetry.url("/debug/requests"))
            assert code == 200
            doc = json.loads(body)
            assert doc["finished_total"] == 1
            rec = doc["recent"][0]
            kinds = [e["kind"] for e in rec["events"]]
            assert kinds[0] == "queued" and kinds[-1] == "finish"
            assert rec["counts"]["tokens_emitted"] == 4
            code, body = self._get(eng.telemetry.url("/trace"))
            assert code == 200
            trace = json.loads(body)
            assert any(e.get("cat") == "serving.request"
                       for e in trace["traceEvents"])
            assert self._get(eng.telemetry.url("/nope"))[0] == 404
        finally:
            url = eng.telemetry.url("/healthz")
            eng.close()
        # clean shutdown: the port no longer answers
        assert not eng.telemetry or not eng.telemetry.running
        with pytest.raises(Exception):
            self._get(url)

    @pytest.mark.slow
    def test_readyz_flips_on_ttft_breach_and_recovers(self):
        eng = self._engine()
        try:
            code, body = self._get(eng.telemetry.url("/readyz"))
            assert code == 200 and json.loads(body)["ready"]
            # injected sustained TTFT breach fills both windows
            for _ in range(8):
                eng.slo.observe("ttft", 120.0)
            code, body = self._get(eng.telemetry.url("/readyz"))
            assert code == 503
            doc = json.loads(body)
            assert not doc["ready"]
            burn = doc["slo"]["objectives"]["ttft"]["fast"]["burn_rate"]
            assert burn > 1.0
            # the burn-rate gauge is visible in the same scrape
            _, metrics_body = self._get(eng.telemetry.url("/metrics"))
            assert 'slo_burn_rate{' in metrics_body
            # recovery: fast window refills with good observations
            for _ in range(4):
                eng.slo.observe("ttft", 0.01)
            code, body = self._get(eng.telemetry.url("/readyz"))
            assert code == 200 and json.loads(body)["ready"]
        finally:
            eng.close()


class TestProgramCards:
    """Phase 3 program cards: capture from a real Lowered, process-wide
    memoization, renderers, and NaN exposition for backends without an
    analysis."""

    def _capture_tiny(self, fn_name="test.prog", key="k0", **kw):
        import jax
        import jax.numpy as jnp

        from paddle_tpu.observability import profiling

        f = jax.jit(lambda x: (x * 2.0).sum())
        lowered = f.lower(jnp.ones((8, 8), jnp.float32))
        return profiling.capture(fn_name, key, lowered,
                                 compile_seconds=0.012,
                                 donated_bytes=256,
                                 meta={"bucket": 8}, backend="cpu", **kw)

    def test_capture_from_lowered(self):
        from paddle_tpu.observability import profiling

        reg = profiling.ProgramCardRegistry()
        card = self._capture_tiny(registry=reg)
        assert card.flops and card.flops > 0
        assert card.bytes_accessed and card.bytes_accessed > 0
        assert card.analysis_source in ("lowered", "compiled")
        assert card.compile_seconds == pytest.approx(0.012)
        assert card.donated_bytes == 256
        assert card.meta == {"bucket": 8}
        # gauges published per (fn, key)
        assert obs_metrics.value("compile.program_flops",
                                 fn="test.prog", key="k0") == card.flops
        assert obs_metrics.value("compile.programs",
                                 fn="test.prog") == 1
        # memoization handle: the registry serves the same card back
        assert reg.get("test.prog", "k0") is card
        assert reg.get("test.prog", "other") is None

    def test_registry_json_totals_and_render(self):
        from paddle_tpu.observability import profiling

        reg = profiling.ProgramCardRegistry()
        card = self._capture_tiny(registry=reg)
        card.dispatches = 3
        doc = reg.to_json()
        assert doc["count"] == 1
        assert doc["total_flops_dispatched"] == pytest.approx(
            card.flops * 3)
        assert doc["total_bytes_dispatched"] == pytest.approx(
            card.bytes_accessed * 3)
        json.dumps(doc)                       # JSON-able as-is
        text = reg.render_text()
        assert "test.prog" in text and "bucket=8" in text
        assert profiling.ProgramCardRegistry().render_text().startswith(
            "no program cards")

    def test_capture_never_raises_and_records_nones(self):
        """A backend without any analysis still yields a card; its
        gauges render as NaN, and the exposition stays parseable."""
        from paddle_tpu.observability import profiling
        from paddle_tpu.observability.metrics import validate_exposition

        class _DeadLowered:
            def cost_analysis(self):
                raise NotImplementedError("no analysis on this backend")

            def compile(self):
                raise NotImplementedError

        reg = profiling.ProgramCardRegistry()
        card = profiling.capture("test.dead", "kx", _DeadLowered(),
                                 compile_seconds=0.5, backend="cpu",
                                 registry=reg)
        assert card.flops is None and card.bytes_accessed is None
        assert card.analysis_source is None
        v = obs_metrics.value("compile.program_flops",
                              fn="test.dead", key="kx")
        assert v != v                          # NaN
        text = obs_metrics.render_prometheus()
        assert validate_exposition(text) > 0
        assert "compile_program_flops" in text and "NaN" in text

    def test_deep_probe_fills_memory_stats(self):
        """deep=True reads the executable's memory_analysis (where the
        backend provides one) — argument bytes at minimum."""
        card = self._capture_tiny(fn_name="test.deep", key="kd",
                                  deep=True)
        # cpu's memory_analysis may legitimately be absent; when it is
        # present the fields must be ints, and to_json carries them
        doc = card.to_json()
        for f in ("argument_bytes", "output_bytes", "temp_bytes"):
            assert doc[f] is None or isinstance(doc[f], int)


class TestMemoryLedger:
    """Phase 3 device-memory ledger: component accounting, leak-delta
    baseline, gauge publication, and the roofline helpers."""

    def test_account_and_raising_component(self):
        from paddle_tpu.observability.memory import MemoryLedger

        led = MemoryLedger("t")
        led.register("a", lambda: 100).register("b", lambda: 28)

        def boom():
            raise RuntimeError("accounting down")

        led.register("bad", boom)
        assert led.account() == {"a": 100, "b": 28, "bad": 0}
        led.unregister("bad")
        assert sorted(led.components()) == ["a", "b"]
        with pytest.raises(TypeError):
            led.register("notfn", 42)

    def test_snapshot_reconciles_and_publishes(self):
        from paddle_tpu.observability.memory import MemoryLedger

        led = MemoryLedger("snap-test")
        led.register("kv", lambda: 64)
        snap = led.snapshot()
        assert snap["accounted_total_bytes"] == 64
        assert snap["live_bytes"] >= 0
        assert snap["unaccounted_bytes"] == snap["live_bytes"] - 64
        # first snapshot self-baselines -> zero leak
        assert snap["leak_delta_bytes"] == 0
        assert obs_metrics.value("memory.accounted_bytes",
                                 ledger="snap-test", component="kv") == 64
        assert obs_metrics.value(
            "memory.accounted_total_bytes", ledger="snap-test") == 64
        # the memory.* gauges render as a parseable exposition
        from paddle_tpu.observability.metrics import validate_exposition

        text = obs_metrics.render_prometheus()
        assert validate_exposition(text) > 0
        for name in ("memory_accounted_bytes", "memory_live_bytes",
                     "memory_unaccounted_bytes",
                     "memory_leak_delta_bytes"):
            assert name in text
        # ...and survive snapshot() too (NaN-bearing registries broke
        # this once: int(NaN) in _as_scalar)
        json.dumps(obs_metrics.snapshot())

    def test_leak_delta_tracks_unaccounted_growth(self, monkeypatch):
        from paddle_tpu.observability import memory as mem

        led = mem.MemoryLedger("leak-test")
        led.register("pool", lambda: 1000)
        live = {"v": 1500}
        monkeypatch.setattr(mem, "live_device_bytes",
                            lambda: live["v"])
        assert led.snapshot()["leak_delta_bytes"] == 0
        # pool growth alone is NOT a leak: accounted grows with live
        led.unregister("pool")
        led.register("pool", lambda: 1400)
        live["v"] = 1900
        assert led.snapshot()["leak_delta_bytes"] == 0
        # unaccounted residue growth IS
        live["v"] = 2100
        assert led.snapshot()["leak_delta_bytes"] == 200
        # re-anchoring forgives the residue
        led.mark_baseline()
        assert led.snapshot()["leak_delta_bytes"] == 0

    def test_backend_bandwidth_lookup(self):
        from paddle_tpu.observability import memory as mem

        v5e = "TPU v5 lite"                   # jax's device_kind
        assert mem.backend_bandwidth_gbs(v5e) == 819.0   # datasheet entry
        # a backend name is not a chip: no row, no default
        with pytest.raises(ValueError, match="no published peaks"):
            mem.backend_bandwidth_gbs("tpu")
        # the per-dispatch roofline gauge is gone with its families
        assert not hasattr(mem, "publish_roofline")
        assert obs_metrics.default_registry().get(
            "memory.roofline_utilization") is None

    def test_bandwidth_probe_memoized(self):
        from paddle_tpu.observability import memory as mem

        a = mem.backend_bandwidth_gbs("cpu")
        b = mem.backend_bandwidth_gbs("cpu")
        assert a == b and a > 0               # one probe per process


class TestProgramsEndpointAndCLI:
    """/debug/programs routing + the programs CLI mode."""

    def test_debug_programs_route(self):
        import jax
        import jax.numpy as jnp

        from paddle_tpu.observability import profiling
        from paddle_tpu.observability.server import TelemetryServer

        f = jax.jit(lambda x: x + 1)
        lowered = f.lower(jnp.ones((4,), jnp.float32))
        profiling.capture("test.route", "rk", lowered, backend="cpu")
        try:
            srv = TelemetryServer(port=0)
            status, ctype, body = srv.handle("/debug/programs")
            assert status == 200 and ctype == "application/json"
            doc = json.loads(body)
            assert doc["count"] >= 1
            assert any(c["fn"] == "test.route" for c in doc["cards"])
            # the index advertises the route
            _, _, idx = srv.handle("/")
            assert "/debug/programs" in json.loads(idx)["endpoints"]
        finally:
            profiling.clear()

    @pytest.mark.slow
    def test_programs_cli_mode(self, tmp_path):
        script = tmp_path / "load.py"
        script.write_text(
            "import jax, jax.numpy as jnp\n"
            "from paddle_tpu.observability import profiling\n"
            "f = jax.jit(lambda x: x * 3.0)\n"
            "low = f.lower(jnp.ones((8,), jnp.float32))\n"
            "profiling.capture('cli.prog', 'ck', low,\n"
            "                  compile_seconds=0.02, backend='cpu',\n"
            "                  meta={'bucket': 8})\n")
        env = {**__import__("os").environ, "JAX_PLATFORMS": "cpu"}
        out = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.observability",
             "programs", "--exec", str(script)],
            capture_output=True, text=True, timeout=120, env=env)
        assert out.returncode == 0, out.stderr
        assert "cli.prog" in out.stdout and "bucket=8" in out.stdout
        out = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.observability",
             "programs", "--exec", str(script), "--json"],
            capture_output=True, text=True, timeout=120, env=env)
        assert out.returncode == 0, out.stderr
        doc = json.loads(out.stdout)
        assert doc["cards"][0]["fn"] == "cli.prog"
        assert doc["cards"][0]["flops"] > 0


class TestTelemetryServerLifecycle:
    """Satellite: the server's own provider registers on start(),
    unregisters on stop()/GC, and the serving thread is joined."""

    def test_provider_registered_while_running(self):
        from paddle_tpu.observability.server import TelemetryServer

        reg = Registry()
        srv = TelemetryServer(port=0, registry=reg)
        assert reg.provider_counters() == {}
        srv.start()
        name = srv._provider_name
        try:
            assert name.startswith("telemetry.server")
            provided = reg.provider_counters()[name]
            assert provided == {"up": 1, "port": srv.port}
        finally:
            srv.stop()
        assert name not in reg.provider_counters()
        assert not srv.running and srv._thread is None

    def test_stop_joins_thread_and_is_idempotent(self):
        import urllib.request

        from paddle_tpu.observability.server import TelemetryServer

        srv = TelemetryServer(port=0, registry=Registry())
        srv.start()
        thread = srv._thread
        url = srv.url("/healthz")
        with urllib.request.urlopen(url, timeout=10) as r:
            assert r.status == 200
        srv.stop()
        assert not thread.is_alive()
        srv.stop()                            # idempotent
        with pytest.raises(Exception):
            urllib.request.urlopen(url, timeout=2)

    def test_gc_unregisters_provider(self):
        from paddle_tpu.observability.server import TelemetryServer

        reg = Registry()
        srv = TelemetryServer(port=0, registry=reg)
        srv.start()
        name = srv._provider_name
        assert name in reg.provider_counters()
        del srv
        gc.collect()
        assert name not in reg.provider_counters()

    def test_repeated_cycles_leave_no_stale_providers(self):
        from paddle_tpu.observability.server import TelemetryServer

        reg = Registry()
        for _ in range(3):
            srv = TelemetryServer(port=0, registry=reg)
            srv.start()
            assert len([n for n in reg.provider_counters()
                        if n.startswith("telemetry.server")]) == 1
            srv.stop()
        assert not [n for n in reg.provider_counters()
                    if n.startswith("telemetry.server")]
