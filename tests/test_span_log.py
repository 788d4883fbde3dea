"""The span log (observability/span.py + the events ring) and the spans the
serving worker loop, the gateway's delivery path, the program builds and
the train step write into it.  Counts and order only: nothing here asserts
a time."""

import http.client
import importlib
import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.observability import events as obs_events
from paddle_tpu.observability import metrics as obs_metrics
from paddle_tpu.observability.metrics import validate_exposition
from paddle_tpu.serving import Engine, EngineConfig
from paddle_tpu.serving.engine import CompiledFn
from paddle_tpu.serving.gateway import Gateway, GatewayConfig

# `observability.span` the attribute is the class; this is the module
span_log = importlib.import_module("paddle_tpu.observability.span")
span = span_log.span

TINY = GPTConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                 num_hidden_layers=2, num_attention_heads=4,
                 max_position_embeddings=64)


def _records(name):
    return span_log.records(name)


# ------------------------------------------------------------------ the log
class TestRecord:
    def test_one_record_a_span_with_its_cause(self):
        n0 = len(_records("log.a")) + len(_records("log.b"))
        with span("log.a", rid=7, lanes=2):
            with span("log.b"):
                pass
        a, b = _records("log.a")[-1], _records("log.b")[-1]
        assert len(_records("log.a")) + len(_records("log.b")) == n0 + 2
        assert (a.cause, b.cause) == (None, "log.a")
        assert a.id == 7 and a.args == {"lanes": 2}
        assert a.tid == b.tid == threading.get_ident()
        # the inner span lies inside the outer one, on one clock
        assert a.start_ns <= b.start_ns
        assert b.start_ns + b.dur_ns <= a.start_ns + a.dur_ns
        assert isinstance(a.start_ns, int) and isinstance(a.dur_ns, int)

    def test_cross_thread_span_is_written_by_whoever_ends_it(self):
        handed = {}

        def begin():
            handed["t"] = span_log.now_ns()
            handed["tid"] = threading.get_ident()

        t = threading.Thread(target=begin)
        t.start()
        t.join()
        with span("log.outer"):
            span_log.complete("log.deliver", handed["t"], rid=11, tokens=3)
        rec = _records("log.deliver")[-1]
        assert rec.start_ns == handed["t"] and rec.dur_ns >= 0
        assert rec.tid == threading.get_ident() != handed["tid"]
        assert rec.id == 11 and rec.args == {"tokens": 3}
        assert rec.cause is None         # it did not begin on this thread

    def test_ring_forgets_the_oldest_and_the_totals_do_not(self):
        log = obs_events.default_log()
        keep = log.capacity
        assert keep >= 32768
        before = (obs_metrics.value("span.seconds", name="log.many")
                  or {"count": 0})["count"]
        try:
            obs_events.set_capacity(8)
            for i in range(20):
                with span("log.many", i=i):
                    pass
            kept = [e.args["i"] for e in _records("log.many")]
            assert kept == list(range(12, 20))
            assert log.dropped >= 12
        finally:
            obs_events.set_capacity(keep)
        st = obs_metrics.value("span.seconds", name="log.many")
        assert st["count"] == before + 20 and st["sum"] >= 0

    def test_record_says_whether_a_trace_was_running(self, tmp_path):
        with span("log.traced", i=0):
            pass
        jax.profiler.start_trace(str(tmp_path))
        try:
            with span("log.traced", i=1):
                pass
            span_log.complete("log.traced", span_log.now_ns(), i=2)
        finally:
            jax.profiler.stop_trace()
        with span("log.traced", i=3):
            pass
        assert [(e.args["i"], e.traced) for e in _records("log.traced")[-4:]] \
            == [(0, False), (1, True), (2, True), (3, False)]

    def test_arguments_may_be_added_until_the_span_ends(self):
        with span("log.args", a=1) as sp:
            sp.args["b"] = 2
        sp.args["c"] = 3            # the record holds the span's own dict
        assert _records("log.args")[-1].args == {"a": 1, "b": 2, "c": 3}
        assert sp.elapsed >= 0

    def test_family_is_a_summary_in_the_exposition(self):
        with span("log.expo"):
            pass
        text = obs_metrics.render_prometheus()
        assert "# TYPE span_seconds summary" in text
        assert 'span_seconds_count{name="log.expo"}' in text
        assert 'span_seconds_sum{name="log.expo"}' in text
        assert "span_seconds_bucket" not in text
        assert validate_exposition(text) > 0

    def test_chrome_export_carries_one_complete_event_a_span(self):
        with span("log.chrome.outer"):
            with span("log.chrome.inner", rid=5):
                pass
        doc = json.loads(obs_events.export_chrome_trace())
        inner = [e for e in doc["traceEvents"]
                 if e["name"] == "log.chrome.inner"]
        assert [e["ph"] for e in inner[-1:]] == ["X"]
        assert inner[-1]["args"]["cause"] == "log.chrome.outer"
        assert inner[-1]["id"] == "5" and inner[-1]["dur"] >= 0

    def test_summary_metric(self):
        reg = obs_metrics.Registry()
        s = reg.summary("t.sum", "help")
        s.observe(0.5, op="a")
        s.observe(1.5, op="a")
        assert reg.value("t.sum", op="a") == {"count": 2, "sum": 2.0}
        assert reg.value("t.sum", op="b") is None
        assert reg.snapshot()["metrics"]["t.sum"]["values"] == {
            "op=a": {"count": 2, "sum": 2.0}}
        assert validate_exposition(reg.render_prometheus()) == 2


# ---------------------------------------------------------- the build table
class TestBuildTable:
    def test_one_record_a_miss_and_none_a_hit(self):
        def step(x, y):
            return jnp.sin(x) @ y

        fn = CompiledFn(step, name="spanlog.step",
                        meta_fn=lambda args: {"n": int(args[0].shape[0])})
        n0 = len(span_log.builds())
        a = jnp.ones((4, 4))
        fn(a, a)
        fn(a, a)                                       # a hit
        assert len(span_log.builds()) == n0 + 1
        b = jnp.ones((8, 8))
        fn(b, b)                                       # a new signature
        mine = span_log.builds()[n0:]
        assert [(r["program"], r["key"]) for r in mine] == [
            ("spanlog.step", {"n": 4}), ("spanlog.step", {"n": 8})]
        assert fn.misses == 2 and fn.hits == 1
        # the ring events keep their names
        assert obs_events.events(name="jit.retrace")
        assert {e.phase for e in obs_events.events(name="jit.compile")} \
            >= {obs_events.BEGIN, obs_events.END}

    def test_phases_are_non_negative_and_within_the_total(self):
        @jax.jit
        def inner(x):
            return x * 2.0

        def outer(x):                      # a jit traced inside a trace
            return inner(x) + jnp.cos(x)

        fn = CompiledFn(outer, name="spanlog.nested")
        fn(jnp.ones((3,)))
        rec = span_log.builds()[-1]
        phases = [rec[k] for k in ("trace_s", "lower_s", "compile_s",
                                   "cache_retrieval_s", "rest_s")]
        assert all(p >= 0 for p in phases)
        assert sum(phases) <= rec["total_s"] * (1 + 1e-9) + 1e-9
        assert rec["trace_s"] > 0 and rec["compile_s"] > 0
        assert rec["cache_hit"] in (True, False) and rec["error"] is None
        assert rec["thread"] == threading.get_ident()

    def test_a_build_collects_only_its_own_thread(self):
        seen = {}

        def other():
            with span_log.build("spanlog.idle") as b:
                pass
            seen["rec"] = span_log.builds()[-1]

        with span_log.build("spanlog.busy"):
            jax.jit(lambda x: x + 1)(jnp.ones((5,))).block_until_ready()
            t = threading.Thread(target=other)
            t.start()
            t.join()
        assert seen["rec"]["program"] == "spanlog.idle"
        assert seen["rec"]["trace_s"] == seen["rec"]["compile_s"] == 0.0
        assert span_log.builds()[-1]["program"] == "spanlog.busy"
        assert span_log.builds()[-1]["trace_s"] > 0


# ------------------------------------------------------------ the train step
class TestTrainerSpans:
    def test_train_step_enqueue_and_first_build(self):
        paddle.seed(0)
        model = nn.Linear(4, 1)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=model.parameters())
        step = paddle.jit.TrainStep(
            model, lambda m, x, y: nn.functional.mse_loss(m(x), y), opt)
        x = paddle.to_tensor(np.ones((2, 4), np.float32))
        y = paddle.to_tensor(np.zeros((2, 1), np.float32))
        n0, b0 = len(_records("train.step.enqueue")), len(span_log.builds())
        for _ in range(3):
            step(x, y)
        recs = _records("train.step.enqueue")[n0:]
        assert [r.args["step"] for r in recs] == [0, 1, 2]
        built = span_log.builds()[b0:]
        assert [r["program"] for r in built] == ["train_step"]
        assert built[0]["key"] == {"batch": [[2, 4], [2, 1]]}


# ------------------------------------ a tiny engine behind the gateway, once
ENGINE_SPANS = {
    "worker.inbox": {"commands"},
    "worker.idle": set(),
    "worker.flush": {"handles", "tokens", "in_flight"},
    "engine.admit": {"requests"},
    "engine.prefill.build": {"bucket", "lanes"},
    "engine.prefill.enqueue": {"bucket", "lanes", "requests"},
    "engine.prefill.wait": set(),
    "engine.prefill.harvest": set(),
    "engine.decode.prepare": set(),     # twice a step, see below
    "engine.decode.enqueue": {"horizon", "width", "k", "lanes"},
    "engine.decode.wait": set(),
    "engine.decode.harvest": {"tokens", "retired"},
    "engine.step.publish": set(),
    "gateway.deliver": {"tokens"},
}


def _post_stream(port, prompt, max_tokens):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    conn.request("POST", "/v1/completions", json.dumps(
        {"model": "paddle-tpu", "prompt": prompt, "max_tokens": max_tokens,
         "stream": True}), {"Content-Type": "application/json"})
    return conn


def _read_stream(conn):
    toks = []
    for raw in conn.getresponse():
        line = raw.decode().strip()
        if line.startswith("data: ") and line != "data: [DONE]":
            toks += json.loads(line[6:])["choices"][0]["token_ids"]
    return toks


@pytest.fixture(scope="module")
def served():
    """Streamed requests through Gateway -> EngineWorker -> Engine: three
    one after the other, each on an engine that then runs dry, and two that
    overlap, the second admitted while the first decodes.  Gives the span
    records written meanwhile and what was sent."""
    paddle.seed(0)
    model = GPTForCausalLM(TINY)
    model.eval()
    engine = Engine(model, EngineConfig(num_slots=2, max_seq_len=64,
                                        max_horizon=4),
                    register_profiler=False)
    mark = span_log.now_ns()
    sent = []
    with Gateway([engine], GatewayConfig()) as gw:
        worker = gw.router.workers[0]
        worker_tid = worker._thread.ident
        for i in range(3):
            sent.append(_read_stream(
                _post_stream(gw.port, [5 + i, 6, 7, 8, 9], 6)))
        # the overlap, by construction and not by timing: the worker is held
        # inside the first request's first hand-over (its prefill is in
        # flight) until the second request lies in its inbox, so the second
        # is admitted by the very next step, with the first one's tokens of
        # the step before not yet handed over
        hand_over, held, go = engine.while_in_flight, threading.Event(), \
            threading.Event()

        def hold_once():
            if not held.is_set():
                held.set()
                go.wait(60)
            hand_over()

        engine.while_in_flight = hold_once
        first = _post_stream(gw.port, [11, 6, 7, 8, 9], 12)
        assert held.wait(60)
        second = _post_stream(gw.port, [12, 6, 7, 8, 9], 6)
        while worker._inbox.empty():
            time.sleep(0.001)
        go.set()
        sent += [_read_stream(first), _read_stream(second)]
    recs = [e for e in span_log.records() if e.start_ns >= mark]
    return {"records": recs, "worker_tid": worker_tid, "sent": sent,
            "builds": [b for b in span_log.builds()
                       if b["start_ns"] >= mark]}


def _on_worker(served):
    return sorted((e for e in served["records"]
                   if e.tid == served["worker_tid"]),
                  key=lambda e: e.start_ns)


class TestServingSpans:
    @pytest.mark.parametrize("name", sorted(ENGINE_SPANS))
    def test_span_is_written_with_its_arguments(self, served, name):
        mine = [e for e in served["records"] if e.name == name]
        assert mine, f"no {name} span"
        for e in mine:
            assert ENGINE_SPANS[name] <= set(e.args), (name, e.args)

    def test_worker_thread_runs_enqueue_wait_harvest_in_order(self, served):
        on_worker = sorted((e for e in served["records"]
                            if e.tid == served["worker_tid"]),
                           key=lambda e: e.start_ns)
        for kind in ("engine.decode", "engine.prefill"):
            names = [e.name[len(kind) + 1:] for e in on_worker
                     if e.name.startswith(kind + ".")
                     and not e.name.endswith((".prepare", ".build"))]
            assert names and len(names) % 3 == 0
            assert names == ["enqueue", "wait", "harvest"] * (len(names) // 3)
        # at most 14 spans an engine step (build and prepare are written
        # where their work is: prepare twice)
        steps = sum(1 for e in on_worker if e.name == "engine.step.publish")
        engine_side = sum(1 for e in on_worker
                          if e.name.startswith(("engine.", "worker.")))
        assert engine_side <= 14 * steps

    def test_build_and_prepare_end_before_their_enqueue(self, served):
        on_worker = sorted((e for e in served["records"]
                            if e.tid == served["worker_tid"]),
                           key=lambda e: e.start_ns)
        for kind, before in (("engine.decode", "prepare"),
                             ("engine.prefill", "build")):
            seen = 0
            for i, e in enumerate(on_worker):
                if e.name != f"{kind}.enqueue":
                    continue
                prev = [p for p in on_worker[:i]
                        if p.name == f"{kind}.{before}"][-1]
                assert prev.start_ns + prev.dur_ns <= e.start_ns
                seen += 1
            assert seen
        # the prepare record next to the enqueue says what it uploaded
        uploads = [e for e in on_worker if e.name == "engine.decode.prepare"
                   and "state" in e.args]
        enqueues = [e for e in on_worker
                    if e.name == "engine.decode.enqueue"]
        assert len(uploads) == len(enqueues)
        assert all({"state", "tables"} <= set(e.args) for e in uploads)

    @pytest.mark.parametrize("kind", ["decode", "prefill"])
    def test_flush_that_pushed_rides_a_dispatch_in_flight(self, served, kind):
        """While the engine has more work, a step's tokens go out between
        the next enqueue's end and its wait's start: both kinds of dispatch
        carry such a flush, on the worker's thread."""
        on_worker = _on_worker(served)
        rode = 0
        for i, e in enumerate(on_worker):
            if not (e.name == "worker.flush" and e.args["in_flight"]
                    and e.args["tokens"]):
                continue
            before = [p for p in on_worker[:i] if p.name != "worker.flush"]
            after = [p for p in on_worker[i + 1:] if p.name != "worker.flush"]
            enqueue, wait = before[-1], after[0]
            assert enqueue.name.endswith(".enqueue"), enqueue.name
            assert wait.name == enqueue.name[:-len("enqueue")] + "wait"
            assert enqueue.start_ns + enqueue.dur_ns <= e.start_ns
            assert e.start_ns + e.dur_ns <= wait.start_ns
            rode += enqueue.name == f"engine.{kind}.enqueue"
        assert rode

    def test_in_flight_says_where_a_flush_lies(self, served):
        """``in_flight`` is true exactly for the flushes between an enqueue
        and its wait; every dispatch carries one."""
        on_worker = _on_worker(served)
        inside, carried = False, 0
        for e in on_worker:
            if e.name.endswith(".enqueue"):
                inside = True
            elif e.name.endswith(".wait"):
                inside = False
            elif e.name == "worker.flush":
                assert e.args["in_flight"] is inside
                carried += inside
        assert carried == sum(1 for e in on_worker
                              if e.name.endswith(".enqueue"))

    def test_no_yield_span_is_written(self, served):
        assert not [e for e in served["records"] if e.name == "worker.yield"]
        assert obs_metrics.value("span.seconds", name="worker.yield") is None

    def test_last_tokens_go_out_when_the_engine_runs_dry(self, served):
        """A flush outside every dispatch that pushed something is the one
        after the step that left the engine without work: the worker idles
        next, and each stream's last tokens (and its finish) came that way
        or with a dispatch, never later."""
        on_worker = [e for e in _on_worker(served)
                     if e.name in ("worker.flush", "worker.idle")
                     or e.name.endswith(".enqueue")]
        dry = [i for i, e in enumerate(on_worker)
               if e.name == "worker.flush" and not e.args["in_flight"]
               and e.args["tokens"]]
        assert len(dry) == 4            # three alone, then the overlapping two
        for i in dry:
            assert on_worker[i + 1].name == "worker.idle"
        # nothing is handed over at any other moment
        assert not [e for e in on_worker if e.name == "worker.flush"
                    and not e.args["in_flight"] and not e.args["tokens"]
                    and e.args["handles"]]

    def test_no_two_queued_stretches_overlap(self, served):
        on_worker = sorted((e for e in served["records"]
                            if e.tid == served["worker_tid"]
                            and e.name.endswith((".enqueue", ".wait"))),
                           key=lambda e: e.start_ns)
        stretches = [(a.start_ns, b.start_ns + b.dur_ns)
                     for a, b in zip(on_worker[::2], on_worker[1::2])]
        assert all(a.name.endswith(".enqueue") and b.name.endswith(".wait")
                   and a.name.split(".")[1] == b.name.split(".")[1]
                   for a, b in zip(on_worker[::2], on_worker[1::2]))
        assert stretches
        for (_, end), (start, _) in zip(stretches, stretches[1:]):
            assert end <= start

    def test_children_name_their_cause(self, served):
        by_name = {}
        for e in served["records"]:
            by_name.setdefault(e.name, set()).add(e.cause)
        assert by_name["engine.prefill.build"] == {"engine.admit"}
        assert by_name["engine.prefill.wait"] == {"engine.admit"}
        assert by_name["engine.decode.wait"] == {None}
        assert by_name["gateway.deliver"] == {None}

    def test_deliver_spans_belong_to_the_requests_sent(self, served):
        delivered = {}
        for e in served["records"]:
            if e.name == "gateway.deliver":
                delivered[e.id] = delivered.get(e.id, 0) + e.args["tokens"]
                assert e.tid != served["worker_tid"]    # the handler's
        assert len(delivered) == len(served["sent"]) == 5
        assert None not in delivered
        assert sorted(delivered.values()) == sorted(
            len(t) for t in served["sent"])

    def test_flush_counts_what_it_pushed(self, served):
        pushed = sum(e.args["tokens"] for e in served["records"]
                     if e.name == "worker.flush")
        assert pushed == sum(len(t) for t in served["sent"])

    def test_enqueue_arguments_count_the_programs_used(self, served):
        used = {(e.args["horizon"], e.args["width"], e.args["k"])
                for e in served["records"]
                if e.name == "engine.decode.enqueue"}
        built = {(b["key"]["horizon"], b["key"]["nb"], b["key"]["k_draft"])
                 for b in served["builds"]
                 if b["program"] == "serving.decode"}
        assert used == built
        assert {b["program"] for b in served["builds"]} == {
            "serving.decode", "serving.prefill"}

    def test_engine_holds_no_three_sink_span(self):
        import inspect

        from paddle_tpu.serving import engine

        src = inspect.getsource(engine)
        assert "_obs_span(" not in src and "publish_roofline" not in src
