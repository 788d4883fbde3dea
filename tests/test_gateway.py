"""Serving gateway tests: SSE wire format and bitwise stream parity over
real HTTP, per-tenant token-bucket quotas (429 -> refill), SLO load
shedding (503 + Retry-After), prefix-affinity routing across replicas,
priority-aware admission (bounded starvation), deadline aborts, and
graceful drain."""

import http.client
import importlib
import json
import queue
import time

import pytest

import paddle_tpu as paddle
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.observability import metrics as obs_metrics
from paddle_tpu.observability.metrics import validate_exposition
from paddle_tpu.serving import (
    Engine, EngineConfig, SamplingParams, Scheduler,
)
from paddle_tpu.serving.faults import (
    FAULT_EXCEPTION, FAULT_STALL, SITE_WORKER_DISPATCH, FaultInjector,
    FaultPlan, FaultSpec, RetryPolicy,
)
from paddle_tpu.serving.gateway import (
    EngineWorker, FleetSupervisor, Gateway, GatewayConfig,
    PrefixAffinityRouter, TenantQuotas, TokenBucket,
)

# `observability.span` the attribute is the class; this is the module
span_log = importlib.import_module("paddle_tpu.observability.span")

TINY = GPTConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                 num_hidden_layers=2, num_attention_heads=4,
                 max_position_embeddings=64)


def _model(seed=0):
    paddle.seed(seed)
    m = GPTForCausalLM(TINY)
    m.eval()
    return m


def _cfg(**kw):
    kw.setdefault("num_slots", 4)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("max_horizon", 4)
    return EngineConfig(**kw)


def _post(port, payload, timeout=60):
    """POST /v1/completions on a fresh connection; returns the
    http.client response (unread)."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request("POST", "/v1/completions", json.dumps(payload),
                 {"Content-Type": "application/json"})
    return conn.getresponse()


def _parse_sse(raw):
    """Parse an SSE body into (chunks, finish_reason), asserting the
    wire format: every frame is ``data: <json>`` + blank line, the last
    is the ``data: [DONE]`` sentinel, exactly one chunk carries a
    finish_reason."""
    frames = raw.split("\n\n")
    assert frames[-1] == ""                     # body ends on the blank
    frames = frames[:-1]
    assert frames and all(f.startswith("data: ") for f in frames)
    assert frames[-1] == "data: [DONE]"
    chunks = [json.loads(f[len("data: "):]) for f in frames[:-1]]
    reasons = [c["choices"][0]["finish_reason"] for c in chunks]
    assert all(r is None for r in reasons[:-1])
    assert reasons[-1] is not None
    assert all(c["object"] == "text_completion.chunk" for c in chunks)
    toks = [t for c in chunks for t in c["choices"][0]["token_ids"]]
    return toks, reasons[-1]


class _FakeWorker:
    """Duck-typed replica for router-only tests (no engine, no JAX)."""

    def __init__(self, name, healthy=True, load=0, block=4):
        self.name = name
        self._healthy = healthy
        self.load = load
        self.prefix_block_size = block

    @property
    def healthy(self):
        return self._healthy


# --------------------------------------------------------------------- quotas
class TestTokenBucket:
    def test_refill_and_retry_after(self):
        now = [0.0]
        b = TokenBucket(100, 10, clock=lambda: now[0])
        ok, retry = b.try_take(60)
        assert ok and retry == 0.0
        ok, retry = b.try_take(60)               # only 40 left
        assert not ok and retry == pytest.approx(2.0)
        now[0] += 2.0                            # +20 tokens
        ok, _ = b.try_take(60)
        assert ok and b.available == pytest.approx(0.0)

    def test_oversized_request_points_at_full_bucket(self):
        b = TokenBucket(10, 5, clock=lambda: 0.0)
        ok, retry = b.try_take(1000)             # can never be granted
        assert not ok and retry == pytest.approx(0.0)

    def test_tenant_isolation_and_overrides(self):
        now = [0.0]
        q = TenantQuotas(50, 10, clock=lambda: now[0])
        assert q.admit("a", 50) == (True, 0.0)
        ok, retry = q.admit("a", 1)              # a is broke
        assert not ok and retry > 0
        assert q.admit("b", 50)[0]               # b unaffected
        q.set_quota("vip", 500)
        assert q.admit("vip", 400)[0]

    def test_disabled_by_default(self):
        q = TenantQuotas()
        assert not q.enforcing
        assert q.admit("anyone", 10**9) == (True, 0.0)


# --------------------------------------------------------------------- router
class TestPrefixAffinityRouter:
    def test_affinity_key_chunks_like_radix_cache(self):
        r = PrefixAffinityRouter([_FakeWorker("a", block=4)],
                                 affinity_blocks=2)
        assert r.affinity_key([1, 2, 3]) is None          # < one block
        assert r.affinity_key([1, 2, 3, 4, 5]) == (1, 2, 3, 4)
        assert (r.affinity_key(list(range(20)))
                == tuple(range(8)))                       # capped at 2

    def test_same_prefix_same_replica_distinct_prefixes_spread(self):
        ws = [_FakeWorker(f"w{i}") for i in range(4)]
        r = PrefixAffinityRouter(ws)
        picks = set()
        for suffix in range(10):                 # shared system prompt
            w, how = r.route([1, 2, 3, 4, suffix])
            assert how == "affine"
            picks.add(w.name)
        assert len(picks) == 1                   # sticky
        spread = {r.route([p] * 8)[0].name for p in range(32)}
        assert len(spread) >= 2                  # rendezvous spreads keys

    def test_unhealthy_replica_excluded_until_recovery(self):
        ws = [_FakeWorker("w0"), _FakeWorker("w1")]
        r = PrefixAffinityRouter(ws)
        prompt = [9, 9, 9, 9, 1]
        home, _ = r.route(prompt)
        home._healthy = False                    # SLO burn
        w, how = r.route(prompt)
        assert w is not home and how == "affine"
        home._healthy = True                     # recovered
        assert r.route(prompt)[0] is home        # rendezvous is stable
        ws[0]._healthy = ws[1]._healthy = False
        assert r.route(prompt) == (None, "shed")

    def test_short_prompt_falls_back_to_least_loaded(self):
        ws = [_FakeWorker("w0", load=5), _FakeWorker("w1", load=1)]
        w, how = PrefixAffinityRouter(ws).route([1, 2])
        assert how == "least-loaded" and w.name == "w1"


# ---------------------------------------------------------- priority/deadline
class TestPriorityAdmission:
    """Scheduler-level: priority widens the overtake budget but the
    per-victim cap bounds starvation."""

    @staticmethod
    def _bucket(r):
        return r.prompt_len

    def test_priority_overtakes_within_bound(self):
        s = Scheduler(4, reorder_window=2)
        lo = s.submit([1] * 8, SamplingParams(max_new_tokens=2))
        his = [s.submit([2] * 4, SamplingParams(max_new_tokens=2),
                        priority=1)
               for _ in range(8)]
        order = []
        while s.queue_depth:
            order.extend(s.pop_batch(1, bucket_of=self._bucket))
        # cap = w * (1 + dp) = 2 * (1 + 1) = 4 overtakes, then lo runs
        assert order.index(lo) == 4
        assert lo.bypassed == 4
        assert order[:4] == his[:4] and order[5:] == his[4:]

    def test_equal_priority_stays_fifo(self):
        s = Scheduler(4, reorder_window=4)
        rs = [s.submit([1] * 4, SamplingParams(max_new_tokens=2),
                       priority=3)
              for _ in range(6)]
        got = []
        while s.queue_depth:
            got.extend(s.pop_batch(2, bucket_of=self._bucket))
        assert got == rs

    def test_deadline_expired_queued_request_aborts(self):
        m = _model()
        eng = Engine(m, _cfg(num_slots=1), register_profiler=False)
        runner = eng.submit([1, 2, 3, 4], SamplingParams(max_new_tokens=8))
        doomed = eng.submit([5, 6, 7, 8],
                            SamplingParams(max_new_tokens=8),
                            deadline_s=0.01, tenant="t0")
        time.sleep(0.03)                         # let the deadline pass
        eng.run()
        assert runner.finish_reason == "length"
        assert doomed.finish_reason == "abort"
        c = eng.counters()
        assert c["deadline_expired"] == 1
        assert c["requests_aborted"] == 1
        # the flight record shows queued -> abort(cause=deadline)
        kinds = [(k, a) for k, _, a in doomed.trace.events]
        assert kinds[0][0] == "queued"
        assert kinds[-1][0] == "abort"
        assert kinds[-1][1]["cause"] == "deadline"
        assert doomed.trace.counts()["aborted"] == 1
        # tenant ledger billed the submit and the abort
        t = eng.stats()["tenants"]["t0"]
        assert t["submitted"] == 1 and t["aborted"] == 1
        eng.close()

    def test_admitted_requests_outrun_their_deadline(self):
        m = _model()
        eng = Engine(m, _cfg(num_slots=1), register_profiler=False)
        r = eng.submit([1, 2, 3, 4], SamplingParams(max_new_tokens=6),
                       deadline_s=30.0)
        eng.run()                                # admitted immediately
        assert r.finish_reason == "length" and r.n_generated == 6
        eng.close()


# ---------------------------------------------------------------------- drain
class TestDrain:
    def test_drain_finishes_work_and_releases_every_block(self):
        m = _model()
        eng = Engine(m, _cfg(num_slots=2,
                             prefix_cache_bytes=1 << 20),
                     register_profiler=False)
        a = eng.submit([1, 2, 3, 4], SamplingParams(max_new_tokens=6))
        q = eng.submit([5, 6, 7, 8], SamplingParams(max_new_tokens=6))
        eng.step()                               # a+q admitted, cached
        retired = eng.drain()
        assert eng.pool.blocks_in_use == 0       # the invariant drain asserts
        assert {r.request_id for r in retired} >= set()
        assert a.finish_reason == "length" and q.finish_reason == "length"
        # draining refuses new work...
        # ...but a FINISHED drain leaves the engine usable again
        r = eng.submit([9, 9, 9], SamplingParams(max_new_tokens=2))
        eng.run()
        assert r.n_generated == 2
        eng.close()

    def test_drain_aborts_queued_backlog(self):
        m = _model()
        eng = Engine(m, _cfg(num_slots=1), register_profiler=False)
        eng.submit([1, 2, 3, 4], SamplingParams(max_new_tokens=4))
        backlog = eng.submit([5, 6, 7, 8], SamplingParams(max_new_tokens=4))
        eng.step()
        eng.drain()
        assert backlog.finish_reason == "abort"
        assert eng.pool.blocks_in_use == 0
        eng.close()

    def test_mixed_mesh_replica_drain_releases_every_block(self):
        """A router over one single-chip and one tp=2 MeshEngine
        replica (8 virtual CPU devices): EngineWorker drives the mesh
        engine through the same duck type, both replicas take work, and
        drain's block-leak invariant (``kv_blocks_in_use == 0``) holds
        on the mesh-sharded pool too."""
        from paddle_tpu.serving import MeshEngine

        # one model INSTANCE per replica: engines trace through
        # use_state() on their model, and a mesh engine swaps in
        # locally-SLICED weights — sharing one module object between
        # concurrently-stepping workers would race the swap (benign
        # between same-shape single-chip engines, a shape error against
        # a mesh engine; see the MeshEngine docstring)
        e0 = Engine(_model(), _cfg(num_slots=2), register_profiler=False)
        e1 = MeshEngine(_model(), _cfg(num_slots=2), tp=2,
                        register_profiler=False)
        w0, w1 = EngineWorker(e0, "chip"), EngineWorker(e1, "mesh")
        router = PrefixAffinityRouter([w0, w1])
        handles = []
        for i in range(4):                 # spread across both replicas
            h, _, _ = router.submit([1 + i, 2, 3, 4],
                                    SamplingParams(max_new_tokens=4))
            handles.append(h)
        for h in handles:
            kind, reason = _drain_handle(h)
            assert (kind, reason) == ("finish", "length")
        for w in (w0, w1):
            w.drain()
            assert w.engine.pool.blocks_in_use == 0
            assert w.stats()["kv_pool"]["blocks_in_use"] == 0
            w.stop()
        assert e1.stats()["mesh"]["mesh_shape"] == {"dp": 1, "tp": 2}
        e0.close()
        e1.close()

    def test_worker_rejects_non_engine_objects(self):
        """The duck-type assertion: a router-level fake without the
        Engine API fails fast with the missing names, instead of dying
        later on the worker thread."""
        with pytest.raises(TypeError, match="submit"):
            EngineWorker(object(), "bogus")

    def test_router_remove_is_graceful(self):
        m = _model()
        e0 = Engine(m, _cfg(num_slots=2), register_profiler=False)
        e1 = Engine(m, _cfg(num_slots=2), register_profiler=False)
        w0, w1 = EngineWorker(e0, "w0"), EngineWorker(e1, "w1")
        router = PrefixAffinityRouter([w0, w1])
        h, w, _ = router.submit([1, 2, 3, 4],
                                SamplingParams(max_new_tokens=4))
        router.remove(w, close_engine=False)
        assert w not in router.workers
        kind, reason = _drain_handle(h)
        assert (kind, reason) == ("finish", "length")    # work finished
        assert w.engine.pool.blocks_in_use == 0
        other = router.workers[0]
        with pytest.raises(RuntimeError):
            w.submit([1, 2], SamplingParams(max_new_tokens=1))
        other.drain()
        other.stop()
        e0.close()
        e1.close()


def _drain_handle(h, timeout=30.0):
    """Consume a StreamHandle's event queue to its terminal event."""
    return _drain_stream(h, timeout)[1:]


def _drain_stream(h, timeout=120.0):
    """``(tokens, "finish", reason)`` of a StreamHandle read to its end."""
    deadline = time.monotonic() + timeout
    toks = []
    while True:
        kind, value = h.events.get(timeout=max(0.1,
                                               deadline - time.monotonic()))
        if kind == "finish":
            return toks, kind, value
        toks.extend(value)


# ------------------------------------------------ the worker's hand-over
#: three requests for two lanes: the third is admitted when the first
#: retires, while the second still decodes
HAND_OVER_PROMPTS = ([1, 2, 3, 4, 5], [9, 8, 7, 6, 5, 4, 3], [2, 4, 6, 8])
HAND_OVER_BUDGETS = (5, 19, 9)


def _sampling(kind, i, eos=None):
    kw = dict(max_new_tokens=HAND_OVER_BUDGETS[i], eos_token_id=eos)
    if kind == "seeded":
        kw.update(temperature=0.8, top_k=20, seed=11 + i)
    return SamplingParams(**kw)


def _bare_loop(kind, eos=None):
    """The same requests through ``Engine.step()`` and no worker."""
    eng = Engine(_model(), _cfg(num_slots=2), register_profiler=False)
    assert eng.while_in_flight is None
    reqs = [eng.submit(list(p), _sampling(kind, i, eos))
            for i, p in enumerate(HAND_OVER_PROMPTS)]
    while eng.scheduler.has_work:
        eng.step()
    eng.close()
    return [(list(r.output_ids), r.finish_reason) for r in reqs]


def _stalled_at(worker, injector):
    """Wait until ``injector``'s stall holds ``worker`` before a step."""
    deadline = time.monotonic() + 120
    while not injector.fired and time.monotonic() < deadline:
        time.sleep(0.002)
    assert injector.fired and injector.fired[0][2] == FAULT_STALL


class TestWorkerHandOver:
    """The worker hands a step's tokens to the handles while the NEXT
    dispatch is in flight.  Only the moment of the put moved: the tokens,
    their order, the finish reasons and ``handle.sent`` are the engine's."""

    @pytest.mark.parametrize("kind", ["greedy", "seeded"])
    def test_streams_equal_a_bare_step_loop(self, kind):
        # an EOS id one of the streams reaches, so both finish reasons occur
        free = _bare_loop(kind)
        eos = free[1][0][7]
        want = _bare_loop(kind, eos)
        assert {r for _, r in want} == {"length", "eos"}
        eng = Engine(_model(), _cfg(num_slots=2), register_profiler=False)
        w = EngineWorker(eng, "w")
        try:
            handles = [w.submit(list(p), sampling=_sampling(kind, i, eos))
                       for i, p in enumerate(HAND_OVER_PROMPTS)]
            got = [_drain_stream(h) for h in handles]
            assert [(t, r) for t, _, r in got] == want
            assert [h.sent for h in handles] == [len(t) for t, _ in want]
            w.drain()
            assert eng.pool.blocks_in_use == 0
        finally:
            w.stop()
            eng.close()
        assert eng.while_in_flight is None      # the worker took it along

    def test_dispatch_fault_neither_loses_nor_repeats_a_token(self):
        want = _bare_loop("greedy")
        eng = Engine(_model(), _cfg(num_slots=2), register_profiler=False)
        w = EngineWorker(eng, "w")
        inj = FaultInjector(FaultPlan([
            FaultSpec(SITE_WORKER_DISPATCH, FAULT_EXCEPTION, at=n)
            for n in (1, 2, 4, 7)]))
        w.set_faults(inj)
        try:
            handles = [w.submit(list(p), sampling=_sampling("greedy", i))
                       for i, p in enumerate(HAND_OVER_PROMPTS)]
            got = [_drain_stream(h) for h in handles]
            assert [(t, r) for t, _, r in got] == want
            assert inj.counts() == {FAULT_EXCEPTION: 4}
            assert w.stats()["worker"]["dispatch_faults"] == 4
        finally:
            w.stop()
            eng.close()

    def test_abort_before_the_deferred_flush_sends_each_token_once(self):
        """The worker is held before its third step with the second
        step's tokens harvested and not yet handed over; an abort that
        lands then flushes them, once, before the terminal event."""
        want = _bare_loop("greedy")[1][0]
        eng = Engine(_model(), _cfg(num_slots=2), register_profiler=False)
        w = EngineWorker(eng, "w")
        inj = FaultInjector(FaultPlan([
            FaultSpec(SITE_WORKER_DISPATCH, FAULT_STALL, at=2)]))
        w.set_faults(inj)
        try:
            h = w.submit(list(HAND_OVER_PROMPTS[1]),
                         sampling=_sampling("greedy", 1))
            _stalled_at(w, inj)
            harvested = h.request.n_generated
            assert 0 < h.sent < harvested       # a step's tokens wait
            h.abort()
            w._unstall.set()
            toks, _, reason = _drain_stream(h)
            assert reason == "abort"
            assert h.sent == len(toks) >= harvested
            assert toks == want[:len(toks)]     # each once, in order
        finally:
            w.stop()
            eng.close()

    def test_failover_before_the_deferred_flush_resumes_from_sent(self):
        """Condemned while a step's tokens were harvested and not handed
        over: the adopting replica resumes from ``handle.sent``, so the
        client sees those tokens once, from the survivor."""
        prompt, samp = list(HAND_OVER_PROMPTS[1]), _sampling("seeded", 1)
        ref = Engine(_model(), _cfg(num_slots=2), register_profiler=False)
        want = list(ref.generate(list(prompt), samp))
        ref.close()
        workers = [EngineWorker(Engine(_model(), _cfg(num_slots=2),
                                       register_profiler=False), f"r{i}")
                   for i in range(2)]
        router = PrefixAffinityRouter(workers, retry=RetryPolicy())
        sup = FleetSupervisor(router, watchdog_timeout_s=None)
        try:
            target, _ = router.route(prompt)
            inj = FaultInjector(FaultPlan([
                FaultSpec(SITE_WORKER_DISPATCH, FAULT_STALL, at=2)]))
            target.set_faults(inj)
            h, w0, _ = router.submit(prompt, sampling=samp)
            assert w0 is target
            _stalled_at(target, inj)
            sent, harvested = h.sent, h.request.n_generated
            assert 0 < sent < harvested
            sup.condemn(target, "watchdog_stall")
            toks, _, reason = _drain_stream(h)
            assert (toks, reason) == (want, "length")
            assert h.failovers == 1 and h.worker is not target
            assert h.request.trace.counts()["resumed_tokens"] == sent
        finally:
            sup.stop()
            for w in workers:
                if w.alive:
                    w.stop()

    def test_flush_counter_counts_what_the_spans_say(self):
        def count(flag):
            return obs_metrics.value("gateway.flushes", in_flight=flag) or 0

        eng = Engine(_model(), _cfg(num_slots=2), register_profiler=False)
        w = EngineWorker(eng, "w")
        mark = span_log.now_ns()
        before = {f: count(f) for f in ("true", "false")}
        try:
            handles = [w.submit(list(p), sampling=_sampling("greedy", i))
                       for i, p in enumerate(HAND_OVER_PROMPTS)]
            streams = [_drain_stream(h) for h in handles]
        finally:
            w.stop()
            eng.close()
        mine = [e for e in span_log.records("worker.flush")
                if e.start_ns >= mark and e.tid == w._thread.ident]
        # a flush pushed if it carried tokens or ended a stream; here every
        # stream's end comes with its last tokens
        pushed = {flag: sum(1 for e in mine if e.args["tokens"]
                            and e.args["in_flight"] is (flag == "true"))
                  for flag in before}
        assert {f: count(f) - before[f] for f in before} == pushed
        assert pushed["true"] > pushed["false"] == 1
        assert sum(e.args["tokens"] for e in mine) == sum(
            len(t) for t, _, _ in streams)


# ----------------------------------------------------------------- HTTP layer
@pytest.mark.slow
class TestGatewayHTTP:
    """One live gateway over two tiny replicas, exercised with stdlib
    http.client — wire format, parity, admission errors, metrics."""

    @pytest.fixture()
    def gw(self):
        m = _model()
        engines = [Engine(m, _cfg(), register_profiler=False)
                   for _ in range(2)]
        g = Gateway(engines,
                    GatewayConfig(model_id="tiny")).start()
        yield g
        g.shutdown()
        for e in engines:
            assert e.pool.blocks_in_use == 0

    def test_models_and_health(self, gw):
        conn = http.client.HTTPConnection("127.0.0.1", gw.port, timeout=30)
        conn.request("GET", "/v1/models")
        r = conn.getresponse()
        doc = json.loads(r.read())
        assert r.status == 200 and doc["data"][0]["id"] == "tiny"
        conn.request("GET", "/readyz")
        r = conn.getresponse()
        assert r.status == 200 and json.loads(r.read())["ready"]
        conn.request("GET", "/nope")
        r = conn.getresponse()
        assert r.status == 404
        assert json.loads(r.read())["error"]["code"] == "route_not_found"

    def test_stream_is_bitwise_in_process_output(self, gw):
        """The tentpole parity claim: streamed SSE tokens equal
        ``Engine.generate`` for the same request — greedy AND
        seeded-stochastic (the engine's fold_in(seed, n) sampling makes
        both deterministic)."""
        m = _model()
        ref = Engine(m, _cfg(), register_profiler=False)
        prompt = list(range(1, 17))
        cases = [
            {"max_tokens": 12},
            {"max_tokens": 12, "temperature": 0.8, "top_k": 8, "seed": 7},
        ]
        for extra in cases:
            sp = SamplingParams(
                max_new_tokens=extra["max_tokens"],
                temperature=extra.get("temperature", 0.0),
                top_k=extra.get("top_k", 0),
                seed=extra.get("seed", 0))
            want = ref.generate(list(prompt), sp)
            r = _post(gw.port, dict(extra, prompt=prompt, stream=True))
            assert r.status == 200
            assert r.getheader("Content-Type").startswith(
                "text/event-stream")
            toks, reason = _parse_sse(r.read().decode())
            assert toks == want                  # bitwise, not approx
            assert reason == "length"
        ref.close()

    def test_sync_completion_shape_and_usage(self, gw):
        r = _post(gw.port, {"model": "tiny", "prompt": [3, 1, 4, 1, 5],
                            "max_tokens": 6})
        doc = json.loads(r.read())
        assert r.status == 200
        assert doc["object"] == "text_completion"
        choice = doc["choices"][0]
        assert len(choice["token_ids"]) == 6
        assert choice["finish_reason"] == "length"
        assert doc["usage"] == {"prompt_tokens": 5,
                                "completion_tokens": 6,
                                "total_tokens": 11}

    def test_validation_errors(self, gw):
        for payload, status, code in (
                ({"prompt": "text"}, 400, None),
                ({"prompt": []}, 400, None),
                ({"prompt": [1, 2.5]}, 400, None),
                ({"prompt": [1, 2], "model": "other"}, 404,
                 "model_not_found"),
                ({"prompt": [1, 2], "top_p": 0.0}, 400, None),
                ({"prompt": [1, 2], "priority": 99}, 400, None),
                ({"prompt": [1, 2], "priority": "high"}, 400, None),
                ({"prompt": [1, 2], "priority": -1, "stream": True},
                 400, "batch_no_stream"),
                ({"prompt": [1, 2], "deadline_s": 0}, 400, None),
                ({"prompt": [1, 2], "stream": "yes"}, 400, None),
                ({"prompt": [1] * 100, "max_tokens": 10}, 400, None)):
            r = _post(gw.port, payload)
            err = json.loads(r.read())["error"]
            assert r.status == status, (payload, err)
            assert err["code"] == code
        # malformed JSON body
        conn = http.client.HTTPConnection("127.0.0.1", gw.port,
                                          timeout=30)
        conn.request("POST", "/v1/completions", "{not json",
                     {"Content-Type": "application/json"})
        assert conn.getresponse().status == 400

    def test_metrics_exposition(self, gw):
        _post(gw.port, {"prompt": [1, 2, 3, 4], "max_tokens": 2,
                        "stream": True}).read()
        conn = http.client.HTTPConnection("127.0.0.1", gw.port, timeout=30)
        conn.request("GET", "/metrics")
        r = conn.getresponse()
        text = r.read().decode()
        assert r.status == 200
        validate_exposition(text)
        for fam in ("gateway_requests", "gateway_streams",
                    "gateway_stream_tokens", "gateway_routed",
                    "gateway_ttft_seconds", "gateway_request_seconds"):
            assert fam in text, fam


@pytest.mark.slow
class TestGatewayAdmissionHTTP:
    def test_quota_429_then_refill_grants(self):
        m = _model()
        eng = Engine(m, _cfg(), register_profiler=False)
        now = [0.0]
        quotas = TenantQuotas(40, 10, clock=lambda: now[0])
        gw = Gateway([eng], GatewayConfig(), quotas=quotas).start()
        try:
            ok = _post(gw.port, {"prompt": [1] * 10, "max_tokens": 20,
                                 "tenant": "acme"})
            ok.read()
            assert ok.status == 200              # cost 30 <= 40
            denied = _post(gw.port, {"prompt": [1] * 10, "max_tokens": 20,
                                     "tenant": "acme"})
            body = json.loads(denied.read())
            assert denied.status == 429
            assert body["error"]["type"] == "tenant_quota_exceeded"
            assert int(denied.getheader("Retry-After")) >= 1
            # another tenant is unaffected
            other = _post(gw.port, {"prompt": [1] * 10, "max_tokens": 20,
                                    "tenant": "other"})
            other.read()
            assert other.status == 200
            now[0] += 3.0                        # refill 30 tokens
            again = _post(gw.port, {"prompt": [1] * 10, "max_tokens": 20,
                                    "tenant": "acme"})
            again.read()
            assert again.status == 200
        finally:
            gw.shutdown()

    def test_slo_breach_sheds_503_with_retry_after(self):
        m = _model()
        eng = Engine(m, _cfg(slo_ttft_s=1e-9, slo_fast_window=4,
                             slo_slow_window=4),
                     register_profiler=False)
        gw = Gateway([eng], GatewayConfig(shed_retry_after_s=2.0)).start()
        try:
            assert eng.slo.healthy
            for _ in range(8):                   # burn both windows
                eng.slo.observe("ttft", 1.0)
            assert not eng.slo.healthy
            conn = http.client.HTTPConnection("127.0.0.1", gw.port,
                                              timeout=30)
            conn.request("GET", "/readyz")       # same signal
            assert conn.getresponse().status == 503
            r = _post(gw.port, {"prompt": [1, 2, 3], "max_tokens": 2})
            body = json.loads(r.read())
            assert r.status == 503
            assert body["error"]["code"] == "slo_shedding"
            assert r.getheader("Retry-After") == "2"
            for _ in range(8):                   # recover
                eng.slo.observe("ttft", 0.0)
            r = _post(gw.port, {"prompt": [1, 2, 3], "max_tokens": 2})
            r.read()
            assert r.status == 200
        finally:
            gw.shutdown()


# ---------------------------------------------------------- affinity end2end
@pytest.mark.slow
class TestAffinityEndToEnd:
    def test_affine_routing_beats_round_robin_on_prefix_hits(self):
        """Two replicas, two 16-token system prompts, four sessions
        each: affinity routing keeps every session on its prefix's home
        replica, so the radix cache serves repeats; round-robin splits
        them and halves the hit rate."""
        m = _model()

        def fleet():
            return [Engine(m, _cfg(num_slots=2,
                                   prefix_block_size=8,
                                   prefix_cache_bytes=1 << 22),
                           register_profiler=False)
                    for _ in range(2)]

        sysA, sysB = [7] * 16, [9] * 16
        prompts = [sys + [i, i + 1, i + 2, i + 3]
                   for sys in (sysA, sysB) for i in range(4)]
        sp = SamplingParams(max_new_tokens=2)

        # affinity routing through real workers
        engines = fleet()
        workers = [EngineWorker(e, f"w{i}")
                   for i, e in enumerate(engines)]
        router = PrefixAffinityRouter(workers, affinity_blocks=2)
        homes = set()
        for p in prompts:
            h, w, how = router.submit(list(p), sp)
            assert how == "affine"
            homes.add((tuple(p[:16]), w.name))
            _drain_handle(h)
        # every session with the same system prompt hit ONE replica
        assert len({n for k, n in homes if k == tuple(sysA)}) == 1
        assert len({n for k, n in homes if k == tuple(sysB)}) == 1
        affine_hits = sum(e.counters()["prefix_hit_tokens"]
                          for e in engines)
        for w in workers:
            w.drain()
            w.stop()
        for e in engines:
            e.close()

        # round-robin baseline on a fresh fleet
        engines = fleet()
        for i, p in enumerate(prompts):
            engines[i % 2].submit(list(p), sp)
        for e in engines:
            e.run()
        rr_hits = sum(e.counters()["prefix_hit_tokens"] for e in engines)
        for e in engines:
            e.close()

        assert affine_hits > rr_hits, (affine_hits, rr_hits)
