"""Discrete-event fleet capacity simulator: the same workload trace,
stepped through a MODEL of the fleet instead of the fleet itself.

The fleet observatory's second half (observability phase 5).  Given a
:class:`~paddle_tpu.observability.loadgen.WorkloadTrace`, the
simulator answers the capacity question — "how many replicas for this
traffic at this SLO" — as a computable curve, in milliseconds instead
of a load test:

* **service times** come from a :class:`ServiceModel` — per-token
  prefill and decode seconds plus a per-request overhead — built one
  of three ways: analytically from the ProgramCard registry's
  FLOPs/bytes against the published peaks of the chip (``peaks.PEAKS``
  and :func:`~paddle_tpu.observability.memory.backend_bandwidth_gbs`),
  calibrated from a live replay report
  (:meth:`ServiceModel.from_replay` — the honest path on the CPU
  proxy, where rooflines do not bind), or given directly;
* **the fleet model** mirrors the serving stack's admission shape:
  prefix-population affinity routing (a stable hash, standing in for
  the router's rendezvous hash), per-replica slot pools, the
  scheduler's priority overtake BOUND (``window * (1 + gap)`` bypasses
  per victim, unbounded against offline batch-lane victims), queue
  deadlines, a per-replica radix-cache model (first request of a
  population pays full prefill, later ones pay the suffix), and
  client abort storms;
* **everything is deterministic** — no wall clock, no randomness; the
  event heap is keyed ``(time, sequence)`` so replays of the same
  trace produce identical timelines, and the 3-request micro-trace in
  the tests is checked against a hand-computed timeline exactly.

:func:`simulate` rolls its per-request records through the SAME
``loadgen.summarize`` the live replay uses, so
:func:`calibration_report` compares sim vs live like with like:
replica-count ordering must match exactly and attainment must agree
within a stated tolerance — the FLEET_BENCH row check-bench gates.
:func:`fleet_report` is the CLI ``fleet`` mode's engine: attainment-
vs-replica-count curves for named workload shapes in one invocation.
"""

from __future__ import annotations

import dataclasses
import hashlib
import heapq
from dataclasses import dataclass

from . import memory as _memory
from . import peaks as _peaks
from . import profiling as _profiling
from .loadgen import SHAPES, SLOSpec, generate, summarize

#: the CPU proxy has no published peak: a modest sustained FLOP/s keeps
#: analytic models finite — calibrate from a live replay for honest CPU
#: numbers.  Accelerators read their bf16 row of ``peaks.PEAKS``.
_CPU_PROXY_FLOPS = 5e10


@dataclass(frozen=True)
class ServiceModel:
    """Per-phase service-time model of one replica."""

    prefill_s_per_token: float = 2e-4
    decode_s_per_token: float = 2e-3
    #: per-request admission overhead (routing + submit hop)
    overhead_s: float = 1e-3

    def to_json(self):
        return dataclasses.asdict(self)

    @classmethod
    def from_program_cards(cls, backend=None, registry=None,
                           overhead_s=1e-3):
        """Analytic model from the ProgramCard registry: each card's
        service time is its roofline ``max(flops/FLOP-rate,
        bytes/bandwidth)`` against the published peaks of the cards'
        device kind (``backend``, see ``ProgramCard``); per-token
        times average over the cards' dispatch-weighted token volume.
        Falls back to the defaults when no serving cards exist."""
        reg = registry if registry is not None \
            else _profiling.default_registry()
        cards = reg.cards()
        backend = backend or (cards[0].backend if cards else "cpu")
        bw = _memory.backend_bandwidth_gbs(backend) * 1e9
        row = _peaks.chip_peaks(backend)
        flops_rate = row.bf16_flops if row else _CPU_PROXY_FLOPS

        def _per_token(fn, tokens_of):
            t_sum = tok_sum = 0.0
            for c in cards:
                if c.fn != fn:
                    continue
                toks = tokens_of(c)
                if toks <= 0:
                    continue
                svc = max(float(c.flops) / flops_rate,
                          float(c.bytes_accessed) / bw)
                n = max(1, int(getattr(c, "dispatches", 1)))
                t_sum += svc * n
                tok_sum += toks * n
            return t_sum / tok_sum if tok_sum else None

        def _prefill_tokens(c):
            meta = c.meta or {}
            return (int(meta.get("lanes", 0) or 0)
                    * int(meta.get("bucket", 0) or 0))

        def _decode_tokens(c):
            meta = c.meta or {}
            return (int(meta.get("horizon", 0) or 0)
                    * int(meta.get("nb", meta.get("lanes", 0)) or 0))

        d = cls()
        pre = _per_token("serving.prefill", _prefill_tokens)
        dec = _per_token("serving.decode", _decode_tokens)
        return cls(
            prefill_s_per_token=(pre if pre is not None
                                 else d.prefill_s_per_token),
            decode_s_per_token=(dec if dec is not None
                                else d.decode_s_per_token),
            overhead_s=overhead_s)

    @classmethod
    def from_replay(cls, report):
        """Calibrate from a live replay report (``loadgen.replay``):
        decode seconds-per-token is the median observed TPOT, prefill
        seconds-per-token is the median (TTFT - queue wait) over the
        tokens each prefill actually computed (prompt minus prefix
        hits)."""
        pre, dec = [], []
        for r in report.get("records", []):
            if not r.get("completed"):
                continue
            if r.get("tpot_s") is not None:
                dec.append(r["tpot_s"])
            if (r.get("ttft_s") is not None
                    and r.get("queue_s") is not None):
                tokens = max(1, (r.get("prompt_tokens", 1)
                                 - r.get("prefix_hit_tokens", 0)))
                pre.append(max(0.0, r["ttft_s"] - r["queue_s"])
                           / tokens)

        def _median(vals, default):
            if not vals:
                return default
            vals = sorted(vals)
            return vals[len(vals) // 2]

        d = cls()
        return cls(
            prefill_s_per_token=_median(pre, d.prefill_s_per_token),
            decode_s_per_token=_median(dec, d.decode_s_per_token),
            overhead_s=d.overhead_s)


# ----------------------------------------------------------------- the sim
def _affine_replica(prefix_pop, n_replicas):
    """Stable population -> replica map (stands in for the router's
    rendezvous hash; any deterministic uniform map preserves the
    property that matters — same population, same replica)."""
    h = hashlib.blake2b(str(int(prefix_pop)).encode(),
                        digest_size=4).digest()
    return int.from_bytes(h, "big") % max(1, int(n_replicas))


class _SimReq:
    __slots__ = ("req", "t_arrive", "bypassed")

    def __init__(self, req, t_arrive):
        self.req = req
        self.t_arrive = t_arrive
        self.bypassed = 0

    @property
    def priority(self):
        return self.req.priority


def _overtake_cap(victim, overtaker, window):
    """The scheduler's overtake bound, batch-lane exemption included:
    a batch victim (priority < 0) may be passed by interactive traffic
    without bound; otherwise ``window * (1 + priority gap)``."""
    if victim.priority < 0 <= overtaker.priority:
        return float("inf")
    gap = max(0, int(overtaker.priority) - int(victim.priority))
    return window * (1 + gap)


def _pick_next(queue, window):
    """Pop the next admissible request: the highest-priority candidate
    whose every skipped-over victim still has overtake budget, FIFO
    within a priority.  Charges one bypass to each passed victim —
    the same budget discipline ``Scheduler.promote`` enforces."""
    if not queue:
        return None
    best = 0
    for i in range(1, len(queue)):
        r = queue[i]
        if r.priority <= queue[best].priority:
            continue
        if all(v.bypassed < _overtake_cap(v, r, window)
               for v in queue[:i]):
            best = i
    for v in queue[:best]:
        v.bypassed += 1
    return queue.pop(best)


class _Replica:
    __slots__ = ("free_slots", "queue", "cached_pops")

    def __init__(self, num_slots):
        self.free_slots = int(num_slots)
        self.queue = []
        self.cached_pops = set()

    @property
    def load(self):
        return len(self.queue)


def simulate(trace, n_replicas, model=None, *, speed=1.0, num_slots=4,
             reorder_window=8, max_queue=64, slo=None):
    """Step one trace through a fleet of ``n_replicas`` modeled
    replicas; returns the same report shape ``loadgen.replay``
    produces (``summarize`` rollup + ``records``), so the two are
    directly comparable.  ``speed`` compresses virtual arrival times
    exactly like replay's client threads, so calibration compares the
    same timeline."""
    model = model or ServiceModel()
    slo = slo or SLOSpec()
    speed = float(speed)
    if speed <= 0:
        raise ValueError("speed must be > 0")
    replicas = [_Replica(num_slots) for _ in range(int(n_replicas))]
    records = []
    heap = []
    seq = 0
    for req in trace.requests:
        heapq.heappush(heap, (req.t_submit / speed, seq, "arrive", req,
                              None))
        seq += 1

    def _admit(rep, now):
        nonlocal seq
        while rep.free_slots > 0 and rep.queue:
            sr = _pick_next(rep.queue, reorder_window)
            req = sr.req
            queue_s = now - sr.t_arrive
            deadline = (req.deadline_s / speed
                        if req.deadline_s is not None else None)
            if deadline is not None and queue_s > deadline:
                records.append(_record(req, queue_s=None,
                                       deadline_expired=True,
                                       aborted=True))
                continue
            hit = (req.prefix_len
                   if req.prefix_pop in rep.cached_pops else 0)
            rep.cached_pops.add(req.prefix_pop)
            prefill = (model.overhead_s
                       + (req.prompt_len - hit)
                       * model.prefill_s_per_token)
            t_first = now + prefill
            decode = (req.max_new_tokens - 1) * model.decode_s_per_token
            t_done = t_first + decode
            tokens = req.max_new_tokens
            aborted = False
            if req.abort_after_s is not None:
                t_abort = sr.t_arrive + req.abort_after_s / speed
                if t_abort < t_done:
                    aborted = True
                    tokens = (0 if t_abort < t_first else 1 + int(
                        (t_abort - t_first)
                        / model.decode_s_per_token))
                    t_done = max(t_abort, now)
            ttft = (t_first - sr.t_arrive) if tokens > 0 else None
            rec = _record(
                req, queue_s=round(queue_s, 9),
                ttft_s=round(ttft, 9) if ttft is not None else None,
                tpot_s=(model.decode_s_per_token
                        if tokens > 1 else None),
                tokens=tokens, prefix_hit_tokens=hit,
                aborted=aborted, completed=not aborted)
            records.append(rec)
            rep.free_slots -= 1
            heapq.heappush(heap, (t_done, seq, "finish", None, rep))
            seq += 1

    while heap:
        now, _, kind, req, rep = heapq.heappop(heap)
        if kind == "arrive":
            target = replicas[_affine_replica(req.prefix_pop,
                                              len(replicas))]
            if target.load >= max_queue:
                target = min(replicas, key=lambda r: (r.load,
                                                      -r.free_slots))
            if target.load >= max_queue:
                records.append(_record(req, shed=True))
                continue
            target.queue.append(_SimReq(req, now))
            _admit(target, now)
        else:
            rep.free_slots += 1
            _admit(rep, now)

    report = summarize(records, slo=slo)
    report["records"] = records
    report["replicas"] = int(n_replicas)
    report["speed"] = speed
    report["trace_digest"] = trace.digest()
    report["service_model"] = model.to_json()
    return report


def _record(req, *, queue_s=None, ttft_s=None, tpot_s=None, tokens=0,
            prefix_hit_tokens=0, completed=False, shed=False,
            aborted=False, deadline_expired=False):
    return {"index": req.index, "tenant": req.tenant, "tier": req.tier,
            "priority": req.priority, "prompt_tokens": req.prompt_len,
            "tokens": int(tokens),
            "prefix_hit_tokens": int(prefix_hit_tokens),
            "completed": completed, "shed": shed, "aborted": aborted,
            "deadline_expired": deadline_expired, "queue_s": queue_s,
            "ttft_s": ttft_s, "tpot_s": tpot_s}


# ----------------------------------------------------------- curves + calib
def attainment_curve(trace, replica_counts, model=None, **sim_kw):
    """SLO attainment at each replica count — the "how many chips for
    this traffic" curve."""
    curve = []
    for n in replica_counts:
        rep = simulate(trace, n, model, **sim_kw)
        curve.append({
            "replicas": int(n),
            "attainment": rep["attainment"],
            "shed": rep["shed"],
            "completed": rep["completed"],
            "tokens_total": rep["tokens_total"],
            "p95_ttft_s": rep["phase_latency"]["ttft_s"]["p95"],
            "per_tier_attainment": {
                t: g["attainment"]
                for t, g in rep["per_tier"].items()},
        })
    return curve


def calibration_report(trace, live_reports, model, *, speed,
                       tolerance=0.15, tie_eps=0.05, **sim_kw):
    """Sim-vs-live agreement on the CPU proxy: for each replica count
    with a live replay report, run the simulator on the same trace at
    the same speed and compare SLO attainment.  Gated claims: the
    ORDERING of replica counts by attainment must match, and the worst
    absolute attainment error must stay within ``tolerance``.

    Ordering is gated tie-aware: two replica counts whose live
    attainments sit within ``tie_eps`` are indistinguishable at live
    measurement noise (one stray scheduler hiccup moves one request
    across the threshold), so the gate fails only on a STRICT
    disagreement — a pair the live replay separates by more than
    ``tie_eps`` that the sim orders the other way (or vice versa).
    ``ordering_exact`` (sorted orders identical, ties broken by
    replica count) is still reported for the curious."""
    rows = []
    for n in sorted(live_reports):
        live = live_reports[n]
        sim = simulate(trace, n, model, speed=speed, **sim_kw)
        rows.append({"replicas": int(n),
                     "live_attainment": live["attainment"],
                     "sim_attainment": sim["attainment"],
                     "abs_err": round(abs(live["attainment"]
                                          - sim["attainment"]), 6)})
    order_live = [r["replicas"] for r in
                  sorted(rows, key=lambda r: (r["live_attainment"],
                                              r["replicas"]))]
    order_sim = [r["replicas"] for r in
                 sorted(rows, key=lambda r: (r["sim_attainment"],
                                             r["replicas"]))]
    eps = float(tie_eps)
    consistent = True
    for a in rows:
        for b in rows:
            live_says = a["live_attainment"] < b["live_attainment"] - eps
            sim_says = a["sim_attainment"] > b["sim_attainment"] + eps
            if live_says and sim_says:
                consistent = False
    max_err = max((r["abs_err"] for r in rows), default=0.0)
    ordering_exact = order_live == order_sim
    return {"rows": rows, "ordering_exact": ordering_exact,
            "ordering_consistent": consistent,
            "tie_eps": eps,
            "max_abs_err": round(max_err, 6),
            "tolerance": float(tolerance),
            "ok": consistent and max_err <= float(tolerance)}


# -------------------------------------------------------------- CPU proxy
def build_cpu_proxy_gateway(n_replicas, seed=0, num_slots=4,
                            max_seq_len=64, max_horizon=1,
                            model_id="fleet-proxy"):
    """A started live gateway over ``n_replicas`` tiny CPU engines
    with IDENTICAL weights (same init seed) — the live half of the
    calibration loop.  Caller owns shutdown().

    The engines run with ``ragged_attention=False`` and (by default)
    ``max_horizon=1``: the ragged path's block-table width ``nb``
    re-buckets as live sequences deepen and the adaptive horizon
    policy's picks depend on queue depth, so a measured replay that
    reaches a composition the warmup passes never hit pays a mid-run
    decode compile that stalls every in-flight request — pinning both
    collapses the decode program space to ONE program per engine so
    warmup coverage is complete.  (Numerics are bitwise-identical
    either way; only bytes-read and dispatch cadence change, which is
    exactly what ``ServiceModel.from_replay`` measures.)"""
    import paddle_tpu as paddle
    from ..models import GPTConfig, GPTForCausalLM
    from ..serving import Engine, EngineConfig
    from ..serving.gateway import Gateway, GatewayConfig

    cfg = GPTConfig(vocab_size=128, hidden_size=64,
                    intermediate_size=128, num_hidden_layers=2,
                    num_attention_heads=4,
                    max_position_embeddings=max_seq_len)
    engines = []
    for _ in range(int(n_replicas)):
        paddle.seed(seed)
        m = GPTForCausalLM(cfg)
        m.eval()
        engines.append(Engine(
            m, EngineConfig(num_slots=num_slots,
                            max_seq_len=max_seq_len,
                            max_horizon=max_horizon,
                            ragged_attention=False),
            register_profiler=False))
    return Gateway(engines,
                   GatewayConfig(model_id=model_id)).start()


def warm_gateway(gw, trace, speed=20.0, passes=2):
    """Replay ``trace`` against a live gateway ``passes`` times and
    discard the results: compiles the (lane-bucket, length-bucket)
    prefill and (horizon, nb) decode programs the measured replay will
    exercise.  Without this, multi-second jit compiles land inside the
    first requests' TTFT and poison the sim-vs-live calibration.  Two
    passes by default — routing is affinity-stable so the second pass
    mops up the lane-bucket combinations the first pass's co-batch
    timing happened to miss.  Clears the engines' flight recorders
    afterwards so the measured replay's record matching starts from a
    clean pool."""
    from .loadgen import replay

    for _ in range(int(passes)):
        replay(trace, gw, speed=speed)
    for w in gw.workers:
        rec = getattr(getattr(w, "engine", None), "recorder", None)
        if rec is not None:
            rec.clear()


def fleet_report(shapes=("chat", "mixed"), replica_counts=(1, 2, 4),
                 n_requests=48, seed=0, live=False, speed=4.0,
                 slo=None, tolerance=0.15, model=None, num_slots=4,
                 live_replica_counts=(1, 2), warmup=True,
                 live_shape="calib"):
    """The CLI ``fleet`` mode's engine: attainment-vs-replica-count
    curves for each named workload shape (``loadgen.SHAPES``) from one
    invocation, optionally closed against a LIVE CPU-proxy fleet.

    Sim-only (default): the service model comes from ``model``, else
    from the ProgramCard registry, else defaults.  With ``live=True``,
    the ``live_shape`` trace (default the no-abort/no-deadline
    ``calib`` probe, so the gate is not flaky near wall-clock races)
    is replayed against real gateways at ``live_replica_counts``, the
    service model is calibrated from the largest live fleet's replay,
    and a :func:`calibration_report` (ordering exact + attainment
    within ``tolerance``) is attached — the row FLEET_BENCH.json
    commits and check-bench gates."""
    from .loadgen import replay

    slo = slo or SLOSpec()
    shapes = list(shapes)
    replica_counts = [int(n) for n in replica_counts]
    traces = {}
    for name in shapes:
        if name not in SHAPES:
            raise ValueError(f"unknown workload shape {name!r} "
                             f"(known: {sorted(SHAPES)})")
        traces[name] = generate(SHAPES[name](seed=seed,
                                             n_requests=n_requests))

    calibration = None
    live_summaries = {}
    if live:
        live_reports = {}
        probe = live_shape if live_shape in SHAPES else shapes[0]
        live_trace = traces.get(probe)
        if live_trace is None:
            live_trace = generate(SHAPES[probe](seed=seed,
                                                n_requests=n_requests))
        for n in live_replica_counts:
            gw = build_cpu_proxy_gateway(n, seed=seed,
                                         num_slots=num_slots)
            try:
                if warmup:
                    warm_gateway(gw, live_trace, speed=speed)
                live_reports[int(n)] = replay(live_trace, gw,
                                              speed=speed, slo=slo)
            finally:
                gw.shutdown()
        if model is None:
            model = ServiceModel.from_replay(
                live_reports[max(live_reports)])
        calibration = calibration_report(
            live_trace, live_reports, model, speed=speed,
            tolerance=tolerance, num_slots=num_slots)
        calibration["shape"] = probe
        calibration["trace_digest"] = live_trace.digest()
        live_summaries = {
            str(n): {k: v for k, v in rep.items() if k != "records"}
            for n, rep in live_reports.items()}
    if model is None:
        model = ServiceModel.from_program_cards()

    out_shapes = {}
    for name in shapes:
        out_shapes[name] = {
            "spec": dataclasses.asdict(traces[name].spec),
            "trace_digest": traces[name].digest(),
            "curve": attainment_curve(traces[name], replica_counts,
                                      model, speed=speed, slo=slo,
                                      num_slots=num_slots),
        }
    return {
        "shapes": out_shapes,
        "replica_counts": replica_counts,
        "speed": float(speed),
        "slo": {"ttft_s": slo.ttft_s, "tpot_s": slo.tpot_s},
        "service_model": model.to_json(),
        "live": {"enabled": bool(live), "reports": live_summaries},
        "calibration": calibration,
        "ok": calibration is None or calibration["ok"],
    }
