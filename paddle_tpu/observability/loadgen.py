"""Trace-driven load generation: deterministic, production-shaped
workload traces plus a live-gateway replay harness.

The fleet observatory's first half (observability phase 5).  A
:class:`WorkloadSpec` describes a traffic shape the way capacity
planners do — arrival process, length distributions, tenant mix,
prefix reuse, admission tiers — and :func:`generate` expands it into a
concrete :class:`WorkloadTrace`:

* **heavy-tailed lengths** — prompt lengths are lognormal (median ×
  ``exp(sigma * N(0,1))``, clipped), output budgets are Pareto
  (``xm * (1 + Pareto(alpha))``, clipped): a few long requests dominate
  token volume, as in production;
* **bursty arrivals** — a 2-state Markov-modulated Poisson process
  (calm/burst states with exponential dwell, the burst state multiplies
  the rate by ``burst_factor``), so inter-arrival times are
  overdispersed (CV > 1), not memoryless;
* **shared-prefix populations** — each request draws a "system prompt"
  population from a Zipf over ``n_prefix_populations`` and prepends
  that population's fixed ``prefix_len`` tokens, so the radix cache and
  the router's prefix affinity see realistic reuse skew;
* **multi-tenant mix** — tenants drawn from their own Zipf;
* **admission mixes** — a priority distribution over interactive
  tiers, a ``deadline_fraction`` with uniform deadlines, an
  ``abort_fraction`` applied to BURST-state arrivals only (an "abort
  storm": clients hang up exactly when the system is busiest), and a
  ``batch_fraction`` routed to the offline batch lane
  (``priority=-1``, non-streaming, no deadline — interactive traffic
  overtakes it without bound).

Determinism is the contract: generation draws every random variate
from one seeded ``numpy`` Generator, uses **virtual time** only (no
wall-clock reads, per the PTA513 doctrine), and serializes through
:meth:`WorkloadTrace.to_json` as canonical JSON (sorted keys, fixed
separators, rounded floats) — the same seed produces a byte-identical
trace in any process, so a trace digest pins a benchmark's workload
the way a git SHA pins its code.

The second half is :func:`replay`: drive a generated trace against a
LIVE serving gateway over real HTTP/SSE (``speed`` compresses virtual
time so a 5-minute trace replays in seconds), then reconstruct
per-phase latency — queue wait, prefill/TTFT, decode TPOT — from the
engines' RequestTrace flight records and aggregate SLO attainment per
tenant and per priority tier with :func:`summarize`.  ``summarize``
takes any list of per-request records with these fields, so a
modelled run and a live one can be rolled up alike
(tests/test_fleet_observatory.py builds such records by hand).
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
from dataclasses import dataclass

import numpy as np

#: canonical trace-document format tag (bump on incompatible change)
TRACE_FORMAT = "paddle_tpu.workload_trace/1"

#: aggregation label of the offline batch lane (``priority < 0``)
BATCH_TIER = "batch"


def tier_of(priority):
    """Aggregation tier of a priority: ``"batch"`` for the offline
    lane, ``"p<N>"`` for interactive tiers."""
    p = int(priority)
    return BATCH_TIER if p < 0 else f"p{p}"


# --------------------------------------------------------------------- spec
@dataclass(frozen=True)
class WorkloadSpec:
    """One traffic shape, fully determined by its fields + ``seed``.

    Defaults are sized for the CPU-proxy engines the test/CI fleet
    runs (``max_seq_len=64``): ``prompt_len_max + max_new_tokens_cap``
    stays within a tiny engine's sequence budget."""

    seed: int = 0
    n_requests: int = 64
    # ---- arrivals: 2-state Markov-modulated Poisson (virtual seconds)
    rate_rps: float = 8.0
    burst_factor: float = 4.0
    calm_dwell_s: float = 4.0
    burst_dwell_s: float = 1.0
    # ---- tenant mix (Zipf-skewed: tenant0 is the whale)
    n_tenants: int = 3
    tenant_zipf_a: float = 1.2
    # ---- heavy-tailed lengths
    prompt_len_median: int = 12
    prompt_len_sigma: float = 0.7
    prompt_len_max: int = 40
    output_pareto_xm: float = 3.0
    output_pareto_alpha: float = 2.0
    max_new_tokens_cap: int = 12
    # ---- shared-prefix populations (Zipf over system prompts)
    n_prefix_populations: int = 8
    prefix_zipf_a: float = 1.3
    prefix_len: int = 8
    # ---- admission mixes
    priority_levels: tuple = (0, 1, 2)
    priority_weights: tuple = (0.7, 0.2, 0.1)
    #: fraction routed to the offline batch lane (priority=-1, no SSE)
    batch_fraction: float = 0.0
    deadline_fraction: float = 0.0
    deadline_min_s: float = 0.5
    deadline_max_s: float = 4.0
    #: abort storm: this fraction of BURST-state interactive arrivals
    #: disconnect ``abort_after_s`` (virtual) after submit
    abort_fraction: float = 0.0
    abort_after_s: float = 0.25
    #: prompt token ids are drawn uniformly from [0, vocab)
    vocab: int = 120

    def validate(self):
        if self.n_requests < 1:
            raise ValueError("n_requests must be >= 1")
        if not self.rate_rps > 0 or not self.burst_factor >= 1:
            raise ValueError("need rate_rps > 0 and burst_factor >= 1")
        if self.prefix_len < 1 or self.prompt_len_max <= self.prefix_len:
            raise ValueError("need prompt_len_max > prefix_len >= 1")
        if len(self.priority_levels) != len(self.priority_weights):
            raise ValueError("priority_levels/priority_weights length "
                             "mismatch")
        if any(int(p) < 0 for p in self.priority_levels):
            raise ValueError("priority_levels are interactive tiers "
                             "(>= 0); the batch lane comes from "
                             "batch_fraction")
        for f in ("batch_fraction", "deadline_fraction",
                  "abort_fraction"):
            v = getattr(self, f)
            if not 0.0 <= float(v) <= 1.0:
                raise ValueError(f"{f} must be in [0, 1], got {v}")
        return self


@dataclass
class WorkloadRequest:
    """One generated request: everything a replay client or the
    simulator needs, in virtual time."""

    index: int
    #: virtual seconds from trace start (divide by the replay
    #: ``speed`` for wall seconds)
    t_submit: float
    tenant: str
    #: -1 = offline batch lane; >= 0 interactive
    priority: int
    prompt_ids: list
    #: leading tokens shared with every request of ``prefix_pop``
    prefix_len: int
    prefix_pop: int
    max_new_tokens: int
    deadline_s: float | None
    #: virtual seconds after submit at which the client hangs up
    #: (None = patient client)
    abort_after_s: float | None
    #: interactive requests stream over SSE; the batch lane does not
    stream: bool
    #: True when the MMPP was in its burst state at arrival
    arrived_in_burst: bool

    @property
    def tier(self):
        return tier_of(self.priority)

    @property
    def prompt_len(self):
        return len(self.prompt_ids)


class WorkloadTrace:
    """A generated workload: the spec it came from plus its concrete
    request list, with canonical byte-stable serialization."""

    def __init__(self, spec, requests):
        self.spec = spec
        self.requests = list(requests)

    def __len__(self):
        return len(self.requests)

    @property
    def duration_s(self):
        """Virtual seconds from trace start to the last submit."""
        return self.requests[-1].t_submit if self.requests else 0.0

    def to_json(self):
        """Canonical serialization: sorted keys, minimal separators,
        floats pre-rounded at generation — the same spec+seed is
        byte-identical across processes (tested via subprocess)."""
        doc = {"format": TRACE_FORMAT,
               "spec": dataclasses.asdict(self.spec),
               "requests": [dataclasses.asdict(r)
                            for r in self.requests]}
        return json.dumps(doc, sort_keys=True, separators=(",", ":"))

    def digest(self):
        """sha256 of the canonical serialization — the workload's
        provenance stamp (tests/test_fleet_observatory.py pins three)."""
        return hashlib.sha256(self.to_json().encode()).hexdigest()

    @classmethod
    def from_json(cls, text):
        doc = json.loads(text)
        if doc.get("format") != TRACE_FORMAT:
            raise ValueError(
                f"not a workload trace document "
                f"(format={doc.get('format')!r})")
        sd = dict(doc["spec"])
        sd["priority_levels"] = tuple(sd["priority_levels"])
        sd["priority_weights"] = tuple(sd["priority_weights"])
        return cls(WorkloadSpec(**sd),
                   [WorkloadRequest(**r) for r in doc["requests"]])


def _zipf_weights(n, a):
    w = 1.0 / np.arange(1, n + 1, dtype=float) ** float(a)
    return w / w.sum()


def generate(spec):
    """Expand a :class:`WorkloadSpec` into a concrete
    :class:`WorkloadTrace`.  Every variate comes from one seeded PCG64
    Generator and all times are virtual — no wall-clock reads."""
    spec.validate()
    rng = np.random.default_rng(int(spec.seed))
    # shared-prefix populations: each "system prompt" is a fixed token
    # run drawn once, so same-population requests share radix-cache
    # blocks and hash to the same affinity key
    prefixes = [[int(t) for t in
                 rng.integers(0, spec.vocab, size=spec.prefix_len)]
                for _ in range(spec.n_prefix_populations)]
    pop_p = _zipf_weights(spec.n_prefix_populations, spec.prefix_zipf_a)
    ten_p = _zipf_weights(spec.n_tenants, spec.tenant_zipf_a)
    pri_p = np.asarray(spec.priority_weights, dtype=float)
    pri_p = pri_p / pri_p.sum()

    t = 0.0
    in_burst = False
    state_left = float(rng.exponential(spec.calm_dwell_s))
    requests = []
    for i in range(spec.n_requests):
        # MMPP: draw the next arrival, crossing state boundaries as
        # the exponential dwell expires
        while True:
            rate = spec.rate_rps * (spec.burst_factor if in_burst
                                    else 1.0)
            gap = float(rng.exponential(1.0 / rate))
            if gap <= state_left:
                state_left -= gap
                t += gap
                break
            t += state_left
            in_burst = not in_burst
            state_left = float(rng.exponential(
                spec.burst_dwell_s if in_burst else spec.calm_dwell_s))
        tenant = f"tenant{int(rng.choice(spec.n_tenants, p=ten_p))}"
        pop = int(rng.choice(spec.n_prefix_populations, p=pop_p))
        plen = int(np.clip(
            round(spec.prompt_len_median
                  * float(np.exp(rng.normal(0.0, spec.prompt_len_sigma)))),
            spec.prefix_len + 1, spec.prompt_len_max))
        suffix = [int(x) for x in
                  rng.integers(0, spec.vocab, size=plen - spec.prefix_len)]
        budget = int(np.clip(
            round(spec.output_pareto_xm
                  * (1.0 + float(rng.pareto(spec.output_pareto_alpha)))),
            1, spec.max_new_tokens_cap))
        if float(rng.random()) < spec.batch_fraction:
            priority, deadline, abort_after, stream = -1, None, None, False
        else:
            priority = int(spec.priority_levels[int(
                rng.choice(len(spec.priority_levels), p=pri_p))])
            deadline = (round(float(rng.uniform(
                spec.deadline_min_s, spec.deadline_max_s)), 6)
                if float(rng.random()) < spec.deadline_fraction else None)
            abort_after = (float(spec.abort_after_s)
                           if in_burst
                           and float(rng.random()) < spec.abort_fraction
                           else None)
            stream = True
        requests.append(WorkloadRequest(
            index=i, t_submit=round(t, 6), tenant=tenant,
            priority=priority, prompt_ids=prefixes[pop] + suffix,
            prefix_len=spec.prefix_len, prefix_pop=pop,
            max_new_tokens=budget, deadline_s=deadline,
            abort_after_s=abort_after, stream=stream,
            arrived_in_burst=in_burst))
    return WorkloadTrace(spec, requests)


# ------------------------------------------------------- workload shapes
def chat_heavy(seed=0, n_requests=64, **overrides):
    """Interactive chat fleet: no batch lane, deadline and abort-storm
    mixes on."""
    kw = dict(seed=seed, n_requests=n_requests, batch_fraction=0.0,
              deadline_fraction=0.2, abort_fraction=0.15)
    kw.update(overrides)
    return WorkloadSpec(**kw)


def mixed_chat_batch(seed=0, n_requests=64, **overrides):
    """Mixed fleet: a third of traffic rides the offline batch lane
    (priority=-1, non-streaming) under the same interactive foreground."""
    kw = dict(seed=seed, n_requests=n_requests, batch_fraction=0.35,
              deadline_fraction=0.15, abort_fraction=0.1)
    kw.update(overrides)
    return WorkloadSpec(**kw)


def calibration_probe(seed=0, n_requests=32, **overrides):
    """Gentle, deterministic-outcome workload for sim-vs-live
    calibration: no client aborts and no deadlines (both race the wall
    clock, so their outcome flips run-to-run near the boundary and
    would make the calibration gate flaky), mild arrival rate.  The
    calibration regime is deliberately UNCONTENDED — on a shared-core
    CI host, co-located replicas cannot beat one replica once host
    compute saturates, so the live side can only certify the service-
    time model where queueing, not the host, is the story."""
    kw = dict(seed=seed, n_requests=n_requests, rate_rps=6.0,
              burst_factor=2.0, batch_fraction=0.0,
              deadline_fraction=0.0, abort_fraction=0.0)
    kw.update(overrides)
    return WorkloadSpec(**kw)


#: named shapes the CLI ``fleet`` mode exposes
SHAPES = {"chat": chat_heavy, "mixed": mixed_chat_batch,
          "calib": calibration_probe}


# ------------------------------------------------------------ SLO + rollup
@dataclass(frozen=True)
class SLOSpec:
    """Attainment thresholds, in wall seconds at replay speed.  A
    request ATTAINS when it completed (not shed/aborted/expired) with
    ``ttft_s`` and ``tpot_s`` within threshold; batch-lane requests
    attain on completion alone (throughput tier, no latency SLO)."""

    ttft_s: float = 2.0
    tpot_s: float = 0.5


def _attains(rec, slo):
    if not rec.get("completed"):
        return False
    if rec.get("tier") == BATCH_TIER:
        return True
    ttft = rec.get("ttft_s")
    if ttft is None or ttft > slo.ttft_s:
        return False
    tpot = rec.get("tpot_s")
    return tpot is None or tpot <= slo.tpot_s


def _pctl(values, q):
    if not values:
        return None
    return round(float(np.percentile(np.asarray(values, float), q)), 6)


def summarize(records, slo=None):
    """Roll normalized per-request records (replay or sim) into the
    fleet report: counts, shed/abort/deadline rates, per-phase latency
    percentiles, prefix hit ratio, and SLO attainment overall, per
    tenant, and per priority tier.

    A record is a dict with: ``tenant``, ``tier``, ``completed``,
    ``status`` (HTTP code or sim disposition), ``shed``, ``aborted``,
    ``deadline_expired``, ``queue_s``/``ttft_s``/``tpot_s`` (None when
    unknown), ``tokens``, ``prompt_tokens``, ``prefix_hit_tokens``."""
    slo = slo or SLOSpec()
    records = list(records)
    n = len(records)
    done = [r for r in records if r.get("completed")]
    shed = sum(1 for r in records if r.get("shed"))
    aborted = sum(1 for r in records if r.get("aborted"))
    expired = sum(1 for r in records if r.get("deadline_expired"))
    prompt_tok = sum(r.get("prompt_tokens", 0) for r in done)
    hit_tok = sum(r.get("prefix_hit_tokens", 0) for r in done)

    def _phase(key):
        vals = [r[key] for r in records if r.get(key) is not None]
        return {"p50": _pctl(vals, 50), "p95": _pctl(vals, 95),
                "max": _pctl(vals, 100), "n": len(vals)}

    def _group(keyfn):
        out = {}
        for r in records:
            g = out.setdefault(keyfn(r), {"requests": 0, "completed": 0,
                                          "tokens": 0, "shed": 0,
                                          "attained": 0})
            g["requests"] += 1
            g["completed"] += int(bool(r.get("completed")))
            g["tokens"] += int(r.get("tokens", 0))
            g["shed"] += int(bool(r.get("shed")))
            g["attained"] += int(_attains(r, slo))
        for g in out.values():
            g["attainment"] = round(g["attained"] / g["requests"], 6)
        return dict(sorted(out.items()))

    attained = sum(1 for r in records if _attains(r, slo))
    return {
        "requests": n,
        "completed": len(done),
        "shed": shed,
        "aborted": aborted,
        "deadline_expired": expired,
        "tokens_total": sum(r.get("tokens", 0) for r in records),
        "prefix_hit_ratio": (round(hit_tok / prompt_tok, 6)
                             if prompt_tok else 0.0),
        "phase_latency": {"queue_s": _phase("queue_s"),
                          "ttft_s": _phase("ttft_s"),
                          "tpot_s": _phase("tpot_s")},
        "slo": {"ttft_s": slo.ttft_s, "tpot_s": slo.tpot_s},
        "attainment": round(attained / n, 6) if n else 1.0,
        "per_tenant": _group(lambda r: r.get("tenant", "")),
        "per_tier": _group(lambda r: r.get("tier", "p0")),
    }


# ------------------------------------------------------------- live replay
def _phase_from_events(events):
    """Reconstruct (queue_s, ttft_s, tpot_s, tokens, prefix_hits) from
    one flight-record event list (``RequestTrace.to_json()['events']``):
    queue wait is submit -> first prefill admission, TTFT is submit ->
    first sampled token, TPOT averages the decode span over the tokens
    it emitted."""
    t_admit = t_first = t_last = None
    tokens = 0
    prefix_hits = 0
    for ev in events:
        kind, t = ev.get("kind"), ev.get("t", 0.0)
        if kind == "prefill" and t_admit is None:
            t_admit = t
            prefix_hits = ev.get("prefix_hit_tokens", prefix_hits)
        elif kind == "first_token":
            if t_first is None:
                t_first = t
            tokens += 1
            t_last = t
        elif kind == "decode":
            tokens += ev.get("tokens", 0)
            t_last = t
    tpot = None
    if t_first is not None and t_last is not None and tokens > 1:
        tpot = (t_last - t_first) / (tokens - 1)
    return t_admit, t_first, tpot, tokens, prefix_hits


def fleet_flight_records(gateway):
    """Per-request flight records across every replica's engine
    recorder, as ``RequestTrace.to_json()`` dicts (the replay hook the
    phase reconstruction reads)."""
    out = []
    for w in gateway.workers:
        rec = getattr(getattr(w, "engine", None), "recorder", None)
        if rec is None:
            continue
        doc = rec.to_json()
        out.extend(doc["recent"])
        out.extend(doc["live"])
    return out


def replay(trace, gateway, speed=20.0, slo=None, timeout_s=60.0):
    """Replay a trace against a STARTED gateway over real HTTP/SSE.

    One client thread per request sleeps until its (speed-compressed)
    submit time, POSTs ``/v1/completions`` — SSE for interactive,
    blocking JSON for the batch lane — and records status, streamed
    token ids, client-side TTFT, and disposition.  Requests with
    ``abort_after_s`` close their connection mid-stream (the abort
    storm).  After the last response, per-phase latencies are
    reconstructed from the engines' flight records and rolled up with
    :func:`summarize`; the returned report carries the raw per-request
    records under ``"records"`` (token ids under ``"token_ids"``) for
    parity checks and reconciliation."""
    import http.client
    import threading
    import time

    if not getattr(gateway, "running", False):
        raise RuntimeError("replay needs a started gateway")
    speed = float(speed)
    if speed <= 0:
        raise ValueError("speed must be > 0")
    host, port = gateway.config.host, gateway.port
    model_id = gateway.config.model_id
    records = [None] * len(trace.requests)
    t0 = time.monotonic()

    def _client(req):
        rec = {"index": req.index, "tenant": req.tenant,
               "tier": req.tier, "priority": req.priority,
               "prompt_tokens": req.prompt_len, "tokens": 0,
               "completed": False, "shed": False, "aborted": False,
               "deadline_expired": False, "queue_s": None,
               "ttft_s": None, "tpot_s": None, "token_ids": [],
               "prefix_hit_tokens": 0}
        records[req.index] = rec
        delay = req.t_submit / speed - (time.monotonic() - t0)
        if delay > 0:
            time.sleep(delay)
        payload = {"model": model_id, "prompt": req.prompt_ids,
                   "max_tokens": req.max_new_tokens,
                   "temperature": 0.0, "tenant": req.tenant,
                   "priority": req.priority, "stream": req.stream}
        if req.deadline_s is not None:
            payload["deadline_s"] = req.deadline_s / speed
        conn = http.client.HTTPConnection(host, port,
                                          timeout=timeout_s)
        t_send = time.monotonic()
        try:
            conn.request("POST", "/v1/completions",
                         json.dumps(payload),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            rec["status"] = resp.status
            if resp.status != 200:
                body = json.loads(resp.read() or b"{}")
                rec["error"] = body.get("error", {}).get("code")
                rec["shed"] = resp.status in (429, 503)
                return
            if not req.stream:
                body = json.loads(resp.read())
                choice = body["choices"][0]
                rec["token_ids"] = list(choice["token_ids"])
                rec["tokens"] = len(rec["token_ids"])
                reason = choice["finish_reason"]
                rec["aborted"] = reason == "abort"
                rec["completed"] = not rec["aborted"]
                return
            cutoff = (t_send + req.abort_after_s / speed
                      if req.abort_after_s is not None else None)
            reason = None
            while True:
                if cutoff is not None and time.monotonic() > cutoff:
                    rec["aborted"] = True   # client hangs up mid-storm
                    return
                line = resp.readline()
                if not line:
                    break
                line = line.strip()
                if not line.startswith(b"data: "):
                    continue
                data = line[len(b"data: "):]
                if data == b"[DONE]":
                    break
                choice = json.loads(data)["choices"][0]
                ids = choice["token_ids"]
                if ids and rec["ttft_s"] is None:
                    rec["ttft_s"] = time.monotonic() - t_send
                rec["token_ids"].extend(int(i) for i in ids)
                if choice["finish_reason"] is not None:
                    reason = choice["finish_reason"]
            rec["tokens"] = len(rec["token_ids"])
            rec["aborted"] = reason == "abort"
            rec["deadline_expired"] = (rec["aborted"]
                                       and req.deadline_s is not None)
            rec["completed"] = reason in ("stop", "length")
        except Exception as e:  # client-side failure is a record, not
            rec["error"] = f"{type(e).__name__}: {e}"   # a crash
        finally:
            conn.close()

    threads = [threading.Thread(target=_client, args=(r,), daemon=True)
               for r in trace.requests]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=timeout_s + trace.duration_s / speed)

    # phase reconstruction from the engines' flight records: match by
    # per-request identity (tenant + prompt length + token count is
    # ambiguous, so match the whole output stream where possible)
    flights = fleet_flight_records(gateway)
    by_stream = {}
    for fl in flights:
        q, ttft, tpot, toks, hits = _phase_from_events(fl["events"])
        by_stream.setdefault(
            (fl["counts"]["tokens_emitted"],), []).append(
                {"queue_s": q, "ttft_s": ttft, "tpot_s": tpot,
                 "prefix_hit_tokens": hits, "flight": fl})
    for rec in records:
        if rec is None or not rec.get("completed"):
            continue
        pool = by_stream.get((rec["tokens"],))
        if pool:
            ph = pool.pop(0)
            rec["queue_s"] = ph["queue_s"]
            if rec["ttft_s"] is None:
                rec["ttft_s"] = ph["ttft_s"]
            rec["tpot_s"] = ph["tpot_s"]
            rec["prefix_hit_tokens"] = ph["prefix_hit_tokens"]

    report = summarize([r for r in records if r is not None], slo=slo)
    report["speed"] = speed
    report["trace_digest"] = trace.digest()
    report["records"] = [r for r in records if r is not None]
    return report


def reconcile_tokens(gateway, report):
    """Token-conservation check between a replay report and the
    engines themselves: client-streamed tokens (completed requests),
    flight-record emitted tokens, and the engines' per-tenant ledger
    must tell one story.  Returns the three totals; on a drain-clean
    fleet with no client aborts they are equal."""
    client = sum(r.get("tokens", 0) for r in report["records"]
                 if r.get("completed"))
    flight = sum(fl["counts"]["tokens_emitted"]
                 for fl in fleet_flight_records(gateway))
    ledger = 0
    for w in gateway.workers:
        eng = getattr(w, "engine", None)
        if eng is None:
            continue
        for counts in eng.tenant_ledger().values():
            ledger += counts.get("tokens_generated", 0)
    return {"client_tokens": client, "flight_tokens": flight,
            "ledger_tokens": ledger}
