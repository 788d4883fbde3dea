#!/usr/bin/env python
"""Fleet-observatory benchmark (observability phase 5): the committed
FLEET_BENCH.json rows the ``check-bench`` regression gate enforces.

Two sections:

* ``sim_curve`` — SLO-attainment-vs-replica-count curves from the
  discrete-event capacity simulator (``fleetsim.simulate``) for the
  chat-heavy and mixed chat+batch workload shapes, under a PINNED
  reference service model (constants below, chosen near the live
  CPU-proxy calibration so the curves sit in the queueing-bound
  regime).  The simulator reads no clock and draws no randomness, so
  these rows are exact run-to-run — any drift is a real behavior
  change in the trace generator, the router/admission model, or the
  rollup math.
* ``calibration`` — the sim-vs-live loop: replay the no-abort
  ``calib`` workload probe over real HTTP/SSE against live 1- and
  2-replica CPU-proxy gateways (tiny identical-weight engines, warmed
  so jit compiles stay out of the measured run), calibrate a service
  model from the observed TTFT/TPOT, and gate the simulator's
  attainment predictions: replica-count ordering must be consistent
  (tie-aware — see ``fleetsim.calibration_report``) and worst
  attainment error within tolerance.  The calibration regime is
  deliberately UNCONTENDED: on a shared-core CI host, co-located
  replicas cannot beat one replica once host compute saturates, so
  the live side certifies the service-time model, while capacity
  scaling is the (deterministic) simulator's claim.

Gated ``value`` fields are all attainment-like fractions (higher is
better, robust at ~1.0) or the 0/1 ordering flag; noisy wall-clock
latencies ride along as ungated informational fields.

Prints one JSON line per metric; writes FLEET_BENCH.json at the repo
root when run there (merge-preserving, same provenance discipline as
bench_decode.py: schema_version, git sha, monotonic run_id).
"""

import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

SCHEMA_VERSION = 3

SECTIONS = ("sim_curve", "calibration")

#: pinned reference service model for the sim_curve rows — near the
#: live CPU-proxy calibration (prefill ~9 ms/token, decode ~7 ms/token
#: at max_horizon=1) so the curves are representative, but CONSTANT so
#: the rows never move unless the simulator/trace generator does
REF_MODEL = {"prefill_s_per_token": 9e-3,
             "decode_s_per_token": 7e-3,
             "overhead_s": 1e-3}

#: sim_curve knobs: heavy arrival rate + tight TTFT so the curve is
#: queueing-bound and strictly separates replica counts
SIM_RATE_RPS = 24.0
SIM_SPEED = 4.0
SIM_SLO = {"ttft_s": 0.35, "tpot_s": 0.25}
SIM_REPLICAS = (1, 2, 4)

#: calibration knobs: gentle load, generous SLO (the live gate must
#: not sit on a knife edge on a shared CI runner)
CAL_N_REQUESTS = 32
CAL_SPEED = 4.0
CAL_SLO = {"ttft_s": 2.0, "tpot_s": 0.5}
CAL_TOLERANCE = 0.25


def _git_sha():
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short=12", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__)))
        sha = out.stdout.strip()
        return sha if out.returncode == 0 and sha else "unknown"
    except Exception:
        return "unknown"


def _bench_sim_curve(backend):
    from paddle_tpu.observability import fleetsim, loadgen

    model = fleetsim.ServiceModel(**REF_MODEL)
    slo = loadgen.SLOSpec(**SIM_SLO)
    rows = []
    for shape in ("chat", "mixed"):
        trace = loadgen.generate(loadgen.SHAPES[shape](
            seed=0, n_requests=48, rate_rps=SIM_RATE_RPS))
        curve = fleetsim.attainment_curve(
            trace, SIM_REPLICAS, model, speed=SIM_SPEED, slo=slo)
        for c in curve:
            p95 = c["p95_ttft_s"]
            rows.append({
                "metric": (f"fleet sim attainment {shape} "
                           f"r{c['replicas']} seed0 ({backend})"),
                "value": c["attainment"],
                "unit": "attained fraction",
                # deterministic companions (informational; the sim is
                # exact, so value itself already gates at tolerance)
                "completed": c["completed"],
                "shed": c["shed"],
                "tokens_total": c["tokens_total"],
                "p95_ttft_ms": (round(p95 * 1e3, 2)
                                if p95 is not None else None),
                "trace_digest": trace.digest()[:12],
                "slo_ttft_s": SIM_SLO["ttft_s"],
                "rate_rps": SIM_RATE_RPS,
                "sim_speed": SIM_SPEED,
            })
    return rows


def _bench_calibration(backend):
    from paddle_tpu.observability import fleetsim, loadgen

    report = fleetsim.fleet_report(
        shapes=("calib",), replica_counts=(1, 2),
        n_requests=CAL_N_REQUESTS, seed=0, live=True, speed=CAL_SPEED,
        slo=loadgen.SLOSpec(**CAL_SLO), tolerance=CAL_TOLERANCE)
    cal = report["calibration"]
    live2 = report["live"]["reports"]["2"]
    ttft = live2["phase_latency"]["ttft_s"]
    tpot = live2["phase_latency"]["tpot_s"]
    rows = [
        {
            "metric": (f"fleet sim-vs-live attainment agreement "
                       f"calib ({backend})"),
            "value": round(1.0 - cal["max_abs_err"], 6),
            "unit": "agreement fraction",
            "max_abs_err": cal["max_abs_err"],
            "tolerance": cal["tolerance"],
            "calibration_rows": cal["rows"],
            "service_model": report["service_model"],
            "trace_digest": cal["trace_digest"][:12],
        },
        {
            "metric": (f"fleet sim-vs-live replica ordering "
                       f"consistent calib ({backend})"),
            "value": 1.0 if cal["ordering_consistent"] else 0.0,
            "unit": "bool",
            "ordering_exact": cal["ordering_exact"],
            "tie_eps": cal["tie_eps"],
        },
        {
            "metric": f"fleet live attainment calib r2 ({backend})",
            "value": live2["attainment"],
            "unit": "attained fraction",
            # wall-clock latencies are runner noise — informational
            "ttft_p50_ms": round(ttft["p50"] * 1e3, 2),
            "ttft_p95_ms": round(ttft["p95"] * 1e3, 2),
            "tpot_p50_ms": round(tpot["p50"] * 1e3, 2),
            "completed": live2["completed"],
            "tokens_total": live2["tokens_total"],
            "prefix_hit_ratio": live2["prefix_hit_ratio"],
        },
    ]
    return rows


def main(argv=None):
    import argparse

    import jax

    parser = argparse.ArgumentParser(
        description="fleet-observatory benchmark suite")
    parser.add_argument(
        "--only", default=None,
        help="comma-separated section filter (choices: %s); a filtered "
             "run only replaces its OWN rows in FLEET_BENCH.json"
             % ",".join(SECTIONS))
    parser.add_argument(
        "--out", default=None,
        help="write this run's rows to FILE (fresh document, committed "
             "FLEET_BENCH.json untouched) — the input the check-bench "
             "regression gate compares against the committed baseline")
    args = parser.parse_args(argv)
    if args.only is None:
        only = set(SECTIONS)
    else:
        only = set(s.strip() for s in args.only.split(",") if s.strip())
        unknown = only - set(SECTIONS)
        if unknown:
            parser.error("unknown section(s) %s; choices: %s"
                         % (sorted(unknown), ",".join(SECTIONS)))

    from paddle_tpu.observability.memory import backend_bandwidth_gbs

    backend = jax.default_backend()
    bw_gbs = backend_bandwidth_gbs(jax.devices()[0].device_kind)
    results = []
    if "sim_curve" in only:
        results.extend(_bench_sim_curve(backend))
    if "calibration" in only:
        results.extend(_bench_calibration(backend))

    # --out: a fresh standalone document for the check-bench gate —
    # provenance still stamped, committed FLEET_BENCH.json untouched
    if args.out is not None:
        sha = _git_sha()
        for r in results:
            r["schema_version"] = SCHEMA_VERSION
            r["git_sha"] = sha
            r["run_id"] = 0
            r.setdefault("roofline_bw_gbs", bw_gbs)
        for r in results:
            print(json.dumps(r))
        with open(args.out, "w") as f:
            json.dump({"backend": backend, "results": results},
                      f, indent=1)
        return

    # merge-preserving write (bench_decode.py's discipline): rows from
    # OTHER backends survive, same-backend rows are replaced — all of
    # them on a full run, only the re-measured metrics on --only —
    # and every new row carries provenance with a monotonic run_id.
    out = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "FLEET_BENCH.json")

    def _same_backend(metric):
        return metric.endswith((f"({backend})", f", {backend})"))

    new_metrics = {r["metric"] for r in results}

    def _keep(metric):
        if args.only is not None:
            return metric not in new_metrics
        return not _same_backend(metric)

    kept, run_id = [], 1
    if os.path.exists(out):
        try:
            with open(out) as f:
                prev = json.load(f)
            prev_rows = prev.get("results", [])
            latest = {}
            for r in prev_rows:
                if _keep(r.get("metric", "")):
                    latest[r.get("metric", "")] = r
            kept = list(latest.values())
            run_id = 1 + max((int(r.get("run_id", 0))
                              for r in prev_rows), default=0)
        except (ValueError, OSError):
            kept, run_id = [], 1
    sha = _git_sha()
    for r in results:
        r["schema_version"] = SCHEMA_VERSION
        r["git_sha"] = sha
        r["run_id"] = run_id
        r.setdefault("roofline_bw_gbs", bw_gbs)
    for r in results:
        print(json.dumps(r))
    with open(out, "w") as f:
        json.dump({"backend": backend, "results": kept + results},
                  f, indent=1)


if __name__ == "__main__":
    sys.exit(main())
