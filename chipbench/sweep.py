#!/usr/bin/env python3
"""The sweep that reads an open-loop mix's capacity: one engine, warmed
once, then a window at each of a few rates, on the chip.  From some rate on
the queue at the close grows and the tokens/s stay flat: that flat reading
is the capacity, and the rate rule of chipbench/README.md fixes the cell's
rate from it (an overload cell offers 1.4 times the requests/s the engine
completes with its lanes full: the flat tokens/s over the mean generated
tokens a request of the mix's fixed trace).

    python3 chipbench/sweep.py --workload <name> --rates 4,6,8,10,12 --seconds 20 --seed 7
"""

import argparse
import copy
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None):
    from chipbench import harness, serving
    from chipbench.readers.mean_ms import percentile

    p = argparse.ArgumentParser(prog="chipbench/sweep.py")
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--seed", type=int, default=7)
    p.add_argument("--out")
    args = p.parse_args(argv)
    cell = harness.Cell(ROOT, harness.load_json(
        os.path.join(ROOT, "BENCHMARK.json")), args.workload)
    devices, info = harness.require_chip(cell, "chipbench/sweep.py")
    harness.enable_compile_cache(ROOT)
    meter = harness.CompileMeter()
    t0 = time.perf_counter()

    def log(msg):
        print(f"[sweep {time.perf_counter() - t0:7.1f}s] {msg}", flush=True)

    ctx = harness.Context(cell, args.seed, args.seconds, False,
                          cell.peaks(info["kind"]),
                          os.path.join(ROOT, "chipbench", ".work"), log)
    st = serving.build_engine(ctx, warm=True)
    log(f"set-up {time.perf_counter() - t0:.1f} s, compiles "
        f"{meter.since((0, 0.0, 0))}, memory peak "
        f"{harness.peak_memory(devices)}")
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = copy.deepcopy(cell.traffic)
        mix["arrivals"]["rate_rps"] = rate
        ctx.seed = args.seed + i
        serving.start_client(ctx, st, mix, args.seconds)
        mark = meter.mark()
        ctx.records = serving.window(ctx, st, args.seconds)
        at_close = st.stats_at_close
        serving.drain(ctx, st)
        e2e = serving.end_to_end(ctx, st)
        recs = ctx.records["requests"]
        ends = [r["end_s"] for r in recs if r["end_s"] is not None]
        ttft = ctx.records["ttft_s"]
        row = {"rate_rps": rate, "seconds": ctx.records["seconds"],
               "requests": len(recs),
               "complete": sum(1 for r in recs if r["ok"]),
               "ttft_p50_ms": percentile(ttft, 50) * 1e3,
               "ttft_p95_ms": e2e["ttft_p95_ms"]["value"],
               "gap_p99_ms": e2e["gap_p99_ms"]["value"],
               "queue_depth_at_close": at_close["queue_depth"],
               "active_slots_at_close": at_close["active_slots"],
               "last_answer_s_after_close": max(ends) - ctx.records["seconds"],
               "tokens_generated_per_s": ctx.records["engine"][
                   "tokens_generated"] / ctx.records["seconds"],
               "decode_steps": ctx.records["engine"]["decode_steps"],
               "late_p95_ms": percentile(ctx.records["late_s"], 95) * 1e3,
               "programs_compiled_in_window":
                   ctx.records["programs_compiled_in_window"],
               "xla_compiles_in_window": meter.since(mark)["xla_compiles"],
               "memory_peak_bytes": harness.peak_memory(devices)}
        print(json.dumps(row), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
    st.free()
    return 0


if __name__ == "__main__":
    sys.exit(main())
