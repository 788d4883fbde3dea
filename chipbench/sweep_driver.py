#!/usr/bin/env python3
"""chipbench/sweep.py's rate sweep for a mix whose driver builds its own
engine and leads into its window (sweep.py calls chipbench/serving.py's
directly): one engine from the driver's `build_engine`, warmed once, then
ONE trace that climbs through the rates, on the chip.  The first rate is
offered for the mix's `lead_in_s` before its window, every later one for
`--settle` seconds, so that each window of `--seconds` begins in the state
its rate leaves the engine in, and not on an empty one.  A window's row
gives the queue and the live lanes at its two ends and the tokens the
engine generated in between; the tokens/s of the rates whose queue grows
are the capacity the rate rule of chipbench/README.md starts from.  Nothing
is waited for after the last window.

    python3 chipbench/sweep_driver.py --workload <name> --rates 3.5,4,4.5,5 --seconds 20 --seed 7
"""

import argparse
import copy
import importlib
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None):
    from chipbench import harness, loadgen, serving

    p = argparse.ArgumentParser(prog="chipbench/sweep_driver.py")
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--settle", type=float, default=10.0)
    p.add_argument("--seed", type=int, default=7)
    p.add_argument("--out")
    args = p.parse_args(argv)
    cell = harness.Cell(ROOT, harness.load_json(
        os.path.join(ROOT, "BENCHMARK.json")), args.workload)
    devices, info = harness.require_chip(cell, "chipbench/sweep_driver.py")
    harness.enable_compile_cache(ROOT)
    meter = harness.CompileMeter()
    driver = importlib.import_module(
        "chipbench.drivers." + cell.traffic["driver"])
    t0 = time.perf_counter()

    def log(msg):
        print(f"[sweep {time.perf_counter() - t0:7.1f}s] {msg}", flush=True)

    ctx = harness.Context(cell, args.seed, args.seconds, False,
                          cell.peaks(info["kind"]),
                          os.path.join(ROOT, "chipbench", ".work"), log)
    st = driver.build_engine(ctx, warm=True)
    log(f"set-up {time.perf_counter() - t0:.1f} s, compiles "
        f"{meter.since((0, 0.0, 0))}, memory peak "
        f"{harness.peak_memory(devices)}")
    rates = [float(r) for r in args.rates.split(",")]
    requests, windows, t = [], [], 0.0
    for i, rate in enumerate(rates):
        mix = copy.deepcopy(cell.traffic)
        mix["arrivals"]["rate_rps"] = rate
        ahead = float(mix.get("lead_in_s", 0.0)) if i == 0 else args.settle
        part = loadgen.schedule(mix, args.seed + i, ahead + args.seconds,
                                cell.config["vocab_size"])
        for q in part["requests"]:
            q["index"], q["due_s"] = len(requests), q["due_s"] + t
            requests.append(q)
        windows.append((rate, t + ahead, t + ahead + args.seconds))
        t += ahead + args.seconds
    sched = {"mode": "open", "seconds": t, "drain_s": 30.0,
             "requests": requests}
    os.makedirs(ctx.work_dir, exist_ok=True)
    sched_path = os.path.join(ctx.work_dir, "schedule.sweep.json")
    with open(sched_path, "w") as f:
        json.dump(sched, f)
    client = subprocess.Popen(
        [sys.executable, os.path.join(ROOT, "chipbench", "loadclient.py"),
         sched_path, os.path.join(ctx.work_dir, "records.sweep.json"),
         "127.0.0.1", str(st.gateway.port)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    if client.stdout.readline().strip() != "ready":
        raise RuntimeError("the load client did not start")
    log(f"{len(requests)} requests over {t:.0f} s, rates {rates}")
    client.stdin.write("go\n")
    client.stdin.flush()
    t_go = time.monotonic()

    def reading_at(when):
        time.sleep(max(0.0, when - (time.monotonic() - t_go)))
        return (time.monotonic() - t_go, st.engine.stats(),
                serving.engine_programs(st.engine)["count"], meter.mark())

    keys = ("tokens_generated", "decode_steps", "prefill_calls",
            "prefill_requests")
    for rate, start, end in windows:
        t_a, a, progs_a, mark = reading_at(start)
        t_b, b, progs_b, _ = reading_at(end)
        row = {"rate_rps": rate, "from_s": t_a, "seconds": t_b - t_a,
               "gen_tokens_per_s": (b["tokens_generated"]
                                    - a["tokens_generated"]) / (t_b - t_a),
               "queue_at_go": a["queue_depth"],
               "queue_at_close": b["queue_depth"],
               "queue_grew": b["queue_depth"] - a["queue_depth"],
               "lanes_at_go": a["active_slots"],
               "lanes_at_close": b["active_slots"],
               **{k: b[k] - a[k] for k in keys},
               "programs_compiled_in_window": progs_b - progs_a,
               "xla_compiles_in_window": meter.since(mark)["xla_compiles"],
               "memory_peak_bytes": harness.peak_memory(devices)}
        print(json.dumps(row), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
    # answers are still due, and nobody reads them: end here, at once
    client.kill()
    sys.stdout.flush()
    os._exit(0)


if __name__ == "__main__":
    sys.exit(main())
