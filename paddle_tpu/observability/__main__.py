"""``python -m paddle_tpu.observability`` — dump the process's live
observability state.

Modes:
  snapshot      nested JSON of every metric + legacy provider (default)
  prometheus    text exposition (# HELP / # TYPE / samples)
  trace         chrome-trace JSON of the event timeline
  programs      program-card registry: per-compiled-program FLOPs,
                bytes-accessed, compile seconds (--json for raw dump)
  mesh          live HybridCommunicateGroup topology (axes, dims, comm
                rank-lists) + the collective-comms ledger, as JSON —
                the CLI twin of the ``/debug/mesh`` endpoint
  serve         start the telemetry HTTP endpoint (blocks; --port,
                --duration to exit after N seconds)

``-o FILE`` writes to a file instead of stdout. ``--exec SCRIPT`` runs a
Python file first (in this process), so the dump reflects an actual
workload — the one-process analog of scraping a serving worker. With
``serve``, ``--exec`` runs the script while the endpoint is already up,
so it can be scraped mid-workload.
"""

from __future__ import annotations

import argparse
import json
import sys


def main(argv=None):
    parser = argparse.ArgumentParser(
        prog="python -m paddle_tpu.observability",
        description="dump paddle_tpu observability state")
    parser.add_argument("mode", nargs="?", default="snapshot",
                        choices=("snapshot", "prometheus", "trace",
                                 "programs", "mesh", "serve"))
    parser.add_argument("-o", "--output", default=None,
                        help="write to FILE instead of stdout")
    parser.add_argument("--exec", dest="script", default=None,
                        help="run a Python script first, then dump")
    parser.add_argument("--json", action="store_true",
                        help="programs mode: raw JSON instead of a table")
    parser.add_argument("--port", type=int, default=9400,
                        help="serve mode: port to bind (0 = ephemeral)")
    parser.add_argument("--duration", type=float, default=None,
                        help="serve mode: exit after N seconds "
                        "(default: serve until interrupted)")
    args = parser.parse_args(argv)

    if args.mode == "serve":
        return _serve(args)

    if args.script:
        with open(args.script) as f:
            code = compile(f.read(), args.script, "exec")
        exec(code, {"__name__": "__main__", "__file__": args.script})

    from . import events, metrics

    if args.mode == "snapshot":
        text = json.dumps(metrics.snapshot(), indent=2, default=repr)
    elif args.mode == "prometheus":
        text = metrics.render_prometheus()
    elif args.mode == "programs":
        from . import profiling

        text = (json.dumps(profiling.to_json(), indent=2, default=repr)
                if args.json else profiling.render_text())
    elif args.mode == "mesh":
        from . import comms

        text = json.dumps(comms.mesh_json(), indent=2, default=repr)
    else:
        text = events.export_chrome_trace()

    if args.output:
        with open(args.output, "w") as f:
            f.write(text)
    else:
        sys.stdout.write(text if text.endswith("\n") else text + "\n")
    return 0


def _serve(args):
    import time

    from .server import TelemetryServer

    srv = TelemetryServer(port=args.port).start()
    print(f"telemetry listening on {srv.url()} "
          f"(endpoints: /metrics /healthz /readyz /debug/requests "
          f"/debug/slo /debug/programs /trace)", flush=True)
    try:
        if args.script:
            with open(args.script) as f:
                code = compile(f.read(), args.script, "exec")
            exec(code, {"__name__": "__main__", "__file__": args.script})
        if args.duration is not None:
            time.sleep(args.duration)
        else:
            while True:
                time.sleep(3600)
    except KeyboardInterrupt:
        pass
    finally:
        srv.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
