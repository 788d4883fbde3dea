"""Launcher implementation (see package docstring; ref launch/main.py (U))."""

from __future__ import annotations

import argparse
import os
import runpy
import subprocess
import sys
import time


def _parse(argv):
    p = argparse.ArgumentParser(
        prog="paddle_tpu.distributed.launch",
        description="TPU training launcher (one process per host)")
    p.add_argument("--nnodes", type=str, default="1",
                   help="node count or range 'N' / 'N:M'")
    p.add_argument("--master", type=str, default=None,
                   help="coordinator address host:port (rank-0 host)")
    p.add_argument("--rank", type=int,
                   default=int(os.getenv("POD_RANK", os.getenv("RANK", "0"))),
                   help="this host's rank in [0, nnodes)")
    p.add_argument("--log_dir", type=str, default=None,
                   help="write per-rank logs to this dir")
    p.add_argument("--max_restarts", type=int, default=0,
                   help="watcher: relaunch the script this many times on "
                        "failure (autoresume from user checkpoints)")
    p.add_argument("--elastic_np", type=str, default=None,
                   help="elastic mode: 'min:max' node range; membership is "
                        "tracked via PADDLE_ELASTIC_DIR heartbeats and a "
                        "scale event relaunches the script (ref fleet "
                        "elastic, SURVEY.md §5)")
    p.add_argument("--devices", "--gpus", "--tpus", type=str, default=None,
                   help="visible device ids (TPU: informational)")
    p.add_argument("script", type=str, help="training script")
    p.add_argument("script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def _export_env(args):
    nnodes = int(str(args.nnodes).split(":")[0])
    env = {
        "PADDLE_TRAINER_ID": str(args.rank),
        "PADDLE_TRAINERS_NUM": str(nnodes),
        "RANK": str(args.rank),
        "WORLD_SIZE": str(nnodes),
    }
    if args.master:
        eps = [args.master]
        env["PADDLE_TRAINER_ENDPOINTS"] = ",".join(eps)
        env["PADDLE_CURRENT_ENDPOINT"] = args.master if args.rank == 0 else ""
        env["MASTER_ADDR"], _, port = args.master.partition(":")
        env["MASTER_PORT"] = port or "8090"
    if args.devices:
        env["FLAGS_selected_tpus"] = args.devices
    os.environ.update(env)
    return env


# a crashed run only resets the restart budget if it survived this long —
# longer than any plausible startup + XLA compile, so deterministic
# post-startup crashes still exhaust max_restarts
_RECOVERY_SECS = float(os.getenv("PADDLE_ELASTIC_RECOVERY_SECS", "300"))


def _run_elastic(args):
    """Elastic supervisor: register membership, run the trainer as a
    subprocess, relaunch on scale events (autoresume from checkpoints)."""
    from ..fleet.elastic import ElasticManager, ElasticStatus

    import signal as _signal

    mgr = ElasticManager(node_id=str(args.rank), np=args.elastic_np).enter()
    current = {"proc": None}

    def _on_term(signum, frame):
        # deregister AND take the trainer down with us — an orphaned trainer
        # would keep training against the shrunken membership's checkpoints.
        # terminate -> wait -> kill escalation mirrors the in-loop teardown.
        p = current["proc"]
        if p is not None and p.poll() is None:
            p.terminate()
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        mgr.exit(completed=False)
        raise SystemExit(128 + signum)

    _signal.signal(_signal.SIGTERM, _on_term)
    failures = 0
    try:
        while True:
            # wait for quorum
            while mgr.poll() == ElasticStatus.HOLD:
                time.sleep(mgr.interval)
            if mgr.poll() == ElasticStatus.EXIT:
                print("[launch.elastic] above max_np; exiting", file=sys.stderr)
                return 0
            world = mgr.world_size()
            env = dict(os.environ,
                       PADDLE_TRAINERS_NUM=str(world),
                       WORLD_SIZE=str(world))
            started = time.time()
            current["proc"] = proc = subprocess.Popen(
                [sys.executable, args.script] + list(args.script_args), env=env)
            # watch for membership change while the trainer runs
            status = None
            while proc.poll() is None:
                status = mgr.poll()
                if status in (ElasticStatus.RESTART, ElasticStatus.EXIT):
                    proc.terminate()
                    try:
                        proc.wait(timeout=30)
                    except subprocess.TimeoutExpired:
                        proc.kill()
                    break
                time.sleep(mgr.interval)
            if status == ElasticStatus.EXIT:
                return 0
            if status == ElasticStatus.RESTART:
                print(f"[launch.elastic] scale event -> world={mgr.world_size()}; "
                      f"relaunching (autoresume from checkpoint)", file=sys.stderr)
                continue
            rc = proc.returncode
            current["proc"] = None
            if rc == 0:
                return 0
            if time.time() - started > _RECOVERY_SECS:
                # ran productively for a while before this crash — treat it
                # as a NEW incident (restart budgets are per-incident). The
                # threshold must exceed startup+XLA-compile time or a
                # deterministic post-startup crash would loop forever.
                failures = 0
            failures += 1
            if failures > args.max_restarts:
                print(f"[launch.elastic] trainer failed rc={rc}; restarts "
                      f"exhausted ({args.max_restarts})", file=sys.stderr)
                return rc
            print(f"[launch.elastic] trainer failed rc={rc}; relaunch "
                  f"({failures}/{args.max_restarts})", file=sys.stderr)
            time.sleep(3 * mgr.interval)
    finally:
        mgr.exit()


def launch(argv=None):
    args = _parse(argv if argv is not None else sys.argv[1:])
    _export_env(args)

    if args.log_dir:
        os.makedirs(args.log_dir, exist_ok=True)

    if args.elastic_np:
        return _run_elastic(args)

    attempt = 0
    while True:
        if attempt == 0 and not args.log_dir and not args.master:
            # common case: run in-process (no fork) — jax owns the devices.
            # Multi-node runs (--master) MUST fork instead: paddle_tpu
            # joins the coordination service while it is imported, from
            # the env contract _export_env() has only just written, and
            # this launcher process imported it before that.
            sys.argv = [args.script] + list(args.script_args)
            runpy.run_path(args.script, run_name="__main__")
            return 0
        # watcher mode: subprocess so a crash can be observed and
        # restarted.  A chip belongs to one process at a time and the
        # worker needs it: this parent has imported paddle_tpu, which
        # initializes no backend (tests/test_chip_smoke.py), and must
        # never touch jax itself.
        log = None
        if args.log_dir:
            log = open(os.path.join(
                args.log_dir, f"workerlog.{args.rank}.{attempt}"), "w")
        child_env = dict(os.environ)
        # the worker must resolve imports from the launch cwd, like the
        # in-process path does (script dir becomes sys.path[0] otherwise)
        child_env["PYTHONPATH"] = os.getcwd() + os.pathsep + \
            child_env.get("PYTHONPATH", "")
        proc = subprocess.run(
            [sys.executable, args.script] + list(args.script_args),
            stdout=log or None, stderr=subprocess.STDOUT if log else None,
            env=child_env)
        if log:
            log.close()
        if proc.returncode == 0:
            return 0
        if attempt >= args.max_restarts:
            print(f"[launch] worker failed (rc={proc.returncode}), "
                  f"restarts exhausted", file=sys.stderr)
            return proc.returncode
        attempt += 1
        print(f"[launch] worker failed (rc={proc.returncode}); restart "
              f"{attempt}/{args.max_restarts} (autoresume from checkpoint)",
              file=sys.stderr)
        time.sleep(3)


def main():
    raise SystemExit(launch())


if __name__ == "__main__":
    main()
