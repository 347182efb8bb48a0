#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics` (the cell's end-to-end metrics, or with
`--trace 1` its per-layer metrics), `device`, with `--trace 1`
`breakdown`, and last `compared`: every number `correct` rests on beside
its limit. Without a TPU the run fails and prints no such line.

This process is the server: its main thread runs `pilosa_tpu.cli.main(
["server", ...])` with the configuration's flags and nothing else set, and
a driver thread (benchmark/harness/cell.py) does the rest over HTTP.

`--rehearse-shards N` with an explicit JAX_PLATFORMS=cpu runs the same
control flow at N shards on the host: answers are compared, no time, rate
or share is printed under a metric's name, and the exit code is 3. It is
a rehearsal, never a measurement. `--fault <name>` breaks the timed path
first (benchmark/harness/faults.py): such a run has to read
`"correct": false`.
"""

import time

T0 = time.monotonic()       # set-up is counted from here

import argparse     # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402
import threading    # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "benchmark")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

DEADLINE_S = 340    # a run must have ended well inside the driver's 360 s
REHEARSAL_EXIT = 3


def parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse-shards", type=int, default=None)
    p.add_argument("--fault", default=None)
    p.add_argument("--keep-records", default=None, metavar="PATH",
                   help="write every request's record to PATH (gzipped "
                        "JSON), for a look at stalls and sub-windows")
    p.add_argument("--keep-trace", default=None, metavar="PATH",
                   help="with --trace 1: write PATH.events.json.gz and "
                        "PATH.planes.txt")
    return p.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    on_cpu = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    if args.rehearse_shards is not None and not on_cpu:
        raise SystemExit("benchmark: --rehearse-shards needs an explicit "
                         "JAX_PLATFORMS=cpu")
    if on_cpu and args.rehearse_shards is None:
        raise SystemExit("benchmark: JAX_PLATFORMS=cpu is for "
                         "--rehearse-shards; a measurement needs the chip")

    from harness import cell
    from pilosa_tpu import cli

    run = cell.Run(args, T0)
    if args.fault:
        from harness import faults

        faults.apply(args.fault)

    def give_up():
        cell.say(f"no end after {DEADLINE_S} s: giving up")
        run.cleanup()
        os._exit(4)

    watchdog = threading.Timer(DEADLINE_S, give_up)
    watchdog.daemon = True
    watchdog.start()
    driver = run.thread()
    driver.start()
    try:
        # the normal server, on the main thread (its signal handlers need
        # it); returns after the driver's SIGINT and a graceful shutdown
        rc = cli.main(run.server_argv())
        driver.join()
    finally:
        watchdog.cancel()
        run.cleanup()
    if run.error or run.result is None or rc != 0:
        print(run.error or f"benchmark: server returned {rc}",
              file=sys.stderr)
        return 1
    result = run.result
    for name, pair in result["compared"].items():
        print(f"benchmark: compared {name} = {pair['value']} "
              f"(limit {pair['limit']})", file=sys.stderr)
    if args.rehearse_shards is not None:
        # control flow and answers only: names of what was readable, no
        # number under a metric's name
        result["metrics_readable"] = sorted(result.pop("metrics"))
        result["device"].pop("busy_s", None)
        result["device"].pop("window_s", None)
        result["device"].pop("idle_share_by_device", None)
        result.pop("breakdown", None)
        result["rehearsal"] = True
        result["compared"] = result.pop("compared")     # stays last
        print(json.dumps(result), flush=True)
        return REHEARSAL_EXIT
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
