"""Helper process: one call into a configuration's data module.

    python benchmark/harness/child.py <load|expected> < request.json

Run as a child of `run.py` (never forked: the parent holds the chip), so
that building roaring blobs and the numpy oracle use other cores than the
server's interpreter. Imports nothing that imports JAX. Reads one JSON
request on stdin, prints one JSON answer as its last stdout line.
"""

import importlib
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def data_module(name):
    """`benchmark/data/<name>.py`, imported as `data.<name>` so that pool
    workers (spawned with this process's sys.path) can import it too."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    return importlib.import_module("data." + name)


def main():
    what = sys.argv[1]
    req = json.load(sys.stdin)
    module = data_module(req["config"]["data_module"])
    if what == "load":
        out = module.load(req["config"], req["seed"], req["url"])
    elif what == "expected":
        out = module.expected(req["config"], req["seed"], req["pqls"])
    else:
        raise SystemExit(f"child: unknown call {what!r}")
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
