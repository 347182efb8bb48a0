"""`BENCHMARK.json` and the files its names lead to. Every cell,
configuration, traffic mix and metric is found by the name in its entry:
this module and `run.py` hold none of those names.

    cell <name>     -> BENCHMARK.json `workloads`
    config <name>   -> the entry's `file` (a JSON object of sizes, its
                       `data_module` in benchmark/data/, its server flags)
    traffic <name>  -> benchmark/traffic/<name>.json
    metric <name>   -> benchmark/metrics/<name>.py exposing read(ctx);
                       where one quantity is split over cells that report
                       different end-to-end metrics (`<name>.<suffix>`, each
                       with its own `moves`) and the longer name has no
                       file, the shorter name's file reads it
"""

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def load(root=ROOT):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _named(entries, name, what):
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise SystemExit(f"benchmark: no {what} named {name!r} in BENCHMARK.json")


def cell(manifest, name):
    return _named(manifest["workloads"], name, "workload")


def config(manifest, name, root=ROOT):
    entry = _named(manifest["configs"], name, "configuration")
    with open(os.path.join(root, entry["file"])) as f:
        return json.load(f)


def traffic_path(name):
    return os.path.join(BENCH, "traffic", name + ".json")


def metrics(manifest, group, cell_name):
    """The `end_to_end` or `per_layer` entries this cell reports."""
    return [m for m in manifest[group]
            if "workloads" not in m or cell_name in m["workloads"]]


def reader_path(name):
    """benchmark/metrics/<name>.py, else the file of the longest dotted
    prefix of `name` that has one."""
    path = os.path.join(BENCH, "metrics", name + ".py")
    while not os.path.exists(path) and "." in name:
        name = name.rpartition(".")[0]
        path = os.path.join(BENCH, "metrics", name + ".py")
    return path


def reader(name):
    """`read(ctx)` of the metric's file."""
    path = reader_path(name)
    spec = importlib.util.spec_from_file_location(
        "benchmetric_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read
