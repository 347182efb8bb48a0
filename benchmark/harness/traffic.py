"""The one general traffic generator: a traffic file's parameters -> the
operations each client sends. Standard library only (the load generator's
processes import it).

A traffic file (`benchmark/traffic/<name>.json`) holds

    loop       "closed": a client sends its next operation when the last
               one was answered ("open" with `target_rate` is reserved for a
               later cell; the generator refuses what it cannot do)
    clients    client threads; `processes` spreads them over processes
    ops_per_client_per_s   upper estimate used to size the pre-drawn list
    operations weighted templates, each of kind
        "query"        `pql` with {placeholders} filled from `args`
        "import_bits"  `pairs` (row, column) pairs into one `field`, `row`
                       and `shard`, acknowledged, then read back with
                       `readback` restricted to that shard

An argument is drawn from one of
    {"choice": [...]}                       uniform over the list
    {"zipf": {"n": N, "s": S, "base": B}}   B..B+N-1, weight 1/rank^S
    {"client": {"base": B}}                 B + the client's number
    {"client_shard": true}                  one shard per client, drawn once
                                            from the seed over the
                                            configuration's shards
Every seed sends the same mix in another order.
"""

import itertools
import json
import random


def load(path):
    with open(path) as f:
        spec = json.load(f)
    if spec.get("loop") != "closed" or spec.get("target_rate") is not None:
        raise ValueError(f"{path}: only closed loops are generated yet")
    total = sum(op["weight"] for op in spec["operations"])
    if not spec["operations"] or total <= 0:
        raise ValueError(f"{path}: no weighted operation")
    return spec


def _domain(arg):
    if "choice" in arg:
        return list(arg["choice"]), None
    if "zipf" in arg:
        z = arg["zipf"]
        values = [z.get("base", 0) + i for i in range(z["n"])]
        return values, [1.0 / (i + 1) ** z["s"] for i in range(z["n"])]
    return None, None


def distinct_queries(spec):
    """Every query text the file can send (the oracle answers each once).
    A query template's arguments must have finite, client-free domains."""
    out = []
    for op in spec["operations"]:
        if op["kind"] != "query":
            continue
        names = sorted(op.get("args", {}))
        domains = []
        for name in names:
            values, _ = _domain(op["args"][name])
            if values is None:
                raise ValueError(
                    f"query argument {name!r} has no finite domain")
            domains.append(values)
        for combo in itertools.product(*domains):
            out.append(op["pql"].format(**dict(zip(names, combo))))
    return sorted(set(out))


class ClientDraw:
    """One client's stream of operations from (seed, client number)."""

    def __init__(self, spec, config, seed, client):
        self.spec = spec
        self.config = config
        self.client = client
        self.rng = random.Random(f"ops:{int(seed)}:{int(client)}")
        self.shard = random.Random(
            f"shard:{int(seed)}:{int(client)}").randrange(config["shards"])
        self.weights = [op["weight"] for op in spec["operations"]]

    def _value(self, arg):
        values, weights = _domain(arg)
        if values is not None:
            if weights is None:
                return self.rng.choice(values)
            return self.rng.choices(values, weights)[0]
        if "client" in arg:
            return arg["client"].get("base", 0) + self.client
        if "client_shard" in arg:
            return self.shard
        raise ValueError(f"unknown distribution {arg!r}")

    def draw(self):
        """One operation as a plain dict (`requests` says how it is sent)."""
        return self.fill(self.rng.choices(
            self.spec["operations"], self.weights)[0])

    def fill(self, op):
        """The template `op` with its arguments drawn."""
        if op["kind"] == "query":
            args = {k: self._value(v)
                    for k, v in sorted(op.get("args", {}).items())}
            return {"kind": "query", "name": op["name"],
                    "pql": op["pql"].format(**args)}
        if op["kind"] == "import_bits":
            field = self._value(op["field"])
            row = self._value(op["row"])
            shard = self._value(op["shard"])
            width = self.config["shard_width"]
            cols = [shard * width + self.rng.randrange(width)
                    for _ in range(op["pairs"])]
            return {"kind": "import_bits", "name": op["name"],
                    "field": field, "row": row, "shard": shard,
                    "columns": cols,
                    "readback": op["readback"].format(field=field, row=row)}
        raise ValueError(f"unknown operation kind {op['kind']!r}")

    def warm_ops(self):
        """One operation of every template, for the warm-up."""
        return [self.fill(op) for op in self.spec["operations"]]


def requests(op, index, profile=False):
    """How an operation goes over HTTP: [(path, body, content type)], the
    operation itself and, for an import, its read-back restricted to the
    written shard (without the restriction each read-back would build a
    whole-index stack for a row of a few bits and evict the hot rows)."""
    if op["kind"] == "query":
        path = f"/index/{index}/query" + ("?profile=true" if profile else "")
        return [(path, op["pql"].encode(), "text/plain")]
    body = json.dumps({"rowIDs": [op["row"]] * len(op["columns"]),
                       "columnIDs": op["columns"]}).encode()
    return [(f"/index/{index}/field/{op['field']}/import", body,
             "application/json"),
            (f"/index/{index}/query?shards={op['shard']}",
             op["readback"].encode(), "text/plain")]
