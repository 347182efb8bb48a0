"""The plain reference: PQL set algebra over numpy bit planes.

Independent of `pilosa_tpu`: it imports numpy and nothing of the program,
and takes nothing the program has made. A plane is a uint32 array whose bit
`b` of word `w` is column `32 * w + b` (any leading axes are shards). The
grammar is the subset the traffic files send:

    Count(<tree>)      tree := Row(<field>=<row>)
                             | Intersect|Union|Difference|Xor(tree, tree, ...)

`evaluate(pql, planes)` returns the count a correct server answers.
"""

import re

import numpy as np

_TOKEN = re.compile(r"\s*([A-Za-z_][A-Za-z_0-9]*|\d+|[(),=])")

_FOLD = {
    "Intersect": np.bitwise_and,
    "Union": np.bitwise_or,
    "Xor": np.bitwise_xor,
    "Difference": lambda a, b: a & ~b,
}


def popcount(words):
    return int(np.bitwise_count(words).sum(dtype=np.int64))


def parse(pql):
    """PQL text -> nested tuples: ("Count", tree), (op, tree, ...),
    ("Row", field, row)."""
    tokens = _TOKEN.findall(pql)
    if "".join(tokens) != re.sub(r"\s+", "", pql):
        raise ValueError(f"oracle cannot tokenise {pql!r}")
    node, rest = _parse_call(tokens)
    if rest:
        raise ValueError(f"trailing input in {pql!r}")
    return node


def _parse_call(tokens):
    name, tokens = tokens[0], tokens[1:]
    if not tokens or tokens[0] != "(":
        raise ValueError(f"expected '(' after {name}")
    tokens = tokens[1:]
    if name == "Row":
        field, eq, row, close = tokens[:4]
        if eq != "=" or close != ")" or not row.isdigit():
            raise ValueError("oracle reads only Row(<field>=<int>)")
        return ("Row", field, int(row)), tokens[4:]
    if name != "Count" and name not in _FOLD:
        raise ValueError(f"oracle has no operator {name!r}")
    args = []
    while True:
        arg, tokens = _parse_call(tokens)
        args.append(arg)
        sep, tokens = tokens[0], tokens[1:]
        if sep == ")":
            break
        if sep != ",":
            raise ValueError(f"expected ',' or ')' in {name}")
    if name == "Count" and len(args) != 1:
        raise ValueError("Count takes one argument")
    return (name, *args), tokens


def leaves(node):
    """The Row leaves of a parsed query, with repeats, in order."""
    if node[0] == "Row":
        return [node]
    return [leaf for child in node[1:] for leaf in leaves(child)]


def plane(node, planes):
    if node[0] == "Row":
        return planes[node[1], node[2]]
    out = plane(node[1], planes)
    for child in node[2:]:
        out = _FOLD[node[0]](out, plane(child, planes))
    return out


def parse_count(pql):
    """The tree under a `Count(...)`, parsed once for many blocks of shards."""
    node = parse(pql)
    if node[0] != "Count":
        raise ValueError("oracle answers Count(...) only")
    return node[1]


def evaluate(pql, planes):
    return popcount(plane(parse_count(pql), planes))
