"""PQL AST (reference: pql/ast.go).

A Query is a list of Calls; a Call has a name, an args dict, and child
calls. Comparison args hold Condition values; the between conditional
(`4 < field <= 9`) folds into a BETWEEN condition with adjusted bounds.
"""

# Condition operators (reference: pql/token.go:25-31).
EQ = "=="
NEQ = "!="
LT = "<"
LTE = "<="
GT = ">"
GTE = ">="
BETWEEN = "><"

RESERVED_ARGS = {"from", "to"}  # plus any _-prefixed (reference: ast.go:281)


def is_reserved_arg(name):
    return name.startswith("_") or name in RESERVED_ARGS


class Condition:
    __slots__ = ("op", "value")

    def __init__(self, op, value):
        self.op = op
        self.value = value

    def int_values(self):
        """Bounds for BETWEEN (list) or single predicate."""
        if isinstance(self.value, list):
            return [int(v) for v in self.value]
        return [int(self.value)]

    def __eq__(self, other):
        return (isinstance(other, Condition)
                and self.op == other.op and self.value == other.value)

    def __repr__(self):
        return f"Condition({self.op!r}, {self.value!r})"


class Call:
    __slots__ = ("name", "args", "children")

    def __init__(self, name, args=None, children=None):
        self.name = name
        self.args = args or {}
        self.children = children or []

    def field_arg(self):
        """The single non-reserved arg key (reference: Call.FieldArg)."""
        for key in self.args:
            if not is_reserved_arg(key):
                return key
        raise ValueError("no field argument specified")

    def has_conditions(self):
        return any(isinstance(v, Condition) for v in self.args.values())

    def shape(self):
        """Literal-free normal form for workload fingerprinting
        (utils/workload.py): call name, arg KEYS (field names), condition
        operators, and child nesting survive; row ids, values, and time
        bounds collapse to `_`. `field=`/`_field=` values ARE field names,
        so they survive too — Rows(f) and Rows(g) are different shapes,
        Row(f=3) and Row(f=9) are the same shape."""
        out = []
        self._shape_into(out)
        return "".join(out)

    def _shape_into(self, out):
        # append-based builder: shape() runs once per served query, and
        # nested f-string joins were its single largest per-query cost
        out.append(self.name)
        out.append("(")
        sep = ""
        for c in self.children:
            out.append(sep)
            c._shape_into(out)
            sep = ","
        for key in sorted(self.args):
            out.append(sep)
            sep = ","
            value = self.args[key]
            if key in ("field", "_field"):
                out.append(f"{key}={value}")
            elif isinstance(value, Condition):
                out.append(f"{key}{value.op}_")
            else:
                out.append(key)
                out.append("=_")
        out.append(")")

    def __eq__(self, other):
        return (isinstance(other, Call) and self.name == other.name
                and self.args == other.args and self.children == other.children)

    def __repr__(self):
        parts = [repr(c) for c in self.children]
        parts += [f"{k}={v!r}" for k, v in self.args.items()]
        return f"{self.name}({', '.join(parts)})"

    def writes(self):
        """True when the call mutates data (reference: executor write set)."""
        return self.name in {
            "Set", "Clear", "ClearRow", "Store", "SetRowAttrs",
            "SetColumnAttrs"}


class Query:
    __slots__ = ("calls",)

    def __init__(self, calls=None):
        self.calls = calls or []

    def write_calls(self):
        return [c for c in self.calls if c.writes()]

    def shape(self):
        """Normalized shape of the whole query (see Call.shape)."""
        out = []
        sep = ""
        for c in self.calls:
            out.append(sep)
            c._shape_into(out)
            sep = ";"
        return "".join(out)

    def __eq__(self, other):
        return isinstance(other, Query) and self.calls == other.calls

    def __repr__(self):
        return f"Query({self.calls!r})"
