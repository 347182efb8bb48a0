"""The plain reference of the `ssb-*` configurations: filtered Sums
answered from per-row value columns.

Independent of `pilosa_tpu`: it imports numpy and nothing of the program,
and works from the rows' values (an order date, a discount, a quantity,
the measure), not from bit planes. The grammar is what the SSB traffic
files send:

    Sum(Intersect(Row(<c>), Row(<c>), ...), field=<measure>)

each condition <c> one of

    <f>=<v>              a date attribute carried on the row equals v
    <a> <= <f> <= <b>    an int field lies in [a, b]
    <f> < <v>            an int field lies below v

A `Table` sums the measure and counts the rows once over (day, and the
value of every int field but the measure); `tally(columns)` adds one
block of rows to it and `answer(query)` is the JSON a correct server
returns for the Sum, `{"value": ..., "count": ...}`: the sum of the
measure over the rows that meet every condition, and their number.

Exact: a block's sums go through numpy's float64 bincount only while no
cell of it can pass 2^53 (rows x the measure's largest value, checked),
so every partial sum is an integer float64 holds exactly; across blocks
and in the answer they are int64 and Python ints.
"""

import re

import numpy as np

_SUM = re.compile(r"Sum\(Intersect\((.*)\),\s*field=(\w+)\)")
_ROW = re.compile(r"Row\(([^()]*)\)")
_EQ = re.compile(r"(\w+)=(-?\d+)")
_BETWEEN = re.compile(r"(-?\d+)\s*<=\s*(\w+)\s*<=\s*(-?\d+)")
_BELOW = re.compile(r"(\w+)\s*<\s*(-?\d+)")


def parse(pql):
    """-> (measure, ((field, lo, hi), ...)): each condition as the
    inclusive range of values it admits."""
    m = _SUM.fullmatch(pql.strip())
    if m is None:
        raise ValueError(f"ssb reference cannot read {pql!r}")
    conditions = []
    for text in _ROW.findall(m[1]):
        text = text.strip()
        if (c := _BETWEEN.fullmatch(text)) is not None:
            conditions.append((c[2], int(c[1]), int(c[3])))
        elif (c := _BELOW.fullmatch(text)) is not None:
            conditions.append((c[1], None, int(c[2]) - 1))
        elif (c := _EQ.fullmatch(text)) is not None:
            conditions.append((c[1], int(c[2]), int(c[2])))
        else:
            raise ValueError(f"ssb reference cannot read Row({text})")
    return m[2], tuple(conditions)


class Table:
    """Sums of the measure and row counts over (day, each dimension)."""

    def __init__(self, cfg, dates):
        self.measure = cfg["measure"]
        ints = cfg["int_fields"]
        self.dims = sorted(f for f in ints if f != self.measure)
        self.lows = [ints[f]["min"] for f in self.dims]
        self.dates = {field: np.asarray(dates[attribute])
                      for field, attribute in cfg["set_fields"].items()}
        days = len(next(iter(self.dates.values())))
        self.shape = [days] + [ints[f]["max"] - ints[f]["min"] + 1
                               for f in self.dims]
        self.largest = ints[self.measure]["max"]
        self.sums = np.zeros(self.shape, np.int64)
        self.counts = np.zeros(self.shape, np.int64)

    def tally(self, columns):
        """Add one block of rows ({column: value of each row})."""
        flat = np.asarray(columns["orderdate"], np.int64)
        for field, low, size in zip(self.dims, self.lows, self.shape[1:]):
            at = np.asarray(columns[field], np.int64) - low
            if at.size and (at.min() < 0 or at.max() >= size):
                raise ValueError(f"a row's {field} is out of its range")
            flat = flat * size + at
        measure = np.asarray(columns[self.measure], np.int64)
        if measure.size and (measure.min() < 0
                             or measure.max() > self.largest):
            raise ValueError(f"a row's {self.measure} is out of its range")
        if len(flat) * self.largest >= 2 ** 53:
            raise ValueError("a block too large to sum exactly in float64")
        size = self.sums.size
        self.counts += np.bincount(flat, minlength=size).reshape(self.shape)
        self.sums += np.bincount(flat, weights=measure, minlength=size
                                 ).astype(np.int64).reshape(self.shape)

    def answer(self, query):
        measure, conditions = query
        if measure != self.measure:
            raise ValueError(f"the table sums {self.measure}, not {measure}")
        keep = [np.ones(n, bool) for n in self.shape]
        for field, lo, hi in conditions:
            if field in self.dates:
                values, axis = self.dates[field], 0
            else:
                axis = 1 + self.dims.index(field)
                values = self.lows[axis - 1] + np.arange(self.shape[axis])
            admitted = values <= hi
            if lo is not None:
                admitted &= values >= lo
            keep[axis] &= admitted
        sums, counts = self.sums, self.counts
        for axis, mask in enumerate(keep):
            sums = sums.compress(mask, axis=axis)
            counts = counts.compress(mask, axis=axis)
        return {"value": int(sums.sum()), "count": int(counts.sum())}
