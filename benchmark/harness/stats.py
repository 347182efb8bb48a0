"""Arithmetic on the load generator's records: the window, rates and
percentiles. Standard library only.

A request belongs to the window when its answer arrived inside it, so a
request stalled across the window's end is not dropped from the tail of
the next reading but never counted twice either. Every percentile is over
all requests of the window, never over medians of chunks.
"""

RECORD = ("client", "kind", "sent", "done", "status", "answer", "gap",
          "expect", "pql")
CLIENT, KIND, SENT, DONE, STATUS, ANSWER, GAP, EXPECT, PQL = range(len(RECORD))


def percentile(values, q):
    """The q-th percentile (0..100) by linear interpolation between the
    two nearest ranks; None for no values."""
    if not values:
        return None
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def in_window(records, opened, closed):
    return [r for r in records if opened <= r[DONE] < closed]


def ok(record):
    """Answered, and with what it had to say."""
    _, kind, _, _, status, answer, _, expect, _ = record
    if status != 200:
        return False
    return kind == "import_bits" or answer == expect


def latencies_ms(records, kind):
    return [(r[DONE] - r[SENT]) * 1e3 for r in records
            if r[KIND] == kind and ok(r)]


def rate(records, kind, seconds):
    return sum(1 for r in records if r[KIND] == kind and ok(r)) / seconds


def summary(records, opened, closed):
    """What a run reports of its window: counts by kind, failures, and
    the numbers `correct` rests on."""
    window = in_window(records, opened, closed)
    by_kind = {}
    for r in window:
        by_kind[r[KIND]] = by_kind.get(r[KIND], 0) + 1
    return {
        "seconds": closed - opened,
        "by_kind": by_kind,
        "attempted": len(window),
        "failed": sum(1 for r in window if not ok(r)),
    }


def wrong(records):
    """(answers that say the wrong thing, requests never answered) over
    every record handed in, read-backs included."""
    said_wrong = sum(1 for r in records if r[STATUS] == 200 and not ok(r))
    unanswered = sum(1 for r in records if r[STATUS] != 200)
    return said_wrong, unanswered
