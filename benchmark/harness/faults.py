"""Ways to break the timed path underneath the harness, to show that
`correct` then reads false. `run.py --fault <name>` applies one before the
server starts; the driver never passes it, and a run with a fault prints
`"fault"` in its line.

Controls (each breaks a guarantee the configuration states):

    low-precision-count   a Count keeps only its high bits, as a reduce in
                          a narrower type would: "answers are exact" broken
    ack-without-apply     an import is acknowledged and not applied:
                          "an acknowledged write is readable at once" broken
    ack-without-append    an import_bits batch is applied and acknowledged
                          and never reaches the oplog: "imports ride the
                          oplog and are acknowledged after the append" broken

Faults of the timed path:

    answer-plus-one       one Count answer in seven is altered where it is
                          produced
    half-batch            an import applies the first half of its pairs
    first-chip-only       across chips: only the first chip's share of
                          every stack reaches the answer, as when the
                          exchange between chips is left out
"""


def _wrap_count(alter):
    from pilosa_tpu.exec.stacked import StackedEvaluator

    inner = StackedEvaluator._batched_count
    calls = [0]

    def _batched_count(self, sig, stacks):
        calls[0] += 1
        return alter(int(inner(self, sig, stacks)), calls[0])

    StackedEvaluator._batched_count = _batched_count


def _wrap_import(alter):
    from pilosa_tpu.core.field import Field

    inner = Field.import_bits

    def import_bits(self, row_ids, column_ids, timestamps=None, clear=False):
        return alter(inner, self, row_ids, column_ids, timestamps, clear)

    Field.import_bits = import_bits


def low_precision_count():
    _wrap_count(lambda count, n: count & ~0x3F)


def answer_plus_one():
    _wrap_count(lambda count, n: count + (n % 7 == 0))


def ack_without_apply():
    _wrap_import(lambda inner, field, rows, cols, ts, clear: len(cols))


def ack_without_append():
    from pilosa_tpu.server.api import API

    inner = API._oplog_append

    def _oplog_append(self, kind, kwargs):
        return None if kind == "bits" else inner(self, kind, kwargs)

    API._oplog_append = _oplog_append


def half_batch():
    def alter(inner, field, rows, cols, ts, clear):
        half = len(cols) // 2
        inner(field, rows[:half], cols[:half], ts, clear)
        return len(cols)

    _wrap_import(alter)


def first_chip_only():
    import numpy as np

    from pilosa_tpu.exec.stacked import StackedEvaluator

    inner = StackedEvaluator._place

    def _place(self, host_stack, shard_axis):
        chips = self._n_pad_devices()
        if chips > 1:
            host_stack = np.array(host_stack, copy=True)
            rest = [slice(None)] * host_stack.ndim
            rest[shard_axis] = slice(
                host_stack.shape[shard_axis] // chips, None)
            host_stack[tuple(rest)] = 0
        return inner(self, host_stack, shard_axis)

    StackedEvaluator._place = _place


FAULTS = {
    "low-precision-count": low_precision_count,
    "answer-plus-one": answer_plus_one,
    "ack-without-apply": ack_without_apply,
    "ack-without-append": ack_without_append,
    "half-batch": half_batch,
    "first-chip-only": first_chip_only,
}


def apply(name):
    try:
        FAULTS[name]()
    except KeyError:
        raise SystemExit(f"benchmark: no fault named {name!r}; "
                         f"have {sorted(FAULTS)}") from None
