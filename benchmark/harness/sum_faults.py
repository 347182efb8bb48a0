"""Faults of the Sum path, for a cell whose traffic sends only Sums:
`harness/faults.py`'s count faults wrap
`StackedEvaluator._batched_count`, which a Sum never calls, so they leave
such a cell's answers whole.

    sum-low-precision     every Sum's per-plane counts keep only their
                          high bits (the low half of each plane's
                          cross-shard reduce loses its last six bits), as a
                          reduce in a narrower type would: "answers are
                          exact" broken (the control)
    sum-plus-one          one Sum in seven has its value raised by one
                          where it is produced (a fault of the timed path)

`run.py --fault` reads `faults.FAULTS`; this file is also the command that
adds these two to it and runs `run.py` with the same arguments:

    JAX_PLATFORMS=cpu python3 benchmark/harness/sum_faults.py \\
        --workload <cell> --seed 7 --seconds 3 --trace 0 \\
        --rehearse-shards 8 --fault sum-plus-one

Such a run has to read `"correct": false` with `wrong_answers` over its
limit. The driver never runs it.
"""

import itertools
import os
import sys


def low_precision():
    from pilosa_tpu.exec.stacked import StackedEvaluator

    inner = StackedEvaluator._sum_fn

    def _sum_fn(self, has_filt):
        fn = inner(self, has_filt)

        def low(*args):
            p_hi, p_lo, *rest = fn(*args)
            return (p_hi, p_lo & ~0x3F, *rest)

        return low

    StackedEvaluator._sum_fn = _sum_fn


def plus_one():
    from pilosa_tpu.exec.stacked import StackedEvaluator

    inner = StackedEvaluator.try_sum
    calls = itertools.count(1)

    def try_sum(self, *args, **kwargs):
        out = inner(self, *args, **kwargs)
        if out is None or next(calls) % 7:
            return out
        total, count = out
        return total + 1, count

    StackedEvaluator.try_sum = try_sum


FAULTS = {
    "sum-low-precision": low_precision,
    "sum-plus-one": plus_one,
}


def main(argv=None):
    bench = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    here = os.path.join(bench, "harness")
    sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != here]
    sys.path.insert(0, bench)
    import run

    from harness import faults

    faults.FAULTS.update(FAULTS)
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
