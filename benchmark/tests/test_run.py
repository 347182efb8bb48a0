"""run.py end to end on the host CPU at 8 shards (`--rehearse-shards`):
the harness's look for a chip is skipped, everything else is a run's own
path. A sound run reads `correct: true` against the numpy oracle; with the
timed path broken underneath (harness/faults.py: the controls and the
faults each cell can have) `correct` has to read false."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
REHEARSAL_EXIT = 3


def run(workload, *extra, seed=2**31 + 5, trace=0, devices=1, env=None):
    full = dict(os.environ, JAX_PLATFORMS="cpu")
    full.pop("XLA_FLAGS", None)
    if devices > 1:
        full["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={devices}"
    full.update(env or {})
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", workload,
         "--seed", str(seed), "--seconds", "3", "--trace", str(trace),
         *extra],
        cwd=ROOT, env=full, capture_output=True, text=True, timeout=300)
    lines = proc.stdout.strip().splitlines()
    try:
        last = json.loads(lines[-1]) if lines else None
    except ValueError:
        last = None
    return proc, last


def cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return {c["name"]: c for c in json.load(f)["workloads"]}


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", sorted(cells()))
def test_rehearsal_agrees_with_the_oracle(cell, trace):
    chips = cells()[cell]["chips"]
    proc, line = run(cell, "--rehearse-shards", "8", trace=trace,
                     devices=chips)
    assert proc.returncode == REHEARSAL_EXIT, proc.stderr[-2000:]
    assert line["rehearsal"] is True and line["correct"] is True
    assert line["attempted"] > 50 and line["failed"] == 0
    assert "metrics" not in line            # no number under a metric's name
    assert line["device"] == {"platform": "cpu", "kind": "cpu",
                              "count": chips, "memory_peak_bytes": 0}
    assert list(line)[-1] == "compared"
    assert all(v["value"] <= v["limit"] for v in line["compared"].values())
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    group = "per_layer" if trace else "end_to_end"
    want = {m["name"] for m in bench[group]
            if cell in m.get("workloads", [cell])}
    missing = want - set(line["metrics_readable"])
    # the roofline needs the chip's peak: nothing to read on the host
    assert all(m.startswith("kernel.roofline_share") for m in missing)


def faults_of(cell):
    """The controls and faults a cell can have, each with the compared
    number it has to fail: every cell counts, a mix with imports
    acknowledges writes, a four-chip cell exchanges."""
    out = ["low-precision-count", "answer-plus-one"]
    if "rw" in cells()[cell]["traffic"]:
        out += ["ack-without-apply", "half-batch", "ack-without-append"]
    if cells()[cell]["chips"] > 1:
        out = ["low-precision-count", "first-chip-only"]
    return [(f, "acked_not_appended" if f == "ack-without-append"
             else "wrong_answers") for f in out]


@pytest.mark.parametrize("cell,fault,number", [
    (c, f, n) for c in sorted(cells()) for f, n in faults_of(c)])
def test_a_broken_path_reads_incorrect(cell, fault, number):
    proc, line = run(cell, "--rehearse-shards", "8", "--fault", fault,
                     devices=cells()[cell]["chips"])
    assert proc.returncode == REHEARSAL_EXIT, proc.stderr[-2000:]
    assert line["fault"] == fault
    assert line["correct"] is False
    failing = {k for k, v in line["compared"].items()
               if v["value"] > v["limit"]}
    assert number in failing
    if fault == "ack-without-append":
        # applied and readable, only never logged: no other number sees it
        assert failing == {number}
    last = proc.stderr.splitlines()[-len(line["compared"]):]
    assert all(text.startswith("benchmark: compared ") for text in last)
    assert any(f"compared {number} = " in text for text in last)


def test_no_chip_no_result():
    """JAX_PLATFORMS=cpu without --rehearse-shards, and no JAX_PLATFORMS at
    all on a host without a TPU: non-zero exit and no result line."""
    cell = sorted(cells())[0]
    proc, line = run(cell)
    assert proc.returncode not in (0, REHEARSAL_EXIT) and line is None
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed",
         "1", "--seconds", "3", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert not proc.stdout.strip().startswith("{")


def test_wrong_device_count_is_refused():
    four = [c for c, v in cells().items() if v["chips"] == 4]
    if not four:
        pytest.skip("no four-chip cell")
    proc, line = run(four[0], "--rehearse-shards", "8", devices=1)
    assert proc.returncode == 1 and line is None
