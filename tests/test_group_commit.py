"""Group commit on the serving path (ISSUE 27, ISSUE 36): one leader at a
time in `GroupCommit`, and `_process_count_batch` — one hold of the
dispatch lock, one fetch and one launch a group (the root operator is
data, a spare slot reads nothing), never a compile with followers
waiting. Nothing below is timed: threads meet on events, and `queued`
polls the commit's own queue."""

import json
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from pilosa_tpu.exec import stacked
from pilosa_tpu.exec.stacked import GroupCommit, StackedEvaluator
from pilosa_tpu.ops import containers
from pilosa_tpu.shardwidth import SHARD_WIDTH, WORDS_PER_ROW

WAIT = 30


def start(fn, *args):
    thread = threading.Thread(target=fn, args=args)
    thread.start()
    return thread


def joined(threads):
    for t in threads:
        t.join(WAIT)
    return not any(t.is_alive() for t in threads)


def queued(commit, n):
    """Spin until `n` callers stand in the queue (they queue under the
    commit's lock, so what is seen there has arrived)."""
    while len(commit._queue) < n:
        time.sleep(0.001)


class Gate:
    """A `process` that records its batches and can be held inside."""

    def __init__(self, hold=False):
        self.batches, self.inside, self.most_inside = [], 0, 0
        self.entered = threading.Event()
        self.release = threading.Event()
        if not hold:
            self.release.set()
        self._lock = threading.Lock()

    def __call__(self, payloads):
        with self._lock:
            self.batches.append(list(payloads))
            self.inside += 1
            self.most_inside = max(self.most_inside, self.inside)
        self.entered.set()
        assert self.release.wait(WAIT)
        with self._lock:
            self.inside -= 1
        return [("done", p) for p in payloads]


# ---------------------------------------------------------------- the class


def test_a_lone_submit_leads_at_once():
    """No batch in flight: the caller leads a batch of one on its own
    thread, with nothing to wait for (no event of its own is ever set)."""
    commit, gate = GroupCommit(), Gate()
    called_on = []

    def process(payloads):
        called_on.append(threading.get_ident())
        return gate(payloads)

    assert commit.submit("a", process) == ("done", "a")
    assert commit.submit("b", process) == ("done", "b")
    assert gate.batches == [["a"], ["b"]]
    assert called_on == [threading.get_ident()] * 2
    assert (commit.batches, commit.batched) == (2, 2)
    assert not commit._in_flight and not commit._queue


def test_arrivals_during_a_batch_form_exactly_one_next_batch():
    commit, gate = GroupCommit(), Gate(hold=True)
    results = {}

    def submit(p):
        results[p] = commit.submit(p, gate)

    first = start(submit, 0)
    assert gate.entered.wait(WAIT)
    late = []
    for p in range(1, 6):
        late.append(start(submit, p))
        queued(commit, p)
    assert gate.batches == [[0]]  # nobody led while the first was in flight
    gate.release.set()
    assert joined([first, *late])
    assert gate.batches == [[0], [1, 2, 3, 4, 5]]
    assert results == {p: ("done", p) for p in range(6)}
    assert gate.most_inside == 1
    assert (commit.batches, commit.batched) == (2, 6)
    assert not commit._in_flight and not commit._queue


def test_a_batchs_payloads_die_with_the_batch():
    """No waiter, batch or result keeps a payload after its caller has
    its answer: a payload holds device stacks (119 MiB each at a billion
    columns), and a reference cycle would keep them until a collection."""
    import gc
    import weakref

    class Payload:
        pass

    commit, gate = GroupCommit(), Gate(hold=True)
    gate.batches = type("Forgetful", (), {"append": lambda self, b: None})()
    alive = []

    def submit():
        payload = Payload()
        alive.append(weakref.ref(payload))
        commit.submit(payload, lambda ps: [None for _ in gate(ps)])

    gc.disable()
    try:
        first = start(submit)
        assert gate.entered.wait(WAIT)
        late = []
        for n in range(1, 4):
            late.append(start(submit))
            queued(commit, n)
        gate.release.set()
        assert joined([first, *late])
        assert [ref() for ref in alive] == [None] * 4
    finally:
        gc.enable()


def test_a_leaders_failure_reaches_its_batch_and_the_next_batch_runs():
    commit = GroupCommit()
    entered, release = threading.Event(), threading.Event()
    seen = []

    def process(payloads):
        seen.append(list(payloads))
        if len(seen) == 1:
            entered.set()
            assert release.wait(WAIT)
            return [p for p in payloads]
        if len(seen) == 2:
            # the failing batch: hold until the third batch has queued
            queued(commit, 1)
            raise RuntimeError("device lost")
        return [p * 10 for p in payloads]

    outcomes = {}

    def submit(p):
        try:
            outcomes[p] = commit.submit(p, process)
        except RuntimeError as exc:
            outcomes[p] = exc

    first = start(submit, 1)
    assert entered.wait(WAIT)
    doomed = []
    for n, p in enumerate((2, 3, 4), 1):
        doomed.append(start(submit, p))
        queued(commit, n)
    release.set()          # batch 2 = [2, 3, 4] starts and waits for a 5
    assert joined([first])
    after = start(submit, 5)
    assert joined([*doomed, after])
    assert seen == [[1], [2, 3, 4], [5]]
    assert outcomes[1] == 1 and outcomes[5] == 50
    assert all(isinstance(outcomes[p], RuntimeError) for p in (2, 3, 4))
    assert len({id(outcomes[p]) for p in (2, 3, 4)}) == 1
    assert not commit._in_flight and not commit._queue
    assert commit.submit(6, process) == 60   # and a lone caller still leads


def test_64_threads_lose_no_wakeup_and_get_their_own_results():
    commit = GroupCommit()
    inside = [0, 0]
    guard = threading.Lock()

    def process(payloads):
        with guard:
            inside[0] += 1
            inside[1] = max(inside[1], inside[0])
        out = [(p[0], p[1], p[0] * 1000 + p[1]) for p in payloads]
        with guard:
            inside[0] -= 1
        return out

    wrong = []

    def client(t):
        for n in range(200):
            if commit.submit((t, n), process) != (t, n, t * 1000 + n):
                wrong.append((t, n))

    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [start(client, t) for t in range(64)]
        assert joined(threads)
    finally:
        sys.setswitchinterval(before)
    assert not wrong
    assert inside[1] == 1            # never two `process` calls at once
    assert commit.batched == 64 * 200
    assert commit.batches <= commit.batched
    assert not commit._in_flight and not commit._queue


# ---------------------------------------------------------------- the batch

OPS = {"&": np.bitwise_and, "|": np.bitwise_or, "^": np.bitwise_xor,
       "-": lambda a, b: a & ~b}


def sig_of(op):
    return (op, (("leaf", 0), ("leaf", 1)))


def clustered(rng, shards):
    """A plane that compresses: a shard holds three of its first eight
    blocks of 128 words (so that two planes meet) — one full, one of
    half-set words (a run a word), one of nibbles (four runs a word)."""
    plane = np.zeros((shards, WORDS_PER_ROW), dtype=np.uint32)
    for shard in range(shards):
        blocks = 128 * rng.choice(8, size=3, replace=False)
        for at, word in zip(blocks, (0xFFFFFFFF, 0x0000FFFF, 0x0F0F0F0F)):
            plane[shard, at:at + 128] = word
    return plane


def popcount(words):
    return int(np.unpackbits(words.view(np.uint8)).sum())


class Planes:
    """Leaf stacks of two shard counts on the evaluator's devices, their
    numpy originals beside them: dense random planes, or with `reprs`
    (a representation a leaf) clustered planes built as the serving
    path builds them, each forced into its representation."""

    def __init__(self, ev, seed=5, reprs=None):
        rng = np.random.default_rng(seed)
        self.host, self.dev = {}, {}
        for shards in (8, 16):
            for leaf in range(3):
                plane = clustered(rng, shards) if reprs else rng.integers(
                    0, 2**32, (shards, WORDS_PER_ROW), dtype=np.uint32)
                self.host[shards, leaf] = plane
                self.dev[shards, leaf] = containers.build(
                    plane, lambda a: ev._place(a, shard_axis=0),
                    ev._place_replicated,
                    mode=reprs[leaf] if reprs else "dense")

    def payload(self, op, shards, a, b):
        return (sig_of(op), (self.dev[shards, a], self.dev[shards, b]))

    def answer(self, op, shards, a, b):
        return popcount(OPS[op](self.host[shards, a], self.host[shards, b]))


class CountingLock:
    def __init__(self):
        self._lock, self.holds = threading.Lock(), 0

    def acquire(self):
        self._lock.acquire()
        self.holds += 1

    def release(self):
        self._lock.release()


def mixed_batch(planes, n, programs=8):
    """`n` queries over three leaves, `programs` of the eight pairs that
    four operators and two shard sets make: whatever the operators, one
    group a shard set."""
    picks = [("&|^-"[i % programs % 4], (8, 16)[i % programs // 4],
              i % 3, (i + 1) % 3) for i in range(n)]
    return ([planes.payload(*p) for p in picks],
            [planes.answer(*p) for p in picks])


def expected_launches(payloads):
    """The (slots, queries) launches of a batch: one group a (shape x
    representation x shard set) — the root operator is no part of it —
    and each group's launch plan."""
    groups = {}
    for sig, stacks in payloads:
        key = (stacked._split_root(sig)[0],
               tuple((c.kind, c.shape, tuple(a.shape for a in c.arrays))
                     for c in stacks))
        groups[key] = groups.get(key, 0) + 1
    return [stacked._launch_plan(n, StackedEvaluator.MAX_COUNT_BATCH)
            for n in groups.values()]


def finish_builds(ev):
    for thread in list(ev._count_builds.values()):
        thread.join(120)
        assert not thread.is_alive()


def run_counted(ev, payloads):
    """One `_process_count_batch` under a lock that counts its holds:
    (results, holds, what the counters gained)."""
    ev._dispatch_lock = lock = CountingLock()
    before = dict(ev.cache_stats(), **ev._kernels.get("count", {}))
    try:
        got = ev._process_count_batch(payloads)
    finally:
        ev._dispatch_lock = stacked._DISPATCH_LOCK
    after = dict(ev.cache_stats(), **ev._kernels["count"])
    gained = {k: after[k] - before.get(k, 0) for k in (
        "count", "bytes_in", "count_launches", "count_pad_slots",
        "count_batch_fallbacks")}
    return got, lock.holds, gained


def built_sizes(ev):
    return sorted(key[2] for key in ev._fns if key[0] == "countB")


BATCHES = [(1, 8), (3, 8), (5, 8), (17, 8), (33, 8), (5, 1), (33, 1)]


@pytest.fixture(scope="module")
def warm():
    """An evaluator that has seen every batch below once, so that every
    bucket they ask for is built."""
    ev = StackedEvaluator()
    planes = Planes(ev)
    for n, programs in BATCHES:
        ev._process_count_batch(mixed_batch(planes, n, programs)[0])
        finish_builds(ev)
    assert not ev._count_builds
    return ev, planes


@pytest.mark.parametrize("n", range(1, 71))
def test_a_launch_plan_is_one_padded_launch_after_the_full_ones(n):
    plan = stacked._launch_plan(n, 32)
    assert sum(queries for _, queries in plan) == n
    assert all(slots & (slots - 1) == 0 and slots <= 32
               and slots // 2 < queries <= slots for slots, queries in plan)
    assert [slots for slots, _ in plan[:-1]] == [32] * (len(plan) - 1)
    assert len([1 for slots, queries in plan if slots > queries]) <= 1
    assert len(plan) == -(-n // 32)


def test_the_plans_the_issue_names():
    assert stacked._launch_plan(37, 32) == [(32, 32), (8, 5)]
    assert stacked._launch_plan(33, 32) == [(32, 32), (1, 1)]
    assert stacked._launch_plan(16, 32) == [(16, 16)]
    assert stacked._launch_plan(1, 32) == [(1, 1)]


@pytest.mark.parametrize("sig,shape,code", [
    (("leaf", 0), ("leaf", 0), 0),
    (sig_of("&"), (None, sig_of("&")[1]), 0),
    (sig_of("|"), (None, sig_of("&")[1]), 1),
    (sig_of("^"), (None, sig_of("&")[1]), 2),
    (sig_of("-"), (None, sig_of("&")[1]), 3),
    (("|", (sig_of("-"), ("leaf", 2))), (None, (sig_of("-"), ("leaf", 2))), 1),
], ids=["leaf", "and", "or", "xor", "andnot", "nested"])
def test_a_signature_splits_into_shape_and_root_operator(sig, shape, code):
    assert stacked._split_root(sig) == (shape, code)
    assert stacked.PAD_OP == 4 and code < stacked.PAD_OP


@pytest.mark.parametrize("n,programs", BATCHES)
def test_a_batch_gives_the_solo_answers_in_one_launch_a_group(
        warm, n, programs):
    ev, planes = warm
    payloads, answers = mixed_batch(planes, n, programs)
    solo = [ev._process_count_batch([p])[0][0] for p in payloads]
    assert solo == answers
    got, holds, gained = run_counted(ev, payloads)
    launches = expected_launches(payloads)
    # one launch a (shape x representation x shard set) group, two for 33
    assert [len(group) for group in launches] == [
        2 if sum(queries for _, queries in group) > 32 else 1
        for group in launches]
    assert [count for count, _ in got] == answers
    # the size a query reports is the number of real queries in its launch
    assert sorted(size for _, size in got) == sorted(
        queries for group in launches for _, queries in group
        for _ in range(queries))
    assert holds == 1
    assert gained["count"] == 1
    assert gained["count_launches"] == sum(len(g) for g in launches)
    assert gained["count_pad_slots"] == sum(
        slots - queries for group in launches for slots, queries in group)
    assert gained["count_batch_fallbacks"] == 0
    # a spare slot reads nothing: the bytes sent in are the answered
    # queries' own
    assert gained["bytes_in"] == sum(
        c.nbytes for _, stacks in payloads for c in stacks)


@pytest.mark.parametrize("reprs", [
    ("dense", "sparse"), ("dense", "rle"), ("sparse", "sparse"),
    ("sparse", "rle"), ("rle", "rle")], ids="-".join)
def test_a_batch_over_mixed_representations_groups_by_representation(reprs):
    """Leaves 0 and 1 in the two representations named, leaf 2 dense:
    fifteen queries over the pairs (0, 1), (1, 2), (2, 0), (2, 2) under
    all four operators are one group a pair of representations whatever
    the operator, each sent as one launch under one lock hold — and
    answer what numpy and the solo program answer (each branch keeps
    count_program's own strategy for its operator)."""
    ev = StackedEvaluator()
    planes = Planes(ev, reprs=reprs + ("dense",))
    assert [planes.dev[8, leaf].kind for leaf in range(3)] \
        == list(reprs) + ["dense"]
    picks = [("&|^-"[i // 4 % 4], 8, *((0, 1), (1, 2), (2, 0), (2, 2))[i % 4])
             for i in range(11)]
    payloads = [planes.payload("&", *p[1:]) for p in picks]
    payloads += [planes.payload(*p) for p in picks[4:8]]
    answers = [planes.answer("&", *p[1:]) for p in picks]
    answers += [planes.answer(*p) for p in picks[4:8]]
    assert len(set(answers)) > 4
    assert [ev._process_count_batch([p])[0][0] for p in payloads] == answers
    ev._process_count_batch(payloads)  # builds the buckets it needs
    finish_builds(ev)
    got, holds, gained = run_counted(ev, payloads)
    launches = expected_launches(payloads)
    assert [count for count, _ in got] == answers
    # a group of its own for each pair of representations, whichever
    # leaves and whichever operator a query names
    assert len(launches) == len({
        tuple(c.kind for c in stacks) for _, stacks in payloads})
    assert all(len(group) == 1 for group in launches)
    assert sorted(size for _, size in got) == sorted(
        queries for group in launches for _, queries in group
        for _ in range(queries))
    assert holds == 1
    assert gained["count_launches"] == len(launches)
    assert gained["count_batch_fallbacks"] == 0


def test_every_operator_and_a_bare_leaf_ride_buckets_against_numpy():
    """Two of each operator are ONE bucket of eight (one `countB` program
    whatever the operators); three bare leaves are a group of their own
    (another arity), one bucket of four with a spare slot."""
    ev = StackedEvaluator()
    planes = Planes(ev)
    picks = [(op, 8, a, b) for op in "&|^-" for a, b in ((0, 1), (2, 1))]
    payloads = [planes.payload(*p) for p in picks]
    answers = [planes.answer(*p) for p in picks]
    for leaf in range(3):
        payloads.append((("leaf", 0), (planes.dev[8, leaf],)))
        answers.append(popcount(planes.host[8, leaf]))
    assert len(set(answers)) == len(answers)
    ev._process_count_batch(payloads)
    finish_builds(ev)
    assert built_sizes(ev) == [4, 8]
    got, holds, gained = run_counted(ev, payloads)
    assert [count for count, _ in got] == answers
    assert [size for _, size in got] == [8] * 8 + [3] * 3
    assert holds == 1
    assert gained["count_launches"] == 2
    assert gained["count_pad_slots"] == 1
    assert gained["count_batch_fallbacks"] == 0


@pytest.mark.parametrize("subs", [
    (("leaf", 0), ("leaf", 1), ("leaf", 2)),
    (("-", (("leaf", 0), ("leaf", 1))), ("leaf", 2)),
    (("leaf", 2), ("^", (("leaf", 1), ("|", (("leaf", 0), ("leaf", 2)))))),
], ids=["three-leaf-root", "nested-left", "nested-right"])
def test_the_root_is_data_and_inner_operators_stay_in_the_shape(subs):
    """All four root operators over one shape — a 3-leaf root, or a
    nested tree whose inner operators stay in the shape — share ONE
    bucket and answer what the solo program and numpy answer."""
    ev = StackedEvaluator()
    planes = Planes(ev)
    stacks = tuple(planes.dev[8, leaf] for leaf in range(3))
    host = [planes.host[8, leaf] for leaf in range(3)]

    def numpy_eval(sig):
        if sig[0] == "leaf":
            return host[sig[1]]
        acc = numpy_eval(sig[1][0])
        for sub in sig[1][1:]:
            acc = OPS[sig[0]](acc, numpy_eval(sub))
        return acc

    payloads = [((op, subs), stacks) for op in "&|^-"]
    answers = [popcount(numpy_eval(sig)) for sig, _ in payloads]
    assert len(set(answers)) == 4
    solo = [ev._process_count_batch([p])[0][0] for p in payloads]
    assert solo == answers
    batch, want = payloads + payloads[:1], answers + answers[:1]
    ev._process_count_batch(batch)
    finish_builds(ev)
    got, _, gained = run_counted(ev, batch)
    assert [count for count, _ in got] == want
    assert gained["count_launches"] == 1
    assert gained["count_pad_slots"] == 3      # 5 ride a bucket of 8
    assert built_sizes(ev) == [8]
    assert len({key[1][0] for key in ev._fns if key[0] == "countB"}) == 1


def test_spare_slots_answer_nobody_and_are_counted():
    ev = StackedEvaluator()
    planes = Planes(ev)
    payloads, answers = mixed_batch(planes, 5, 4)   # one group of five
    ev._process_count_batch(payloads)
    finish_builds(ev)
    assert built_sizes(ev) == [8]
    got, _, gained = run_counted(ev, payloads)
    assert [count for count, _ in got] == answers
    assert len(got) == 5 and {size for _, size in got} == {5}
    assert gained["count_launches"] == 1
    assert gained["count_pad_slots"] == 3
    assert ev.cache_stats()["count_pad_slots"] == 3
    # the program itself: a spare slot's count is zero whatever leaves
    # it was handed
    fn = ev._cached_fn(next(k for k in ev._fns if k[0] == "countB"))
    ops = np.full(8, stacked.PAD_OP, dtype=np.int32)
    flat = containers.flatten(payloads[0][1])
    his, los = fn(ops, *flat * 8)
    assert not np.asarray(his).any() and not np.asarray(los).any()


@pytest.mark.parametrize("size", [2, 16])
def test_a_bucket_is_one_program_of_one_case_a_slot(size):
    """The lowered bucket holds exactly `size` `stablehlo.case` — a
    five-way switch a slot, nothing vmapped or selected — and is the one
    program of its size for all four operators."""
    import jax

    ev = StackedEvaluator()
    planes = Planes(ev)
    shape, _ = stacked._split_root(sig_of("&"))
    leaf = jax.ShapeDtypeStruct((8, WORDS_PER_ROW), np.uint32)
    text = ev._count_batch_fn(shape, 2, size).lower(
        jax.ShapeDtypeStruct((size,), np.int32),
        *[leaf] * (2 * size)).as_text()
    assert text.count("stablehlo.case") == size
    assert "stablehlo.select" not in text
    batch = [planes.payload("&|^-"[i % 4], 8, i % 3, (i + 1) % 3)
             for i in range(size)]
    ev._process_count_batch(batch)
    finish_builds(ev)
    ev._process_count_batch(batch[::-1])
    assert built_sizes(ev) == [size]


def test_an_unbuilt_bucket_goes_out_as_solos_and_is_built_afterwards():
    ev = StackedEvaluator()
    planes = Planes(ev)
    payloads, answers = mixed_batch(planes, 4)
    other = planes.payload("&", 16, 0, 1)
    batch = payloads + payloads[:1] + [other]
    want = answers + answers[:1] + [planes.answer("&", 16, 0, 1)]
    got, holds, gained = run_counted(ev, batch)
    assert [count for count, _ in got] == want
    assert {size for _, size in got} == {1}
    assert gained["count_batch_fallbacks"] == 1   # the group of 5
    assert gained["count_launches"] == 6          # 5 solos + the other's
    assert gained["count_pad_slots"] == 0
    assert holds == 1
    finish_builds(ev)
    assert not ev._count_builds
    assert built_sizes(ev) == [8]
    # the same batch now rides the bucket: 5 in 8, and the other group's 1
    got, _, gained = run_counted(ev, batch)
    assert [count for count, _ in got] == want
    assert sorted(size for _, size in got) == [1, 5, 5, 5, 5, 5]
    assert gained["count_batch_fallbacks"] == 0
    assert gained["count_launches"] == 2
    assert gained["count_pad_slots"] == 3
    # /debug/kernels prices the bucket from the shapes it was built for
    assert any("countB" in entry["key"]
               for entry in ev.kernels_snapshot()["compiled"])


def test_an_unbuilt_16_rides_two_built_8s():
    """While a bucket compiles its chunk goes out in the buckets that
    exist — the smallest built one that holds it, else the largest as
    often as it fills — and as solos only where none does."""
    ev = StackedEvaluator()
    planes = Planes(ev)
    payloads, answers = mixed_batch(planes, 16, 4)   # one group
    ev._process_count_batch(payloads[:8])
    finish_builds(ev)
    assert built_sizes(ev) == [8]
    started = []
    ev._build_count_bucket = lambda key, size: started.append(size)
    got, holds, gained = run_counted(ev, payloads)
    assert [count for count, _ in got] == answers
    assert {size for _, size in got} == {8}
    assert holds == 1
    assert gained["count_launches"] == 2
    assert gained["count_batch_fallbacks"] == 1
    assert gained["count_pad_slots"] == 0
    assert started == [16]       # the missing bucket is built afterwards
    # 13 = a full 8 and 5 in another 8; 3 fit the one built bucket
    got, _, gained = run_counted(ev, payloads[:13])
    assert [count for count, _ in got] == answers[:13]
    assert sorted({size for _, size in got}) == [5, 8]
    assert (gained["count_launches"], gained["count_pad_slots"]) == (2, 3)
    got, _, gained = run_counted(ev, payloads[:3])
    assert [count for count, _ in got] == answers[:3]
    assert (gained["count_launches"], gained["count_pad_slots"]) == (1, 5)
    assert gained["count_batch_fallbacks"] == 1
    assert started == [16, 16, 4]
    # 9 with one left over: a full 8 and a solo
    got, _, gained = run_counted(ev, payloads[:9])
    assert [count for count, _ in got] == answers[:9]
    assert sorted(size for _, size in got) == [1] + [8] * 8
    assert (gained["count_launches"], gained["count_pad_slots"]) == (2, 0)


def test_a_batch_of_one_is_the_solo_program_and_builds_no_operator_vector(
        monkeypatch):
    ev = StackedEvaluator()
    planes = Planes(ev)
    payload, answer = planes.payload("^", 8, 0, 2), planes.answer("^", 8, 0, 2)
    calls = []
    solo = ev._count_fn(payload[0], 2)
    monkeypatch.setattr(
        ev, "_count_fn", lambda sig, csig: calls.append(sig) or solo)
    monkeypatch.setattr(stacked.np, "full", lambda *a, **k: pytest.fail(
        "a lone query built an operator vector"))
    monkeypatch.setattr(ev, "_build_count_bucket", lambda *a: pytest.fail(
        "a lone query asked for a bucket"))
    got, holds, gained = run_counted(ev, [payload])
    assert got == [(answer, 1)]
    assert calls == [payload[0]]      # the concrete signature, its operator in it
    assert holds == 1
    assert gained["count_launches"] == 1
    assert gained["count_pad_slots"] == 0
    assert built_sizes(ev) == []
    # the solo program is today's: the operator in the signature, no switch
    import jax
    leaf = jax.ShapeDtypeStruct((8, WORDS_PER_ROW), np.uint32)
    text = solo._jit_fn.lower(leaf, leaf).as_text()
    assert "stablehlo.case" not in text and "count_tree" in text


def test_a_leaders_lapsed_deadline_does_not_fail_its_batch(warm):
    """Each caller checks its own deadline before it queues; the leader's
    has no say over the batch it leads."""
    ev, planes = warm
    payloads, answers = mixed_batch(planes, 2)
    stacked.set_thread_deadline(0.0)  # long past
    try:
        with pytest.raises(stacked.DeadlineExceededError):
            ev._batched_count(*payloads[0])
        got = ev._process_count_batch(payloads)
    finally:
        stacked.set_thread_deadline(None)
    assert [count for count, _ in got] == answers


# ------------------------------------------------------------ over the wire


def test_32_http_clients_share_batches(tmp_path):
    from tests.harness import ServerHarness

    h = ServerHarness(data_dir=str(tmp_path))
    try:
        h.client.create_index("i")
        for field in ("f", "g"):
            h.client.create_field("i", field)
            cols = [s * SHARD_WIDTH + c for s in range(4)
                    for c in range(0, 40, 1 + len(field))]
            h.client.import_bits("i", field, [1] * len(cols), cols)
        queries = [f"Count({op}(Row(f=1), Row(g=1)))"
                   for op in ("Intersect", "Union", "Difference", "Xor")]
        url = f"{h.address}/index/i/query"

        def ask(pql):
            req = urllib.request.Request(url, data=pql.encode(),
                                         method="POST")
            with urllib.request.urlopen(req, timeout=WAIT) as r:
                return json.loads(r.read())["results"][0]

        want = {q: ask(q) for q in queries}
        assert len(set(want.values())) > 1
        before = h.api.executor._stacked.cache_stats()
        wrong = []

        def client(t):
            for n in range(25):
                q = queries[(t + n) % 4]
                if ask(q) != want[q]:
                    wrong.append(q)

        threads = [start(client, t) for t in range(32)]
        assert joined(threads)
        assert not wrong
        with urllib.request.urlopen(f"{h.address}/debug/vars",
                                    timeout=WAIT) as r:
            after = json.loads(r.read())["stacked"]
        queries_n = after["count_batched_queries"] \
            - before["count_batched_queries"]
        assert queries_n == 32 * 25
        assert after["count_batches"] - before["count_batches"] < queries_n
        assert after["count_launches"] >= after["count_batches"]
        assert "count_batch_fallbacks" in after
        assert "count_pad_slots" in after
    finally:
        h.close()
