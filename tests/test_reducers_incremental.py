"""The tuple reducer keeps its order as rows arrive.

``TupleAcc`` and ``NdarrayAcc`` against the plain reference below — the
accumulator as it was before it kept its order: a dict of entries, and in
``compute`` a list of every entry, sorted — over seeded random sequences of
adds and retractions. Counts, never times."""

import pickle
import random

import numpy as np
import pytest

import pathway_tpu as pw
from pathway_tpu.engine.batch import Batch
from pathway_tpu.engine.graph import EngineGraph
from pathway_tpu.engine.operators.core import InputNode
from pathway_tpu.engine.operators.reduce import GroupbyNode
from pathway_tpu.engine.reducers_impl import NdarrayAcc, TupleAcc, _hashable
from pathway_tpu.engine.value import Pointer
from pathway_tpu.internals.json import Json


class PlainTuple:
    """The reducer that re-sorts everything it holds on every ``compute``."""

    def __init__(self, skip_nones=False, user_order=False):
        self.skip_nones = skip_nones
        self.user_order = user_order
        self._entries = {}
        self._times = {}

    def add(self, args, diff, time):
        hk = _hashable(args)
        if hk not in self._times:
            self._times[hk] = time
        entry = self._entries.get(hk)
        if entry is None:
            entry = [args, 0]
            self._entries[hk] = entry
        entry[1] += diff
        if entry[1] == 0:
            del self._entries[hk]
            self._times.pop(hk, None)

    def compute(self):
        items = []
        for hk, (args, c) in self._entries.items():
            v, order = args[0], args[1] if len(args) > 1 else None
            if v is None and self.skip_nones:
                continue
            t = self._times.get(hk, 0)
            sort_key = (order, t) if self.user_order else (t, order)
            items.extend([(sort_key, v)] * max(c, 0))
        try:
            items.sort(key=lambda t: t[0])
        except TypeError:
            items.sort(key=lambda t: repr(t[0]))
        return tuple(v for _o, v in items)


def _value(rng):
    kind = rng.randrange(6)
    if kind == 0:
        return None
    if kind == 1:
        return rng.randrange(5)
    if kind == 2:
        return Json({"path": f"doc{rng.randrange(6)}", "n": rng.randrange(3)})
    if kind == 3:
        return f"s{rng.randrange(4)}"
    if kind == 4:
        return (rng.randrange(3), f"t{rng.randrange(2)}")
    return float(rng.randrange(3))


def _order(rng, orders):
    if orders == "pointer":
        return Pointer(rng.randrange(1 << 62))
    if orders == "int":
        return rng.randrange(8)
    if orders == "none":
        return None
    # keys that do not order: an int beside a str beside None
    return rng.choice([rng.randrange(4), f"k{rng.randrange(4)}", None])


def _sequence(seed, times, orders):
    """(args, diff, time) steps: fresh rows, rows seen before (multiplicity
    above one), retractions of rows held (to nothing, and then added
    again), and now and then a retraction of a row never seen."""
    rng = random.Random(seed)
    held, steps, now = [], [], 0
    for _ in range(rng.randrange(40, 120)):
        if times == "in_order":
            now += rng.randrange(2)
        else:
            now = rng.randrange(6)
        roll = rng.random()
        if held and roll < 0.25:
            args = held.pop(rng.randrange(len(held)))
            steps.append((args, -1, now))
        elif held and roll < 0.40:
            args = rng.choice(held)
            held.append(args)
            steps.append((args, rng.choice([1, 1, 2]), now))
        elif roll < 0.44:
            steps.append(((_value(rng), _order(rng, orders)), -1, now))
        else:
            args = (_value(rng), _order(rng, orders))
            held.append(args)
            steps.append((args, 1, now))
    return steps


CASES = [
    (times, orders, user_order, skip_nones)
    for times in ("in_order", "out_of_order")
    for orders in ("pointer", "int", "none", "unorderable")
    for user_order in (False, True)
    for skip_nones in (False, True)
]


@pytest.mark.parametrize("times,orders,user_order,skip_nones", CASES)
def test_every_compute_of_a_random_sequence_is_the_plain_reducers(
        times, orders, user_order, skip_nones):
    for seed in range(12):
        plain = PlainTuple(skip_nones=skip_nones, user_order=user_order)
        kept = TupleAcc(skip_nones=skip_nones, user_order=user_order)
        for step, (args, diff, time) in enumerate(
                _sequence(seed, times, orders)):
            plain.add(args, diff, time)
            kept.add(args, diff, time)
            want, got = plain.compute(), kept.compute()
            assert type(got) is tuple and len(got) == len(want), (seed, step)
            # the same objects in the same places, not merely equal ones
            assert all(g is w for g, w in zip(got, want)), (seed, step)
            assert kept.is_empty() == (not plain._entries)


@pytest.mark.parametrize("times", ["in_order", "out_of_order"])
@pytest.mark.parametrize("user_order", [False, True])
def test_the_ndarray_reducer_is_the_tuple_as_an_array(times, user_order):
    for seed in range(6):
        rng = random.Random(seed)
        plain = PlainTuple(user_order=user_order)
        kept = NdarrayAcc(user_order=user_order)
        held, now = [], 0
        for _ in range(60):
            now = now + rng.randrange(2) if times == "in_order" \
                else rng.randrange(5)
            if held and rng.random() < 0.3:
                args, diff = held.pop(rng.randrange(len(held))), -1
            else:
                args = (float(rng.randrange(9)), Pointer(rng.randrange(99)))
                diff = 1
                held.append(args)
            plain.add(args, diff, now)
            kept.add(args, diff, now)
            got = kept.compute()
            assert isinstance(got, np.ndarray)
            assert got.tolist() == list(plain.compute())


def test_a_retraction_and_a_readd_take_the_readds_time():
    acc = TupleAcc()
    a, b = ("a", Pointer(1)), ("b", Pointer(2))
    acc.add(a, 1, 1)
    acc.add(b, 1, 2)
    assert acc.compute() == ("a", "b")
    acc.add(a, -1, 3)
    acc.add(a, 1, 3)
    assert acc.compute() == ("b", "a")


def test_keys_that_do_not_order_sort_by_repr():
    acc = TupleAcc(user_order=True)
    for value, order in [("x", 2), ("y", "b"), ("z", 1), ("w", None)]:
        acc.add((value, order), 1, 1)
    # repr of the sort key (order, time): (1, 1) (2, 1) ('b', 1) (None, 1)
    assert acc.compute() == ("y", "z", "x", "w")
    acc.add(("y", "b"), -1, 2)
    acc.add(("w", None), -1, 2)
    assert acc.compute() == ("z", "x")


def test_an_in_order_append_hashes_no_json_and_calls_no_key(monkeypatch):
    """What ``compute`` costs after a commit appended its rows: a copy.
    Before, every entry held paid a ``Json.__hash__`` (a ``json.dumps``)
    and a Python-level sort key call in every ``compute``."""
    acc = TupleAcc()
    for i in range(3000):
        acc.add((Json({"path": f"doc{i}"}), Pointer(i)), 1, 1 + i // 500)
    hashes, calls = [], []
    real_hash = Json.__hash__
    monkeypatch.setattr(
        Json, "__hash__", lambda self: hashes.append(1) or real_hash(self))

    import sys

    def count_calls(frame, event, arg):
        if event == "call":
            calls.append(frame.f_code.co_name)

    for i in range(3000, 3064):          # a commit's rows, sorting last
        acc.add((Json({"path": f"doc{i}"}), Pointer(i)), 1, 7)
    added = len(hashes)
    assert 0 < added <= 4 * 64           # O(1) a row added, none for the held
    sys.setprofile(count_calls)
    try:
        out = acc.compute()
    finally:
        sys.setprofile(None)
    assert len(out) == 3064
    assert [o.value["path"] for o in out[-2:]] == ["doc3062", "doc3063"]
    assert len(hashes) == added          # compute hashed nothing
    assert calls == ["compute"]          # and ran no other Python function


def _groupby_of_tuples():
    graph = EngineGraph()
    source = InputNode(graph, ["g", "v", "o"])
    node = GroupbyNode(
        graph, source, ["g"],
        [("vs", "tuple", ["v", "o"], {}),
         ("arr", "ndarray", ["v", "o"], {})],
    )
    return node


def _rows_of(batch):
    return sorted((k, tuple(
        v.tolist() if isinstance(v, np.ndarray) else v for v in row), d)
        for k, row, d in batch.rows())


def test_a_groupbys_state_pickled_mid_stream_gives_the_same_next_output():
    rng = random.Random(5)
    steps = []
    for t in range(1, 9):
        rows = [(rng.randrange(1 << 40),
                 (f"g{rng.randrange(2)}", rng.randrange(100),
                  Pointer(rng.randrange(1 << 40))), 1)
                for _ in range(6)]
        steps.append(rows)
    steps[5] = steps[5] + [(k, row, -1) for k, row, _ in steps[1][:3]]
    through, resumed = _groupby_of_tuples(), _groupby_of_tuples()
    for t, rows in enumerate(steps[:5], start=1):
        through.step(t, [Batch.from_rows(["g", "v", "o"], rows)])
    state = pickle.loads(pickle.dumps(
        {a: getattr(through, a) for a in through._state_attrs}))
    for attr, held in state.items():
        setattr(resumed, attr, held)
    for t, rows in enumerate(steps[5:], start=6):
        batch = Batch.from_rows(["g", "v", "o"], rows)
        assert _rows_of(resumed.step(t, [batch])) \
            == _rows_of(through.step(t, [batch]))


def test_through_the_engine_the_tuple_keeps_arrival_order_under_updates():
    t = pw.debug.table_from_markdown("""
          | g | v  | __time__ | __diff__
        1 | a | 1  | 2        | 1
        2 | a | 2  | 2        | 1
        3 | b | 9  | 2        | 1
        4 | a | 3  | 4        | 1
        1 | a | 1  | 6        | -1
        1 | a | 1  | 8        | 1
    """)
    out = t.groupby(t.g).reduce(t.g, vs=pw.reducers.tuple(t.v))
    updates = []
    pw.io.subscribe(out, on_change=lambda key, row, time, is_addition:
                    updates.append((row["g"], row["vs"], time, is_addition)))
    pw.run()
    of_a = [u[1:] for u in updates if u[0] == "a"]
    first = of_a[0][0]
    assert sorted(first) == [1, 2]          # rows of one time: by row id
    rest = tuple(v for v in first if v != 1)
    assert of_a == [
        (first, 2, True),
        (first, 4, False), (first + (3,), 4, True),
        (first + (3,), 6, False), (rest + (3,), 6, True),
        (rest + (3,), 8, False), (rest + (3, 1), 8, True),
    ]
