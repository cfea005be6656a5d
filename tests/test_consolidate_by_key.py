"""Consolidation decides by key before it looks at content.

(a) ``consolidate`` against the plain reference below (the canonical
serialisation of EVERY row, diffs summed by (key, bytes), zeros dropped) over
seeded random batches; (b) what it reads to get there, counted in calls of
``serialize_value``. Counts, never times."""

import datetime
import random

import numpy as np
import pytest

from pathway_tpu.engine import batch as batch_mod
from pathway_tpu.engine import value as value_mod
from pathway_tpu.engine.batch import Batch, consolidate, consolidate_counted
from pathway_tpu.engine.value import ERROR, Pointer
from pathway_tpu.internals.json import Json


def _bytes_of(row):
    out = bytearray()
    for value in row:
        value_mod.serialize_value(value, out)
        out += b"|"
    return bytes(out)


@pytest.fixture
def serialised(monkeypatch):
    """Calls of ``serialize_value`` (the recursion into a tuple's elements
    counts each), as a list that grows."""
    calls = []
    real = value_mod.serialize_value
    monkeypatch.setattr(value_mod, "serialize_value",
                        lambda v, out: calls.append(1) or real(v, out))
    return calls


def plain_consolidate(batch):
    """[(key, row bytes, diff)] in order of first occurrence."""
    summed = {}
    for key, row, diff in batch.rows():
        at = (key, _bytes_of(row))
        summed[at] = summed.get(at, 0) + diff
    return [(k, b, d) for (k, b), d in summed.items() if d != 0]


def _as_listed(batch):
    if batch is None:
        return []
    return [(k, _bytes_of(row), d) for k, row, d in batch.rows()]


# pools of values that are near one another: equal to ``==`` and not to the
# serialiser, or the other way round
POOLS = {
    "numbers_of_equal_value": lambda: [
        1, 1.0, True, 0, 0.0, False, -0.0, np.int64(1), np.float64(1.0),
        np.bool_(True), 2**70, -(2**70), float("nan"), float("inf")],
    "none_and_error": lambda: [None, ERROR, 0, "", b"", ()],
    "pointers": lambda: [
        Pointer(7), Pointer(7), Pointer(8), 7, Pointer(2**64 - 1)],
    "strings_and_bytes": lambda: [
        "a", "a", "b", b"a", b"a", "", "ab" * 40, ("ab" * 40)[:], "é"],
    "nested_tuples": lambda: [
        (1, "x"), (1, "x"), [1, "x"], (1, ("x",)), (1, "x", None), (),
        ((), ()), (1.0, "x"), (Pointer(3), (Json({"k": 1}), b"z")),
        (Pointer(3), (Json({"k": 1}), b"z")), (Pointer(3), (Json({"k": 2}), b"z"))],
    "json_of_equal_value": lambda: [
        Json({"a": 1, "b": [1, 2]}), Json({"a": 1, "b": [1, 2]}),
        Json({"b": [1, 2], "a": 1}), Json({"a": 1.0, "b": [1, 2]}),
        Json(None), None, Json("a"), "a", Json([1, 2]), (1, 2)],
    "arrays_of_equal_bytes": lambda: [
        np.zeros(4, np.int32), np.zeros(4, np.int32), np.zeros(4, np.uint32),
        np.zeros(2, np.int64), np.zeros((2, 2), np.int32),
        np.zeros(4, np.float32), np.arange(4, dtype=np.float32),
        np.arange(4, dtype=np.float32), np.array([1, "a", None], object),
        np.array([1, "a", None], object)],
    "datetimes": lambda: [
        datetime.datetime(2026, 1, 2), datetime.datetime(2026, 1, 2),
        datetime.datetime(2026, 1, 2, tzinfo=datetime.timezone.utc),
        datetime.timedelta(seconds=1), 1],
    "everything": lambda: [v for name, pool in POOLS.items()
                           if name != "everything" for v in pool()],
}


def _random_batch(seed, pool_name, n_keys):
    rng = random.Random(seed)
    pool = POOLS[pool_name]()
    keys = [rng.randrange(1, 2**64) for _ in range(n_keys)]
    rows = []           # (key, (index into the pool, another), diff)
    for _ in range(rng.randrange(1, 60)):
        at = (rng.randrange(len(pool)), rng.randrange(len(pool)))
        rows.append((rng.choice(keys), at, rng.choice([-2, -1, 1, 1, 2]), pool))
        roll = rng.random()
        if roll < 0.3:        # the row again, cancelling or summing
            k, at, d, source = rng.choice(rows)
            rows.append((k, at, rng.choice([-d, d, 1]), source))
        elif roll < 0.4:      # an equal row in fresh objects
            k, at, d, _source = rng.choice(rows)
            rows.append((k, at, -d, POOLS[pool_name]()))
    rng.shuffle(rows)
    return Batch.from_rows(["x", "y"], [
        (k, (source[i], source[j]), d) for k, (i, j), d, source in rows])


@pytest.mark.parametrize("n_keys", [1, 3, 40])
@pytest.mark.parametrize("pool_name", sorted(POOLS))
def test_random_batches_consolidate_as_the_plain_reference(pool_name, n_keys):
    for seed in range(25):
        batch = _random_batch(seed, pool_name, n_keys)
        want = plain_consolidate(batch)
        got = _as_listed(consolidate(batch))
        # the same rows with the same diffs, each where it first occurred
        assert got == want, (pool_name, n_keys, seed)


@pytest.mark.parametrize("pool_name", sorted(POOLS))
def test_every_two_row_batch_of_a_pool_consolidates_as_the_plain_reference(
        pool_name):
    """One group's update, the engine's most frequent mixed batch, takes a
    short cut of its own: every pair of a pool's values under one key."""
    pool, fresh = POOLS[pool_name](), POOLS[pool_name]()
    for i, x in enumerate(pool):
        for j in range(len(pool)):
            for diffs in ((-1, 1), (1, 1), (2, -1)):
                for keys in ((5, 5), (5, 6)):
                    batch = Batch.from_rows(["x", "y"], [
                        (keys[0], (x, "same"), diffs[0]),
                        (keys[1], (fresh[j], "same"), diffs[1])])
                    assert _as_listed(consolidate(batch)) \
                        == plain_consolidate(batch), (i, j, diffs, keys)
    one = Batch.from_rows(["x"], [(5, (pool[0],), -2)])
    assert consolidate(one) is one and one._consolidated


@pytest.mark.parametrize("dtype", ["int64", "float64", "bool", "float32",
                                   "uint64", "datetime64[ns]", "U3"])
def test_typed_columns_compare_as_their_values_serialise(dtype):
    rng = np.random.default_rng(3)
    for seed in range(10):
        n = 40
        keys = rng.integers(1, 6, n).astype(np.uint64)
        raw = rng.integers(0, 3, n)
        if dtype == "float64" or dtype == "float32":
            col = np.array([0.0, -0.0, np.nan], dtype)[raw]
        elif dtype == "U3":
            col = np.array(["a", "b", "ab"])[raw]
        else:
            col = raw.astype(dtype)
        batch = Batch(keys, {"x": col, "y": raw.astype(object)},
                      rng.choice([-1, 1], n))
        assert sorted(_as_listed(consolidate(batch))) \
            == sorted(plain_consolidate(batch))
        for i in range(0, n - 1, 2):        # and two rows at a time
            two = Batch(np.array([7, 7], np.uint64),
                        {"x": col[i:i + 2], "y": raw[i:i + 2].astype(object)},
                        np.array([-1, 1]))
            assert _as_listed(consolidate(two)) == plain_consolidate(two)


def test_rows_alone_under_their_key_are_kept_and_never_read(serialised):
    class Unreadable:
        def __eq__(self, other):
            raise AssertionError("content read")
        __hash__ = None

    rows = [(k, (Unreadable(), Json({"k": k})), 1 if k % 2 else -1)
            for k in range(1, 200)]
    batch = Batch.from_rows(["x", "y"], rows)
    out, compared = consolidate_counted(batch)
    assert out is batch and compared == 0 and serialised == []
    assert not batch._consolidated          # mixed signs: no proof to carry


def _json_tuple(n, start=0):
    return tuple(Json({"path": f"doc{i}", "modified_at": i})
                 for i in range(start, start + n))


@pytest.mark.parametrize("shape", ["appended", "one_changed", "retracted"])
def test_an_updated_tuples_pair_costs_what_changed_not_what_it_holds(
        serialised, shape):
    """The (-old, +new) pair of a standing ``reducers.tuple``: N ``Json``
    before a commit, N + 2,048 after. Every mixed-sign batch used to be
    hashed whole: 2 N + 2,048 serialisations to learn that two tuples of
    unequal length differ."""
    counts = {}
    for n in (1_000, 50_000):
        old = _json_tuple(n)
        if shape == "appended":
            new = old + _json_tuple(2_048, start=n)
        elif shape == "one_changed":
            new = old[:n // 2] + (Json({"path": "moved"}),) + old[n // 2 + 1:]
        else:
            new = old[:n // 2] + old[n // 2 + 1:]
        batch = Batch.from_rows(
            ["metadatas", "n"], [(5, (old, len(old)), -1), (5, (new, len(new)), 1)])
        before = len(serialised)
        out, compared = consolidate_counted(batch)
        assert out is batch and compared == 2
        counts[n] = len(serialised) - before
    assert counts[1_000] == counts[50_000]
    assert counts[1_000] == {"appended": 0, "one_changed": 2, "retracted": 0}[shape]


def test_a_true_cancellation_walks_the_value_and_cancels(serialised):
    old, again = _json_tuple(300), _json_tuple(300)
    batch = Batch.from_rows(["m"], [(5, (old,), -1), (5, (again,), 1),
                                    (6, (old,), -1), (6, (old,), 1),
                                    (7, (old,), 1)])
    out, compared = consolidate_counted(batch)
    # equal values in distinct objects: each leaf of both, as the hash did;
    # the same object twice: nothing; the row alone under its key: nothing
    assert len(serialised) == 600 and compared == 4
    assert [(k, d) for k, _row, d in out.rows()] == [(7, 1)]


def test_many_equal_rows_under_few_keys_cost_no_more_than_the_deep_hash(
        monkeypatch, serialised):
    """The wordcount shape: every row shares its key with many others. Rows
    of three and more under one key are grouped by a content hash of THOSE
    rows, each serialised once, as every row of the batch was before."""
    monkeypatch.setattr(batch_mod, "_native_consolidate", None)
    monkeypatch.setattr(value_mod, "_native_hash_col", None)
    rows = [(k, (f"word{k}", Json({"n": k})), d)
            for k in (1, 2, 3) for d in (1, 1, -1, 1, -1)]
    rows += [(9, ("alone", Json({})), 1)]
    batch = Batch.from_rows(["w", "j"], rows)
    out, compared = consolidate_counted(batch)
    assert compared == 15 and len(serialised) == 2 * 15    # two leaves a row
    assert [(k, d) for k, _row, d in out.rows()] == [(1, 1), (2, 1), (3, 1), (9, 1)]


@pytest.mark.parametrize("native", [True, False])
def test_crowded_keys_with_and_without_the_native_grouping(monkeypatch, native):
    if not native:
        monkeypatch.setattr(batch_mod, "_native_consolidate", None)
    for seed in range(20):
        batch = _random_batch(seed, "everything", 2)
        assert _as_listed(consolidate(batch)) == plain_consolidate(batch)


def test_the_two_early_exits_keep_their_meaning():
    distinct = Batch.from_rows(["x"], [(k, (k,), 1) for k in range(1, 50)])
    out, compared = consolidate_counted(distinct)
    assert out is distinct and distinct._consolidated and compared == 0
    # the proof rides through column transforms; a proven batch is not
    # looked at again
    again, compared = consolidate_counted(distinct.with_cols({"x": distinct.cols["x"]}))
    assert again._consolidated and compared == 0
    # a zero diff alone under its key is dropped, the others kept
    zeros = Batch(np.array([1, 2, 3], np.uint64),
                  {"x": np.array([1, 2, 3], object)}, np.array([1, 0, -1]))
    assert [(k, d) for k, _r, d in consolidate(zeros).rows()] == [(1, 1), (3, -1)]
    assert consolidate(Batch.empty(["x"])) is None and consolidate(None) is None
    gone = Batch.from_rows(["x"], [(1, ("a",), 1), (1, ("a",), -1)])
    assert consolidate(gone) is None
