"""Columnar delta batches — the unit of data flowing between engine operators.

The reference moves per-row ``(key, tuple, time, diff)`` triples through
timely exchange channels (``external/differential-dataflow``). Here a batch is
a **struct-of-arrays**: a uint64 key vector, aligned value columns (typed numpy
arrays for dense numeric data, object arrays otherwise) and an int64 diff
vector, all for one logical timestamp. Dense columns can be handed to jitted
XLA kernels without conversion; irregular columns stay on host.
"""

from __future__ import annotations

import struct
from typing import Any, Iterable, Mapping

import numpy as np

from pathway_tpu.engine import value as value_mod

_rows_split = False  # lazily bound: False = unchecked, None = unavailable


def _native_rows_split():
    """C++ SoA transpose for from_rows (one pass instead of n*ncols
    Python array writes); None when the native module isn't built."""
    global _rows_split
    if _rows_split is False:
        from pathway_tpu.native.binding import native_bind

        _rows_split = native_bind("batch_rows_split")
    return _rows_split


class Batch:
    """A set of keyed row deltas at a single logical time."""

    __slots__ = ("keys", "cols", "diffs", "_consolidated")

    def __init__(
        self,
        keys: np.ndarray,
        cols: dict[str, np.ndarray],
        diffs: np.ndarray | None = None,
    ):
        keys = np.asarray(keys, dtype=np.uint64)
        self.keys = keys
        self.cols = cols
        if diffs is None:
            diffs = np.ones(len(keys), dtype=np.int64)
        self.diffs = np.asarray(diffs, dtype=np.int64)
        # True once a consolidate() proved this batch single-sign with
        # all-distinct keys. That invariant survives row subsetting and any
        # column transform (keys/diffs untouched), so downstream operators
        # inherit it through take/with_cols/... and their consolidate pass
        # is O(1) instead of a per-epoch np.unique sort over the spine
        # (gated by PATHWAY_TPU_EPOCH_CLOSEOUT at the consumer).
        self._consolidated = False

    def __len__(self) -> int:
        return len(self.keys)

    def __repr__(self) -> str:
        return f"Batch(n={len(self)}, cols={list(self.cols)})"

    @property
    def column_names(self) -> list[str]:
        return list(self.cols)

    def rows(self) -> Iterable[tuple[int, tuple, int]]:
        """Iterate (key, row_tuple, diff). Columns are converted with
        ``tolist`` and zipped in C — ~3x faster than per-element numpy
        scalar extraction on row-loop-heavy operators."""
        keys = self.keys.tolist()
        diffs = self.diffs.tolist()
        col_lists = [c.tolist() for c in self.cols.values()]
        if col_lists:
            return zip(keys, zip(*col_lists), diffs)
        return zip(keys, ((),) * len(keys), diffs)

    def take(self, mask_or_idx: np.ndarray) -> "Batch":
        if mask_or_idx.dtype == bool:
            # all-true mask: skip the nonzero scan AND the per-column gather
            # copies (the hot shape — filters on streaming ingest mostly
            # pass everything). Safe to alias: batches are treated as
            # immutable by operators (consolidate only mutates fresh
            # int-indexed copies).
            if mask_or_idx.all():
                return self
            idx = np.nonzero(mask_or_idx)[0]
        else:
            idx = mask_or_idx
        out = Batch(
            self.keys[idx],
            {n: c[idx] for n, c in self.cols.items()},
            self.diffs[idx],
        )
        out._consolidated = self._consolidated  # subset of distinct keys
        return out

    def with_cols(self, cols: dict[str, np.ndarray]) -> "Batch":
        out = Batch(self.keys, cols, self.diffs)
        out._consolidated = self._consolidated  # keys/diffs untouched
        return out

    def rename(self, mapping: Mapping[str, str]) -> "Batch":
        out = Batch(
            self.keys,
            {mapping.get(n, n): c for n, c in self.cols.items()},
            self.diffs,
        )
        out._consolidated = self._consolidated
        return out

    def select_cols(self, names: list[str]) -> "Batch":
        out = Batch(self.keys, {n: self.cols[n] for n in names}, self.diffs)
        out._consolidated = self._consolidated
        return out

    def negate(self) -> "Batch":
        out = Batch(self.keys, self.cols, -self.diffs)
        out._consolidated = self._consolidated  # sign flip stays single-sign
        return out

    @staticmethod
    def empty(column_names: Iterable[str]) -> "Batch":
        return Batch(
            np.empty(0, dtype=np.uint64),
            {n: np.empty(0, dtype=object) for n in column_names},
            np.empty(0, dtype=np.int64),
        )

    @staticmethod
    def from_rows(
        column_names: list[str],
        rows: list[tuple[int, tuple, int]],
    ) -> "Batch":
        n = len(rows)
        names = list(column_names)
        split = _native_rows_split()
        if split is not None and n:
            keys = np.empty(n, dtype=np.uint64)
            diffs = np.empty(n, dtype=np.int64)
            try:
                col_lists = split(
                    rows if isinstance(rows, list) else list(rows),
                    len(names), memoryview(keys), memoryview(diffs),
                )
            except TypeError:
                pass  # list rows / odd key types: python path below
            else:
                cols = {}
                for name, cl in zip(names, col_lists):
                    a = np.empty(n, dtype=object)
                    a[:] = cl
                    cols[name] = a
                return Batch(keys, cols, diffs)
        keys = np.empty(n, dtype=np.uint64)
        diffs = np.empty(n, dtype=np.int64)
        cols = {name: np.empty(n, dtype=object) for name in names}
        for i, (k, row, d) in enumerate(rows):
            keys[i] = k
            diffs[i] = d
            for j, name in enumerate(names):
                cols[name][i] = row[j]
        return Batch(keys, cols, diffs)


def concat_batches(batches: list[Batch]) -> Batch | None:
    batches = [b for b in batches if b is not None and len(b) > 0]
    if not batches:
        return None
    if len(batches) == 1:
        return batches[0]
    names = batches[0].column_names
    keys = np.concatenate([b.keys for b in batches])
    diffs = np.concatenate([b.diffs for b in batches])
    cols = {}
    for n in names:
        arrays = [b.cols[n] for b in batches]
        if all(a.dtype == arrays[0].dtype and a.dtype != object for a in arrays):
            cols[n] = np.concatenate(arrays)
        else:
            cols[n] = np.concatenate([a.astype(object) for a in arrays])
    return Batch(keys, cols, diffs)


def _canonical(value: Any) -> bytes:
    out = bytearray()
    value_mod.serialize_value(value, out)
    return bytes(out)


_PACK_DOUBLE = struct.Struct("<d").pack
# exact types whose ``==`` is the serialiser's equality
_PLAIN = frozenset((str, int, bytes, bool, value_mod.Pointer))


def same_value(a: Any, b: Any) -> bool:
    """Whether two values are the same value to the engine: their canonical
    serialisations (``value.serialize_value``) are equal. Decided by the
    cheapest evidence first — identity, then (for two values of one exact
    type) a tuple's length and its elements in turn, a plain ``==`` where
    that IS the serialiser's equality — and by the serialisation itself
    only for what is still undecided (a ``Json``, an ndarray, a datetime,
    values of unlike types: ``1`` and ``np.int64(1)`` are one value, ``1``,
    ``1.0`` and ``True`` three). So two tuples of unequal length cost a
    length check, a tuple with one changed element a walk of identity
    checks and one leaf, and only a true match walks the whole value."""
    if a is b:
        return True
    kind = type(a)
    if kind is type(b):
        if kind is tuple or kind is list:
            if len(a) != len(b):
                return False
            for x, y in zip(a, b):
                if x is not y and not same_value(x, y):
                    return False
            return True
        if kind in _PLAIN:
            return a == b
        if kind is float:  # by bits, as packed: 0.0 is not -0.0, nan is nan
            return _PACK_DOUBLE(a) == _PACK_DOUBLE(b)
    return _canonical(a) == _canonical(b)


def _same_row(batch: Batch, i: int, j: int) -> bool:
    """Are rows ``i`` and ``j`` of ``batch`` the same row?"""
    for col in batch.cols.values():
        x, y = col[i], col[j]
        if col.dtype != object:  # as ``astype(object)`` would hand them over
            x, y = x.item(), y.item()
        if x is not y and not same_value(x, y):
            return False
    return True


def _same_pairs(batch: Batch, left: np.ndarray, right: np.ndarray) -> np.ndarray:
    """For rows ``left[i]`` and ``right[i]`` of ``batch``: are they the same
    row? Column by column, and in a later column only for the pairs that
    the earlier ones left equal."""
    same = np.ones(len(left), dtype=bool)
    for col in batch.cols.values():
        if col.dtype.kind in "biuf" and col.dtype.itemsize <= 8:
            # a typed column holds one type: equal bits, equal serialisation
            bits = col.view(f"u{col.dtype.itemsize}")
            same &= bits[left] == bits[right]
            continue
        live = same.nonzero()[0]
        if len(live) == 0:
            break
        if col.dtype != object:
            col = col.astype(object)
        pairs = zip(col[left[live]].tolist(), col[right[live]].tolist())
        for i, (x, y) in zip(live.tolist(), pairs):
            if x is not y and not same_value(x, y):
                same[i] = False
    return same


def _sum_by_content(batch: Batch, rows: np.ndarray):
    """Rows of ``batch`` under keys that three or more of them share: the
    first row of each distinct (key, content) and the sum of its diffs,
    grouped by a hash of the canonical serialisation of these rows alone."""
    content = value_mod.keys_for_value_columns(
        [col[rows] for col in batch.cols.values()], len(rows)
    )
    keys, diffs = batch.keys[rows], batch.diffs[rows]
    native = _get_native_consolidate()
    if native is not None:
        first, summed = native(keys, content, diffs)
        return rows[first.astype(np.int64)], summed
    combo = np.empty(len(rows), dtype=[("k", np.uint64), ("r", np.uint64)])
    combo["k"] = keys
    combo["r"] = content
    _uniq, first, inverse = np.unique(
        combo, return_index=True, return_inverse=True
    )
    summed = np.zeros(len(first), dtype=np.int64)
    np.add.at(summed, inverse.ravel(), diffs)
    return rows[first], summed


def _distinct_keys(batch: Batch) -> tuple[Batch | None, int]:
    """No two rows share a key: identical (key, row) pairs are impossible —
    the common shape of every bulk-ingest commit, where reading wide object
    columns (e.g. embedding vectors) would dominate the epoch."""
    diffs = batch.diffs
    if diffs.min() > 0 or diffs.max() < 0:
        batch._consolidated = True
        return batch, 0
    if diffs.all():
        return batch, 0
    live = diffs.nonzero()[0]
    return (batch.take(live) if len(live) else None), 0


def consolidate_counted(batch: Batch | None) -> tuple[Batch | None, int]:
    """Sum diffs of identical (key, row) pairs; drop zero-diff rows. Also
    returns how many rows had their CONTENT examined to decide that.

    Two rows can only cancel or sum if they share a key, so the keys decide
    first: a row whose key is alone in the batch is kept as it is, its
    content never read — what a commit pays follows the rows it changes,
    not the size of the values they hold (a standing ``reducers.tuple`` of
    every row ever seen is one value). Two rows under one key — the (-old,
    +new) of every update — are compared by :func:`same_value`; three or
    more by a content hash of those rows alone."""
    if batch is None or len(batch) == 0:
        return None, 0
    # a producer already proved this batch single-sign with distinct keys
    # (the invariant column transforms preserve) — skip even the sort-based
    # uniqueness re-check, which otherwise repeats at EVERY node of the
    # operator spine per epoch
    if batch._consolidated:
        from pathway_tpu.internals import config as config_mod

        if config_mod.pathway_config.epoch_closeout:
            return batch, 0
    n = len(batch)
    keys, diffs = batch.keys, batch.diffs
    if n <= 2:
        # one group's update: a row, or its (-old, +new) — no sort needed
        if n == 1 or keys[0] != keys[1]:
            return _distinct_keys(batch)
        if not _same_row(batch, 0, 1):
            return batch, 2
        total = diffs[0] + diffs[1]
        if total == 0:
            return None, 2
        out = batch.take(np.zeros(1, dtype=np.int64))
        out.diffs = np.array([total], dtype=np.int64)
        return out, 2
    order = keys.argsort(kind="stable")
    sorted_keys = keys[order]
    new_key = sorted_keys[1:] != sorted_keys[:-1]
    if new_key.all():
        return _distinct_keys(batch)
    # runs of rows under one key, in sorted order. The stable sort keeps
    # the rows of a run in batch order, so a run's first row is its first
    # occurrence: the one that stays for the group
    starts = np.empty(int(new_key.sum()) + 1, dtype=np.int64)
    starts[0] = 0
    starts[1:] = new_key.nonzero()[0] + 1
    sizes = np.empty_like(starts)
    sizes[:-1] = starts[1:] - starts[:-1]
    sizes[-1] = n - starts[-1]
    summed = diffs.copy()
    keep = np.ones(n, dtype=bool)
    pairs = starts[sizes == 2]
    compared = 2 * len(pairs)
    if len(pairs):
        left, right = order[pairs], order[pairs + 1]
        same = _same_pairs(batch, left, right)
        summed[left[same]] += diffs[right[same]]
        keep[right[same]] = False
    crowd = sizes > 2
    if crowd.any():
        rows = np.sort(order[np.repeat(crowd, sizes)])
        compared += len(rows)
        first, total = _sum_by_content(batch, rows)
        keep[rows] = False
        keep[first] = True
        summed[first] = total
    keep &= summed != 0
    if keep.all():
        return batch, compared
    live = keep.nonzero()[0]
    if len(live) == 0:
        return None, compared
    out = batch.take(live)
    out.diffs = summed[live]
    return out, compared


def consolidate(batch: Batch | None) -> Batch | None:
    """Sum diffs of identical (key, row) pairs; drop zero-diff rows."""
    return consolidate_counted(batch)[0]


_native_consolidate = False


def _get_native_consolidate():
    global _native_consolidate
    if _native_consolidate is False:
        try:
            from pathway_tpu import native as _native_mod

            _native_consolidate = (
                _native_mod.consolidate_pairs_native if _native_mod.AVAILABLE else None
            )
        except Exception:  # noqa: BLE001
            _native_consolidate = None
    return _native_consolidate
