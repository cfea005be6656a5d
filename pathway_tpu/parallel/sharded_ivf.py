"""Pod-sharded IVF-Flat: per-chip inverted files, ICI top-k merge.

Extends the sharded brute-force design (``parallel/sharded_knn.py``) to the
approximate index: every device owns an independent IVF shard — its own
centroids and cell-major corpus block — mirroring the reference's
one-index-instance-per-worker contract
(``/root/reference/src/external_integration/mod.rs:46``) with uSearch HNSW
replaced by the TPU-native IVF (``ops/ivf.py``). One ``shard_map`` step does

    local centroid gemm -> top-nprobe cells -> local member gemm + top-k
    -> all_gather(k per shard over ICI) -> replicated merge top-k

so per query only ``dp * k`` candidates cross the interconnect while each
chip scans ``nprobe / n_cells`` of its shard — the compute drops multiply:
``dp`` ways data-parallel x ``n_cells/nprobe`` IVF pruning.
"""

from __future__ import annotations

import functools
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp

from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pathway_tpu.parallel.mesh import DATA_AXIS, MeshRef as _MeshRef

_NEG_INF = -1e30


def _local_ivf_topk(cells, valid, centroids, q, k: int, nprobe: int,
                    metric: str):
    """One shard's IVF search: (C, cap, d) cells -> (Q, k) local best.
    Returns (scores, flat local slot = cell * cap + slot)."""
    if nprobe >= cells.shape[0]:
        # exhaustive probing (what the KNN factories ask for): every query
        # scans every cell, so there is nothing to gather — a per-query
        # copy of the WHOLE shard, (Q, C, cap, d), is 24 GiB at 1M rows a
        # shard and the chip's compiler refuses it
        probe = jnp.broadcast_to(
            jnp.arange(cells.shape[0], dtype=jnp.int32)[None, :],
            (q.shape[0], cells.shape[0]),
        )
        cand_valid = valid[None]                               # (1,C,cap)
        dots = jnp.einsum("qd,pcd->qpc", q.astype(jnp.bfloat16), cells,
                          preferred_element_type=jnp.float32)
        cn = (jnp.sum(cells.astype(jnp.float32) ** 2, axis=2)[None]
              if metric == "l2" else None)
    else:
        if metric == "l2":
            qn = jnp.sum(q * q, axis=1, keepdims=True)
            cent_n = jnp.sum(centroids * centroids, axis=1)[None, :]
            cent_scores = -(qn + cent_n - 2.0 * q @ centroids.T)
        else:
            cent_scores = q @ centroids.T
        _, probe = jax.lax.top_k(cent_scores, nprobe)          # (Q, nprobe)
        cand = jnp.take(cells, probe, axis=0)                  # (Q,np,cap,d)
        cand_valid = jnp.take(valid, probe, axis=0)            # (Q,np,cap)
        dots = jnp.einsum("qd,qpcd->qpc", q.astype(jnp.bfloat16), cand,
                          preferred_element_type=jnp.float32)
        cn = (jnp.sum(cand.astype(jnp.float32) ** 2, axis=3)
              if metric == "l2" else None)
    if metric == "l2":
        qn = jnp.sum(q * q, axis=1)[:, None, None]
        scores = -(qn + cn - 2.0 * dots)
    else:
        scores = dots
    scores = jnp.where(cand_valid, scores, _NEG_INF)
    Q, npr, cap = scores.shape
    k_local = min(k, npr * cap)
    top_sc, flat_idx = jax.lax.top_k(scores.reshape(Q, npr * cap), k_local)
    cell_ids = jnp.take_along_axis(probe, flat_idx // cap, axis=1)
    local_slot = cell_ids * cap + flat_idx % cap
    return top_sc, local_slot




@functools.partial(
    jax.jit, static_argnames=("k", "nprobe", "metric", "mesh_ref")
)
def _sharded_ivf_search(cells, valid, centroids, queries, k: int,
                        nprobe: int, metric: str, mesh_ref):
    """cells (dp*C, cap, d), valid (dp*C, cap), centroids (dp*C, d) — all
    sharded on axis 0; queries (Q, d) replicated. Returns replicated
    (scores (Q, k'), global slots (Q, k')) where a global slot is
    ``shard * (C * cap) + cell * cap + slot``."""
    mesh = mesh_ref.mesh
    dp = mesh.shape[DATA_AXIS]
    C = cells.shape[0] // dp
    cap = cells.shape[1]

    def local(cells_blk, valid_blk, cent_blk, q):
        sc, local_slot = _local_ivf_topk(
            cells_blk, valid_blk, cent_blk, q, k, nprobe, metric
        )
        shard = jax.lax.axis_index(DATA_AXIS)
        gslot = local_slot + shard * (C * cap)
        all_sc = jax.lax.all_gather(sc, DATA_AXIS)      # (dp, Q, k_local)
        all_idx = jax.lax.all_gather(gslot, DATA_AXIS)
        Q = q.shape[0]
        k_local = sc.shape[1]
        flat_sc = jnp.transpose(all_sc, (1, 0, 2)).reshape(Q, dp * k_local)
        flat_idx = jnp.transpose(all_idx, (1, 0, 2)).reshape(Q, dp * k_local)
        k_final = min(k, dp * k_local)
        m_sc, m_pos = jax.lax.top_k(flat_sc, k_final)
        m_idx = jnp.take_along_axis(flat_idx, m_pos, axis=1)
        return m_sc, m_idx

    return shard_map(
        local,
        mesh=mesh,
        in_specs=(P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS), P()),
        out_specs=(P(), P()),
        check_vma=False,
    )(cells, valid, centroids, queries)


def sharded_ivf_topk_merge(mesh: Mesh, cells, valid, centroids, queries,
                           k: int, nprobe: int, metric: str = "cos"):
    """Functional entry (used by the dryrun and the host wrapper)."""
    return _sharded_ivf_search(cells, valid, centroids, queries, k, nprobe,
                               metric, _MeshRef(mesh))


class ShardedIvfIndex:
    """Multi-chip IVF index: host routes each key to the least-loaded shard
    and into that shard's nearest cell; the dense state lives
    device-sharded. Centroids are seeded per shard from its first batch and
    refined with k-means once ``train_after`` vectors have arrived
    (matching the single-chip ``IvfFlatIndex`` lifecycle)."""

    def __init__(self, mesh: Mesh, dimensions: int, n_cells: int = 64,
                 nprobe: int = 8, cell_capacity: int = 64,
                 metric: str = "cos", train_after: int | None = None,
                 dtype=jnp.bfloat16):
        from pathway_tpu.ops import canonical_metric, next_pow2

        self.mesh = mesh
        self.dp = mesh.shape[DATA_AXIS]
        self.dim = dimensions
        self.n_cells = n_cells
        self.nprobe = min(nprobe, n_cells)
        self.cell_cap = next_pow2(cell_capacity, 16)
        self.metric = canonical_metric(metric)
        self.dtype = dtype
        self.train_after = (
            n_cells * 16 if train_after is None else train_after
        )
        self._trained = False
        self._pending: list[np.ndarray] = []
        # host mirrors (synced to device on flush) — simpler than the
        # brute-force index's dirty-scatter because IVF rebuilds move rows
        # between cells at train time anyway
        total = self.dp * n_cells
        self._h_cells = np.zeros((total, self.cell_cap, dimensions),
                                 np.float32)
        self._h_valid = np.zeros((total, self.cell_cap), bool)
        self._h_centroids: np.ndarray | None = None  # (dp*C, d)
        self._key_of: dict[int, Any] = {}     # global slot -> key
        self._loc: dict[Any, int] = {}        # key -> global slot
        self._shard_count = [0] * self.dp
        self._dev = None  # (cells, valid, centroids) device copies

    def __len__(self) -> int:
        return len(self._loc)

    def _prep(self, vectors) -> np.ndarray:
        from pathway_tpu.ops import prep_host_vectors

        return prep_host_vectors(vectors, self.metric)

    def _seed(self, v: np.ndarray) -> None:
        if self._h_centroids is not None:
            return
        total = self.dp * self.n_cells
        reps = int(np.ceil(total / max(len(v), 1)))
        seed = np.tile(v, (reps, 1))[:total]
        seed = seed + np.random.default_rng(0).normal(scale=1e-3,
                                                      size=seed.shape)
        self._h_centroids = seed.astype(np.float32)

    def _place(self, key, vec: np.ndarray, shard: int, cell: int) -> None:
        """Slot-allocation invariant lives HERE only: a free slot in the
        chosen (shard, cell), growing on overflow, then cells/valid/key
        maps/shard counts updated together."""
        gcell = shard * self.n_cells + cell
        free = np.nonzero(~self._h_valid[gcell])[0]
        if len(free) == 0:
            self._grow_cells()
            free = np.nonzero(~self._h_valid[gcell])[0]
        slot = int(free[0])
        self._h_cells[gcell, slot] = vec
        self._h_valid[gcell, slot] = True
        g = gcell * self.cell_cap + slot
        self._key_of[g] = key
        self._loc[key] = g
        self._shard_count[shard] += 1

    def _insert_batch(self, keys: list, vecs: np.ndarray) -> None:
        """Batched insert: shards chosen so final loads balance, then ONE
        centroid gemm per shard assigns cells (vs a per-vector gemm)."""
        counts = list(self._shard_count)
        shards = np.empty(len(keys), dtype=np.int64)
        for i in range(len(keys)):
            s = int(np.argmin(counts))
            counts[s] += 1
            shards[i] = s
        for s in np.unique(shards):
            idx = np.nonzero(shards == s)[0]
            c0 = int(s) * self.n_cells
            cents = self._h_centroids[c0 : c0 + self.n_cells]
            block = vecs[idx]
            if self.metric == "l2":
                d2 = (
                    np.sum(block * block, axis=1, keepdims=True)
                    + np.sum(cents * cents, axis=1)[None, :]
                    - 2.0 * block @ cents.T
                )
                cells = np.argmin(d2, axis=1)
            else:
                cells = np.argmax(block @ cents.T, axis=1)
            for j, i in enumerate(idx):
                self._place(keys[int(i)], vecs[int(i)], int(s), int(cells[j]))

    def add(self, keys: list, vectors) -> None:
        if not keys:
            return
        v = self._prep(vectors)
        self._seed(v)
        if len(set(keys)) != len(keys):
            # duplicate keys in one batch: last occurrence wins (upsert)
            last = {k: i for i, k in enumerate(keys)}
            keep = sorted(last.values())
            keys = [keys[i] for i in keep]
            v = v[keep]
        existing = [k for k in keys if k in self._loc]
        if existing:
            self.remove(existing)
        self._insert_batch(keys, v)
        if not self._trained:
            self._pending.append(v)
            self._maybe_train()
        self._dev = None  # host state changed; re-upload on next search

    def _train_from(self, v: np.ndarray) -> None:
        """Train centroids directly from an incoming sample (classic IVF
        build order: train, then add) instead of waiting for the
        ``train_after`` watermark — the bulk path would otherwise pay a
        per-vector ``_rebuild`` over millions of rows after training."""
        from pathway_tpu.ops.ivf import kmeans_fit

        per = self.train_after * 4
        for shard in range(self.dp):
            c0 = shard * self.n_cells
            rows = v[shard :: self.dp][:per]
            if len(rows) == 0:
                continue
            self._h_centroids[c0 : c0 + self.n_cells] = np.asarray(
                kmeans_fit(
                    jnp.asarray(rows, jnp.float32),
                    jnp.asarray(self._h_centroids[c0 : c0 + self.n_cells]),
                )
            )
        self._trained = True
        self._pending.clear()
        if self._loc:
            # rows placed before training sit in seed-centroid cells;
            # re-place them under the trained centroids
            self._rebuild()

    def _balanced_quotas(self, n: int) -> np.ndarray:
        """Rows-per-shard so the FINAL loads are as level as possible
        (water filling): find the lowest level L whose fill capacity
        covers ``n``, fill every shard to L-1, then hand the leftover to
        the shards still below L. Equivalent to n iterations of
        argmin(counts) without the per-row Python loop."""
        counts = np.asarray(self._shard_count, np.int64)
        lo, hi = int(counts.min()), int(counts.max()) + n
        while lo < hi:
            mid = (lo + hi) // 2
            if int(np.maximum(0, mid - counts).sum()) >= n:
                hi = mid
            else:
                lo = mid + 1
        quota = np.maximum(0, (lo - 1) - counts)
        leftover = n - int(quota.sum())
        elig = np.nonzero(counts + quota < lo)[0]
        quota[elig[:leftover]] += 1
        return quota

    def add_bulk(self, keys: list, vectors, chunk: int = 65536) -> None:
        """Bulk build for multi-million-row loads: everything per-row in
        :meth:`add` becomes per-cell or per-chunk.

        * shard choice: closed-form water filling (``_balanced_quotas``)
          instead of an argmin per vector;
        * cell choice: chunked ``block @ centroids.T`` argmax, bounding the
          score temp at ``chunk x n_cells`` floats;
        * slot packing: rows grouped by destination cell (one stable sort),
          then each touched cell takes a contiguous run of its free slots —
          at most ``n_cells`` Python iterations per shard, not one per row.

        Untrained indexes train from the incoming sample first (build-time
        k-means), so no post-hoc rebuild is needed. Falls back to
        :meth:`add` for upserts/duplicates, where per-key handling is the
        point."""
        if not keys:
            return
        if len(set(keys)) != len(keys) or any(k in self._loc for k in keys):
            self.add(keys, vectors)
            return
        v = self._prep(vectors)
        self._seed(v)
        if not self._trained:
            self._train_from(v)
        quota = self._balanced_quotas(len(keys))
        start = 0
        for s in range(self.dp):
            m = int(quota[s])
            if m == 0:
                continue
            block = v[start : start + m]
            bkeys = keys[start : start + m]
            start += m
            c0 = s * self.n_cells
            cents = self._h_centroids[c0 : c0 + self.n_cells]
            cells = np.empty(m, np.int64)
            for o in range(0, m, chunk):
                blk = block[o : o + chunk]
                if self.metric == "l2":
                    d2 = (
                        np.sum(blk * blk, axis=1, keepdims=True)
                        + np.sum(cents * cents, axis=1)[None, :]
                        - 2.0 * blk @ cents.T
                    )
                    cells[o : o + len(blk)] = np.argmin(d2, axis=1)
                else:
                    cells[o : o + len(blk)] = np.argmax(blk @ cents.T, axis=1)
            order = np.argsort(cells, kind="stable")
            sorted_cells = cells[order]
            uniq, first = np.unique(sorted_cells, return_index=True)
            bounds = np.append(first, m)
            for ui in range(len(uniq)):
                rows = order[bounds[ui] : bounds[ui + 1]]
                gcell = c0 + int(uniq[ui])
                free = np.nonzero(~self._h_valid[gcell])[0]
                while len(free) < len(rows):
                    self._grow_cells()
                    free = np.nonzero(~self._h_valid[gcell])[0]
                slots = free[: len(rows)]
                self._h_cells[gcell, slots] = block[rows]
                self._h_valid[gcell, slots] = True
                g = (gcell * self.cell_cap + slots).tolist()
                kk = [bkeys[r] for r in rows.tolist()]
                self._key_of.update(zip(g, kk))
                self._loc.update(zip(kk, g))
            self._shard_count[s] += m
        self._dev = None

    def _grow_cells(self) -> None:
        new_cap = self.cell_cap * 2
        cells = np.zeros(
            (self._h_cells.shape[0], new_cap, self.dim), np.float32
        )
        valid = np.zeros((self._h_valid.shape[0], new_cap), bool)
        cells[:, : self.cell_cap] = self._h_cells
        valid[:, : self.cell_cap] = self._h_valid
        remap = {}
        for g, key in self._key_of.items():
            gcell, slot = divmod(g, self.cell_cap)
            remap[gcell * new_cap + slot] = key
        self._key_of = remap
        self._loc = {k: g for g, k in remap.items()}
        self._h_cells, self._h_valid = cells, valid
        self.cell_cap = new_cap

    def _maybe_train(self) -> None:
        if self._trained or len(self._loc) < self.train_after * self.dp:
            return
        from pathway_tpu.ops.ivf import kmeans_fit

        sample = np.concatenate(self._pending)
        # per-shard k-means on the rows that shard owns
        for shard in range(self.dp):
            c0 = shard * self.n_cells
            rows = sample[shard::self.dp][: self.train_after * 4]
            if len(rows) == 0:
                continue
            self._h_centroids[c0 : c0 + self.n_cells] = np.asarray(
                kmeans_fit(
                    jnp.asarray(rows, jnp.float32),
                    jnp.asarray(self._h_centroids[c0 : c0 + self.n_cells]),
                )
            )
        self._trained = True
        self._pending.clear()
        self._rebuild()

    def _rebuild(self) -> None:
        items = list(self._loc.items())
        vecs = np.stack(
            [
                self._h_cells[g // self.cell_cap, g % self.cell_cap]
                for _, g in items
            ]
        ) if items else np.zeros((0, self.dim), np.float32)
        keys = [k for k, _ in items]
        self._h_cells[:] = 0.0
        self._h_valid[:] = False
        self._key_of.clear()
        self._loc.clear()
        self._shard_count = [0] * self.dp
        # re-add without re-normalizing (vectors are already prepped)
        if keys:
            self._insert_batch(keys, vecs)
        self._dev = None

    def remove(self, keys: list) -> None:
        for key in keys:
            g = self._loc.pop(key, None)
            if g is None:
                continue
            gcell, slot = divmod(g, self.cell_cap)
            self._h_valid[gcell, slot] = False
            self._key_of.pop(g, None)
            self._shard_count[gcell // self.n_cells] -= 1
        self._dev = None

    def _device_state(self):
        if self._dev is None:
            # cast on the HOST and put each shard straight onto its own
            # device: jnp.asarray would first commit the whole f32 mirror
            # to device 0, which at 1M rows a shard is more than one chip
            shd = NamedSharding(self.mesh, P(DATA_AXIS))
            self._dev = (
                jax.device_put(self._h_cells.astype(self.dtype), shd),
                jax.device_put(self._h_valid, shd),
                jax.device_put(self._h_centroids, shd),
            )
        return self._dev

    def search(self, queries, k: int) -> list[list[tuple[Any, float]]]:
        from pathway_tpu.engine.probes import record_retrieval_backend
        from pathway_tpu.ops import next_pow2

        if len(self._loc) == 0:
            q = np.asarray(queries)
            nq = 1 if q.ndim == 1 else len(q)
            record_retrieval_backend("sharded_ivf", nq)
            return [[] for _ in range(nq)]
        q = self._prep(queries)
        nq = len(q)
        record_retrieval_backend("sharded_ivf", nq)
        bucket = next_pow2(nq, 16)
        if bucket > nq:
            q = np.concatenate(
                [q, np.zeros((bucket - nq, self.dim), np.float32)]
            )
        cells, valid, cents = self._device_state()
        sc, gslots = jax.device_get(
            sharded_ivf_topk_merge(
                self.mesh, cells, valid, cents, jnp.asarray(q), k,
                self.nprobe, self.metric,
            )
        )
        out = []
        for qi in range(nq):
            row = []
            for j in range(sc.shape[1]):
                s = float(sc[qi, j])
                if s <= _NEG_INF / 2:
                    continue
                key = self._key_of.get(int(gslots[qi, j]))
                if key is not None:
                    row.append((key, s))
                if len(row) >= k:
                    break
            out.append(row)
        return out
