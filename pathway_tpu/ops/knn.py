"""Brute-force KNN on TPU: HBM-resident corpus, jitted gemm + top-k.

The reference's brute-force index is a growable host ``Array2<f64>`` with
gemm-based distances (``src/external_integration/brute_force_knn_integration.rs``).
TPU-first redesign:

* the corpus lives **in HBM** as a capacity-doubling padded matrix — append is
  an on-device dynamic_update_slice, no host round-trip;
* distances are one MXU matmul: queries (padded to a bucket size) x corpus^T
  in bfloat16 with float32 accumulation, fused by XLA with the mask and the
  ``lax.top_k`` that follows — exactly the "keep the FLOPs on the MXU, fuse
  the elementwise" recipe;
* deletes are O(1) swaps with the last row (index is unordered);
* static shapes: (capacity, query-bucket, k) are compile-time constants, so
  streams of ragged batches reuse cached executables.
"""

from __future__ import annotations

import functools
import math

from pathway_tpu.engine.probes import (
    record_device_dispatch,
    record_knn_search,
)
from pathway_tpu.engine.tracing import region
from pathway_tpu.ops import canonical_metric, next_pow2, prep_host_vectors
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp


_NEG_INF = -1e30


def knn_scores(corpus, valid_mask, queries, metric: str,
               f32_scores: bool = False):
    """Masked similarity scores, higher is better; one MXU gemm.
    corpus (N,d) bf16, queries (Q,d) f32 -> (Q,N) f32. Shared by the
    single-chip kernel below and parallel/sharded_knn's per-shard kernel.

    Accumulation is f32 either way (``preferred_element_type``); the
    default casts OPERANDS to bf16 for the MXU fast path, which is where
    the ~4% recall@10 vs f32 host truth actually goes. ``f32_scores=True``
    (PATHWAY_TPU_KNN_F32_SCORES, or ``BruteForceKnnIndex(f32_scores=...)``)
    keeps queries f32 and upcasts the corpus for the dot — recall-first at
    roughly half the gemm throughput."""
    if f32_scores:
        q = queries.astype(jnp.float32)
        c = corpus.astype(jnp.float32)
    else:
        q = queries.astype(jnp.bfloat16)
        c = corpus
    dots = jax.lax.dot_general(
        q,
        c,
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (Q, N)
    if metric == "l2":
        qn = jnp.sum(queries.astype(jnp.float32) ** 2, axis=1, keepdims=True)
        cn = jnp.sum(c.astype(jnp.float32) ** 2, axis=1)[None, :]
        scores = -(qn + cn - 2.0 * dots)  # negative squared L2
    else:  # cosine / dot on normalized vectors
        scores = dots
    return jnp.where(valid_mask[None, :], scores, _NEG_INF)


def _normalize(v):
    """Device-side unit-normalise (zero vectors map to ~0, not NaN)."""
    return v / jnp.clip(jnp.linalg.norm(v, axis=1, keepdims=True), 1e-9, None)


_TOPK_BLOCK = 8192
# the query buckets of one search dispatch, each its own executable. The
# kernel holds bucket x capacity f32 scores and their block winners, 96 MiB
# a query at capacity 8,388,608 (3 GiB at 32, beside a 6.44 GB index on a
# 16 GB chip; 64 would not fit): ``search`` splits what is above the largest.
_SEARCH_BUCKETS = (16, 32)
_MAX_SEARCH_BUCKET = _SEARCH_BUCKETS[-1]


def topk_scores(scores, k: int):
    """top-k over (Q, N) scores; for large N a two-stage blocked reduction
    — ``lax.top_k`` cost grows superlinearly in row length (sorting
    networks), so per-block top-k followed by top-k over the block winners
    is MUCH faster at 10^6-row corpora (measured seconds -> milliseconds).

    A ragged tail (``N % _TOPK_BLOCK != 0``) pads the last block with
    ``_NEG_INF`` instead of falling back to the superlinear full-row
    ``lax.top_k``: shapes here are trace-time constants, so the pad is a
    static concat compiled into the executable. Pad slots can never win a
    top-k spot against any real score, and downstream resolvers already
    treat ``score <= _NEG_INF / 2`` as an empty slot."""
    Q, N = scores.shape
    if N <= 2 * _TOPK_BLOCK:
        return jax.lax.top_k(scores, k)
    pad = (-N) % _TOPK_BLOCK
    if pad:
        scores = jnp.concatenate(
            [scores, jnp.full((Q, pad), _NEG_INF, dtype=scores.dtype)],
            axis=1,
        )
    nb = (N + pad) // _TOPK_BLOCK
    kb = min(k, _TOPK_BLOCK)
    bs, bi = jax.lax.top_k(scores.reshape(Q, nb, _TOPK_BLOCK), kb)
    flat_s = bs.reshape(Q, nb * kb)
    fs, fi = jax.lax.top_k(flat_s, k)
    within = jnp.take_along_axis(bi.reshape(Q, nb * kb), fi, axis=1)
    idx = (fi // kb) * _TOPK_BLOCK + within
    return fs, idx


@functools.partial(
    jax.jit, static_argnames=("k", "metric", "normalize", "f32_scores")
)
def _search_kernel(corpus, valid_mask, queries, k: int, metric: str,
                   normalize: bool = False, f32_scores: bool = False):
    """One fused dispatch for the whole search: cast, normalise (optional),
    gemm + top_k. Queries arrive ALREADY padded to their pow2 bucket —
    padding outside the jit makes the executable cache key on the BUCKET,
    not the raw query count (nq=3 and nq=5 share the bucket-16 binary)."""
    q = queries.astype(jnp.float32)
    if normalize:
        q = _normalize(q)
    return topk_scores(
        knn_scores(corpus, valid_mask, q, metric, f32_scores=f32_scores), k
    )


def _write_rows(corpus, valid, n_dev, v, m):
    """Shared in-kernel append body: write ``v`` (f32, already normalized
    as required) at the device cursor, mark the first ``m`` rows valid,
    advance the cursor by ``m``. Both append kernels trace through this so
    the write/cursor invariant has exactly one home."""
    vmask = jnp.arange(v.shape[0]) < m
    corpus = jax.lax.dynamic_update_slice(
        corpus, v.astype(corpus.dtype), (n_dev, 0)
    )
    valid = jax.lax.dynamic_update_slice(valid, vmask, (n_dev,))
    return corpus, valid, n_dev + m


@functools.partial(
    jax.jit, donate_argnums=(0, 1, 2), static_argnames=("normalize",)
)
def _append_kernel(corpus, valid, n_dev, v, m, normalize: bool):
    """One fused dispatch for the whole append: normalise (optional), cast,
    write the corpus rows + valid flags, and advance the device-resident
    write cursor. Donating corpus/valid makes the update in-place in HBM.
    The cursor lives ON DEVICE (``n_dev``): shipping a fresh start offset
    from the host each call would cost one h2d transfer per append.

    ``v`` is padded to a pow2 row bucket with ``m`` the real count:
    streaming commits have ragged sizes, and one executable per BUCKET (not
    per size) keeps XLA from recompiling mid-stream. Pad rows land beyond
    the cursor with valid=False and are overwritten by the next append."""
    v = v.astype(jnp.float32)
    if normalize:
        v = _normalize(v)
    return _write_rows(corpus, valid, n_dev, v, m)


@functools.partial(
    jax.jit,
    donate_argnums=(0, 1, 2),
    static_argnames=("embed", "cfg", "pad_id"),
)
def _embed_append_kernel(corpus, valid, n_dev, params, ids, mask, m, *,
                         embed, cfg, pad_id=0):
    """Embed + append in ONE dispatch: token ids go in, corpus rows come
    out, and the (normalized) embeddings are returned for queries riding
    the stream. Every dispatch has a fixed launch cost, so halving the
    per-batch dispatch count matters beside the kernels themselves.

    ``ids`` may be any integer dtype (int16 halves the h2d transfer for
    vocabularies under 32k — every BERT-family vocab); ``mask=None``
    derives the attention mask on device as ``ids != pad_id``, removing
    the mask transfer entirely. On a bandwidth-constrained link the
    ids-only int16 form cuts per-batch host bytes 4x."""
    ids = ids.astype(jnp.int32)
    if mask is None:
        mask = (ids != pad_id).astype(jnp.int32)
    emb = embed(params, ids, mask, cfg)  # (B, d) f32, unit-normalized
    corpus, valid, n_dev = _write_rows(corpus, valid, n_dev, emb, m)
    return corpus, valid, n_dev, emb


@functools.partial(
    jax.jit,
    donate_argnums=(0, 1, 2),
    static_argnames=(
        "embed", "cfg", "pad_id", "query_rows", "k", "metric", "f32_scores"
    ),
)
def _embed_append_query_kernel(corpus, valid, n_dev, params, ids, mask, m, *,
                               embed, cfg, pad_id, query_rows, k, metric,
                               f32_scores=False):
    """Ingest AND ride-along query in one dispatch: embed the batch, append
    it, then search the first ``query_rows`` fresh embeddings against the
    corpus *as updated by this very append* (self-inclusive as-of-now
    semantics — identical to dispatching a search right after the append).
    Each extra dispatch costs a fixed overhead, so a streaming pipeline
    with queries riding the ingest stream should prefer this over
    ``search_device`` after ``add_embed``."""
    ids = ids.astype(jnp.int32)
    if mask is None:
        mask = (ids != pad_id).astype(jnp.int32)
    emb = embed(params, ids, mask, cfg)
    corpus, valid, n_dev = _write_rows(corpus, valid, n_dev, emb, m)
    # emb is already unit-normalized (embed contract), so cos needs no
    # renormalise here
    scores, idx = topk_scores(
        knn_scores(
            corpus, valid, emb[:query_rows], metric, f32_scores=f32_scores
        ),
        k,
    )
    return corpus, valid, n_dev, emb, scores, idx


_M_SCALARS: dict[int, Any] = {}


def _m_scalar(m: int):
    """Cached device scalar for the append row count — a fresh h2d transfer
    per append would cost one more transfer each. Bounded: a
    bulk loader with wildly varied commit sizes must not pin device buffers
    for the process lifetime."""
    s = _M_SCALARS.get(m)
    if s is None:
        if len(_M_SCALARS) >= 256:
            _M_SCALARS.clear()
        s = jnp.asarray(m, jnp.int32)
        _M_SCALARS[m] = s
    return s


class BruteForceKnnIndex:
    """Single-device TPU KNN index (one instance per worker, like the
    reference's ``ExternalIndexFactory::make_instance``)."""

    def __init__(
        self,
        dimensions: int,
        reserved_space: int = 1024,
        metric: str = "cos",
        auxiliary_space: int = 0,
        dtype=jnp.bfloat16,
        f32_scores: bool | None = None,
    ):
        from pathway_tpu.internals.config import pathway_config

        self.dim = dimensions
        self.metric = canonical_metric(metric)
        # None defers to PATHWAY_TPU_KNN_F32_SCORES (recall-first scoring
        # with f32 operands vs the default bf16 MXU fast path)
        self.f32_scores = (
            pathway_config.knn_f32_scores
            if f32_scores is None else bool(f32_scores)
        )
        self.capacity = next_pow2(reserved_space, 16)
        self.dtype = dtype
        self._corpus = jnp.zeros((self.capacity, self.dim), dtype=dtype)
        self._valid = jnp.zeros((self.capacity,), dtype=bool)
        self._n_dev = jnp.zeros((), dtype=jnp.int32)  # device write cursor
        self.n = 0
        self._keys: list[Any] = []
        self._slot_of: dict[Any, int] = {}
        # (k, capacity) whose every bucket ``search`` has compiled
        self._compiled: set[tuple[int, int]] = set()

    # ------------------------------------------------------------------ sizing
    def _grow(self, needed: int) -> None:
        new_cap = self.capacity
        while new_cap < needed:
            new_cap *= 2
        if new_cap == self.capacity:
            return
        corpus = jnp.zeros((new_cap, self.dim), dtype=self.dtype)
        corpus = jax.lax.dynamic_update_slice(corpus, self._corpus, (0, 0))
        valid = jnp.zeros((new_cap,), dtype=bool)
        valid = jax.lax.dynamic_update_slice(valid, self._valid, (0,))
        self._corpus, self._valid = corpus, valid
        self.capacity = new_cap

    # ------------------------------------------------------------------ update
    def _prep(self, vectors: np.ndarray) -> np.ndarray:
        return prep_host_vectors(vectors, self.metric)

    def _append(self, keys: list, v, normalize: bool) -> None:
        """Shared append: v is a (m, d) array; normalised on device iff
        ``normalize`` (host callers pre-normalise in _prep). Rows pad to a
        pow2 bucket so ragged streaming commits reuse one executable per
        bucket size."""
        m = len(keys)
        # growth is driven by REAL rows only — growing for transient pad
        # rows would double capacity (and recompile every kernel) exactly
        # when reserved_space was sized to the corpus. If the pad bucket
        # would overflow remaining capacity, shrink it to fit (only happens
        # on the final boundary commit).
        self._grow(self.n + m)
        start = self.n
        bucket = min(next_pow2(m, 16), self.capacity - self.n)
        if not isinstance(v, jax.Array):
            v_host = np.asarray(v, dtype=np.float32)
            if bucket > m:
                v_host = np.pad(v_host, ((0, bucket - m), (0, 0)))
            v = jnp.asarray(v_host)
        elif bucket > m:
            v = jnp.pad(v, ((0, bucket - m), (0, 0)))
        self._corpus, self._valid, self._n_dev = _append_kernel(
            self._corpus, self._valid, self._n_dev, v,
            _m_scalar(m), normalize=normalize,
        )
        record_device_dispatch("knn_append")
        self._record_keys(keys, start)

    def add(self, keys: list, vectors: np.ndarray) -> None:
        if not keys:
            return
        self._append(keys, self._prep(vectors), normalize=False)

    def add_device(self, keys: list, vectors) -> None:
        """Fast path: vectors already on device (e.g. straight out of the
        embedder) — normalise and append without a host round-trip."""
        if not keys:
            return
        v = jnp.asarray(vectors)
        if v.ndim == 1:
            v = v[None, :]
        self._append(keys, v, normalize=self.metric == "cos")

    def _record_keys(self, keys: list, start: int) -> None:
        """Host-side half of an append: key -> slot bookkeeping (one home
        for both the plain and the fused ingest paths). zip/update/extend
        keep the whole batch in C — this sits on the per-batch ingest path."""
        # "append" = the host-side index bookkeeping share of the ingest
        # wall; the vector write itself rides the fused device dispatch
        with region("pw.index.append", rows=len(keys)):
            self._slot_of.update(zip(keys, range(start, start + len(keys))))
            self._keys.extend(keys)
            self.n += len(keys)

    def add_embed(self, keys: list, params, input_ids, attention_mask,
                  cfg, embed, pad_id: int = 0, query_rows: int = 0,
                  k: int = 0):
        """Fastest ingest path: embed the tokenized batch AND append the
        vectors in one fused dispatch (see ``_embed_append_kernel``).
        ``embed(params, ids, mask, cfg)`` must return unit-normalized
        (rows, d) float32 — e.g. ``models.embedder.embed_fn``. Returns the
        embeddings (device array) for downstream queries.

        ``attention_mask=None`` derives the mask on device from
        ``input_ids != pad_id`` — pass int16 ids and no mask to cut the
        per-batch host->device bytes 4x.

        ``query_rows=q, k=n`` additionally searches the first ``q`` fresh
        embeddings against the just-updated corpus INSIDE the same
        dispatch and returns ``(emb, scores, idx)`` instead of ``emb`` —
        the streaming ingest-with-live-queries shape with zero extra
        dispatches (a separate ``search_device`` costs 2 more).

        The write covers ALL ``input_ids.shape[0]`` token rows (pad rows
        land beyond the cursor, valid=False, and are overwritten by the
        next append), so capacity must fit ``n + rows``. Size
        ``reserved_space`` with one token-bucket of headroom: growing here
        for transient pad rows recompiles every capacity-shaped kernel
        mid-stream — hence the warning."""
        m = len(keys)
        if m == 0:
            # keep the arity of the documented return shape so callers can
            # unpack unconditionally
            return (None, None, None) if query_rows else None
        rows = input_ids.shape[0]
        if rows < m:
            raise ValueError(f"{m} keys but only {rows} token rows")
        if query_rows:
            # degenerate top-k (k=0) and out-of-range query slices would
            # silently produce empty/garbage results from the fused kernel
            if k < 1:
                raise ValueError(
                    f"query_rows={query_rows} requires k >= 1 (got {k})"
                )
            if not 0 <= query_rows <= rows:
                raise ValueError(
                    f"query_rows={query_rows} must be within the {rows} "
                    f"token rows"
                )
        if self.n + rows > self.capacity:
            import warnings

            warnings.warn(
                f"add_embed growing capacity ({self.capacity} -> fit "
                f"{self.n + rows}) for a padded batch; every "
                f"capacity-shaped kernel recompiles. Size reserved_space "
                f"with one token-bucket of headroom to avoid this.",
                stacklevel=2,
            )
            self._grow(self.n + rows)
        start = self.n
        if query_rows:
            (self._corpus, self._valid, self._n_dev, emb, scores,
             idx) = _embed_append_query_kernel(
                self._corpus, self._valid, self._n_dev,
                params, input_ids, attention_mask, _m_scalar(m),
                embed=embed, cfg=cfg, pad_id=pad_id,
                query_rows=query_rows, k=min(k, self.capacity),
                metric=self.metric, f32_scores=self.f32_scores,
            )
            record_device_dispatch("knn_embed_append_query")
            self._record_keys(keys, start)
            return emb, scores, idx
        self._corpus, self._valid, self._n_dev, emb = _embed_append_kernel(
            self._corpus, self._valid, self._n_dev,
            params, input_ids, attention_mask, _m_scalar(m),
            embed=embed, cfg=cfg, pad_id=pad_id,
        )
        record_device_dispatch("knn_embed_append")
        self._record_keys(keys, start)
        return emb

    def remove(self, keys: list) -> None:
        for key in keys:
            slot = self._slot_of.pop(key, None)
            if slot is None:
                continue
            last = self.n - 1
            if slot != last:
                last_key = self._keys[last]
                row = jax.lax.dynamic_slice(self._corpus, (last, 0), (1, self.dim))
                self._corpus = jax.lax.dynamic_update_slice(self._corpus, row, (slot, 0))
                self._keys[slot] = last_key
                self._slot_of[last_key] = slot
            self._valid = self._valid.at[last].set(False)
            self._keys.pop()
            self.n -= 1
            self._n_dev = self._n_dev - 1  # keep the device cursor in step

    # ------------------------------------------------------------------ search
    def search_device(self, queries, k: int):
        """Dispatch-only search: queries may live on device (straight out of
        the embedder); returns device ``(scores (Qb,k), idx (Qb,k))`` with the
        query axis padded to its pow2 bucket. No host synchronisation — a
        streaming pipeline can dispatch many searches and drain results with
        one ``jax.device_get`` (device→host fetches dominate end-to-end
        latency when the host is remote from the chip)."""
        # pad the query axis to its pow2 bucket BEFORE the jit boundary:
        # host arrays pad for free in numpy; device arrays pay one tiny
        # cached pad op — either way the big gemm+top_k executable is
        # shared per bucket instead of per raw query count
        is_device = isinstance(queries, jax.Array)
        q = queries if is_device else np.asarray(queries, dtype=np.float32)
        if q.ndim == 1:
            q = q[None, :]
        nq = q.shape[0]
        bucket = next_pow2(nq, _SEARCH_BUCKETS[0])
        if bucket > nq:
            pad_spec = ((0, bucket - nq), (0, 0))
            q = jnp.pad(q, pad_spec) if is_device else np.pad(q, pad_spec)
        if not is_device:
            q = jnp.asarray(q)
        with region("pw.index.search", queries=nq, bucket=bucket):
            scores, idx = self._dispatch(q, k)
        record_knn_search(nq, bucket)
        return scores, idx

    def _dispatch(self, q, k: int):
        return _search_kernel(self._corpus, self._valid, q,
                              min(k, self.capacity), self.metric,
                              normalize=self.metric == "cos",
                              f32_scores=self.f32_scores)

    def _compile_buckets(self, k: int) -> None:
        """With the first ``search`` at ``k``, one dispatch of every bucket:
        a served index meets its largest bucket when requests pile up behind
        a busy engine, the one moment a compile of seconds must not come
        (6.44 GB of rows: 5-9 s an executable). Costs a batch job one
        executable it may not use."""
        if (k, self.capacity) in self._compiled:
            return
        self._compiled.add((k, self.capacity))
        for bucket in _SEARCH_BUCKETS:
            self._dispatch(
                jnp.asarray(np.zeros((bucket, self.dim), np.float32)), k)

    def resolve(self, scores, idx, nq: int, k: int) -> list[list[tuple[Any, float]]]:
        """Map fetched (host) score/index arrays back to [(key, score)] rows."""
        scores = np.asarray(scores)[:nq]
        idx = np.asarray(idx)[:nq]
        out = []
        for qi in range(nq):
            row = []
            for j in range(scores.shape[1]):
                s = float(scores[qi, j])
                if s <= _NEG_INF / 2:
                    break
                slot = int(idx[qi, j])
                if slot < len(self._keys):
                    row.append((self._keys[slot], s))
            out.append(row)
        return out

    def search(self, queries: np.ndarray, k: int) -> list[list[tuple[Any, float]]]:
        """Return per-query [(key, score)] sorted by decreasing score."""
        if not isinstance(queries, (np.ndarray, jax.Array)):
            queries = np.asarray(queries)
        if queries.ndim == 1:
            queries = queries[None, :]
        nq = queries.shape[0]
        if self.n == 0:
            return [[] for _ in range(nq)]
        self._compile_buckets(k)
        # a guard, not a batching policy: an epoch's queries are one
        # dispatch up to the largest bucket, and as many as it takes above
        chunks = [queries[s:s + _MAX_SEARCH_BUCKET]
                  for s in range(0, nq, _MAX_SEARCH_BUCKET)]
        handles = [self.search_device(chunk, k) for chunk in chunks]
        with region("pw.index.fetch", queries=nq):
            # one round trip for every result array
            fetched = jax.device_get(handles)
            record_device_dispatch("knn_drain")
            return [
                row
                for chunk, (scores, idx) in zip(chunks, fetched)
                for row in self.resolve(scores, idx, len(chunk), k)
            ]

    def __len__(self) -> int:
        return self.n
