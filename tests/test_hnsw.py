"""Host-side HNSW (``ops/hnsw.py``) — the uSearch-parity graph index.

Reference parity: ``src/external_integration/usearch_integration.rs``
(connectivity / expansion knobs, mask-style deletion). Scale-recall is
covered here at test size; the TPU-native ANN index (IVF) has its own
recall tests (``tests/test_indexing.py``).
"""

import numpy as np

from pathway_tpu.ops.hnsw import HnswIndex


def _clustered(n, d, rng, n_centers=32):
    centers = rng.standard_normal((n_centers, d)).astype(np.float32) * 3
    x = centers[rng.integers(0, n_centers, n)] + rng.standard_normal(
        (n, d)
    ).astype(np.float32)
    return (x / np.linalg.norm(x, axis=1, keepdims=True)).astype(np.float32)


def test_hnsw_recall_cos():
    rng = np.random.default_rng(0)
    n, d, nq, k = 3000, 32, 50, 10
    corpus = _clustered(n, d, rng)
    queries = _clustered(nq, d, rng)
    idx = HnswIndex(d, metric="cos")
    for s in range(0, n, 500):
        idx.add(list(range(s, s + 500)), corpus[s:s + 500])
    truth = np.argsort(-(queries @ corpus.T), axis=1)[:, :k]
    res = idx.search(queries, k)
    recall = np.mean([
        len({key for key, _ in row} & set(truth[i].tolist())) / k
        for i, row in enumerate(res)
    ])
    assert recall >= 0.9, recall
    # scores are bigger-is-better and sorted
    for row in res[:5]:
        scores = [s for _, s in row]
        assert scores == sorted(scores, reverse=True)


def test_hnsw_delete_and_upsert():
    rng = np.random.default_rng(1)
    n, d, k = 1000, 16, 5
    corpus = _clustered(n, d, rng)
    idx = HnswIndex(d, metric="cos")
    idx.add(list(range(n)), corpus)
    dels = list(range(0, n, 3))
    idx.remove(dels)
    assert len(idx) == n - len(dels)
    res = idx.search(corpus[:40], k)
    dset = set(dels)
    for row in res:
        assert all(key not in dset for key, _ in row)
    # upsert: re-adding a live key replaces its vector
    target = corpus[500]
    idx.add([1], target[None, :])
    top = idx.search(target[None, :], 3)[0]
    assert {key for key, _ in top} >= {1}


def test_hnsw_l2sq_and_empty():
    rng = np.random.default_rng(2)
    d = 8
    idx = HnswIndex(d, metric="l2sq")
    assert idx.search(rng.standard_normal((2, d)).astype(np.float32), 3) == [
        [], []
    ]
    pts = rng.standard_normal((200, d)).astype(np.float32)
    idx.add(list(range(200)), pts)
    res = idx.search(pts[:10], 1)
    # nearest neighbor of a stored point is itself under l2
    assert [row[0][0] for row in res] == list(range(10))


def test_usearch_knn_uses_hnsw_end_to_end():
    """DataIndex + USearchKnn drives the graph index through the engine
    (build -> query_as_of_now -> ranked replies)."""
    import pandas as pd

    import pathway_tpu as pw
    from pathway_tpu.stdlib.indexing import DataIndex, USearchKnn

    rng = np.random.default_rng(3)
    vecs = _clustered(64, 12, rng)
    qv = vecs[7] + 0.01 * rng.standard_normal(12).astype(np.float32)

    pw.clear_graph()
    docs = pw.debug.table_from_pandas(
        pd.DataFrame({"doc_id": range(64), "vec": [v.tolist() for v in vecs]})
    )
    index = DataIndex(
        docs,
        USearchKnn(
            docs.vec, dimensions=12, connectivity=8,
            expansion_add=64, expansion_search=32,
        ),
    )
    queries = pw.debug.table_from_pandas(
        pd.DataFrame({"qvec": [qv.tolist()]})
    )
    res = index.query_as_of_now(queries.qvec, number_of_matches=3)
    _, cols = pw.debug.table_to_dicts(res)
    (ids,) = cols["doc_id"].values()
    assert 7 in ids, ids
