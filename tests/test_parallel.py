"""Tests for the parallel layer: mesh construction, corpus-sharded KNN with
ICI-style top-k merge, dp+tp-sharded training step. All on the virtual
8-device CPU mesh (conftest)."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from pathway_tpu.models import (
    MINILM_L6,
    HashTokenizer,
    init_train_state,
    make_train_step,
    param_partition_specs,
)
from pathway_tpu.models.train import TrainState
from pathway_tpu.parallel import ShardedKnnIndex, make_mesh, sharded_topk_merge

TINY = dataclasses.replace(
    MINILM_L6, layers=2, hidden=32, heads=4, intermediate=64,
    vocab_size=500, max_position=64,
)


def test_make_mesh_shapes():
    mesh = make_mesh()
    assert mesh.devices.size == len(jax.devices())
    mesh2 = make_mesh(dp=4, tp=2)
    assert mesh2.shape["dp"] == 4 and mesh2.shape["tp"] == 2
    with pytest.raises(ValueError):
        make_mesh(dp=3, tp=2)


def test_sharded_knn_exact_vs_numpy():
    mesh = make_mesh(tp=1)
    dim, n = 16, 256
    idx = ShardedKnnIndex(mesh, dimensions=dim, reserved_space=n)
    rng = np.random.default_rng(0)
    vecs = rng.normal(size=(n, dim))
    for i in range(n):
        idx.add(f"k{i}", vecs[i])
    q = rng.normal(size=(3, dim))
    res = idx.search(q, k=5)
    # numpy reference: cosine
    vn = vecs / np.linalg.norm(vecs, axis=1, keepdims=True)
    qn = q / np.linalg.norm(q, axis=1, keepdims=True)
    scores = qn @ vn.T
    for r in range(3):
        expect = set(np.argsort(-scores[r])[:5])
        got = {int(key[1:]) for key, _ in res[r]}
        assert got == expect


def test_sharded_knn_delete_and_grow():
    mesh = make_mesh(tp=1)
    idx = ShardedKnnIndex(mesh, dimensions=8, reserved_space=64)
    rng = np.random.default_rng(1)
    vecs = {f"k{i}": rng.normal(size=8) for i in range(100)}
    for k_, v in vecs.items():
        idx.add(k_, v)
    res = idx.search(np.stack([vecs["k7"]]), k=1)
    assert res[0][0][0] == "k7"
    idx.remove("k7")
    res = idx.search(np.stack([vecs["k7"]]), k=1)
    assert res[0][0][0] != "k7"
    # growth keeps old entries findable
    for i in range(100, 1200):
        idx.add(f"k{i}", rng.normal(size=8))
    res = idx.search(np.stack([vecs["k42"]]), k=1)
    assert res[0][0][0] == "k42"


def test_sharded_topk_merge_functional():
    mesh = make_mesh(tp=1)
    dp = mesh.shape["dp"]
    rows = 8 * dp
    corpus = jnp.asarray(
        np.random.default_rng(2).normal(size=(rows, 4)), jnp.bfloat16
    )
    valid = jnp.ones((rows,), bool)
    queries = jnp.asarray(np.asarray(corpus[5:6], np.float32))
    sc, ix = sharded_topk_merge(mesh, corpus, valid, queries, k=3,
                                metric="cos")
    assert sc.shape == (1, 3) and ix.shape == (1, 3)


def test_dp_tp_sharded_train_step():
    mesh = make_mesh(dp=4, tp=2)
    state, tx = init_train_state(jax.random.PRNGKey(0), TINY,
                                 learning_rate=1e-3)
    step = make_train_step(TINY, tx)
    specs = param_partition_specs(TINY)
    shd = jax.tree.map(lambda s: NamedSharding(mesh, s), specs)
    params = jax.device_put(state.params, shd)
    opt_state = jax.jit(tx.init)(params)  # moments inherit param sharding
    state = TrainState(params, opt_state, state.step)
    tok = HashTokenizer(vocab_size=TINY.vocab_size, max_length=8)
    texts = [f"text {i}" for i in range(8)]
    qi, qm = tok(texts, pad_to=8)
    di, dm = tok([t + " doc" for t in texts], pad_to=8)
    bshd = NamedSharding(mesh, P("dp", None))
    batch = {k: jax.device_put(jnp.asarray(v), bshd)
             for k, v in dict(q_ids=qi, q_mask=qm,
                              d_ids=di, d_mask=dm).items()}
    jstep = jax.jit(step)
    with mesh:
        state, l1 = jstep(state, batch)
        state, l2 = jstep(state, batch)
    assert float(l2) < float(l1)


def test_graft_entry_contracts():
    import sys
    sys.path.insert(0, "/root/repo")
    import __graft_entry__ as g

    fn, args = g.entry()
    out = jax.jit(fn)(*args)
    assert out.shape == (8, MINILM_L6.hidden)
    g.dryrun_multichip(len(jax.devices()))


def test_ring_attention_matches_dense():
    """Sequence-parallel ring attention over 8 shards must reproduce the
    single-device dense encoder (f32, unmasked positions) exactly."""
    from jax.sharding import Mesh
    from pathway_tpu.models.transformer import (
        TransformerConfig, init_params, encode,
    )
    from pathway_tpu.parallel import encode_sequence_parallel

    cfg = TransformerConfig(vocab_size=100, hidden=64, layers=2, heads=4,
                            intermediate=128, max_position=64,
                            dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    B, S = 2, 32
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(1, 100, size=(B, S)), jnp.int32)
    mask = jnp.ones((B, S), jnp.int32).at[0, 28:].set(0)

    ref = encode(params, ids, mask, cfg)
    mesh = Mesh(np.array(jax.devices()[:8]), ("sp",))
    out = encode_sequence_parallel(params, ids, mask, cfg, mesh, "sp")
    d = np.abs(np.asarray(ref) - np.asarray(out))
    m = np.broadcast_to(np.asarray(mask)[:, :, None].astype(bool), d.shape)
    assert d[m].max() < 1e-4


def test_ring_attention_core_vs_softmax():
    """The ring core alone (no transformer) vs plain softmax attention,
    including a fully-padded tail shard."""
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec
    from pathway_tpu.parallel import ring_attention_core

    B, nh, S, hd = 2, 2, 64, 8
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(B, nh, S, hd)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, nh, S, hd)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, nh, S, hd)).astype(np.float32))
    mask = np.ones((B, S), np.int32)
    mask[0, 40:] = 0  # last 24 kv positions masked -> final shard all-pad
    maskj = jnp.asarray(mask)

    scores = jnp.einsum("bnqd,bnkd->bnqk", q, k) / np.sqrt(hd)
    scores = scores + jnp.where(maskj[:, None, None, :] > 0, 0.0, -1e9)
    ref = jnp.einsum("bnqk,bnkd->bnqd", jax.nn.softmax(scores, -1), v)

    mesh = Mesh(np.array(jax.devices()[:8]), ("sp",))
    out = jax.shard_map(
        lambda q_, k_, v_, m_: ring_attention_core(q_, k_, v_, m_, "sp", 8),
        mesh=mesh,
        in_specs=(PartitionSpec(None, None, "sp", None),) * 3
        + (PartitionSpec(None, "sp"),),
        out_specs=PartitionSpec(None, None, "sp", None),
        check_vma=False,
    )(q, k, v, maskj)
    # compare only queries that attend to something real (all of them here)
    assert np.abs(np.asarray(out) - np.asarray(ref)).max() < 1e-5


# ``add``: row by row (argmin shard, one cell at a time); ``add_bulk``: the
# build for millions of rows (water-filled shards, one centroid gemm a chunk,
# build-time k-means), which the four-chip bring-up loads its shards with
@pytest.mark.parametrize("build", ["add", "add_bulk"])
def test_sharded_ivf_full_probe_is_exact(build):
    # nprobe == n_cells scans every cell: results must match numpy exact
    from pathway_tpu.parallel import ShardedIvfIndex

    mesh = make_mesh(tp=1)
    dim, n = 16, 256
    idx = ShardedIvfIndex(mesh, dimensions=dim, n_cells=4, nprobe=4,
                          cell_capacity=32)
    rng = np.random.default_rng(1)
    vecs = rng.normal(size=(n, dim))
    getattr(idx, build)([f"k{i}" for i in range(n)], vecs)
    assert len(idx) == n
    q = rng.normal(size=(3, dim))
    res = idx.search(q, k=5)
    vn = vecs / np.linalg.norm(vecs, axis=1, keepdims=True)
    qn = q / np.linalg.norm(q, axis=1, keepdims=True)
    scores = qn @ vn.T
    for r in range(3):
        expect = set(np.argsort(-scores[r])[:5])
        got = {int(key[1:]) for key, _ in res[r]}
        assert got == expect


@pytest.mark.parametrize("build", ["add", "add_bulk"])
def test_sharded_ivf_pruned_recall_reasonable(build):
    # nprobe < n_cells prunes; trained clustering must keep recall@10 high
    from pathway_tpu.parallel import ShardedIvfIndex

    mesh = make_mesh(tp=1)
    dim, n = 16, 2048
    rng = np.random.default_rng(2)
    # clustered corpus (IVF's intended shape)
    centers = rng.normal(size=(32, dim)) * 4
    vecs = centers[rng.integers(0, 32, n)] + rng.normal(size=(n, dim))
    idx = ShardedIvfIndex(mesh, dimensions=dim, n_cells=8, nprobe=4,
                          cell_capacity=64, train_after=32)
    getattr(idx, build)([f"k{i}" for i in range(n)], vecs)
    assert idx._trained
    nq = 16
    q = centers[rng.integers(0, 32, nq)] + rng.normal(size=(nq, dim))
    res = idx.search(q, k=10)
    vn = vecs / np.linalg.norm(vecs, axis=1, keepdims=True)
    qn = q / np.linalg.norm(q, axis=1, keepdims=True)
    scores = qn @ vn.T
    hits = 0
    for r in range(nq):
        expect = set(np.argsort(-scores[r])[:10].tolist())
        got = {int(key[1:]) for key, _ in res[r]}
        hits += len(expect & got)
    recall = hits / (nq * 10)
    assert recall >= 0.8, recall


def test_sharded_ivf_remove_and_upsert():
    from pathway_tpu.parallel import ShardedIvfIndex

    mesh = make_mesh(tp=1)
    dim = 8
    idx = ShardedIvfIndex(mesh, dimensions=dim, n_cells=2, nprobe=2,
                          cell_capacity=16)
    rng = np.random.default_rng(3)
    vecs = rng.normal(size=(32, dim))
    idx.add([f"k{i}" for i in range(32)], vecs)
    idx.remove(["k0", "k1"])
    assert len(idx) == 30
    res = idx.search(vecs[0][None, :], k=5)
    assert all(key not in ("k0", "k1") for key, _ in res[0])
    # upsert moves the key
    idx.add(["k2"], -vecs[2][None, :])
    res2 = idx.search(-vecs[2][None, :], k=1)
    assert res2[0][0][0] == "k2"
