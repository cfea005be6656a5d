"""Device-dispatch counters and the cascade ledger (``engine/probes.py``)
and the ragged-tail blocked top-k (``ops/knn.py``)."""

import numpy as np
import pytest

from pathway_tpu.engine import probes

# ----------------------------------------------------- dispatch counters


def test_dispatch_counters_global_and_per_op():
    probes.reset_dispatch_counts()
    probes.record_device_dispatch("embed_dispatch")
    probes.record_device_dispatch("embed_dispatch", 2)
    probes.record_device_dispatch("knn_search")
    counts = probes.dispatch_counts()
    assert counts["embed_dispatch"] == 3
    assert counts["knn_search"] == 1

    # per-operator attribution rides a thread-local set by the scheduler
    op = probes.OperatorStats(name="embed")
    probes._current_op.stats = op
    try:
        probes.record_device_dispatch("embed_dispatch")
    finally:
        probes._current_op.stats = None
    assert op.dispatches == 1
    assert probes.dispatch_counts()["embed_dispatch"] == 4
    probes.reset_dispatch_counts()
    assert probes.dispatch_counts() == {}


def test_cascade_ledger_survivor_rate():
    probes.reset_cascade_stats()
    assert probes.cascade_stats()["survivor_rate"] == 1.0  # no cascade ran
    probes.record_cascade("cheap", 32, flops=2e9)
    probes.record_cascade("full", 8, flops=3e9)
    probes.record_cascade("cheap", 32, flops=2e9)
    probes.record_cascade("full", 8, flops=3e9)
    s = probes.cascade_stats()
    assert s["pairs"] == {"cheap": 64, "full": 16}
    assert s["survivor_rate"] == pytest.approx(0.25)
    assert s["gflops"]["cheap"] == pytest.approx(4.0)
    probes.reset_cascade_stats()
    assert probes.cascade_stats()["pairs"] == {}


def test_fused_rerank_one_dispatch_per_cascade_tick():
    """The fused retrieve-rerank path must stay ONE device dispatch per
    call/tick — the cascade's cheap and full stages share that single
    executable (survivor selection never returns to the host), so the
    per-operator dispatch counters may move by exactly one kind, once,
    per tick. Guards against silent dispatch regressions in the fused
    path."""
    import os

    from pathway_tpu.models.cross_encoder import CrossEncoderModel
    from pathway_tpu.models.embedder import SentenceEmbedderModel
    from pathway_tpu.models.transformer import TransformerConfig
    from pathway_tpu.ops.fused_query import FusedRAGPipeline
    from pathway_tpu.ops.query_server import QueryServer

    cfg = TransformerConfig(
        vocab_size=2048, hidden=32, layers=2, heads=2, intermediate=64
    )
    emb = SentenceEmbedderModel(cfg=cfg, max_length=16)
    rr = CrossEncoderModel(cfg=cfg, tokenizer=emb.tokenizer, max_length=64)
    pipe = FusedRAGPipeline(emb, rr, reserved_space=32, doc_seq=16,
                            pair_seq=48)
    pipe.add([f"k{i}" for i in range(24)],
             [f"doc {i} alpha beta gamma" for i in range(24)])
    saved = {
        v: os.environ.get(v)
        for v in ("PATHWAY_TPU_RERANK_CASCADE",
                  "PATHWAY_TPU_RERANK_CASCADE_DEPTH",
                  "PATHWAY_TPU_RERANK_CASCADE_SURVIVORS")
    }
    try:
        os.environ["PATHWAY_TPU_RERANK_CASCADE"] = "1"
        os.environ["PATHWAY_TPU_RERANK_CASCADE_DEPTH"] = "1"
        os.environ["PATHWAY_TPU_RERANK_CASCADE_SURVIVORS"] = "4"
        pipe.retrieve_rerank("alpha beta", k=8)  # compile outside the count
        probes.reset_dispatch_counts()
        for i in range(3):
            pipe.retrieve_rerank(f"alpha {i}", k=8)
        counts = probes.dispatch_counts()
        assert counts == {"fused_rerank_cascade": 3}

        # a micro-batching tick dispatches once for the whole batch too
        probes.reset_dispatch_counts()
        with QueryServer(pipe, tick_ms=30.0, max_batch=8) as srv:
            from concurrent.futures import ThreadPoolExecutor

            with ThreadPoolExecutor(4) as ex:
                list(ex.map(
                    lambda t: srv.query(t, 8, rerank=True),
                    [f"beta {i}" for i in range(4)],
                ))
            stats = srv.stats()
        counts = probes.dispatch_counts()
        assert counts == {"fused_rerank_cascade": stats["dispatches"]}
        assert stats["dispatches"] < stats["requests"]

        # kill switch: still exactly one dispatch, on the full-depth kind
        os.environ["PATHWAY_TPU_RERANK_CASCADE"] = "0"
        pipe.retrieve_rerank("alpha beta", k=8)  # compile outside the count
        probes.reset_dispatch_counts()
        pipe.retrieve_rerank("gamma", k=8)
        assert probes.dispatch_counts() == {"fused_retrieve_rerank": 1}
    finally:
        for var, val in saved.items():
            if val is None:
                os.environ.pop(var, None)
            else:
                os.environ[var] = val


# ------------------------------------------------- blocked top-k ragged


def test_blocked_topk_ragged_tail_matches_flat():
    """N not a multiple of the block AND N > 2*block: the tail must be
    padded with -inf INSIDE the blocked path (no full-row top_k fallback)
    and stay exact vs the flat reference."""
    import jax
    import jax.numpy as jnp

    from pathway_tpu.ops import knn as knn_mod

    rng = np.random.default_rng(3)
    old = knn_mod._TOPK_BLOCK
    knn_mod._TOPK_BLOCK = 64
    try:
        for n in (300, 64 * 5 + 1, 64 * 4 - 1):
            scores = jnp.asarray(
                rng.standard_normal((5, n)).astype(np.float32)
            )
            fs, fi = jax.device_get(knn_mod.topk_scores(scores, 10))
            es, ei = jax.device_get(jax.lax.top_k(scores, 10))
            assert np.allclose(fs, es), f"scores diverged at N={n}"
            s_np = np.asarray(scores)
            for q in range(5):
                assert np.allclose(s_np[q][fi[q]], es[q]), f"idx at N={n}"
            # no pad index may leak out: all indices inside the real corpus
            assert int(fi.max()) < n
    finally:
        knn_mod._TOPK_BLOCK = old
